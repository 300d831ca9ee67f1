"""aphromesh: static placement ledger / collective-cost pass tests.

Four layers:

1. Rule precision on the seeded fixtures: each MESH fixture trips
   exactly its one rule and nothing else, and the clean-construct
   fixture (the column/row `shard_along` seam, all three tp-gate
   forms, classified commits with explicit shardings) produces ZERO
   findings.
2. The MESHPLAN.json ledger drift gate: the checked-in baseline must
   byte-match `--meshplan --json` (line numbers excluded by schema so
   pure code motion cannot drift it), the ledger must cover every
   jitted step program with the verified collective attribution
   (all-reduce 2/layer + 1 fixed for the Llama chain — the count the
   compiled tp=8 HLO assertion in tests/engine/test_tp_parity.py
   pins), and the placement-domain map must name the disagg
   `kv_partition_spec` handoff set.
3. MESH005 reproduces drift on a seeded tree: a stale baseline fires
   the generic out-of-sync finding, a baseline with a LOWER program
   all-reduce count fires the count-grew finding, an in-sync (or
   absent) baseline stays silent, and subset scans skip the gate.
4. The placement boundary holds on the real tree: zero MESH findings
   without any allowlist entry — the eleven live ungated-launcher
   findings were FIXED (context_tp()/InputMetadata.tp gates), not
   suppressed.

Pure AST — no JAX device work; runs under JAX_PLATFORMS=cpu in tier-1
and in CI.
"""
import copy
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.aphrocheck import build_context, run
from tools.aphrocheck.core import REPO_ROOT
from tools.aphrocheck.passes import mesh_pass

FIXDIR = os.path.join("tests", "analysis", "fixtures")


def _fixture(name: str) -> str:
    return os.path.join(FIXDIR, name)


def _findings(rels, root=REPO_ROOT):
    ctx, parse_findings = build_context(root, rels)
    assert not parse_findings, parse_findings
    return mesh_pass.run(ctx)


def _baseline():
    with open(os.path.join(REPO_ROOT, mesh_pass.BASELINE_FILE),
              encoding="utf-8") as f:
        return json.load(f)


# ------------------------------------------------------------------
# 1. fixture precision
# ------------------------------------------------------------------

@pytest.mark.parametrize("fixture,rule", [
    ("fixture_mesh_unsharded_put.py", "MESH001"),
    ("fixture_mesh_collective.py", "MESH002"),
    ("fixture_mesh_ungated_launcher.py", "MESH003"),
    ("fixture_mesh_domain.py", "MESH004"),
])
def test_rule_fires_exactly_once_and_alone(fixture, rule):
    """Each seeded fixture trips exactly its one rule (recall AND
    precision — the family's other rules stay quiet on it, including
    MESH005, which subset scans with no jitted program skip)."""
    findings = _findings([_fixture(fixture)])
    assert [f.rule for f in findings] == [rule], \
        f"{fixture}: {[f.render() for f in findings]}"


def test_clean_constructs_stay_quiet():
    """The real tree's idioms — the declared column/row seam, the
    direct `metadata.tp == 1` gate, the gate-variable form, the
    one-hop `_use_pallas` predicate (context_tp), explicit-sharded
    commits from classified functions — produce ZERO findings."""
    findings = _findings([_fixture("fixture_mesh_clean.py")])
    assert not findings, [f.render() for f in findings]


def test_subset_scan_covers_mesh_through_run():
    """The full run() pipeline reaches the MESH family on explicit
    paths, and the subset scan does NOT fire the drift gate (MESH005
    needs the full tree)."""
    report = run(rels=[_fixture("fixture_mesh_unsharded_put.py")],
                 allowlist_path=None, rule_prefixes=["MESH"])
    assert [f.rule for f in report.findings] == ["MESH001"], \
        [f.render() for f in report.findings]


# ------------------------------------------------------------------
# 2. the checked-in ledger
# ------------------------------------------------------------------

def test_checked_in_ledger_in_sync():
    """MESHPLAN.json must match what the tree generates — regenerate
    with `python -m tools.aphrocheck --meshplan --json >
    MESHPLAN.json` when the placement structure changes."""
    ctx, parse_findings = build_context()
    assert not parse_findings, parse_findings
    assert mesh_pass.report_payload(ctx) == _baseline(), \
        "MESHPLAN.json out of date: regenerate with " \
        "`python -m tools.aphrocheck --meshplan --json > MESHPLAN.json`"


def test_ledger_covers_step_programs_with_verified_counts():
    """Every jitted step program is ledgered with the attribution the
    compiled tp=8 HLO verifies (tests/engine/test_tp_parity.py):
    all-reduce 2/layer (o_proj + down_proj) + 1 fixed (embed
    combine), all-gather deferred to the consumer (seam count, not a
    step collective). Line numbers are excluded by schema so pure
    code motion cannot drift the baseline."""
    baseline = _baseline()
    programs = baseline["programs"]
    runner = "aphrodite_tpu/executor/model_runner.py::ModelRunner"
    for name in ("_step", "_step_sample", "_burst_scan"):
        rec = programs[f"{runner}.{name}"]
        assert rec["model_forward"] and rec["logits_head"]
        assert rec["all_reduce"] == {"per_layer": 2, "fixed": 1}
        assert rec["all_gather_consumer_seam"] == 1
    assert programs[f"{runner}._copy_blocks"]["all_reduce"] == \
        {"per_layer": 0, "fixed": 0}
    assert programs[f"{runner}._burst_scan"]["multi_step_scan"]

    llama = baseline["models"]["LlamaForCausalLM"]
    assert llama["all_reduce"] == {"per_layer": 2, "fixed": 1}
    assert llama["all_gather"] == {"per_layer": 0, "fixed": 1}
    # Mixtral's MoE combine is GSPMD-inferred from the expert-parallel
    # weight specs, not an annotation seam — one declared AR per layer
    # (the attention o_proj), and that asymmetry must stay visible.
    assert baseline["models"]["MixtralForCausalLM"]["all_reduce"] == \
        {"per_layer": 1, "fixed": 1}

    geo = baseline["geometry_7b"]
    assert geo["all_reduce_count_per_step"] == \
        llama["all_reduce"]["per_layer"] * geo["n_layers"] + \
        llama["all_reduce"]["fixed"] == 65
    assert geo["tp"] == 8 and geo["ici_gbps"] == 180.0

    blob = json.dumps(baseline)
    assert '"line"' not in blob and '"lineno"' not in blob, \
        "ledger schema must not carry line numbers"


def test_ledger_domain_map_and_kv_handoff():
    """The placement-domain map classifies every executor commit site
    and names the disagg handoff set: the KV planes (the ONLY
    shared_kv commits) hand off under kv_partition_spec; prompt-side
    staging is prefill, burst/spec dispatch is decode."""
    baseline = _baseline()
    domains = baseline["domains"]
    runner = "aphrodite_tpu/executor/model_runner.py::ModelRunner"
    assert domains[f"{runner}._prepare_prompt"] == "prefill"
    # a decode and a speculative-verify batch are sent by one function
    assert domains[f"{runner}._send_decode_batch"] == "decode"
    # what a burst's scan takes beside a decode step's operands
    assert domains[f"{runner}._burst_operands"] == "decode"
    assert domains[f"{runner}._apply_block_copies"] == "maintenance"
    assert domains[f"{runner}._params_with_lora"] == "shared"
    handoff = baseline["kv_handoff"]
    assert handoff["partition_spec"] == "kv_partition_spec"
    cache = "aphrodite_tpu/executor/cache_engine.py::CacheEngine"
    assert handoff["commit_sites"] == [
        f"{cache}._allocate_device",
        f"{cache}._allocate_prefill_pool",
        f"{cache}.kv_handoff"]
    assert handoff["commit_sites"] == \
        [q for q, d in domains.items() if d == "shared_kv"]


def test_ledger_sharding_plan_resolves_linear_mro():
    """The sharding plan resolves class attributes through the mixin
    diamond (MergedColumnParallelLinear inherits out_axis="tp" from
    ColumnParallelLinear, not the mixin's LinearBase) and tags the
    collective-bearing classes."""
    plan = _baseline()["sharding_plan"]
    for name in ("ColumnParallelLinear", "MergedColumnParallelLinear",
                 "QKVParallelLinear"):
        assert plan[name]["out_axis"] == "tp", (name, plan[name])
    row = plan["RowParallelLinear"]
    assert row["in_axis"] == "tp" and row["collective"] == "all_reduce"
    assert plan["VocabParallelEmbedding"]["collective"] == "all_reduce"
    assert plan["ParallelLMHead"]["collective"] == "all_gather"


def test_cli_meshplan_human_and_json():
    """`--meshplan` renders the ledger for humans; `--meshplan
    --json` must byte-match the checked-in baseline (the CI drift
    gate diffs exactly this output)."""
    human = subprocess.run(
        [sys.executable, "-m", "tools.aphrocheck", "--meshplan"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert human.returncode == 0, human.stderr
    assert "MESH placement ledger" in human.stdout
    assert "65 all-reduces/step" in human.stdout
    assert "consumer seam" in human.stdout

    js = subprocess.run(
        [sys.executable, "-m", "tools.aphrocheck", "--meshplan",
         "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert js.returncode == 0, js.stderr
    assert json.loads(js.stdout) == _baseline()


# ------------------------------------------------------------------
# 3. MESH005 drift on a seeded tree
# ------------------------------------------------------------------

_SEEDED_TREE = textwrap.dedent('''\
    import jax


    class RowParallelLinear:

        out_activation = None


    class DecoderLayer:

        def __init__(self):
            self.o_proj = RowParallelLinear()
            self.down_proj = RowParallelLinear()


    class LlamaForCausalLM:

        def __init__(self, n_layers):
            self.layers = [DecoderLayer() for _ in range(n_layers)]


    class SeededRunner:

        def __init__(self, model):
            self.model = model
            self._step_fn = jax.jit(self._step)

        def _step(self, params, ids):
            return self.model(params, ids)
''')


def _seeded_ctx(tmp_path):
    (tmp_path / "seeded_runner.py").write_text(_SEEDED_TREE)
    ctx, parse_findings = build_context(str(tmp_path),
                                        ["seeded_runner.py"])
    assert not parse_findings, parse_findings
    return ctx


def test_mesh005_quiet_in_sync_and_without_baseline(tmp_path):
    """No baseline file (a fresh checkout mid-rebase) and an in-sync
    baseline both stay silent — the gate only speaks on drift."""
    ctx = _seeded_ctx(tmp_path)
    assert not mesh_pass.run(ctx)
    payload = mesh_pass.report_payload(ctx)
    assert payload["programs"], "seeded tree must ledger its program"
    (tmp_path / mesh_pass.BASELINE_FILE).write_text(
        json.dumps(payload, indent=2))
    assert not mesh_pass.run(ctx)


def test_mesh005_fires_on_stale_baseline(tmp_path):
    """A baseline that no longer matches the tree fires the generic
    out-of-sync finding with the regeneration command."""
    ctx = _seeded_ctx(tmp_path)
    (tmp_path / mesh_pass.BASELINE_FILE).write_text(
        json.dumps({"programs": {}}))
    findings = mesh_pass.run(ctx)
    assert [f.rule for f in findings] == ["MESH005"], \
        [f.render() for f in findings]
    assert "out of sync" in findings[0].message
    assert "--meshplan" in findings[0].message


def test_mesh005_names_the_program_whose_count_grew(tmp_path):
    """When a jitted program's static all-reduce count exceeds the
    baseline's — a new collective on the step path the ICI model has
    not priced — the finding names the program specifically."""
    ctx = _seeded_ctx(tmp_path)
    payload = mesh_pass.report_payload(ctx)
    qual = "seeded_runner.py::SeededRunner._step"
    assert payload["programs"][qual]["all_reduce"] == \
        {"per_layer": 2, "fixed": 0}
    stale = copy.deepcopy(payload)
    stale["programs"][qual]["all_reduce"]["per_layer"] = 1
    (tmp_path / mesh_pass.BASELINE_FILE).write_text(
        json.dumps(stale, indent=2))
    findings = mesh_pass.run(ctx)
    assert [f.rule for f in findings] == ["MESH005"], \
        [f.render() for f in findings]
    assert "count grew" in findings[0].message
    assert qual in findings[0].message


# ------------------------------------------------------------------
# 4. the real tree is clean, with an EMPTY allowlist
# ------------------------------------------------------------------

def test_real_tree_clean_without_allowlist():
    """Zero MESH findings on the full tree with NO allowlist: the
    live ungated-launcher findings (the quantized-matmul dispatchers
    and the KV-cache writer) were fixed with real tp gates
    (`context_tp() == 1`, `InputMetadata.tp`), not suppressed."""
    report = run(allowlist_path=None, rule_prefixes=["MESH"])
    assert not report.findings, \
        [f.render() for f in report.findings]
