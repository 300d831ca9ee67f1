"""aphroleak: KV-page ownership / leak-lifecycle pass tests.

Four layers:

1. Rule precision on the seeded fixtures: each LEAK/OWN fixture trips
   exactly its one rule and nothing else, and the clean-construct
   fixture (the CoW append_slot free-then-read-number and swap-mapping
   idioms the real block manager relies on) produces ZERO findings.
2. The OWNERSHIP.json ledger drift gate: the checked-in baseline must
   byte-match `--ledger --json` (line numbers excluded by schema so
   pure code motion cannot drift it), and the ledger must cover every
   canonical alloc site with a reachable free seam.
3. The motivating findings reproduce: the SEED tree's sliding-window
   refcount clobber and PrefixPool pin-forever (both fixed in-tree
   this PR) fire LEAK002 when their exact old shapes are scanned.
4. The ownership boundary holds on the real tree: the scheduler /
   executor / engine files are clean under OWN001/OWN002 without any
   `# owner-ok:` pragma, and the block manager carries none either —
   the live findings were FIXED (block_numbers projection), not
   pragma'd.

Pure AST — no JAX device work; runs under JAX_PLATFORMS=cpu in tier-1
and in CI.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.aphrocheck import build_context, run
from tools.aphrocheck.core import REPO_ROOT
from tools.aphrocheck.passes import leak_pass, own_pass

FIXDIR = os.path.join("tests", "analysis", "fixtures")


def _fixture(name: str) -> str:
    return os.path.join(FIXDIR, name)


def _findings(pass_mod, rels, root=REPO_ROOT):
    ctx, parse_findings = build_context(root, rels)
    assert not parse_findings, parse_findings
    return pass_mod.run(ctx)


# ------------------------------------------------------------------
# 1. fixture precision
# ------------------------------------------------------------------

@pytest.mark.parametrize("pass_mod,fixture,rule", [
    (leak_pass, "fixture_leak_escape.py", "LEAK001"),
    (leak_pass, "fixture_leak_clobber.py", "LEAK002"),
    (leak_pass, "fixture_leak_pin.py", "LEAK002"),
    (leak_pass, "fixture_leak_uaf.py", "LEAK003"),
    (leak_pass, "fixture_leak_rollback.py", "LEAK004"),
    (own_pass, "fixture_own_refcount.py", "OWN001"),
    (own_pass, "fixture_own_escape.py", "OWN002"),
])
def test_rule_fires_exactly_once_and_alone(pass_mod, fixture, rule):
    """Each seeded fixture trips exactly its one rule (recall AND
    precision — the family's other rules stay quiet on it)."""
    findings = _findings(pass_mod, [_fixture(fixture)])
    assert [f.rule for f in findings] == [rule], \
        f"{fixture}: {[f.render() for f in findings]}"


def test_cow_and_swap_idioms_stay_quiet():
    """The owner module's real shapes — CoW free-then-read-number and
    the swap mapping (alloc, map, append, free-the-other-side) —
    produce ZERO LEAK findings."""
    findings = _findings(leak_pass,
                         [_fixture("fixture_leak_cow_clean.py")])
    assert not findings, [f.render() for f in findings]


def test_owner_pragma_glossary_in_fixture():
    """The `# owner-ok:` escape hatch works: the documented variant in
    the OWN001 fixture carries the pragma and is what keeps the count
    at exactly one."""
    with open(os.path.join(REPO_ROOT,
                           _fixture("fixture_own_refcount.py")),
              encoding="utf-8") as f:
        assert "owner-ok:" in f.read()


# ------------------------------------------------------------------
# 2. the OWNERSHIP.json ledger drift gate
# ------------------------------------------------------------------

def test_checked_in_ledger_in_sync():
    """The drift gate of record: OWNERSHIP.json must equal the current
    full-tree ledger exactly — regenerate with
    `python -m tools.aphrocheck --ledger --json > OWNERSHIP.json`."""
    ctx, _ = build_context()
    payload = own_pass.report_payload(ctx)
    with open(os.path.join(REPO_ROOT, "OWNERSHIP.json"),
              encoding="utf-8") as f:
        baseline = json.load(f)
    assert payload == baseline, \
        "OWNERSHIP.json out of date: regenerate with `python -m " \
        "tools.aphrocheck --ledger --json > OWNERSHIP.json`"


def test_ledger_covers_canonical_sites():
    """Every pool-allocating owner seam appears in the ledger, each
    with at least one statically-reachable free seam, and the schema
    carries no line numbers (code motion must not drift it)."""
    with open(os.path.join(REPO_ROOT, "OWNERSHIP.json"),
              encoding="utf-8") as f:
        baseline = json.load(f)
    sites = baseline["alloc_sites"]
    bm = "aphrodite_tpu/processing/block_manager.py::BlockSpaceManager"
    # (since PR 33 a slot's pages are taken in `_cover`, for every
    # page group's table, and a prompt's in `_allocate_groups` where
    # the groups are not one plain one)
    for fn in ("allocate", "_allocate_groups", "_cover", "append_slots",
               "swap_in", "swap_out"):
        key = f"{bm}.{fn}"
        assert key in sites, f"{key} missing from OWNERSHIP.json"
        assert sites[key]["free_seams"], f"{key} has no free seam"
    # the prefix pin is balanced by the free_prefix seam specifically
    pins = baseline["refcount_seams"][f"{bm}.allocate"]
    assert any(s.endswith("free_prefix") for s in pins["free_seams"])
    blob = json.dumps(baseline)
    assert '"line"' not in blob and '"lineno"' not in blob


def test_cli_ledger_human_and_json():
    human = subprocess.run(
        [sys.executable, "-m", "tools.aphrocheck", "--ledger"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert human.returncode == 0, human.stderr
    assert "BlockSpaceManager.allocate" in human.stdout
    assert "free_prefix" in human.stdout
    as_json = subprocess.run(
        [sys.executable, "-m", "tools.aphrocheck", "--ledger",
         "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert as_json.returncode == 0, as_json.stderr
    payload = json.loads(as_json.stdout)
    with open(os.path.join(REPO_ROOT, "OWNERSHIP.json"),
              encoding="utf-8") as f:
        assert payload == json.load(f), \
            "--ledger --json drifted from OWNERSHIP.json"


# ------------------------------------------------------------------
# 3. the motivating findings reproduce on the seed shapes
# ------------------------------------------------------------------

_SEED_SHAPE = textwrap.dedent('''
    class BlockSpaceManager:
        def __init__(self, pool):
            self.hbm_pool = pool
            self.block_tables = {}

        def allocate(self, seq_group, prefix, window):
            block_table = []
            if prefix is not None and prefix.allocated:
                for block in prefix.block_table:
                    block.ref_count += seq_group.num_seqs()
                    block_table.append(block)
            for logical_idx in range(seq_group.blocks_needed()):
                if window is not None and logical_idx >= window:
                    block = block_table[logical_idx % window]
                else:
                    block = self.hbm_pool.allocate()
                block.ref_count = seq_group.num_seqs()   # the clobber
                block_table.append(block)
            if prefix is not None and not prefix.allocated:
                shared = block_table[:prefix.get_num_blocks()]
                for block in shared:
                    block.ref_count += 1                 # pin forever
                prefix.set_block_table(shared)
            for seq in seq_group.seqs():
                self.block_tables[seq.seq_id] = block_table.copy()

        def free(self, seq):
            self._free_block_table(self.block_tables.pop(seq.seq_id))

        def _free_block_table(self, block_table):
            for block in set(block_table):
                self.hbm_pool.free(block)


    class Prefix:
        def __init__(self):
            self.block_table = None

        def set_block_table(self, block_table):
            self.block_table = block_table.copy()
''')


def test_seed_shapes_reproduce_both_leak002_forms(tmp_path):
    """The exact pre-fix `allocate` shape fires BOTH LEAK002 forms:
    the `ref_count = n` clobber on the window-reused path, and the
    prefix pin with no free seam — the two live findings this PR
    fixed in-tree (increment-only reuse + free_prefix)."""
    mod = tmp_path / "seed_shape.py"
    mod.write_text(_SEED_SHAPE)
    ctx, parse_findings = build_context(str(tmp_path),
                                       ["seed_shape.py"])
    assert not parse_findings
    findings = leak_pass.run(ctx)
    rules = sorted(f.rule for f in findings)
    assert rules == ["LEAK002", "LEAK002"], \
        [f.render() for f in findings]
    messages = " ".join(f.message for f in findings)
    assert "clobbers" in messages
    assert "pin-forever" in messages


# ------------------------------------------------------------------
# 4. the boundary holds on the real tree, pragma-free
# ------------------------------------------------------------------

def test_real_tree_clean_and_pragma_free():
    """The LEAK/OWN gate is green on the whole tree with the
    allowlist disabled, and WITHOUT any `# owner-ok:` pragma in the
    engine/processing/executor layers — the live findings (the
    scheduler's raw `block_manager.block_tables` reach-in, the
    clobber, the pin) were fixed in-tree, not registered."""
    report = run(allowlist_path=None, rule_prefixes=["LEAK", "OWN"])
    assert not report.findings, \
        [f.render() for f in report.findings]
    for rel in ("aphrodite_tpu/processing/scheduler.py",
                "aphrodite_tpu/processing/block_manager.py",
                "aphrodite_tpu/common/prefix.py",
                "aphrodite_tpu/engine/aphrodite_engine.py",
                "aphrodite_tpu/executor/model_runner.py"):
        with open(os.path.join(REPO_ROOT, rel), encoding="utf-8") as f:
            assert "owner-ok:" not in f.read(), \
                f"{rel} should be clean WITHOUT pragmas"


def test_scheduler_uses_owner_projection():
    """The one live OWN002 finding this pass surfaced — the burst
    reservation reaching into `block_manager.block_tables` for raw
    block objects — is fixed: the scheduler uses the int-only
    `block_numbers()` projection."""
    with open(os.path.join(REPO_ROOT, "aphrodite_tpu", "processing",
                           "scheduler.py"), encoding="utf-8") as f:
        src = f.read()
    assert "block_manager.block_numbers(" in src
    assert "block_manager.block_tables[" not in src


def test_subset_scan_covers_new_passes(tmp_path):
    """`--changed`-style subset scans run the LEAK/OWN families: a
    seeded violation in an explicitly-passed file is reported through
    the full `run()` pipeline (not just the pass entry points)."""
    report = run(rels=[_fixture("fixture_own_refcount.py")],
                 rule_prefixes=["OWN"], allowlist_path=None)
    assert [f.rule for f in report.findings] == ["OWN001"]
