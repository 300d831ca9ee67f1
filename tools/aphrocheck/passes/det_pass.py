"""DET pass: static determinism / replay-surface analysis (aphrodet).

Every recent subsystem — mid-stream failover, spec decode, the disagg
split mesh — rests on ONE invariant: seeded streams are BIT-EQUAL
across resume, reincarnation, journal splice, and mesh reshaping. The
dynamic parity suites sample that invariant; this pass machine-proves
the static half and ledgers the whole replay surface in
REPLAYPLAN.json (regenerate with
`python -m tools.aphrocheck --replayplan --json > REPLAYPLAN.json`).

The replay contract has three legs:

1. The PRNG salt seam: every sampled token's key derives from
   `SamplingParams.seed` folded with the OUTPUT POSITION
   (`sampler._make_row_keys`: fold_in(fold_in(PRNGKey(seed),
   output_len), sibling_index)), so a resumed stream continues at
   position n with the exact key the original stream would have used.
2. The ordered-commit rule: any loop that commits state (token
   emission, page alloc/free, queue mutation) must iterate in a
   REPRODUCIBLE order — FCFS list order, `sorted(...)`, or dict
   insertion order. Python sets hash by id/PYTHONHASHSEED: iterating
   one into a commit replays differently per process.
3. The continuation seams: `add_request(emitted_token_ids=)`, the
   reincarnation FCFS restore, and the router's `_issue_continuation`
   journal splice. Everything a continuation reads must come from the
   journaled surface (emitted tokens, prompt, seed) — never from
   tracker ephemera (EWMAs, monotonic counters) that died with the
   old incarnation.

- DET001: a loop in engine//executor//processing step-path scope whose
  body commits state while iterating an UNORDERED collection (a set
  constructor/literal/comprehension, a set-algebra result, or a name
  assigned from one) without `sorted(...)` — the replay-order hazard.
  Dict iteration is insertion-ordered (3.7+) and stays quiet.
- DET002: PRNG derivation outside the registered salt seam — a
  `jax.random.PRNGKey` not folded through `fold_in` (the position-salt
  idiom), a `split`/`fold_in` whose key is neither a threaded
  parameter nor derived from the seam, or any host
  `random.*`/`np.random.*` call in engine/fleet/sampler scope.
- DET003: `id()` / builtin `hash()` / wall-clock reads flowing into a
  sampling or scheduling DECISION — a sort key or a PRNG seed/salt
  argument. str/object hashes are PYTHONHASHSEED-salted and ids are
  addresses: both replay differently per process (complements
  CLOCK001, which bans wall-clock deadlines wholesale).
- DET004: drift vs the checked-in REPLAYPLAN.json — the enumerated
  salt sites, committed-iteration-order sites, continuation seams and
  `# replay-ok:` pragmas must byte-match the baseline (line numbers
  excluded, so pure code motion cannot drift it); a NEW salt site or
  continuation seam reports the grown replay surface specifically.
- DET005: a continuation-seam function reading token-affecting
  ephemera outside the ledger'd replay surface — EWMA/load/latency
  tracker attributes or wall-clock reads — without a reasoned
  `# replay-ok: <reason>` pragma. The pragma is the registration
  idiom (`# bounded-by:`/`# owner-ok:` family): the reason is
  ledgered, so every escape is a reviewed, named decision.
"""
from __future__ import annotations

import ast
import json
import os
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.aphrocheck.core import (Finding, Module, assignments_of,
                                   call_tail, dotted_name, has_pragma,
                                   keyword_arg, walk_nodes)

BASELINE_FILE = "REPLAYPLAN.json"

PRAGMA = "replay-ok:"

#: DET001/DET003/DET005 scope: the step-path surface whose iteration
#: order and entropy sources decide token values and commit order.
_HOT_PREFIXES = ("aphrodite_tpu/engine/", "aphrodite_tpu/executor/",
                 "aphrodite_tpu/processing/")

#: DET002/DET003/DET005 extended scope: the fleet router hosts the
#: journal-splice continuation seam.
_FLEET_PREFIX = "aphrodite_tpu/fleet/"

#: The two modules that ARE the salt seam — scanned so a new
#: derivation beside the registered one cannot hide in its own file.
_SEAM_MODULES = ("aphrodite_tpu/modeling/layers/sampler.py",
                 "aphrodite_tpu/modeling/layers/rejection.py")

#: Everything the CLI normally scans; explicitly-passed files outside
#: these roots (the seeded fixtures) are treated as in-scope.
_SCAN_PREFIXES = ("aphrodite_tpu/", "benchmarks/", "bench.py")

#: jax.random derivation tails (consumption — gumbel/uniform/
#: categorical — is keyed by what derivation produced and needs no
#: rule of its own).
_DERIVE_TAILS = ("PRNGKey", "key", "split", "fold_in")

#: Loop-body calls that commit engine state whatever the receiver.
_COMMIT_TAILS = frozenset((
    "append_token_id", "add_seq_group", "add_request",
    "abort_seq_group", "allocate", "swap_in", "swap_out",
    "kv_handoff", "put_nowait", "fork", "emit_token"))

#: Container verbs that commit only through a `self.`-rooted receiver
#: (mutating a loop-local accumulator is not a commit).
_CONTAINER_TAILS = frozenset((
    "append", "appendleft", "add", "extend", "update", "pop",
    "popleft", "remove", "discard", "clear", "insert", "put"))

#: Set-returning constructors and set-algebra methods (DET001).
_SET_MAKERS = ("set", "frozenset")
_SET_METHODS = ("intersection", "union", "difference",
                "symmetric_difference")

#: Tracker-ephemera attribute markers (DET005): per-incarnation
#: rolling state that dies with the process and must never decide
#: token values on a continuation.
_EPHEMERA_MARKERS = ("ewma", "latency", "load_score", "tokens_per_s",
                     "inflight", "heat_")

#: Entropy-drawing tails of the stdlib `random` module (a bare
#: `parts[0] == "random"` test would flag locals named `random` — the
#: sampler unpacks one from `_sample_tokens`).
_HOST_RANDOM_TAILS = frozenset((
    "random", "randint", "randrange", "getrandbits", "randbytes",
    "choice", "choices", "shuffle", "sample", "uniform", "triangular",
    "gauss", "normalvariate", "lognormvariate", "expovariate",
    "vonmisesvariate", "betavariate", "gammavariate", "paretovariate",
    "weibullvariate", "seed", "Random", "SystemRandom"))

#: Wall-clock reads (DET003 seed/sort-key contexts, DET005 seams).
_WALLCLOCK_NAMES = ("time.time", "time.monotonic", "time.perf_counter",
                    "time.time_ns", "time.monotonic_ns",
                    "time.perf_counter_ns")


def _fixture_scope(rel: str) -> bool:
    rel = rel.replace("\\", "/")
    return not any(rel == p.rstrip("/") or rel.startswith(p)
                   for p in _SCAN_PREFIXES)


def _step_scope(rel: str) -> bool:
    rel = rel.replace("\\", "/")
    return any(rel.startswith(p) for p in _HOT_PREFIXES) or \
        _fixture_scope(rel)


def _replay_scope(rel: str) -> bool:
    """DET002/003/005 scope: step path + fleet router + seam modules."""
    rel = rel.replace("\\", "/")
    return (any(rel.startswith(p) for p in _HOT_PREFIXES) or
            rel.startswith(_FLEET_PREFIX) or rel in _SEAM_MODULES or
            _fixture_scope(rel))


def _qualname(module: Module, fn: ast.AST) -> str:
    parts = [fn.name]
    cur = module.parents.get(fn)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            parts.append(cur.name)
        cur = module.parents.get(cur)
    return ".".join(reversed(parts))


def _params_of(scope: Optional[ast.AST]) -> Set[str]:
    if scope is None or not hasattr(scope, "args"):
        return set()
    a = scope.args
    return {p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)} | \
        {p.arg for p in ([a.vararg] if a.vararg else []) +
         ([a.kwarg] if a.kwarg else [])}


# ------------------------------------------------------------------
# DET001 — unordered-collection iteration committing state
# ------------------------------------------------------------------

def _order_class(module: Module, scope: Optional[ast.AST],
                 expr: ast.AST, depth: int = 0) -> str:
    """Iteration-order class of a loop iterable: 'unordered' (set
    hash order), 'sorted', 'insertion-ordered' (dict views,
    dict.fromkeys dedup), or 'fcfs' (list/deque arrival order — the
    default for anything we cannot prove set-like, which is the sound
    direction: what DET001 flags is real)."""
    if depth > 3 or expr is None:
        return "fcfs"
    if isinstance(expr, (ast.Set, ast.SetComp)):
        return "unordered"
    if isinstance(expr, ast.Call):
        t = call_tail(expr)
        if t in _SET_MAKERS or t in _SET_METHODS:
            return "unordered"
        if t == "sorted":
            return "sorted"
        if t in ("items", "keys", "values", "fromkeys"):
            return "insertion-ordered"
        if t in ("reversed", "enumerate", "list", "tuple") and \
                expr.args:
            return _order_class(module, scope, expr.args[0], depth + 1)
        return "fcfs"
    if isinstance(expr, ast.Name) and scope is not None:
        classes = {
            _order_class(module, scope, src, depth + 1)
            for src in assignments_of(scope, expr.id, module)}
        if "unordered" in classes:
            return "unordered"
        if classes == {"sorted"}:
            return "sorted"
        if classes == {"insertion-ordered"}:
            return "insertion-ordered"
    return "fcfs"


def _rooted_in_self(node: ast.AST) -> bool:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return isinstance(node, ast.Name) and node.id == "self"


def _commits_state(loop: ast.For) -> bool:
    """Whether the loop body commits engine state: a domain commit
    call (token emission, page alloc/free, queue ops), a free/alloc-
    named helper, a `self.`-rooted container verb, or a store through
    a `self.`-rooted attribute/subscript."""
    for stmt in loop.body + loop.orelse:
        for node in walk_nodes(stmt):
            if isinstance(node, ast.Call):
                t = call_tail(node) or ""
                if t in _COMMIT_TAILS or \
                        t.lstrip("_").startswith(("free", "alloc")):
                    return True
                if t in _CONTAINER_TAILS and \
                        isinstance(node.func, ast.Attribute) and \
                        _rooted_in_self(node.func.value):
                    return True
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for tgt in targets:
                    if isinstance(tgt, (ast.Attribute, ast.Subscript)) \
                            and _rooted_in_self(tgt):
                        return True
    return False


def _committing_loops(module: Module
                      ) -> Iterator[Tuple[ast.For, str, ast.AST]]:
    """(loop, order class, enclosing top-level fn) for every
    committing for-loop in the module."""
    for node in module.nodes:
        if not isinstance(node, ast.For):
            continue
        fn = module.top_level_function(node)
        if fn is None or not _commits_state(node):
            continue
        scope = module.enclosing_function(node)
        yield node, _order_class(module, scope, node.iter), fn


def _det001(module: Module, findings: List[Finding]) -> None:
    if not _step_scope(module.rel):
        return
    for loop, order, _fn in _committing_loops(module):
        if order != "unordered":
            continue
        if has_pragma(module, loop.lineno, PRAGMA):
            continue
        findings.append(module.finding(
            "DET001", loop,
            "state-committing loop iterates a SET — set order hashes "
            "by id/PYTHONHASHSEED, so a resumed or reincarnated "
            "process replays commits in a different order; iterate "
            "sorted(...) or dedup order-preserving with "
            "dict.fromkeys(...), or register a reason with "
            "`# replay-ok: <reason>`"))


# ------------------------------------------------------------------
# DET002 — PRNG derivation outside the salt seam
# ------------------------------------------------------------------

def _jax_random_derive(call: ast.Call) -> Optional[str]:
    """Derivation tail for jax.random.PRNGKey/key/split/fold_in calls
    (dotted through the `jax` root, so str.split stays invisible)."""
    name = dotted_name(call.func)
    if not name:
        return None
    parts = name.split(".")
    if len(parts) >= 3 and parts[0] == "jax" and \
            parts[-2] == "random" and parts[-1] in _DERIVE_TAILS:
        return parts[-1]
    return None


def _host_prng(call: ast.Call) -> bool:
    name = dotted_name(call.func)
    if not name:
        return False
    parts = name.split(".")
    if parts[0] == "random" and len(parts) == 2 and \
            parts[1] in _HOST_RANDOM_TAILS:
        return True
    return len(parts) >= 3 and parts[0] in ("np", "numpy") and \
        parts[1] == "random"


def _under_fold_in(module: Module, call: ast.Call) -> bool:
    cur = module.parents.get(call)
    while cur is not None:
        if isinstance(cur, ast.Call) and call_tail(cur) == "fold_in":
            return True
        cur = module.parents.get(cur)
    return False


def _tuple_unpacked_from_derive(scope: ast.AST, name: str) -> bool:
    """`key_u, key_r = jax.random.split(key)` — assignments_of only
    indexes Name targets, so the threaded check scans Tuple targets
    here."""
    for node in walk_nodes(scope):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if isinstance(tgt, (ast.Tuple, ast.List)) and any(
                    isinstance(e, ast.Name) and e.id == name
                    for e in tgt.elts):
                if isinstance(node.value, ast.Call) and \
                        call_tail(node.value) in _DERIVE_TAILS:
                    return True
    return False


def _key_threaded(module: Module, scope: Optional[ast.AST],
                  arg: Optional[ast.AST], depth: int = 0) -> bool:
    """Whether a split/fold_in key operand traces to the seam: a
    threaded parameter, a derivation call, or a local assigned from
    either. Attribute/subscript reads are treated as threaded (a
    stored key was derived where it was stored — the storing site is
    in scope and checked there)."""
    if arg is None or depth > 3:
        return False
    if isinstance(arg, ast.Call):
        return call_tail(arg) in _DERIVE_TAILS
    if isinstance(arg, (ast.Attribute, ast.Subscript)):
        return True
    if isinstance(arg, ast.Name):
        if arg.id in _params_of(scope):
            return True
        if scope is not None:
            for src in assignments_of(scope, arg.id, module):
                if _key_threaded(module, scope, src, depth + 1):
                    return True
            return _tuple_unpacked_from_derive(scope, arg.id)
    return False


def _det002(module: Module, findings: List[Finding]) -> None:
    if not _replay_scope(module.rel):
        return
    for call in module.calls:
        if has_pragma(module, call.lineno, PRAGMA):
            continue
        if _host_prng(call):
            findings.append(module.finding(
                "DET002", call,
                "host PRNG (`random`/`np.random`) in replay scope — "
                "process-local entropy cannot replay; thread "
                "randomness from SamplingParams.seed through the "
                "position-salt seam (sampler._make_row_keys)"))
            continue
        derive = _jax_random_derive(call)
        if derive in ("PRNGKey", "key"):
            if not _under_fold_in(module, call):
                findings.append(module.finding(
                    "DET002", call,
                    "jax.random.PRNGKey outside the salt seam — a "
                    "fresh key root ignores SamplingParams.seed and "
                    "the output-position salt, so a resumed stream "
                    "diverges; derive keys via fold_in(fold_in("
                    "PRNGKey(seed), output_len), sibling_index)"))
        elif derive in ("split", "fold_in"):
            scope = module.enclosing_function(call)
            key = call.args[0] if call.args else \
                keyword_arg(call, "key")
            if not _key_threaded(module, scope, key):
                findings.append(module.finding(
                    "DET002", call,
                    f"jax.random.{derive} of a key that does not "
                    "trace to the salt seam — keys must be threaded "
                    "parameters or fold_in/PRNGKey derivations so "
                    "every consumed key is position-salted"))


# ------------------------------------------------------------------
# DET003 — id()/hash()/wall-clock flowing into decisions
# ------------------------------------------------------------------

def _nondet_value(node: ast.AST) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    if isinstance(node.func, ast.Name) and \
            node.func.id in ("id", "hash"):
        return node.func.id + "()"
    name = dotted_name(node.func)
    if name in _WALLCLOCK_NAMES:
        return name + "()"
    return None


def _nondet_in(root: ast.AST) -> Optional[Tuple[ast.AST, str]]:
    """First nondeterministic value in the subtree that is USED as a
    value — `scores[id(r)]` uses id() as an identity token for a dict
    lookup (the decision value is the score, not the address), so
    anything inside a Subscript slice is exempt."""
    lookup_keys: Set[int] = set()
    for node in walk_nodes(root):
        if isinstance(node, ast.Subscript):
            for sub in walk_nodes(node.slice):
                lookup_keys.add(id(sub))
    for node in walk_nodes(root):
        if id(node) in lookup_keys:
            continue
        what = _nondet_value(node)
        if what:
            return node, what
    return None


def _det003(module: Module, findings: List[Finding]) -> None:
    if not _replay_scope(module.rel):
        return

    def report(anchor: ast.AST, what: str, where: str) -> None:
        if has_pragma(module, anchor.lineno, PRAGMA):
            return
        findings.append(module.finding(
            "DET003", anchor,
            f"{what} flows into {where} — id() is a memory address "
            "and str/object hash() is PYTHONHASHSEED-salted, so the "
            "decision replays differently per process; key on stable "
            "request/sequence ids (int/tuple hashes are exempt only "
            "because they never reach a decision here)"))

    for call in module.calls:
        t = call_tail(call)
        if t in ("sorted", "sort", "min", "max"):
            keyfn = keyword_arg(call, "key")
            if keyfn is not None:
                hit = _nondet_in(keyfn)
                if hit:
                    report(hit[0], hit[1], "a sort/selection key")
            continue
        seed_args: List[ast.AST] = []
        if t in ("PRNGKey", "fold_in", "Random", "RandomState",
                 "default_rng", "seed"):
            seed_args.extend(call.args)
            seed_args.extend(kw.value for kw in call.keywords)
        else:
            kw = keyword_arg(call, "seed")
            if kw is not None:
                seed_args.append(kw)
        for arg in seed_args:
            hit = _nondet_in(arg)
            if hit:
                report(hit[0], hit[1], "a PRNG seed/salt")


# ------------------------------------------------------------------
# DET005 — continuation seams reading un-ledgered ephemera
# ------------------------------------------------------------------

def _seam_functions(module: Module
                    ) -> Iterator[Tuple[ast.AST, str]]:
    """(fn, classification) for every continuation-seam function: the
    emitted-token replay seams and the router splice are 'journaled'
    (their whole input is the journal), the reincarnation restore is
    'fcfs-restore' (waiting-queue list order)."""
    for node in module.nodes:
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        if "emitted_token_ids" in _params_of(node):
            yield node, "journaled"
        elif node.name == "_issue_continuation":
            yield node, "journaled"
        elif node.name == "reincarnate":
            yield node, "fcfs-restore"


def _ephemera_reads(module: Module, fn: ast.AST
                    ) -> Iterator[Tuple[ast.AST, str]]:
    seen: Set[int] = set()
    for node in walk_nodes(fn):
        what = None
        if isinstance(node, ast.Attribute) and \
                isinstance(node.ctx, ast.Load) and \
                any(m in node.attr.lower() for m in _EPHEMERA_MARKERS):
            what = f"tracker ephemera `{node.attr}`"
        else:
            clock = _nondet_value(node)
            if clock and not clock.startswith(("id(", "hash(")):
                what = f"wall-clock `{clock}`"
        if what and node.lineno not in seen:
            seen.add(node.lineno)
            yield node, what


def _det005(module: Module, findings: List[Finding]) -> None:
    if not _replay_scope(module.rel):
        return
    for fn, _kind in _seam_functions(module):
        for node, what in _ephemera_reads(module, fn):
            if has_pragma(module, node.lineno, PRAGMA):
                continue
            findings.append(module.finding(
                "DET005", node,
                f"continuation seam `{fn.name}` reads {what} outside "
                "the ledger'd replay surface — a resumed stream must "
                "rebuild from the journal (emitted tokens, prompt, "
                "seed) alone; derive the value from journaled state "
                "or register the reason with `# replay-ok: <reason>`"))


# ------------------------------------------------------------------
# the replay-surface ledger (DET004's baseline)
# ------------------------------------------------------------------

def _salt_sites(ctx) -> Dict[str, str]:
    """Top-level functions containing a jax.random derivation,
    classified: 'position-salted' when the function folds salts in,
    'threaded-from-salted' when it only splits/consumes threaded
    keys, 'unsalted' otherwise (which DET002 fires on)."""
    sites: Dict[str, str] = {}
    for module in ctx.modules:
        if not _replay_scope(module.rel):
            continue
        if "jax" not in module.text:
            continue
        per_fn: Dict[int, Tuple[ast.AST, Set[str], bool]] = {}
        for call in module.calls:
            derive = _jax_random_derive(call)
            if derive is None:
                continue
            fn = module.top_level_function(call)
            if fn is None:
                continue
            rec = per_fn.setdefault(id(fn), (fn, set(), True))
            rec[1].add(derive)
            if derive in ("split", "fold_in"):
                scope = module.enclosing_function(call)
                key = call.args[0] if call.args else \
                    keyword_arg(call, "key")
                if not _key_threaded(module, scope, key):
                    per_fn[id(fn)] = (rec[0], rec[1], False)
            elif derive in ("PRNGKey", "key") and \
                    not _under_fold_in(module, call):
                per_fn[id(fn)] = (rec[0], rec[1], False)
        for fn, derives, clean in per_fn.values():
            qual = f"{module.rel}::{_qualname(module, fn)}"
            if not clean:
                sites[qual] = "unsalted"
            elif "fold_in" in derives:
                sites[qual] = "position-salted"
            else:
                sites[qual] = "threaded-from-salted"
    return {k: sites[k] for k in sorted(sites)}


def _commit_order_sites(ctx) -> Dict[str, List[str]]:
    sites: Dict[str, Set[str]] = {}
    for module in ctx.modules:
        if not _step_scope(module.rel):
            continue
        for _loop, order, fn in _committing_loops(module):
            qual = f"{module.rel}::{_qualname(module, fn)}"
            sites.setdefault(qual, set()).add(order)
    return {k: sorted(sites[k]) for k in sorted(sites)}


def _continuation_seams(ctx) -> Dict[str, str]:
    seams: Dict[str, str] = {}
    for module in ctx.modules:
        if not _replay_scope(module.rel):
            continue
        for fn, kind in _seam_functions(module):
            seams[f"{module.rel}::{_qualname(module, fn)}"] = kind
    return {k: seams[k] for k in sorted(seams)}


def _replay_pragmas(ctx) -> List[dict]:
    out: List[dict] = []
    for module in ctx.modules:
        if not (_step_scope(module.rel) or _replay_scope(module.rel)):
            continue
        if PRAGMA not in module.text:
            continue
        reasons: List[str] = []
        for line in module.lines:
            idx = line.find("# " + PRAGMA)
            if idx < 0:
                continue
            reasons.append(
                line[idx + len("# " + PRAGMA):].strip())
        for reason in sorted(set(reasons)):
            out.append({"path": module.rel.replace("\\", "/"),
                        "reason": reason})
    return sorted(out, key=lambda e: (e["path"], e["reason"]))


def report_payload(ctx) -> dict:
    """The REPLAYPLAN.json schema. Line numbers are excluded on
    purpose: pure code motion must not drift the baseline, only
    replay-surface changes."""
    return {
        "invariant": "seeded streams are bit-equal across resume, "
                     "reincarnation, journal splice, and mesh "
                     "reshaping",
        "salt_seam": {
            "base": "SamplingParams.seed",
            "salts": ["output position (len(output_token_ids))",
                      "sibling index within the sequence group"],
            "sites": _salt_sites(ctx),
        },
        "commit_order_sites": _commit_order_sites(ctx),
        "continuation_seams": _continuation_seams(ctx),
        "replay_ok_pragmas": _replay_pragmas(ctx),
    }


def render_report(ctx) -> str:
    payload = report_payload(ctx)
    lines = ["DET replay-surface ledger — the static half of the "
             "bit-equal resume invariant", ""]
    lines.append(f"invariant: {payload['invariant']}")
    seam = payload["salt_seam"]
    lines.append("")
    lines.append(f"salt seam: base={seam['base']}; "
                 f"salts={', '.join(seam['salts'])}")
    for qual, kind in seam["sites"].items():
        lines.append(f"  {qual}: {kind}")
    lines.append("")
    lines.append("committed-iteration-order sites:")
    for qual, orders in payload["commit_order_sites"].items():
        lines.append(f"  {qual}: {', '.join(orders)}")
    lines.append("")
    lines.append("continuation seams:")
    for qual, kind in payload["continuation_seams"].items():
        lines.append(f"  {qual}: {kind}")
    if payload["replay_ok_pragmas"]:
        lines.append("")
        lines.append("replay-ok pragmas (reviewed escapes):")
        for entry in payload["replay_ok_pragmas"]:
            lines.append(f"  {entry['path']}: {entry['reason']}")
    return "\n".join(lines)


# ------------------------------------------------------------------
# DET004 — drift vs the checked-in baseline
# ------------------------------------------------------------------

def _load_baseline(root: str) -> Optional[dict]:
    path = os.path.join(root, BASELINE_FILE)
    try:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _det004(ctx, payload: dict, findings: List[Finding]) -> None:
    if not getattr(ctx, "full_scan", True):
        return
    if not (payload["salt_seam"]["sites"] and
            payload["continuation_seams"]):
        # Subset scans without both seam legs in view have no plan to
        # compare; the full sweep and the tier-1 ledger test carry
        # the gate.
        return
    baseline = _load_baseline(getattr(ctx, "root", "."))
    if baseline is None or baseline == payload:
        return
    by_rel = {m.rel: m for m in ctx.modules}
    anchor_rel = next(iter(sorted(
        payload["continuation_seams"]))).split("::")[0]
    module = by_rel.get(anchor_rel, ctx.modules[0])
    anchor = module.tree.body[0] if getattr(module.tree, "body", None) \
        else module.tree
    base_seams = (baseline.get("continuation_seams", {})
                  if isinstance(baseline, dict) else {})
    base_salts = baseline.get("salt_seam", {}).get("sites", {}) \
        if isinstance(baseline, dict) else {}
    grew = sorted(
        [q for q in payload["continuation_seams"]
         if q not in base_seams] +
        [q for q in payload["salt_seam"]["sites"]
         if q not in base_salts])
    if grew:
        findings.append(module.finding(
            "DET004",  anchor,
            f"replay surface grew: {', '.join(grew)} not in the "
            f"checked-in {BASELINE_FILE} — a new salt site or "
            "continuation seam widens the bit-equal resume contract; "
            "if intentional, regenerate with `python -m "
            "tools.aphrocheck --replayplan --json > REPLAYPLAN.json`"))
    else:
        findings.append(module.finding(
            "DET004", anchor,
            f"{BASELINE_FILE} is out of sync with the tree — "
            "regenerate with `python -m tools.aphrocheck --replayplan "
            "--json > REPLAYPLAN.json`"))


# ------------------------------------------------------------------

def run(ctx) -> List[Finding]:
    findings: List[Finding] = []
    for module in ctx.modules:
        _det001(module, findings)
        _det002(module, findings)
        _det003(module, findings)
        _det005(module, findings)
    payload = report_payload(ctx)
    _det004(ctx, payload, findings)
    return findings


#: (rule, one-line contract, example) — rendered by `--rules-md`.
RULES = (
    ("DET001", "a state-committing loop in engine//executor//"
     "processing scope iterating a SET (constructor/literal/"
     "comprehension/set algebra, or a name assigned from one) — set "
     "order hashes by id/PYTHONHASHSEED and replays differently per "
     "process; iterate `sorted(...)` or dedup with `dict.fromkeys`",
     "`for block in set(block_table): pool.free(block)`"),
    ("DET002", "PRNG derivation outside the registered salt seam: a "
     "`jax.random.PRNGKey` not folded through `fold_in`, a "
     "`split`/`fold_in` key that traces to no threaded parameter or "
     "seam derivation, or any host `random`/`np.random` call in "
     "replay scope",
     "`jax.random.PRNGKey(step)` in the engine step path"),
    ("DET003", "`id()`/builtin `hash()`/wall-clock reads flowing "
     "into a sampling or scheduling decision (a sort key or a PRNG "
     "seed/salt argument) — addresses and PYTHONHASHSEED-salted "
     "hashes replay differently per process (complements CLOCK001)",
     "`sorted(groups, key=lambda g: id(g))` in the scheduler"),
    ("DET004", "REPLAYPLAN.json out of sync with the tree — the "
     "enumerated salt sites, committed-iteration-order sites, "
     "continuation seams, and replay-ok pragmas must byte-match; a "
     "grown replay surface is named specifically; regenerate with "
     "`python -m tools.aphrocheck --replayplan --json > "
     "REPLAYPLAN.json`",
     "a new `add_request(emitted_token_ids=)` seam not yet ledgered"),
    ("DET005", "a continuation-seam function (`emitted_token_ids` "
     "replay, router `_issue_continuation`, reincarnation restore) "
     "reading tracker ephemera (EWMA/load/latency attributes) or "
     "wall-clock outside the ledger'd replay surface without a "
     "reasoned `# replay-ok: <reason>` pragma",
     "a resume path trimming tokens by `self.decode_ewma`"),
)
