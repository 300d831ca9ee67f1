"""RACE pass: two-world shared-state hazards over the domain-classified
call graph.

PR 10 established the engine's concurrency invariants by convention:
state shared between the event loop and the `run_in_executor` step
thread is either written from ONE world (the other only reads,
tolerating staleness), sequenced by the loop's await of the step
future, or protected by the reincarnation epoch guard (`_step_tls`
vs `engine._epoch`). This pass makes those conventions machine-checked
so the next off-loop commit path cannot silently forget them —
especially before ROADMAP item 5 multiplies the engine by N replicas.

Scope: `aphrodite_tpu/engine/`, `aphrodite_tpu/endpoints/`,
`aphrodite_tpu/processing/` (RACE002: `engine/` only — the epoch
guard is an engine-class invariant), plus explicitly-passed modules
outside the scanned roots (the seeded fixtures).

- RACE001: a `self.` attribute WRITTEN (assignment, augmented
  assignment, subscript store, or a mutating method call — append/
  pop/clear/...) in BOTH execution domains of the same class, without
  a `# thread-safe: <reason>` pragma. One-world writers with
  other-world readers are recognized clean by construction — that is
  the documented pattern (tracker/admission/health counters); it is
  two-world WRITES that need either a reasoned pragma or a fix.
  `__init__`/`__post_init__` writes do not count as a domain (they
  run before the object is shared) but their lines — and the class
  definition line, for a documented class-wide seam — are honored as
  pragma carriers.
- RACE002: a scheduler/tracker-committing call (`self.scheduler.
  schedule/add_seq_group/crash_rollback/...`) in a STEP_THREAD-domain
  engine function with no epoch guard on the path: the function
  neither compares an `epoch` value itself nor calls a helper that
  does (``_check_epoch``). This is the PR-10 invariant: a
  watchdog-abandoned step thread that wakes up after a reincarnation
  must raise StaleEngineStepError instead of committing against the
  rebuilt scheduler. The function that ROTATES the epoch (writes
  `_epoch`) is the rotation point and exempt.
- RACE003: mutable module-level state (dict/list/set/deque literal or
  constructor) that is MUTATED inside a domain-classified function
  and touched from both worlds. Module globals have no owning
  instance to sequence access through; either move the state onto the
  object whose lifecycle guards it, or pragma the line with the
  reason it is safe.

Escape hatch: `# thread-safe: <reason>` on the flagged line, any
write site of the attribute (its `__init__` line included), or the
class definition line (a class-wide documented seam), same comment
idiom as BP001's `# bounded-by:`.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.aphrocheck.core import (EVENT_LOOP, STEP_THREAD, Finding,
                                   Module, call_tail, has_pragma,
                                   tail_name, walk_nodes)

_HOT_PREFIXES = ("aphrodite_tpu/engine/", "aphrodite_tpu/endpoints/",
                 "aphrodite_tpu/processing/", "aphrodite_tpu/fleet/")
_ENGINE_PREFIXES = ("aphrodite_tpu/engine/",)

#: Everything the CLI normally scans; explicitly-passed files outside
#: these roots (the seeded fixtures) are treated as in-scope.
_SCAN_PREFIXES = ("aphrodite_tpu/", "benchmarks/", "bench.py")

_PRAGMA = "thread-safe:"

#: Method calls that mutate their receiver in place.
_MUTATORS = {
    "append", "appendleft", "extend", "extendleft", "insert", "add",
    "update", "setdefault", "pop", "popleft", "remove", "discard",
    "clear", "put_nowait", "sort", "reverse",
}

#: Scheduler/tracker receivers + the committing methods on them
#: (RACE002). These mutate scheduling state a reincarnation rebuilds.
_COMMIT_RECEIVERS = ("scheduler", "_request_tracker", "tracker")
_COMMIT_METHODS = {
    "schedule", "schedule_prompt_only", "add_seq_group",
    "abort_seq_group", "crash_rollback", "free_finished_seq_groups",
    "expire_waiting", "reserve_decode_burst", "fork_seq",
}

#: Constructor tails that produce mutable containers (RACE003).
_MUTABLE_CTORS = {"dict", "list", "set", "deque", "defaultdict",
                  "OrderedDict", "Counter"}


def _in_scope(rel: str, prefixes=_HOT_PREFIXES) -> bool:
    rel = rel.replace("\\", "/")
    if any(rel.startswith(p) for p in prefixes):
        return True
    return not any(rel == p.rstrip("/") or rel.startswith(p)
                   for p in _SCAN_PREFIXES)


def _self_attr_of_target(node: ast.AST) -> Optional[str]:
    """'x' for a `self.x` / `self.x[k]` store target."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _mutator_self_attr(call: ast.Call) -> Optional[str]:
    """'x' for `self.x.append(...)`-style in-place mutation."""
    fn = call.func
    if isinstance(fn, ast.Attribute) and fn.attr in _MUTATORS:
        return _self_attr_of_target(fn.value)
    return None


def _method_class(module: Module, fn: ast.AST) -> Optional[ast.ClassDef]:
    cur = module.parents.get(fn)
    while cur is not None:
        if isinstance(cur, ast.ClassDef):
            return cur
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return None      # nested def: not a direct method
        cur = module.parents.get(cur)
    return None


def _attr_writes(module: Module, fn: ast.AST
                 ) -> List[Tuple[str, ast.AST]]:
    """(attr, node) for every `self.X` write in one method body."""
    out: List[Tuple[str, ast.AST]] = []
    for node in walk_nodes(fn):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in targets:
                if isinstance(tgt, ast.Tuple):
                    elts = tgt.elts
                else:
                    elts = [tgt]
                for elt in elts:
                    attr = _self_attr_of_target(elt)
                    if attr is not None:
                        out.append((attr, node))
        elif isinstance(node, ast.Call):
            attr = _mutator_self_attr(node)
            if attr is not None:
                out.append((attr, node))
    return out


def _race001(ctx, module: Module) -> List[Finding]:
    cg = ctx.call_graph
    findings: List[Finding] = []
    for cls in module.nodes:
        if not isinstance(cls, ast.ClassDef):
            continue
        if has_pragma(module, cls.lineno, _PRAGMA):
            continue         # documented class-wide seam
        # attr -> {domain -> first write node}, plus every write line
        # (pragma carriers) incl. __init__'s initializing stores.
        by_attr: Dict[str, Dict[str, ast.AST]] = {}
        pragma_lines: Dict[str, List[int]] = {}
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            writes = _attr_writes(module, fn)
            if fn.name in ("__init__", "__post_init__"):
                for attr, node in writes:
                    pragma_lines.setdefault(attr, []).append(
                        node.lineno)
                continue
            domains = cg.domains_of(fn)
            if not domains:
                continue
            for attr, node in writes:
                pragma_lines.setdefault(attr, []).append(node.lineno)
                slots = by_attr.setdefault(attr, {})
                for d in domains:
                    slots.setdefault(d, node)
        for attr, slots in sorted(by_attr.items()):
            if EVENT_LOOP not in slots or STEP_THREAD not in slots:
                continue
            if any(has_pragma(module, line, _PRAGMA)
                   for line in pragma_lines.get(attr, ())):
                continue
            node = slots[STEP_THREAD]
            findings.append(module.finding(
                "RACE001", node,
                f"self.{attr} of {cls.name} is written from BOTH the "
                "event loop and the step thread with nothing "
                "documenting why that is safe — single-writer it, "
                "sequence it through the engine loop, or register "
                "the reason with a `# thread-safe: <reason>` comment"))
    return findings


def _epoch_compare_fns(ctx) -> Set[str]:
    """Names of functions whose body compares an epoch value — the
    guard carriers RACE002 recognizes (directly or one call away)."""
    out: Set[str] = set()
    for module in ctx.modules:
        if "epoch" not in module.text:
            # text prefilter: no epoch mentions, no guard carriers
            continue
        for name, defs in _defs_of(module).items():
            for fn in defs:
                if _has_epoch_compare(fn):
                    out.add(name)
    return out


def _defs_of(module: Module) -> Dict[str, List[ast.AST]]:
    out: Dict[str, List[ast.AST]] = {}
    for node in module.nodes:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    return out


def _has_epoch_compare(fn: ast.AST) -> bool:
    for node in walk_nodes(fn):
        if not isinstance(node, ast.Compare):
            continue
        for sub in walk_nodes(node):
            if isinstance(sub, ast.Attribute) and \
                    "epoch" in sub.attr:
                return True
            if isinstance(sub, ast.Name) and "epoch" in sub.id:
                return True
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str) and \
                    "epoch" in sub.value:
                return True      # getattr(self._step_tls, "epoch", ..)
    return False


def _rotates_epoch(fn: ast.AST) -> bool:
    """The epoch-rotation point (reincarnate) writes `_epoch` itself."""
    for node in walk_nodes(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in targets:
                if isinstance(tgt, ast.Attribute) and \
                        "epoch" in tgt.attr:
                    return True
    return False


def _race002(ctx, module: Module, guarded_names: Set[str]
             ) -> List[Finding]:
    cg = ctx.call_graph
    findings: List[Finding] = []
    for name, defs in _defs_of(module).items():
        for fn in defs:
            if name in ("__init__", "__post_init__"):
                continue
            if STEP_THREAD not in cg.domains_of(fn):
                continue
            if _has_epoch_compare(fn) or _rotates_epoch(fn):
                continue
            called = {call_tail(c) for c in walk_nodes(fn)
                      if isinstance(c, ast.Call)}
            if called & guarded_names:
                continue
            for call in walk_nodes(fn):
                if not isinstance(call, ast.Call):
                    continue
                f = call.func
                if not (isinstance(f, ast.Attribute) and
                        f.attr in _COMMIT_METHODS):
                    continue
                recv = f.value
                if not (isinstance(recv, ast.Attribute) and
                        isinstance(recv.value, ast.Name) and
                        recv.value.id == "self" and
                        recv.attr in _COMMIT_RECEIVERS):
                    continue
                if has_pragma(module, call.lineno, _PRAGMA):
                    continue
                findings.append(module.finding(
                    "RACE002", call,
                    f"self.{recv.attr}.{f.attr}(...) commits "
                    "scheduling state from the step thread with no "
                    "epoch guard on the path: a watchdog-abandoned "
                    "step waking after a reincarnation would corrupt "
                    "the rebuilt scheduler — call the engine's "
                    "_check_epoch() (or compare _step_tls.epoch to "
                    "_epoch) before committing"))
    return findings


def _race003(ctx, module: Module) -> List[Finding]:
    cg = ctx.call_graph
    # module-level mutable containers
    mutables: Dict[str, ast.AST] = {}
    for stmt in module.tree.body:
        if not isinstance(stmt, ast.Assign):
            continue
        value = stmt.value
        is_mutable = isinstance(value, (ast.List, ast.Dict, ast.Set,
                                        ast.ListComp, ast.DictComp,
                                        ast.SetComp)) or (
            isinstance(value, ast.Call) and
            tail_name(value.func) in _MUTABLE_CTORS)
        if not is_mutable:
            continue
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Name):
                mutables[tgt.id] = stmt
    if not mutables:
        return []
    touched: Dict[str, Set[str]] = {}   # name -> domains touching it
    mutated: Dict[str, bool] = {}
    for node in module.nodes:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        domains = cg.domains_of(node)
        if not domains:
            continue
        for inner in walk_nodes(node):
            name = None
            is_write = False
            if isinstance(inner, ast.Name) and inner.id in mutables:
                name = inner.id
                parent = module.parents.get(inner)
                if isinstance(parent, ast.Subscript) and \
                        isinstance(parent.ctx, ast.Store):
                    is_write = True
                elif isinstance(parent, ast.Attribute) and \
                        parent.attr in _MUTATORS:
                    is_write = True
            if name is None:
                continue
            touched.setdefault(name, set()).update(domains)
            if is_write:
                mutated[name] = True
    findings: List[Finding] = []
    for name, stmt in sorted(mutables.items(),
                             key=lambda kv: kv[1].lineno):
        domains = touched.get(name, set())
        if not mutated.get(name) or \
                not {EVENT_LOOP, STEP_THREAD} <= domains:
            continue
        if has_pragma(module, stmt.lineno, _PRAGMA):
            continue
        findings.append(module.finding(
            "RACE003", stmt,
            f"module-level mutable `{name}` is mutated in one world "
            "and touched from the other; module globals have no "
            "owning instance to sequence access through — move the "
            "state onto the object whose lifecycle guards it, or "
            "register the reason with `# thread-safe: <reason>`"))
    return findings


def run(ctx) -> List[Finding]:
    findings: List[Finding] = []
    guarded_names = _epoch_compare_fns(ctx)
    for module in ctx.modules:
        if _in_scope(module.rel):
            findings.extend(_race001(ctx, module))
            findings.extend(_race003(ctx, module))
        if _in_scope(module.rel, _ENGINE_PREFIXES):
            findings.extend(_race002(ctx, module, guarded_names))
    return findings


#: (rule, one-line contract, example) — rendered by `--rules-md`.
RULES = (
    ("RACE001", "a `self.` attribute written (assign/augassign/"
     "subscript/mutator call) in BOTH the event-loop and step-thread "
     "domains of one class without a `# thread-safe: <reason>` "
     "pragma (write site, `__init__` line, or class line for a "
     "documented seam) — single-writer + other-world readers is the "
     "recognized-clean pattern",
     "a counter `+= 1`'d in an async handler AND in a "
     "run_in_executor callee"),
    ("RACE002", "a scheduler/tracker-committing call (`schedule`, "
     "`add_seq_group`, `crash_rollback`, ...) in a STEP_THREAD-domain "
     "engine function with no epoch guard on the path (no `epoch` "
     "compare in the function or a called helper) — the PR-10 "
     "stale-step invariant",
     "`self.scheduler.schedule()` in an off-loop helper that never "
     "checks `_step_tls.epoch`"),
    ("RACE003", "mutable module-level state (dict/list/set/deque) "
     "mutated inside a domain-classified function and touched from "
     "both worlds, without a `# thread-safe: <reason>` pragma",
     "a module-level `PENDING = {}` filled on the loop and drained "
     "in a thread"),
)
