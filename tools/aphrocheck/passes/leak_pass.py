"""LEAK pass: static KV-page alloc/free pairing and refcount-lifecycle
analysis — the machine-checked twin of the chaos harnesses' dynamic
`kv_leak_pages == 0` proof.

The engine's central resource invariant — every KV page allocated is
freed exactly once, across preemption, CoW fork, swap, crash rollback,
reincarnation, and drain — was until now proven only dynamically. The
refcount mutations that uphold it are concentrated in the OWNER modules
(`processing/block_manager.py`, `common/block.py`, `common/prefix.py`);
this pass builds a static ownership model over them (alloc sites, the
owned containers blocks land in, and the free seams that drain each
container) and checks four contracts:

- LEAK001: a pool `.allocate()` result that can escape its function
  without reaching an owned table, a free, or the caller — including
  the EXCEPTION edge: a call that may raise sitting between the
  allocation and its store, outside any try, loses the page when it
  throws.
- LEAK002: refcount-lifecycle balance per seam. (a) every
  `ref_count +=` increment's destination container must have a
  statically-reachable free seam — this is what flagged the
  PrefixPool pin-forever (fixed in-tree by
  `BlockSpaceManager.free_prefix` + `Scheduler.clear_prefixes`);
  (b) a plain `ref_count = n` CLOBBER on a block that is not freshly
  allocated on every path — the sliding-window-over-prefix bug shape
  (a reused in-window block overwriting a pinned/shared count).
- LEAK003: use-after-free / double-free of a freed block name on a
  non-conflicting path — freeing again, re-storing it, or mutating
  its refcount. Reading `.block_number` after the free (the
  `append_slot` CoW return idiom) is recognized clean, as is a free
  whose block ends in `continue`/`break`/`return`/`raise` before the
  later use.
- LEAK004: state-removal seams (`crash_rollback`, `reincarnate`,
  abort, finished-group cleanup, drain force-abort — any engine/
  processing function) that `.pop`/`del`/`.clear`/rebind an owned
  block table without routing the removed entries through a free seam
  (or, for `.clear()`, capturing/returning them first — the
  `PrefixPool.clear()` ownership-transfer idiom).

The same model feeds `--ledger`: every alloc site -> its containers ->
their statically-reachable free seams, emitted as OWNERSHIP.json and
byte-equality drift-gated in tier-1 (see passes/own_pass.py).

Escape hatch: `# owner-ok: <reason>` on the flagged line or the
comment block above it (shared with the OWN rules).
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from tools.aphrocheck.core import (Finding, Module, call_tail,
                                   dotted_name, has_pragma,
                                   paths_conflict, tail_name,
                                   walk_nodes)

#: The page-owner modules: the only places block internals may be
#: touched (OWN001/002 enforce the outside; LEAK rules audit the
#: inside).
OWNER_MODULES = (
    "aphrodite_tpu/processing/block_manager.py",
    "aphrodite_tpu/common/block.py",
    "aphrodite_tpu/common/prefix.py",
)

#: Where state-removal seams live (LEAK004 scope on top of the owners).
_SEAM_PREFIXES = ("aphrodite_tpu/engine/", "aphrodite_tpu/processing/")

#: Everything the CLI normally scans; explicitly-passed files outside
#: these roots (the seeded fixtures) are treated as in-scope.
_SCAN_PREFIXES = ("aphrodite_tpu/", "benchmarks/", "bench.py")

_PRAGMA = "owner-ok:"

#: Receiver tails that denote a page pool (`X.allocate()` on these is
#: an alloc site; `X.free()` a free site).
POOL_NAMES = {"hbm_pool", "host_pool", "gpu_allocator", "cpu_allocator",
              "allocator", "pool", "block_pool"}

#: Owned-table attribute names LEAK004 guards removal of.
OWNED_TABLES = {"block_tables", "more_tables", "summary_tables",
                "prefixes"}

#: Container-mutating call tails that store a block.
_STORE_TAILS = {"append", "appendleft", "insert", "add", "extend"}

#: Block-object attribute READS that are safe after a free (the
#: append_slot read-number-after-free idiom).
_SAFE_AFTER_FREE = {"block_number", "device", "block_size"}


def _is_owner(rel: str) -> bool:
    return rel.replace("\\", "/") in OWNER_MODULES


def _in_scope(rel: str, prefixes=_SEAM_PREFIXES) -> bool:
    rel = rel.replace("\\", "/")
    if _is_owner(rel) or any(rel.startswith(p) for p in prefixes):
        return True
    return not any(rel == p.rstrip("/") or rel.startswith(p)
                   for p in _SCAN_PREFIXES)


def _qualname(module: Module, fn: ast.AST) -> str:
    parts = [fn.name]
    cur = module.parents.get(fn)
    while cur is not None:
        if isinstance(cur, ast.ClassDef):
            parts.append(cur.name)
        cur = module.parents.get(cur)
    return ".".join(reversed(parts))


def _fns(module: Module) -> List[ast.AST]:
    return [n for n in module.nodes
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


def _recv_tail(call: ast.Call) -> Optional[str]:
    """Tail name of a method call's receiver ('hbm_pool' for
    `self.hbm_pool.allocate()`)."""
    if isinstance(call.func, ast.Attribute):
        return tail_name(call.func.value)
    return None


def _is_alloc_call(call: ast.Call) -> bool:
    return call_tail(call) == "allocate" and \
        _recv_tail(call) in POOL_NAMES


def _is_fresh_source(value: ast.AST) -> bool:
    """Whether an assignment source yields a freshly-allocated block
    (`pool.allocate()` or the free-list `._free.pop()`)."""
    if not isinstance(value, ast.Call):
        return False
    if _is_alloc_call(value):
        return True
    return call_tail(value) == "pop" and _recv_tail(value) == "_free"


def _container_key(expr: ast.AST) -> Optional[str]:
    """Owned-container key of an expression: the tail attribute of
    `self.block_tables`, `prefix.block_table`,
    `self.block_tables[k]`, or `X.values()` / `set(X)` / `list(X)`
    wrappers around one."""
    if isinstance(expr, ast.Call):
        t = call_tail(expr)
        if t in ("values", "items", "keys", "pop", "popitem") and \
                isinstance(expr.func, ast.Attribute):
            return _container_key(expr.func.value)
        if t in ("set", "list", "sorted", "tuple", "reversed") and \
                expr.args:
            return _container_key(expr.args[0])
        return None
    if isinstance(expr, ast.Subscript):
        return _container_key(expr.value)
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


def _storing_methods(ctx) -> Dict[str, str]:
    """name -> attribute key, for defs that store a parameter into a
    `self.` attribute (`Prefix.set_block_table` stores to
    `self.block_table`) — the ownership-transfer calls LEAK002/the
    ledger resolve destinations through."""
    out: Dict[str, str] = {}
    for module in ctx.modules:
        if not _in_scope(module.rel) or "self." not in module.text:
            continue
        for fn in _fns(module):
            args = fn.args
            params = {a.arg for a in args.posonlyargs + args.args +
                      args.kwonlyargs} - {"self", "cls"}
            for node in walk_nodes(fn):
                if not isinstance(node, ast.Assign):
                    continue
                src = node.value
                if isinstance(src, ast.Call) and \
                        call_tail(src) == "copy" and \
                        isinstance(src.func, ast.Attribute):
                    src = src.func.value
                if not (isinstance(src, ast.Name) and
                        src.id in params):
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute) and \
                            isinstance(tgt.value, ast.Name) and \
                            tgt.value.id == "self":
                        out[fn.name] = tgt.attr
    return out


def _free_helpers(ctx) -> Set[str]:
    """Defs whose parameter flows into a pool `.free()` (directly or
    via iteration) — calls to them count as free sites
    (`_free_block_table`, `free_prefix`, wrappers in fixtures)."""
    helpers: Set[str] = {"free"}
    # One AST walk per function: collect (callee tail -> derived-name
    # first args) facts, then run the cheap fixpoint over those.
    facts: List[Tuple[str, Set[str]]] = []   # (fn name, callee tails)
    for module in ctx.modules:
        # text prefilter: only modules that mention freeing at all
        # can contribute helpers
        if not _in_scope(module.rel) or \
                ("free" not in module.text and
                 "ref_count" not in module.text):
            continue
        for fn in _fns(module):
            args = fn.args
            params = {a.arg for a in args.posonlyargs + args.args +
                      args.kwonlyargs} - {"self", "cls"}
            if not params:
                continue
            derived = set(params)
            calls: List[Tuple[str, str]] = []
            for node in walk_nodes(fn):
                if isinstance(node, ast.For) and \
                        isinstance(node.target, ast.Name):
                    src = node.iter
                    if isinstance(src, ast.Call) and src.args:
                        src = src.args[0]
                    if isinstance(src, ast.Name) and \
                            src.id in derived:
                        derived.add(node.target.id)
                    elif isinstance(src, ast.Attribute) and \
                            isinstance(src.value, ast.Name) and \
                            src.value.id in derived:
                        derived.add(node.target.id)
                elif isinstance(node, ast.Call) and node.args and \
                        isinstance(node.args[0], ast.Name):
                    t = call_tail(node)
                    if t:
                        calls.append((t, node.args[0].id))
            tails = {t for t, arg in calls if arg in derived}
            if tails:
                facts.append((fn.name, tails))
    changed = True
    while changed:
        changed = False
        for name, tails in facts:
            if name not in helpers and tails & helpers:
                helpers.add(name)
                changed = True
    return helpers


@dataclasses.dataclass
class FreeSeam:
    key: str            # container the seam drains
    where: str          # "path::Qual"
    fn_name: str        # bare function name (reachability check)


def _loop_container(module: Module, fn: ast.AST,
                    name_node: ast.Name) -> Optional[str]:
    """Container key of the loop a Name is the target of, resolving a
    Name iterable through its local assignment one level."""
    for node in walk_nodes(fn):
        if isinstance(node, ast.For) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == name_node.id:
            key = _container_key(node.iter)
            if key is not None:
                return key
            if isinstance(node.iter, ast.Name):
                for value in _local_sources(fn, node.iter.id):
                    key = _container_key(value)
                    if key is not None:
                        return key
    return None


def _local_sources(fn: ast.AST, name: str) -> List[ast.AST]:
    out = []
    for node in walk_nodes(fn):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    out.append(node.value)
    return out


def _local_container_keys(module: Module, fn: ast.AST, local: str,
                          storing: Dict[str, str]) -> Set[str]:
    """Owned-container keys a local list/dict corresponds to: what it
    was ASSIGNED FROM (`table = self.block_tables[k]`), what it is
    STORED INTO (`self.block_tables[k] = table(.copy())`), or the
    attribute a storing call files it under
    (`prefix.set_block_table(table)`)."""
    keys: Set[str] = set()
    for value in _local_sources(fn, local):
        key = _container_key(value)
        if key in OWNED_TABLES or key == "block_table":
            keys.add(key)
    for node in walk_nodes(fn):
        if isinstance(node, ast.Assign):
            src = node.value
            if isinstance(src, ast.Call) and call_tail(src) == "copy" \
                    and isinstance(src.func, ast.Attribute):
                src = src.func.value
            if not (isinstance(src, ast.Name) and src.id == local):
                continue
            for tgt in node.targets:
                key = _container_key(tgt)
                if key is not None:
                    keys.add(key)
        elif isinstance(node, ast.Call):
            t = call_tail(node)
            if t in storing and any(
                    isinstance(a, ast.Name) and a.id == local
                    for a in node.args):
                keys.add(storing[t])
    return keys


def _enclosing_loop(module: Module, node: ast.AST,
                    name: str) -> Optional[ast.For]:
    """Nearest For ancestor whose target is Name `name`."""
    cur = module.parents.get(node)
    while cur is not None and not isinstance(
            cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
        if isinstance(cur, ast.For) and \
                isinstance(cur.target, ast.Name) and \
                cur.target.id == name:
            return cur
        cur = module.parents.get(cur)
    return None


def _block_destinations(module: Module, fn: ast.AST, name: str,
                        storing: Dict[str, str],
                        anchor: Optional[ast.AST] = None) -> Set[str]:
    """Container keys a block NAME lands in: appended into a local
    that maps to an owned table, stored by subscript into one, handed
    to a storing method, or drawn from (and left in) an owned
    container it iterates. With an `anchor` node whose enclosing loop
    binds `name`, attribution is scoped to THAT loop — two loops
    reusing the conventional `block` name (the prefix-share loop and
    the pin loop in `allocate`) must not conflate their destinations.
    """
    if anchor is not None:
        loop = _enclosing_loop(module, anchor, name)
        if loop is not None:
            dests: Set[str] = set()
            key = _container_key(loop.iter)
            if key is None and isinstance(loop.iter, ast.Name):
                for value in _local_sources(fn, loop.iter.id):
                    k2 = _container_key(value)
                    if k2 is not None:
                        key = k2
                if key is None:
                    dests |= _local_container_keys(
                        module, fn, loop.iter.id, storing)
            if key is not None:
                dests.add(key)
            dests |= _stores_of_name(module, fn, loop, name, storing)
            return dests
    dests = _stores_of_name(module, fn, fn, name, storing)
    loop_key = None
    for node in walk_nodes(fn):
        if isinstance(node, ast.For) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == name:
            loop_key = _container_key(node.iter)
            if loop_key is None and isinstance(node.iter, ast.Name):
                # A derived local (e.g. a slice) stays unresolved ON
                # PURPOSE: the pin idiom's `shared = table[:n]` must
                # attribute to where `shared` is handed, not to the
                # table it sliced from.
                for value in _local_sources(fn, node.iter.id):
                    key = _container_key(value)
                    if key is not None:
                        loop_key = key
                if loop_key is None:
                    dests |= _local_container_keys(
                        module, fn, node.iter.id, storing)
            if loop_key is not None:
                dests.add(loop_key)
    return dests


def _stores_of_name(module: Module, fn: ast.AST, root: ast.AST,
                    name: str, storing: Dict[str, str]) -> Set[str]:
    """Append/subscript-store/storing-call destinations of `name`
    within `root` (container locals resolved across the whole fn)."""
    dests: Set[str] = set()
    for node in walk_nodes(root):
        if isinstance(node, ast.Call):
            t = call_tail(node)
            takes = any(isinstance(a, ast.Name) and a.id == name
                        for a in node.args)
            if not takes:
                continue
            if t in _STORE_TAILS and \
                    isinstance(node.func, ast.Attribute):
                recv = node.func.value
                key = _container_key(recv)
                if key is None and isinstance(recv, ast.Name):
                    dests |= _local_container_keys(
                        module, fn, recv.id, storing)
                elif key is not None:
                    dests.add(key)
            elif t in storing:
                dests.add(storing[t])
        elif isinstance(node, ast.Assign):
            if not (isinstance(node.value, ast.Name) and
                    node.value.id == name):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript):
                    key = _container_key(tgt.value)
                    if key is None and isinstance(tgt.value, ast.Name):
                        dests |= _local_container_keys(
                            module, fn, tgt.value.id, storing)
                    elif key is not None:
                        dests.add(key)
    return dests


def _free_seams(ctx, helpers: Set[str]) -> List[FreeSeam]:
    """Every (container key, function) pair where the function routes
    blocks of that container into a pool free."""
    seams: List[FreeSeam] = []
    for module in ctx.modules:
        if not _in_scope(module.rel) or \
                not any(h in module.text for h in helpers):
            continue
        for fn in _fns(module):
            where = f"{module.rel.replace(chr(92), '/')}::" \
                    f"{_qualname(module, fn)}"
            for call in walk_nodes(fn):
                if not isinstance(call, ast.Call):
                    continue
                if call_tail(call) not in helpers or not call.args:
                    continue
                arg = call.args[0]
                key = _container_key(arg)
                if key is None and isinstance(arg, ast.Name):
                    key = _loop_container(module, fn, arg)
                    if key is None:
                        for value in _local_sources(fn, arg.id):
                            k2 = _container_key(value)
                            if k2 is not None:
                                key = k2
                if key is not None:
                    seams.append(FreeSeam(key, where, fn.name))
    return seams


def _called_names(ctx) -> Set[str]:
    out: Set[str] = set()
    for module in ctx.modules:
        if not _in_scope(module.rel):
            continue
        for call in module.calls:
            t = call_tail(call)
            if t:
                out.add(t)
    return out


@dataclasses.dataclass
class OwnershipModel:
    """The shared alloc-site/refcount-seam/free-seam model (LEAK002
    verdicts + the --ledger payload are two views of it)."""
    storing: Dict[str, str]
    helpers: Set[str]
    seams: List[FreeSeam]
    called: Set[str]

    def seams_for(self, key: str, reachable_only: bool) -> List[str]:
        out = []
        for s in self.seams:
            if s.key != key:
                continue
            if reachable_only and s.fn_name not in self.called:
                continue
            out.append(s.where)
        return sorted(set(out))


def build_model(ctx) -> OwnershipModel:
    helpers = _free_helpers(ctx)
    return OwnershipModel(_storing_methods(ctx), helpers,
                          _free_seams(ctx, helpers),
                          _called_names(ctx))


def ownership_model(ctx) -> OwnershipModel:
    """Per-context memoized model (leak run, own run, and the ledger
    all share one build)."""
    cached = getattr(ctx, "_ownership_model", None)
    if cached is None:
        cached = build_model(ctx)
        ctx._ownership_model = cached
    return cached


# ------------------------------------------------------------------
# LEAK001: alloc-result escape (exception edges included)
# ------------------------------------------------------------------

def _stmt_of(module: Module, node: ast.AST) -> ast.AST:
    cur = node
    parent = module.parents.get(cur)
    while parent is not None and not isinstance(parent, (
            ast.FunctionDef, ast.AsyncFunctionDef, ast.Module,
            ast.If, ast.For, ast.While, ast.Try, ast.With)):
        cur, parent = parent, module.parents.get(parent)
    return cur


def _inside_try(module: Module, node: ast.AST) -> bool:
    cur = module.parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.Try):
            return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
        cur = module.parents.get(cur)
    return False


def _name_sinks(module: Module, fn: ast.AST, name: str,
                helpers: Set[str], resolvable: Set[str]) -> List[ast.AST]:
    """Uses of `name` that settle ownership: stored into a container,
    freed, returned, or handed to a same-package function."""
    sinks: List[ast.AST] = []
    for node in walk_nodes(fn):
        if isinstance(node, ast.Call):
            t = call_tail(node)
            takes = any(isinstance(a, ast.Name) and a.id == name
                        for a in node.args)
            if takes and (t in _STORE_TAILS or t in helpers or
                          t in resolvable):
                sinks.append(node)
        elif isinstance(node, ast.Assign):
            if isinstance(node.value, ast.Name) and \
                    node.value.id == name:
                for tgt in node.targets:
                    if isinstance(tgt, (ast.Subscript, ast.Attribute)):
                        sinks.append(node)
        elif isinstance(node, ast.Return) and node.value is not None:
            for sub in walk_nodes(node.value):
                if isinstance(sub, ast.Name) and sub.id == name:
                    sinks.append(node)
                    break
    return sinks


def _leak001(ctx, module: Module, model: OwnershipModel,
             resolvable: Set[str]) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _fns(module):
        for call in walk_nodes(fn):
            if not (isinstance(call, ast.Call) and _is_alloc_call(call)):
                continue
            if has_pragma(module, call.lineno, _PRAGMA):
                continue
            parent = module.parents.get(call)
            # nested directly in a settling position
            if isinstance(parent, ast.Call) and \
                    (call_tail(parent) in _STORE_TAILS or
                     call_tail(parent) in model.helpers or
                     call_tail(parent) in resolvable):
                continue
            if isinstance(parent, ast.Return):
                continue
            name = None
            if isinstance(parent, (ast.Assign, ast.AnnAssign)):
                targets = parent.targets if isinstance(
                    parent, ast.Assign) else [parent.target]
                if any(isinstance(t, (ast.Subscript, ast.Attribute))
                       for t in targets):
                    continue        # m[k] = alloc() / self.x = alloc()
                names = [t.id for t in targets
                         if isinstance(t, ast.Name)]
                name = names[0] if names else None
            if name is None:
                findings.append(module.finding(
                    "LEAK001", call,
                    "allocate() result is dropped — the page leaves "
                    "the free list and lands in no owned table, free, "
                    "or return"))
                continue
            sinks = _name_sinks(module, fn, name, model.helpers,
                                resolvable)
            if not sinks:
                findings.append(module.finding(
                    "LEAK001", call,
                    f"allocate() result `{name}` never reaches an "
                    "owned table, a free, or the caller — the page "
                    "leaks when this function returns"))
                continue
            if _inside_try(module, call):
                continue
            # exception edge: a raise-capable call strictly between
            # the allocation and its first sink in the same block
            alloc_stmt = _stmt_of(module, call)
            body = getattr(module.parents.get(alloc_stmt), "body", None)
            holder = module.parents.get(alloc_stmt)
            for attr in ("body", "orelse", "finalbody"):
                seq = getattr(holder, attr, None)
                if isinstance(seq, list) and alloc_stmt in seq:
                    body = seq
                    break
            if body is None:
                continue
            sink_stmts = [_stmt_of(module, s) for s in sinks]
            in_body = [s for s in sink_stmts if s in body]
            if not in_body:
                continue
            first = min(body.index(s) for s in in_body)
            start = body.index(alloc_stmt)
            for stmt in body[start + 1:first]:
                hazard = None
                for sub in walk_nodes(stmt):
                    if isinstance(sub, ast.Call):
                        recv = _recv_tail(sub)
                        if recv == name:
                            continue    # method on the block itself
                        hazard = sub
                        break
                if hazard is not None:
                    findings.append(module.finding(
                        "LEAK001", call,
                        f"`{call_tail(hazard)}(...)` can raise between "
                        f"this allocation and the store of `{name}` "
                        "(no enclosing try) — the page leaks on the "
                        "exception edge; store first, or free in a "
                        "finally"))
                    break
    return findings


# ------------------------------------------------------------------
# LEAK002: refcount inc/dec balance + clobber
# ------------------------------------------------------------------

def _refcount_target(node: ast.AST) -> Optional[ast.Name]:
    if isinstance(node, ast.Attribute) and node.attr == "ref_count" \
            and isinstance(node.value, ast.Name):
        return node.value
    return None


def _leak002(ctx, module: Module, model: OwnershipModel) -> List[Finding]:
    findings: List[Finding] = []
    reachable_only = bool(getattr(ctx, "full_scan", False))
    for fn in _fns(module):
        if fn.name in ("__init__", "__post_init__"):
            continue
        for node in walk_nodes(fn):
            if isinstance(node, ast.AugAssign) and \
                    isinstance(node.op, ast.Add):
                recv = _refcount_target(node.target)
                if recv is None or recv.id == "self":
                    continue
                if has_pragma(module, node.lineno, _PRAGMA):
                    continue
                dests = _block_destinations(module, fn, recv.id,
                                            model.storing, anchor=node)
                balanced = any(
                    model.seams_for(k, reachable_only) for k in dests)
                if not dests:
                    findings.append(module.finding(
                        "LEAK002", node,
                        f"`{recv.id}.ref_count` is incremented but the "
                        "block lands in no owned container — nothing "
                        "can ever pair the decrement"))
                elif not balanced:
                    names = ", ".join(sorted(dests))
                    findings.append(module.finding(
                        "LEAK002", node,
                        f"refcount increment pins `{recv.id}` into "
                        f"`{names}` but no statically-reachable free "
                        "seam drains that container — a pin-forever "
                        "leak (add a free seam like "
                        "BlockSpaceManager.free_prefix, or register "
                        "the reason with `# owner-ok: <reason>`)"))
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    recv = _refcount_target(tgt)
                    if recv is None or recv.id == "self":
                        continue
                    if has_pragma(module, node.lineno, _PRAGMA):
                        continue
                    sources = [
                        (v, module.branch_path(v))
                        for v in _local_sources(fn, recv.id)]
                    if not sources:
                        # parameter or loop var: not provably fresh
                        stale = True
                    else:
                        at = module.branch_path(node)
                        live = [v for v, p in sources
                                if not paths_conflict(at, p)]
                        stale = any(not _is_fresh_source(v)
                                    for v in live) or not live
                    if stale:
                        findings.append(module.finding(
                            "LEAK002", node,
                            f"`{recv.id}.ref_count = ...` clobbers a "
                            "block that is not freshly allocated on "
                            "every path — a reused/shared/pinned "
                            "count is overwritten (the sliding-"
                            "window-over-prefix bug shape); increment "
                            "on reuse instead, or assign only in the "
                            "fresh-allocation branch"))
    return findings


# ------------------------------------------------------------------
# LEAK003: use-after-free / double-free
# ------------------------------------------------------------------

def _terminates_after(body: List[ast.AST], idx: int) -> bool:
    return any(isinstance(s, (ast.Continue, ast.Break, ast.Return,
                              ast.Raise))
               for s in body[idx + 1:])


def _free_body(module: Module, call: ast.Call
               ) -> Tuple[Optional[list], int]:
    """(statement list, index) holding a free call's statement."""
    stmt = _stmt_of(module, call)
    holder = module.parents.get(stmt)
    for attr in ("body", "orelse", "finalbody"):
        seq = getattr(holder, attr, None)
        if isinstance(seq, list) and stmt in seq:
            return seq, seq.index(stmt)
    return None, -1


def _index_in(module: Module, body: list, node: ast.AST) -> int:
    """Index of the statement in `body` that contains `node`, -1 when
    the node lives outside this statement list."""
    cur = node
    while cur is not None:
        if cur in body:
            return body.index(cur)
        cur = module.parents.get(cur)
    return -1


def _leak003(ctx, module: Module, model: OwnershipModel) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _fns(module):
        frees: List[Tuple[str, ast.Call, tuple, list, int]] = []
        for call in walk_nodes(fn):
            if isinstance(call, ast.Call) and \
                    call_tail(call) in model.helpers and call.args and \
                    isinstance(call.args[0], ast.Name):
                body, idx = _free_body(module, call)
                frees.append((call.args[0].id, call,
                              module.branch_path(call), body, idx))
        if not frees:
            continue
        for node in walk_nodes(fn):
            use_kind = None
            name = None
            if isinstance(node, ast.Call):
                t = call_tail(node)
                if t in model.helpers and node.args and \
                        isinstance(node.args[0], ast.Name):
                    use_kind, name = "freed again (double free)", \
                        node.args[0].id
                elif t in _STORE_TAILS:
                    for a in node.args:
                        if isinstance(a, ast.Name):
                            use_kind, name = "re-stored into a table", \
                                a.id
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                tgts = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for tgt in tgts:
                    recv = _refcount_target(tgt)
                    if recv is not None:
                        use_kind, name = "refcount-mutated", recv.id
            if use_kind is None:
                continue
            use_path = module.branch_path(node)
            for fname, fcall, fpath, fbody, fidx in frees:
                if fname != name or node is fcall:
                    continue
                if node.lineno <= fcall.lineno:
                    continue
                if paths_conflict(use_path, fpath):
                    continue
                if fbody is not None:
                    uidx = _index_in(module, fbody, node)
                    if uidx >= 0:
                        # same statement list: only a terminator
                        # STRICTLY BETWEEN free and use breaks the path
                        if any(isinstance(s, (ast.Continue, ast.Break,
                                              ast.Return, ast.Raise))
                               for s in fbody[fidx + 1:uidx]):
                            continue
                    elif _terminates_after(fbody, fidx) and not (
                            fpath and tuple(fpath) ==
                            tuple(use_path[:len(fpath)])):
                        # the free's block exits before falling
                        # through to the use outside it (the swap_out
                        # free-then-continue shape)
                        continue
                if has_pragma(module, node.lineno, _PRAGMA):
                    continue
                findings.append(module.finding(
                    "LEAK003", node,
                    f"`{name}` was freed at line {fcall.lineno} and is "
                    f"{use_kind} here — reading `.block_number` after "
                    "a free is fine, mutating or re-freeing is "
                    "use-after-free"))
                break
    return findings


# ------------------------------------------------------------------
# LEAK004: state removal without routing through a free seam
# ------------------------------------------------------------------

def _reads_table_before(fn: ast.AST, attr: str,
                        before_line: int) -> bool:
    """A Load of the table on an EARLIER line than its `.clear()` —
    the iterate-free (reset) or capture-and-return (PrefixPool.clear)
    idioms. Strictly earlier: the clear call's own receiver load must
    not satisfy this."""
    for node in walk_nodes(fn):
        if isinstance(node, ast.Attribute) and node.attr == attr and \
                isinstance(node.ctx, ast.Load) and \
                getattr(node, "lineno", 0) < before_line:
            return True
    return False


def _leak004(ctx, module: Module, model: OwnershipModel) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _fns(module):
        if fn.name in ("__init__", "__post_init__"):
            continue
        for node in walk_nodes(fn):
            if isinstance(node, ast.Delete):
                for tgt in node.targets:
                    key = _container_key(tgt)
                    if key in OWNED_TABLES and \
                            not has_pragma(module, node.lineno, _PRAGMA):
                        findings.append(module.finding(
                            "LEAK004", node,
                            f"`del ...{key}[...]` removes a block "
                            "table without routing it through a free "
                            "seam — use `.pop()` into "
                            "`_free_block_table`/`free_prefix` (or "
                            "register the reason with `# owner-ok: "
                            "<reason>`)"))
                continue
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute) and \
                            tgt.attr in OWNED_TABLES and \
                            isinstance(node.value,
                                       (ast.Dict, ast.List)) and \
                            not has_pragma(module, node.lineno,
                                           _PRAGMA):
                        findings.append(module.finding(
                            "LEAK004", node,
                            f"rebinding `{tgt.attr}` to a fresh "
                            "container outside __init__ drops every "
                            "held page un-freed — free the entries "
                            "first (reset()), or register the reason "
                            "with `# owner-ok: <reason>`"))
                continue
            if not isinstance(node, ast.Call):
                continue
            t = call_tail(node)
            if t not in ("pop", "clear", "popitem"):
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            key = _container_key(node.func.value)
            if key not in OWNED_TABLES:
                continue
            if has_pragma(module, node.lineno, _PRAGMA):
                continue
            if t == "clear":
                # iterate-free-then-clear (reset) or capture-and-
                # return (PrefixPool.clear) both read the table first
                if _reads_table_before(fn, key, node.lineno):
                    continue
                findings.append(module.finding(
                    "LEAK004", node,
                    f"`{key}.clear()` drops every entry un-freed — "
                    "free or hand off the entries first (the reset()/"
                    "PrefixPool.clear() idioms), or register the "
                    "reason with `# owner-ok: <reason>`"))
                continue
            # pop/popitem: the removed value must be routed
            parent = module.parents.get(node)
            routed = False
            if isinstance(parent, ast.Call) and \
                    call_tail(parent) in model.helpers:
                routed = True
            elif isinstance(parent, (ast.Assign,)):
                names = [x.id for x in parent.targets
                         if isinstance(x, ast.Name)]
                if names:
                    sinks = _name_sinks(module, fn, names[0],
                                        model.helpers, set())
                    routed = bool(sinks)
            if not routed:
                findings.append(module.finding(
                    "LEAK004", node,
                    f"`{key}.pop(...)` discards a block table without "
                    "routing it through a free seam "
                    "(`_free_block_table`/`free_prefix`) — the "
                    "removed pages leak (the crash_rollback/abort/"
                    "drain seams must free what they remove)"))
    return findings


def run(ctx) -> List[Finding]:
    model = ownership_model(ctx)
    resolvable = set()
    for module in ctx.modules:
        if not _in_scope(module.rel):
            continue
        for fn in _fns(module):
            resolvable.add(fn.name)
    findings: List[Finding] = []
    for module in ctx.modules:
        rel = module.rel.replace("\\", "/")
        if _is_owner(rel) or not any(
                rel == p.rstrip("/") or rel.startswith(p)
                for p in _SCAN_PREFIXES):
            findings.extend(_leak001(ctx, module, model, resolvable))
            findings.extend(_leak002(ctx, module, model))
            findings.extend(_leak003(ctx, module, model))
        if _in_scope(rel) and any(t in module.text
                                  for t in OWNED_TABLES):
            findings.extend(_leak004(ctx, module, model))
    return findings


#: (rule, one-line contract, example) — rendered by `--rules-md`.
RULES = (
    ("LEAK001", "a pool `.allocate()` result that can escape its "
     "function without reaching an owned table, a free, or the "
     "caller — exception edges included (a raise-capable call between "
     "the allocation and its store, outside any try, loses the page)",
     "`block = pool.allocate(); validate(tok); table.append(block)` — "
     "validate() raising leaks the page"),
    ("LEAK002", "refcount-lifecycle balance: every `ref_count +=` "
     "destination container needs a statically-reachable free seam "
     "(the PrefixPool pin-forever class), and `ref_count = n` must "
     "only hit freshly-allocated blocks (the sliding-window clobber "
     "class)",
     "a prefix pin with no `free_prefix`, or `= num_seqs` on a "
     "window-reused block"),
    ("LEAK003", "use-after-free / double-free of a freed block name "
     "on a non-conflicting path: freeing again, re-storing, or "
     "mutating `ref_count` — reading `.block_number` after the free "
     "(the append_slot CoW idiom) is clean",
     "`pool.free(b)` twice on the same path"),
    ("LEAK004", "state-removal seams (crash_rollback/reincarnate/"
     "abort/drain cleanup) that `.pop`/`del`/`.clear`/rebind an owned "
     "block table without routing the entries through a free seam",
     "`self.block_tables.pop(seq_id)` discarding the table"),
)
