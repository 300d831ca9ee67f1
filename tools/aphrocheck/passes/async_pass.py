"""ASYNC pass: event-loop hygiene over the domain-classified call
graph.

The serving engine is two worlds sharing one process: the asyncio
event loop (frontends, RequestTracker, drain/reincarnation
supervisors) and the `run_in_executor` step thread. The loop world has
contracts of its own — nothing may block it, background tasks must not
swallow their exceptions, and loop acquisition must name the RUNNING
loop — which none of the kernel/engine-invariant passes could see
before the call graph learned execution domains (core.CallGraph
ensure_domains). Scope for every rule: `aphrodite_tpu/engine/`,
`aphrodite_tpu/endpoints/`, `aphrodite_tpu/processing/` (the layers
that execute on or next to the loop), plus explicitly-passed modules
outside the scanned roots (the seeded fixtures).

- ASYNC001: a blocking call — `time.sleep`, `subprocess.*`,
  `requests.*`/`urlopen`, `socket` connects, sync `open()` in a
  coroutine body, or `Future.result()` — in a function the domain
  classifier places on the EVENT LOOP (async defs and the sync
  helpers they call). One blocked coroutine stalls every stream,
  heartbeat, and health probe in the process. `fut.result()` is
  exempt when the same function awaited `asyncio.wait(...)` over that
  future first (the watchdog idiom: the future is resolved by the
  time it is read).
- ASYNC002: `create_task`/`ensure_future` whose task is neither
  stored nor given a done-callback (the bare-statement form). An
  unreferenced task can be garbage-collected mid-flight, and its
  exception is swallowed until interpreter shutdown — the
  fire-and-forget swallow.
- ASYNC003: `asyncio.get_event_loop()`. Deprecated since 3.10 and
  wrong in both worlds: on the loop it must be `get_running_loop()`,
  off it (a non-main thread without a set loop) it raises or —
  worse, historically — silently creates a SECOND loop that nothing
  runs. The engine is driven from worker threads in fleet mode, so
  this is a correctness rule, not a style rule.
- ASYNC004: an await point inside critical state — `await` under a
  held SYNC lock (`with ...lock:` — parks the coroutine while every
  other task that wants the lock deadlocks behind it; asyncio locks
  use `async with`), or a read of `self.X` followed by an `await`
  followed by a write of the same `self.X` (await-point TOCTOU: the
  loop runs OTHER tasks during the await, and the write commits a
  stale read). Flow-sensitive like FOLD001; reads/writes in branch
  arms that cannot coexist are not paired.

Escape hatch: `# async-ok: <reason>` on the flagged line (or the
contiguous comment block above) registers a reasoned exception in
source, same idiom as BP001's `# bounded-by:`.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from tools.aphrocheck.core import (EVENT_LOOP, Finding, Module,
                                   call_tail, dotted_name, has_pragma,
                                   paths_conflict, tail_name,
                                   walk_nodes)

#: Scope: the layers between a client connection and the step thread,
#: plus the fleet router — pure event-loop code where one blocked
#: coroutine stalls every proxied stream and health poll.
_HOT_PREFIXES = ("aphrodite_tpu/engine/", "aphrodite_tpu/endpoints/",
                 "aphrodite_tpu/processing/", "aphrodite_tpu/fleet/")

#: Everything the CLI normally scans; explicitly-passed files outside
#: these roots (the seeded fixtures) are treated as in-scope.
_SCAN_PREFIXES = ("aphrodite_tpu/", "benchmarks/", "bench.py")

_PRAGMA = "async-ok:"

#: Dotted-name prefixes/tails that block the calling thread.
_BLOCKING_DOTTED = {
    "time.sleep",
    "urllib.request.urlopen",
    "socket.create_connection",
}
_BLOCKING_HEADS = {
    "subprocess": {"run", "call", "check_call", "check_output",
                   "Popen", "getoutput", "getstatusoutput"},
    "requests": {"get", "post", "put", "patch", "delete", "head",
                 "request"},
}


def _in_scope(rel: str) -> bool:
    rel = rel.replace("\\", "/")
    if any(rel.startswith(p) for p in _HOT_PREFIXES):
        return True
    return not any(rel == p.rstrip("/") or rel.startswith(p)
                   for p in _SCAN_PREFIXES)


def _bare_imports(module: Module) -> Set[str]:
    """Names that are blocking when called bare (`from time import
    sleep`, `from subprocess import run`, ...)."""
    out: Set[str] = set()
    for node in module.nodes:
        if not isinstance(node, ast.ImportFrom):
            continue
        if node.module == "time":
            out |= {a.asname or a.name for a in node.names
                    if a.name == "sleep"}
        elif node.module == "subprocess":
            out |= {a.asname or a.name for a in node.names
                    if a.name in _BLOCKING_HEADS["subprocess"]}
        elif node.module == "asyncio":
            # tracked separately for ASYNC003
            pass
    return out


def _awaited_wait_names(fn: ast.AST) -> Set[str]:
    """Names passed into `asyncio.wait(...)` / `asyncio.wait_for(...)`
    within `fn` — futures known resolved before `.result()` reads."""
    out: Set[str] = set()
    for node in walk_nodes(fn):
        if isinstance(node, ast.Call) and \
                dotted_name(node.func) in ("asyncio.wait",
                                           "asyncio.wait_for"):
            for arg in node.args:
                for inner in walk_nodes(arg):
                    if isinstance(inner, ast.Name):
                        out.add(inner.id)
    return out


def _blocking_reason(call: ast.Call, bare: Set[str],
                     owner: ast.AST,
                     wait_names: Set[str]) -> Optional[str]:
    name = dotted_name(call.func) or ""
    if name in _BLOCKING_DOTTED:
        return name
    head, _, tail = name.rpartition(".")
    if head in _BLOCKING_HEADS and tail in _BLOCKING_HEADS[head]:
        return name
    if isinstance(call.func, ast.Name) and call.func.id in bare:
        return call.func.id
    if tail == "result" and isinstance(call.func, ast.Attribute):
        recv = dotted_name(call.func.value)
        if recv is not None and recv.split(".")[0] in wait_names:
            return None       # resolved via awaited asyncio.wait
        return f"{recv or '<future>'}.result()"
    if name == "open" and isinstance(owner, ast.AsyncFunctionDef):
        return "open() (sync file I/O in a coroutine body)"
    return None


def _task_is_consumed(module: Module, call: ast.Call) -> bool:
    """A create_task/ensure_future result is consumed unless the call
    is a bare expression statement (not assigned, not passed on, not
    chained into .add_done_callback)."""
    parent = module.parents.get(call)
    return not isinstance(parent, ast.Expr)


def _imports_bare_get_event_loop(module: Module) -> bool:
    for node in module.nodes:
        if isinstance(node, ast.ImportFrom) and \
                node.module == "asyncio" and \
                any(a.name == "get_event_loop" and a.asname is None
                    for a in node.names):
            return True
    return False


def _looks_like_lock(node: ast.AST) -> bool:
    """A `with` context expression that names a sync lock: a dotted
    name whose tail contains 'lock', or a direct threading
    Lock/RLock construction."""
    if isinstance(node, ast.Call):
        return tail_name(node.func) in ("Lock", "RLock")
    name = tail_name(node)
    return name is not None and "lock" in name.lower()


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and \
            node.value.id == "self":
        return node.attr
    return None


def _toctou_findings(module: Module, fn: ast.AsyncFunctionDef
                     ) -> List[Finding]:
    """Read of self.X -> await -> write of self.X within one
    coroutine (branch-compatible occurrences only)."""
    # only nodes whose nearest enclosing function IS this coroutine
    # (a nested def's awaits/attribute traffic is its own analysis)
    direct = [n for n in walk_nodes(fn)
              if module.enclosing_function(n) is fn]
    awaits = [n for n in direct if isinstance(n, ast.Await)]
    if not awaits:
        return []
    reads: Dict[str, List[ast.AST]] = {}
    writes: Dict[str, List[ast.AST]] = {}
    for node in direct:
        attr = _self_attr(node)
        if attr is None:
            continue
        if isinstance(node.ctx, ast.Load):
            reads.setdefault(attr, []).append(node)
        elif isinstance(node.ctx, ast.Store):
            writes.setdefault(attr, []).append(node)
    out: List[Finding] = []
    for attr, wlist in writes.items():
        for w in wlist:
            hazard = None
            for r in reads.get(attr, ()):
                if r.lineno >= w.lineno:
                    continue
                if paths_conflict(module.branch_path(r),
                                  module.branch_path(w)):
                    continue
                for a in awaits:
                    if r.lineno < a.lineno <= w.lineno and \
                            not paths_conflict(
                                module.branch_path(a),
                                module.branch_path(w)):
                        hazard = (r, a)
                        break
                if hazard:
                    break
            if hazard and not has_pragma(module, w.lineno, _PRAGMA):
                out.append(module.finding(
                    "ASYNC004", w,
                    f"self.{attr} is read (line "
                    f"{hazard[0].lineno}), awaited across (line "
                    f"{hazard[1].lineno}), then written: the loop "
                    "runs other tasks during the await, so the "
                    "write commits a stale read (await-point "
                    "TOCTOU) — re-read after the await or restructure"))
                break       # one finding per attribute per function
    return out


def run(ctx) -> List[Finding]:
    findings: List[Finding] = []
    cg = ctx.call_graph
    for module in ctx.modules:
        if not _in_scope(module.rel):
            continue
        bare = _bare_imports(module)
        bare_loop = _imports_bare_get_event_loop(module)
        wait_names_cache: Dict[int, Set[str]] = {}
        for call in module.calls:
            name = dotted_name(call.func) or ""
            tail = call_tail(call)
            owner = cg.owner_function(module, call)
            # ASYNC003: wrong loop-acquisition API, any domain
            if name == "asyncio.get_event_loop" or \
                    (bare_loop and name == "get_event_loop"):
                if not has_pragma(module, call.lineno, _PRAGMA):
                    findings.append(module.finding(
                        "ASYNC003", call,
                        "asyncio.get_event_loop() is deprecated and "
                        "grabs the wrong loop when the engine is "
                        "driven from a non-main thread; use "
                        "asyncio.get_running_loop() (coroutines/"
                        "callbacks) or asyncio.run (entry points)"))
                continue
            # ASYNC002: fire-and-forget task swallow, any domain
            if tail in ("create_task", "ensure_future"):
                if not _task_is_consumed(module, call) and \
                        not has_pragma(module, call.lineno, _PRAGMA):
                    findings.append(module.finding(
                        "ASYNC002", call,
                        f"{tail}(...) result is neither stored nor "
                        "given a done-callback: the task can be "
                        "garbage-collected mid-flight and its "
                        "exception is silently swallowed — retain it "
                        "and attach an exception-logging callback"))
                continue
            # ASYNC001: blocking call in the EVENT_LOOP domain
            if owner is None or \
                    EVENT_LOOP not in cg.domains_of(owner):
                continue
            if id(owner) not in wait_names_cache:
                wait_names_cache[id(owner)] = _awaited_wait_names(owner)
            reason = _blocking_reason(call, bare, owner,
                                      wait_names_cache[id(owner)])
            if reason is not None and \
                    not has_pragma(module, call.lineno, _PRAGMA):
                findings.append(module.finding(
                    "ASYNC001", call,
                    f"blocking call {reason} in event-loop domain: "
                    "one blocked coroutine stalls every stream, "
                    "heartbeat and health probe — await an async "
                    "equivalent or run_in_executor it"))
        # ASYNC004: await under a sync lock / await-point TOCTOU
        for node in module.nodes:
            if isinstance(node, ast.With):
                owner = cg.owner_function(module, node)
                if owner is None or not isinstance(
                        owner, ast.AsyncFunctionDef):
                    continue
                locky = any(_looks_like_lock(item.context_expr)
                            for item in node.items)
                if locky and any(isinstance(n, ast.Await)
                                 for n in walk_nodes(node)) and \
                        not has_pragma(module, node.lineno, _PRAGMA):
                    findings.append(module.finding(
                        "ASYNC004", node,
                        "await inside a held sync lock: the coroutine "
                        "parks holding the lock and every other task "
                        "that wants it deadlocks behind the loop — "
                        "use asyncio.Lock with `async with`, or drop "
                        "the lock across the await"))
            elif isinstance(node, ast.AsyncFunctionDef):
                findings.extend(_toctou_findings(module, node))
    return findings


#: (rule, one-line contract, example) — rendered by `--rules-md`.
RULES = (
    ("ASYNC001", "blocking call (`time.sleep`, `subprocess.*`, sync "
     "HTTP/file/socket I/O, `Future.result()`) in a function the "
     "domain classifier places on the EVENT LOOP, within the "
     "`engine/`/`endpoints/`/`processing/`/`fleet/` scope — one "
     "blocked coroutine stalls every stream and health probe "
     "(`fut.result()` after an awaited `asyncio.wait` over it is "
     "recognized clean)",
     "`time.sleep(0.5)` in a helper called from `engine_step`"),
    ("ASYNC002", "`create_task`/`ensure_future` whose task is neither "
     "stored nor given a done-callback — the task can be GC'd "
     "mid-flight and its exception is swallowed",
     "`loop.create_task(_drain_then_exit(engine))` as a bare "
     "statement"),
    ("ASYNC003", "`asyncio.get_event_loop()` in the serving layers — "
     "deprecated, and grabs the wrong loop off the main thread; use "
     "`get_running_loop()`",
     "`asyncio.get_event_loop().run_in_executor(...)` in a coroutine"),
    ("ASYNC004", "an await point inside critical state: `await` under "
     "a held sync lock, or read-of-`self.X` → `await` → "
     "write-of-`self.X` (await-point TOCTOU; flow- and branch-"
     "sensitive)",
     "`seen = self.inflight` / `await ...` / `self.inflight = "
     "seen + 1`"),
)
