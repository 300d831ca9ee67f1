"""Shared infrastructure for the aphrocheck passes.

Everything here is pure-AST: the checker never imports the code it
analyzes (so it runs in milliseconds under JAX_PLATFORMS=cpu with no
TPU, and a broken module under analysis cannot break the analyzer —
only a SyntaxError can, which is itself reported as a finding).

Key pieces:

- Finding / Allowlist: stable-rule-ID findings and the checked-in
  exception list. Allowlist entries pin (rule, path, line-content
  substring) rather than line numbers, so they survive unrelated
  edits; entries that match nothing are STALE and reported (and the
  tier-1 test fails on them).
- Module: one parsed source file plus parent links and the
  enclosing-scope / enclosing-branch maps the passes share.
- Branch paths: every AST node carries the chain of (if-node, arm)
  decisions above it. Two nodes CONFLICT when they sit in different
  arms of the same `if` — passes use this to avoid pairing values
  that can never coexist (e.g. the ragged vs classic grid-spec arms
  of paged_attention).
- Interval: [lo, hi] integer bounds with a small abstract evaluator
  (literals, names via branch-aware constant propagation, arithmetic,
  min/max, literal-tuple generators) used by the VMEM/DMA/REF passes.
- CallGraph: lightweight same-package call graph — every module-level
  def plus every direct call and `functools.partial` binding of it —
  so a helper parameter (`n_slots`, `page_size`, a kernel's ring
  depth) resolves to the expressions its callers pass. The evaluator
  consults it when a name is a parameter of the scope under analysis,
  which is what lets the passes see through the helper-wrapped
  pallas_call idiom (one `_stream_call`-style launcher shared by
  several wrappers) instead of stopping at the function boundary.
- Execution domains: the call graph also tags every function with the
  WORLD that executes it — EVENT_LOOP (an `async def` body, or a
  callback handed to `create_task`/`call_soon`/`add_done_callback`/
  signal handlers, plus everything those call), STEP_THREAD (callables
  handed to `run_in_executor`/`Executor.submit`/`Thread(target=)`,
  plus everything those call), or both. The ASYNC and RACE passes
  reason about which world executes a statement: a blocking call only
  matters on the loop, an unguarded scheduler commit only matters off
  it, and a `self.` attribute written in BOTH worlds is a data race
  unless something documents why it is not. Resolution is by tail
  name (over-approximate for same-named methods, like the rest of the
  graph); indirect dispatch through stored callables is invisible, so
  domains under-approximate reachability — rules built on them can
  miss, but what they flag is real.
"""
from __future__ import annotations

import ast
import collections
import dataclasses
import json
import os
import weakref
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Scanned roots, relative to the repo root. Bench harnesses are
#: scanned too so bench-only flags stay registered (FLAG004/005).
SCAN_ROOTS = ("aphrodite_tpu", "bench.py", "benchmarks")

#: The registry module — exempt from FLAG001/002/003 (it IS the one
#: place raw os.environ reads are allowed).
FLAGS_MODULE = os.path.join("aphrodite_tpu", "common", "flags.py")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str          # stable ID, e.g. "FLAG001"
    path: str          # repo-relative path
    line: int
    message: str

    def render(self) -> str:
        return f"{self.rule} {self.path}:{self.line} {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


class Module:
    """One parsed source file with parent/scope/branch maps."""

    def __init__(self, path: str, rel: str, text: str,
                 tree: ast.AST) -> None:
        self.path = path
        self.rel = rel
        self.text = text
        self.lines = text.splitlines()
        self.tree = tree
        # One BFS builds parents, nodes, AND the call list (same
        # traversal order as ast.walk; walking via ast.walk and then
        # re-iterating children doubled the child enumeration, which
        # dominated the sweep's runtime budget).
        parents: Dict[ast.AST, ast.AST] = {}
        nodes: List[ast.AST] = [tree]
        calls: List[ast.Call] = []
        queue = collections.deque((tree,))
        while queue:
            node = queue.popleft()
            for child in ast.iter_child_nodes(node):
                parents[child] = node
                nodes.append(child)
                if isinstance(child, ast.Call):
                    calls.append(child)
                queue.append(child)
        self.parents = parents
        self.nodes = nodes
        #: every ast.Call in the module — the whole-tree walk most
        #: passes need, done once
        self.calls = calls
        # per-scope memoized walks (the evaluator consults these on
        # every name lookup; rebuilding them per lookup dominated the
        # 2 s runtime budget)
        self._assign_idx: Dict[int, Dict[str, List[ast.AST]]] = {}
        self._mutated_idx: Dict[int, set] = {}
        self._def_idx: Dict[int, Dict[str, List[ast.AST]]] = {}

    def def_index(self, scope: Optional[ast.AST]
                  ) -> Dict[str, List[ast.AST]]:
        """name -> FunctionDefs within `scope` (module when None)."""
        key = id(scope) if scope is not None else 0
        idx = self._def_idx.get(key)
        if idx is None:
            idx = {}
            root = scope if scope is not None else self.tree
            for node in ast.walk(root):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    idx.setdefault(node.name, []).append(node)
            self._def_idx[key] = idx
        return idx

    def assign_index(self, scope: Optional[ast.AST]
                     ) -> Dict[str, List[ast.AST]]:
        """name -> value nodes of plain Assigns within `scope`
        (module tree when None), built once per scope."""
        key = id(scope) if scope is not None else 0
        idx = self._assign_idx.get(key)
        if idx is None:
            idx = {}
            root = scope if scope is not None else self.tree
            for node in ast.walk(root):
                if isinstance(node, ast.Assign):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            idx.setdefault(tgt.id, []).append(
                                node.value)
            self._assign_idx[key] = idx
        return idx

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1]
        return ""

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        return Finding(rule, self.rel, getattr(node, "lineno", 0),
                       message)

    # -- scopes ------------------------------------------------------

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        """Nearest FunctionDef/AsyncFunctionDef/Lambda above node."""
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = self.parents.get(cur)
        return None

    def top_level_function(self, node: ast.AST) -> Optional[ast.AST]:
        """Outermost function containing node (kernel bodies nest
        closures under pl.when — DMA matching aggregates at this
        granularity)."""
        top = None
        cur = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                top = cur
            cur = self.parents.get(cur)
        return top

    def at_module_level(self, node: ast.AST) -> bool:
        """True when node executes at import time (module or class
        body; any enclosing function defers execution)."""
        return self.enclosing_function(node) is None

    # -- branch paths ------------------------------------------------

    def branch_path(self, node: ast.AST) -> Tuple[Tuple[int, str], ...]:
        """((id(if_node), arm), ...) from outermost to innermost."""
        path: List[Tuple[int, str]] = []
        cur = node
        parent = self.parents.get(cur)
        while parent is not None:
            if isinstance(parent, (ast.If, ast.IfExp)):
                if cur in getattr(parent, "body", []) or \
                        cur is getattr(parent, "body", None):
                    path.append((id(parent), "then"))
                elif cur in getattr(parent, "orelse", []) or \
                        cur is getattr(parent, "orelse", None):
                    path.append((id(parent), "else"))
            cur, parent = parent, self.parents.get(parent)
        path.reverse()
        return tuple(path)


def has_pragma(module: "Module", lineno: int, pragma: str) -> bool:
    """Whether `pragma` appears on the given line or in the contiguous
    comment block directly above it — the registration idiom shared by
    BP001's `# bounded-by:` and the perf passes' `# perf-known:`."""
    if pragma in module.line_text(lineno):
        return True
    line = lineno - 1
    while line >= 1:
        text = module.line_text(line).strip()
        if not text.startswith("#"):
            return False
        if pragma in text:
            return True
        line -= 1
    return False


def paths_conflict(a: Sequence[Tuple[int, str]],
                   b: Sequence[Tuple[int, str]]) -> bool:
    """Two branch paths conflict when they take different arms of the
    same `if` — such nodes can never be live together."""
    arms_a = dict(a)
    for if_id, arm in b:
        if arms_a.get(if_id, arm) != arm:
            return True
    return False


#: Parsed-module memo keyed by (abs path, mtime_ns, size): parsing and
#: the parent/child index build dominate a sweep, and one process
#: commonly runs several (a --changed subset then the full gate, the
#: test suite's dozens of build_context calls, the budget's
#: best-of-3). Keying on stat() makes edits invalidate naturally.
_MODULE_CACHE: Dict[Tuple[str, str, int, int], Module] = {}


def parse_file(path: str, rel: str) -> Tuple[Optional[Module],
                                             Optional[Finding]]:
    try:
        st = os.stat(path)
        key = (path, rel, st.st_mtime_ns, st.st_size)
    except OSError:
        key = None
    if key is not None:
        cached = _MODULE_CACHE.get(key)
        if cached is not None:
            return cached, None
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return None, Finding("PARSE", rel, e.lineno or 0,
                             f"syntax error: {e.msg}")
    module = Module(path, rel, text, tree)
    if key is not None:
        _MODULE_CACHE[key] = module
    return module, None


def collect_files(root: str = REPO_ROOT,
                  roots: Sequence[str] = SCAN_ROOTS) -> List[str]:
    """Repo-relative paths of every scanned .py file, sorted."""
    out: List[str] = []
    for entry in roots:
        full = os.path.join(root, entry)
        if os.path.isfile(full):
            out.append(entry)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__",)]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.relpath(
                        os.path.join(dirpath, fn), root))
    return sorted(out)


def load_modules(root: str, rels: Iterable[str]
                 ) -> Tuple[List[Module], List[Finding]]:
    modules, findings = [], []
    for rel in rels:
        mod, err = parse_file(os.path.join(root, rel), rel)
        if err is not None:
            findings.append(err)
        else:
            modules.append(mod)
    return modules, findings


# -- allowlist --------------------------------------------------------

@dataclasses.dataclass
class AllowEntry:
    rule: str
    path: str
    contains: str      # substring of the source line the finding is on
    reason: str
    hits: int = 0

    def matches(self, finding: Finding, line_text: str) -> bool:
        return (self.rule == finding.rule and
                self.path == finding.path and
                self.contains in line_text)


class Allowlist:
    """Checked-in intentional exceptions. JSON list of
    {rule, path, contains, reason}; `contains` pins the source line's
    content (not its number), so entries go stale — and are reported —
    when the code they covered changes."""

    def __init__(self, entries: List[AllowEntry]) -> None:
        self.entries = entries

    @classmethod
    def load(cls, path: str) -> "Allowlist":
        if not os.path.exists(path):
            return cls([])
        with open(path, "r", encoding="utf-8") as f:
            raw = json.load(f)
        return cls([AllowEntry(e["rule"], e["path"], e["contains"],
                               e.get("reason", "")) for e in raw])

    def suppresses(self, finding: Finding, line_text: str) -> bool:
        for entry in self.entries:
            if entry.matches(finding, line_text):
                entry.hits += 1
                return True
        return False

    def stale_entries(self) -> List[AllowEntry]:
        return [e for e in self.entries if e.hits == 0]


# -- small AST helpers ------------------------------------------------

def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Optional[str]:
    """Dotted name of a call's callee ('pltpu.make_async_copy')."""
    return dotted_name(call.func)


def tail_name(node: ast.AST) -> Optional[str]:
    """Last attribute segment ('make_async_copy' of any x.y.z chain)."""
    name = dotted_name(node)
    return name.rsplit(".", 1)[-1] if name else None


def call_tail(call: ast.Call) -> Optional[str]:
    """Tail name of a call's callee, robust to non-Name receivers:
    `asyncio.get_running_loop().run_in_executor(...)` has a Call at
    the base of its attribute chain (dotted_name sees nothing), but
    the method name is still the Attribute's own attr."""
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    return tail_name(call.func)


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def int_const(node: ast.AST) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


def keyword_arg(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


#: walk_nodes() memo: the passes walk the same few thousand function
#: bodies again and again (20,000 walks a sweep for their calls alone
#: were a quarter of its runtime budget); a tree is never mutated
#: after parsing, and the weak keys die with their Module
_NODES_UNDER: "weakref.WeakKeyDictionary[ast.AST, Tuple[ast.AST, ...]]" \
    = weakref.WeakKeyDictionary()
_CALLS_UNDER: "weakref.WeakKeyDictionary[ast.AST, Tuple[ast.Call, ...]]" \
    = weakref.WeakKeyDictionary()


def walk_nodes(root: ast.AST) -> Tuple[ast.AST, ...]:
    """`ast.walk(root)`, in its order, walked once a root."""
    nodes = _NODES_UNDER.get(root)
    if nodes is None:
        nodes = tuple(ast.walk(root))
        _NODES_UNDER[root] = nodes
    return nodes


def iter_calls(root: ast.AST) -> Iterable[ast.Call]:
    calls = _CALLS_UNDER.get(root)
    if calls is None:
        calls = tuple(node for node in walk_nodes(root)
                      if isinstance(node, ast.Call))
        _CALLS_UNDER[root] = calls
    return calls


def assignments_of(scope: ast.AST, name: str,
                   module: Optional[Module] = None) -> List[ast.AST]:
    """Value nodes assigned to `name` anywhere in `scope` (plain
    Assign targets only; tuple-unpack yields the whole call value,
    marked by wrapping position). With a `module`, the per-scope
    index is memoized."""
    if module is not None:
        return list(module.assign_index(scope).get(name, ()))
    out: List[ast.AST] = []
    for node in ast.walk(scope):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    out.append(node.value)
    return out


# -- same-package call graph ------------------------------------------

#: Execution-domain tags (CallGraph.domains_of).
EVENT_LOOP = "event_loop"
STEP_THREAD = "step_thread"

#: Callables that schedule their argument ONTO the asyncio event loop:
#: tail name -> positional index of the callback/coroutine argument.
_LOOP_SINKS = {
    "create_task": 0, "ensure_future": 0, "run_until_complete": 0,
    "run_coroutine_threadsafe": 0, "call_soon": 0,
    "call_soon_threadsafe": 0, "add_done_callback": 0,
    "call_later": 1, "call_at": 1, "add_signal_handler": 1,
}

#: Callables that move their argument onto a worker thread (the step
#: thread world): tail name -> positional index of the callable.
#: Thread(target=...) is handled separately (keyword form).
_THREAD_SINKS = {"run_in_executor": 1, "submit": 0}


@dataclasses.dataclass
class ParamBinding:
    """One caller-site expression bound to a callee parameter."""
    module: Module
    scope: Optional[ast.AST]     # caller's enclosing function
    node: ast.AST                # the argument expression


class CallGraph:
    """Defs and call-site argument bindings across the scanned modules.

    Resolution is BY NAME (tail name of the callee), which is exact
    for this package's flat module-level helpers and over-approximate
    for same-named methods — over-approximation joins intervals, so
    bounds stay sound in the join-to-UNKNOWN direction. Both direct
    calls and `functools.partial(fn, ...)` keyword/positional
    bindings are recorded; `self`/`cls` receivers are skipped when a
    method is invoked through an attribute."""

    def __init__(self, modules: Sequence[Module]) -> None:
        self.defs: Dict[str, List[Tuple[Module, ast.AST]]] = {}
        self._bindings: Dict[str, Dict[str, List[ParamBinding]]] = {}
        self._modules = list(modules)
        self._domains: Optional[Dict[int, set]] = None
        for module in modules:
            for node in module.nodes:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    self.defs.setdefault(node.name, []).append(
                        (module, node))
        for module in modules:
            for call in module.calls:
                name = tail_name(call.func)
                if name == "partial" and call.args:
                    target = tail_name(call.args[0])
                    if target in self.defs:
                        self._record(target, module, call,
                                     arg_offset=1)
                elif name in self.defs:
                    self._record(name, module, call, arg_offset=0)

    def _record(self, target: str, module: Module, call: ast.Call,
                arg_offset: int) -> None:
        _, fn = self.defs[target][0]
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        if params and params[0] in ("self", "cls") and \
                isinstance(call.func, ast.Attribute):
            params = params[1:]
        scope = module.top_level_function(call)
        per = self._bindings.setdefault(target, {})
        for i, arg in enumerate(call.args[arg_offset:]):
            if isinstance(arg, ast.Starred):
                break
            if i < len(params):
                per.setdefault(params[i], []).append(
                    ParamBinding(module, scope, arg))
        for kw in call.keywords:
            if kw.arg is not None:
                per.setdefault(kw.arg, []).append(
                    ParamBinding(module, scope, kw.value))

    def param_values(self, fn_name: str, param: str
                     ) -> List[ParamBinding]:
        return self._bindings.get(fn_name, {}).get(param, [])

    def functions_named(self, name: str
                        ) -> List[Tuple[Module, ast.AST]]:
        return self.defs.get(name, [])

    # -- execution domains (the two-world classification) -------------

    @staticmethod
    def owner_function(module: Module, node: ast.AST
                       ) -> Optional[ast.AST]:
        """Nearest enclosing def (lambdas skipped: their bodies run
        where the surrounding code hands them off, which the sinks
        below already model for the cases we care about)."""
        cur = module.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = module.parents.get(cur)
        return None

    @staticmethod
    def _callback_names(node: Optional[ast.AST]) -> List[str]:
        """Function names a callback argument may refer to: a bare
        reference (`self.engine.step`), a coroutine invocation
        (`self.run_engine_loop()`), or a functools.partial of either."""
        if node is None:
            return []
        if isinstance(node, ast.Call):
            if tail_name(node.func) == "partial" and node.args:
                return CallGraph._callback_names(node.args[0])
            name = tail_name(node.func)
            return [name] if name else []
        name = tail_name(node)
        return [name] if name else []

    @staticmethod
    def _call_arity(call: ast.Call) -> Optional[int]:
        """Positional+keyword argument count, or None when the call
        spreads (*args/**kwargs) and arity cannot be known."""
        for arg in call.args:
            if isinstance(arg, ast.Starred):
                return None
        for kw in call.keywords:
            if kw.arg is None:
                return None
        return len(call.args) + len(call.keywords)

    @staticmethod
    def _def_accepts(fn: ast.AST, n: Optional[int],
                     method_call: bool) -> bool:
        """Whether a def could be the target of a call with `n`
        arguments — the cheap arity filter that keeps same-named
        methods of unrelated classes (`AsyncStream.put(item)` vs
        `LRUCache.put(key, value)`) from cross-polluting domains."""
        if n is None:
            return True
        a = fn.args
        pos = list(a.posonlyargs) + list(a.args)
        required = len(pos) - len(a.defaults)
        maxn = len(pos) + len(a.kwonlyargs)
        if method_call and pos and pos[0].arg in ("self", "cls"):
            required -= 1
            maxn -= 1
        required += sum(1 for d in a.kw_defaults if d is None)
        if a.vararg is not None or a.kwarg is not None:
            maxn = len(pos) + len(a.kwonlyargs) + 1_000_000
        return max(0, required) <= n <= maxn

    @staticmethod
    def _is_awaited(module: Module, call: ast.Call) -> bool:
        """Whether a call's result is consumed as an awaitable
        (`await f()`, `async for ... in f()`, `async with f()`)."""
        parent = module.parents.get(call)
        if isinstance(parent, ast.Await):
            return True
        if isinstance(parent, ast.AsyncFor) and parent.iter is call:
            return True
        if isinstance(parent, ast.withitem) and \
                parent.context_expr is call:
            grand = module.parents.get(parent)
            return isinstance(grand, ast.AsyncWith)
        return False

    def _edge_targets(self, name: str, arity: Optional[int],
                      awaited: bool, method_call: bool) -> list:
        """Defs a call edge may reach. Two disambiguators prune
        same-name collisions: arity (the callee must accept the call),
        and sync/async kind — an awaited call runs async defs, a plain
        call runs sync defs (calling a coroutine function without
        awaiting only creates the coroutine; the loop sinks handle the
        hand-off forms). Either filter is skipped when it would prune
        ALL candidates (an unambiguous name resolves as before)."""
        cands = self.defs.get(name, [])
        by_arity = [(m, f) for m, f in cands
                    if self._def_accepts(f, arity, method_call)]
        if by_arity:
            cands = by_arity
        async_defs = [(m, f) for m, f in cands
                      if isinstance(f, ast.AsyncFunctionDef)]
        sync_defs = [(m, f) for m, f in cands
                     if not isinstance(f, ast.AsyncFunctionDef)]
        if async_defs and sync_defs:
            return async_defs if awaited else sync_defs
        return cands

    def ensure_domains(self) -> Dict[int, set]:
        """id(def-node) -> {EVENT_LOOP, STEP_THREAD} subset, computed
        once: seeds (async defs, loop-sink callbacks, thread-sink
        callables) propagated through the name-resolved call edges.
        STEP_THREAD never propagates INTO an async def (sync code
        calling a coroutine function only creates the coroutine)."""
        if self._domains is not None:
            return self._domains
        domains: Dict[int, set] = {}
        # owner id -> [(callee name, arity, awaited, method_call)]
        edges: Dict[int, list] = {}
        work: List[Tuple[ast.AST, str]] = []

        def seed(fn: ast.AST, domain: str) -> None:
            if domain == STEP_THREAD and \
                    isinstance(fn, ast.AsyncFunctionDef):
                return
            tagged = domains.setdefault(id(fn), set())
            if domain not in tagged:
                tagged.add(domain)
                work.append((fn, domain))

        for module in self._modules:
            for node in module.nodes:
                if isinstance(node, ast.AsyncFunctionDef):
                    seed(node, EVENT_LOOP)
            for call in module.calls:
                owner = self.owner_function(module, call)
                name = call_tail(call)
                if owner is not None and name in self.defs:
                    edges.setdefault(id(owner), []).append(
                        (name, self._call_arity(call),
                         self._is_awaited(module, call),
                         isinstance(call.func, ast.Attribute)))
                # sink seeds: the handed-off callable changes worlds
                targets: List[str] = []
                domain = None
                if name in _LOOP_SINKS:
                    idx = _LOOP_SINKS[name]
                    if idx < len(call.args):
                        targets = self._callback_names(call.args[idx])
                        domain = EVENT_LOOP
                elif name in _THREAD_SINKS:
                    idx = _THREAD_SINKS[name]
                    if idx < len(call.args):
                        targets = self._callback_names(call.args[idx])
                        domain = STEP_THREAD
                elif name == "Thread":
                    targets = self._callback_names(
                        keyword_arg(call, "target"))
                    domain = STEP_THREAD
                for target in targets:
                    for _, fn in self.defs.get(target, ()):
                        seed(fn, domain)

        while work:
            fn, domain = work.pop()
            for name, arity, awaited, meth in edges.get(id(fn), ()):
                for _, callee_fn in self._edge_targets(
                        name, arity, awaited, meth):
                    seed(callee_fn, domain)
        self._domains = domains
        return domains

    def domains_of(self, fn: ast.AST) -> frozenset:
        """Execution domains of one def node (empty = unreachable from
        any seed — the rules built on domains stay silent there)."""
        return frozenset(self.ensure_domains().get(id(fn), ()))


# -- integer interval evaluation (VMEM pass) --------------------------

INF = float("inf")


@dataclasses.dataclass(frozen=True)
class Interval:
    lo: float
    hi: float

    @property
    def exact(self) -> Optional[int]:
        if self.lo == self.hi and self.lo != INF:
            return int(self.lo)
        return None


UNKNOWN = Interval(1, INF)   # shape dims are >= 1


def _join(a: Interval, b: Interval) -> Interval:
    return Interval(min(a.lo, b.lo), max(a.hi, b.hi))


class IntervalEvaluator:
    """Branch-aware [lo, hi] bounds for integer shape expressions.

    Scope: one function (plus module-level constants). Names resolve
    through plain assignments; a name reassigned via AugAssign or in a
    loop is UNKNOWN (sound: we never narrow a value we cannot track).
    Flag reads (`flags.get_int(...)`) resolve to their registry/call-
    site default — the analysis states its assumption as "flags at
    defaults" rather than treating every knob as unbounded.

    `bindings` pins additional names to exact values BEFORE any source
    resolution (they shadow locals and parameters alike). The roofline
    calibration hook uses this: `profile_step.py --only roofline`
    computes the real tile geometry at a bench shape and asks the
    static estimator for bytes/flops at those concrete values, so the
    same AST walk serves both the lint-time bound and the
    measured-vs-estimated drift table.

    With a `call_graph`, a name that is a PARAMETER of the scope
    function joins the intervals of every caller-site binding
    (including functools.partial keywords), each evaluated in its own
    caller's scope — depth-capped, and UNKNOWN when no binding is
    found (dynamic dispatch must not produce narrow bounds).
    """

    _MAX_CALLER_DEPTH = 3

    def __init__(self, module: Module, scope: Optional[ast.AST],
                 flag_defaults: Optional[Dict[str, int]] = None,
                 call_graph: Optional[CallGraph] = None,
                 _depth: int = 0,
                 bindings: Optional[Dict[str, int]] = None) -> None:
        self.module = module
        self.scope = scope
        self.flag_defaults = dict(flag_defaults or {})
        if bindings:
            self.flag_defaults.update(bindings)
        self.call_graph = call_graph
        self._depth = _depth
        self._mutated = self._collect_mutated()
        self._stack: List[str] = []    # recursion guard

    def _collect_mutated(self) -> set:
        # One module-wide walk, cached per module: every scope is a
        # subtree of module.tree, so the module walk already covers it
        # (re-walking per evaluator dominated the 2 s runtime budget).
        cached = self.module._mutated_idx.get(0)
        if cached is not None:
            return cached
        bad = set()
        for node in ast.walk(self.module.tree):
            if isinstance(node, ast.AugAssign) and \
                    isinstance(node.target, ast.Name):
                bad.add(node.target.id)
            elif isinstance(node, (ast.For, ast.While)):
                for inner in ast.walk(node):
                    if isinstance(inner, (ast.Assign, ast.AugAssign)):
                        tgts = inner.targets if isinstance(
                            inner, ast.Assign) else [inner.target]
                        for t in tgts:
                            if isinstance(t, ast.Name):
                                bad.add(t.id)
        self.module._mutated_idx[0] = bad
        return bad

    def eval(self, node: ast.AST,
             at: Optional[ast.AST] = None) -> Interval:
        """Bounds of `node`; `at` anchors branch-compatibility (default:
        the node itself)."""
        at = at if at is not None else node
        if isinstance(node, ast.Constant):
            v = int_const(node)
            return Interval(v, v) if v is not None else UNKNOWN
        if isinstance(node, ast.Name):
            return self._eval_name(node.id, at)
        if isinstance(node, ast.BinOp):
            return self._eval_binop(node, at)
        if isinstance(node, ast.IfExp):
            return _join(self.eval(node.body, at),
                         self.eval(node.orelse, at))
        if isinstance(node, ast.Call):
            return self._eval_call(node, at)
        if isinstance(node, ast.UnaryOp) and \
                isinstance(node.op, ast.USub):
            inner = self.eval(node.operand, at)
            return Interval(-inner.hi, -inner.lo)
        return UNKNOWN

    def _eval_name(self, name: str, at: ast.AST) -> Interval:
        if name in self._stack:
            return UNKNOWN
        # explicit bindings / flag defaults win over the mutated-name
        # bailout: a caller pinning `block_n` (the roofline
        # calibration hook) means THAT value, even though the sizing
        # helper reassigns the same name in a loop somewhere.
        if name in self.flag_defaults:
            v = self.flag_defaults[name]
            return Interval(v, v)
        if name in self._mutated:
            return UNKNOWN
        sources: List[ast.AST] = []
        if self.scope is not None:
            sources.extend(assignments_of(self.scope, name,
                                          self.module))
        if not sources:
            # module-level constant (e.g. _WB_SLOTS = 8)
            for stmt in self.module.tree.body:
                if isinstance(stmt, ast.Assign):
                    for tgt in stmt.targets:
                        if isinstance(tgt, ast.Name) and tgt.id == name:
                            sources.append(stmt.value)
        if not sources:
            return self._eval_param(name)
        at_path = self.module.branch_path(at)
        result: Optional[Interval] = None
        self._stack.append(name)
        try:
            for value in sources:
                if paths_conflict(at_path,
                                  self.module.branch_path(value)):
                    continue
                iv = self.eval(value, value)
                result = iv if result is None else _join(result, iv)
        finally:
            self._stack.pop()
        return result if result is not None else UNKNOWN

    def _eval_param(self, name: str) -> Interval:
        """Caller-site bounds for a parameter of the scope function."""
        if self.call_graph is None or \
                self._depth >= self._MAX_CALLER_DEPTH or \
                not isinstance(self.scope, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)):
            return UNKNOWN
        params = {a.arg for a in (self.scope.args.posonlyargs +
                                  self.scope.args.args +
                                  self.scope.args.kwonlyargs)}
        if name not in params:
            return UNKNOWN
        bindings = self.call_graph.param_values(self.scope.name, name)
        if not bindings:
            # fall back to the parameter's default value, if literal
            return self._param_default(name)
        result: Optional[Interval] = None
        for b in bindings:
            ev = IntervalEvaluator(b.module, b.scope,
                                   self.flag_defaults, self.call_graph,
                                   _depth=self._depth + 1)
            iv = ev.eval(b.node)
            result = iv if result is None else _join(result, iv)
        return result if result is not None else UNKNOWN

    def _param_default(self, name: str) -> Interval:
        a = self.scope.args
        pos = a.posonlyargs + a.args
        n_def = len(a.defaults)
        for i, arg in enumerate(pos):
            if arg.arg == name and i >= len(pos) - n_def:
                return self.eval(a.defaults[i - (len(pos) - n_def)])
        for arg, d in zip(a.kwonlyargs, a.kw_defaults):
            if arg.arg == name and d is not None:
                return self.eval(d)
        return UNKNOWN

    def _eval_binop(self, node: ast.BinOp, at: ast.AST) -> Interval:
        a = self.eval(node.left, at)
        b = self.eval(node.right, at)
        op = node.op
        if isinstance(op, ast.Add):
            return Interval(a.lo + b.lo, a.hi + b.hi)
        if isinstance(op, ast.Sub):
            return Interval(a.lo - b.hi, a.hi - b.lo)
        if isinstance(op, ast.Mult):
            if a.lo < 0 or b.lo < 0:
                return UNKNOWN
            return Interval(a.lo * b.lo, a.hi * b.hi)
        if isinstance(op, ast.FloorDiv):
            if b.lo <= 0:
                return UNKNOWN
            hi = a.hi if b.lo == 0 else a.hi / b.lo
            lo = 0 if a.lo < 0 or b.hi == INF or b.hi == 0 \
                else a.lo // b.hi
            return Interval(lo, hi)
        if isinstance(op, ast.Mod):
            if b.hi == INF or b.hi <= 0:
                return UNKNOWN
            return Interval(0, b.hi - 1)
        if isinstance(op, ast.LShift):
            if b.exact is not None and a.lo >= 0 and a.hi != INF:
                return Interval(int(a.lo) << b.exact,
                                int(a.hi) << b.exact)
            return UNKNOWN
        if isinstance(op, ast.Pow):
            if a.exact is not None and b.exact is not None and \
                    b.exact >= 0:
                v = a.exact ** b.exact
                return Interval(v, v)
            return UNKNOWN
        return UNKNOWN

    def _eval_call(self, node: ast.Call, at: ast.AST) -> Interval:
        fn = tail_name(node.func)
        if fn in ("min", "max"):
            ivs = [self.eval(a, at) for a in self._spread_args(node)]
            if not ivs:
                return UNKNOWN
            if fn == "min":
                # Upper bound of min() is sound from ANY bounded arg.
                hi = min(iv.hi for iv in ivs)
                lo = min(iv.lo for iv in ivs)
                return Interval(lo, hi)
            hi = max(iv.hi for iv in ivs)
            lo = max(iv.lo for iv in ivs)
            return Interval(lo, hi)
        if fn in ("get_int", "get_float"):
            # flags accessor: assume registry/call-site default.
            default = keyword_arg(node, "default")
            cand = default if default is not None else (
                node.args[1] if len(node.args) > 1 else None)
            if cand is not None:
                return self.eval(cand, at)
            return UNKNOWN
        if fn == "len":
            return Interval(0, INF)
        if fn == "rem" and len(node.args) == 2:
            # jax.lax.rem(x, m): same bounds as the Mod binop.
            m = self.eval(node.args[1], at)
            if m.hi != INF and m.hi > 0:
                return Interval(0, m.hi - 1)
            return UNKNOWN
        if fn == "program_id":
            return Interval(0, INF)
        if fn == "num_programs":
            return Interval(1, INF)
        return UNKNOWN

    def _spread_args(self, node: ast.Call) -> List[ast.AST]:
        """min/max over a literal-tuple generator contributes the
        tuple's elements (`max(bn for bn in (2048, 1024, ...) if ...)`
        is bounded by the tuple, whatever the filter keeps)."""
        out: List[ast.AST] = []
        for arg in node.args:
            if isinstance(arg, ast.GeneratorExp) and \
                    len(arg.generators) == 1 and \
                    isinstance(arg.generators[0].iter, ast.Tuple):
                out.extend(arg.generators[0].iter.elts)
            elif isinstance(arg, ast.Starred):
                continue
            else:
                out.append(arg)
        for kw in node.keywords:
            if kw.arg == "default":
                out.append(kw.value)
        return out


#: dtype attribute name -> byte width (Pallas scratch/blockspec math).
DTYPE_BYTES = {
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2,
    "int8": 1, "uint8": 1, "float8_e5m2": 1, "float8_e4m3fn": 1,
    "bool_": 1,
    "float64": 8, "int64": 8,
}


def dtype_bytes(node: ast.AST) -> Interval:
    """Byte width of a dtype expression; unknown dtypes bound to
    [1, 8] (lower bound keeps definite-overflow reasoning sound)."""
    name = tail_name(node)
    if name in DTYPE_BYTES:
        w = DTYPE_BYTES[name]
        return Interval(w, w)
    return Interval(1, 8)


#: src -> dsts the src dtype embeds into without loss (REF004). The
#: pseudo-dtypes 'int'/'float' stand for Python literals, which JAX
#: weak-types into whatever the ref holds.
_LOSSLESS_WIDENING = {
    "int8": {"int16", "int32", "int64", "float32", "float64",
             "bfloat16", "float16"},
    "uint8": {"int16", "int32", "int64", "float32", "float64"},
    "int16": {"int32", "int64", "float32", "float64"},
    "int32": {"int64", "float64"},
    "bfloat16": {"float32", "float64"},
    "float16": {"float32", "float64"},
    "float32": {"float64"},
    "bool_": {"int8", "int16", "int32", "int64", "float32",
              "bfloat16", "float16"},
}


def dtype_lossless(src: str, dst: str) -> bool:
    """Whether every value of dtype `src` lands exactly in `dst`."""
    if src == dst:
        return True
    if src == "int":
        return dst in DTYPE_BYTES    # literal ints weak-type freely
    if src == "float":
        return dst in ("float16", "bfloat16", "float32", "float64")
    return dst in _LOSSLESS_WIDENING.get(src, ())
