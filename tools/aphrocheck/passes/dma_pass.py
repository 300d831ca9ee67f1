"""DMA pass: async-copy start/wait pairing and ring-slot invariants.

Analyzed at TOP-LEVEL-FUNCTION granularity: Pallas kernel bodies
stage their copies through nested closures (`@pl.when` blocks,
chunk_dmas-style helpers), so starts and waits for one semaphore
routinely live in different inner defs of the same kernel.

Rules:

- DMA001: a semaphore base that is `.start()`ed somewhere in the
  kernel but never `.wait()`ed (matching is by the SEMAPHORE ARRAY,
  not the slot index — start slot i / wait slot (i-depth) is the
  normal ring pattern). An unwaited start leaks an in-flight DMA past
  the kernel's lifetime; an unstarted wait deadlocks. Receivers that
  cannot be traced to a constructor (dynamic dispatch) are treated as
  matching every base — unresolvable code must not produce noise.
- DMA002: one semaphore base indexed through ring-slot arithmetic
  with TWO DIFFERENT moduli that can be live together (branch-aware:
  a `chunk_slots` arm and a 2-slot arm of one `if` do not conflict,
  but a genuine depth mismatch within one path does). Mixed moduli mean the n-th start and the
  matching wait disagree about which slot they share.
- DMA003: at a pallas_call site, the largest statically-resolvable
  ring modulus in the kernel exceeds the largest resolvable
  SemaphoreType.DMA leading dimension — the ring wraps past the
  semaphore array. (Sites whose depths are runtime-computed resolve
  to nothing and are skipped; shared module constants like _WB_SLOTS
  resolve on both sides.)
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.aphrocheck.core import (Finding, IntervalEvaluator, Module,
                                   dotted_name, iter_calls,
                                   paths_conflict, tail_name,
                                   walk_nodes)
from tools.aphrocheck.sites import (find_sites, list_elements,
                                    resolve, resolve_kernel_functions)

WILDCARD = "*"


def _sem_base(sem: ast.AST) -> Optional[str]:
    """Base array name of a semaphore expression: `sems.at[slot, 0]`
    -> 'sems', plain `sem` -> 'sem'."""
    node = sem
    while isinstance(node, ast.Subscript):
        node = node.value
    name = dotted_name(node)
    if name is None:
        return None
    base = name.split(".")[0]
    return base


def _sem_index(sem: ast.AST) -> Optional[ast.AST]:
    """First index element of the semaphore subscript, if any."""
    node = sem
    while isinstance(node, ast.Subscript):
        idx = node.slice
        if isinstance(idx, ast.Tuple) and idx.elts:
            return idx.elts[0]
        return idx
    return None


def _constructors(fn: ast.AST) -> List[ast.Call]:
    return [c for c in iter_calls(fn)
            if tail_name(c.func) == "make_async_copy"]


def _constructor_base(call: ast.Call) -> Optional[str]:
    sem = call.args[2] if len(call.args) >= 3 else None
    return _sem_base(sem) if sem is not None else None


class _Kernel:
    """Start/wait and slot-arithmetic facts for one top-level fn."""

    def __init__(self, module: Module, fn: ast.AST) -> None:
        self.module = module
        self.fn = fn
        self.ctors = _constructors(fn)
        self.bases: Set[str] = set(
            filter(None, (_constructor_base(c) for c in self.ctors)))
        self.local_fns: Dict[str, ast.AST] = {
            n.name: n for n in walk_nodes(fn)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}

    def _bases_of_expr(self, node: ast.AST, depth: int = 0
                       ) -> Set[str]:
        """Semaphore bases an expression's async copies may use."""
        if depth > 4:
            return {WILDCARD}
        if isinstance(node, ast.Call):
            fn_name = tail_name(node.func)
            if fn_name == "make_async_copy":
                base = _constructor_base(node)
                return {base} if base else {WILDCARD}
            if fn_name in self.local_fns:
                return {b for c in _constructors(
                    self.local_fns[fn_name])
                    for b in [_constructor_base(c)] if b} or {WILDCARD}
            return {WILDCARD}
        if isinstance(node, (ast.List, ast.Tuple)):
            out: Set[str] = set()
            for elt in node.elts:
                out |= self._bases_of_expr(elt, depth + 1)
            return out or {WILDCARD}
        if isinstance(node, ast.IfExp):
            return self._bases_of_expr(node.body, depth + 1) | \
                self._bases_of_expr(node.orelse, depth + 1)
        if isinstance(node, ast.Name):
            out = set()
            found = False
            for n in walk_nodes(self.fn):
                if isinstance(n, ast.Assign):
                    for tgt in n.targets:
                        if isinstance(tgt, ast.Name) and \
                                tgt.id == node.id:
                            found = True
                            out |= self._bases_of_expr(n.value,
                                                       depth + 1)
                elif isinstance(n, ast.For) and \
                        isinstance(n.target, ast.Name) and \
                        n.target.id == node.id:
                    found = True
                    out |= self._bases_of_expr(n.iter, depth + 1)
            return out if found else {WILDCARD}
        return {WILDCARD}

    def op_bases(self, op: str) -> Set[str]:
        """Bases reached by `.start()` / `.wait()` applications."""
        out: Set[str] = set()
        for call in iter_calls(self.fn):
            if not isinstance(call.func, ast.Attribute) or \
                    call.func.attr != op or call.args:
                continue
            out |= self._bases_of_expr(call.func.value)
        return out

    # -- ring-slot arithmetic ---------------------------------------

    def _modulus_of(self, node: ast.AST, path, depth: int = 0
                    ) -> List[Tuple[str, tuple, ast.AST]]:
        """(modulus_dump, branch_path, modulus_node) candidates for a
        slot-index expression."""
        if depth > 5 or node is None:
            return []
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
            return [(ast.dump(node.right), path, node.right)]
        if isinstance(node, ast.Call) and \
                tail_name(node.func) == "rem" and len(node.args) == 2:
            return [(ast.dump(node.args[1]), path, node.args[1])]
        if isinstance(node, ast.Name):
            out = []
            # assignments to the name
            for n in walk_nodes(self.fn):
                if isinstance(n, ast.Assign):
                    for tgt in n.targets:
                        if isinstance(tgt, ast.Name) and \
                                tgt.id == node.id:
                            out.extend(self._modulus_of(
                                n.value,
                                self.module.branch_path(n),
                                depth + 1))
            if out:
                return out
            # function parameter: look at call sites inside the kernel
            owner = self.module.enclosing_function(node)
            if isinstance(owner, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                params = [a.arg for a in owner.args.args]
                if node.id in params:
                    pos = params.index(node.id)
                    for call in iter_calls(self.fn):
                        if isinstance(call.func, ast.Name) and \
                                call.func.id == owner.name:
                            arg = None
                            if pos < len(call.args):
                                arg = call.args[pos]
                            for kw in call.keywords:
                                if kw.arg == node.id:
                                    arg = kw.value
                            if arg is not None:
                                out.extend(self._modulus_of(
                                    arg,
                                    self.module.branch_path(call),
                                    depth + 1))
            return out
        return []

    def sem_moduli(self) -> Dict[str, List[Tuple[str, tuple, ast.AST]]]:
        cached = getattr(self, "_sem_moduli", None)
        if cached is not None:
            return cached
        out: Dict[str, List[Tuple[str, tuple, ast.AST]]] = {}
        for ctor in self.ctors:
            base = _constructor_base(ctor)
            if base is None or len(ctor.args) < 3:
                continue
            idx = _sem_index(ctor.args[2])
            if idx is None:
                continue
            mods = self._modulus_of(idx,
                                    self.module.branch_path(ctor))
            if mods:
                out.setdefault(base, []).extend(mods)
        self._sem_moduli = out
        return out


def _top_level_kernel_fns(module: Module) -> List[ast.AST]:
    cached = getattr(module, "_dma_kernel_fns", None)
    if cached is not None:
        return cached
    if "make_async_copy" not in module.text:
        # text prefilter: no async copies, no DMA kernels to walk
        module._dma_kernel_fns = []
        return []
    out = []
    for node in module.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(tail_name(c.func) == "make_async_copy"
                   for c in iter_calls(node)):
                out.append(node)
        elif isinstance(node, ast.ClassDef):
            for meth in node.body:
                if isinstance(meth, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        any(tail_name(c.func) == "make_async_copy"
                            for c in iter_calls(meth)):
                    out.append(meth)
    module._dma_kernel_fns = out
    return out


def _check_start_wait(module: Module, kernel: _Kernel,
                      findings: List[Finding]) -> None:
    started = kernel.op_bases("start")
    waited = kernel.op_bases("wait")
    if WILDCARD in waited:
        unwaited: Set[str] = set()
    else:
        unwaited = (started - {WILDCARD}) - waited
    for base in sorted(unwaited):
        node = next((c for c in kernel.ctors
                     if _constructor_base(c) == base), kernel.fn)
        findings.append(module.finding(
            "DMA001", node,
            f"async copies on semaphore '{base}' are started but "
            f"never waited in {kernel.fn.name}; every "
            "make_async_copy(...).start() needs a reachable "
            "matching .wait()"))
    if WILDCARD not in started:
        unstarted = (waited - {WILDCARD}) - started
        for base in sorted(unstarted):
            findings.append(module.finding(
                "DMA001", kernel.fn,
                f"async copies on semaphore '{base}' are waited but "
                f"never started in {kernel.fn.name} (deadlock: the "
                "semaphore is never signaled)"))


def _check_moduli(module: Module, kernel: _Kernel,
                  findings: List[Finding]) -> None:
    for base, mods in kernel.sem_moduli().items():
        for i in range(len(mods)):
            for j in range(i + 1, len(mods)):
                dump_i, path_i, node_i = mods[i]
                dump_j, path_j, _ = mods[j]
                if dump_i == dump_j:
                    continue
                if paths_conflict(path_i, path_j):
                    continue    # mutually-exclusive branches
                findings.append(module.finding(
                    "DMA002", node_i,
                    f"semaphore '{base}' in {kernel.fn.name} is "
                    "indexed with two different ring moduli on the "
                    "same path; start and wait slots will disagree"))
                return


def _check_sem_lengths(module: Module, findings: List[Finding],
                       call_graph=None, kernels=None) -> None:
    if kernels is None:
        kernels = {k.fn.name if hasattr(k.fn, 'name') else '': k
                   for k in (_Kernel(module, fn)
                             for fn in _top_level_kernel_fns(module))}
    for site in find_sites(module):
        sem_dims: List[int] = []
        for variant in site.variants:
            base, appended, _ = list_elements(module, site.scope,
                                              variant.scratch_shapes)
            ev = IntervalEvaluator(module, site.scope,
                                   call_graph=call_graph)
            for entry in base + appended:
                if isinstance(entry, ast.Call) and \
                        (dotted_name(entry.func) or "").endswith(
                            "SemaphoreType.DMA") and entry.args:
                    shape = entry.args[0]
                    lead = shape.elts[0] if isinstance(
                        shape, ast.Tuple) and shape.elts else shape
                    exact = ev.eval(lead, entry).exact
                    if exact is not None:
                        sem_dims.append(exact)
        if not sem_dims:
            continue
        moduli: List[int] = []
        for fn in resolve_kernel_functions(module, site.scope,
                                           site.kernel_arg):
            kernel = kernels.get(fn.name)
            if kernel is None:
                kernel = _Kernel(module, fn)
            kev = IntervalEvaluator(module, fn, call_graph=call_graph)
            for mods in kernel.sem_moduli().values():
                for _, _, mod_node in mods:
                    exact = kev.eval(mod_node, mod_node).exact
                    if exact is not None:
                        moduli.append(exact)
        if moduli and max(moduli) > max(sem_dims):
            findings.append(module.finding(
                "DMA003", site.call,
                f"kernel ring modulus {max(moduli)} exceeds the "
                f"largest SemaphoreType.DMA leading dimension "
                f"{max(sem_dims)} at this pallas_call; the ring "
                "wraps past the semaphore array"))


def run(ctx) -> List[Finding]:
    findings: List[Finding] = []
    for module in ctx.modules:
        kernels = {}
        for fn in _top_level_kernel_fns(module):
            kernel = _Kernel(module, fn)
            if hasattr(fn, "name"):
                kernels[fn.name] = kernel
            _check_start_wait(module, kernel, findings)
            _check_moduli(module, kernel, findings)
        # text prefilter: DMA semaphores only exist at pallas_call
        # sites
        if "pallas_call" in module.text:
            _check_sem_lengths(module, findings,
                               getattr(ctx, "call_graph", None),
                               kernels=kernels)
    return findings


#: (rule, one-line contract, example) — rendered by `--rules-md`.
RULES = (
    ("DMA001", "async copy started but never waited in the kernel "
     "(or waited but never started)",
     "`make_async_copy(...).start()` with no reachable `.wait()`"),
    ("DMA002", "one semaphore array indexed with two different ring "
     "moduli on the same path",
     "start at `sem.at[i % 4]`, wait at `sem.at[i % 2]`"),
    ("DMA003", "ring modulus exceeds the `SemaphoreType.DMA` leading "
     "dimension at the pallas_call site",
     "`rem(i, 4)` slots against `SemaphoreType.DMA((2,))`"),
)
