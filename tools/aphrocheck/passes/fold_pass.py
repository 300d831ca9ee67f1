"""FOLD pass: kernel-adjacent elementwise work paying HBM round trips.

Zen-Attention (arxiv 2508.17593) showed that the elementwise chains a
compiler leaves ADJACENT to an attention kernel — scales, casts,
activation epilogues — each cost a full HBM round trip of the tensor
the kernel just produced (or is about to consume), and that folding
them into the kernel's prologue/epilogue is free VPU time under a
memory-bound kernel. AMLA (arxiv 2509.25224) makes the same point
inside the kernel: the online-softmax rescale MULTIPLY on the
accumulator can become exponent-bias ADDS, taking the per-chunk
[rows, d] multiply off the VPU's critical path.

- FOLD001: an elementwise jnp chain (>= 2 of: arithmetic binops,
  `astype`, exp/tanh/sigmoid/relu/gelu/silu, maximum/minimum, clip,
  round, abs, where, multiply/add/subtract/divide) whose result flows
  into a `pallas_call` launch in the same launcher function, or that
  is applied to a launch's result — one avoidable HBM round trip of a
  kernel-sized tensor per chain. Resolution is INTERPROCEDURAL: a
  chain returned by a same-package helper fires at the helper's
  return when a launcher feeds the helper's result into the kernel
  (the `_quantize_activations_int8` idiom: div/round/clip/astype on
  the full activation block, whose output the streamed kernel then
  re-reads from HBM even though the raw block is already VMEM-
  resident there). Layout plumbing — reshape/transpose/pad/
  concatenate/bitwise unpacks — is NOT elementwise work a kernel
  epilogue absorbs and never counts toward a chain.
- FOLD002: an online-softmax rescale multiply inside a Pallas kernel
  body: an accumulator update `acc = acc * corr + x` (store or name
  assign) where `corr` resolves to `exp(a - b)` — the multiply AMLA's
  mul-by-add rewrite eliminates. One finding per kernel function.

Known, deliberate candidates carry a `# perf-known: FOLD00x <reason>`
pragma (see roofline_pass) — they stay visible in the `--roofline`
report while the gate stays green and the allowlist stays empty.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.aphrocheck.core import (Finding, Module, has_pragma,
                                   iter_calls, tail_name, walk_nodes)
from tools.aphrocheck.passes.roofline_pass import PRAGMA
from tools.aphrocheck.sites import find_sites, resolve_kernel_functions

#: Call tail-names that count as foldable elementwise work.
_ELEMENTWISE_CALLS = {
    "exp", "tanh", "sigmoid", "relu", "gelu", "silu", "maximum",
    "minimum", "clip", "round", "abs", "where", "multiply", "add",
    "subtract", "divide", "true_divide", "square", "sqrt", "rsqrt",
    "log", "erf",
}

#: astype/casting attribute calls count too (a dtype round trip).
_CAST_CALLS = {"astype"}

#: Chains must clear this many elementwise ops to fire FOLD001 — a
#: single bias add or cast is not worth a kernel-variant explosion.
_MIN_CHAIN = 2


def _assigns_in_order(module: Module, scope: ast.AST
                      ) -> List[ast.Assign]:
    return sorted((n for n in walk_nodes(scope)
                   if isinstance(n, ast.Assign)),
                  key=lambda n: n.lineno)


def _nearest_assign(module: Module, scope: ast.AST, name: str,
                    before_line: int) -> Optional[ast.AST]:
    """The value of the LAST assignment to `name` above `before_line`
    — order-aware resolution, so `y = launch(...); y = y + b;
    y = fallback(...)` chains don't bleed across rebindings."""
    best = None
    best_line = -1
    for node in _assigns_in_order(module, scope):
        if node.lineno >= before_line:
            break
        for tgt in node.targets:
            if isinstance(tgt, ast.Name) and tgt.id == name and \
                    node.lineno > best_line:
                best, best_line = node.value, node.lineno
    return best


def _chain_len(node: ast.AST, module: Module,
               scope: Optional[ast.AST], use_line: int,
               depth: int = 0) -> int:
    """Number of foldable elementwise ops in an expression tree,
    following Name reads to their nearest PRECEDING assignment."""
    if depth > 6 or node is None:
        return 0
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.Add, ast.Sub, ast.Mult, ast.Div)):
        return 1 + _chain_len(node.left, module, scope, use_line,
                              depth + 1) + \
            _chain_len(node.right, module, scope, use_line, depth + 1)
    if isinstance(node, ast.Call):
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _CAST_CALLS:
            return 1 + _chain_len(node.func.value, module, scope,
                                  use_line, depth + 1)
        fn = tail_name(node.func)
        if fn in _ELEMENTWISE_CALLS:
            n = 1
            for arg in node.args:
                n += _chain_len(arg, module, scope, use_line,
                                depth + 1)
            return n
        return 0          # any other call breaks the chain
    if isinstance(node, ast.Name) and scope is not None:
        value = _nearest_assign(module, scope, node.id, use_line)
        if value is not None:
            return _chain_len(value, module, scope, value.lineno,
                              depth + 1)
        return 0
    return 0


def _launch_arg_names(module: Module, scope,
                      launcher_calls: List[ast.Call]) -> Set[str]:
    """Names flowing positionally into kernel launches in this scope:
    the pallas_call invocation's args, same-package launcher-helper
    calls' args, and names extended into arg-list builders."""
    names: Set[str] = set()
    # one pass over the scope's calls: name -> args appended/extended
    # onto it (the `inputs.append(...)` arg-list builder idiom)
    appended: Dict[str, List[ast.AST]] = {}
    if scope is not None:
        for call in iter_calls(scope):
            f = call.func
            if isinstance(f, ast.Attribute) and \
                    isinstance(f.value, ast.Name) and \
                    f.attr in ("append", "extend"):
                appended.setdefault(f.value.id, []).extend(call.args)

    def collect(node: ast.AST, depth: int = 0) -> None:
        if depth > 3 or node is None:
            return
        if isinstance(node, ast.Name):
            if node.id in names:
                return
            names.add(node.id)
            for value in module.assign_index(scope).get(node.id, ()):
                collect(value, depth + 1)
            for a in appended.get(node.id, ()):
                collect(a, depth + 1)
        elif isinstance(node, (ast.List, ast.Tuple)):
            for elt in node.elts:
                collect(elt, depth + 1)
        elif isinstance(node, ast.Starred):
            collect(node.value, depth + 1)
        elif isinstance(node, ast.BinOp) and \
                isinstance(node.op, ast.Add):
            collect(node.left, depth + 1)
            collect(node.right, depth + 1)

    for call in launcher_calls:
        for arg in call.args:
            collect(arg)
        for kw in call.keywords:
            collect(kw.value)
    return names


def _launcher_functions(ctx) -> Dict[int, Tuple[Module, ast.AST,
                                                List[ast.Call]]]:
    """id(fn) -> (module, fn, launch calls): functions that launch a
    kernel, directly (a pallas_call invocation) or through one level
    of same-package helper (`_stream_call`-style)."""
    direct: Dict[str, Tuple[Module, ast.AST]] = {}
    out: Dict[int, Tuple[Module, ast.AST, List[ast.Call]]] = {}
    for module in ctx.modules:
        for site in find_sites(module):
            if site.scope is None or not hasattr(site.scope, "name"):
                continue
            launch = site.invocation if site.invocation is not None \
                else site.call
            key = id(site.scope)
            if key not in out:
                out[key] = (module, site.scope, [])
            out[key][2].append(launch)
            direct[site.scope.name] = (module, site.scope)
    # one level of wrapping: calls TO a direct launcher count as
    # launches too — both in pure wrappers and in direct launchers
    # that route one path through a helper (`_stream_call`). One pass
    # over each module's precomputed call list.
    for module in ctx.modules:
        for call in module.calls:
            name = tail_name(call.func)
            if name not in direct:
                continue
            fn = module.top_level_function(call)
            if fn is None or fn.name == name:
                continue
            entry = out.setdefault(id(fn), (module, fn, []))
            entry[2].append(call)
    return out


def _helper_chain_return(ctx, module: Module, call: ast.Call
                         ) -> Optional[Tuple[Module, ast.AST, int]]:
    """When `call` targets a same-package helper whose return value is
    an elementwise chain, return (module, return stmt, chain len)."""
    name = tail_name(call.func)
    if name is None or ctx.call_graph is None:
        return None
    for mod, fn in ctx.call_graph.functions_named(name):
        for node in walk_nodes(fn):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            values = node.value.elts if isinstance(
                node.value, (ast.Tuple, ast.List)) else [node.value]
            best = max((_chain_len(v, mod, fn, node.lineno + 1)
                        for v in values), default=0)
            if best >= _MIN_CHAIN:
                return mod, node, best
    return None


def _breaks_adjacency(node: ast.AST, launch_ids: Set[int]) -> bool:
    """Whether an expression puts OTHER compute between the kernel and
    the chain — a matmul or a non-elementwise call (reshape,
    hadamard helpers, gathers) — after which folding into the kernel
    epilogue is no longer the rewrite."""
    for sub in walk_nodes(node):
        if isinstance(sub, ast.BinOp) and \
                isinstance(sub.op, ast.MatMult):
            return True
        if isinstance(sub, ast.Call) and id(sub) not in launch_ids:
            if isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in _CAST_CALLS:
                continue
            if tail_name(sub.func) not in _ELEMENTWISE_CALLS:
                return True
    return False


def _assign_targets(node: ast.Assign) -> List[str]:
    targets = []
    for tgt in node.targets:
        if isinstance(tgt, ast.Name):
            targets.append(tgt.id)
        elif isinstance(tgt, ast.Tuple):
            targets.extend(t.id for t in tgt.elts
                           if isinstance(t, ast.Name))
    return targets


def _fold001(ctx, findings: List[Finding],
             honor_pragmas: bool) -> None:
    reported: Set[int] = set()

    def fire(mod: Module, node: ast.AST, message: str) -> None:
        if id(node) in reported:
            return
        reported.add(id(node))
        if honor_pragmas and has_pragma(mod, node.lineno, PRAGMA):
            return
        findings.append(mod.finding("FOLD001", node, message))

    for module, fn, launches in _launcher_functions(ctx).values():
        arg_names = _launch_arg_names(module, fn, launches)
        launch_ids = {id(c) for c in launches}
        derived: Set[str] = set()     # names holding kernel output
        for node in _assigns_in_order(module, fn):
            targets = _assign_targets(node)
            if not targets:
                continue
            reads = {n.id for n in walk_nodes(node.value)
                     if isinstance(n, ast.Name)}
            contains_launch = any(
                isinstance(c, ast.Call) and id(c) in launch_ids
                for c in walk_nodes(node.value))
            if contains_launch:
                derived.update(targets)
                continue
            # producer side: a chain (direct or through a same-package
            # helper) whose result feeds the launch
            if set(targets) & arg_names:
                helper = _helper_chain_return(ctx, module, node.value) \
                    if isinstance(node.value, ast.Call) else None
                if helper is not None:
                    hmod, ret, n = helper
                    fire(hmod, ret,
                         f"elementwise chain ({n} ops) returned here "
                         f"feeds the kernel launch in {fn.name}: one "
                         "avoidable HBM round trip — fold it into the "
                         "kernel prologue (the operand is staged into "
                         "VMEM there anyway)")
                else:
                    n = _chain_len(node.value, module, fn, node.lineno)
                    if n >= _MIN_CHAIN:
                        fire(module, node,
                             f"elementwise chain ({n} ops) feeds the "
                             f"kernel launch in {fn.name}: one "
                             "avoidable HBM round trip — fold it into "
                             "the kernel prologue")
            # consumer side: a chain applied to a name still holding
            # kernel output (flow-sensitive: rebinding to non-kernel
            # values, or passing through other compute, ends it)
            if reads & derived:
                n = _chain_len(node.value, module, fn, node.lineno)
                if n >= _MIN_CHAIN:
                    fire(module, node,
                         f"elementwise chain ({n} ops) consumes the "
                         f"kernel output of {fn.name}: one avoidable "
                         "HBM round trip — fold it into the kernel "
                         "epilogue")
                if _breaks_adjacency(node.value, launch_ids):
                    derived.difference_update(targets)
                else:
                    derived.update(targets)
            else:
                derived.difference_update(targets)


def _resolves_to_exp_sub(module: Module, fn: ast.AST,
                         node: ast.AST, depth: int = 0) -> bool:
    """Whether an expression is (or names) `exp(a - b)` — the online-
    softmax correction factor."""
    if depth > 4 or node is None:
        return False
    if isinstance(node, ast.Call) and tail_name(node.func) == "exp" \
            and node.args:
        arg = node.args[0]
        return isinstance(arg, ast.BinOp) and \
            isinstance(arg.op, ast.Sub)
    if isinstance(node, ast.Name):
        return any(_resolves_to_exp_sub(module, fn, v, depth + 1)
                   for v in module.assign_index(fn).get(node.id, ()))
    return False


def _fold002(ctx, findings: List[Finding],
             honor_pragmas: bool) -> None:
    seen_fns: Set[int] = set()
    for module in ctx.modules:
        for site in find_sites(module):
            for fn in resolve_kernel_functions(module, site.scope,
                                               site.kernel_arg):
                if id(fn) in seen_fns:
                    continue
                seen_fns.add(id(fn))
                _fold002_kernel(module, fn, findings, honor_pragmas)


def _fold002_kernel(module: Module, fn: ast.AST,
                    findings: List[Finding],
                    honor_pragmas: bool) -> None:
    matches: List[ast.Assign] = []
    for node in walk_nodes(fn):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not (isinstance(value, ast.BinOp) and
                isinstance(value.op, ast.Add)):
            continue
        mul = value.left if isinstance(value.left, ast.BinOp) and \
            isinstance(value.left.op, ast.Mult) else (
                value.right if isinstance(value.right, ast.BinOp) and
                isinstance(value.right.op, ast.Mult) else None)
        if mul is None:
            continue
        if _resolves_to_exp_sub(module, fn, mul.right) or \
                _resolves_to_exp_sub(module, fn, mul.left):
            matches.append(node)
    if not matches:
        return
    # one finding per kernel fn, anchored at the FIRST rescale by
    # source order (deterministic pragma placement)
    node = min(matches, key=lambda n: n.lineno)
    if honor_pragmas and has_pragma(module, node.lineno, PRAGMA):
        return
    findings.append(module.finding(
        "FOLD002", node,
        f"online-softmax rescale multiply in {fn.name}: the "
        "accumulator is scaled by `exp(m_prev - m_new)` every "
        "chunk — AMLA's mul-by-add rewrite (arxiv 2509.25224) "
        "turns the rescale into exponent-bias adds, taking the "
        "per-chunk multiply off the VPU"))


def findings(ctx, honor_pragmas: bool = True) -> List[Finding]:
    out: List[Finding] = []
    _fold001(ctx, out, honor_pragmas)
    _fold002(ctx, out, honor_pragmas)
    return out


def run(ctx) -> List[Finding]:
    return findings(ctx, honor_pragmas=True)


#: (rule, one-line contract, example) — rendered by `--rules-md`.
RULES = (
    ("FOLD001", "elementwise chain (>= 2 mul/add/cast/activation "
     "ops, resolved interprocedurally through same-package helpers) "
     "whose producer or consumer is a `pallas_call` launch: one "
     "avoidable HBM round trip a kernel prologue/epilogue could "
     "absorb (Zen-Attention, arxiv 2508.17593)",
     "`x8 = clip(round(x / s)).astype(int8)` feeding the launch"),
    ("FOLD002", "online-softmax rescale multiply (`acc = acc * "
     "exp(m_prev - m_new) + ...`) inside a Pallas kernel — AMLA's "
     "mul-by-add rewrite eliminates the per-chunk VPU multiply "
     "(arxiv 2509.25224)",
     "`acc_scr[...] = acc_scr[...] * corr + pv` in a decode kernel"),
)
