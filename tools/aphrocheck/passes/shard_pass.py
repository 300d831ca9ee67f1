"""SHARD pass: PartitionSpec/mesh consistency and deprecated imports.

The mesh is declared ONCE (executor.build_mesh's literal axis-name
tuple); every PartitionSpec axis written anywhere else must name one
of those axes, or GSPMD rejects the spec at dispatch time with an
error that names neither the spec nor the layer that owns it.

- SHARD001: a literal string axis in a `PartitionSpec(...)` / `P(...)`
  call that no `Mesh(...)`/`make_mesh(...)` axis-name declaration in
  the scanned tree provides. Declarations resolve through named
  constants (the production `Mesh(devices, ParallelConfig.MESH_AXES)`
  spelling), and an `axis_name: str = "sp"`-style parameter DEFAULT
  counts as that literal at its P() uses; truly variable axes
  (`in_axis`, a defaultless parameter) stay silent, as does the pass
  when the scan contains no mesh declaration at all (subset scans of
  non-mesh files).
- SHARD002: `jax.device_put(x, NamedSharding(mesh, P(...)))` where the
  spec has MORE axes than x's statically-known rank (resolved through
  assignments to literal-shape constructors — jnp.zeros/ones/full —
  and literal reshape chains). A spec shorter than the rank is legal
  (trailing dims replicate); a longer one raises at runtime on the
  first device_put of a multi-GB cache.
- SHARD003: any import of `jax.experimental.shard_map` — deprecated
  (the installed jax 0.9.0 warns on it); the supported spelling is
  `jax.shard_map`, called at the point of use. No module is exempt:
  the tree supports the one installed JAX and carries no bridge.
- SHARD004: a host transfer (`.item()`, `np.asarray`/`np.array`,
  `jax.device_get`) of a MESH-SHARDED array inside an executor-scope
  (`aphrodite_tpu/executor/`) hot-path (`execute_*`/`dispatch_*`/
  `finalize_*`) function — plus EVERY function of the hot modules
  that build PartitionSpecs outside the executor (lora/layers.py's
  per-token apply, ops/ring_attention.py's per-layer ring), where
  any host pull sits on the step path regardless of its name. Pulling a tp-sharded KV plane or parameter
  is a cross-device all-gather plus a multi-GB device->host copy per
  call — the exact class of silent step-time cliff the multichip
  sharding plan exists to avoid. "Mesh-sharded" is the repo's naming
  convention for the committed-sharded set (the same contract by
  which HOT_NAME defines the hot path): identifiers `kv_caches`,
  `new_caches`, `caches`, `kv`, `k_pages`, `v_pages`, `params`, and
  `.kv_caches` attribute reads. Small per-step RESULTS (`packed`,
  logits rows) transfer freely — one pull per round is the engine's
  sync contract, policed by SYNC001/002.
"""
from __future__ import annotations

import ast
import re
from typing import List, Optional, Set, Tuple

from tools.aphrocheck.core import (Finding, Module, dotted_name,
                                   iter_calls, str_const, tail_name)

_SPEC_NAMES = ("PartitionSpec", "P")
_MESH_NAMES = ("Mesh", "make_mesh")
_ARRAY_CTORS = ("zeros", "ones", "full", "empty")

#: SHARD004 hot-path shape (shared contract with sync_pass.HOT_NAME).
_HOT_NAME = re.compile(r"^(execute_|dispatch_|finalize_)")

#: SHARD004 scope: the executor layer, where the committed-sharded
#: arrays (weights pytree, KV planes) live.
_EXECUTOR_PREFIXES = ("aphrodite_tpu/executor/",)

#: Everything the CLI normally scans; explicitly-passed files outside
#: these roots (the seeded fixtures) are treated as executor scope.
_SCAN_PREFIXES = ("aphrodite_tpu/", "benchmarks/", "bench.py")

#: Identifiers that name the committed mesh-sharded set by repo
#: convention (cache_engine KV planes, the loader's params pytree).
_SHARDED_NAMES = frozenset((
    "kv_caches", "new_caches", "caches", "kv", "k_pages", "v_pages",
    "params",
))

_TRANSFER_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                   "numpy.array"}

#: SHARD004 hot MODULES: PartitionSpec builders outside the executor
#: whose every function sits on the step path (per-token LoRA apply,
#: per-layer ring rotation) — hot regardless of function name.
_HOT_MODULES = frozenset((
    "aphrodite_tpu/lora/layers.py",
    "aphrodite_tpu/ops/ring_attention.py",
))


def _literal_axis_names(node: ast.AST) -> Optional[List[str]]:
    if isinstance(node, (ast.Tuple, ast.List)) and node.elts:
        names = [str_const(e) for e in node.elts]
        if all(n is not None for n in names):
            return names
    return None


def _resolve_axis_constant(modules: List[Module],
                           name: str) -> Optional[List[str]]:
    """A named axis-tuple constant (`MESH_AXES = ("dp", ...)`) — the
    production `Mesh(devices, ParallelConfig.MESH_AXES)` spelling —
    resolved by tail name across the scanned tree."""
    for module in modules:
        for node in module.nodes:
            value = None
            if isinstance(node, ast.Assign):
                if any(isinstance(t, ast.Name) and t.id == name
                       for t in node.targets):
                    value = node.value
            elif isinstance(node, ast.AnnAssign):
                if isinstance(node.target, ast.Name) and \
                        node.target.id == name:
                    value = node.value
            names = _literal_axis_names(value) if value is not None \
                else None
            if names is not None:
                return names
    return None


def _declared_axes(modules: List[Module]) -> Tuple[Set[str], bool]:
    """(axis names, any declaration found) across the scanned tree."""
    axes: Set[str] = set()
    found = False
    for module in modules:
        for call in module.calls:
            if tail_name(call.func) not in _MESH_NAMES:
                continue
            cand = None
            for kw in call.keywords:
                if kw.arg == "axis_names":
                    cand = kw.value
            if cand is None and len(call.args) >= 2:
                cand = call.args[1]
            names = _literal_axis_names(cand)
            if names is None and cand is not None:
                const = tail_name(cand)
                if const:
                    names = _resolve_axis_constant(modules, const)
            if names is not None:
                axes.update(names)
                found = True
    return axes, found


def _spec_aliases(module: Module) -> Set[str]:
    """Local names PartitionSpec is bound to in this module."""
    out = {"PartitionSpec"}
    for node in module.nodes:
        if isinstance(node, ast.ImportFrom) and \
                node.module == "jax.sharding":
            for alias in node.names:
                if alias.name == "PartitionSpec":
                    out.add(alias.asname or alias.name)
    return out


def _spec_calls(module: Module) -> List[ast.Call]:
    aliases = _spec_aliases(module)
    out = []
    for call in module.calls:
        name = tail_name(call.func)
        if name in aliases or (name in _SPEC_NAMES and
                               (dotted_name(call.func) or "").endswith(
                                   "sharding." + name)):
            out.append(call)
    return out


def _param_default(module: Module, call: ast.Call,
                   name: str) -> Optional[str]:
    """String DEFAULT of parameter `name` in the function enclosing
    `call` (`axis_name: str = "sp"`) — the axis that P() use binds
    unless a caller overrides it."""
    scope = module.enclosing_function(call)
    if scope is None:
        return None
    pos = scope.args.args
    for param, default in zip(pos[len(pos) - len(scope.args.defaults):],
                              scope.args.defaults):
        if param.arg == name:
            return str_const(default)
    for param, default in zip(scope.args.kwonlyargs,
                              scope.args.kw_defaults):
        if param.arg == name and default is not None:
            return str_const(default)
    return None


def _spec_axis_literals(module: Module,
                        call: ast.Call) -> List[Tuple[str, ast.AST]]:
    out = []

    def visit(e: ast.AST) -> None:
        s = str_const(e)
        if s is None and isinstance(e, ast.Name):
            s = _param_default(module, call, e.id)
        if s is not None:
            out.append((s, e))

    for arg in call.args:
        if isinstance(arg, (ast.Tuple, ast.List)):
            for e in arg.elts:
                visit(e)
        else:
            visit(arg)
    return out


def _static_rank(module: Module, scope, node: ast.AST,
                 depth: int = 0) -> Optional[int]:
    """Rank of an array expression when statically certain."""
    if depth > 4 or node is None:
        return None
    if isinstance(node, ast.Call):
        fn = tail_name(node.func)
        if fn in _ARRAY_CTORS and node.args:
            shape = node.args[0]
            if isinstance(shape, (ast.Tuple, ast.List)):
                return len(shape.elts)
            if isinstance(shape, ast.Constant):
                return 1
            return None
        if fn == "reshape":
            # x.reshape(a, b, c) or x.reshape((a, b, c))
            args = node.args
            if len(args) == 1 and isinstance(args[0],
                                             (ast.Tuple, ast.List)):
                return len(args[0].elts)
            if args and not any(isinstance(a, ast.Starred)
                                for a in args):
                return len(args)
        return None
    if isinstance(node, ast.Name):
        from tools.aphrocheck.core import assignments_of
        sources = assignments_of(scope, node.id) if scope is not None \
            else []
        if not sources:
            return None
        ranks = [_static_rank(module, scope, s, depth + 1)
                 for s in sources]
        # certain only when EVERY assignment resolves to ONE rank
        if all(r is not None for r in ranks) and len(set(ranks)) == 1:
            return ranks[0]
        return None
    return None


def _check_rank(module: Module, findings: List[Finding]) -> None:
    aliases = _spec_aliases(module)
    for call in module.calls:
        if tail_name(call.func) != "device_put" or \
                len(call.args) < 2:
            continue
        sharding = call.args[1]
        if not isinstance(sharding, ast.Call) or \
                tail_name(sharding.func) != "NamedSharding" or \
                len(sharding.args) < 2:
            continue
        spec = sharding.args[1]
        if not isinstance(spec, ast.Call) or \
                tail_name(spec.func) not in aliases:
            continue
        if any(isinstance(a, ast.Starred) for a in spec.args):
            continue
        spec_len = len(spec.args)
        scope = module.enclosing_function(call)
        rank = _static_rank(module, scope, call.args[0])
        if rank is not None and spec_len > rank:
            findings.append(module.finding(
                "SHARD002", call,
                f"PartitionSpec has {spec_len} axes but the operand's "
                f"statically-known rank is {rank}; device_put raises "
                "on rank-mismatched specs"))


def _check_imports(module: Module, findings: List[Finding]) -> None:
    for node in module.nodes:
        if isinstance(node, ast.ImportFrom):
            if (node.module or "").startswith(
                    "jax.experimental.shard_map") or \
                    ((node.module or "") == "jax.experimental" and
                     any(a.name == "shard_map" for a in node.names)):
                findings.append(module.finding(
                    "SHARD003", node,
                    "deprecated jax.experimental.shard_map import; "
                    "use jax.shard_map"))
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith("jax.experimental.shard_map"):
                    findings.append(module.finding(
                        "SHARD003", node,
                        "deprecated jax.experimental.shard_map "
                        "import; use jax.shard_map"))


def _executor_scope(rel: str) -> bool:
    rel = rel.replace("\\", "/")
    if any(rel.startswith(p) for p in _EXECUTOR_PREFIXES):
        return True
    return not any(rel == p.rstrip("/") or rel.startswith(p)
                   for p in _SCAN_PREFIXES)


def _sharded_operand(node: ast.AST) -> bool:
    """True when the expression references the mesh-sharded set: a
    convention name, or a `.kv_caches` attribute read."""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in _SHARDED_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in ("kv_caches",):
            return True
    return False


def _check_host_transfers(module: Module,
                          findings: List[Finding]) -> None:
    rel = module.rel.replace("\\", "/")
    hot_module = rel in _HOT_MODULES
    if not (_executor_scope(module.rel) or hot_module):
        return
    hot = [n for n in module.nodes
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
           and (hot_module or _HOT_NAME.match(n.name))]
    for fn in hot:
        for call in iter_calls(fn):
            if isinstance(call.func, ast.Attribute) and \
                    call.func.attr == "item" and not call.args:
                if _sharded_operand(call.func.value):
                    findings.append(module.finding(
                        "SHARD004", call,
                        f".item() on a mesh-sharded array in hot-path "
                        f"function {fn.name}: a cross-device gather + "
                        "host sync per element"))
                continue
            callee = dotted_name(call.func) or ""
            is_transfer = callee in _TRANSFER_CALLS or \
                tail_name(call.func) == "device_get"
            if is_transfer and call.args and \
                    _sharded_operand(call.args[0]):
                findings.append(module.finding(
                    "SHARD004", call,
                    f"{callee or 'device_get'} of a mesh-sharded "
                    f"array in hot-path function {fn.name}: pulls the "
                    "whole sharded buffer (all-gather + device->host "
                    "copy) every step; keep KV/params device-resident "
                    "and transfer only the packed step results"))


def run(ctx) -> List[Finding]:
    findings: List[Finding] = []
    axes, have_mesh = _declared_axes(ctx.modules)
    for module in ctx.modules:
        if have_mesh:
            for call in _spec_calls(module):
                for axis, node in _spec_axis_literals(module, call):
                    if axis not in axes:
                        findings.append(module.finding(
                            "SHARD001", node,
                            f"PartitionSpec axis {axis!r} is not an "
                            f"axis of any declared mesh "
                            f"({', '.join(sorted(axes))}); GSPMD "
                            "rejects the spec at dispatch"))
        _check_rank(module, findings)
        _check_imports(module, findings)
        _check_host_transfers(module, findings)
    return findings


#: (rule, one-line contract, example) — rendered by `--rules-md`.
RULES = (
    ("SHARD001", "literal PartitionSpec axis (incl. `axis_name=\"sp\"`"
     "-style parameter defaults) that no declared mesh provides — "
     "declarations resolve through named constants like "
     "`ParallelConfig.MESH_AXES`",
     '`P("model")` against `Mesh(..., ("dp", "pp", "sp", "tp"))`'),
    ("SHARD002", "NamedSharding spec with more axes than the "
     "operand\'s statically-known rank",
     '`device_put(jnp.zeros((4, 8)), ... P("dp", None, "tp"))`'),
    ("SHARD003", "deprecated `jax.experimental.shard_map` import "
     "(use `jax.shard_map`)",
     "`from jax.experimental.shard_map import shard_map`"),
    ("SHARD004", "host transfer (`.item()`/`np.asarray`/`device_get`) "
     "of a mesh-sharded array (KV planes, params) in an "
     "executor-scope hot-path function or anywhere in the hot "
     "spec-building modules (lora/layers.py, ops/ring_attention.py)",
     "`np.asarray(kv_caches[0])` in `execute_model`"),
)
