"""TP-shardable linear layers.

Reference semantics: `aphrodite/modeling/layers/linear.py` (ReplicatedLinear
`:79`, ColumnParallelLinear `:132`, MergedColumnParallelLinear `:230`,
QKVParallelLinear `:324`, RowParallelLinear `:452`).

TPU-first difference: there is NO explicit collective code here. Layers are
written with single-device semantics (full shapes, plain matmuls); tensor
parallelism is expressed purely as `PartitionSpec` annotations on the weight
pytree ("tp" mesh axis on the output dim for column-parallel, the input dim
for row-parallel). Under `jit` over a Mesh, GSPMD partitions the matmuls and
inserts the all-reduce that the reference performs manually in
`RowParallelLinear.forward` (`linear.py:562-565`).

Activation shardings are EXPLICIT, not inferred: when the step traces
under a mesh context (`ModelRunner` enters `jax.set_mesh` around every
jitted dispatch), each layer pins its output with
`with_sharding_constraint` — column-parallel outputs sharded "tp" on
the feature dim, row-parallel outputs replicated (which is exactly
where GSPMD must place the per-layer all-reduce the MULTICHIP ICI
cost model priced: o_proj + down_proj, ~2/layer). Without the pins
GSPMD solves a global layout problem whose answer can drift between
compiler versions and batch shapes; with them the collective schedule
is part of the source. Outside a mesh the annotations vanish
(`shard_along` is a no-op), so single-chip programs are unchanged.

Weight layout is [in_features, out_features] (x @ W) — transposed from the
HF/torch [out, in] layout at load time — so the contraction dim is the
leading dim XLA prefers for MXU tiling.

Each layer owns a `weight_loader(param, hf_weight, shard_id)` that places
(possibly stacked) HF checkpoint tensors into the merged parameter, the
same per-param loader pattern as the reference (`linear.py:196-213`).
Quantization plugs in via LinearMethod objects (reference
`LinearMethodBase`, `linear.py:20-38`).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

ParamDict = Dict[str, jax.Array]
SpecDict = Dict[str, P]


def shard_along(x: jax.Array, axis: Optional[str]) -> jax.Array:
    """Pin x's LAST dim to mesh axis `axis` (None = fully replicated)
    when tracing under a mesh that actually partitions that axis;
    identity otherwise (single-chip jit, or a trivial 1-sized axis)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    if axis is not None and mesh.shape.get(axis, 1) <= 1:
        return x
    spec = P() if axis is None else \
        P(*([None] * (x.ndim - 1) + [axis]))
    return jax.lax.with_sharding_constraint(x, spec)


def replicated_specs(params) -> Dict[str, SpecDict]:
    """Every leaf of a parameter tree (`{bucket: {leaf: array}}`, or
    its `jax.eval_shape`) replicated: the specs of a model that one
    chip holds whole."""
    return {key: {name: P(*([None] * leaf.ndim))
                  for name, leaf in bucket.items()}
            for key, bucket in params.items()}


class LinearMethod:
    """Creates and applies the weights of a linear layer.

    The unquantized base class; quant methods (gptq/awq/...) subclass this
    and store packed params (reference `linear.py:20-76`).
    """

    def create_weights(self, in_features: int, out_features: int,
                       dtype: jnp.dtype, bias: bool,
                       out_axis: Optional[str], in_axis: Optional[str]
                       ) -> ParamDict:
        params = {"weight": jnp.zeros((in_features, out_features),
                                      dtype=dtype)}
        if bias:
            params["bias"] = jnp.zeros((out_features,), dtype=dtype)
        return params

    def create_specs(self, bias: bool, out_axis: Optional[str],
                     in_axis: Optional[str]) -> SpecDict:
        """Specs without allocating any arrays (param_specs() runs for
        every layer on the load path)."""
        specs = {"weight": P(in_axis, out_axis)}
        if bias:
            specs["bias"] = P(out_axis)
        return specs

    def apply(self, params: ParamDict, x: jax.Array) -> jax.Array:
        y = x @ params["weight"]
        if "bias" in params:
            y = y + params["bias"]
        return y

    def load_weight(self, params: ParamDict, name: str,
                    hf_tensor: np.ndarray) -> np.ndarray:
        """Convert one HF checkpoint tensor to this method's layout.
        For dense weights: torch [out, in] -> [in, out]. May set
        self.pending_sidecar = {pname: array} for derived params
        (e.g. int8 scales) placed alongside the converted tensor."""
        if name == "weight":
            return np.ascontiguousarray(hf_tensor.T)
        return hf_tensor

    def out_scale(self, name: str) -> int:
        """Divisor applied to output-dim offsets/sizes when placing this
        param into a merged layer (packed quant formats pack several
        output channels per int32)."""
        return 1


class LinearBase:
    """Shared shape/spec bookkeeping. Subclasses set sharding axes."""

    out_axis: Optional[str] = None
    in_axis: Optional[str] = None
    # Activation pin applied to the layer OUTPUT under a mesh context:
    # False = leave GSPMD free (replicated weights put no constraint
    # on the output), else the `shard_along` axis ("tp" for
    # column-parallel, None = replicate-here for row-parallel, which
    # is the explicit all-reduce point).
    out_activation: object = False

    # Number of stacked sub-projections sharing this layer's matmul
    # (qkv = 3, gate_up = 2); LoRA sizes its merged rank by this.
    packed_factor: int = 1

    def __init__(self, in_features: int, out_features: int, *,
                 bias: bool = False, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None) -> None:
        self.in_features = in_features
        self.out_features = out_features
        self.bias = bias
        self.dtype = dtype
        self.linear_method = linear_method or LinearMethod()

    def init(self) -> ParamDict:
        self.linear_method.packed_factor = self.packed_factor
        return self.linear_method.create_weights(
            self.in_features, self.out_features, self.dtype, self.bias,
            self.out_axis, self.in_axis)

    def specs(self) -> SpecDict:
        return self.linear_method.create_specs(self.bias, self.out_axis,
                                               self.in_axis)

    def __call__(self, params: ParamDict, x: jax.Array) -> jax.Array:
        y = self.linear_method.apply(params, x)
        if self.out_activation is not False:
            y = shard_along(y, self.out_activation)
        return y

    def weight_loader(self, params: Dict[str, np.ndarray], name: str,
                      hf_tensor: np.ndarray,
                      shard_id=None) -> None:
        converted = self.linear_method.load_weight(params, name,
                                                   hf_tensor)
        # Methods may store a checkpoint tensor under a different param
        # name (e.g. QuIP's Qidxs decompresses into `weight`).
        rename = getattr(self.linear_method, "pending_rename", None)
        if rename:
            name = rename
            self.linear_method.pending_rename = None
        params[name] = converted
        sidecar = getattr(self.linear_method, "pending_sidecar", None)
        if sidecar:
            params.update(sidecar)
            self.linear_method.pending_sidecar = None


class ReplicatedLinear(LinearBase):
    """Weight replicated on every shard (reference `linear.py:79`)."""


class ColumnParallelLinear(LinearBase):
    """Output dim sharded over the tp axis (reference `linear.py:132`).
    Output activations stay feature-sharded — the following row-parallel
    matmul contracts over that same dim, so no collective lands here."""
    out_axis = "tp"
    out_activation = "tp"


class RowParallelLinear(LinearBase):
    """Input dim sharded over tp; GSPMD inserts the psum the reference
    calls explicitly (`linear.py:562-565`). The output pin to
    replicated is the explicit placement of that all-reduce."""
    in_axis = "tp"
    out_activation = None


class _ShardedLoadMixin(LinearBase):
    """Shared placement of an HF shard into a slice of a merged param."""

    # Param names whose last dim is the (packed) OUTPUT dim. Anything
    # else ("bias", "scales", 1-D) also slices on its last dim; "g_idx"
    # spans the input dim and is shard-invariant.
    _OUT_DIM_2D = ("weight", "qweight", "qzeros", "scales",
                   "lookup_table")

    def _write_shard(self, params: Dict[str, np.ndarray], name: str,
                     converted: np.ndarray, offset: int,
                     size: int) -> None:
        if name == "g_idx":
            params[name] = converted
            return
        div = self.linear_method.out_scale(name)
        offset //= div
        size //= div
        if name == "lookup_table":
            # [out, 16]: output dim is FIRST.
            if name not in params:
                params[name] = np.zeros(
                    (self.out_features,) + converted.shape[1:],
                    dtype=converted.dtype)
            params[name][offset:offset + size] = converted
            return
        if name not in params:
            full_shape = converted.shape[:-1] + \
                (self.out_features // div,)
            params[name] = np.zeros(full_shape, dtype=converted.dtype)
        params[name][..., offset:offset + size] = converted

    def _write_with_sidecar(self, params: Dict[str, np.ndarray],
                            name: str, converted: np.ndarray, offset: int,
                            size: int) -> None:
        self._write_shard(params, name, converted, offset, size)
        sidecar = getattr(self.linear_method, "pending_sidecar", None)
        if sidecar:
            for pname, arr in sidecar.items():
                self._write_shard(params, pname, arr, offset, size)
            self.linear_method.pending_sidecar = None


class MergedColumnParallelLinear(_ShardedLoadMixin, ColumnParallelLinear):
    """Several column-parallel outputs fused in one matmul, e.g. gate+up
    (reference `linear.py:230`). HF ships the pieces separately; the loader
    writes each into its slice of the merged weight."""

    def __init__(self, in_features: int, output_sizes, **kw) -> None:
        self.output_sizes = list(output_sizes)
        self.packed_factor = len(self.output_sizes)
        super().__init__(in_features, sum(self.output_sizes), **kw)

    def weight_loader(self, params: Dict[str, np.ndarray], name: str,
                      hf_tensor: np.ndarray, shard_id=None) -> None:
        converted = self.linear_method.load_weight(params, name, hf_tensor)
        # Methods may store under a different param name (GGUF's raw
        # blocks repack into qweight/qs) — same contract as
        # LinearBase.weight_loader.
        rename = getattr(self.linear_method, "pending_rename", None)
        if rename:
            name = rename
            self.linear_method.pending_rename = None
        if shard_id is None:
            # Whole-tensor load (pre-fused checkpoints): the sidecar
            # params are whole too — store them directly, don't leave
            # them pending (they'd leak into the NEXT layer's shard
            # placement).
            params[name] = converted
            sidecar = getattr(self.linear_method, "pending_sidecar",
                              None)
            if sidecar:
                params.update(sidecar)
                self.linear_method.pending_sidecar = None
            return
        offset = sum(self.output_sizes[:shard_id])
        self._write_with_sidecar(params, name, converted,
                                 offset, self.output_sizes[shard_id])


class QKVParallelLinear(_ShardedLoadMixin, ColumnParallelLinear):
    """Fused QKV projection, column-sharded by attention head
    (reference `linear.py:324`). Loader slices by ('q'|'k'|'v')."""

    packed_factor = 3

    def __init__(self, hidden_size: int, head_size: int, num_heads: int,
                 num_kv_heads: Optional[int] = None, **kw) -> None:
        self.hidden_size = hidden_size
        self.head_size = head_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads if num_kv_heads is not None \
            else num_heads
        out = (num_heads + 2 * self.num_kv_heads) * head_size
        super().__init__(hidden_size, out, **kw)

    def shard_offsets(self) -> Dict[str, Tuple[int, int]]:
        q = self.num_heads * self.head_size
        kv = self.num_kv_heads * self.head_size
        return {"q": (0, q), "k": (q, kv), "v": (q + kv, kv)}

    def split(self, qkv: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        q = self.num_heads * self.head_size
        kv = self.num_kv_heads * self.head_size
        return (qkv[..., :q], qkv[..., q:q + kv], qkv[..., q + kv:])

    def weight_loader(self, params: Dict[str, np.ndarray], name: str,
                      hf_tensor: np.ndarray, shard_id=None) -> None:
        converted = self.linear_method.load_weight(params, name, hf_tensor)
        rename = getattr(self.linear_method, "pending_rename", None)
        if rename:
            name = rename
            self.linear_method.pending_rename = None
        if shard_id is None:
            # Whole-tensor load (fused qkv checkpoints, e.g. GPT-NeoX):
            # consume the sidecar here too — see
            # MergedColumnParallelLinear.weight_loader.
            params[name] = converted
            sidecar = getattr(self.linear_method, "pending_sidecar",
                              None)
            if sidecar:
                params.update(sidecar)
                self.linear_method.pending_sidecar = None
            return
        offset, size = self.shard_offsets()[shard_id]
        self._write_with_sidecar(params, name, converted, offset, size)
