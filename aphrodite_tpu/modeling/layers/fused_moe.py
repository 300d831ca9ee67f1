"""Mixture-of-Experts layer.

Reference: Triton `fused_moe` + `moe_align_block_size`
(`aphrodite/modeling/layers/triton_kernel/fused_moe.py:234,142`,
`kernels/moe/align_block_size_kernel.cu`) and Mixtral's per-expert dense
loop with TP-partitioned experts (`models/mixtral.py:115-161`).

TPU-native design: expert weights live STACKED as [num_experts, in, out]
with the expert axis annotated P("tp") — the expert-parallel partitioning
the reference does by hand with np.array_split becomes a sharding
annotation, and GSPMD inserts the combining all-reduce. Token dispatch is
a dense masked combine:

    out = sum_e weight_e(token) * FFN_e(token)

computed as batched einsum over all experts — but ONLY when experts are
few (<= 4) or sharded over a mesh. Above that, the (token, slot) pairs
sort by assigned expert and run GROUPED matmuls, costing top_k/E of the
dense path's FLOPs — 4x fewer for Mixtral's top-2-of-8 — with no
capacity dropping. What runs where:

- **On one TPU, weights in bfloat16 or float32** (`takes_expert_kernel`):
  the two Pallas kernels of `ops/pallas/grouped_matmul.py` over the
  reference's own `moe_align_block_size` layout: an expert's group
  starts on a multiple of the row tile, every tile belongs to one
  expert, and an expert's three matrices cross HBM once a call; gate,
  up and the activation are one kernel, down the other. A pair whose
  expert is held elsewhere gets no row at all.
- **Everywhere else** (the CPU, widths no block of columns fits):
  three `jax.lax.ragged_dot` over the sorted rows, the sort as the
  alignment and the ragged group sizes as the block boundaries,
  operation for operation what it was before the kernels.

Either way pairs reach the matmuls and return to their tokens by
gathers: every token has exactly top_k pairs, so the rows go back into
a [T, top_k, H] block that is summed over top_k under the routing
weights. Group sizes are a comparison against the expert ids, summed.
Nothing in the grouped path scatters: XLA runs a row scatter on the TPU
one update after another (77 ns a pair at 64 experts, top 6 and 2,048
tokens, PERF.md §6 PR 34). The dense combine remains the mesh path:
expert-axis sharding composes with it through plain GSPMD annotations,
whereas a sharded grouped dispatch needs an all-to-all token exchange
(future work).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.compat import context_tp
from aphrodite_tpu.common.utils import note_kernel_path
from aphrodite_tpu.ops.pallas import grouped_matmul

#: what a call counts into `counts`, in the tuple's order (the last
#: only where the kernels run): `tracing.NAMES`
COUNTED = ("moe.tokens_routed", "moe.experts_touched", "moe.pairs_held",
           "moe.rows_walked")


def takes_expert_kernel(sharded: bool, dtype, hidden_size: int,
                        intermediate_size: int) -> bool:
    """Whether the grouped path is the Pallas kernels
    (`ops/pallas/grouped_matmul.py`): one TPU (the expert axis not
    partitioned over a mesh, no `tp` axis in the mesh the program is
    traced under), weights in bfloat16 or float32, widths of
    whole lanes for which a block of columns fits VMEM. Else it is
    `jax.lax.ragged_dot`. The one predicate: the layer dispatches by
    it, and a model says by it which counters its step programs
    carry."""
    return jax.default_backend() == "tpu" and not sharded and \
        context_tp() == 1 and \
        jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32) and \
        grouped_matmul.takes_shapes(hidden_size, intermediate_size, dtype)


def sum_counts(counts: list, names: Tuple[str, ...]) -> jax.Array:
    """What a step's expert layers counted (`counts`, a tuple a layer
    in `COUNTED`'s order), summed over the layers: int32 `[len(names)]`
    in `names`' order, inside the same program."""
    return jnp.stack([sum(c[COUNTED.index(name)] for c in counts)
                      for name in names])


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


class FusedMoE:
    """Stacked-expert gated MoE (`act(gate) * up`, SwiGLU by default,
    ReGLU with `activation="relu"`) with top-k softmax routing.

    `own_router=False`: the layer holds no router; the caller computes
    the router logits from whatever tensor its architecture routes on
    and passes them to `__call__` (SmallThinker routes on the layer's
    input, before the norm and attention).

    **A share of the experts** (`routed_experts` over `num_experts`):
    the layer HOLDS `num_experts` experts, `first_expert` and the ones
    after it, of the `routed_experts` its router scores: one chip's
    part of an expert-parallel layer. The stacked weights are
    `[num_experts, ...]`, the router `routed_experts` wide, `route()`
    what it always is (over all of them), and only the token-expert
    pairs whose expert is held reach the grouped matmuls; what the
    experts held elsewhere would add is left out, not stood in for.
    A layer that holds every expert it routes over is the layer
    without a share, operation for operation."""

    def __init__(self, num_experts: int, top_k: int, hidden_size: int,
                 intermediate_size: int, *,
                 renormalize: bool = True,
                 activation: str = "silu",
                 own_router: bool = True,
                 routed_experts: Optional[int] = None,
                 first_expert: int = 0,
                 scoring: str = "softmax",
                 selection_bias: bool = False,
                 dtype: jnp.dtype = jnp.bfloat16) -> None:
        self.num_experts = num_experts
        #: the router's width; `num_experts` of them are held here
        self.routed_experts = routed_experts or num_experts
        self.first_expert = first_expert
        if not 0 <= first_expert <= self.routed_experts - num_experts:
            raise ValueError(
                f"FusedMoE holds experts {first_expert} to "
                f"{first_expert + num_experts - 1} of "
                f"{self.routed_experts}")
        self.top_k = top_k
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.renormalize = renormalize
        if activation not in _ACTIVATIONS:
            raise ValueError(f"FusedMoE gates with one of "
                             f"{sorted(_ACTIVATIONS)}, not {activation!r}")
        self.act = _ACTIVATIONS[activation]
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError("FusedMoE scores by 'softmax' or 'sigmoid', "
                             f"not {scoring!r}")
        #: the router's scoring form (`route`), and whether the top-k
        #: is taken over the scores plus a bias an expert (`e_bias`,
        #: float32: the selection's alone, never a weight's)
        self.scoring = scoring
        self.selection_bias = selection_bias
        self.own_router = own_router
        self.dtype = dtype
        # Set by the loader when the expert axis is actually partitioned
        # over a mesh; selects the GSPMD-friendly dense combine.
        self.sharded = False

    # Params: router gate [hidden, E] replicated; experts stacked with
    # the expert axis sharded (expert parallelism).
    def init(self) -> Dict[str, jax.Array]:
        e, h, i = self.num_experts, self.hidden_size, \
            self.intermediate_size
        params = {
            "w_gate": jnp.zeros((e, h, i), dtype=self.dtype),
            "w_up": jnp.zeros((e, h, i), dtype=self.dtype),
            "w_down": jnp.zeros((e, i, h), dtype=self.dtype),
        }
        if self.own_router:
            params["gate"] = jnp.zeros((h, self.routed_experts),
                                       dtype=self.dtype)
        if self.selection_bias:
            params["e_bias"] = jnp.zeros((self.routed_experts,),
                                         dtype=jnp.float32)
        return params

    def specs(self) -> Dict[str, P]:
        specs = {
            "w_gate": P("tp", None, None),
            "w_up": P("tp", None, None),
            "w_down": P("tp", None, None),
        }
        if self.own_router:
            specs["gate"] = P(None, None)
        if self.selection_bias:
            specs["e_bias"] = P(None)
        return specs

    def route(self, router_logits: jax.Array,
              e_bias: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """`(probs [T, E], top_vals [T, k], top_idx [T, k])` of float32
        logits: the softmax over all experts, its `top_k` largest and,
        renormalised, their weights (which is the softmax over the
        `top_k` largest logits alone). The one routing function, in
        its two forms: `scoring="sigmoid"` scores each expert by the
        sigmoid of its own logit, and with `e_bias` `[E]` the `top_k`
        are the largest of score + bias while their weights are the
        scores WITHOUT it (the Ling/Bailing-V2 and DeepSeek-V3 gate)."""
        if self.scoring == "sigmoid":
            probs = jax.nn.sigmoid(router_logits)
        else:
            probs = jax.nn.softmax(router_logits, axis=-1)
        if e_bias is not None:
            _, top_idx = jax.lax.top_k(probs + e_bias, self.top_k)
            top_vals = jnp.take_along_axis(probs, top_idx, axis=-1)
        else:
            top_vals, top_idx = jax.lax.top_k(probs, self.top_k)  # [T, k]
        if self.renormalize:
            top_vals = top_vals / jnp.sum(top_vals, axis=-1,
                                          keepdims=True)
        return probs, top_vals, top_idx

    def __call__(self, params: Dict[str, jax.Array], hidden: jax.Array,
                 router_logits: Optional[jax.Array] = None,
                 counts: Optional[list] = None) -> jax.Array:
        """hidden [..., hidden_size] -> same shape. `router_logits`
        [..., E]: the caller's, in the place of `hidden @ gate`.
        `counts`: a list that gains this call's `(token-expert pairs,
        held experts with a pair, pairs that met a held expert)`, int32
        scalars counted in the program; where the kernels run
        (`kernel_counters`) also the rows of the tiles they walked, in
        `COUNTED`'s order."""
        sharded = self.sharded
        orig_shape = hidden.shape
        x = hidden.reshape(-1, self.hidden_size)          # [T, H]

        if router_logits is None:
            router_logits = (x.astype(jnp.float32) @
                             params["gate"].astype(jnp.float32))  # [T, E]
        probs, top_vals, top_idx = self.route(
            router_logits.reshape(-1, self.routed_experts).astype(
                jnp.float32), params.get("e_bias"))
        share = self.num_experts < self.routed_experts
        if share:
            if sharded:
                raise NotImplementedError(
                    "a share of the experts on a mesh: the exchange "
                    "between shares is not written")
            # A pair's expert by its place among the held ones; a pair
            # of an expert held elsewhere gets the place after the
            # last, so that it sorts behind every group and belongs to
            # none (`_ragged_ffn` drops its row).
            local = top_idx - self.first_expert
            held = (local >= 0) & (local < self.num_experts)
            top_idx = jnp.where(held, local, self.num_experts)
        ragged = (share or self.num_experts > 4) and not sharded
        if ragged or counts is not None:
            # Pairs an expert: each pair's expert compared with every
            # expert id, summed over the pairs.
            group_sizes = jnp.sum(
                top_idx.reshape(-1, 1) == jnp.arange(self.num_experts),
                axis=0, dtype=jnp.int32)                  # [E]
        if counts is not None:
            pairs = jnp.int32(top_idx.size)
            counted = (pairs,
                       jnp.sum(group_sizes > 0, dtype=jnp.int32),
                       jnp.sum(group_sizes) if share else pairs)

        if ragged:
            out, walked = self._ragged_ffn(params, x, top_vals, top_idx,
                                           group_sizes,
                                           held=held if share else None)
            if counts is not None and walked is not None:
                counted += (walked,)
        else:
            out = self._dense_ffn(params, x, probs, top_vals, top_idx)
        if counts is not None:
            counts.append(counted)
        return out.reshape(orig_shape).astype(hidden.dtype)

    @property
    def kernel_counters(self) -> Tuple[str, ...]:
        """What a call counts beyond the first three of `COUNTED`:
        the rows walked, where its grouped path is the Pallas kernels
        (a model adds them to its step programs' counters)."""
        return COUNTED[3:] if takes_expert_kernel(
            self.sharded, self.dtype, self.hidden_size,
            self.intermediate_size) else ()

    def _dense_ffn(self, params, x, probs, top_vals, top_idx):
        # Dense per-token expert weights: [T, E].
        combine = jnp.zeros_like(probs)
        rows = jnp.arange(x.shape[0])[:, None]
        combine = combine.at[rows, top_idx].set(top_vals)

        # All-expert SwiGLU: [E, T, I] intermediates.
        gate = jnp.einsum("th,ehi->eti", x, params["w_gate"])
        up = jnp.einsum("th,ehi->eti", x, params["w_up"])
        act = self.act(gate) * up
        expert_out = jnp.einsum("eti,eih->eth", act, params["w_down"])
        return jnp.einsum("eth,te->th", expert_out,
                          combine.astype(expert_out.dtype))

    def _ragged_ffn(self, params, x, top_vals, top_idx, group_sizes,
                    held=None):
        """Grouped-GEMM dispatch: (token, slot) pairs sort by expert,
        each expert's group of rows multiplies its own weights, and the
        rows return to their pairs' places by a gather, where a token's
        `top_k` rows are summed under its routing weights in float32 —
        the moe_align + fused-GEMM design, with no scatter on either
        side. `held` `[T, k]` (a share of the experts): the pairs that
        have a group. Returns the `[T, H]` float32 sum and, where the
        kernels ran, the rows of the tiles they walked (an int32
        scalar; else None).

        On the kernels' path (`takes_expert_kernel`) the rows are laid
        out so that every tile of `tile` rows belongs to one expert
        and the tiles in use are walked once (`ops/pallas/
        grouped_matmul.py`); a pair that is not `held` has no row (its
        `dest` is row 0, and `_combine` masks it). Else the sort is
        the alignment, `jax.lax.ragged_dot` takes the group sizes as
        the block boundaries, and the pairs that are not `held` lie
        behind the last group, where the grouped matmuls write nothing
        that is read."""
        T = x.shape[0]
        # Pairs in slot-major order: pair p is token p % T in slot
        # p // T, so the rows that come back split into [k, T, H] on the
        # leading axis (a [T, k, H] block would pad k to the TPU's
        # 8-row tile and copy itself into that layout).
        if takes_expert_kernel(self.sharded, self.dtype, self.hidden_size,
                               self.intermediate_size):
            note_kernel_path("expert_matmul", "pallas",
                             "grouped_ffn over tile-aligned groups")
            # rows a tile by the pairs a held expert can expect
            tile = grouped_matmul.row_tile(
                top_idx.size * self.num_experts // self.routed_experts,
                self.num_experts)
            source, dest, tile_expert, tiles_used = \
                grouped_matmul.aligned_layout(top_idx.T.reshape(-1),
                                              group_sizes, tile, T)
            rows = x.at[source].get(mode="promise_in_bounds")
            down = grouped_matmul.grouped_ffn(
                rows, params["w_gate"], params["w_up"], params["w_down"],
                tile_expert, tiles_used, tile=tile, act=self.act)
            return self._combine(down, dest, top_vals, held,
                                 unique=held is None), tiles_used * tile

        note_kernel_path("expert_matmul", "reference", "jax.lax.ragged_dot")
        # `order[i]` is the pair in sorted row i; `dest[p]` is the
        # sorted row of pair p.
        order = jnp.argsort(top_idx.T.reshape(-1))        # [k*T]
        dest = jnp.argsort(order)
        x_sorted = x.at[order % T].get(
            mode="promise_in_bounds")                     # [k*T, H]

        gate = jax.lax.ragged_dot(x_sorted, params["w_gate"],
                                  group_sizes)
        up = jax.lax.ragged_dot(x_sorted, params["w_up"], group_sizes)
        act = (self.act(gate.astype(jnp.float32)) *
               up.astype(jnp.float32)).astype(x.dtype)
        down = jax.lax.ragged_dot(act, params["w_down"], group_sizes)
        return self._combine(down, dest, top_vals, held, unique=True), None

    def _combine(self, down, dest, top_vals, held, unique: bool):
        """The pairs' rows of `down` back at their tokens: a slot's T
        rows come back by one gather (`dest[p]` is the row of pair p,
        slot-major), and the k slots are weighted and added in float32
        with no [k*T, H] float32 array in between."""
        T = top_vals.shape[0]
        weights = top_vals.astype(jnp.float32)
        out = jnp.zeros((T, self.hidden_size), jnp.float32)
        for slot in range(self.top_k):
            rows = down.at[dest[slot * T:(slot + 1) * T]].get(
                unique_indices=unique, mode="promise_in_bounds")
            rows = rows.astype(jnp.float32)
            if held is not None:
                # a row of no group is whatever the matmul left there
                rows = jnp.where(held[:, slot:slot + 1], rows, 0.0)
            out += rows * weights[:, slot:slot + 1]
        return out

    # -- host-side weight placement --

    def load_expert_weight(self, params_np: Dict[str, np.ndarray],
                           which: str, expert_id: int,
                           hf_tensor: np.ndarray) -> None:
        """Place one expert's HF [out, in] tensor into the stacked
        [E, in, out] param (`expert_id` counts among the held)."""
        e = self.num_experts
        if which in ("w_gate", "w_up"):
            full_shape = (e, self.hidden_size, self.intermediate_size)
        else:
            full_shape = (e, self.intermediate_size, self.hidden_size)
        if which not in params_np:
            params_np[which] = np.zeros(full_shape,
                                        dtype=hf_tensor.dtype)
        params_np[which][expert_id] = hf_tensor.T

    def load_gate_weight(self, params_np: Dict[str, np.ndarray],
                         hf_tensor: np.ndarray) -> None:
        params_np["gate"] = np.ascontiguousarray(hf_tensor.T)
