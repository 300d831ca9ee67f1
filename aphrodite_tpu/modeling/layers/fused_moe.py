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
sort by assigned expert and run GROUPED matmuls via `jax.lax.ragged_dot`
(the TPU-native equivalent of the reference's moe_align_block_size +
fused expert GEMM: sorting IS the alignment, the ragged group sizes ARE
the block boundaries), costing top_k/E of the dense path's FLOPs — 4x
fewer for Mixtral's top-2-of-8 — with no capacity dropping. Pairs reach
the matmuls and return to their tokens by a permutation and its inverse,
two gathers: every token has exactly top_k pairs, so the sorted rows go
back into a [T, top_k, H] block that is summed over top_k under the
routing weights. Group sizes are a comparison against the expert ids,
summed. Nothing in the grouped path scatters: XLA runs a row scatter on
the TPU one update after another (77 ns a pair at 64 experts, top 6 and
2,048 tokens, PERF.md §6 PR 34). The dense combine remains the mesh
path: expert-axis sharding composes with it through plain GSPMD
annotations, whereas a sharded ragged dispatch needs an all-to-all
token exchange (future work).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


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
        return params

    def specs(self) -> Dict[str, P]:
        specs = {
            "w_gate": P("tp", None, None),
            "w_up": P("tp", None, None),
            "w_down": P("tp", None, None),
        }
        if self.own_router:
            specs["gate"] = P(None, None)
        return specs

    def route(self, router_logits: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """`(probs [T, E], top_vals [T, k], top_idx [T, k])` of float32
        logits: the softmax over all experts, its `top_k` largest and,
        renormalised, their weights (which is the softmax over the
        `top_k` largest logits alone). The one routing function."""
        probs = jax.nn.softmax(router_logits, axis=-1)
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
        scalars counted in the program."""
        sharded = self.sharded
        orig_shape = hidden.shape
        x = hidden.reshape(-1, self.hidden_size)          # [T, H]

        if router_logits is None:
            router_logits = (x.astype(jnp.float32) @
                             params["gate"].astype(jnp.float32))  # [T, E]
        probs, top_vals, top_idx = self.route(
            router_logits.reshape(-1, self.routed_experts).astype(
                jnp.float32))
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
            counts.append((pairs,
                           jnp.sum(group_sizes > 0, dtype=jnp.int32),
                           jnp.sum(group_sizes) if share else pairs))

        if ragged:
            out = self._ragged_ffn(params, x, top_vals, top_idx,
                                   group_sizes,
                                   held=held if share else None)
        else:
            out = self._dense_ffn(params, x, probs, top_vals, top_idx)
        return out.reshape(orig_shape).astype(hidden.dtype)

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
        each expert's contiguous group of rows multiplies its own
        weights (`jax.lax.ragged_dot`), and the rows return to their
        pairs' places by the inverse permutation, where a token's
        `top_k` rows are summed under its routing weights in float32 —
        the moe_align + fused-GEMM design, with the sort as the
        alignment and no scatter on either side. `held` `[T, k]` (a
        share of the experts): the pairs that have a group; the others
        lie behind the last group, where the grouped matmuls write
        nothing that is read."""
        T = x.shape[0]
        k = self.top_k
        # Pairs in slot-major order: pair p is token p % T in slot
        # p // T, so the rows that come back split into [k, T, H] on the
        # leading axis (a [T, k, H] block would pad k to the TPU's
        # 8-row tile and copy itself into that layout). `order[i]` is
        # the pair in sorted row i; `dest[p]` is the sorted row of
        # pair p.
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

        # A slot's T rows come back by one gather; the k slots are
        # weighted and added in float32 with no [k*T, H] float32 array
        # in between.
        weights = top_vals.astype(jnp.float32)
        out = jnp.zeros((T, self.hidden_size), jnp.float32)
        for slot in range(k):
            rows = down.at[dest[slot * T:(slot + 1) * T]].get(
                unique_indices=True, mode="promise_in_bounds")
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
