"""KV-cache allocation and swap execution.

Reference: `aphrodite/task_handler/cache_engine.py` (alloc `:48-49`, swap
on side stream `:118-134`, copy `:136-146`) and the CUDA cache kernels
(`kernels/cache_kernels.cu`).

TPU-native: per layer the cache is (k_pages, v_pages) arrays of shape
[num_pages, page_size, num_kv_heads * head_dim] — token-major, heads
collapsed into lanes (see ops/kv_cache.py for the layout rationale).
Where the model's layers lie in several page groups
(`common/config.py::PageGroups`) there is a pair for each PLACE in a
group, not for each layer: the layers at one place of the groups share
it, each under its own group's page ids, so one page id is the same
bytes whichever group holds it and one free list serves them all.
A model whose pages are LATENT (`PageGroups.latent`: multi-head
latent attention) has ONE array for each place instead of a pair,
`(pages,)` with `pages` `[num_pages, page_size, lanes]`: a token's row
is its key, its value the row's first `latent` lanes; nothing is
swapped, handed off, partitioned or quantised there
(`common/config.py::LATENT_PAGE_REFUSALS`).
A model that keeps recurrent state beside its pages
(`common/config.py::StateSpec`) has, after those pairs in `kv_caches`,
ONE tuple of state arrays for the model, `[state layers, slots + 1,
...]` each (`StateSpec.allocated`): a sequence's STATE SLOT is the same
row of every layer of all of them, the last row the pad rows' scratch.
They ride through the step programs with the pages, donated and
updated in place, a layer's kernel call indexing its layer of the
whole array; a slot is never zeroed from the host (the program of a
sequence's first chunk starts from zeros).
Swap space is pinned host numpy; swap_in/out are `jax.device_put`/
`device_get` of whole pages — JAX dispatches these asynchronously, which
replaces the reference's dedicated CUDA stream + event machinery.
Copy-on-write page copies run as one fused gather/scatter inside the
jitted step (ops.kv_cache.copy_blocks).

Under a mesh, pages shard over the tp axis on the LANE dim — head
blocks are contiguous lane ranges, so a lane partition IS a head
partition: each chip holds its heads' pages, the direct analog of the
reference's per-worker cache (`cache_engine.py:48`, heads divided by
TP).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.config import CacheConfig, ModelConfig, ParallelConfig
from aphrodite_tpu.common.logger import init_logger

logger = init_logger(__name__)

KVCache = Tuple[jax.Array, jax.Array]


def kv_partition_spec(num_heads: int, mesh: Mesh) -> P:
    """PartitionSpec for one layer's [pages, page, heads*dim] KV plane.

    Lane partition == head partition (heads are contiguous lane
    blocks), so dividing kv heads shard over "tp"; fewer KV heads than
    chips replicate the pages, exactly as the reference replicates KV
    heads when heads < tp (common/config.py:265-273). One function so
    CacheEngine allocation, the model runner's plan, and tests agree
    on the spec by construction."""
    if num_heads % mesh.shape["tp"] == 0:
        return P(None, None, "tp")
    return P(None, None, None)

_CACHE_DTYPES = {
    "auto": None,                 # follow model dtype
    "fp8": jnp.float8_e5m2,
    "int8": jnp.int8,
}

_MODEL_DTYPES = {
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
}


class CacheEngine:
    """Owns the paged KV cache for every layer + the host swap pool."""

    def __init__(
        self,
        cache_config: CacheConfig,
        model_config: ModelConfig,
        parallel_config: ParallelConfig,
        mesh: Optional[Mesh] = None,
        prefill_mesh: Optional[Mesh] = None,
    ) -> None:
        self.cache_config = cache_config
        self.model_config = model_config
        # Disaggregated serving: `mesh` is the DECODE group's submesh
        # (the pool the scheduler's block tables, swaps, and CoW copies
        # live on) and `prefill_mesh` the prefill group's. The two
        # pools mirror ONE logical page-id space — the block manager
        # stays the single allocator, so the ownership ledger and the
        # free seams are unchanged by construction — and kv_handoff()
        # reshards exactly the pages a finished prefill wrote from the
        # prefill pool into the decode pool (a batched cross-submesh
        # device_put over ICI). Colocated engines pass prefill_mesh =
        # None and get the classic single pool.
        self.mesh = mesh
        self.prefill_mesh = prefill_mesh

        self.page_size = cache_config.block_size
        self.num_device_pages = cache_config.num_gpu_blocks
        self.num_host_pages = cache_config.num_cpu_blocks or 0
        assert self.num_device_pages is not None

        self.num_layers = model_config.hf_config.num_hidden_layers
        self.num_kv_heads = model_config.get_total_num_kv_heads()
        # (a pair of page arrays a layer, or a place in a page group)
        self.kv_heads_per_layer = model_config.get_kv_heads_per_slot()
        # Pages store head_dim padded to the 128-lane tile (see
        # ops/kv_cache.padded_head_size) so every head size runs the
        # Pallas decode/write kernels.
        from aphrodite_tpu.ops.kv_cache import padded_head_size
        self.head_size = padded_head_size(model_config.get_head_size())

        model_dtype = _MODEL_DTYPES[model_config.dtype]
        quant = _CACHE_DTYPES[cache_config.cache_dtype]
        self.dtype = quant if quant is not None else model_dtype

        # int8 KV dequant scale: owned here, threaded explicitly through
        # InputMetadata.kv_scale (static field) so jit caches key on it
        # — no process-global (round-2 advisor finding).
        self.kv_scale = 1.0
        if cache_config.cache_dtype == "int8":
            from aphrodite_tpu.common import flags
            from aphrodite_tpu.ops.kv_quant import DEFAULT_KV_SCALE
            # Strict registry read: a typo'd value raises FlagError
            # naming the flag instead of a bare float() ValueError.
            self.kv_scale = flags.get_float(
                "APHRODITE_KV_SCALE", default=DEFAULT_KV_SCALE)

        #: arrays a place holds: a K/V pair, or a latent page's one
        self.arrays_per_page = cache_config.page_groups.arrays_per_page
        #: pairs of page arrays at the head of `kv_caches`
        self.num_page_pairs = len(self.kv_heads_per_layer)
        self.kv_caches: List[KVCache] = self._allocate_device() + \
            self._allocate_state()
        # Prefill-group pool: same page count as the decode pool so the
        # two mirror one logical page space — a handed-off page keeps
        # its id, only its physical residency changes. None when
        # colocated.
        self.prefill_kv_caches: Optional[List[KVCache]] = None
        if self.prefill_mesh is not None:
            self.prefill_kv_caches = self._allocate_prefill_pool()
        # Handoff accounting (read by benchmarks / DISAGG capture):
        # totals survive for the engine lifetime.
        self.handoff_pages_total = 0
        self.handoff_bytes_total = 0
        self.handoff_flushes = 0
        # Host swap pool: per layer [2, pages, page, heads_i*dim] numpy
        # — token-major like the device pages, indexed by page on axis 1
        # (list because DeciLM-style models vary heads per layer).
        # Stored in the CACHE dtype (f32 would double/quadruple host RAM).
        # np.zeros at init reserves only virtual memory — physical pages
        # commit on first write — so this fails fast on absurd sizes
        # without stalling startup or the first preemption.
        self._host_pool: Optional[List[np.ndarray]] = None
        if self.num_host_pages > 0 and self.arrays_per_page == 2:
            self._ensure_host_pool()

    def _ensure_host_pool(self) -> None:
        if self.arrays_per_page != 2:
            raise NotImplementedError(
                "preemption by swap is not supported for a model whose "
                "KV pages are latent: the host pool holds K/V pairs")
        if self._host_pool is None:
            self._host_pool = [
                np.zeros((2, self.num_host_pages, self.page_size,
                          heads * self.head_size),
                         dtype=np.dtype(self.dtype))
                for heads in self.kv_heads_per_layer
            ]

    # -- allocation --

    def _allocate_device(self) -> List[KVCache]:
        def alloc(num_heads: int):
            shape = (self.num_device_pages, self.page_size,
                     num_heads * self.head_size)
            z = jnp.zeros(shape, dtype=self.dtype)
            if self.mesh is not None:
                z = jax.device_put(z, NamedSharding(
                    self.mesh, kv_partition_spec(num_heads, self.mesh)))
            return z

        return [tuple(alloc(heads) for _ in range(self.arrays_per_page))
                for heads in self.kv_heads_per_layer]

    def _allocate_state(self) -> List[tuple]:
        """The state arrays of a model with recurrent state: one tuple
        for the model, an array an entry of the spec with the layers
        leading, zeros (what a slot holds before its first owner
        matters to no program; the scratch slot's to none at all)."""
        spec = self.cache_config.state_spec
        if spec is None:
            return []
        if self.mesh is not None and self.mesh.size > 1:
            raise NotImplementedError(
                "a model with recurrent state is served on one chip: "
                "its state arrays and scan kernels are single-device")
        slots = self.cache_config.num_state_slots
        return [tuple(jnp.zeros((spec.layers, slots + 1) + shape,
                                dtype=jnp.dtype(dtype))
                      for shape, dtype in spec.allocated)]

    def _allocate_prefill_pool(self) -> List[KVCache]:
        """Prefill-group mirror of the device pool.

        Same shapes and page ids as `kv_caches`, placed on the prefill
        submesh with the same `kv_partition_spec` head partition — the
        one-truth spec keeps both pools' lane layout identical, which
        is what lets kv_handoff resolve the cross-submesh device_put as
        a pure ICI reshard (no host bounce, no gather reshuffle)."""
        assert self.prefill_mesh is not None

        def alloc(num_heads: int):
            shape = (self.num_device_pages, self.page_size,
                     num_heads * self.head_size)
            z = jnp.zeros(shape, dtype=self.dtype)
            return jax.device_put(z, NamedSharding(
                self.prefill_mesh,
                kv_partition_spec(num_heads, self.prefill_mesh)))

        return [(alloc(heads), alloc(heads))
                for heads in self.kv_heads_per_layer]

    # -- disaggregated handoff --

    def handoff_page_bytes(self) -> int:
        """Bytes moved over ICI per handed-off page (K+V, all layers)
        — the static price MESHPLAN's handoff domain uses, kept here so
        the ledger and the live path share one formula."""
        elt = np.dtype(self.dtype).itemsize
        per_token = sum(self.kv_heads_per_layer) * self.head_size * elt
        return 2 * self.page_size * per_token

    def kv_handoff(self, pages: List[int]) -> int:
        """Reshard `pages` from the prefill pool into the decode pool.

        Page-granular and batched: one gather per layer-side on the
        prefill submesh, one cross-submesh `device_put` onto the decode
        pool's `kv_partition_spec` sharding (the ICI transfer), one
        scatter into the decode pool at the SAME page ids. The copy is
        idempotent — shared prefix pages may be handed off again by a
        later fork and land bit-identically — and never touches pages
        outside `pages`, so the block manager's ownership ledger and
        free seams stay exact on both pools by construction.

        Returns bytes transferred (0 when colocated or no pages)."""
        if self.prefill_kv_caches is None or not pages:
            return 0
        idx = jnp.asarray(sorted(set(pages)), dtype=jnp.int32)
        n = int(idx.shape[0])
        new_caches: List[KVCache] = []
        for layer, (pk, pv) in enumerate(self.prefill_kv_caches):
            dk, dv = self.kv_caches[layer]
            heads = self.kv_heads_per_layer[layer]
            spec = kv_partition_spec(heads, self.mesh) \
                if self.mesh is not None else None
            planes = []
            for src, dst in ((pk, dk), (pv, dv)):
                slab = jnp.take(src, idx, axis=0)
                if spec is not None:
                    # Explicit target sharding: this device_put IS the
                    # ICI hop between the submeshes.
                    slab = jax.device_put(
                        slab, NamedSharding(self.mesh, spec))
                planes.append(dst.at[idx].set(slab))
            new_caches.append((planes[0], planes[1]))
        self.kv_caches = new_caches
        moved = n * self.handoff_page_bytes()
        self.handoff_pages_total += n
        self.handoff_bytes_total += moved
        self.handoff_flushes += 1
        return moved

    def kv_shardings(self) -> Optional[List[NamedSharding]]:
        """Per-layer NamedSharding of the KV planes (None off-mesh) —
        the explicit spec record tests and the runner's sharding plan
        check against."""
        if self.mesh is None:
            return None
        return [
            NamedSharding(self.mesh, kv_partition_spec(heads, self.mesh))
            for heads in self.kv_heads_per_layer
        ]

    @property
    def num_slots(self) -> int:
        return self.num_device_pages * self.page_size

    # -- swap --

    def swap_out(self, mapping: Dict[int, int]) -> None:
        """Device pages -> host pool (reference swap_out :141)."""
        if not mapping:
            return
        self._ensure_host_pool()
        src = np.fromiter(mapping.keys(), dtype=np.int64)
        dst = np.fromiter(mapping.values(), dtype=np.int64)
        for layer, (k_pages, v_pages) in enumerate(
                self.kv_caches[:self.num_page_pairs]):
            # One bulk gather per side, then a single host transfer in
            # the page dtype (no f32 inflation).
            k_host = np.asarray(jnp.take(k_pages, src, axis=0))
            v_host = np.asarray(jnp.take(v_pages, src, axis=0))
            self._host_pool[layer][0][dst] = k_host
            self._host_pool[layer][1][dst] = v_host

    def swap_in(self, mapping: Dict[int, int]) -> None:
        """Host pool -> device pages (reference swap_in :136)."""
        if not mapping:
            return
        self._ensure_host_pool()
        src = np.fromiter(mapping.keys(), dtype=np.int64)
        dst = np.fromiter(mapping.values(), dtype=np.int64)
        new_caches: List[KVCache] = []
        for layer, (k_pages, v_pages) in enumerate(
                self.kv_caches[:self.num_page_pairs]):
            k_in = jnp.asarray(self._host_pool[layer][0][src],
                               dtype=self.dtype)
            v_in = jnp.asarray(self._host_pool[layer][1][src],
                               dtype=self.dtype)
            k_pages = k_pages.at[dst].set(k_in)
            v_pages = v_pages.at[dst].set(v_in)
            new_caches.append((k_pages, v_pages))
        self.kv_caches = new_caches + self.kv_caches[self.num_page_pairs:]

    @staticmethod
    def get_cache_block_size(cache_config: CacheConfig,
                             model_config: ModelConfig,
                             parallel_config: ParallelConfig) -> int:
        """Bytes per page across all layers (reference
        `cache_engine.py:148-171`), for the profiling -> page-count math.
        Uses TOTAL kv heads: with TP sharding each chip holds
        heads/tp, but it also only gets budget/tp of the pool."""
        from aphrodite_tpu.ops.kv_cache import padded_head_size
        total_heads = sum(model_config.get_kv_heads_per_slot())
        head_size = padded_head_size(model_config.get_head_size())
        if cache_config.cache_dtype in ("fp8", "int8"):
            elt = 1
        elif model_config.dtype == "float32":
            elt = 4
        else:
            elt = 2
        per_token = total_heads * head_size * elt
        return cache_config.page_groups.arrays_per_page * \
            cache_config.block_size * per_token
