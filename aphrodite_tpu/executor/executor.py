"""TPUExecutor: owns the mesh, model, KV cache, and model runner.

TPU-native replacement for the reference's `task_handler/worker.py` +
`engine/ray_tools.py`: where the reference spawns one Ray actor per GPU
and NCCL-broadcasts per-step metadata (`worker.py:187-212`), a TPU slice
is driven by ONE host process whose jitted step function is SPMD over a
`jax.sharding.Mesh` — the control plane collapses into XLA (SURVEY.md
§2.3). Multi-host TPU pods use jax.distributed with the same code.

Memory profiling (reference `profile_num_available_blocks`,
`worker.py:102-143`) becomes: load weights, read the device's memory
stats, and give the KV cache `gpu_memory_utilization` of what remains.
"""
from __future__ import annotations

import gc
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax

from aphrodite_tpu.common import faultinject, tracing
from aphrodite_tpu.common.config import (CacheConfig, DeviceConfig,
                                         ModelConfig, ParallelConfig,
                                         SchedulerConfig,
                                         refuse_for_latent_pages)
from aphrodite_tpu.common.logger import init_logger
from aphrodite_tpu.common.sequence import SequenceGroupMetadata
from aphrodite_tpu.executor.cache_engine import CacheEngine
from aphrodite_tpu.executor.model_runner import (_DECODE_BATCH_BUCKETS,
                                                  ModelRunner, StepHandle)
from aphrodite_tpu.executor.program_store import ProgramStore
from aphrodite_tpu.modeling.loader import get_model

logger = init_logger(__name__)

_GB = 1 << 30
# KV budget on the CPU backend, which reports no memory stats (the
# tests): enough for a few hundred tiny-model pages.
_CPU_CACHE_BYTES = 256 << 20


def require_platform(device_config: DeviceConfig) -> None:
    """Refuse to build an engine on another platform than the one the
    device config resolves to (see DeviceConfig)."""
    want = device_config.resolve()
    dev = jax.devices()[0]
    if dev.platform != want:
        raise RuntimeError(
            f"device {device_config.device_type!r} needs a {want} "
            f"backend, but JAX found platform {dev.platform!r} "
            f"({dev.device_kind}, {len(jax.devices())} device(s)). "
            "To serve from the CPU on purpose, set JAX_PLATFORMS=cpu "
            "or pass --device cpu.")


def build_mesh(parallel_config: ParallelConfig,
               group: Optional[str] = None):
    """Construct the (dp, pp, sp, tp) mesh, or None for one device.

    sp (sequence parallel) sits next to tp on the fast axis ordering so
    ring-attention ppermute hops ride ICI neighbours.

    `group` ("prefill" | "decode") builds one of the disaggregated
    submeshes instead: prefill over devices[0:n_p], decode over
    devices[n_p:world] — contiguous device ranges so each group's
    all-reduces stay on neighbour ICI links and the inter-group handoff
    crosses exactly the one seam between them. Both submeshes carry all
    four axis names so every jitted program's PartitionSpecs resolve
    unchanged against either group."""
    from jax.sharding import Mesh
    devices = jax.devices()
    if len(devices) < parallel_config.world_size:
        raise ValueError(
            f"world_size {parallel_config.world_size} exceeds available "
            f"devices ({len(devices)}).")
    if group is not None:
        assert parallel_config.disagg_split is not None
        n_p, _ = parallel_config.disagg_split
        world = parallel_config.world_size
        sel = devices[:n_p] if group == "prefill" else devices[n_p:world]
        return Mesh(
            np.asarray(sel).reshape(
                parallel_config.group_mesh_shape(group)),
            ParallelConfig.MESH_AXES)
    if parallel_config.world_size == 1:
        return None
    mesh_devices = np.asarray(
        devices[:parallel_config.world_size]).reshape(
            parallel_config.mesh_shape)
    return Mesh(mesh_devices, ParallelConfig.MESH_AXES)


@dataclass(frozen=True)
class Round:
    """What one scheduling round asks of the device: the one argument
    of `TPUExecutor.dispatch_steps`. A round has up to two steps, over
    disjoint rows and pages: its prompt chunks' and its decode rows'.

    `num_steps`, `extra_cap`: the device iterations the decode step
    runs, 1 or a burst's, and each sequence's useful steps of them
    (`AphroditeEngine._burst_steps`). `drafts`: makes the decode step
    a speculative verify step, k+1 rows for a sequence with k drafted
    tokens. `ahead`: the round is dispatched before the one in flight
    is pulled; the handles of that one are `fed_by`, its decode step's
    first, and the rows with a token still on the device take it from
    there."""
    prompt: List[SequenceGroupMetadata] = field(default_factory=list)
    decode: List[SequenceGroupMetadata] = field(default_factory=list)
    blocks_to_swap_in: Dict[int, int] = field(default_factory=dict)
    blocks_to_swap_out: Dict[int, int] = field(default_factory=dict)
    blocks_to_copy: Dict[int, List[int]] = field(default_factory=dict)
    num_steps: int = 1
    extra_cap: Optional[Dict[int, int]] = None
    drafts: Optional[Dict[int, List[int]]] = None
    ahead: bool = False
    fed_by: Tuple[StepHandle, ...] = ()
    #: (from, to) state slots of forks, copied before the steps
    state_copies: List[Tuple[int, int]] = field(default_factory=list)
    #: (window's pages, summary pages) of the windows that pooled page
    #: groups closed, pooled before the steps
    window_closes: list = field(default_factory=list)


class TPUExecutor:
    """Single-replica executor (the engine's only 'worker')."""

    def __init__(
        self,
        model_config: ModelConfig,
        cache_config: CacheConfig,
        parallel_config: ParallelConfig,
        scheduler_config: SchedulerConfig,
        device_config: DeviceConfig,
        lora_config=None,
        tracer: Optional[tracing.Tracer] = None,
    ) -> None:
        # The engine's span accumulators (its own, when built alone).
        self.tracer = tracer or tracing.Tracer()
        self.model_config = model_config
        self.cache_config = cache_config
        self.parallel_config = parallel_config
        self.scheduler_config = scheduler_config
        self.lora_config = lora_config
        require_platform(device_config)

        # Disaggregated serving (TPLA, arxiv 2508.15881): the DECODE
        # group's submesh becomes the primary `self.mesh` (block tables,
        # swaps, sampling state — everything long-lived lives there) and
        # the prefill group gets its own submesh, a resharded copy of
        # the params, and its own runner. Colocated engines keep the
        # classic single full mesh and `prefill_runner is model_runner`.
        self.prefill_mesh = None
        refuse_for_latent_pages(
            cache_config.page_groups, parallel_config.disagg,
            parallel_config.world_size, cache_config.cache_dtype)
        if parallel_config.disagg:
            if cache_config.state_spec is not None:
                raise NotImplementedError(
                    "disagg_split + a model with recurrent state is not "
                    "supported: kv_handoff carries pages, not state")
            if cache_config.page_groups.pooled_window is not None:
                raise NotImplementedError(
                    "disagg_split + a pooled page group is not "
                    "supported: a window's summaries would have to be "
                    "pooled in both pools")
            if lora_config is not None:
                raise NotImplementedError(
                    "disagg_split + LoRA is not supported: adapter "
                    "slots would need mirroring across both groups")
            self.mesh = build_mesh(parallel_config, group="decode")
            self.prefill_mesh = build_mesh(parallel_config,
                                           group="prefill")
            logger.info(
                "Disaggregated mesh: prefill group %s, decode group %s "
                "(%d+%d of %d %s devices); KV handoff over the group "
                "seam", dict(self.prefill_mesh.shape),
                dict(self.mesh.shape), self.prefill_mesh.size,
                self.mesh.size, parallel_config.world_size,
                jax.devices()[0].platform)
        else:
            self.mesh = build_mesh(parallel_config)
            if self.mesh is not None:
                logger.info(
                    "SPMD mesh %s over %d %s devices: weights "
                    "column/row-sharded on tp, KV pages lane(=head)-"
                    "sharded, batch inputs replicated",
                    dict(self.mesh.shape), self.mesh.size,
                    jax.devices()[0].platform)
        logger.info("Loading model %s ...", model_config.model)
        with self.tracer.phase("setup.weights"):
            self.model, self.params = get_model(model_config, self.mesh,
                                                lora_config)
            self.prefill_params = None
            if self.prefill_mesh is not None:
                self.prefill_params = self._stage_prefill_params()

        with self.tracer.phase("setup.kv_pool"):
            self._profile_and_size_cache()
            self.cache_engine = CacheEngine(
                cache_config, model_config, parallel_config, self.mesh,
                prefill_mesh=self.prefill_mesh)
        self.log_device_memory("after load")
        with self.tracer.phase("setup.runner"):
            sp = None
            if self.mesh is not None and \
                    parallel_config.sequence_parallel_size > 1:
                sp = (self.mesh, parallel_config.sp_prefill_threshold)
            self.model_runner = ModelRunner(
                self.model, self.params, model_config, scheduler_config,
                page_size=cache_config.block_size,
                num_slots=self.cache_engine.num_slots,
                mesh=self.mesh,
                kv_scale=self.cache_engine.kv_scale,
                sp=sp,
                kv_cache_dtype=self.cache_engine.dtype,
                tracer=self.tracer,
                num_state_slots=cache_config.num_state_slots,
                program_store=self._open_program_store())
            self.prefill_runner = self.model_runner
            if self.prefill_mesh is not None:
                self.prefill_runner = ModelRunner(
                    self.model, self.prefill_params, model_config,
                    scheduler_config,
                    page_size=cache_config.block_size,
                    num_slots=self.cache_engine.num_slots,
                    mesh=self.prefill_mesh,
                    kv_scale=self.cache_engine.kv_scale,
                    sp=None,
                    kv_cache_dtype=self.cache_engine.dtype,
                    tracer=self.tracer)

            self.lora_manager = None
            if lora_config is not None:
                from aphrodite_tpu.lora.models import layouts_from_model
                from aphrodite_tpu.lora.worker_manager import WorkerLoRAManager
                self.lora_manager = WorkerLoRAManager(
                    lora_config,
                    write_slot_fn=self.model_runner.write_lora_slot,
                    clear_slot_fn=self.model_runner.clear_lora_slot,
                    module_layouts=layouts_from_model(self.model))

    def _open_program_store(self):
        """The store of this engine's step programs, keyed by what
        their traces read of the engine: the published configuration,
        the engine's own, and the quantisation's (read as `get_model`
        read it). None under a mesh, and wherever
        `ProgramStore.open` finds none to keep."""
        if self.mesh is not None or self.prefill_mesh is not None:
            return None
        quantisation = None
        if self.model_config.quantization is not None:
            from aphrodite_tpu.modeling.layers.quantization import (
                get_quantization_config)
            quantisation = get_quantization_config(self.model_config)
        return ProgramStore.open(
            self.model, published=self.model_config.hf_config,
            engine=self.model_config, cache=self.cache_config,
            parallel=self.parallel_config,
            scheduler=self.scheduler_config, lora=self.lora_config,
            quantisation=quantisation)

    @property
    def mesh_shape(self) -> Optional[Tuple[int, int, int, int]]:
        """(dp, pp, sp, tp) of the live mesh, None single-device —
        recorded by the bench harnesses next to every number. Under
        disagg this is the DECODE group's shape; the split itself is in
        parallel_config.disagg_split."""
        if self.mesh is None:
            return None
        return tuple(int(self.mesh.shape[a])
                     for a in ("dp", "pp", "sp", "tp"))

    @property
    def disagg(self) -> bool:
        return self.prefill_mesh is not None

    def _stage_prefill_params(self):
        """Prefill-group weights: leaf-wise reshard of the decode-mesh
        params onto the prefill submesh — same PartitionSpecs,
        different device group. The model is mesh-agnostic and
        resolves sharding at trace time, so both runners share one
        model object and this copy is the only extra weight
        residency the split costs."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def _to_prefill(leaf):
            spec = getattr(leaf.sharding, "spec", P())
            return jax.device_put(
                leaf, NamedSharding(self.prefill_mesh, spec))

        return jax.tree_util.tree_map(_to_prefill, self.params)

    # The disaggregated layout is which runner and which pool a
    # round's prompt step uses: `prefill_runner` and the prefill
    # group's pool. Colocated they're `model_runner` and the one
    # shared pool. The decode step always uses kv_caches.
    def _prompt_pool(self):
        if self.disagg:
            return self.cache_engine.prefill_kv_caches
        return self.cache_engine.kv_caches

    def _set_prompt_pool(self, kv) -> None:
        if self.disagg:
            self.cache_engine.prefill_kv_caches = kv
        else:
            self.cache_engine.kv_caches = kv

    def kv_handoff(self, pages: List[int]) -> int:
        """Flush one round's finished-prefill pages across the group
        seam (no-op when colocated). Called by the engine with exactly
        the block tables of groups whose FINAL prompt chunk ran this
        round — the groups enter decode next round, so end-of-round is
        always in time and the pages are still owned (no free/realloc
        race)."""
        if not self.disagg or not pages:
            return 0
        with self.tracer.span("cache.kv_handoff", pages=len(pages)):
            return self.cache_engine.kv_handoff(pages)

    # -- sizing --

    def _devices(self):
        """The devices this engine's weights and KV pool live on."""
        if self.mesh is None:
            return [jax.devices()[0]]
        devs = list(self.mesh.devices.flat)
        if self.prefill_mesh is not None:
            devs += list(self.prefill_mesh.devices.flat)
        return devs

    def _device_free_memory(self) -> Optional[int]:
        """Free bytes on the fullest device the engine uses, from the
        runtime's own accounting; None on the CPU backend, which keeps
        none. A TPU that reports no limit is an error, not a guess."""
        if jax.devices()[0].platform == "cpu":
            return None
        # The weights and nothing else: what the loader's programs
        # still hold while they run, and what Python has not yet
        # collected, is gone by the time the pool is in use. The page
        # count is a shape of every step program, so a megabyte that
        # comes and goes is a second set of programs where pages are
        # small (PERF.md §6, PR 33: 44,346 or 44,362 pages of 96 KiB).
        jax.block_until_ready(self.params)
        gc.collect()
        free = []
        for dev in self._devices():
            stats = dev.memory_stats()
            if not stats or not stats.get("bytes_limit"):
                raise RuntimeError(
                    f"{dev} reports no memory_stats()['bytes_limit']; "
                    "cannot size the KV pool")
            free.append(int(stats["bytes_limit"] -
                            stats.get("bytes_in_use", 0)))
        return min(free)

    def log_device_memory(self, when: str) -> None:
        """One line with every device's bytes_in_use, so a log reader
        can see that a mesh's devices hold comparable shares."""
        if jax.devices()[0].platform == "cpu":
            return
        used = [int(dev.memory_stats()["bytes_in_use"])
                for dev in self._devices()]
        logger.info("Device memory %s: bytes_in_use=%s", when, used)

    def _profile_and_size_cache(self) -> None:
        block_bytes = CacheEngine.get_cache_block_size(
            self.cache_config, self.model_config, self.parallel_config)
        #: what a token takes of the pool, all layers (the engine's
        #: `aphrodite:kv_cache_bytes_per_token`)
        self.kv_bytes_per_token = block_bytes // \
            self.cache_config.block_size
        if self.cache_config.num_gpu_blocks is not None:
            # Device pool explicitly sized (tests); still derive the host
            # swap pool if unset.
            if self.cache_config.num_cpu_blocks is None:
                self.cache_config.num_cpu_blocks = int(
                    self.cache_config.swap_space_bytes // block_bytes)
            if self.cache_config.state_spec is not None and \
                    self.cache_config.num_state_slots is None:
                self.cache_config.num_state_slots = \
                    self.scheduler_config.max_num_seqs
            return
        free = self._device_free_memory()
        if free is None:
            budget = _CPU_CACHE_BYTES
        else:
            logger.info("Device memory before the KV pool: %d bytes free",
                        free)
            # Weights are already resident; reserve headroom for compiled
            # programs + transient activations, then give the cache the
            # configured fraction of the rest. The dominant transient is
            # the prefill round at max_num_batched_tokens: roughly the
            # gate_up output + silu_mul + qkv/residual streams, ~1.5x
            # overlap (measured: an 8192-token Mistral-7B round peaks
            # ~1.1 GB; 512 MB headroom OOMed by exactly that delta).
            cfg = self.model_config.hf_config
            # (a model of experts alone states no dense width)
            top_k = getattr(cfg, "num_experts_per_tok", 0) or getattr(
                cfg, "moe_num_active_primary_experts", 0)
            inter = getattr(cfg, "intermediate_size", None) or (
                0 if top_k else 4 * cfg.hidden_size)
            tokens = self.scheduler_config.max_num_batched_tokens
            if self.cache_config.page_groups.window is not None:
                # the scheduler writes such a model's prompts in
                # chunks
                tokens = min(tokens, self.scheduler_config.window_chunk_cap)
            act_bytes = int(tokens * (2 * inter + 4 * cfg.hidden_size) *
                            2 * 1.5)
            # Quantized matmuls add XLA-side activation copies on top
            # of the dense estimate. AWQ and GGUF-Q4K un-permute their
            # OUTPUT columns ([tokens, 2*inter]-sized copies — AWQ at
            # 8192-token prefill measured ~2.5 GB over the dense
            # estimate); GPTQ only permutes x (small).
            fudge = {"awq": 2.8, "gguf": 2.3}.get(
                self.model_config.quantization, 1.0)
            act_bytes = int(act_bytes * fudge)
            # MoE ragged dispatch materializes f32 gate/up/act tensors
            # at [tokens * top_k, moe_inter] (layers/fused_moe.py) —
            # for Mixtral shapes that dwarfs the dense estimate. A layer
            # that holds a share of its experts sorts every pair all the
            # same (the pairs of experts held elsewhere lie behind the
            # last group), so its rows are tokens * top_k too.
            if top_k:
                moe_inter = getattr(cfg, "moe_intermediate_size", None) \
                    or getattr(cfg, "moe_ffn_hidden_size", inter)
                act_bytes = max(act_bytes, int(
                    tokens * top_k * moe_inter * 4 * 3 * 1.2))
            headroom = min(free // 2, max(512 << 20, act_bytes))
            budget = int((free - headroom) *
                         self.cache_config.gpu_memory_utilization)
            # The in-place KV scatter keeps a temp copy of one layer's
            # (k, v) pair live during the update; cap the pool so
            # budget * (1 + 1/layers) still fits.
            layers = max(1, self.model_config.get_num_layers(
                self.parallel_config))
            budget = int(budget * layers / (layers + 1))
        budget -= self._size_state_slots(budget, block_bytes)
        num_pages = max(budget // block_bytes, 16)
        self.cache_config.num_gpu_blocks = int(num_pages)
        if self.cache_config.num_cpu_blocks is None:
            self.cache_config.num_cpu_blocks = int(
                self.cache_config.swap_space_bytes // block_bytes)
        logger.info("KV cache: %d device pages, %d host pages "
                    "(%.2f GiB device)", self.cache_config.num_gpu_blocks,
                    self.cache_config.num_cpu_blocks,
                    num_pages * block_bytes / _GB)

    def _size_state_slots(self, budget: int, block_bytes: int) -> int:
        """Pages and state slots share the one budget. The rule: as
        many slots as the largest decode bucket (no more than
        `--max-num-seqs`) whose rows, each at `max_model_len` tokens
        with its slot, fit the budget; the rest of the budget is
        pages. So every slot can be fed pages to the longest context
        the engine admits, and no page waits for a slot that is not
        there. Returns the bytes the slots take as they are allocated
        (`StateSpec.allocated_slot_bytes`; the scratch slot among
        them); 0 for a model without state."""
        spec = self.cache_config.state_spec
        if spec is None:
            return 0
        groups, page = self.cache_config.page_groups, \
            self.cache_config.block_size
        longest = -(-self.model_config.max_model_len // page)
        # a window group holds the window and a page once a row has
        # passed it
        held = -(-(groups.window or 0) // page) + 1
        row_pages = sum(min(longest, held) if kind == "window" else longest
                        for kind in groups.kinds)
        slot_bytes = spec.allocated_slot_bytes
        row_bytes = row_pages * block_bytes + slot_bytes
        fitting = [b for b in _DECODE_BATCH_BUCKETS
                   if b <= self.scheduler_config.max_num_seqs and
                   (b + 1) * row_bytes <= budget]
        slots = max(fitting, default=1)
        self.cache_config.num_state_slots = slots
        taken = (slots + 1) * slot_bytes
        logger.info(
            "State slots: %d of %d bytes each (%.2f GiB with the scratch "
            "slot) from the KV budget of %.2f GiB; a row at %d tokens "
            "holds %d pages of %d bytes",
            slots, slot_bytes, taken / _GB, budget / _GB,
            self.model_config.max_model_len, row_pages, block_bytes)
        return taken

    # -- step execution --

    def _pre_step(self, seq_group_metadata_list, blocks_to_swap_in,
                  blocks_to_swap_out) -> None:
        """Swaps + LoRA activation, once a round."""
        # Every round funnels through here, so one injection point
        # covers the whole device-round surface.
        faultinject.fire("executor.execute_model")
        if blocks_to_swap_out:
            self.cache_engine.swap_out(blocks_to_swap_out)
        if blocks_to_swap_in:
            self.cache_engine.swap_in(blocks_to_swap_in)
        if self.lora_manager is not None and seq_group_metadata_list:
            self.lora_manager.set_active_adapters(
                [md.lora_request for md in seq_group_metadata_list])
            self.model_runner.lora_slot_of = self.lora_manager.slot_of

    def _copy_blocks(self, rnd: Round) -> None:
        """The round's CoW copies, applied to each distinct pool a
        step of it uses (same page ids, idempotent, so the split
        layout's mirrors stay coherent whichever phase forked); a
        round without rows copies in the decode pool."""
        if rnd.state_copies:
            self.cache_engine.kv_caches = self.model_runner.copy_state(
                self.cache_engine.kv_caches, rnd.state_copies)
        if rnd.window_closes:
            self.cache_engine.kv_caches = \
                self.model_runner.summarise_windows(
                    self.cache_engine.kv_caches, rnd.window_closes)
        if not rnd.blocks_to_copy:
            return
        if rnd.prompt:
            self._set_prompt_pool(self.prefill_runner._apply_block_copies(
                self._prompt_pool(), rnd.blocks_to_copy))
            if not rnd.decode or \
                    self._prompt_pool() is self.cache_engine.kv_caches:
                return
        self.cache_engine.kv_caches = self.model_runner._apply_block_copies(
            self.cache_engine.kv_caches, rnd.blocks_to_copy)

    def dispatch_steps(
            self, rnd: Round) -> Optional[Tuple[StepHandle, ...]]:
        """Enqueue a round: the one way into the device. Returns its
        steps' handles, the decode step's first, for `finalize_steps`
        (a synced round is this followed at once by that), and for
        the next round's `fed_by`.

        A synced round enqueues its prompt step, then its decode step
        (a burst behind a prefill consumes its donated KV handles, so
        the device serializes them; on the split layout the two run on
        their own submeshes and pools with NO data dependency and
        genuinely overlap), and ONE host sync collects both: an
        arrival costs its prefill's device time, not a round of its
        own. A step off the fused program (host processors, logprobs,
        best_of>1) runs through the raw-logits route at once, and its
        handle comes back finalised. A combined round whose decode
        step is no burst pulls its prompt step before it enqueues the
        decode step: two syncs (ROADMAP D3).

        A round `ahead` (colocated, no swaps or copies: the engine's
        `_runs_ahead`) enqueues its decode step, then its prompt
        step, both or neither: None, nothing enqueued, when a step is
        off the fused program and the caller must run the round
        synced. The prompt step is prepared on the host only after
        the decode step's program is on the device's queue
        (`ModelRunner.dispatch_steps`), under the device's work and
        not in front of it."""
        self._pre_step(rnd.prompt + rnd.decode, rnd.blocks_to_swap_in,
                       rnd.blocks_to_swap_out)
        self._copy_blocks(rnd)
        if rnd.ahead:
            handles, kv = self.model_runner.dispatch_steps(
                [rnd.decode] + ([rnd.prompt] if rnd.prompt else []),
                self.cache_engine.kv_caches, rnd.fed_by)
            self.cache_engine.kv_caches = kv
            return tuple(handles) if handles else None
        handles: List[StepHandle] = []
        if rnd.prompt:
            handles, kv = self.prefill_runner.dispatch_steps(
                [rnd.prompt], self._prompt_pool(), or_raw=True)
            self._set_prompt_pool(kv)
            if rnd.decode and rnd.num_steps == 1:
                self.finalize_steps(handles)
        if rnd.decode:
            # (Read after the prompt step wrote its pool back:
            # colocated, that is this step's input.)
            (handle,), kv = self.model_runner.dispatch_steps(
                [rnd.decode], self.cache_engine.kv_caches, or_raw=True,
                num_steps=rnd.num_steps, extra_cap=rnd.extra_cap,
                drafts=rnd.drafts)
            self.cache_engine.kv_caches = kv
            handles.insert(0, handle)
        return tuple(handles)

    def finalize_steps(self, handles) -> List[list]:
        """One transfer for every pending step's packed results, and
        each step's outputs (`ModelRunner.finalize_step`), in the
        order of `handles`."""
        pending = [h for h in handles if h.outputs is None]
        if pending:
            pulled = self.model_runner.pull(pending)
            with self.tracer.span("sampler.finalize"):
                for handle, packed in zip(pending, pulled):
                    handle.outputs = self.model_runner.finalize_step(
                        handle, packed)
        return [h.outputs for h in handles]
