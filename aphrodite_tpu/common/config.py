"""Engine configuration objects.

Reference semantics: `aphrodite/common/config.py:19,280,359,407,454,461`
(ModelConfig/CacheConfig/ParallelConfig/SchedulerConfig/DeviceConfig/
LoRAConfig). TPU-first differences:

- dtype defaults to **bfloat16** (MXU-native) instead of float16.
- `ParallelConfig` describes a `jax.sharding.Mesh` (tp/pp/dp axes) instead
  of a Ray/NCCL world; world_size = product of mesh axes.
- `DeviceConfig` selects the jax platform ('tpu'/'cpu') instead of cuda.
- KV-cache quantization accepts 'auto' | 'fp8' | 'int8' (TPU has no e5m2
  load path; fp8 maps to float8_e5m2 arrays, int8 to scaled int8).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import List, Optional, Tuple, Union

from aphrodite_tpu.common.logger import init_logger
from aphrodite_tpu.transformers_utils.config import get_config

logger = init_logger(__name__)

_GB = 1 << 30

# String names avoid importing jax at config time.
_STR_DTYPE_TO_JAX = {
    "half": "float16",
    "float16": "float16",
    "bfloat16": "bfloat16",
    "bf16": "bfloat16",
    "float": "float32",
    "float32": "float32",
}


_DTYPE_BYTES = {"float16": 2, "bfloat16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class PageGroups:
    """Which KV page group each layer's attention belongs to.

    A layer is one of five kinds (and, whatever its kind, its pages
    are K/V pairs or LATENT: `latent` below):

    - `full`: it writes K and V and needs every key of its sequence;
    - `window`: it writes K and V and needs only the newest `window`
      keys;
    - `pooled`: it writes K and V and needs the exact keys of its
      query's own aligned block of `pooled_window` positions and, for
      every block behind that one, ONE pooled key and value for each
      page of tokens (EVA's chunked attention; the chunk is the page).
      A sequence holds two lists for such a group, the current block's
      pages and the summary pages of the blocks behind, and the layer
      attends over the one table `[summary pages ; block's pages]`
      (`processing/block_manager.py`);
    - it **holds nothing** (a state-space layer, a delta-rule layer, a
      gated unit, an MLP: whatever it keeps per sequence is no KV
      page): `group_of_layer` and `slot_of_layer` are -1. Such a layer
      may keep recurrent state in the sequence's state slot
      (`stateful`) while the model's other layers hold pages of either
      kind, latent ones too: a model states `latent` and `stateful`
      together, and is refused what either is refused;
    - it **reads layer k's pages** (a cross-attention layer over
      another layer's K and V): it writes no page, and its group and
      place are layer k's.

    The layers that hold pages are dealt, kind by kind and in order,
    into groups of `layers_per_group` = gcd(full layers, window
    layers), so that every group has the same number of layers and ONE
    free list serves all of them: the pool is `layers_per_group` pairs
    of page arrays, the layer at place `slot_of_layer[l]` of its group
    uses pair `slot_of_layer[l]`, and a page id means the same bytes
    (that page of every pair) whichever group takes it. A sequence
    holds one block table a group; a window group's table lets go of
    the pages its window has passed. A model whose layers are all of
    one kind has one group of all its layers, and its pool is what it
    always was: a pair a layer."""
    kinds: Tuple[str, ...]      # a group: "full" | "window" | "pooled"
    group_of_layer: Tuple[int, ...]     # -1: the layer holds nothing
    slot_of_layer: Tuple[int, ...]
    window: Optional[int] = None        # tokens; None: no window group
    #: a layer that holds nothing keeps recurrent state instead
    #: (`StateSpec`): what follows the pages alone (swap, prefix pins,
    #: bursts, speculative rounds) does not carry it
    stateful: bool = False
    #: tokens of a pooled group's aligned block; None: no pooled group
    pooled_window: Optional[int] = None
    #: the LATENT page kind (multi-head latent attention): a layer's
    #: pages are ONE array `[pages, page, lanes]`, no K/V pair and no
    #: head axis; a token's row is its key (`ModelConfig.
    #: get_head_size()` lanes, padded to the lane tile) and its value
    #: is the first `latent` lanes of that row. None: K/V pairs. What
    #: such pages are refused is `LATENT_PAGE_REFUSALS`.
    latent: Optional[int] = None

    def pooled_pages(self, block_size: int) -> Tuple[int, int]:
        """(pages of a pooled group's full block, summary pages a
        finished block leaves: a pooled key a page of tokens)."""
        block = self.pooled_window // block_size
        return block, block // block_size

    @classmethod
    def of(cls, layer_kinds: List[Union[bool, str, int, None]],
           window: Optional[int], stateful: bool = False,
           pooled_window: Optional[int] = None,
           latent: Optional[int] = None) -> "PageGroups":
        """`layer_kinds[l]`: "window" (or True), "full" (or False),
        "pooled", None for a layer that holds nothing, or the index of
        the earlier layer whose pages layer `l` reads."""
        def kind_of(entry):
            if entry is None or (isinstance(entry, int) and
                                 not isinstance(entry, bool)):
                return entry
            if entry == "pooled":
                if not pooled_window:
                    raise ValueError(
                        "a pooled layer needs pooled_window")
                return entry
            has_window = entry is True or entry == "window"
            return "window" if has_window and window is not None \
                else "full"
        layer_kinds = [kind_of(entry) for entry in layer_kinds]
        n_window = layer_kinds.count("window")
        n_pooled = layer_kinds.count("pooled")
        per = math.gcd(math.gcd(n_window, layer_kinds.count("full")),
                       n_pooled)
        kinds, group_of, slot_of = [], [], []
        open_group = {}                 # kind -> (group, layers in it)
        for kind in layer_kinds:
            if kind is None:
                group_of.append(-1)
                slot_of.append(-1)
                continue
            if not isinstance(kind, str):   # reads layer `kind`'s pages
                if not 0 <= kind < len(group_of) or group_of[kind] < 0:
                    raise ValueError(
                        f"a layer reads the pages of layer {kind}, "
                        "which comes no earlier or holds none")
                group_of.append(group_of[kind])
                slot_of.append(slot_of[kind])
                continue
            group, filled = open_group.get(kind, (None, per))
            if filled == per:
                group, filled = len(kinds), 0
                kinds.append(kind)
            group_of.append(group)
            slot_of.append(filled)
            open_group[kind] = (group, filled + 1)
        return cls(tuple(kinds), tuple(group_of), tuple(slot_of),
                   window if n_window else None, stateful,
                   pooled_window if n_pooled else None, latent)

    @property
    def layers_per_group(self) -> int:
        """Pairs of page arrays: the places of a group."""
        return 1 + max(self.slot_of_layer)

    @functools.cached_property
    def readers(self) -> Tuple[int, ...]:
        """For each group, the layers whose attention reads its pages:
        its own and those that read theirs."""
        return tuple(self.group_of_layer.count(g)
                     for g in range(len(self.kinds)))

    @property
    def plain(self) -> bool:
        """One group of K/V pairs that lets go of nothing, and no state
        beside it: block tables, swap, prefix pins, bursts and
        speculative rounds as ever."""
        return self.kinds == ("full",) and not self.stateful and \
            self.latent is None

    @property
    def arrays_per_page(self) -> int:
        """Arrays a place in a group holds: a K/V pair, or the one
        array of a latent page."""
        return 2 if self.latent is None else 1


#: What a model whose pages are latent (`PageGroups.latent`) is
#: refused, by name, and where: the one list. The first four are the
#: refusals of every model that is not `PageGroups.plain`
#: (`BlockSpaceManager._plain_only`, `AphroditeEngine.add_request`,
#: the engine's burst and speculative eligibility), so a model that
#: keeps recurrent state beside its pages (`PageGroups.stateful`) is
#: refused them at the same places and by the same names, and what
#: such a model is refused beyond them joins at `TPUExecutor.__init__`
#: (`disagg_split`) and `CacheEngine._allocate_state` (a mesh); the
#: last three are `TPUExecutor.__init__`'s (`refuse_for_latent_pages`).
#: A model with both is refused the union, and there is no second list.
LATENT_PAGE_REFUSALS = (
    "preemption by swap", "the prefix cache", "bursts",
    "speculative rounds", "kv_handoff (disagg_split)",
    "a mesh (tp > 1)", "--kv-cache-dtype fp8|int8")


def refuse_for_latent_pages(groups: PageGroups, disagg: bool,
                            world_size: int, cache_dtype: str) -> None:
    """The refusals of `LATENT_PAGE_REFUSALS` that no block table
    decides, at engine build: the handoff and a mesh partition K/V
    pairs by heads, and the 8-bit page types scale a pair."""
    if groups.latent is None:
        return
    for refused, what, why in (
            (disagg, LATENT_PAGE_REFUSALS[4],
             "kv_handoff carries K/V pairs"),
            (world_size > 1, LATENT_PAGE_REFUSALS[5],
             "a latent page has no head axis to partition, and the "
             "decode kernel that reads it is a single-device program"),
            (cache_dtype != "auto", LATENT_PAGE_REFUSALS[6],
             "the latent and its rotary key would need scales of "
             "their own")):
        if refused:
            raise NotImplementedError(
                f"{what} is not supported for a model whose KV pages "
                f"are latent (one array a layer, no K/V pair): {why}")


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """The constant-size recurrent state a model keeps for a sequence
    beside its KV pages: `layers` layers, each holding `shape` values
    of `dtype` a sequence for every entry of `arrays`. A sequence owns
    one STATE SLOT (`processing/block_manager.py`).

    `arrays` says what a slot HOLDS; `allocated` how the device keeps
    it (`executor/cache_engine.py`): ONE array an entry for the whole
    model, `[layers, slots + 1, *allocated shape]`, a slot the same
    row of every layer of all of them and the last row the pad rows'
    scratch."""
    layers: int
    arrays: Tuple[Tuple[Tuple[int, ...], str], ...]   # (shape, dtype)

    @property
    def slot_bytes(self) -> int:
        return self.layers * sum(
            math.prod(shape) * _DTYPE_BYTES[dtype]
            for shape, dtype in self.arrays)

    @property
    def allocated(self) -> Tuple[Tuple[Tuple[int, ...], str], ...]:
        """`arrays` with each entry's rows (its second-minor axis)
        rounded up to a power of two. The device tiles that axis 1, 2,
        4 or 8 rows deep; at a count between them (the three inputs a
        four-tap convolution keeps) its default layout puts the rows
        OUTERMOST instead of padding them, a Pallas operand is
        row-major, and the whole array is re-laid-out around every
        kernel call (`tests/kernels/test_mosaic_compile.py`). The rows
        added lead: the entry's own are the last."""
        return tuple(
            (shape[:-2] + (1 << (shape[-2] - 1).bit_length(), shape[-1]),
             dtype) for shape, dtype in self.arrays)

    @property
    def allocated_slot_bytes(self) -> int:
        """What a slot takes of the device's memory: the budget's."""
        return dataclasses.replace(self, arrays=self.allocated).slot_bytes


class ModelConfig:
    """Model + tokenizer + dtype + max-length configuration.

    Args mirror the reference ModelConfig (`common/config.py:19-110`), minus
    CUDA-specific knobs; `model` may be a local path or HF repo id.
    """

    def __init__(
        self,
        model: str,
        tokenizer: Optional[str] = None,
        tokenizer_mode: str = "auto",
        trust_remote_code: bool = False,
        download_dir: Optional[str] = None,
        load_format: str = "auto",
        dtype: str = "auto",
        seed: int = 0,
        revision: Optional[str] = None,
        tokenizer_revision: Optional[str] = None,
        max_model_len: Optional[int] = None,
        quantization: Optional[str] = None,
        enforce_eager: bool = False,
        max_context_len_to_capture: Optional[int] = None,
        hf_config=None,
    ) -> None:
        self.model = model
        self.tokenizer = tokenizer or model
        self.tokenizer_mode = tokenizer_mode
        self.trust_remote_code = trust_remote_code
        self.download_dir = download_dir
        self.load_format = load_format
        self.seed = seed
        self.revision = revision
        self.tokenizer_revision = tokenizer_revision
        self.quantization = quantization
        self.enforce_eager = enforce_eager
        self.max_context_len_to_capture = max_context_len_to_capture

        self.hf_config = hf_config if hf_config is not None else get_config(
            model, trust_remote_code, revision)
        self.dtype = _get_and_verify_dtype(self.hf_config, dtype)
        self.max_model_len = _get_and_verify_max_len(self.hf_config,
                                                    max_model_len)
        self._verify_load_format()
        self._verify_tokenizer_mode()
        self._verify_quantization()

    def _verify_load_format(self) -> None:
        load_format = self.load_format.lower()
        if load_format not in ("auto", "pt", "safetensors", "npcache",
                               "dummy", "gguf"):
            raise ValueError(
                f"Unknown load format: {self.load_format}. Must be one of "
                "'auto', 'pt', 'safetensors', 'npcache', 'gguf', or 'dummy'.")
        self.load_format = load_format

    def _verify_tokenizer_mode(self) -> None:
        tokenizer_mode = self.tokenizer_mode.lower()
        if tokenizer_mode not in ("auto", "slow"):
            raise ValueError(
                f"Unknown tokenizer mode: {self.tokenizer_mode}. Must be "
                "either 'auto' or 'slow'.")
        self.tokenizer_mode = tokenizer_mode

    def _verify_quantization(self) -> None:
        supported = ("awq", "gptq", "gguf", "squeezellm", "int8", "quip")
        if self.quantization is not None:
            self.quantization = self.quantization.lower()
            if self.quantization not in supported:
                raise ValueError(
                    f"Unknown quantization method: {self.quantization}. "
                    f"Must be one of {supported}.")
        hf_quant_config = getattr(self.hf_config, "quantization_config", None)
        if hf_quant_config is not None:
            hf_quant_method = str(hf_quant_config.get("quant_method",
                                                      "")).lower()
            if self.quantization is None:
                self.quantization = hf_quant_method
            elif self.quantization != hf_quant_method:
                raise ValueError(
                    "Quantization method specified in the model config "
                    f"({hf_quant_method}) does not match the quantization "
                    f"method specified in the `quantization` argument "
                    f"({self.quantization}).")

    def verify_with_parallel_config(
            self, parallel_config: "ParallelConfig") -> None:
        total_num_attention_heads = self.hf_config.num_attention_heads
        tp = parallel_config.tensor_parallel_size
        if total_num_attention_heads % tp != 0:
            raise ValueError(
                f"Total number of attention heads "
                f"({total_num_attention_heads}) must be divisible by "
                f"tensor parallel size ({tp}).")
        total_num_hidden_layers = self.hf_config.num_hidden_layers
        pp = parallel_config.pipeline_parallel_size
        if total_num_hidden_layers % pp != 0:
            raise ValueError(
                f"Total number of hidden layers ({total_num_hidden_layers}) "
                f"must be divisible by pipeline parallel size ({pp}).")
        if parallel_config.disagg_split is not None:
            # Each disagg group is its own tp submesh: every tp-sharded
            # weight dim must divide BOTH group sizes (jax rejects an
            # uneven NamedSharding at device_put time, so fail here
            # with the real constraint instead of mid-load).
            for group, n in zip(("prefill", "decode"),
                                parallel_config.disagg_split):
                if total_num_attention_heads % n != 0:
                    raise ValueError(
                        f"Total number of attention heads "
                        f"({total_num_attention_heads}) must be divisible "
                        f"by the disagg {group} group size ({n}).")

    def get_sliding_window(self) -> Optional[int]:
        return getattr(self.hf_config, "sliding_window", None)

    def get_page_groups(self) -> PageGroups:
        """The layers' page groups: from `sliding_window_layout` and
        `sliding_window_size` where the config has a layer-wise
        pattern, else every layer in one group, a window group where
        the model-wide `sliding_window` is set."""
        cfg = self.hf_config
        kinds = getattr(cfg, "page_layer_kinds", None)
        if kinds is not None:
            # the config states each layer's kind itself
            return PageGroups.of(
                kinds, self.get_sliding_window(),
                stateful=self.get_state_spec() is not None,
                pooled_window=getattr(cfg, "pooled_window", None),
                latent=getattr(cfg, "latent_value_lanes", None))
        layout = getattr(cfg, "sliding_window_layout", None)
        if layout is not None:
            return PageGroups.of([bool(x) for x in layout],
                                 getattr(cfg, "sliding_window_size", None))
        window = self.get_sliding_window()
        return PageGroups.of(
            [window is not None] * cfg.num_hidden_layers, window,
            latent=getattr(cfg, "latent_value_lanes", None))

    def get_vocab_size(self) -> int:
        return self.hf_config.vocab_size

    def get_hidden_size(self) -> int:
        return self.hf_config.hidden_size

    def get_state_spec(self) -> Optional[StateSpec]:
        """What the model keeps for a sequence that is no KV page, as
        its config states it (`state_spec(dtype)`): None for a stack
        of attention layers."""
        stated = getattr(self.hf_config, "state_spec", None)
        if stated is None:
            return None
        layers, arrays = stated(self.dtype)
        return StateSpec(layers=layers, arrays=arrays)

    def get_head_size(self) -> int:
        """(of what the KV pages hold, where that is not the model's
        own head: `paged_head_dim`, `paged_kv_heads`)"""
        paged = getattr(self.hf_config, "paged_head_dim", None)
        if paged:
            return paged
        if hasattr(self.hf_config, "head_dim") and self.hf_config.head_dim:
            return self.hf_config.head_dim
        return (self.hf_config.hidden_size //
                self.hf_config.num_attention_heads)

    def get_total_num_kv_heads(self) -> int:
        """Total KV heads before TP sharding (GQA/MQA aware)."""
        # Falcon-style multi_query flag.
        if getattr(self.hf_config, "multi_query", False):
            return 1
        for attr in ("paged_kv_heads", "n_head_kv", "num_kv_heads",
                     "num_key_value_heads",
                     "multi_query_group_num"):
            value = getattr(self.hf_config, attr, None)
            if value is not None:
                return value
        return self.hf_config.num_attention_heads

    def get_num_kv_heads(self, parallel_config: "ParallelConfig") -> int:
        """KV heads per TP shard (at least 1: replicate if heads < tp)."""
        total = self.get_total_num_kv_heads()
        return max(1, total // parallel_config.tensor_parallel_size)

    def get_kv_heads_per_layer(self) -> list:
        """Per-layer KV head counts (DeciLM-style variable GQA,
        reference models/decilm.py); uniform for everything else."""
        per_layer = getattr(self.hf_config, "num_key_value_heads_per_layer",
                            None)
        if per_layer is not None:
            return list(per_layer)
        return [self.get_total_num_kv_heads()] * \
            self.hf_config.num_hidden_layers

    def get_kv_heads_per_slot(self) -> list:
        """KV heads of each pair of page arrays: a layer's where the
        layers are one page group, else a group's worth of them (the
        groups' layers have to agree in heads, place by place)."""
        heads, groups = self.get_kv_heads_per_layer(), self.get_page_groups()
        per_slot = [heads[groups.slot_of_layer.index(slot)]
                    for slot in range(groups.layers_per_group)]
        for layer, slot in enumerate(groups.slot_of_layer):
            if slot >= 0 and heads[layer] != per_slot[slot]:
                raise ValueError(
                    "page groups need layers of equal KV heads; layer "
                    f"{layer} has {heads[layer]}, its slot {per_slot[slot]}")
        return per_slot

    def get_num_attention_heads(
            self, parallel_config: "ParallelConfig") -> int:
        return (self.hf_config.num_attention_heads //
                parallel_config.tensor_parallel_size)

    def get_num_layers(self, parallel_config: "ParallelConfig") -> int:
        return (self.hf_config.num_hidden_layers //
                parallel_config.pipeline_parallel_size)


class CacheConfig:
    """Paged KV-cache configuration (reference: common/config.py:280-357).

    block_size defaults to 16 like the reference; on TPU, larger pages
    (64-128 tokens) amortize the per-page DMA into VMEM better and are
    worth setting explicitly for long-context serving.
    """

    def __init__(
        self,
        block_size: int = 16,
        gpu_memory_utilization: float = 0.90,
        swap_space: float = 4,
        cache_dtype: str = "auto",
        sliding_window: Optional[int] = None,
        page_groups: Optional[PageGroups] = None,
        state_spec: Optional[StateSpec] = None,
    ) -> None:
        self.block_size = block_size
        self.gpu_memory_utilization = gpu_memory_utilization
        self.swap_space_bytes = int(swap_space * _GB)
        self.cache_dtype = cache_dtype
        # (`sliding_window` alone: one group of every layer, as a
        # model-wide window is)
        self.page_groups = page_groups or PageGroups.of(
            [sliding_window is not None], sliding_window)
        self.sliding_window = self.page_groups.window
        self._verify_args()
        self._verify_cache_dtype()

        #: what the model keeps for a sequence beside its pages
        self.state_spec = state_spec
        if (state_spec is not None) != self.page_groups.stateful:
            raise ValueError("a model's recurrent state comes with "
                             "page groups that say so, and only then")

        # Set after profiling:
        self.num_gpu_blocks: Optional[int] = None
        self.num_cpu_blocks: Optional[int] = None
        #: state slots (None: the model keeps no state)
        self.num_state_slots: Optional[int] = None

    def _verify_args(self) -> None:
        if self.gpu_memory_utilization > 1.0:
            raise ValueError(
                "HBM memory utilization must be less than 1.0. Got "
                f"{self.gpu_memory_utilization}.")
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        pooled = self.page_groups.pooled_window
        if pooled is not None and pooled % self.block_size ** 2:
            # a pooled key stands for one page of tokens, and a
            # finished block's pooled keys fill whole pages
            raise ValueError(
                f"a pooled page group's window ({pooled}) has to be a "
                f"multiple of block_size squared ({self.block_size}^2)")

    def _verify_cache_dtype(self) -> None:
        if self.cache_dtype not in ("auto", "fp8", "fp8_e5m2", "int8"):
            raise ValueError(
                f"Unknown kv cache dtype: {self.cache_dtype}. Must be one of "
                "'auto', 'fp8', 'fp8_e5m2', 'int8'.")
        if self.cache_dtype == "fp8_e5m2":
            self.cache_dtype = "fp8"

    def verify_with_parallel_config(
            self, parallel_config: "ParallelConfig") -> None:
        total_cpu_memory = _get_total_host_memory()
        num_replicas = parallel_config.tensor_parallel_size
        required = num_replicas * self.swap_space_bytes
        if required > 0.7 * total_cpu_memory:
            raise ValueError(
                "Too large swap space. "
                f"{required / _GB:.2f} GiB out of the "
                f"{total_cpu_memory / _GB:.2f} GiB total CPU memory is "
                "allocated for the swap space.")
        elif required > 0.4 * total_cpu_memory:
            logger.warning(
                "Possibly too large swap space. %.2f GiB out of the %.2f GiB "
                "total CPU memory is allocated for the swap space.",
                required / _GB, total_cpu_memory / _GB)


class ParallelConfig:
    """Mesh-axis sizes for the SPMD step function.

    Replaces the reference's Ray/NCCL world description
    (`common/config.py:359-405`): tp/sp/dp are named axes of one
    `jax.sharding.Mesh`; collectives ride ICI within a slice and DCN across
    slices (XLA picks based on mesh topology). Pipeline parallelism is NOT
    implemented — like the reference (`config.py:392-394`) pp>1 raises
    below, rather than silently building a mesh axis no PartitionSpec uses.
    """

    def __init__(
        self,
        pipeline_parallel_size: int = 1,
        tensor_parallel_size: int = 1,
        data_parallel_size: int = 1,
        worker_use_ray: bool = False,  # accepted for CLI parity; unused
        max_parallel_loading_workers: Optional[int] = None,
        disable_custom_all_reduce: bool = False,
        sequence_parallel_size: int = 1,
        sp_prefill_threshold: int = 1024,
        disagg_split: Optional[Tuple[int, int]] = None,
    ) -> None:
        self.pipeline_parallel_size = pipeline_parallel_size
        self.tensor_parallel_size = tensor_parallel_size
        self.data_parallel_size = data_parallel_size
        self.max_parallel_loading_workers = max_parallel_loading_workers
        self.disable_custom_all_reduce = disable_custom_all_reduce
        # Disaggregated prefill/decode (TPLA, arxiv 2508.15881): split
        # the tp chips into a (prefill, decode) group pair — e.g.
        # (2, 6) of 8 — each its own submesh. Prefill-phase programs
        # compile against the prefill submesh, decode/burst/spec-verify
        # against the decode submesh, and finished prefills hand their
        # KV pages off over ICI (CacheEngine.kv_handoff). None =
        # colocated (the classic single mesh).
        self.disagg_split = tuple(disagg_split) if disagg_split else None
        # Sequence/context parallelism: prompts whose (padded) length is
        # >= sp_prefill_threshold run prefill attention as a ring over
        # the sp mesh axis (ops/ring_attention.py) — K/V shards rotate
        # via ppermute on ICI, peak per-chip activation memory is
        # O(seq/sp). Beyond the reference's capabilities (it has no
        # SP/CP at all, SURVEY.md §2.3); decode stays on tp.
        self.sequence_parallel_size = sequence_parallel_size
        self.sp_prefill_threshold = sp_prefill_threshold
        self.world_size = (pipeline_parallel_size * tensor_parallel_size *
                           data_parallel_size * sequence_parallel_size)
        self._verify_args()

    #: Mesh axis names, in executor.build_mesh's construction order.
    MESH_AXES = ("dp", "pp", "sp", "tp")

    @property
    def mesh_shape(self) -> tuple:
        """(dp, pp, sp, tp) — ONE source of truth for the mesh layout,
        shared by executor.build_mesh and the bench harnesses' output
        JSON (so every capture records the topology it ran on)."""
        return (self.data_parallel_size, self.pipeline_parallel_size,
                self.sequence_parallel_size, self.tensor_parallel_size)

    @property
    def disagg(self) -> bool:
        """Whether the engine serves disaggregated (split submeshes)."""
        return self.disagg_split is not None

    def group_mesh_shape(self, group: str) -> tuple:
        """(dp, pp, sp, tp) of one disagg submesh ("prefill" or
        "decode") — same axis names as the colocated mesh so every
        PartitionSpec in the tree resolves unchanged on either group."""
        assert self.disagg_split is not None
        n = self.disagg_split[0 if group == "prefill" else 1]
        return (1, 1, 1, n)

    @staticmethod
    def parse_disagg_split(spec: Optional[str]
                           ) -> Optional[Tuple[int, int]]:
        """Parse a "2,6"-style split spec (CLI / APHRODITE_DISAGG);
        empty or None disables the split."""
        if not spec:
            return None
        parts = [p.strip() for p in str(spec).split(",")]
        if len(parts) != 2:
            raise ValueError(
                f"disagg split must be 'n_prefill,n_decode', got {spec!r}")
        return (int(parts[0]), int(parts[1]))

    def _verify_args(self) -> None:
        for name, value in (
            ("pipeline_parallel_size", self.pipeline_parallel_size),
            ("tensor_parallel_size", self.tensor_parallel_size),
            ("data_parallel_size", self.data_parallel_size),
            ("sequence_parallel_size", self.sequence_parallel_size),
        ):
            if value < 1:
                raise ValueError(f"{name} must be >= 1, got {value}.")
        if self.pipeline_parallel_size > 1:
            raise NotImplementedError(
                "Pipeline parallelism is not supported yet: no PartitionSpec "
                "uses the pp mesh axis, so pp>1 would allocate chips that do "
                "no work. Shard with tensor_parallel_size and/or "
                "sequence_parallel_size instead.")
        if self.disagg_split is not None:
            n_p, n_d = self.disagg_split
            if n_p < 1 or n_d < 1:
                raise ValueError(
                    f"disagg_split groups must be >= 1 chip each, got "
                    f"{self.disagg_split}.")
            if n_p + n_d != self.tensor_parallel_size:
                raise ValueError(
                    f"disagg_split {self.disagg_split} must sum to "
                    f"tensor_parallel_size ({self.tensor_parallel_size}): "
                    "the split partitions the tp chips into a prefill "
                    "group and a decode group.")
            if (self.data_parallel_size, self.pipeline_parallel_size,
                    self.sequence_parallel_size) != (1, 1, 1):
                raise NotImplementedError(
                    "disagg_split composes with tensor parallelism only "
                    "(dp = pp = sp = 1): each group is a pure-tp "
                    "submesh.")


class SchedulerConfig:
    """Continuous-batching budgets (reference: common/config.py:407-452)."""

    def __init__(
        self,
        max_num_batched_tokens: Optional[int],
        max_num_seqs: int,
        max_model_len: int,
        max_paddings: int,
        multi_step: int = 1,
        max_chunk_tokens: Optional[int] = None,
    ) -> None:
        if max_num_batched_tokens is not None:
            self.max_num_batched_tokens = max_num_batched_tokens
        else:
            # Reasonable prefill budget; at least one full-length prompt.
            self.max_num_batched_tokens = max(max_model_len, 2048)
        self.max_num_seqs = max_num_seqs
        self.max_model_len = max_model_len
        self.max_paddings = max_paddings
        # Decode steps per scheduling round (>1 = device-side multi-step
        # decode with token feedback; eligibility checked per batch).
        self.multi_step = max(1, multi_step)
        # Prefill-token cap for rounds that ALSO carry decode work
        # (chunked prefill): bounds how long an arrival can stall the
        # decode stream. Pure-prefill rounds (nothing running) use the
        # full max_num_batched_tokens budget. 0 disables mixing —
        # prompts then wait for a dedicated round like the reference.
        self.max_chunk_tokens = max_chunk_tokens \
            if max_chunk_tokens is not None else 2048
        self._verify_args()

    @property
    def window_chunk_cap(self) -> int:
        """The longest prompt chunk of a model with a window page
        group, whatever else the round holds (`Scheduler`; the
        executor's headroom counts on it)."""
        return self.max_chunk_tokens or self.max_num_batched_tokens

    def _verify_args(self) -> None:
        if self.max_num_batched_tokens < self.max_model_len:
            raise ValueError(
                f"max_num_batched_tokens ({self.max_num_batched_tokens}) is "
                f"smaller than max_model_len ({self.max_model_len}). "
                "This effectively limits the maximum sequence length to "
                "max_num_batched_tokens and makes the scheduler reject "
                "longer sequences.")
        if self.max_num_batched_tokens < self.max_num_seqs:
            raise ValueError(
                f"max_num_batched_tokens ({self.max_num_batched_tokens}) "
                "must be greater than or equal to max_num_seqs "
                f"({self.max_num_seqs}).")


class DeviceConfig:
    """The JAX platform the engine must find. "tpu" and "cpu" are
    demands; "auto" means the TPU unless JAX itself was pinned to other
    platforms (`JAX_PLATFORMS`), which is how the CPU tests run. The
    executor refuses to build on any other platform than the resolved
    one, so a missing chip is an error and never a silent CPU server."""

    def __init__(self, device: str = "auto") -> None:
        if device not in ("auto", "tpu", "cpu"):
            raise ValueError(f"Unknown device: {device}. "
                             "Must be 'auto', 'tpu', or 'cpu'.")
        self.device_type = device

    def resolve(self) -> str:
        if self.device_type != "auto":
            return self.device_type
        import jax
        pinned = jax.config.jax_platforms
        return "cpu" if pinned and "tpu" not in pinned.split(",") \
            else "tpu"


class LoRAConfig:
    """Multi-LoRA serving limits (reference: common/config.py:461-520)."""

    SUPPORTED_RANKS = (8, 16, 32, 64)

    def __init__(
        self,
        max_lora_rank: int = 16,
        max_loras: int = 1,
        max_cpu_loras: Optional[int] = None,
        lora_extra_vocab_size: int = 256,
        lora_dtype: Optional[str] = None,
    ) -> None:
        self.max_lora_rank = max_lora_rank
        self.max_loras = max_loras
        self.max_cpu_loras = max_cpu_loras
        self.lora_extra_vocab_size = lora_extra_vocab_size
        self.lora_dtype = lora_dtype
        self._verify_args()

    def _verify_args(self) -> None:
        if self.max_lora_rank not in self.SUPPORTED_RANKS:
            raise ValueError(f"max_lora_rank ({self.max_lora_rank}) must be "
                             f"one of {self.SUPPORTED_RANKS}.")
        if self.max_loras < 1:
            raise ValueError(f"max_loras ({self.max_loras}) must be >= 1.")
        if self.max_cpu_loras is None:
            self.max_cpu_loras = self.max_loras
        elif self.max_cpu_loras < self.max_loras:
            raise ValueError(
                f"max_cpu_loras ({self.max_cpu_loras}) must be >= "
                f"max_loras ({self.max_loras}).")

    def verify_with_model_config(self, model_config: ModelConfig) -> None:
        if self.lora_dtype in (None, "auto"):
            self.lora_dtype = model_config.dtype

    def verify_with_scheduler_config(
            self, scheduler_config: SchedulerConfig) -> None:
        if scheduler_config.max_num_batched_tokens > 65528:
            raise ValueError(
                "Due to limitations of the LoRA gather kernel, "
                "max_num_batched_tokens must be <= 65528 when "
                "LoRA is enabled.")


def _get_total_host_memory() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 64 * _GB


def _get_and_verify_dtype(hf_config, dtype: Union[str, "object"]) -> str:
    """Resolve 'auto' to a concrete dtype string.

    TPU-first: 'auto' maps float16-trained checkpoints to bfloat16 (the MXU
    native dtype; fp16 has no performance benefit on TPU and narrower
    exponent range).
    """
    config_dtype = getattr(hf_config, "torch_dtype", None)
    config_dtype = str(config_dtype).replace("torch.", "") if config_dtype \
        else "float32"

    if isinstance(dtype, str):
        dtype = dtype.lower()
        if dtype == "auto":
            if config_dtype in ("float16", "float32"):
                resolved = "bfloat16" if config_dtype == "float16" \
                    else "float32"
            else:
                resolved = config_dtype
        else:
            if dtype not in _STR_DTYPE_TO_JAX:
                raise ValueError(f"Unknown dtype: {dtype}")
            resolved = _STR_DTYPE_TO_JAX[dtype]
    else:
        raise ValueError(f"Unknown dtype: {dtype}")

    if resolved not in ("float16", "bfloat16", "float32"):
        raise ValueError(f"Unsupported compute dtype: {resolved}")
    if resolved == "float16":
        logger.info("float16 requested; note bfloat16 is the native TPU "
                    "dtype and is recommended.")
    return resolved


def _get_and_verify_max_len(hf_config,
                            max_model_len: Optional[int]) -> int:
    """Derive max model length from HF config (reference config.py:560-626),
    including RoPE-scaling multipliers and auto-extension."""
    derived_max_model_len = float("inf")
    possible_keys = [
        "max_position_embeddings",
        "n_positions",
        "max_seq_len",
        "seq_length",
        "max_sequence_length",
        "max_seq_length",
        "seq_len",
    ]
    for key in possible_keys:
        max_len_key = getattr(hf_config, key, None)
        if max_len_key is not None:
            derived_max_model_len = min(derived_max_model_len, max_len_key)
    if derived_max_model_len == float("inf"):
        if max_model_len is not None:
            return max_model_len
        default_max_len = 2048
        logger.warning(
            "The model's config.json does not contain any of the following "
            "keys to determine the original maximum length of the model: "
            "%s. Assuming the model's maximum length is %d.", possible_keys,
            default_max_len)
        derived_max_model_len = default_max_len

    rope_scaling = getattr(hf_config, "rope_scaling", None)
    if rope_scaling is not None:
        factor = rope_scaling.get("factor", 1.0)
        scaling_type = rope_scaling.get("type",
                                        rope_scaling.get("rope_type", ""))
        if scaling_type == "yarn":
            derived_max_model_len = rope_scaling.get(
                "original_max_position_embeddings", derived_max_model_len)
        derived_max_model_len *= factor

    if max_model_len is None:
        return int(derived_max_model_len)
    if max_model_len > derived_max_model_len:
        # Auto-enable dynamic rope scaling to honor the request
        # (reference: config.py:607-626).
        scaling_factor = max_model_len / derived_max_model_len
        logger.warning(
            "Requested max_model_len %d exceeds the derived maximum %d; "
            "enabling dynamic RoPE scaling with factor %.2f.", max_model_len,
            int(derived_max_model_len), scaling_factor)
        hf_config.rope_scaling = {
            "type": "dynamic",
            "factor": scaling_factor,
        }
    return int(max_model_len)
