"""Checkpoint weight iteration + device placement.

Reference: `aphrodite/modeling/hf_downloader.py` (hf_model_weights_iterator
`:285`, dummy weights `:377`) and the npcache/safetensors streaming logic.

TPU-first: weights stream tensor-by-tensor from disk (never materializing
the whole checkpoint), are assembled host-side into the model's merged
layout, then `jax.device_put` with NamedShardings places each parameter
directly into its shard — each device only receives its slice, which is
what lets 13B+ load onto small-HBM chips (SURVEY.md §7 "weight-streaming
into shards").
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, Iterator, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.logger import init_logger

logger = init_logger(__name__)

_TORCH_NP_DTYPES = {
    "torch.float16": np.float16,
    "torch.float32": np.float32,
    "torch.int8": np.int8,
    "torch.int32": np.int32,
    "torch.int64": np.int64,
}


def _bf16_to_f32(raw: np.ndarray) -> np.ndarray:
    """View uint16 bfloat16 payload as float32 (numpy lacks bfloat16)."""
    u32 = raw.astype(np.uint32) << 16
    return u32.view(np.float32)


def safetensors_weights_iterator(
        path: str) -> Iterator[Tuple[str, np.ndarray]]:
    """Stream tensors from *.safetensors without torch.

    Parses the safetensors header directly (8-byte length + JSON) and
    memory-maps tensor data, so peak host memory is one tensor.
    """
    files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    for fname in files:
        with open(fname, "rb") as f:
            header_len = int.from_bytes(f.read(8), "little")
            header = json.loads(f.read(header_len))
        data_offset = 8 + header_len
        mm = np.memmap(fname, dtype=np.uint8, mode="r")
        for name, info in header.items():
            if name == "__metadata__":
                continue
            start, end = info["data_offsets"]
            buf = mm[data_offset + start:data_offset + end]
            dtype = info["dtype"]
            shape = info["shape"]
            if dtype == "BF16":
                arr = _bf16_to_f32(
                    np.frombuffer(buf, dtype=np.uint16).reshape(shape))
            elif dtype == "F16":
                arr = np.frombuffer(buf, dtype=np.float16).reshape(shape)
            elif dtype == "F32":
                arr = np.frombuffer(buf, dtype=np.float32).reshape(shape)
            elif dtype == "I64":
                arr = np.frombuffer(buf, dtype=np.int64).reshape(shape)
            elif dtype == "I32":
                arr = np.frombuffer(buf, dtype=np.int32).reshape(shape)
            elif dtype == "I8":
                arr = np.frombuffer(buf, dtype=np.int8).reshape(shape)
            elif dtype == "U8":
                arr = np.frombuffer(buf, dtype=np.uint8).reshape(shape)
            else:
                raise ValueError(f"Unsupported safetensors dtype {dtype}")
            yield name, arr


def torch_bin_weights_iterator(
        path: str) -> Iterator[Tuple[str, np.ndarray]]:
    """Stream tensors from pytorch_model*.bin via torch (CPU)."""
    import torch
    files = sorted(glob.glob(os.path.join(path, "*.bin")))
    for fname in files:
        state = torch.load(fname, map_location="cpu", weights_only=True)
        for name, tensor in state.items():
            if tensor.dtype == torch.bfloat16:
                yield name, tensor.float().numpy()
            else:
                yield name, tensor.numpy()
        del state


def resolve_model_path(model_path: str) -> str:
    """Local dirs/files pass through; anything else resolves via the HF
    hub cache with a per-repo file lock so concurrent server replicas
    download once (reference `hf_downloader.py:89-107` lock +
    snapshot_download)."""
    if os.path.isdir(model_path) or os.path.isfile(model_path):
        return model_path
    from aphrodite_tpu.common import flags
    lock_dir = flags.get_str(
        "APHRODITE_CACHE",
        default=os.path.expanduser("~/.cache/aphrodite"))
    os.makedirs(lock_dir, exist_ok=True)
    lock_path = os.path.join(
        lock_dir, model_path.replace("/", "--") + ".lock")
    if flags.get_bool("APHRODITE_USE_MODELSCOPE"):
        # Reference hf_downloader.py:30-41: ModelScope replaces the HF
        # hub when requested. Same lock: replicas download once.
        try:
            from modelscope.hub.snapshot_download import (
                snapshot_download as ms_snapshot_download)
        except ImportError as e:
            raise ImportError(
                "APHRODITE_USE_MODELSCOPE is set but the modelscope "
                "package is not installed") from e
        with _file_lock(lock_path):
            return ms_snapshot_download(model_path)
    from huggingface_hub import snapshot_download
    with _file_lock(lock_path):
        return snapshot_download(
            model_path,
            allow_patterns=["*.safetensors", "*.bin", "*.json", "*.model",
                            "*.txt"])


class _file_lock:
    """Minimal advisory flock (the reference uses the `filelock`
    package; fcntl avoids the dependency)."""

    def __init__(self, path: str) -> None:
        self._path = path
        self._fd = None

    def __enter__(self):
        import fcntl
        self._fd = open(self._path, "w")
        fcntl.flock(self._fd, fcntl.LOCK_EX)
        return self

    def __exit__(self, *exc):
        import fcntl
        fcntl.flock(self._fd, fcntl.LOCK_UN)
        self._fd.close()


def _np_cache_iterator(model_path: str
                       ) -> Iterator[Tuple[str, np.ndarray]]:
    """Stream from (building on first use) a numpy-memmap cache of a
    torch-bin checkpoint (reference npcache, `hf_downloader.py:307-340`).
    After the one-time conversion, loads never pay torch deserialization
    and tensors arrive memory-mapped."""
    cache_dir = os.path.join(model_path, "np")
    manifest = os.path.join(cache_dir, "weight_names.json")
    os.makedirs(cache_dir, exist_ok=True)
    with _file_lock(os.path.join(cache_dir, "convert.lock")):
        if not os.path.exists(manifest):
            names = []
            for name, arr in torch_bin_weights_iterator(model_path):
                np.save(os.path.join(cache_dir,
                                     name.replace("/", "--")), arr)
                names.append(name)
            with open(manifest, "w") as f:
                json.dump(names, f)
    with open(manifest) as f:
        names = json.load(f)
    for name in names:
        yield name, np.load(
            os.path.join(cache_dir, name.replace("/", "--") + ".npy"),
            mmap_mode="r")


def hf_model_weights_iterator(
    model_path: str,
    load_format: str = "auto",
    gguf_at_rest: bool = False,
) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (name, numpy array) for every checkpoint tensor
    (reference `hf_downloader.py:285-352`)."""
    model_path = resolve_model_path(model_path)
    if model_path.endswith(".gguf") and os.path.isfile(model_path):
        # GGUF single-file checkpoint: with quantization="gguf" the
        # Q4_K/Q8_0 projections stay packed (RawGGUF) for the at-rest
        # kernels; everything else dequantizes at load (reference
        # `hf_downloader.py:293-295`).
        from aphrodite_tpu.modeling.gguf import gguf_weights_iterator
        yield from gguf_weights_iterator(model_path,
                                         at_rest=gguf_at_rest)
        return

    has_safetensors = bool(glob.glob(os.path.join(model_path,
                                                  "*.safetensors")))
    has_bins = bool(glob.glob(os.path.join(model_path, "*.bin")))
    if load_format == "safetensors" or (load_format == "auto" and
                                        has_safetensors):
        if not has_safetensors:
            raise ValueError(
                f"No *.safetensors files found in {model_path}.")
        yield from safetensors_weights_iterator(model_path)
    elif load_format == "npcache":
        has_cache = os.path.exists(
            os.path.join(model_path, "np", "weight_names.json"))
        if not (has_bins or has_cache):
            raise ValueError(
                f"npcache needs *.bin files (or an existing np/ cache) "
                f"in {model_path}.")
        yield from _np_cache_iterator(model_path)
    elif load_format in ("auto", "pt"):
        if not has_bins:
            raise ValueError(
                f"No weight files (*.safetensors / *.bin) found in "
                f"{model_path}.")
        yield from torch_bin_weights_iterator(model_path)
    else:
        raise ValueError(f"Unsupported load format {load_format} for "
                         f"{model_path}")


def initialize_dummy_params(model, seed: int = 0, scale: float = 1e-3,
                            mesh: Optional[Mesh] = None) -> Dict:
    """Small random weights for profiling/benchmarks without a checkpoint
    (reference `--load-format dummy`, `hf_downloader.py:377-391`).

    Quantized integer payloads (packed codes, zero points, int8 rows)
    get random bit patterns too — all-zero codes make every weight a
    per-group constant, which degenerates accuracy-sensitive harnesses
    (the W4A8 drift artifact measured a near-linear model). Index-like
    integer leaves (g_idx) stay zeros: random values there would be
    out-of-range indices, not data.

    With a mesh, each leaf is committed to its NamedSharding as soon as
    it is made, so no device ever holds more than its own shards plus
    one whole leaf (the whole model would not fit beside a KV pool)."""
    specs = model.param_specs() if mesh is not None else {}
    shapes = jax.eval_shape(model.init_params)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, leaf) in zip(keys, flat):
        name = str(path[-1].key) if path else ""
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            arr = jax.random.uniform(k, leaf.shape, leaf.dtype,
                                     minval=-scale, maxval=scale)
        elif name in ("qweight", "qzeros", "qs", "qs8"):
            info = jnp.iinfo(leaf.dtype)
            arr = jax.random.randint(
                k, leaf.shape, info.min, info.max, dtype=leaf.dtype)
        else:
            arr = jnp.zeros(leaf.shape, leaf.dtype)
        if mesh is not None:
            spec = specs.get(str(path[0].key), {}).get(name, P())
            arr = jax.device_put(arr, NamedSharding(mesh, spec))
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def shard_params(
    params_np: Dict[str, Dict[str, np.ndarray]],
    specs: Dict[str, Dict[str, P]],
    mesh: Optional[Mesh],
    dtype: jnp.dtype,
) -> Dict[str, Dict[str, jax.Array]]:
    """device_put each host tensor with its NamedSharding (or to the
    default device when mesh is None). Floating weights cast to the
    compute dtype; integer (quantized) payloads keep their dtype."""
    out: Dict[str, Dict[str, jax.Array]] = {}
    for key, bucket in params_np.items():
        out[key] = {}
        for pname, arr in bucket.items():
            target = dtype if np.issubdtype(arr.dtype, np.floating) \
                else arr.dtype
            if mesh is None:
                out[key][pname] = jnp.asarray(arr, dtype=target)
            else:
                spec = specs.get(key, {}).get(pname, P())
                sharding = NamedSharding(mesh, spec)
                out[key][pname] = jax.device_put(
                    jnp.asarray(arr, dtype=target), sharding)
    return out
