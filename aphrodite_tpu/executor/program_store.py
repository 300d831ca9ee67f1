"""Step programs kept on disk by what a trace of them would read.

A restarted server finds every executable it needs in JAX's persistent
compilation cache, and still traces each step program and lowers it to
a module, seconds of host work a program, only to compute the key under
which that cache then finds the executable. The store keeps the
executable itself (`jax.experimental.serialize_executable`) under a key
made of everything a trace would read, so that a later process asks by
that key, loads, and neither traces nor lowers.

`ProgramStore.open` makes the part of the key that holds for a whole
engine (the package's source, the installation, the device, the flags,
the configurations); a `StoredProgram` stands in the place of one
jitted function of the model runner. A call of it costs a
flatten of the small operands and a dictionary lookup; a signature it
meets for the first time is made ready once, from the store or through
the function's own `lower(...).compile()` (which still goes through
JAX's persistent cache), and the `Compiled` object serves the process's
life.

The key errs towards a miss: whatever cannot be described stores
nothing and takes the jitted path. What no trace reads is left out by
name (`_NOT_READ`): a new weight seed or another port must not miss.
Weights and sampling keys are arguments, in the key by shape alone.

The store lies in `programs/` under the directory of JAX's persistent
cache (`cache_dir`) and is off exactly where that cache is off. An
entry is written to a temporary name and renamed; one that cannot be
read, parsed or loaded is a miss and is removed; a directory that
cannot be written stops the writes with one warning. Nothing here is
ever fatal. Deleting the directory is always safe.

Entries are pickles (the executable's own serialisation is one): the
directory deserves the trust the process's own code has.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import hashlib
import json
import os
import pickle
import struct
import time
import zlib
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np
from jax.experimental import serialize_executable

from aphrodite_tpu.common import flags, tracing
from aphrodite_tpu.common.logger import init_logger
from aphrodite_tpu.common.utils import (kernel_paths_noted,
                                        note_kernel_path, random_uuid)

logger = init_logger(__name__)

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MAGIC = b"APHPROG1"
#: magic, the payload's codec, meta bytes, payload bytes as they lie on
#: disk, their crc
_HEADER = struct.Struct("<8sBQQI")
try:
    import zstandard
except ImportError:     # (JAX's own cache makes the same choice)
    zstandard = None
#: fields of the engine's configurations that no trace reads, and that
#: differ between two runs of one deployment (the benchmark gives every
#: run a new `--seed`): a key that held them would never hit
_NOT_READ = frozenset((
    "model", "tokenizer", "download_dir", "seed", "revision",
    "tokenizer_revision", "hf_config", "_name_or_path"))
#: backends on which an executable that JAX's persistent cache loaded
#: serialises again into one that runs. The CPU's does not: it loads,
#: and its first call fails on a function the second serialisation left
#: behind (`Function wrapped_convert not found`); there a program the
#: persistent cache answered is left to that cache.
_RESERIALISES = frozenset(("tpu",))


class Undescribable(Exception):
    """Something a key would have to hold has no description that two
    processes share; nothing is stored."""


def cache_dir() -> Optional[str]:
    """This backend's directory of compiled programs, or None where
    the engine keeps none: JAX's persistent cache lies in it
    (`AphroditeEngine`'s `_enable_compilation_cache`) and the program
    store under it. `APHRODITE_COMPILE_CACHE=<dir>` redirects it, `=0`
    turns both off, and on the CPU both are off unless a directory is
    given (CPU compiles are fast: persisting every tiny program of
    tests and development would only grow the directory)."""
    loc = flags.get_str("APHRODITE_COMPILE_CACHE")
    if loc == "0":
        return None
    if not loc:
        if jax.default_backend() == "cpu":
            return None
        loc = os.path.join(
            os.environ.get("XDG_CACHE_HOME",
                           os.path.expanduser("~/.cache")),
            "aphrodite_tpu", "jax_cache")
    # Executables are compiled for one backend; keep each backend's
    # entries in its own subdirectory.
    return os.path.join(loc, jax.default_backend())


def digest_tree(root: str) -> str:
    """sha256 over every `.py` under `root`, by relative path and
    bytes: a changed byte of any of them is another digest."""
    digest = hashlib.sha256()
    for directory, subdirs, files in os.walk(root):
        subdirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(directory, name)
            digest.update(os.path.relpath(path, root).encode())
            digest.update(b"\0")
            with open(path, "rb") as f:
                digest.update(f.read())
            digest.update(b"\0")
    return digest.hexdigest()


@functools.lru_cache(maxsize=None)
def _package_digest() -> str:
    """The package's own source, read once a process (some 25k lines,
    milliseconds)."""
    return digest_tree(_PACKAGE)


def describe(obj, leave_out: Iterable[str] = ()):
    """`obj` as JSON can hold it, the same in every process that built
    it from the same inputs: numbers, strings, sequences, mappings,
    enums, dtypes, dataclasses and plain objects by their fields
    (`leave_out` names fields of `obj` itself, not of what it holds).
    A function, an array or anything else raises `Undescribable`."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, bytes):
        return obj.hex()
    if isinstance(obj, enum.Enum):
        return f"{type(obj).__name__}.{obj.name}"
    if isinstance(obj, (np.dtype, type)):
        try:
            return f"dtype:{np.dtype(obj)}"
        except TypeError:
            raise Undescribable(repr(obj)) from None
    if isinstance(obj, (list, tuple)):
        return [describe(item) for item in obj]
    if isinstance(obj, (set, frozenset)):
        return sorted(json.dumps(describe(item)) for item in obj)
    if isinstance(obj, dict):
        return {str(k): describe(v)
                for k, v in sorted(obj.items(), key=lambda kv: str(kv[0]))
                if k not in leave_out}
    if callable(getattr(obj, "to_dict", None)):     # a published config
        return describe(obj.to_dict(), leave_out)
    if isinstance(obj, (jax.Array, np.ndarray)):
        raise Undescribable(f"an array of {obj.shape}")
    if dataclasses.is_dataclass(obj):
        fields = {f.name: getattr(obj, f.name)
                  for f in dataclasses.fields(obj)}
    elif hasattr(obj, "__dict__") and not callable(obj):
        fields = vars(obj)
    else:
        raise Undescribable(f"{type(obj).__name__}: {obj!r}"[:120])
    return {"__class__": type(obj).__qualname__,
            **describe(fields, leave_out)}


def _environment() -> dict:
    """What the installation, the device and the process's environment
    give a trace and a compile."""
    import jaxlib
    device = jax.devices()[0]
    return dict(
        source=_package_digest(), jax=jax.__version__,
        jaxlib=jaxlib.__version__, numpy=np.__version__,
        platform_version=device.client.platform_version,
        device_kind=device.device_kind, device_count=jax.device_count(),
        x64=bool(jax.config.jax_enable_x64),
        matmul_precision=jax.config.jax_default_matmul_precision,
        # (but for the flag that says where the store lies: a
        # directory that moves keeps its entries)
        flags={name: os.environ.get(name)
               for name in sorted(flags.registry())
               if name != "APHRODITE_COMPILE_CACHE"},
        env={name: value for name, value in sorted(os.environ.items())
             if name in ("XLA_FLAGS", "LIBTPU_INIT_ARGS") or
             name.startswith("JAX_")})


def _leaf(leaf) -> tuple:
    """One operand as a program's key holds it."""
    if isinstance(leaf, jax.Array):
        return (leaf.shape, str(leaf.dtype), bool(leaf.weak_type),
                bool(leaf.committed), repr(leaf.sharding))
    if isinstance(leaf, (np.ndarray, np.generic)):
        return ("numpy", leaf.shape, str(leaf.dtype))
    if isinstance(leaf, (bool, int, float, complex)):
        return ("python", type(leaf).__name__)     # weakly typed: by type
    raise Undescribable(f"an operand of type {type(leaf).__name__}")


def _glance(leaf) -> tuple:
    """`_leaf` for the lookup every round pays: no string is made."""
    try:
        return (leaf.shape, leaf.dtype, leaf.weak_type, leaf.committed)
    except AttributeError:
        return (type(leaf), np.shape(leaf))


def _pack(payload: bytes) -> Tuple[int, bytes]:
    """(codec, `payload` as it lies on disk): a TPU step program of a
    7B model is 72 MB as serialised and a third of it packed."""
    if zstandard is not None:
        return 2, zstandard.ZstdCompressor(level=1).compress(payload)
    return 1, zlib.compress(payload, 1)


def _unpack(codec: int, packed) -> bytes:
    if codec == 2:
        if zstandard is None:
            raise ValueError("packed with zstandard, which is not here")
        return zstandard.ZstdDecompressor().decompress(packed)
    if codec == 1:
        return zlib.decompress(packed)
    raise ValueError(f"unknown codec {codec}")


class ProgramStore:
    """One engine's view of the directory: where the entries lie and
    the part of every key that the engine's life does not change."""

    def __init__(self, directory: str, context: str) -> None:
        self.directory = directory
        self.context = context
        self.writes = True
        self._said: set = set()
        # (whether JAX's cache answered a build is the listeners' to
        # say: `tracing.last_build_cache`)
        tracing.install_listeners()

    @classmethod
    def open(cls, model, **configs) -> Optional["ProgramStore"]:
        """The store of an engine that serves `model` under `configs`
        (the engine's configuration objects by name), or None: where
        the compile cache is off, where the directory cannot be made,
        for a model whose source the package's digest does not cover,
        and where a configuration cannot be described."""
        root = cache_dir()
        if root is None:
            return None
        if not type(model).__module__.startswith("aphrodite_tpu."):
            logger.info("program store off: %s is not the package's",
                        type(model).__module__)
            return None
        directory = os.path.join(root, "programs")
        try:
            os.makedirs(directory, exist_ok=True)
            context = json.dumps(dict(
                _environment(),
                model_class=f"{type(model).__module__}."
                            f"{type(model).__qualname__}",
                **{name: describe(config, _NOT_READ)
                   for name, config in sorted(configs.items())}),
                sort_keys=True)
        except (OSError, Undescribable) as e:
            logger.warning("program store unavailable: %s", e)
            return None
        return cls(directory, context)

    def first(self, what: str) -> bool:
        """Whether `what` happens to this store for the first time:
        each kind of trouble is logged once."""
        if what in self._said:
            return False
        self._said.add(what)
        return True

    def key(self, name: str, closes_over, statics: dict, args: tuple,
            donate_argnums: Tuple[int, ...]) -> str:
        """The entry's name: a digest of the engine's context, the
        function, its static arguments, its operands' tree (with the
        static fields a node carries in it) and every leaf's shape,
        type, weak-type flag, sharding and commitment, and the donated
        positions. Raises `Undescribable`."""
        leaves, tree = jax.tree_util.tree_flatten(args)
        digest = hashlib.sha256(self.context.encode())
        digest.update(json.dumps(
            [name, describe(closes_over), describe(statics), str(tree),
             [_leaf(leaf) for leaf in leaves], list(donate_argnums)],
            sort_keys=True).encode())
        return digest.hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key + ".program")

    def load(self, key: str, args: tuple):
        """(the entry's `Compiled`, loaded for `args`' device; the
        kernel-path notes its trace made), or None: no such entry, or
        one that cannot be read, parsed or loaded, which is removed."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            return self._discard(path, e)
        # (one chip, no mesh: where the first array operand lies)
        device = next((next(iter(leaf.devices()))
                       for leaf in jax.tree_util.tree_leaves(args)
                       if isinstance(leaf, jax.Array)), jax.devices()[0])
        try:
            magic, codec, meta_len, packed_len, crc = \
                _HEADER.unpack_from(blob)
            body = _HEADER.size + meta_len
            packed = memoryview(blob)[body:body + packed_len]
            if magic != _MAGIC or len(blob) != body + packed_len or \
                    zlib.crc32(packed) != crc:
                raise ValueError("truncated or not an entry")
            meta = pickle.loads(memoryview(blob)[_HEADER.size:body])
            compiled = serialize_executable.deserialize_and_load(
                _unpack(codec, packed),
                jax.tree_util.tree_structure((args, {})),
                meta["out_tree"], execution_devices=[device])
        except Exception as e:      # whatever an entry can hold
            logger.debug("program store: %s: %s", path, e)
            return self._discard(path, e)
        return compiled, meta["notes"]

    def _discard(self, path: str, why: Exception) -> None:
        if self.first("discard"):
            logger.warning(
                "program store: an entry that cannot be used is removed "
                "and built again (said once): %s: %s",
                type(why).__name__, str(why)[:200])
        try:
            os.unlink(path)
        except OSError:
            pass
        return None

    def save(self, key: str, name: str, compiled,
             notes: List[tuple]) -> bool:
        """Keep `compiled` under `key` with the notes its trace made,
        and say whether it is kept: written beside its place and
        renamed into it, so that a reader meets a whole entry or none
        and two servers on one directory do not meet each other's
        halves."""
        if not self.writes:
            return False
        try:
            payload, _, out_tree = serialize_executable.serialize(compiled)
            meta = pickle.dumps(dict(fun=name, out_tree=out_tree,
                                     notes=list(notes)))
            codec, packed = _pack(payload)
        except Exception as e:      # no serialisation for this one
            if self.first("serialise"):
                logger.warning(
                    "program store: a program that cannot be serialised "
                    "is not kept (said once): %s: %s", type(e).__name__,
                    str(e)[:200])
            return False
        path = self._path(key)
        partial = f"{path}.{os.getpid()}.{random_uuid()}.tmp"
        try:
            with open(partial, "wb") as f:
                f.write(_HEADER.pack(_MAGIC, codec, len(meta), len(packed),
                                     zlib.crc32(packed)))
                f.write(meta)
                f.write(packed)
            os.replace(partial, path)
        except OSError as e:
            self.writes = False
            logger.warning("program store: writing stops, the entries "
                           "there are still read: %s", e)
            self._said.add("write")
            try:
                os.unlink(partial)
            except OSError:
                pass
            return False
        return True


class StoredProgram:
    """`jitted` (a `jax.jit` of a function called `name`) behind the
    store: called as the jitted function is, static arguments by
    keyword. `closes_over`: what the function reads of its `self`
    beside the engine's configurations. `stable_argnums`: the operands
    whose trees and shapes the process never changes (the parameters,
    the page arrays), which a round's lookup may skip."""

    def __init__(self, store: ProgramStore, jitted, name: str,
                 closes_over, donate_argnums: Tuple[int, ...] = (),
                 stable_argnums: Tuple[int, ...] = ()) -> None:
        self.store = store
        self.jitted = jitted
        self.name = name
        self.closes_over = closes_over
        self.donate_argnums = tuple(donate_argnums)
        self.stable_argnums = frozenset(stable_argnums)
        #: signature of a call -> what serves it: a `Compiled`, or the
        #: jitted function where no key could be made
        self._ready: Dict[tuple, Callable] = {}

    def __call__(self, *args, **statics):
        leaves, tree = jax.tree_util.tree_flatten(
            [arg for i, arg in enumerate(args)
             if i not in self.stable_argnums])
        signature = (tree, tuple(statics.items()),
                     tuple(map(_glance, leaves)))
        program = self._ready.get(signature)
        if program is None:
            program = self._ready[signature] = self._make_ready(
                args, statics)
        return program(*args)

    def _make_ready(self, args: tuple, statics: dict) -> Callable:
        """What serves a signature met for the first time: the entry
        of the store, loaded, or the function built ONCE through its
        own `lower(...).compile()` and kept (a call of the jitted
        function after that would trace a second time)."""
        store, fun = self.store, f"jit({self.name})"
        try:
            key = store.key(self.name, self.closes_over, statics, args,
                            self.donate_argnums)
        except Undescribable as e:
            if store.first("key " + self.name):
                logger.warning("program store: %s takes the jitted path "
                               "(said once): %s", self.name, e)
            return functools.partial(self.jitted, **statics)
        t0 = time.perf_counter()
        found = store.load(key, args)
        if found is not None:
            compiled, notes = found
            # what the trace did besides making a program
            for note in notes:
                note_kernel_path(*note)
            tracing.store_loaded(fun, time.perf_counter() - t0)
            return compiled
        tracing.BUILDS.count("program.store_miss")
        with kernel_paths_noted() as notes:
            compiled = self.jitted.lower(*args, **statics).compile()
        if tracing.last_build_cache() == "hit" and \
                jax.default_backend() not in _RESERIALISES:
            return compiled
        t0 = time.perf_counter()
        if store.save(key, self.name, compiled, notes):
            logger.info("program stored: fun=%s entry=%s write=%.3f", fun,
                        key[:12], time.perf_counter() - t0)
        return compiled
