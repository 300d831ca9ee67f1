"""Seeds SHARD003: the deprecated `jax.experimental.shard_map` import
path (the supported spelling is `jax.shard_map`)."""
from jax.experimental.shard_map import shard_map


def wrap(fn, mesh, spec):
    return shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec)
