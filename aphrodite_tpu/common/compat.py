"""Trace-time view of the mesh a step program is compiled for.

`ModelRunner` enters `jax.set_mesh(mesh)` around every jitted dispatch,
so layer code can ask which mesh it is being traced under without the
mesh being threaded through every call.
"""
from __future__ import annotations

import jax


def context_tp() -> int:
    """Tensor-parallel degree of the context mesh, 1 when tracing
    outside any mesh (single-chip jit) or on a mesh without a "tp"
    axis. Pallas launch gates consult this (aphrocheck MESH003):
    Pallas kernels are single-device programs, so any tp>1 trace must
    take the GSPMD-partitionable jnp path instead."""
    return int(jax.sharding.get_abstract_mesh().shape.get("tp", 1))
