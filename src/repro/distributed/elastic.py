"""Elastic scaling: rebuild the mesh for whatever devices survive and reshard
the checkpointed state onto it.

The contract: training state is checkpointed host-gathered (checkpoint/
manager.py), the data pipeline is a pure function of (seed, step), and
parameter shardings are derived from name-pattern rules — so restoring onto
a DIFFERENT mesh shape is just `make_elastic_mesh(n_devices)` + restore with
the new shardings.  Nothing about the training state encodes the old
topology.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh

from repro.distributed.sharding import tree_shardings
from repro.launch.mesh import make_mesh


def make_elastic_mesh(n_devices: Optional[int] = None, model_parallel: int = 1) -> Mesh:
    """Largest (data, model) mesh fitting the available devices.  model_parallel
    must divide the device count; leftover devices are dropped (reported)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    n = min(n, len(devs))
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by model_parallel={model_parallel}")
    data = n // model_parallel
    return make_mesh((data, model_parallel), ("data", "model"), devices=devs[:n])


def reshard_state(state, mesh: Mesh):
    """Places a host-side state pytree onto `mesh` under the standard rules."""
    sh = tree_shardings(state, mesh)
    return jax.tree.map(lambda a, s: jax.device_put(a, s), state, sh)
