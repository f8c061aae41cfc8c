# teeth: the shipped sharded-engine shape — every knob reaches the
# shard_map body as an explicit argument (the static FleetConfig
# contract), module constants are single-assignment, and host
# materialization happens OUTSIDE the traced program.
# MUST pass: jit-staleness

from functools import partial

import jax
import numpy as np
from jax.sharding import PartitionSpec

from jax import shard_map

SCALE = 2.0  # single-assignment module constant: static, fine


@partial(
    shard_map,
    mesh=None,
    in_specs=(PartitionSpec("clients"), PartitionSpec()),
    out_specs=PartitionSpec("clients"),
)
def shard_body(w, alpha):
    return w * alpha * SCALE


def build(mesh, chunk):
    def body(w):
        return w[:chunk] if chunk else w  # closure over a static python int

    program = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(PartitionSpec("clients"),),
            out_specs=PartitionSpec("clients"),
        )
    )

    def run(w):
        out = program(w)
        return np.asarray(out)  # host sync AFTER dispatch: allowed

    return run
