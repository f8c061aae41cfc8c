# teeth: the sharded-engine staleness shape. A shard_map body is a
# traced device program exactly like a jit body — a Settings read or a
# mutable-global read inside one bakes the first-trace value into every
# later call, and the decorator form (@partial(shard_map, …)) must not
# hide the body from the rule.
# MUST flag: jit-staleness (x3)

from functools import partial

import jax
import numpy as np
from jax.sharding import PartitionSpec

from jax import shard_map
from p2pfl_tpu.settings import Settings

CHUNK_OVERRIDE = 0


def set_chunk(c):
    global CHUNK_OVERRIDE
    CHUNK_OVERRIDE = c


@partial(
    shard_map,
    mesh=None,
    in_specs=(PartitionSpec("clients"),),
    out_specs=PartitionSpec("clients"),
)
def shard_body(w):
    # decorator form: Settings read inside the per-shard program
    return w * Settings.FEDBUFF_ALPHA


def build(mesh):
    def body(w):
        k = CHUNK_OVERRIDE  # mutable global inside the shard program
        total = np.asarray(w)  # host materialization of a traced value
        return w * k + total.sum()

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(PartitionSpec("clients"),),
            out_specs=PartitionSpec("clients"),
        )
    )
