# teeth: the sharded-engine donation shape. The fleet program donates
# its sharded carry through partial(jax.jit, donate_argnums=…) wrapped
# AROUND shard_map — the donation declaration lives on the inner
# partial call, and a later read of the donated buffer without a rebind
# is the same "array has been deleted" poisoning as the plain-jit case.
# MUST flag: donation-reuse

from functools import partial

import jax
from jax.sharding import PartitionSpec

from jax import shard_map


def _body(w, events):
    return w, events.sum()


fleet_step = partial(jax.jit, donate_argnums=(0,))(
    shard_map(
        _body,
        mesh=None,
        in_specs=(PartitionSpec("clients"), PartitionSpec()),
        out_specs=(PartitionSpec("clients"), PartitionSpec()),
    )
)


class Driver:
    def run(self, events):
        out, total = fleet_step(self.w, events)
        return self.w.sum() + total  # self.w was donated: dead buffer
