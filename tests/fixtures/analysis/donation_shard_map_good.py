# teeth: the shipped fix shape for the sharded-engine donation — the
# donated carry is rebound from the program's result before any later
# read, so a failed dispatch can recover and a successful one never
# touches the dead buffer.
# MUST pass: donation-reuse

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
        self.w, total = fleet_step(self.w, events)  # rebind-on-return
        return self.w.sum() + total
