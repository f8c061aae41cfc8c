"""TPU-native parallel runtime.

This is where the rebuild departs most from the reference: instead of N
OS processes exchanging pickled weights over gRPC
(``p2pfl/communication/grpc/``), an entire federation runs as **one SPMD
program** over a ``jax.sharding.Mesh`` — one logical node per mesh slot,
local training as per-slot batched compute, FedAvg as a masked weighted
reduction that XLA lowers to an all-reduce over ICI. Control decisions
(election, round count) stay on host; nothing crosses the host↔device
boundary inside a round.
"""

from p2pfl_tpu.parallel.mesh import (
    federation_mesh,
    node_slices,
    submesh_federation_mesh,
)
from p2pfl_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_mesh,
    pipelined_lm_apply,
    stack_layers,
)
from p2pfl_tpu.parallel.spmd import SpmdFederation

__all__ = [
    "ChunkedFederation",
    "PipelineFederation",
    "ShardedNodeFederation",
    "SpmdFederation",
    "SpmdLmFederation",
    "SpmdLoraFederation",
    "federation_mesh",
    "node_slices",
    "pipeline_apply",
    "pipeline_mesh",
    "pipelined_lm_apply",
    "stack_layers",
    "submesh_federation_mesh",
]

_LAZY = {
    "ChunkedFederation": "p2pfl_tpu.parallel.chunked",
    "ShardedNodeFederation": "p2pfl_tpu.parallel.submesh",
    "SpmdLoraFederation": "p2pfl_tpu.parallel.spmd_lora",
    "SpmdLmFederation": "p2pfl_tpu.parallel.spmd_lm",
    "PipelineFederation": "p2pfl_tpu.parallel.spmd_lm",
}


def __getattr__(name):
    if name in _LAZY:  # lazy: avoid importing optax paths eagerly
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(name)
