"""Real multi-process ``init_multihost`` (VERDICT r4 #7): two CPU processes
form one JAX distributed runtime over localhost and run a global all-reduce
— the non-noop branches of ``parallel/distributed.py``, exercised without
TPU-pod hardware.

The worker runs in subprocesses because ``jax.distributed.initialize``
is once-per-process; the parent (which may already hold a backend) only
orchestrates.
"""

import os
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
pid = int(sys.argv[1])
os.environ["JAX_COORDINATOR_ADDRESS"] = "127.0.0.1:%PORT%"
os.environ["JAX_NUM_PROCESSES"] = "2"
os.environ["JAX_PROCESS_ID"] = str(pid)

from p2pfl_tpu.parallel.distributed import init_multihost

info = init_multihost()  # env-var path: the production bring-up
assert info["initialized"], info
assert info["process_count"] == 2, info
assert info["process_index"] == pid, info
assert info["global_devices"] == 2 * info["local_devices"], info

# one tiny global collective across the two processes: each contributes
# its process_index+1; the psum over the global mesh must see BOTH hosts
import jax
import jax.numpy as jnp
from jax.experimental.multihost_utils import process_allgather

try:
    got = process_allgather(jnp.float32(pid + 1))
except Exception as e:  # jaxlib builds without CPU multiprocess computations
    if "aren't implemented" not in str(e):
        raise
    print(f"BACKEND-NO-MULTIPROC {pid}")
    sys.exit(0)
assert sorted(got.tolist()) == [1.0, 2.0], got
print(f"OK process {pid}: {info['process_count']} procs, "
      f"{info['global_devices']} global devices, allgather {got.tolist()}")
"""


_ROUND_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
pid = int(sys.argv[1])
os.environ["JAX_COORDINATOR_ADDRESS"] = "127.0.0.1:%PORT%"
os.environ["JAX_NUM_PROCESSES"] = "2"
os.environ["JAX_PROCESS_ID"] = str(pid)

from p2pfl_tpu.parallel.distributed import init_multihost

info = init_multihost()
assert info["initialized"] and info["process_count"] == 2, info

# one real federated round on the GLOBAL mesh: each process owns one node
# slot; the round's masked FedAvg reduce + diffusion cross the process
# boundary (DCN on a pod, the distributed runtime here). Both processes
# build identical host state (same seeds), so they dispatch the same
# program over the 2-device global mesh.
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.multihost_utils import process_allgather
from jax.sharding import Mesh

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.models import mlp
from p2pfl_tpu.parallel import SpmdFederation

mesh = Mesh(np.array(jax.devices()), ("nodes",))
data = FederatedDataset.synthetic_mnist(n_train=128, n_test=32, seed=5)
try:
    fed = SpmdFederation.from_dataset(
        mlp(seed=0), data, n_nodes=2, mesh=mesh, batch_size=16, vote=False, seed=3
    )
    entry = fed.run_round(epochs=1)
except Exception as e:  # jaxlib builds without CPU multiprocess computations
    if "aren't implemented" not in str(e):
        raise
    print(f"BACKEND-NO-MULTIPROC {pid}")
    sys.exit(0)

@jax.jit
def probe(tree):
    leaves = jax.tree.leaves(tree)
    fp = sum(jnp.sum(jnp.abs(x.astype(jnp.float32))) for x in leaves)
    # diffusion check: both node slots hold the identical aggregate
    # (jnp.max over a stacked vector — Python max() can't compare tracers)
    slot_diff = jnp.max(jnp.stack([
        jnp.max(jnp.abs(x[0].astype(jnp.float32) - x[1].astype(jnp.float32)))
        for x in leaves
    ]))
    return fp, slot_diff

fp, slot_diff = probe(fed.params)
assert float(slot_diff) == 0.0, float(slot_diff)
loss = float(entry["train_loss"])
assert np.isfinite(loss), loss

# equal models on BOTH processes: every process sees the same replicated
# fingerprint, and the allgathered per-process readings agree exactly
# (host float first — allgather of an already-global array is identity)
got = process_allgather(jnp.float32(float(fp)))
assert got.shape == (2,) and float(got[0]) == float(got[1]), got
print(f"OK round process {pid}: loss {loss:.4f} fingerprint {float(fp):.6f}")
"""


_SHARDED_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
# two virtual devices per process: 4 global devices = 2 sharded nodes x
# model_parallel 2, with each node's slice INTERLEAVED across the hosts
flags = os.environ.get("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=2").strip()
pid = int(sys.argv[1])
os.environ["JAX_COORDINATOR_ADDRESS"] = "127.0.0.1:%PORT%"
os.environ["JAX_NUM_PROCESSES"] = "2"
os.environ["JAX_PROCESS_ID"] = str(pid)

from p2pfl_tpu.parallel.distributed import init_multihost

info = init_multihost()
assert info["initialized"] and info["process_count"] == 2, info
assert info["global_devices"] == 4, info

# the sharded-node witness: every node is a model_parallel=2 submesh that
# SPANS both hosts (device order [p0d0, p1d0] / [p0d1, p1d1]), so the
# row-parallel all-reduce inside each node's round AND the cross-slice
# aggregation fold both cross the process boundary (DCN on a pod). Both
# processes build identical host state (same seeds) and dispatch the same
# global programs — the multi-controller SPMD contract.
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.multihost_utils import process_allgather

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.models import mlp
from p2pfl_tpu.parallel import ShardedNodeFederation

devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
per_proc = [d for d in devs if d.process_index == 0], [d for d in devs if d.process_index == 1]
order = [per_proc[0][0], per_proc[1][0], per_proc[0][1], per_proc[1][1]]
rules = (
    (r"Dense_0/kernel", (None, "model")),
    (r"Dense_1/kernel", ("model", None)),
    (r"Dense_2/kernel", (None, "model")),
    (r".*", ()),
)
data = FederatedDataset.synthetic_mnist(n_train=128, n_test=16, seed=5)
try:
    fed = ShardedNodeFederation.from_dataset(
        mlp(seed=0), data, n_nodes=2, rules=rules, model_parallel=2,
        devices=order, batch_size=16, vote=False, seed=3,
    )
    for node_devs in (fed.slices[0], fed.slices[1]):
        procs = {d.process_index for d in np.asarray(node_devs.devices).flat}
        assert procs == {0, 1}, procs  # each node spans BOTH hosts
    entry = fed.run_round(epochs=1)
except Exception as e:  # jaxlib builds without CPU multiprocess computations
    if "aren't implemented" not in str(e):
        raise
    print(f"BACKEND-NO-MULTIPROC {pid}")
    sys.exit(0)

loss = float(entry["train_loss"])
assert np.isfinite(loss), loss

# the fold's psum saw BOTH slices: the stacked accumulator is sharded over
# the nodes axis and its total weight is both nodes' sample counts
psum_shardings = jax.tree.leaves(
    fed.last_fold["psum_shardings"], is_leaf=lambda x: hasattr(x, "spec")
)
assert all(s.spec[0] == "nodes" for s in psum_shardings), "fold input not node-sharded"
assert float(jnp.sum(fed.last_fold["wsum"])) == float(sum(fed._sizes))

# diffusion: both nodes hold the identical aggregate...
@jax.jit
def fingerprint(tree):
    return sum(jnp.sum(jnp.abs(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))

fp0 = fingerprint(fed.node_params(0))
fp1 = fingerprint(fed.node_params(1))
assert float(fp0) == float(fp1), (float(fp0), float(fp1))
# ...and BOTH processes observe the same bits of it
got = process_allgather(jnp.float32(float(fp0)))
assert got.shape == (2,) and float(got[0]) == float(got[1]), got
print(f"OK sharded process {pid}: loss {loss:.4f} fingerprint {float(fp0):.6f}")
"""


def _run_two_process_workers(tmp_path, worker_src, ok_marker, timeout=240):
    import socket

    with socket.socket() as s:  # a free localhost port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(worker_src.replace("%PORT%", str(port)))
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.getcwd(), env.get("PYTHONPATH")) if p
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process runtime hung (coordinator never formed)")
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
    if all("BACKEND-NO-MULTIPROC" in out for out in outs):
        # the runtime FORMED (both workers initialized, saw 2 procs and the
        # global device view — asserted in-worker) but this jaxlib's CPU
        # backend cannot run cross-process computations. Since
        # init_multihost switched the CPU world onto gloo collectives
        # (parallel/distributed.py _enable_cpu_collectives — the DCN
        # plane's CI substrate, test_dcn_plane.py), this branch is
        # vestigial on the shipped toolchain: it only fires on jaxlib
        # builds without a gloo/mpi CPU collectives implementation
        pytest.skip("jaxlib CPU backend lacks multiprocess computations")
    for pid, out in enumerate(outs):
        assert f"{ok_marker} {pid}" in out, out[-2000:]
    return outs


@pytest.mark.slow
def test_two_process_runtime_and_collective(tmp_path):
    _run_two_process_workers(tmp_path, _WORKER, "OK process")


@pytest.mark.slow
def test_two_process_federated_round_equal_models(tmp_path):
    """The executable witness for the DCN story (parallel/spmd_lm.py):
    a 2-node federated round over the 2-process global mesh — train,
    cross-process FedAvg reduce, diffusion — ends with the identical
    aggregated model on both processes."""
    _run_two_process_workers(tmp_path, _ROUND_WORKER, "OK round process")


@pytest.mark.slow
def test_two_process_sharded_node_round(tmp_path):
    """The sharded-node witness (ISSUE 10): two ``model_parallel=2``
    submesh nodes whose slices each SPAN both processes' devices — the
    in-round row-parallel all-reduce and the cross-slice aggregation
    psum both cross the process boundary, and both processes end holding
    the identical diffused aggregate. Backend-gated like the allgather
    test (CPU jaxlib without multiprocess computations skips)."""
    _run_two_process_workers(tmp_path, _SHARDED_WORKER, "OK sharded process")
