"""DCN weights plane (ISSUE 18): cross-process model diffusion as device
arrays over XLA cross-host collectives — ``Settings.WEIGHTS_PLANE="dcn"``.

Two layers of coverage:

- **Fast unit tests** on the wire-metadata codecs, the world directory's
  TTL cache, the ``try_dcn_send`` eligibility ladder, the receiver's nack
  ladder, verb-command robustness and the analyzer's scope over the new
  modules — all in-process, no distributed runtime.
- **Slow 2-process witnesses** (subprocess workers, like
  ``test_multihost.py``): a real federation whose model payloads cross the
  process boundary with ZERO pickled weight bytes on gRPC and whose final
  params match a byte-plane control fleet bit-close; direct transfer
  parity (raw fp32/bf16 bit-exact, int8/topk8 codec vs the byte decoder);
  the per-edge ICI → DCN → bytes selection matrix with a
  directory-withdrawn node; and a hard process kill of the async global
  root, exercising TierRouter failover while the plane's rendezvous
  timeouts degrade the dead edges loudly.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import p2pfl_tpu
from p2pfl_tpu.communication import dcn
from p2pfl_tpu.communication.message import WeightsEnvelope
from p2pfl_tpu.learning.weights import ModelUpdate
from p2pfl_tpu.parallel import dcn_plane
from p2pfl_tpu.parallel.ici_plane import SliceInfo, slice_info_of
from p2pfl_tpu.settings import Settings

PKG = Path(p2pfl_tpu.__file__).parent


# ---- wire metadata codecs ----


def test_spec_wire_roundtrip():
    for spec in (P(), P("m"), P(None, "m"), P(("a", "b"), None), P("a", None, "b")):
        wire = dcn_plane.spec_to_wire(spec)
        json.dumps(wire)  # must be JSON-serializable as-is
        assert dcn_plane.spec_from_wire(wire) == spec


def test_mesh_wire_roundtrip_and_unknown_ids():
    devs = jax.devices()[:2]
    mesh = Mesh(np.array(devs), ("m",))
    info = SliceInfo(mesh=mesh, specs=())
    meta = dcn_plane.mesh_wire_meta(info)
    json.dumps(meta)
    back = dcn_plane.mesh_from_ids(meta["ids"], meta["shape"], meta["axes"])
    assert back is not None
    assert list(back.devices.flat) == list(mesh.devices.flat)
    assert back.axis_names == mesh.axis_names
    # an id outside this world's device list must refuse, not crash
    assert dcn_plane.mesh_from_ids([10**9], [1], ["m"]) is None
    # a single-process world: every local slice is process-local
    assert dcn_plane.process_local(info)


def test_spec_to_wire_key_hashable():
    k = dcn.spec_to_wire_key(P(("a", "b"), None, "c"))
    assert k == (("a", "b"), None, "c")
    hash(k)


# ---- world directory ----


class _FakeKV:
    def __init__(self):
        self.store = {}
        self.dir_reads = 0

    def key_value_set(self, key, val):
        if key in self.store:
            raise RuntimeError("key exists")
        self.store[key] = val

    def key_value_delete(self, key):
        if key not in self.store:
            raise KeyError(key)
        del self.store[key]

    def key_value_dir_get(self, prefix):
        self.dir_reads += 1
        return [(k, v) for k, v in self.store.items() if k.startswith(prefix)]


def test_world_directory_publish_lookup_ttl(monkeypatch):
    fake = _FakeKV()
    monkeypatch.setattr(dcn, "kv_client", lambda: fake)
    monkeypatch.setattr(dcn, "world_active", lambda: True)
    d = dcn.WorldDirectory()
    d.publish("n1:100")
    assert d.lookup("n1:100") == {"pi": int(jax.process_index())}
    reads = fake.dir_reads
    # served from the TTL snapshot: no second directory read
    assert d.lookup("n1:100") is not None
    assert d.lookup("missing:1") is None
    assert fake.dir_reads == reads
    # withdraw invalidates the snapshot — the next lookup re-reads and
    # no longer sees the entry
    d.withdraw("n1:100")
    assert d.lookup("n1:100") is None
    assert fake.dir_reads == reads + 1
    # re-publish over a stale entry (restarted node) must not raise even
    # though the fake's set is not an upsert
    d.publish("n1:100")
    d.publish("n1:100")
    assert d.lookup("n1:100") is not None


def test_world_directory_tolerates_bad_entries(monkeypatch):
    fake = _FakeKV()
    fake.store[dcn._DIR_PREFIX + "good:1"] = json.dumps({"pi": 0})
    fake.store[dcn._DIR_PREFIX + "bad:1"] = "{not json"
    monkeypatch.setattr(dcn, "kv_client", lambda: fake)
    d = dcn.WorldDirectory()
    assert d.lookup("good:1") == {"pi": 0}
    assert d.lookup("bad:1") is None


# ---- try_dcn_send eligibility ladder ----


def _env(params):
    return WeightsEnvelope(
        "src:1", 0, "add_model", ModelUpdate(params, ["src:1"], 1)
    )


def test_try_dcn_send_silent_when_plane_off():
    dcn.reset_dcn_stats()
    proto = SimpleNamespace(get_address=lambda: "src:1")
    assert Settings.WEIGHTS_PLANE == "bytes"  # set_test_settings default
    assert dcn.try_dcn_send(proto, "peer:2", _env({"w": jnp.ones((4,))})) is None
    # not an eligibility failure — the plane simply isn't on
    assert dcn.dcn_stats()["fallback_bytes"] == 0


def test_try_dcn_send_loud_fallback_without_world():
    dcn.reset_dcn_stats()
    proto = SimpleNamespace(get_address=lambda: "src:1")
    Settings.WEIGHTS_PLANE = "dcn"
    # this test process runs no jax.distributed world: the edge must fall
    # back LOUDLY (counted), not silently
    assert dcn.try_dcn_send(proto, "peer:2", _env({"w": jnp.ones((4,))})) is None
    assert dcn.dcn_stats()["fallback_bytes"] == 1
    # pre-encoded relay frames (no live params) stay silent — bytes is
    # their only possible transport
    env = WeightsEnvelope("src:1", 0, "add_model", ModelUpdate(None, ["src:1"], 1))
    assert dcn.try_dcn_send(proto, "peer:2", env) is None
    assert dcn.dcn_stats()["fallback_bytes"] == 1


# ---- receiver-side nack ladder ----


class _VerbTap:
    """A protocol stub that records the rendezvous verbs sent through it."""

    def __init__(self, addr):
        self.addr = addr
        self.sent = []

    def get_address(self):
        return self.addr

    def _do_send(self, nei, msg, create_connection=False):
        self.sent.append((nei, msg))
        return True


def _offer_to(node, meta=None):
    plane = dcn.DcnPlane.instance()
    plane.on_offer(node, "peer:9", {"tid": "t-test", **(meta or {})})
    nei, msg = node.protocol.sent[-1]
    assert nei == "peer:9"
    return msg.cmd, json.loads(msg.args[0])


def test_on_offer_nack_ladder(monkeypatch):
    dcn.DcnPlane.reset()
    dcn.reset_dcn_stats()
    node = SimpleNamespace(
        protocol=_VerbTap("me:1"), addr="me:1", _running=True, learner=None
    )
    try:
        # plane off
        assert Settings.WEIGHTS_PLANE == "bytes"
        cmd, meta = _offer_to(node)
        assert (cmd, meta["reason"]) == ("dcn_nack", "plane_off")
        # no distributed world (real: this process runs none)
        Settings.WEIGHTS_PLANE = "dcn"
        cmd, meta = _offer_to(node)
        assert (cmd, meta["reason"]) == ("dcn_nack", "no_distributed_world")
        # world up, but no learner on the target node
        monkeypatch.setattr(dcn, "world_active", lambda: True)
        cmd, meta = _offer_to(node)
        assert (cmd, meta["reason"]) == ("dcn_nack", "peer_not_ready")
        # architecture mismatch: shapes in the offer differ from ours
        node.learner = SimpleNamespace(
            get_parameters=lambda: {"w": jnp.ones((4,), jnp.float32)}
        )
        cmd, meta = _offer_to(node, {"model": [["w", [8], "float32"]]})
        assert (cmd, meta["reason"]) == ("dcn_nack", "architecture_mismatch")
        # a "peer" claiming our own devices: same process is ICI territory
        info = slice_info_of({"w": jax.device_put(jnp.ones((4,), jnp.float32))})
        cmd, meta = _offer_to(
            node,
            {
                "model": [["w", [4], "float32"]],
                "mesh": dcn_plane.mesh_wire_meta(info),
            },
        )
        assert (cmd, meta["reason"]) == ("dcn_nack", "same_process")
        assert dcn.dcn_stats()["nacks"] == 5
        # every refusal stayed on the control plane: nack verbs only
        assert all(m.cmd == "dcn_nack" for _n, m in node.protocol.sent)
        assert all(m.ttl == 1 for _n, m in node.protocol.sent)
    finally:
        dcn.DcnPlane.reset()


def test_on_accept_unknown_tid_aborts_peer():
    dcn.DcnPlane.reset()
    try:
        tap = _VerbTap("me:1")
        node = SimpleNamespace(protocol=tap, addr="me:1")
        dcn.DcnPlane.instance().on_accept(node, "peer:9", {"tid": "never-offered"})
        nei, msg = tap.sent[-1]
        assert msg.cmd == "dcn_abort"
        assert json.loads(msg.args[0])["reason"] == "unknown_tid"
        # late verbs for unknown transfers are ignored, never raise
        plane = dcn.DcnPlane.instance()
        for h in (plane.on_nack, plane.on_done, plane.on_ready, plane.on_abort):
            h(node, "peer:9", {"tid": "never-offered"})
    finally:
        dcn.DcnPlane.reset()


# ---- verb command robustness ----


def test_verb_commands_tolerate_malformed_metadata():
    from p2pfl_tpu.commands.dcn import DCN_COMMANDS, DcnOfferCommand

    node = SimpleNamespace(addr="me:1", protocol=None)
    cmd = DcnOfferCommand(node)
    # none of these may raise or reach the plane
    cmd.execute("peer:9", 0)  # no metadata arg
    cmd.execute("peer:9", 0, "{not json")
    cmd.execute("peer:9", 0, json.dumps([1, 2, 3]))  # not a dict
    cmd.execute("peer:9", 0, json.dumps({"no": "tid"}))
    names = sorted(c.get_name() for c in DCN_COMMANDS)
    assert names == sorted(dcn.DCN_VERBS)


# ---- analyzer scope over the new modules ----


def test_hostgather_covers_dcn_modules():
    """The no-host-gather contract extends to the DCN plane: both shipped
    modules are clean, and re-introducing a host gather into either is
    caught — same teeth idiom as test_analysis.py's ICI coverage."""
    from p2pfl_tpu.analysis import analyze
    from p2pfl_tpu.analysis.rules import NoHostGatherRule

    src = (PKG / "communication" / "dcn.py").read_text()
    assert analyze([], [NoHostGatherRule], sources={"communication/dcn.py": src}) == []
    needle = "    plane = DcnPlane.instance()\n"
    mutated = src.replace(
        needle,
        needle + "    _probe = np.asarray(jax.tree.leaves(update.params)[0])\n",
        1,
    )
    assert mutated != src
    found = analyze([], [NoHostGatherRule], sources={"communication/dcn.py": mutated})
    assert any(f.rule == "no-host-gather" and "np.asarray" in f.message for f in found)

    glue = (PKG / "parallel" / "dcn_plane.py").read_text()
    assert analyze([], [NoHostGatherRule], sources={"parallel/dcn_plane.py": glue}) == []
    gneedle = "    leaves = jax.tree.leaves(local_tree)\n"
    gmut = glue.replace(
        gneedle, gneedle + "    _host = [x.tobytes() for x in leaves]\n", 1
    )
    assert gmut != glue
    gfound = analyze([], [NoHostGatherRule], sources={"parallel/dcn_plane.py": gmut})
    assert any(".tobytes()" in f.message for f in gfound)


# ---- 2-process witnesses (subprocess workers, gloo CPU collectives) ----

_PROLOGUE = r"""
import os, sys, time, threading
os.environ["JAX_PLATFORMS"] = "cpu"
pid = int(sys.argv[1])
os.environ["JAX_COORDINATOR_ADDRESS"] = "127.0.0.1:%PORT%"
os.environ["JAX_NUM_PROCESSES"] = "2"
os.environ["JAX_PROCESS_ID"] = str(pid)

from p2pfl_tpu.parallel.distributed import init_multihost, kv_client

info = init_multihost()
assert info["initialized"] and info["process_count"] == 2, info

import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.settings import Settings, set_test_settings

set_test_settings()

from p2pfl_tpu.communication.dcn import DcnPlane, dcn_stats, reset_dcn_stats, try_dcn_send
from p2pfl_tpu.communication.grpc_transport import GrpcProtocol
from p2pfl_tpu.communication.message import WeightsEnvelope
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.learner import JaxLearner
from p2pfl_tpu.learning.weights import ModelUpdate
from p2pfl_tpu.models import mlp
from p2pfl_tpu.node import Node
from p2pfl_tpu.utils import wait_to_finish

base = %PORT%
_client = kv_client()

def barrier(name):
    _client.wait_at_barrier("dcn_t_" + name, 120_000)

def connect_retry(node, addr, tries=150):
    for _ in range(tries):
        # connect() refuses an ALREADY-connected peer — when both ends of
        # an edge dial (or the peer's handshake beat us to it), membership
        # is the success condition, not the dial
        if node.connect(addr) or addr in node.get_neighbors(only_direct=True):
            return
        time.sleep(0.1)
    raise RuntimeError(f"never connected to {addr}")

def wait_neighbors(nodes, n, wait=30):
    deadline = time.time() + wait
    while any(len(x.get_neighbors(only_direct=True)) < n for x in nodes):
        if time.time() > deadline:
            raise RuntimeError("neighbor convergence timeout")
        time.sleep(0.1)

def worst_diff(a_tree, b_tree):
    worst = 0.0
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        a32 = np.asarray(a, dtype=np.float32)
        b32 = np.asarray(b, dtype=np.float32)
        worst = max(worst, float(np.max(np.abs(a32 - b32))))
    return worst
"""


_FED_WORKER = _PROLOGUE + r"""
def run_fleet(tag, plane, port_off):
    Settings.WEIGHTS_PLANE = plane
    my_addr = f"127.0.0.1:{base + port_off + pid}"
    peer_addr = f"127.0.0.1:{base + port_off + 1 - pid}"
    full = FederatedDataset.synthetic_mnist(n_train=256, n_test=64, seed=7)
    learner = JaxLearner(mlp(seed=pid), full.partition(pid, 2), batch_size=32)
    node = Node(learner=learner, protocol=GrpcProtocol(my_addr))
    node.start()
    barrier(tag + "_up")
    if pid == 0:
        connect_retry(node, peer_addr)
    wait_neighbors([node], 1)
    if pid == 0:
        node.set_start_learning(rounds=2, epochs=1)
    wait_to_finish([node], timeout=180)
    params = jax.tree.map(lambda x: np.asarray(x), learner.get_parameters())
    wire = dict(node.protocol.wire_stats)
    node.stop()
    barrier(tag + "_down")
    return params, wire

reset_dcn_stats()
dcn_params, dcn_wire = run_fleet("dcn", "dcn", 1)
stats = dcn_stats()
print(f"STATS {pid}: dcn={stats} wire_weights_bytes={dcn_wire.get('weights_bytes', 0)}")
# the tentpole claims, per process: device payloads moved both ways, ZERO
# pickled model bytes on gRPC, and no silent per-edge fallback
assert stats["dcn_sends"] > 0 and stats["dcn_recvs"] > 0, stats
assert stats["bytes_moved"] > 0, stats
assert stats["fallback_bytes"] == 0, stats
assert dcn_wire.get("weights_bytes", 0) == 0, dcn_wire

# control fleet: same overlay, same seeds, same rounds, byte transport
byte_params, byte_wire = run_fleet("bytes", "bytes", 3)
assert dcn_stats()["dcn_sends"] == stats["dcn_sends"], "byte fleet leaked onto the DCN plane"
assert byte_wire.get("weights_bytes", 0) > 0, byte_wire

# transport equivalence: the two fleets must land bit-close
worst = worst_diff(dcn_params, byte_params)
assert worst <= 1e-4, f"DCN vs byte fleet diverged: {worst}"

# and BOTH processes hold the same diffused aggregate
from jax.experimental.multihost_utils import process_allgather
fp = sum(float(np.sum(np.abs(x))) for x in jax.tree.leaves(dcn_params))
got = process_allgather(jnp.float32(fp))
assert float(got[0]) == float(got[1]), got
print(f"OK fed process {pid}: parity worst {worst:.2e} fingerprint {fp:.6f}")
"""


_XFER_WORKER = _PROLOGUE + r"""
Settings.WEIGHTS_PLANE = "dcn"
my_addr = f"127.0.0.1:{base + 1 + pid}"
peer_addr = f"127.0.0.1:{base + 2 - pid}"
data = FederatedDataset.synthetic_mnist(n_train=64, n_test=16, seed=3)
learner = JaxLearner(mlp(seed=0), data.partition(pid, 2), batch_size=16)
node = Node(learner=learner, protocol=GrpcProtocol(my_addr))

captured = []
evt = threading.Event()

class CaptureCommand:
    # a pass-through data-plane command: records what the DCN plane
    # DELIVERED, outside any experiment gating
    @staticmethod
    def get_name():
        return "dcn_capture"

    def execute(self, source, round, update=None, xp=None, **kw):
        captured.append(update)
        evt.set()

node.protocol.add_command(CaptureCommand())
node.start()
barrier("xfer_up")

tmpl = learner.get_parameters()

def filled(scale, dtype=None):
    leaves, treedef = jax.tree.flatten(tmpl)
    out = []
    for i, x in enumerate(leaves):
        v = (jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape) + i) * scale
        out.append(v.astype(dtype or x.dtype))
    return jax.tree.unflatten(treedef, out)

def send(anchor=None, tag=None):
    upd = ModelUpdate(learner.get_parameters(), [my_addr], 1)
    if anchor is not None:
        upd.anchor = anchor
        upd.anchor_tag = tag
    env = WeightsEnvelope(my_addr, 0, "dcn_capture", upd)
    return try_dcn_send(node.protocol, peer_addr, env)

def received():
    assert evt.wait(30), "transfer never delivered"
    evt.clear()
    return captured[-1].params

# case 1: raw fp32 — bit-exact across the collective
exp = filled(1e-3)
learner.set_parameters(exp)
barrier("c1_set")
if pid == 0:
    assert send() is True
    s = dcn_stats()
    assert s["dcn_sends"] == 1 and s["bytes_moved"] > 0, s
else:
    assert worst_diff(received(), exp) == 0.0
barrier("c1_done")

# case 2: bf16 — dtype survives end to end, still bit-exact
exp = filled(2e-3, jnp.bfloat16)
learner.set_parameters(exp)
barrier("c2_set")
if pid == 0:
    assert send() is True
else:
    got = received()
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(got)
               if jnp.issubdtype(x.dtype, jnp.floating)), "dtype lost in transfer"
    assert worst_diff(got, exp) == 0.0
barrier("c2_done")

# case 3: dense int8 codec on the DCN leg — quantization-bounded
Settings.WIRE_COMPRESSION = "int8"
exp = filled(1e-3)
learner.set_parameters(exp)
barrier("c3_set")
if pid == 0:
    assert send() is True
else:
    got = received()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(exp)):
        b32 = np.asarray(b, dtype=np.float32)
        tol = float(np.max(np.abs(b32))) / 127.0 + 1e-7
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32), b32, atol=tol)
barrier("c3_done")

# case 4: topk8 with a MISMATCHED receiver anchor — the offer is nacked
# (anchor_round_mismatch) and the sender falls back loudly
Settings.WIRE_COMPRESSION = "topk8"
exp = filled(3e-3)
anchor = jax.tree.map(jnp.zeros_like, tmpl)
learner.set_parameters(exp)
if pid == 1:
    learner.set_wire_anchor(anchor, "9:9")
barrier("c4_set")
if pid == 0:
    before = dcn_stats()["fallback_bytes"]
    assert send(anchor=anchor, tag="0:7") is None
    s = dcn_stats()
    assert s["fallback_bytes"] == before + 1, s
barrier("c4_done")
if pid == 1:
    assert dcn_stats()["nacks"] >= 1, dcn_stats()

# case 5: topk8 with matching anchors — parity with the byte codec's
# decode of the same update (the one shared decoder contract)
from p2pfl_tpu.learning import weights as W
if pid == 1:
    learner.set_wire_anchor(anchor, "0:7")
barrier("c5_set")
if pid == 0:
    assert send(anchor=anchor, tag="0:7") is True
else:
    got = received()
    blob = W.encode_params(exp, compression="topk8", anchor=anchor, anchor_tag="0:7")
    ref = W.decode_params(blob, anchor=anchor, anchor_tag="0:7")
    assert worst_diff(got, ref) <= 1e-6
barrier("c5_done")

node.stop()
print(f"OK xfer process {pid}")
"""


_MATRIX_WORKER = _PROLOGUE + r"""
Settings.WEIGHTS_PLANE = "dcn"
from p2pfl_tpu.communication.ici import ici_stats

# four nodes, two per process: A,B on p0; C,D on p1. Every edge class in
# one fleet — co-resident (ICI), cross-process same-world (DCN), and a
# directory-withdrawn node whose inbound edges must fall back to bytes.
addrs = [f"127.0.0.1:{base + 1 + i}" for i in range(4)]
mine = addrs[2 * pid: 2 * pid + 2]
data = FederatedDataset.synthetic_mnist(n_train=256, n_test=32, seed=7)
nodes = []
for j, addr in enumerate(mine):
    idx = 2 * pid + j
    learner = JaxLearner(mlp(seed=idx), data.partition(idx, 4), batch_size=32)
    n = Node(learner=learner, protocol=GrpcProtocol(addr))
    n.start()
    nodes.append(n)
barrier("matrix_up")
for n in nodes:
    for other in addrs:
        if other > n.addr:  # one dialer per edge; links are bidirectional
            connect_retry(n, other)
wait_neighbors(nodes, 3)

# D (addrs[3]) leaves the world directory: senders can no longer place it
# and must degrade those edges to bytes — loudly, per edge
if pid == 1:
    DcnPlane.instance().withdraw_node(addrs[3])
barrier("matrix_withdrawn")
time.sleep(2 * Settings.DCN_DIR_TTL_S)  # let cached snapshots expire

if pid == 0:
    nodes[0].set_start_learning(rounds=1, epochs=1)
wait_to_finish(nodes, timeout=180)

s = dcn_stats()
ici = ici_stats()
wire = sum(dict(n.protocol.wire_stats).get("weights_bytes", 0) for n in nodes)
print(f"MATRIX {pid}: dcn={s} ici_shard_sends={ici['shard_sends']} wire_weights_bytes={wire}")
assert ici["shard_sends"] > 0, ici  # the co-resident pair rode ICI
assert s["dcn_sends"] > 0, s        # cross-process peers rode DCN
if pid == 0:
    assert s["fallback_bytes"] > 0, s  # edges to the withdrawn node fell back...
    assert wire > 0, wire              # ...and actually moved pickled bytes

# mixed transports, one outcome: all four nodes hold the same aggregate
fps = [sum(float(np.sum(np.abs(np.asarray(x, dtype=np.float32))))
           for x in jax.tree.leaves(n.learner.get_parameters())) for n in nodes]
assert abs(fps[0] - fps[1]) <= 1e-3 * max(1.0, abs(fps[0])), fps
from jax.experimental.multihost_utils import process_allgather
got = process_allgather(jnp.float32(fps[0]))
assert abs(float(got[0]) - float(got[1])) <= 1e-3 * max(1.0, abs(float(got[0]))), got
for n in nodes:
    n.stop()
print(f"OK matrix process {pid}")
"""


_KILL_WORKER = _PROLOGUE + r"""
Settings.WEIGHTS_PLANE = "dcn"
Settings.FEDERATION_MODE = "async"
Settings.FEDBUFF_K = 2

# the victim (pid 1) takes the LOWER-sorting address: federation/routing.py
# elects the first live member in address order as global root, so killing
# that process forces the survivor through TierRouter root failover while
# the DCN plane's rendezvous timeouts degrade the dead edges
my_addr = f"127.0.0.1:{base + 2 - pid}"
peer_addr = f"127.0.0.1:{base + 1 + pid}"
full = FederatedDataset.synthetic_mnist(n_train=256, n_test=64, seed=7)
learner = JaxLearner(mlp(seed=pid), full.partition(pid, 2), batch_size=32)
node = Node(learner=learner, protocol=GrpcProtocol(my_addr))
node.start()
barrier("kill_up")
if pid == 0:
    connect_retry(node, peer_addr)
wait_neighbors([node], 1)
if pid == 0:
    node.set_start_learning(rounds=3, epochs=1)
if pid == 1:
    deadline = time.time() + 60
    while node.state.round is None and time.time() < deadline:
        time.sleep(0.05)
    assert node.state.round is not None, "experiment never reached the victim"
    node.state.model_initialized_event.wait(30)
    time.sleep(0.5)  # let at least one DCN payload land while both live
    print("DYING 1", flush=True)
    os._exit(9)

wait_to_finish([node], timeout=150)
assert node.state.round is None, "survivor never finished the experiment"
s = dcn_stats()
from p2pfl_tpu.management.logger import logger
failovers = sum(
    d.get("root_failover", 0.0) for d in logger.get_comm_metrics().values()
)
print(f"KILL {pid}: dcn={s} failovers={failovers}")
assert s["dcn_sends"] >= 1, s  # the init-model broadcast rode DCN pre-kill
assert failovers >= 1, "survivor never took over the dead global root"
node.stop()
print(f"OK kill process {pid}", flush=True)
# skip atexit: jax.distributed's shutdown barrier LOG(FATAL)s (SIGABRT)
# when a world member died mid-run — which is this test's whole point
os._exit(0)
"""


def _launch(tmp_path, worker_src, ok_marker, timeout=300, expect_rc=None):
    """The test_multihost runner, generalized: per-pid expected return
    codes (a killed worker exits nonzero ON PURPOSE) and OK markers only
    for pids expected to survive."""
    import socket

    expect_rc = expect_rc or {}
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
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == expect_rc.get(pid, 0), out[-3000:]
        if expect_rc.get(pid, 0) == 0:
            assert f"{ok_marker} {pid}" in out, out[-3000:]
    return outs


@pytest.mark.slow
def test_two_process_dcn_federation_zero_pickled_bytes_and_parity(tmp_path):
    """The acceptance witness: a 2-process federation over WEIGHTS_PLANE=
    "dcn" completes with device payloads crossing the process boundary,
    ZERO pickled model bytes on gRPC, no silent fallback — and its final
    model matches a byte-plane control fleet bit-close."""
    _launch(tmp_path, _FED_WORKER, "OK fed process", timeout=420)


@pytest.mark.slow
def test_two_process_dcn_transfer_codec_matrix(tmp_path):
    """Direct transfer parity: raw fp32 and bf16 land bit-exact; int8
    within quantization bounds; topk8 matches the byte decoder; a
    mismatched receiver anchor nacks into a loud byte fallback."""
    _launch(tmp_path, _XFER_WORKER, "OK xfer process", timeout=300)


@pytest.mark.slow
def test_two_process_mixed_plane_selection_matrix(tmp_path):
    """Per-edge ladder in one fleet: co-resident pairs ride ICI,
    cross-process same-world peers ride DCN, and a directory-withdrawn
    node's inbound edges fall back to bytes — counted and loud — while
    the fleet still converges to one aggregate."""
    _launch(tmp_path, _MATRIX_WORKER, "OK matrix process", timeout=420)


@pytest.mark.slow
def test_two_process_dcn_root_kill_failover(tmp_path):
    """Hard process kill under async federation: the dead process hosted
    the global root; the survivor rides TierRouter failover, the DCN
    plane's rendezvous timeouts degrade the dead edges without hanging,
    and the experiment still completes."""
    _launch(
        tmp_path, _KILL_WORKER, "OK kill process", timeout=300, expect_rc={1: 9}
    )
