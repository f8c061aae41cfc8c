"""Gossip data-plane benchmark: encode-once payload cache + concurrent fan-out.

Three measurements, all on the in-memory transport with the byte path forced
(``Settings.MEMORY_WIRE_CODEC=True`` — payloads are really encoded, shipped
and materialized, just without sockets):

1. **Codec microbench** — ``encode_params``/``decode_params`` wall-clock per
   payload for the MLP and transformer configs, per wire compression.
2. **Encode accounting** — encode-pipeline invocations per node per round on
   a federation run, plus the payload cache's hit/miss counters as exported
   through ``logger.get_comm_metrics()``. Pre-overhaul behavior was one
   encode per candidate per tick (O(neighbors × ticks)); with the cache it
   is bounded by distinct payload contents per round — own model versions
   (~2: post-fit contribution + post-aggregation diffusion) plus distinct
   partial-aggregation contents.
3. **Slow-peer round time** — end-to-end wall-clock of a federated round on
   an 8-node federation with one peer whose receive path stalls, comparing
   the pre-overhaul data plane (sequential sends, no cache, no send budget:
   ``GOSSIP_SEND_WORKERS=1``, ``GOSSIP_PAYLOAD_CACHE=False``, huge
   ``GOSSIP_SEND_TIMEOUT``) against the shipped defaults (4 send workers,
   cache on, 0.5 s budget).
4. **Compression split** — host (numpy argpartition + native quantize) vs
   device (``ops/compression.py`` fused jit) producer per compression mode:
   encode wall-clock, payload bytes, and the bytes that cross device→host
   per encode (the host producer pulls the FULL fp32 model + anchor; the
   device producer only the compressed ``(idx, q, scale)`` buffers), with a
   decode-parity check between both producers' frames.

``--smoke`` runs a shrunken federation and asserts the encode-once
invariant (encodes per node-round bounded by distinct contents, cache hits
present) plus the compression-split invariants (host/device frames decode
to the same tree within quantization tolerance; device topk8 D2H stays
~payload-sized, not model-sized) — the CI guard that keeps the cache and
the device codec from silently regressing.

usage: JAX_PLATFORMS=cpu python bench_gossip.py [--smoke] [--out BENCH_GOSSIP.json]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np


def _make_model(name: str, seed: int = 0):
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer

    if name == "mlp":
        return mlp(seed=seed)
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
        ffn_hidden=128, lora_rank=0,
    )
    return tiny_transformer(seq_len=32, cfg=cfg, seed=seed)


def bench_codec(repeats: int = 5) -> dict:
    """encode/decode wall-clock per payload, per model config × compression."""
    from p2pfl_tpu.learning.weights import decode_params, encode_params

    out: dict = {}
    for name in ("mlp", "transformer"):
        model = _make_model(name)
        params = {k: np.asarray(v) for k, v in _flatten(model.params).items()}
        anchor = {k: v - 0.01 if v.dtype.kind == "f" else v for k, v in params.items()}
        entry: dict = {"param_bytes": int(sum(v.nbytes for v in params.values()))}
        for comp in ("none", "int8", "topk8"):
            kw = {"compression": comp}
            if comp == "topk8":
                kw.update(anchor=anchor, anchor_tag="0:0")
            payload = encode_params(params, **kw)  # warmup
            t0 = time.perf_counter()
            for _ in range(repeats):
                payload = encode_params(params, **kw)
            enc_ms = (time.perf_counter() - t0) / repeats * 1e3
            dkw = {"anchor": anchor, "anchor_tag": "0:0"} if comp == "topk8" else {}
            t0 = time.perf_counter()
            for _ in range(repeats):
                decode_params(payload, **dkw)
            dec_ms = (time.perf_counter() - t0) / repeats * 1e3
            entry[comp] = {
                "payload_bytes": len(payload),
                "encode_ms": round(enc_ms, 3),
                "decode_ms": round(dec_ms, 3),
            }
        out[name] = entry
    return out


def _flatten(tree):
    from p2pfl_tpu.learning.weights import _flatten_named

    return _flatten_named(tree)


def _wide_tree(n_params: int = 4_000_000, seed: int = 0):
    """Synthetic multi-leaf fp32 tree (device-resident) for the compression
    split — big enough that codec throughput, not dispatch overhead,
    dominates."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    per = n_params // 4
    return {
        f"block{i}/w": jnp.asarray(rng.normal(size=per).astype(np.float32))
        for i in range(4)
    }


def bench_compression(repeats: int = 5, smoke: bool = False) -> dict:
    """Host vs device producer: encode wall-clock, payload bytes, D2H bytes.

    Returns per-model entries like ``topk8_host`` / ``topk8_device`` plus
    ``*_device_speedup``; parity between the two producers' frames is
    asserted (decoded trees agree within the int8 quantization tolerance —
    the wire-format invariance contract).

    Backend caveat (recorded in the output): on the CPU backend "device"
    IS the host CPU — the D2H pull the device producer eliminates is a
    near-free memcpy here, and XLA:CPU's exact TopK (a partial sort) runs
    5–10× slower than numpy's introselect, so ``topk8_device_speedup`` < 1
    on CPU is expected. The structural numbers (``d2h_bytes_per_encode``
    ~payload-sized vs the host's full fp32 model+anchor pull) are
    backend-independent; on a TPU backend the selection is
    hardware-parallel and the host path's per-leaf PCIe pulls dominate.
    """
    import jax
    import jax.numpy as jnp

    from p2pfl_tpu.learning import weights as W
    from p2pfl_tpu.settings import Settings

    configs = {"mlp": None} if smoke else {"mlp": None, "wide_4m": None}
    out: dict = {"backend": jax.default_backend()}
    prev_flag = Settings.WIRE_COMPRESSION_DEVICE
    try:
        for name in configs:
            if name == "wide_4m":
                params = _wide_tree()
            else:
                params = {k: jnp.asarray(v) for k, v in _flatten(_make_model(name).params).items()}
            # proportional perturbation: distinct |delta| per coordinate, so
            # top-k selection is deterministic (no argpartition/top_k
            # tie-break divergence) and the workload is non-degenerate
            anchor = {
                k: (v * 0.99 if np.dtype(v.dtype).kind == "f" else v)
                for k, v in params.items()
            }
            raw_bytes = int(
                sum(v.size * np.dtype(v.dtype).itemsize for v in params.values())
            )
            entry: dict = {"param_bytes": raw_bytes}
            for comp in ("int8", "topk8"):
                kw = {"compression": comp}
                if comp == "topk8":
                    kw.update(anchor=anchor, anchor_tag="0:0")
                payloads = {}
                for mode, flag in (("host", False), ("device", True)):
                    Settings.WIRE_COMPRESSION_DEVICE = flag
                    payload = W.encode_params(params, **kw)  # warmup (jit compile)
                    W.reset_wire_stats()
                    t0 = time.perf_counter()
                    for _ in range(repeats):
                        payload = W.encode_params(params, **kw)
                    ms = (time.perf_counter() - t0) / repeats * 1e3
                    stats = W.wire_stats()
                    payloads[mode] = payload
                    entry[f"{comp}_{mode}"] = {
                        "encode_ms": round(ms, 3),
                        "payload_bytes": len(payload),
                        "d2h_bytes_per_encode": stats["d2h_bytes"] // repeats,
                    }
                entry[f"{comp}_device_speedup"] = round(
                    entry[f"{comp}_host"]["encode_ms"]
                    / max(entry[f"{comp}_device"]["encode_ms"], 1e-9),
                    2,
                )
                # wire-format invariance: both frames through the ONE decoder
                Settings.WIRE_COMPRESSION_DEVICE = False
                dkw = {"anchor": anchor, "anchor_tag": "0:0"} if comp == "topk8" else {}
                ref = W.decode_params(payloads["host"], **dkw)
                cross = W.decode_params(payloads["device"], **dkw)
                for k in ref:
                    np.testing.assert_allclose(
                        np.asarray(ref[k], np.float32),
                        np.asarray(cross[k], np.float32),
                        atol=0.05,
                        err_msg=f"host/device frame parity broke at {k} ({comp})",
                    )
            out[name] = entry
    finally:
        Settings.WIRE_COMPRESSION_DEVICE = prev_flag
    return out


def run_federation(
    n_nodes: int,
    rounds: int,
    model_name: str = "mlp",
    slow_peer_delay: float = 0.0,
    workers: int = 4,
    cache: bool = True,
    send_timeout: float = 0.5,
    train_set_size: int = 0,
    weights_plane: str = "bytes",
) -> dict:
    """One timed federation run on the in-memory byte path.

    Returns round wall-clock plus encode/cache/send accounting. epochs=0
    keeps device compute out of the measurement — what remains IS the
    gossip data plane (init push, partial gossip, diffusion).

    ``weights_plane="ici"`` re-routes model payloads through the
    shard-native ICI plane (``communication/ici.py`` — the ppermute
    fallback on this CPU bench): the byte path below stays armed as the
    per-peer fallback, so the row's host-byte counters measure what the
    plane actually kept off the host.
    """
    from p2pfl_tpu.communication import ici
    from p2pfl_tpu.communication.memory import MemoryRegistry
    from p2pfl_tpu.learning import weights as W
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.management.logger import logger
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import Settings, set_test_settings
    from p2pfl_tpu.utils import full_connection, wait_convergence, wait_to_finish

    set_test_settings()
    logger.set_level("ERROR")
    Settings.MEMORY_WIRE_CODEC = True
    Settings.WEIGHTS_PLANE = weights_plane
    Settings.GOSSIP_SEND_WORKERS = workers
    Settings.GOSSIP_PAYLOAD_CACHE = cache
    Settings.GOSSIP_SEND_TIMEOUT = send_timeout
    ici.ShardPlaneRegistry.reset()
    ici.reset_ici_stats()
    W.reset_wire_stats()
    if train_set_size:
        # slow-peer configs elect EVERYONE so the stalled node is a
        # train-set member being gossiped partials every tick — the
        # worst case the fan-out is built for
        Settings.TRAIN_SET_SIZE = train_set_size
    MemoryRegistry.reset()
    # atomic snapshot_and_reset (not the old get+reset pair): counters a
    # previous scenario's still-draining threads land between the two
    # calls can no longer leak into this scenario's window
    logger.snapshot_and_reset_comm_metrics()

    if model_name == "transformer":
        full = FederatedDataset.synthetic_lm(
            n_train=n_nodes * 32, n_test=32, seq_len=32, vocab_size=256
        )
    else:
        full = FederatedDataset.synthetic_mnist(n_train=n_nodes * 64, n_test=64)
    nodes = []
    for i in range(n_nodes):
        learner = JaxLearner(
            _make_model(model_name, seed=i), full.partition(i, n_nodes), batch_size=16
        )
        nodes.append(Node(learner=learner))
    try:
        for node in nodes:
            node.start()
        for node in nodes:
            full_connection(node, nodes)
        wait_convergence(nodes, n_nodes - 1, only_direct=True, wait=15)

        if slow_peer_delay > 0:
            slow = nodes[-1]
            orig = slow.protocol.handle_weights

            def slow_handle(env):
                time.sleep(slow_peer_delay)
                return orig(env)

            slow.protocol.handle_weights = slow_handle

        encodes_before = W.encode_call_count()
        t0 = time.perf_counter()
        nodes[0].set_start_learning(rounds=rounds, epochs=0)
        # with a stalled peer injected, the figure of merit is when the
        # HEALTHY nodes close their rounds — the stalled peer is slow by
        # construction (it pays its own sleeps) and catches up afterwards
        wait_to_finish(nodes[:-1] if slow_peer_delay > 0 else nodes, timeout=300)
        wall_s = time.perf_counter() - t0
        encodes = W.encode_call_count() - encodes_before
        # harvest atomically: the federation's heartbeat/gossip threads are
        # still incrementing — a get+reset pair here would lose whatever
        # lands in the gap (and double-count it into the next scenario)
        comm = logger.snapshot_and_reset_comm_metrics()

        def total(metric):
            return int(sum(m.get(metric, 0) for m in comm.values()))

        wire = W.wire_stats()
        ici_stats = ici.ici_stats()
        return {
            "n_nodes": n_nodes,
            "rounds": rounds,
            "model": model_name,
            "workers": workers,
            "cache": cache,
            "send_timeout_s": send_timeout,
            "slow_peer_delay_s": slow_peer_delay,
            "weights_plane": weights_plane,
            "round_wall_s": round(wall_s / rounds, 3),
            "total_wall_s": round(wall_s, 3),
            "encode_calls": encodes,
            "encode_calls_per_node_round": round(encodes / (n_nodes * rounds), 3),
            "cache_hits": total("encode_cache_hit"),
            "cache_misses": total("encode_cache_miss"),
            "sends_ok": total("gossip_send_ok"),
            "send_timeouts": total("gossip_send_timeout"),
            "inflight_skips": total("gossip_send_inflight_skip"),
            # bytes-over-host (the ICI row's headline): payload bytes the
            # encode pipeline materialized + D2H it pulled, plus the
            # shard plane's own accounting and the receiver-side D2D
            # fix-up copies FedAvg counted (ICI contract: zero)
            "host_payload_bytes": wire["payload_bytes"],
            "host_d2h_bytes": wire["d2h_bytes"],
            "ici_shard_sends": ici_stats["shard_sends"],
            "ici_bytes_moved": ici_stats["bytes_moved"],
            "ici_fallback_bytes": ici_stats["fallback_bytes"],
            "ici_align_violations": ici_stats["align_violations"],
            "tree_align_copies": total("tree_align_copies"),
        }
    finally:
        for node in nodes:
            node.stop()
        MemoryRegistry.reset()
        ici.ShardPlaneRegistry.reset()
        Settings.MEMORY_WIRE_CODEC = False
        Settings.WEIGHTS_PLANE = "bytes"
        Settings.GOSSIP_PAYLOAD_CACHE = True
        Settings.GOSSIP_SEND_WORKERS = 4


# distinct payload contents a node can produce in one epochs=0 round: the
# init-model push, its (unfit) contribution, one combined partial, and the
# post-aggregation diffusion — the encode-once ceiling asserted in CI
MAX_ENCODES_PER_NODE_ROUND = 4.0


def _stream_worker(mode: str, size_mb: int, chunk_mb: float) -> dict:
    """One weights transfer over REAL loopback gRPC in a fresh process.

    Runs out-of-process so ``ru_maxrss`` is an honest per-mode peak — the
    parent (and the other mode) never pollutes the high-water mark. Both
    endpoints live in this one process (loopback needs a server), so the
    peak covers sender + receiver; the structural gap stays visible: the
    unary path holds payload + gRPC message + receiver bytes + decode
    copies concurrently, the streamed path holds the chunk list plus a
    window of in-flight frames plus the incrementally decoded leaves.
    """
    import resource
    import threading

    from p2pfl_tpu.communication.grpc_transport import GrpcProtocol
    from p2pfl_tpu.learning import weights as W
    from p2pfl_tpu.learning.weights import ModelUpdate
    from p2pfl_tpu.management.logger import logger
    from p2pfl_tpu.settings import Settings

    logger.set_level("ERROR")
    Settings.HEARTBEAT_PERIOD = 30.0
    Settings.GRPC_TIMEOUT = 120.0
    Settings.WIRE_CHUNK_MB = chunk_mb
    if mode == "stream":
        Settings.WIRE_STREAM_ENABLED = True
        Settings.WIRE_STREAM_THRESHOLD = 1.0
    else:
        Settings.WIRE_STREAM_ENABLED = False

    leaf = 4 * 1024 * 1024  # 4 MB fp32 leaves
    n_leaves = max(1, (size_mb * 1024 * 1024) // leaf)
    rng = np.random.default_rng(0)
    tree = {
        f"block{i}/w": rng.normal(size=leaf // 4).astype(np.float32)
        for i in range(n_leaves)
    }

    a, b = GrpcProtocol("127.0.0.1:0"), GrpcProtocol("127.0.0.1:0")
    a.start()
    b.start()
    assert a.connect(b.get_address())

    done = threading.Event()

    class _Sink:
        def get_name(self):
            return "add_model"

        def execute(self, source, round, *args, **kwargs):  # noqa: A002
            done.set()

    b.add_command(_Sink())

    # overlap probe: timestamp every chunk as the receiver's decoder pulls
    # it — on the streamed path decode work is spread across
    # [first_chunk, last_chunk] while bytes are still arriving; unary
    # decodes strictly after the full payload lands (overlap window = 0)
    chunk_ts: list = []
    orig_stream = b.handle_weights_stream

    def probed(env, chunks):
        def ticking():
            for c in chunks:
                chunk_ts.append(time.perf_counter())
                yield c

        return orig_stream(env, ticking())

    b.handle_weights_stream = probed

    try:
        env = a.build_weights("add_model", 0, ModelUpdate(tree, ["bench"], 1))
        payload_bytes = len(env.update.encode())  # warm + exact size
        # best-of-3 transfers (single loopback runs are ±15% noisy); RSS
        # high-water marks accumulate across all repeats in both modes
        walls = []
        rss_before_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        for _ in range(3):
            env.update.encoded = None  # both modes re-encode inside the send
            done.clear()
            del chunk_ts[:]
            t0 = time.perf_counter()
            ok = a.send(b.get_address(), env)
            send_done = time.perf_counter()
            assert ok, f"{mode} transfer failed"
            assert done.wait(timeout=60), "receiver never dispatched the update"
            walls.append(time.perf_counter() - t0)
        wall_s = min(walls)
        rss_after_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        overlap_s = (
            min(send_done, chunk_ts[-1]) - chunk_ts[0] if len(chunk_ts) > 1 else 0.0
        )
        return {
            "mode": mode,
            "payload_mb": round(payload_bytes / 1e6, 1),
            "wall_s": round(wall_s, 3),
            "mb_per_s": round(payload_bytes / 1e6 / wall_s, 1),
            "peak_rss_mb": round(rss_after_kb / 1024, 1),
            "transfer_rss_growth_mb": round((rss_after_kb - rss_before_kb) / 1024, 1),
            "stream_sends": a.wire_stats["stream_sends"],
            "stream_chunks": a.wire_stats["stream_chunks"],
            "stream_fallback_unary": a.wire_stats["stream_fallback_unary"],
            "recv_scratch_peak_mb": round(
                W.wire_stats()["stream_peak_scratch_bytes"] / 1e6, 2
            ),
            "wire_decode_overlap_s": round(overlap_s, 3),
        }
    finally:
        a.stop()
        b.stop()


def bench_stream(size_mb: int = 104, chunk_mb: float = 4.0) -> dict:
    """Streamed vs option-raised-unary weights transfer over loopback gRPC.

    Each mode runs in its own subprocess (``--stream-worker``) so peak RSS
    is per-mode truth. The streamed row's claims: wall-clock at or below
    the unary path (pipelined wire/decode overlap), receiver scratch
    bounded by chunk + largest leaf — NOT payload-sized — and zero
    fallbacks.
    """
    script = os.path.abspath(__file__)
    rows = {}
    for mode in ("unary", "stream"):
        proc = subprocess.run(
            [sys.executable, script, "--stream-worker", mode,
             "--size-mb", str(size_mb), "--chunk-mb", str(chunk_mb)],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, (
            f"stream worker mode={mode} rc={proc.returncode}:\n"
            f"{proc.stdout[-3000:]}\n{proc.stderr[-3000:]}"
        )
        rows[mode] = json.loads(proc.stdout.strip().splitlines()[-1])
    st, un = rows["stream"], rows["unary"]
    assert st["stream_sends"] >= 1 and st["stream_fallback_unary"] == 0, st
    assert un["stream_sends"] == 0, un
    assert st["wire_decode_overlap_s"] > 0, (
        "streamed transfer showed no wire/decode overlap window"
    )
    assert st["recv_scratch_peak_mb"] * 4 < st["payload_mb"], (
        f"receiver scratch {st['recv_scratch_peak_mb']} MB is not bounded "
        f"vs the {st['payload_mb']} MB payload"
    )
    return {
        "unary": un,
        "stream": st,
        "stream_speedup": round(un["wall_s"] / max(st["wall_s"], 1e-9), 2),
        "peak_rss_saved_mb": round(
            un["transfer_rss_growth_mb"] - st["transfer_rss_growth_mb"], 1
        ),
        "chunk_mb": chunk_mb,
        "backend": "loopback gRPC, both endpoints in one subprocess per mode",
    }


def _dcn_fleet(plane: str, rounds: int = 2) -> dict:
    """One 2-process × 1-node fleet via ``examples/dcn_fleet.py --json``.

    The fleet MUST run out-of-process: each worker is a member of one
    ``jax.distributed`` world, and ``jax.distributed.initialize`` is
    once-per-process — the bench parent (which already holds a backend)
    can only orchestrate.
    """
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "examples", "dcn_fleet.py")
    proc = subprocess.run(
        [sys.executable, script, "--json", "--plane", plane,
         "--procs", "2", "--nodes-per-proc", "1", "--rounds", str(rounds)],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, (
        f"dcn_fleet plane={plane} rc={proc.returncode}:\n{proc.stdout[-3000:]}"
        f"\n{proc.stderr[-3000:]}"
    )
    merged = json.loads(proc.stdout.strip().splitlines()[-1])
    assert merged["ok"], merged
    return merged


def bench_dcn(rounds: int = 2) -> dict:
    """DCN weights plane vs the byte path across a REAL process boundary:
    the same 2-process federation once with cross-process model payloads as
    device arrays over the distributed world's collectives, once pickled
    over gRPC. On this CPU anchor the world runs gloo collectives over
    localhost, so round_s is structural (protocol + copies), not an
    interconnect measurement — a TPU pod rides the actual DCN."""
    dcn_row = _dcn_fleet("dcn", rounds=rounds)
    byte_row = _dcn_fleet("bytes", rounds=rounds)
    assert dcn_row["dcn_sends"] > 0, dcn_row
    assert dcn_row["fallback_bytes"] == 0, dcn_row
    assert dcn_row["weights_bytes_grpc"] == 0, dcn_row
    assert byte_row["weights_bytes_grpc"] > 0, byte_row
    return {
        "dcn_plane": dcn_row,
        "grpc_byte_path": byte_row,
        "grpc_weight_bytes": {
            "bytes": byte_row["weights_bytes_grpc"],
            "dcn": dcn_row["weights_bytes_grpc"],
        },
        "device_bytes_moved": {
            "bytes": 0,
            "dcn": dcn_row["bytes_moved_device"],
        },
        "s_per_round": {
            "bytes": byte_row["round_s"],
            "dcn": dcn_row["round_s"],
        },
        "backend": "gloo over localhost (CPU anchor; TPU pods ride the DCN)",
    }


def main() -> int:
    from p2pfl_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small run + invariant asserts (CI)")
    ap.add_argument("--out", default="BENCH_GOSSIP.json")
    ap.add_argument("--stream-worker", choices=("unary", "stream"),
                    help="internal: run one loopback transfer and print JSON")
    ap.add_argument("--size-mb", type=int, default=104)
    ap.add_argument("--chunk-mb", type=float, default=4.0)
    args = ap.parse_args()

    if args.stream_worker:
        print(json.dumps(_stream_worker(args.stream_worker, args.size_mb, args.chunk_mb)))
        return 0

    results: dict = {"smoke": bool(args.smoke)}

    if args.smoke:
        fed = run_federation(n_nodes=3, rounds=1)
        results["federation"] = fed
        assert fed["cache_hits"] >= 1, "payload cache never hit on the byte path"
        assert fed["encode_calls_per_node_round"] <= MAX_ENCODES_PER_NODE_ROUND, (
            f"encode-once regressed: {fed['encode_calls_per_node_round']} encodes "
            f"per node-round (max {MAX_ENCODES_PER_NODE_ROUND}) — the cache is "
            "not being reused across candidates/ticks"
        )
        # device-codec guard: parity is asserted inside bench_compression;
        # on top of it, the device producer's D2H must be ~payload-sized
        comp = bench_compression(repeats=2, smoke=True)
        results["compression"] = comp
        tk_dev = comp["mlp"]["topk8_device"]
        assert tk_dev["d2h_bytes_per_encode"] < comp["mlp"]["param_bytes"] / 4, (
            f"device topk8 encode pulled {tk_dev['d2h_bytes_per_encode']} bytes D2H "
            f"for a {comp['mlp']['param_bytes']}-byte model — the fused encode is "
            "no longer keeping the model on device"
        )
        assert tk_dev["d2h_bytes_per_encode"] < tk_dev["payload_bytes"] * 3, (
            "device topk8 D2H should be on the order of the payload, not the model"
        )
        # ICI weights plane: same fleet, model payloads shard-to-shard —
        # the parity + zero-D2H smoke (the ppermute fallback on CI's CPU)
        ici_fed = run_federation(n_nodes=3, rounds=1, weights_plane="ici")
        results["ici_federation"] = ici_fed
        assert ici_fed["ici_shard_sends"] > 0, "ICI plane never carried a payload"
        assert ici_fed["ici_fallback_bytes"] == 0, (
            f"{ici_fed['ici_fallback_bytes']} co-located sends fell back to bytes"
        )
        assert ici_fed["host_payload_bytes"] == 0 and ici_fed["host_d2h_bytes"] == 0, (
            "ICI round materialized model bytes host-side "
            f"(payload={ici_fed['host_payload_bytes']}, d2h={ici_fed['host_d2h_bytes']})"
            " — the zero-host-bytes contract broke"
        )
        assert ici_fed["encode_calls"] == 0, (
            f"{ici_fed['encode_calls']} byte encodes ran under WEIGHTS_PLANE=ici"
        )
        assert ici_fed["ici_align_violations"] == 0 and ici_fed["tree_align_copies"] == 0, (
            "ICI deliveries needed device fix-up copies — the no-realign "
            "contract broke"
        )
        # DCN weights plane: a real 2-process world, model payloads as
        # device arrays across the process boundary — zero pickled weight
        # bytes on gRPC (the asserts live in bench_dcn / the fleet driver)
        results["dcn_federation"] = bench_dcn(rounds=1)
        # streaming byte plane: a shrunken transfer over real loopback gRPC
        # — the invariant asserts (stream engaged, zero fallbacks, wire/
        # decode overlap observed, receiver scratch bounded) live inside
        # bench_stream; wall-clock claims are left to the full run
        results["stream"] = bench_stream(size_mb=16, chunk_mb=2.0)
        print(json.dumps(results, indent=2))
        print("SMOKE OK: encode-once + device-codec + ICI zero-D2H + "
              "DCN zero-pickled-bytes + stream-overlap invariants hold")
        return 0

    results["codec"] = bench_codec()
    results["compression"] = bench_compression()
    # warm the jit/codec caches so neither timed variant pays first-compile
    run_federation(n_nodes=8, rounds=1)
    results["sequential_nocache"] = run_federation(
        n_nodes=8, rounds=1, slow_peer_delay=2.0, workers=1, cache=False,
        send_timeout=60.0, train_set_size=8,
    )
    results["concurrent_cached"] = run_federation(
        n_nodes=8, rounds=1, slow_peer_delay=2.0, workers=4, cache=True,
        send_timeout=0.25, train_set_size=8,
    )
    results["transformer_federation"] = run_federation(
        n_nodes=8, rounds=1, model_name="transformer"
    )
    seq, conc = results["sequential_nocache"], results["concurrent_cached"]
    results["round_speedup_with_slow_peer"] = round(
        seq["round_wall_s"] / max(conc["round_wall_s"], 1e-9), 2
    )
    # ICI weights plane vs the memory byte path: same fleet, same rounds —
    # bytes-over-host and s/round are the row's two claims (on this CPU
    # anchor "ICI" is the ppermute fallback over virtual devices, so the
    # wall-clock is structural, not an interconnect measurement)
    mem_row = run_federation(n_nodes=4, rounds=2)
    ici_row = run_federation(n_nodes=4, rounds=2, weights_plane="ici")
    results["ici"] = {
        "memory_byte_path": mem_row,
        "ici_plane": ici_row,
        "host_payload_bytes": {
            "memory": mem_row["host_payload_bytes"],
            "ici": ici_row["host_payload_bytes"],
        },
        "s_per_round": {
            "memory": mem_row["round_wall_s"],
            "ici": ici_row["round_wall_s"],
        },
        "backend": "ppermute-fallback (CPU virtual devices)",
    }
    # DCN plane vs byte path across a REAL process boundary (two OS
    # processes, one jax.distributed world) — grpc_weight_bytes drops to
    # zero while the payloads move device-to-device via collectives
    results["dcn"] = bench_dcn(rounds=2)
    # streaming byte plane: ≥100 MB model over real loopback gRPC, chunked
    # stream vs the option-raised unary path — wall-clock, peak RSS and the
    # measured receiver scratch bound are the row's claims
    results["stream"] = bench_stream(size_mb=104, chunk_mb=4.0)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
