"""Multi-process DCN fleet driver: real federation over the DCN weights plane.

Spawns N OS processes that form ONE ``jax.distributed`` world (CPU: gloo
collectives, wired by ``init_multihost``; on a TPU pod the same code rides
the real DCN), runs M gRPC nodes per process through a full federated
experiment, and reports where the model payloads actually travelled:

- co-resident node pairs ride the ICI plane (device-to-device, one process),
- cross-process same-world pairs ride the DCN plane (XLA cross-host
  collectives — ZERO pickled weight bytes on gRPC between them),
- anything else falls back to the byte path, loudly and per edge.

Modes:

    python examples/dcn_fleet.py                    # 2 procs × 1 node, 2 rounds
    python examples/dcn_fleet.py --procs 3 --nodes-per-proc 2 --rounds 3
    python examples/dcn_fleet.py --plane bytes      # control run, byte transport
    python examples/dcn_fleet.py --smoke            # CI: assert zero pickled bytes
    python examples/dcn_fleet.py --kill             # async root kill + failover drill
    python examples/dcn_fleet.py --compression topk8

The parent allocates one coordinator port, spawns workers (re-executing this
file with ``--worker PID``), and aggregates each worker's ``RESULT`` line.
``--json`` restricts parent stdout to a single merged JSON object — the
machine seam ``bench_gossip.py --dcn`` builds its honest ``dcn`` row from.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--procs", type=int, default=2, help="world size (OS processes)")
    ap.add_argument("--nodes-per-proc", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--plane", choices=("dcn", "bytes"), default="dcn")
    ap.add_argument("--compression", choices=("none", "int8", "topk8"), default="none")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small fleet + hard zero-pickled-bytes asserts")
    ap.add_argument("--kill", action="store_true",
                    help="async failover drill: hard-kill the global-root process "
                         "mid-experiment (forces --procs 2, --nodes-per-proc 1)")
    ap.add_argument("--json", action="store_true",
                    help="parent prints ONE merged JSON object, nothing else")
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--coord-port", type=int, default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


# ---------------------------------------------------------------- worker ----


def run_worker(args) -> None:
    pid = args.worker
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{args.coord_port}"
    os.environ["JAX_NUM_PROCESSES"] = str(args.procs)
    os.environ["JAX_PROCESS_ID"] = str(pid)

    from p2pfl_tpu.parallel.distributed import init_multihost, kv_client

    info = init_multihost()
    assert info["initialized"] and info["process_count"] == args.procs, info

    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2pfl_tpu.communication.dcn import dcn_stats
    from p2pfl_tpu.communication.grpc_transport import GrpcProtocol
    from p2pfl_tpu.communication.ici import ici_stats
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import Settings
    from p2pfl_tpu.utils import wait_to_finish

    Settings.WEIGHTS_PLANE = args.plane
    Settings.WIRE_COMPRESSION = args.compression
    if args.kill:
        Settings.FEDERATION_MODE = "async"
        Settings.FEDBUFF_K = 2

    total = args.procs * args.nodes_per_proc
    base_grpc = args.coord_port + 1

    def addr_of(index: int) -> str:
        return f"127.0.0.1:{base_grpc + index}"

    # the kill drill victimizes process 1 but the failover story needs the
    # victim to host the GLOBAL ROOT (federation/routing.py: first live
    # member in address order) — so swap the two processes' address slots
    def my_indices():
        if args.kill:
            return [1 - pid]  # pid 1 → addr slot 0 (the root), pid 0 → slot 1
        return [pid * args.nodes_per_proc + j for j in range(args.nodes_per_proc)]

    client = kv_client()

    def barrier(name: str) -> None:
        client.wait_at_barrier(f"dcn_fleet_{name}", 180_000)

    data = FederatedDataset.synthetic_mnist(
        n_train=128 * max(2, total), n_test=64, seed=7
    )
    nodes = []
    for idx in my_indices():
        learner = JaxLearner(
            mlp(seed=idx), data.partition(idx, total), batch_size=32
        )
        node = Node(learner=learner, protocol=GrpcProtocol(addr_of(idx)))
        node.start()
        nodes.append(node)
    barrier("up")

    # one dialer per edge (links are bidirectional); success = membership
    all_addrs = [addr_of(i) for i in range(total)]
    for node in nodes:
        for other in all_addrs:
            if other <= node.addr:
                continue
            for _ in range(200):
                if node.connect(other) or other in node.get_neighbors(only_direct=True):
                    break
                time.sleep(0.1)
            else:
                raise RuntimeError(f"{node.addr} never connected to {other}")
    deadline = time.time() + 60
    while any(len(n.get_neighbors(only_direct=True)) < total - 1 for n in nodes):
        if time.time() > deadline:
            raise RuntimeError("overlay convergence timeout")
        time.sleep(0.1)
    barrier("mesh")

    t0 = time.monotonic()
    if pid == 0:
        # in the kill drill pid 0 holds slot 1 and survives; otherwise the
        # first node everywhere — either way ONE initiator
        nodes[0].set_start_learning(rounds=args.rounds, epochs=args.epochs)

    if args.kill and pid == 1:
        # the victim: wait until the experiment (and the init-model DCN
        # payload) reached us, then die without any goodbye
        deadline = time.time() + 120
        while nodes[0].state.round is None and time.time() < deadline:
            time.sleep(0.05)
        assert nodes[0].state.round is not None, "experiment never started"
        nodes[0].state.model_initialized_event.wait(30)
        time.sleep(0.5)
        print(f"VICTIM {pid}: dying hard", flush=True)
        os._exit(9)

    wait_to_finish(nodes, timeout=120 + 120 * args.rounds)
    wall = time.monotonic() - t0

    fp = sum(
        float(np.sum(np.abs(np.asarray(x, dtype=np.float32))))
        for x in jax.tree.leaves(nodes[0].learner.get_parameters())
    )
    weights_bytes = sum(
        dict(n.protocol.wire_stats).get("weights_bytes", 0) for n in nodes
    )
    result = {
        "pid": pid,
        "plane": args.plane,
        "compression": args.compression,
        "nodes": len(nodes),
        "rounds": args.rounds,
        "wall_s": round(wall, 3),
        "round_s": round(wall / max(1, args.rounds), 3),
        "weights_bytes_grpc": weights_bytes,
        "fingerprint": fp,
        "dcn": dcn_stats(),
        "ici_shard_sends": ici_stats()["shard_sends"],
    }

    if not args.kill:
        # every process ends holding the same diffused aggregate
        from jax.experimental.multihost_utils import process_allgather

        got = process_allgather(jnp.float32(fp))
        # >2 contributors fold the same aggregate set in per-node arrival
        # order — float32 reassociation, not a transport divergence. Lossy
        # codecs widen it: each node folds its OWN exact params against the
        # peers' quantized deltas (identical on the byte path), so int8/
        # topk8 spreads carry the quantization error, not a plane bug.
        rel_tol = 1e-5 if args.compression == "none" else 1e-2
        spread = float(np.max(got)) - float(np.min(got))
        assert spread <= rel_tol * max(1.0, abs(float(np.max(got)))), got
        if args.plane == "dcn":
            s = result["dcn"]
            assert s["dcn_sends"] > 0 and s["dcn_recvs"] > 0, s
            if args.compression == "topk8":
                # delta payloads whose anchor round the receiver doesn't
                # hold yet fall back loudly (anchor_round_mismatch — the
                # byte path's AnchorMismatchError-skip semantics); allow
                # those transient early-round edges, nothing more
                assert s["fallback_bytes"] <= args.rounds, s
            else:
                assert s["fallback_bytes"] == 0, s
                # the tentpole: zero pickled model bytes on gRPC
                assert weights_bytes == 0, result
        else:
            assert weights_bytes > 0, result

    print("RESULT " + json.dumps(result), flush=True)
    for n in nodes:
        n.stop()
    if args.kill:
        # skip atexit: jax.distributed's shutdown barrier aborts when a
        # world member died mid-run — which is this drill's whole point
        print(f"OK fleet process {pid}", flush=True)
        os._exit(0)
    print(f"OK fleet process {pid}", flush=True)


# ---------------------------------------------------------------- parent ----


def run_parent(args) -> int:
    if args.smoke:
        args.procs, args.nodes_per_proc, args.rounds = 2, 1, 2
    if args.kill:
        args.procs, args.nodes_per_proc = 2, 1
        args.rounds = max(args.rounds, 3)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]

    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH")) if p
    )
    cmd_base = [
        sys.executable, os.path.abspath(__file__),
        "--procs", str(args.procs),
        "--nodes-per-proc", str(args.nodes_per_proc),
        "--rounds", str(args.rounds),
        "--epochs", str(args.epochs),
        "--plane", args.plane,
        "--compression", args.compression,
        "--coord-port", str(coord_port),
    ]
    if args.kill:
        cmd_base.append("--kill")
    procs = [
        subprocess.Popen(
            cmd_base + ["--worker", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )
        for pid in range(args.procs)
    ]
    outs = []
    timeout = 180 + 150 * args.rounds
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            print("FLEET HUNG — coordinator never formed or a worker stalled",
                  file=sys.stderr)
            return 2
        outs.append(out)

    results, ok = [], True
    for pid, (p, out) in enumerate(zip(procs, outs)):
        expected_rc = 9 if (args.kill and pid == 1) else 0
        if p.returncode != expected_rc:
            ok = False
            print(f"worker {pid} rc={p.returncode} (expected {expected_rc}):\n"
                  + out[-3000:], file=sys.stderr)
            continue
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results.append(json.loads(line[len("RESULT "):]))
    if args.kill and ok:
        survivor = [r for r in results if r["pid"] == 0]
        if not survivor or survivor[0]["dcn"]["dcn_sends"] < 1:
            ok = False
            print("kill drill: survivor missing or no DCN traffic pre-kill",
                  file=sys.stderr)

    merged = {
        "plane": args.plane,
        "compression": args.compression,
        "procs": args.procs,
        "nodes_per_proc": args.nodes_per_proc,
        "rounds": args.rounds,
        "kill": args.kill,
        "ok": ok,
        "round_s": max((r["round_s"] for r in results), default=None),
        "weights_bytes_grpc": sum(r["weights_bytes_grpc"] for r in results),
        "dcn_sends": sum(r["dcn"]["dcn_sends"] for r in results),
        "dcn_recvs": sum(r["dcn"]["dcn_recvs"] for r in results),
        "bytes_moved_device": sum(r["dcn"]["bytes_moved"] for r in results),
        "fallback_bytes": sum(r["dcn"]["fallback_bytes"] for r in results),
        "ici_shard_sends": sum(r["ici_shard_sends"] for r in results),
        "workers": results,
    }
    if args.json:
        print(json.dumps(merged))
    else:
        print(f"\n=== DCN fleet: {args.procs} procs × {args.nodes_per_proc} nodes, "
              f"plane={args.plane}, compression={args.compression} ===")
        for r in sorted(results, key=lambda r: r["pid"]):
            print(f"  proc {r['pid']}: round_s={r['round_s']:.2f} "
                  f"dcn_sends={r['dcn']['dcn_sends']} dcn_recvs={r['dcn']['dcn_recvs']} "
                  f"device_bytes={r['dcn']['bytes_moved']} "
                  f"grpc_weight_bytes={r['weights_bytes_grpc']} "
                  f"fallbacks={r['dcn']['fallback_bytes']} "
                  f"ici_sends={r['ici_shard_sends']}")
        verdict = "PASS" if ok else "FAIL"
        if args.kill:
            print(f"  kill drill: victim died, survivor finished → {verdict}")
        else:
            print(f"  fleet {verdict}: zero-pickled-bytes="
                  f"{merged['weights_bytes_grpc'] == 0}")
    return 0 if ok else 1


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.worker is not None:
        run_worker(args)
        return 0
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
