"""The builders' measurement configs (config 1 anchor included).

Each config prints ONE JSON line. The headline driver metric stays in
``bench.py``. Nothing here has been measured on this installation — the
rounds-3–5 results (``BENCH_SUITE.json``/``BASELINE.md``) were deleted at
PR 21, see git history; ROADMAP S1/D7 replaces this harness. A config that
fails makes the process exit non-zero. Configs:

1. MNIST MLP, 2 nodes, FedAvg, in-memory Node mode (reference CI anchor)
2. CIFAR-10-shaped ResNet-18, 8 nodes, FedAvg, SPMD (+ MFU)
3. CIFAR-100-shaped ResNet-50, 64 nodes, Dirichlet(0.5) non-IID, SPMD
4. Krum + TrimmedMean with 20% Byzantine nodes, CIFAR-10 ResNet-18
5. LoRA transformer federation, 32 nodes, FedAvg on LoRA deltas

Data is the synthetic stand-in everywhere (no download egress); provenance
is recorded per line. All accuracy numbers are real multi-round
convergence trajectories, not single-dispatch saturation.

Usage: ``python bench_suite.py [config ...]`` (default: all).
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.management.profiling import force_execution


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def _steady_state(fed, rounds: int = 3) -> float:
    t0 = time.monotonic()
    for _ in range(rounds):
        fed.run_round(epochs=1)
    force_execution(fed.params)
    return (time.monotonic() - t0) / rounds


def _spmd_mfu(fed, sec_per_round: float):
    from p2pfl_tpu.management.profiling import mfu

    flops = fed.round_flops()
    n_dev = len(set(fed.mesh.devices.flat))
    return flops, mfu(flops, sec_per_round, n_devices=n_dev)


def _mfu_from(flops, seconds: float):
    from p2pfl_tpu.management.profiling import mfu

    return mfu(flops, seconds)


def _reexec(config_key: str, timeout: int = 900, cpu: bool = True, virtual_devices: int = 0):
    """Run one config in a child process and forward its JSON.

    A chip belongs to one process: a parent that has touched JAX holds it,
    so the children started here are pinned to the CPU backend
    (``cpu=True``); ``virtual_devices`` adds the host-platform device-count
    flag for virtual-mesh children. A failed child raises after its error
    line is emitted — the run must not exit 0.
    """
    import os
    import subprocess

    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    if virtual_devices:
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={virtual_devices}"]
        )
    proc = subprocess.run(
        [sys.executable, __file__, config_key], env=env,
        capture_output=True, text=True, timeout=timeout,
    )
    sys.stderr.write(proc.stderr[-2000:])
    if proc.returncode == 0 and proc.stdout.strip():
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
    else:
        emit({
            "metric": f"config{config_key}",
            "error": f"re-exec rc={proc.returncode}: {proc.stderr[-300:]}",
        })
        raise RuntimeError(f"config {config_key} re-exec failed (rc={proc.returncode})")


def config1_mnist_2node() -> None:
    """Reference CI anchor: 2 Node objects, in-memory transport, 1 epoch.

    This row is the CPU reference (BASELINE table: "in-memory comm (CPU
    ref)", mirroring the reference's own CI test which runs on CPU) — it
    measures the protocol stack, not an accelerator. The profiling
    breakdown (emitted below) shows the stack is COMPUTE-dominated on CPU:
    fit + evaluate account for most of the wall clock and
    gossip/aggregation waits are sub-second with the documented
    low-latency profile (``set_low_latency_settings``).
    """
    if jax.default_backend() != "cpu":
        # re-exec on the CPU backend this row is defined on; the parent
        # (possibly holding the TPU) just forwards the child's JSON
        _reexec("1", timeout=600)
        return

    import collections
    import functools

    from p2pfl_tpu.communication.gossiper import Gossiper
    from p2pfl_tpu.learning.aggregators.aggregator import Aggregator
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import set_low_latency_settings
    from p2pfl_tpu.utils import wait_to_finish

    # per-primitive wall-clock accounting (summed across both node threads)
    acc: collections.Counter = collections.Counter()

    def timed(name, fn):
        @functools.wraps(fn)
        def wrapper(*a, **k):
            t0 = time.monotonic()
            try:
                return fn(*a, **k)
            finally:
                acc[name] += time.monotonic() - t0

        return wrapper

    Gossiper.gossip_weights = timed("gossip_s", Gossiper.gossip_weights)
    Aggregator.wait_and_get_aggregation = timed("agg_wait_s", Aggregator.wait_and_get_aggregation)
    JaxLearner.fit = timed("fit_s", JaxLearner.fit)
    JaxLearner.evaluate = timed("eval_s", JaxLearner.evaluate)

    from p2pfl_tpu.management.profiling import (
        mfu,
        snapshot_and_reset_dispatch_counts,
    )
    from p2pfl_tpu.settings import Settings

    set_low_latency_settings()
    full = FederatedDataset.synthetic_mnist(n_train=4096, n_test=1024)
    n_nodes = 2

    def run_overlay(rounds: int, epochs: int, fused: bool, telemetry_on: bool = True) -> dict:
        """One fresh 2-node federation; returns sec/round + dispatch split.

        ``dispatches_per_round`` counts MODEL-PLANE device dispatches per
        node per round (management/profiling.py record_dispatch sites:
        eval/train/fused-round programs + aggregate kernels), excluding
        the per-node experiment-end evaluation which is outside the round
        loop on both paths. ``telemetry_on=False`` disables the flight
        recorder (ISSUE 7 overhead split — counters stay on either way,
        so the dispatch accounting is unaffected).
        """
        prev = Settings.ROUND_FUSED
        prev_telemetry = Settings.TELEMETRY_ENABLED
        Settings.ROUND_FUSED = fused
        Settings.TELEMETRY_ENABLED = telemetry_on
        nodes = []
        try:
            # compile warm-up OUTSIDE the timer: the mode's round programs
            # (same module/tx/shapes => shared jit cache) would otherwise
            # bill one XLA compile to whichever mode runs its shape first
            warm = JaxLearner(
                mlp(seed=99), full.partition(0, n_nodes), batch_size=64, epochs=epochs
            )
            if fused:
                warm.fused_round()
            else:
                warm.evaluate()
                warm.fit()
            for i in range(n_nodes):
                learner = JaxLearner(mlp(seed=i), full.partition(i, n_nodes), batch_size=64)
                n = Node(learner=learner)
                n.start()
                nodes.append(n)
            nodes[0].connect(nodes[1].addr)
            time.sleep(0.5)
            snapshot_and_reset_dispatch_counts()  # atomic clear of warm-up counts
            acc_before = dict(acc)  # primitive-timing snapshot (breakdown
            t0 = time.monotonic()   # must exclude warm-up and final eval)
            nodes[0].set_start_learning(rounds=rounds, epochs=epochs)
            wait_to_finish(nodes, timeout=300)
            elapsed = time.monotonic() - t0
            # atomic harvest: the nodes' threads are still live here — a
            # get+reset pair would lose dispatches landing in the gap
            counts = snapshot_and_reset_dispatch_counts()
            run_breakdown = {
                k: round(v - acc_before.get(k, 0.0), 2)
                for k, v in sorted(acc.items())
                if v - acc_before.get(k, 0.0) > 0
            }
            final_acc = nodes[0].learner.evaluate()["test_acc"]
        finally:
            Settings.ROUND_FUSED = prev
            Settings.TELEMETRY_ENABLED = prev_telemetry
            for n in nodes:
                n.stop()
        in_round = sum(counts.values()) - n_nodes  # minus experiment-end evals
        return {
            "sec_per_round": round(elapsed / rounds, 4),
            "dispatches_per_round": round(in_round / (rounds * n_nodes), 2),
            "dispatch_counts": {k: int(v) for k, v in sorted(counts.items())},
            "final_acc": round(float(final_acc), 4),
            "breakdown": run_breakdown,
        }

    rounds = 3
    # anchor pair at the historical config (1 local epoch): staged first —
    # the timed-primitive breakdown wrappers above only fire on the staged
    # path — then the fused default the headline value now reports
    staged1 = run_overlay(rounds, epochs=1, fused=False)
    breakdown = staged1["breakdown"]
    fused1 = run_overlay(rounds, epochs=1, fused=True)

    # dispatch-tax split at 5 local epochs (ISSUE 6 flagship row): the
    # staged path pays 1 eval + 5 train + aggregate dispatches per node
    # per round; the fused path one program + aggregate — the ≥ 3×
    # reduction guarded by tests/test_fused_round.py in round_bench.yml
    split_epochs = 5
    staged5 = run_overlay(rounds, epochs=split_epochs, fused=False)
    fused5 = run_overlay(rounds, epochs=split_epochs, fused=True)

    # ISSUE 7 overhead split: the flight recorder (stage/gossip/dispatch
    # spans, wire trace ctx, per-span histogram feed) must stay ≤5% on
    # this round loop. Longer runs than the headline pair because the
    # on/off delta is small against protocol-tick noise; the headline
    # value above already INCLUDES telemetry (it is on by default).
    tel_rounds = 6
    tel_on = run_overlay(tel_rounds, epochs=1, fused=True)
    tel_off = run_overlay(tel_rounds, epochs=1, fused=True, telemetry_on=False)
    telemetry_overhead_pct = round(
        (tel_on["sec_per_round"] - tel_off["sec_per_round"])
        / tel_off["sec_per_round"]
        * 100,
        2,
    )

    # model FLOPs of one overlay round (all nodes, scan-free single-step
    # probe x steps — the same scan-trip-count correction every SPMD
    # round_flops applies), so the overlay round gets a first-class MFU
    # row (null off-TPU like every other row's)
    import jax.numpy as jnp
    import optax

    from p2pfl_tpu.learning.learner import _loss
    from p2pfl_tpu.management.profiling import compiled_flops

    probe = JaxLearner(mlp(seed=0), full.partition(0, n_nodes), batch_size=64)

    def one_step(p, o, bx, by):
        loss, grads = jax.value_and_grad(
            lambda p_: _loss(p_, probe.model.module, bx, by)[0]
        )(p)
        updates, o = probe.tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o

    bx = jnp.zeros((64, *full.x_train.shape[1:]), jnp.float32)
    by = jnp.zeros((64,), jnp.int32)
    step_flops = compiled_flops(jax.jit(one_step), probe.params, probe.opt_state, bx, by)
    nb = probe.data.num_samples // 64
    flops_round = (
        step_flops * split_epochs * nb * n_nodes if step_flops is not None else None
    )
    overlay_mfu = (
        mfu(flops_round, fused5["sec_per_round"]) if flops_round is not None else None
    )

    emit({
        "metric": "config1_mnist_mlp_2node_memory",
        "value": fused1["sec_per_round"],
        "unit": "sec_per_round",
        "rounds": rounds,
        "final_acc": fused1["final_acc"],
        "staged_sec_per_round": staged1["sec_per_round"],
        "data": "synthetic",
        "transport": "memory (full Node stack: gossip+vote+heartbeat)",
        "backend": "cpu (this row is the CPU reference anchor)",
        "settings_profile": "low_latency",
        # thread-summed primitive totals over the staged anchor run (2
        # node threads run concurrently, so these can exceed wall clock)
        "breakdown_thread_totals_s": breakdown,
        # ISSUE 6 first-class rows: model-plane device dispatches per node
        # per round, staged vs fused, at the 5-local-epoch split config
        "dispatches_per_round": {
            "staged": staged5["dispatches_per_round"],
            "fused": fused5["dispatches_per_round"],
            "reduction_x": round(
                staged5["dispatches_per_round"]
                / max(fused5["dispatches_per_round"], 1e-9),
                2,
            ),
        },
        "overlay_split_epochs5": {
            "staged": {k: staged5[k] for k in ("sec_per_round", "dispatches_per_round")},
            "fused": {k: fused5[k] for k in ("sec_per_round", "dispatches_per_round")},
            "note": "CPU anchor: at 5 local epochs the round is "
            "compute-dominated so staged/fused wall-clock converge here; "
            "the dispatch cut is the accelerator-facing win (each overlay "
            "dispatch is a host-device round trip)",
        },
        "flops_per_round_overlay": flops_round,
        "overlay_mfu": round(overlay_mfu, 4) if overlay_mfu is not None else None,
        # ISSUE 7 acceptance row: flight-recorder overhead on the fused
        # round loop (spans + wire trace ctx + histograms vs all off)
        "telemetry": {
            "on_sec_per_round": tel_on["sec_per_round"],
            "off_sec_per_round": tel_off["sec_per_round"],
            "overhead_pct": telemetry_overhead_pct,
            "budget_pct": 5.0,
            "rounds": tel_rounds,
        },
    })


def config2_resnet18_8node() -> None:
    """Two halves of the north-star metric (BASELINE.md:19-21):

    1. TIME-TO-TARGET-ACCURACY (VERDICT r2 #1): 8-node ResNet-18 FedAvg on
       synthetic-hard CIFAR-10 to ≥70%. Round 2's recipe (constant Adam
       1e-3, per-round moment reset, 6-round budget) flatlined at 15% —
       starved, not unlearnable (a centrally trained ResNet-18 reaches 92%
       by step 200 with a warmup schedule). The fixed federated recipe:
       warmup-cosine LR with ``keep_opt_state=True`` so the schedule and
       Adam moments survive round boundaries.
    2. SEC/ROUND + MFU at throughput settings. The MFU lever found in
       round 3: amortize the round's fixed dispatch/aggregation cost over
       more local steps (bigger shard × multi-epoch rounds) — convs were
       already bf16, buffers already donated.
    """
    import optax

    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models import resnet18
    from p2pfl_tpu.parallel import SpmdFederation

    data = FederatedDataset.synthetic_mnist(
        n_train=8 * 1024, n_test=1024, dim=(32, 32, 3), modes=8, noise=0.7, proto_scale=0.5
    )
    # --- half 1: time to target accuracy ---
    cap, spr_steps, target = 25, 16, 0.70
    sched = optax.warmup_cosine_decay_schedule(
        0.0, 3e-3, warmup_steps=2 * spr_steps, decay_steps=cap * spr_steps, end_value=1e-4
    )
    fed = SpmdFederation.from_dataset(
        resnet18(), data, n_nodes=8, batch_size=64, vote=False, seed=3,
        tx=optax.adam(sched), keep_opt_state=True,
    )
    curve = []
    rounds_to_target = None
    time_to_target = None
    t0 = time.monotonic()
    for r in range(cap):
        acc = float(fed.run_round(epochs=1, eval=True)["test_acc"])
        curve.append(round(acc, 4))
        if rounds_to_target is None and acc >= target:
            rounds_to_target = r + 1
            time_to_target = time.monotonic() - t0
            break
    log(f"config2: target {target} at round {rounds_to_target} ({time_to_target})")
    del fed
    jax.clear_caches()

    # --- half 2: throughput + MFU (2048-sample shards, batch 256) ---
    data_big = FederatedDataset.synthetic_mnist(
        n_train=8 * 2048, n_test=1024, dim=(32, 32, 3), modes=8, noise=0.7, proto_scale=0.5
    )
    fed_big = SpmdFederation.from_dataset(
        resnet18(), data_big, n_nodes=8, batch_size=256, vote=False, seed=3
    )
    fed_big.run_round(epochs=1)
    force_execution(fed_big.params)
    sec_per_round = _steady_state(fed_big)
    flops, round_mfu = _spmd_mfu(fed_big, sec_per_round)
    # multi-epoch rounds amortize the fixed per-round cost further
    fed_big.run_round(epochs=4)
    force_execution(fed_big.params)
    t0 = time.monotonic()
    for _ in range(3):
        fed_big.run_round(epochs=4)
    force_execution(fed_big.params)
    sec_ep4 = (time.monotonic() - t0) / 3
    flops_ep4 = fed_big.round_flops(epochs=4)
    from p2pfl_tpu.management.profiling import mfu as _mfu

    # same per-device normalization as the sibling mfu field
    mfu_ep4 = _mfu(flops_ep4, sec_ep4, n_devices=len(set(fed_big.mesh.devices.flat)))

    emit({
        "metric": "config2_resnet18_cifar10_8node_fedavg",
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "target_acc": target,
        "rounds_to_target": rounds_to_target,
        "time_to_target_s": round(time_to_target, 2) if time_to_target else None,
        "accuracy_curve": curve,
        "recipe": "adam warmup-cosine peak 3e-3, keep_opt_state, batch 64",
        "throughput_point": "batch 256, 2048 samples/node",
        "flops_per_round": flops,
        "mfu": round(round_mfu, 4) if round_mfu is not None else None,
        "epochs4": {
            "sec_per_round": round(sec_ep4, 4),
            "mfu": round(mfu_ep4, 4) if mfu_ep4 is not None else None,
        },
        "data": "synthetic-hard (CIFAR-10 shaped)",
        "devices": len(jax.devices()),
    })


def config3_resnet50_64node_dirichlet() -> None:
    # 64-node ResNet-50 state is 64 × (params + 2 Adam moments) ≈ 19.6 GB —
    # sized for the v4-128 pod target, over one chip's HBM resident. The
    # STATED 64 nodes run anyway by time-sharing the chip in 16-node chunks
    # (ChunkedFederation). Resident folds (32, 16) still run when 64 fails
    # so the failure comes with a measurement, but only the stated 64 is a
    # success: any other fold's line is forwarded and the config then FAILS.
    # Each attempt probes in a FRESH subprocess (a failed attempt leaves
    # the backend's allocator in an unusable state).
    import os
    import subprocess

    if os.environ.get("P2PFL_CONFIG3_NODES"):
        _config3_measure(int(os.environ["P2PFL_CONFIG3_NODES"]))
        return
    for n_nodes in (64, 32, 16):
        env = dict(os.environ, P2PFL_CONFIG3_NODES=str(n_nodes))
        proc = subprocess.run(
            [sys.executable, __file__, "3"], env=env,
            capture_output=True, text=True, timeout=2400,
        )
        sys.stderr.write(proc.stderr[-1500:])
        if proc.returncode == 0 and proc.stdout.strip():
            sys.stdout.write(proc.stdout)
            sys.stdout.flush()
            if n_nodes != 64:
                raise RuntimeError(
                    f"config3 ran the {n_nodes}-node fold, not the stated 64"
                )
            return
        log(f"config3: n={n_nodes} attempt failed (rc={proc.returncode})")
    raise RuntimeError("config3 failed at every fold")


def _config3_measure(n_nodes: int) -> None:
    """ResNet-50 / CIFAR-100-shaped / Dirichlet(0.5) non-IID, at the
    STATED 64 nodes via chip time-sharing.

    Round-3 recipe (VERDICT r2 #1): warmup-cosine + kept optimizer state —
    at 64 nodes "kept" means the ChunkedFederation moment-averaging
    divergence (per-node moments are exactly the state that doesn't fit;
    see ``parallel/chunked.py``), with the schedule's step count surviving
    rounds. Resident SpmdFederation folds (32/16) remain the fallback
    path and the apples-to-apples comparison.
    """
    import optax

    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models import resnet50
    from p2pfl_tpu.parallel import ChunkedFederation, SpmdFederation

    data = FederatedDataset.synthetic_mnist(
        n_train=64 * 256, n_test=1024, dim=(32, 32, 3), num_classes=100,
        modes=2, noise=0.5, proto_scale=0.7,
    )
    cap, target = 60, 0.50
    chunked = n_nodes >= 64
    # chunked batch: the round-5 chunk×batch sweep measured (chunk16)
    # 2.63 s/round at b32, 2.10 at b64, 1.95 at b128 (15.9% model-MFU);
    # chunk 32 OOMs. But the larger batches trade away convergence on the
    # Dirichlet task (b128: 0.04 acc at the 60-round cap, b64: 0.47 —
    # 2 resp. 4 optimizer steps/round starve the recipe), so the row keeps
    # the b32 recipe that reaches target; per-chunk data pre-staging
    # (chunked.py) already cut b32 from round-4's 3.48 to 2.63 s/round
    batch = 32
    spr_steps = (64 * 256 // n_nodes) // batch
    sched = optax.warmup_cosine_decay_schedule(
        0.0, 3e-3, warmup_steps=2 * spr_steps, decay_steps=40 * spr_steps, end_value=1e-4
    )
    if chunked:
        fed = ChunkedFederation.from_dataset(
            resnet50(), data, n_nodes=n_nodes, chunk_size=16,
            strategy="dirichlet", alpha=0.5, batch_size=batch, vote=False,
            seed=3, remat=True, tx=optax.adam(sched), keep_opt_state=True,
        )
    else:
        fed = SpmdFederation.from_dataset(
            resnet50(), data, n_nodes=n_nodes, strategy="dirichlet", alpha=0.5,
            batch_size=32, vote=False, seed=3, remat=True,
            tx=optax.adam(sched), keep_opt_state=True,
        )
    fed.run_round(epochs=1)  # warm-up + OOM probe
    force_execution(fed.params)
    fed.evaluate()  # probe the eval path's memory too
    fed.reset(seed=3)
    curve = []
    rounds_to_target = None
    time_to_target = None
    t0 = time.monotonic()
    for r in range(cap):
        acc = float(fed.run_round(epochs=1, eval=True)["test_acc"])
        curve.append(round(acc, 4))
        if rounds_to_target is None and acc >= target:
            rounds_to_target = r + 1
            time_to_target = time.monotonic() - t0
            break
    sec_per_round = _steady_state(fed)
    mfu_hw = None
    staging_split = None
    if chunked:
        flops = fed.round_flops()
        round_mfu = _mfu_from(flops, sec_per_round)
        # EXECUTED flops: equal to the model's since the whole-loss
        # checkpoint left the step (no recompute to count); the key stays
        # for the row's readers (VERDICT r4 #4: the round-4 "2× MFU gap"
        # compared chunked model-flops against resident hw-flops)
        flops_hw = fed.round_flops(hw=True)
        mfu_hw = _mfu_from(flops_hw, sec_per_round)
        # before/after split for the round-pipeline overhaul: the SERIAL
        # path (host-side per-leaf reduce between chunks, stage-then-
        # dispatch order) vs the OVERLAPPED path (fused on-device
        # accumulators + staged-ahead inputs) on the same warm executables
        from p2pfl_tpu.settings import Settings

        prior = (Settings.CHUNK_FUSED_REDUCE, Settings.CHUNK_STAGING_DEPTH)
        try:
            Settings.CHUNK_FUSED_REDUCE = False
            Settings.CHUNK_STAGING_DEPTH = 1
            fed.run_round(epochs=1)  # warm the serial-path executable
            force_execution(fed.params)
            sec_serial = _steady_state(fed)
        finally:
            # a mid-measurement failure must not leave the de-optimized
            # serial path enabled for every later config in this process
            Settings.CHUNK_FUSED_REDUCE, Settings.CHUNK_STAGING_DEPTH = prior
        staging_split = {
            "serial_sec_per_round": round(sec_serial, 4),
            "overlapped_sec_per_round": round(sec_per_round, 4),
            "overlap_speedup": round(sec_serial / sec_per_round, 3),
            "overlapped_mfu": round(round_mfu, 4) if round_mfu is not None else None,
            "serial_mfu": round(_mfu_from(flops, sec_serial) or 0, 4),
        }
    else:
        flops, round_mfu = _spmd_mfu(fed, sec_per_round)
    emit({
        "metric": "config3_resnet50_cifar100_64node_dirichlet",
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "n_nodes": n_nodes,
        "execution": (
            "chunked time-sharing (16 nodes resident/chunk, aggregated "
            "moments — parallel/chunked.py)" if chunked else "resident SPMD"
        ),
        "target_acc": target,
        "rounds_to_target": rounds_to_target,
        "time_to_target_s": round(time_to_target, 2) if time_to_target else None,
        "accuracy_curve": curve,
        "recipe": f"adam warmup-cosine peak 3e-3, kept opt state "
                  f"(moment-averaged when chunked), batch {batch}, remat",
        "flops_per_round": flops,
        "mfu": round(round_mfu, 4) if round_mfu is not None else None,
        # executed-flops utilization; the step recomputes nothing now, so
        # this reads the same as ``mfu``
        "mfu_hw": round(mfu_hw, 4) if mfu_hw is not None else None,
        # serial vs overlapped chunk pipeline (the round-6 overhaul:
        # fused on-device accumulators + staged-ahead chunk inputs)
        "staging_split": staging_split,
        "gap_attribution": (
            "round-4's '2x MFU gap' vs the 16-node resident proxy was "
            "mostly accounting (chunked reported model flops, resident "
            "executed flops incl. the second forward of a whole-loss "
            "jax.checkpoint, which no step runs any more — mfu_hw now equals "
            "mfu): executed-basis this row ran ~20% vs resident 21%. The per-chunk staging delta (broadcast "
            "aggregate + fp32 reduce serialized behind compute) is now "
            "measured directly by staging_split: the overlapped path folds "
            "the reduce into the chunk program (donated accumulators) and "
            "stages chunk k+1's inputs during chunk k's compute; throughput-"
            "optimal point (chunk16/b128) reaches 1.95 s/round, 15.9% "
            "model-MFU, but starves the convergence recipe (see batch "
            "comment in _config3_measure)" if chunked else None
        ),
        "partition": "dirichlet(0.5)",
        "data": "synthetic (CIFAR-100 shaped)",
        "devices": len(jax.devices()),
    })


def config4_byzantine_robust() -> None:
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models import resnet18
    from p2pfl_tpu.parallel import SpmdFederation

    n, byz, rounds = 10, 2, 10  # 20% Byzantine
    data = FederatedDataset.synthetic_mnist(
        n_train=n * 512, n_test=1024, dim=(32, 32, 3), modes=2, noise=0.5, proto_scale=0.7
    )
    results = {}
    key = jax.random.PRNGKey(0)
    # fedavg is the non-robust control: same attack, no defense
    for agg in ("krum", "trimmed_mean", "clip", "fedavg"):
        fed = SpmdFederation.from_dataset(
            resnet18(), data, n_nodes=n, batch_size=64, vote=False,
            aggregator=agg, trim=byz, clip_tau=3.0, seed=3, remat=True,
        )
        t_rounds = []
        for _ in range(rounds):
            # Byzantine nodes: overwrite their slots with large Gaussian noise
            # before the round — they train from (and contribute) garbage
            fed.params = jax.tree.map(
                lambda x: x.at[:byz].set(
                    jax.random.normal(key, x.shape[1:], x.dtype) * 10.0
                ),
                fed.params,
            )
            t0 = time.monotonic()
            fed.run_round(epochs=1)
            force_execution(fed.params)
            t_rounds.append(time.monotonic() - t0)
        results[agg] = {
            "acc": round(float(fed.evaluate()["test_acc"]), 4),
            "sec_per_round": round(float(np.mean(t_rounds[1:])), 4),
        }
    emit({
        "metric": "config4_byzantine_robust_cifar10",
        "value": results["krum"]["sec_per_round"],
        "unit": "sec_per_round",
        "byzantine_fraction": byz / n,
        "rounds": rounds,
        "krum": results["krum"],
        "trimmed_mean": results["trimmed_mean"],
        "centered_clip": results["clip"],
        "fedavg_under_attack": results["fedavg"],
        "data": "synthetic (CIFAR-10 shaped)",
        "devices": len(jax.devices()),
    })


def config5_lora_32node() -> None:
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.lora import split_lora
    from p2pfl_tpu.models.transformer import tiny_transformer
    from p2pfl_tpu.parallel import SpmdLoraFederation

    import optax

    n = 32
    model = tiny_transformer(seq_len=128)
    # shifted-domain protocol (same as the 104M/1B rows): pretrain the base
    # on the SOURCE chain, federate adapters on a 15%-shifted successor
    # table — the adapters must close a real gap (the previous same-domain
    # row saturated at the base's 0.90 and measured a no-op)
    pretrain_data = FederatedDataset.synthetic_lm(n_train=2048, n_test=256)
    data = FederatedDataset.synthetic_lm(n_train=n * 64, n_test=256, shift_frac=0.15)

    # the real LoRA use case is adapting a PRETRAINED base: briefly pretrain
    # the full model centrally, then federate only the adapters on top
    tx = optax.adam(1e-3)
    params, opt = model.params, None

    @jax.jit
    def pre_step(params, opt, x, y):
        def loss_fn(p):
            logits = model.module.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    opt = tx.init(params)
    rng = np.random.default_rng(0)
    for step in range(300):
        idx = rng.integers(0, len(pretrain_data.y_train), size=16)
        params, opt, loss = pre_step(
            params, opt,
            jnp.asarray(pretrain_data.x_train[idx]),
            jnp.asarray(pretrain_data.y_train[idx]),
        )
    model.params = params
    log(f"config5: base pretrained (loss {float(loss):.3f})")

    fed = SpmdLoraFederation.from_dataset(
        model, data, n_nodes=n, batch_size=8, vote=False, seed=3, remat=True
    )
    base_acc = fed.evaluate()["test_acc"]  # pretrained base on the SHIFTED domain
    fed.run_round(epochs=1)  # warm-up
    fed.run_fused(4, epochs=1)  # warm the fused executable too
    fed.reset(seed=3)
    sec_per_round = _steady_state(fed, rounds=4)
    acc = fed.evaluate()["test_acc"]  # BEFORE the fused span: 4-round acc
    # fused span: 4 rounds in ONE dispatch — adapters are tiny, so the
    # per-round cost is dispatch-dominated and fusing amortizes it
    t0 = time.monotonic()
    fed.run_fused(4, epochs=1)
    force_execution(fed.params)
    sec_fused = (time.monotonic() - t0) / 4
    lora, base = split_lora(model.params)
    n_lora = sum(x.size for x in jax.tree.leaves(lora))
    n_base = sum(x.size for x in jax.tree.leaves(base))
    from p2pfl_tpu.management.profiling import mfu as _mfu

    flops = fed.round_flops()
    emit({
        "metric": "config5_lora_transformer_32node",
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "sec_per_round_fused": round(sec_fused, 4),
        "flops_per_round": flops,
        # MFU on the UNFUSED round (VERDICT r2 #2); the 3.4M-param
        # stand-in is dispatch-dominated (that's what fusing fixes), so
        # this is a lower bound for the TinyLlama-scale target
        "mfu": round(_mfu(flops, sec_per_round) or 0, 4) if flops else None,
        "mfu_fused": round(_mfu(flops, sec_fused) or 0, 4) if flops else None,
        "pretrained_base_acc": round(float(base_acc), 4),
        "next_token_acc_after_4_rounds": round(float(acc), 4),
        "adapter_params": n_lora,
        "base_params": n_base,
        "payload_shrink": round(n_base / n_lora, 1),
        "data": "synthetic-lm (markov, 15% shifted domain)",
        "devices": len(jax.devices()),
    })


def _lora_step_flops_by_depth(
    dim, n_heads, n_kv, ffn, vocab, n_layers, tokens_per_step, seq_len=1024,
    lora_mlp=False,
):
    """XLA-counted LoRA train-step FLOPs, extrapolated linearly in depth.

    Compiling the deep programs a second time just for cost analysis is
    expensive, and per-layer cost is EXACTLY linear in depth, so probe 1-
    and 2-layer clones and extrapolate
    ``f(L) = f(1) + (f(2) − f(1))·(L−1)``, scaled by the real
    step's token count (flops are linear in batch at fixed seq_len). The
    probes use DENSE attention so the attention core is IN the count (the
    big model's Pallas kernel is invisible to cost analysis regardless).
    """
    import optax

    from p2pfl_tpu.learning.lora import merge_params, split_lora
    from p2pfl_tpu.management.profiling import compiled_flops
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer

    def f(layers):
        cfg = TransformerConfig(
            vocab_size=vocab, dim=dim, n_layers=layers, n_heads=n_heads,
            n_kv_heads=n_kv, ffn_hidden=ffn, lora_rank=8, lora_mlp=lora_mlp,
        )
        m = tiny_transformer(seq_len=seq_len, cfg=cfg, attn="dense")
        lora, base = split_lora(m.params)

        def loss(lo, base_, bx, by):
            p = merge_params(lo, base_)
            logits = m.module.apply({"params": p}, bx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()

        bx = jnp.zeros((2, seq_len), jnp.int32)
        return compiled_flops(jax.jit(jax.value_and_grad(loss)), lora, base, bx, bx)

    f1, f2 = f(1), f(2)
    if f1 is None or f2 is None:
        return None
    return (f1 + (f2 - f1) * (n_layers - 1)) * (tokens_per_step / (2 * seq_len))


def config5_scale_lm() -> None:
    """Config 5 grown toward nameplate (VERDICT r3 #2), step 1 of 2: a
    104M-param Llama-recipe transformer (16L/768d, 12 heads / 4 KV heads,
    SwiGLU 2048, vocab 4096, seq 1024, bf16, Pallas flash attention,
    selective remat (mlp_qkv policy, 16-node chunks — round 5; was
    blanket per-block) + lax.scan over the block stack), 32 federated nodes
    training LoRA adapters on a briefly-pretrained base — the LEARNING row
    (real next-token improvement through the federation). The 0.98B
    ``config5_nameplate_1b`` row is the throughput/MFU headline; the toy
    3.4M row stays as the dispatch-bound honesty point.

    MFU is measured on the FEDERATED ROUND program (vmapped node epochs +
    masked FedAvg in one dispatch), not a bare train step.
    """
    import optax

    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.lora import split_lora
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import SpmdLoraFederation

    n = 32
    cfg = TransformerConfig(
        vocab_size=4096, dim=768, n_layers=16, n_heads=12, n_kv_heads=4,
        ffn_hidden=2048, lora_rank=8, lora_mlp=True, remat=True, scan_layers=True,
        remat_policy="mlp_qkv",  # selective remat (round 5): ~11 GB of
        # saved activations at 32 nodes x batch 2 in flight — node_chunk
        # halves the in-flight set to fit (same recipe as the 1B row)
    )
    model = tiny_transformer(seq_len=1024, cfg=cfg, attn="flash")
    n_params = sum(x.size for x in jax.tree.leaves(model.params))
    log(f"config5_scale: {n_params/1e6:.1f}M params")
    # the real LoRA task is DOMAIN ADAPTATION: pretrain the base on the
    # source chain, federate adapters on a 15%-shifted successor table —
    # the base scores ~0.9·0.85 there and the adapters close the gap
    pretrain_data = FederatedDataset.synthetic_lm(
        vocab_size=4096, seq_len=1024, n_train=512, n_test=64
    )
    data = FederatedDataset.synthetic_lm(
        vocab_size=4096, seq_len=1024, n_train=n * 16, n_test=64, shift_frac=0.15
    )

    # the LoRA use case adapts a PRETRAINED base (same shape as the toy
    # row): brief central pretraining, then the federation trains only
    # adapters on top. Base params ride as ARGUMENTS, never closures — a
    # closed-over 104M tree becomes 400MB of MLIR constants.
    tx = optax.adam(3e-4)

    @jax.jit
    def pre_step(params, opt, x, y):
        def loss_fn(p):
            logits = model.module.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    params, opt = model.params, tx.init(model.params)
    rng = np.random.default_rng(0)
    for step in range(300):
        idx = rng.integers(0, len(pretrain_data.y_train), size=8)
        params, opt, loss = pre_step(
            params, opt,
            jnp.asarray(pretrain_data.x_train[idx]),
            jnp.asarray(pretrain_data.y_train[idx]),
        )
    force_execution(loss)
    model.params = params
    log(f"config5_scale: base pretrained (loss {float(loss):.3f})")
    del opt

    fed = SpmdLoraFederation.from_dataset(
        model, data, n_nodes=n, batch_size=2, vote=False, seed=3, node_chunk=16,
    )
    fed.run_round(epochs=1)  # compile warm-up
    force_execution(fed.params)  # async dispatch: let it FINISH before timing
    fed.reset(seed=3)
    acc0 = fed.evaluate()["test_acc"]  # pretrained base on the SHIFTED domain
    sec_per_round = _steady_state(fed, rounds=3)
    accs = []
    for _ in range(5):
        fed.run_round(epochs=1)
        accs.append(round(fed.evaluate()["test_acc"], 4))

    # MODEL flops (remat recompute is real work but not useful flops);
    # the depth-extrapolated XLA count — see _lora_step_flops_by_depth
    step_flops = _lora_step_flops_by_depth(
        768, 12, 4, 2048, 4096, 16, tokens_per_step=n * 2 * 1024, lora_mlp=True
    )
    flops = (fed._nb * step_flops) if step_flops else None
    lora, base = split_lora(model.params)
    n_lora = sum(x.size for x in jax.tree.leaves(lora))
    emit({
        "metric": "config5_scale_lm_104m",
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "model": "16L/768d/12h(kv4) SwiGLU-2048 vocab-4096 seq-1024 bf16 "
                 "flash-attn selective-remat(mlp_qkv) node-chunk-16 "
                 "scan-layers",
        "n_params": n_params,
        "n_nodes": n,
        "batch_per_node": 2,
        "flops_per_round": flops,
        "mfu": round(_mfu_from(flops, sec_per_round) or 0, 4),
        "pretrained_base_acc": round(float(acc0), 4),
        "next_token_acc_curve": accs,
        "adapter_params": n_lora,
        "payload_shrink": round((n_params - n_lora) / n_lora, 1),
        "data": "synthetic-lm (markov, vocab 4096)",
        "devices": len(jax.devices()),
    })


def config5_nameplate_1b() -> None:
    """Config 5 at NAMEPLATE scale: the TinyLlama-1.1B architecture
    (22L/2048d, 32 heads / 4 KV heads GQA, SwiGLU 5632 — vocab 4096
    instead of 32000, sized to the synthetic markov task) = 0.98B params,
    32 federated LoRA nodes on one v5e chip.

    VERDICT r4 #1 rebuilt this row twice over:

    - **it learns now.** Same recipe as the 104M row: central pretrain of
      the base (Adafactor — full-param Adam moments alone are 8 GB, over
      budget with the 4 GB f32 params) until loss is far below the
      ln(4096)=8.32 random floor, then 32 LoRA nodes federate adapters on
      a 15%-shifted successor table — next-token accuracy climbs from the
      pretrained base's shifted-domain score toward the 0.9 determinism
      ceiling, and the federated train loss falls.
    - **selective remat replaces blanket per-block remat.** remat_policy
      ``mlp_qkv`` saves FFN gate/up + post-RoPE q/k/v, so the backward
      recomputes only the flash-kernel forward (~5% of a block) instead of
      the whole block (~75% after XLA DCE). The saved activations don't
      fit with 32 nodes in flight, so ``node_chunk=4`` scans the nodes 4
      at a time (measured ladder, s/round: blanket remat 8.99 → mlp@8
      7.21 → mlp_qkv@8 6.92 → mlp_qkv@4 6.30; mlp@16 OOMs — the sweep
      that proves the policy×chunk choice).

    Two honest numerators, as before: ``mfu`` counts model flops
    (fwd+dgrad, depth-extrapolated), ``mfu_hw`` adds the policy's actual
    recompute (flash fwd ≈ 2·T_causal·dim per token vs the full 2·P
    re-forward the old blanket policy paid).

    Round 6 put the row in BASELINE metric form: 8 steps/round (n·8
    sequences at batch 1) converging to a stated next-token target (0.65)
    on the shifted domain, with ``rounds_to_target`` / ``time_to_target_s``
    like configs 2/3/10.
    """
    import optax

    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.lora import split_lora
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import SpmdLoraFederation

    import dataclasses

    n = 32
    cfg = TransformerConfig(
        vocab_size=4096, dim=2048, n_heads=32, n_kv_heads=4, n_layers=22,
        ffn_hidden=5632, lora_rank=8, lora_mlp=True, remat=True,
        scan_layers=True, remat_policy="mlp_qkv",
    )
    pretrain_data = FederatedDataset.synthetic_lm(
        vocab_size=4096, seq_len=1024, n_train=512, n_test=64
    )
    # n*8 sequences → 8 steps/round at batch 1: the BASELINE-metric floor
    # (≥8 optimizer steps/round) for the rounds-to-target run below
    data = FederatedDataset.synthetic_lm(
        vocab_size=4096, seq_len=1024, n_train=n * 8, n_test=32, shift_frac=0.15
    )

    # central pretrain: Adafactor fits where Adam's 8 GB of moments don't.
    # Donation is mandatory (4 GB f32 params in undonated in/out/grads
    # copies OOMed), and the pretrain uses a FULL-remat twin of the module
    # (same param tree, remat_policy=None): full-param training has no HBM
    # room for the saved mlp_qkv activations the adapter federation enjoys
    pre_model = tiny_transformer(
        seq_len=1024, cfg=dataclasses.replace(cfg, remat_policy=None), attn="flash"
    )
    n_params = sum(x.size for x in jax.tree.leaves(pre_model.params))
    log(f"config5_1b: {n_params/1e9:.3f}B params")
    tx = optax.adafactor(learning_rate=3e-3)

    @partial(jax.jit, donate_argnums=(0, 1))
    def pre_step(params, opt, x, y):
        def loss_fn(p):
            logits = pre_model.module.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    params, opt = pre_model.params, tx.init(pre_model.params)
    pre_model.params = None  # donated into the step; drop the stale handle
    rng = np.random.default_rng(0)
    pre_curve = []
    for step in range(400):
        idx = rng.integers(0, len(pretrain_data.y_train), size=8)
        params, opt, loss = pre_step(
            params, opt,
            jnp.asarray(pretrain_data.x_train[idx]),
            jnp.asarray(pretrain_data.y_train[idx]),
        )
        if step % 50 == 0:
            pre_curve.append(round(float(loss), 4))
    force_execution(loss)
    pre_curve.append(round(float(loss), 4))
    log(f"config5_1b: base pretrained, loss curve {pre_curve} "
        f"(random floor ln(4096) = 8.318)")
    del opt
    jax.clear_caches()  # the pretrain executable holds workspace HBM

    # the federation's module carries the selective-remat policy; its fresh
    # init is transient (replaced by the pretrained tree immediately)
    model = tiny_transformer(seq_len=1024, cfg=cfg, attn="flash")
    model.params = params
    fed = SpmdLoraFederation.from_dataset(
        model, data, n_nodes=n, batch_size=1, vote=False, seed=3, node_chunk=4,
    )
    fed.run_round(epochs=1)  # compile warm-up
    force_execution(fed.params)  # async dispatch: let it FINISH before timing
    fed.reset(seed=3)
    acc0 = fed.evaluate()["test_acc"]  # pretrained base on the SHIFTED domain
    fed.run_round(epochs=1)  # settling round: eval-to-steady transition
    force_execution(fed.params)
    sec_per_round = _steady_state(fed, rounds=3)
    fed.reset(seed=3)
    # BASELINE metric form (like configs 2/3/10): converge to a stated
    # next-token target on the shifted domain, report rounds/time to it
    target = 0.65
    cap = 16
    loss_curve, accs = [], []
    rounds_to_target = None
    time_to_target = None
    t0 = time.monotonic()
    for r in range(cap):
        loss_curve.append(float(fed.run_round(epochs=1)["train_loss"]))
        accs.append(round(fed.evaluate()["test_acc"], 4))
        if rounds_to_target is None and accs[-1] >= target:
            rounds_to_target = r + 1
            time_to_target = time.monotonic() - t0
            break

    tokens_per_step = n * 1 * 1024
    step_flops = _lora_step_flops_by_depth(
        2048, 32, 4, 5632, 4096, 22, tokens_per_step=tokens_per_step, lora_mlp=True
    )
    flops = (fed._nb * step_flops) if step_flops else None
    # executed flops add the policy's recompute: only the flash forward
    # re-runs (2 causal matmuls ≈ 2·2·(T/2)·dim per token) + cheap glue
    recompute_per_token = 2.0 * 2.0 * (1024 / 2) * 2048 * 22  # 2 causal matmuls x 22 layers
    flops_hw = (
        flops + fed._nb * recompute_per_token * tokens_per_step if flops else None
    )
    lora, _ = split_lora(model.params)
    n_lora = sum(x.size for x in jax.tree.leaves(lora))
    emit({
        "metric": "config5_nameplate_1b",
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "model": "22L/2048d/32h(kv4) SwiGLU-5632 vocab-4096 seq-1024 bf16 "
                 "flash-attn selective-remat(mlp_qkv) node-chunk-4 "
                 "scan-layers (TinyLlama-1.1B arch at task vocab)",
        "n_params": n_params,
        "n_nodes": n,
        "batch_per_node": 1,
        "steps_per_round": fed._nb,
        "flops_per_round": flops,
        "flops_per_round_hw": flops_hw,
        "mfu": round(_mfu_from(flops, sec_per_round) or 0, 4),
        "mfu_hw": round(_mfu_from(flops_hw, sec_per_round) or 0, 4),
        "remat_note": "selective remat (save ffn gate/up + post-rope qkv, "
                      "recompute only the flash fwd) + 4-node chunking "
                      "replaces the blanket per-block remat: the eval-free "
                      "policy-ladder sweep measured 8.99 -> 6.30 s/round; "
                      "this row's headline value is the steady state "
                      "inside the federation's eval cadence (settling "
                      "round + eval-adjacent dispatch). No-remat still "
                      "OOMs (21.6G needed, 15.75G HBM), mlp-policy at 16 "
                      "nodes in flight OOMs — the ladder is "
                      "HBM-constrained",
        "pretrain_loss_curve": pre_curve,
        "random_floor_loss": 8.318,
        "pretrained_base_acc": round(float(acc0), 4),
        "target_acc": target,
        "rounds_to_target": rounds_to_target,
        "time_to_target_s": round(time_to_target, 2) if time_to_target else None,
        "next_token_acc_curve": accs,
        "train_loss_curve": [round(l, 4) for l in loss_curve],
        "adapter_params": n_lora,
        "payload_shrink": round((n_params - n_lora) / n_lora, 1),
        "data": "synthetic-lm (markov, vocab 4096, 15% shifted domain)",
        "devices": len(jax.devices()),
    })


def _sharded_1b_hbm_projection() -> dict:
    """Per-device params+Adam-moments bytes for the 1B nameplate tree under
    the default partition rules, at model_parallel 1/4/8.

    Pure accounting: the tree comes from ``jax.eval_shape`` (nothing is
    allocated) and the per-device share from the rule engine's specs +
    divisibility logic — exact on any backend, which is what makes a
    per-node HBM column honest from a CPU-only bench container.
    """
    import jax.numpy as jnp
    import optax

    from p2pfl_tpu.models.transformer import (
        CausalLM, TransformerConfig, resolve_attention,
    )
    from p2pfl_tpu.parallel.mesh import node_slices, submesh_federation_mesh
    from p2pfl_tpu.parallel.sharding import DEFAULT_TRANSFORMER_RULES, tree_shardings

    cfg = TransformerConfig(
        vocab_size=4096, dim=2048, n_heads=32, n_kv_heads=4, n_layers=22,
        ffn_hidden=5632, lora_rank=8, lora_mlp=True,
    )
    module = CausalLM(cfg, resolve_attention("dense"))
    params = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 1024), jnp.int32)
    )["params"]
    opt = jax.eval_shape(optax.adam(1e-3).init, params)
    out = {"n_params": int(sum(np.prod(s.shape) for s in jax.tree.leaves(params)))}
    for m in (1, 4, 8):
        # the same engine that PLACES tensors computes the share: build the
        # one-node (data=1, model=m) slice and ask each NamedSharding for
        # its per-device shard shape — no hand-rolled divisibility copy
        slice_mesh = node_slices(
            submesh_federation_mesh(1, m, devices=jax.devices()[:m])
        )[0]
        total = 0
        for tree in (params, opt):
            shardings = tree_shardings(
                slice_mesh, tree, DEFAULT_TRANSFORMER_RULES, on_unmatched="replicate"
            )

            def bytes_one(sharding, leaf):
                shard = sharding.shard_shape(tuple(leaf.shape))
                size = int(np.prod(shard)) if shard else 1
                return size * np.dtype(leaf.dtype).itemsize

            total += sum(jax.tree.leaves(jax.tree.map(bytes_one, shardings, tree)))
        out[f"bytes_per_device_m{m}"] = int(total)
        out[f"gb_per_device_m{m}"] = round(total / 2**30, 3)
    return out


def config5_sharded() -> None:
    """Config 5's SHARDED-NODE row (ISSUE 10): one federation node = a
    pjit submesh, cross-slice FedAvg fold — vs the single-chip path on
    the same task, same steps/round, same target.

    Two honest parts:

    - an EXECUTED anchor on this container's backend: a small dense LM
      (the nameplate architecture family) federated 2 nodes x
      model_parallel=4 (8 virtual CPU devices) against the single-chip
      SpmdFederation, identical seeds/steps-per-round/target, reporting
      sec/round, rounds-to-target and the measured per-device live bytes
      (the no-full-model-anywhere contract, measured not asserted). On
      the CPU anchor ``mfu`` is null like every CPU row and wall-clock
      favors the single-chip path (GSPMD partitioning overhead without
      real ICI) — the dispatch structure, parity and memory split are
      what transfer;
    - the 1B NAMEPLATE projection: exact per-device params+opt bytes for
      the 0.98B tree under the default partition rules at model_parallel
      1/4/8 (``jax.eval_shape`` + the rule engine — no allocation, no
      chip needed). m=1 is the single-chip row's footprint; m=4/8 is what
      a v4/v5 slice per node buys.
    """
    if jax.default_backend() != "cpu" or len(jax.devices()) < 8:
        _reexec("5sharded", timeout=1500, virtual_devices=8)
        return

    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import ShardedNodeFederation, SpmdFederation
    from p2pfl_tpu.parallel.submesh import per_device_bytes

    n = 2
    target = 0.50
    cap = 12
    cfg = TransformerConfig(
        vocab_size=256, dim=128, n_layers=2, n_heads=4, n_kv_heads=2, ffn_hidden=344
    )
    data = FederatedDataset.synthetic_lm(
        vocab_size=256, seq_len=64, n_train=64, n_test=32, seed=7
    )

    sharded = ShardedNodeFederation.from_dataset(
        tiny_transformer(seq_len=64, cfg=cfg), data, n_nodes=n,
        model_parallel=4, batch_size=4, vote=False, seed=3,
    )
    # steady state measured on a fresh object (no reset on the sharded
    # driver yet); rounds-to-target measured from round 0 on a new one
    sharded.run_round(epochs=1)
    sec_sharded = _steady_state(sharded, rounds=3)
    sharded2 = ShardedNodeFederation.from_dataset(
        tiny_transformer(seq_len=64, cfg=cfg), data, n_nodes=n,
        model_parallel=4, batch_size=4, vote=False, seed=3,
    )
    accs_sh, r2t_sh = [], None
    for r in range(cap):
        sharded2.run_round(epochs=1)
        accs_sh.append(round(sharded2.evaluate()["test_acc"], 4))
        if accs_sh[-1] >= target:
            r2t_sh = r + 1
            break
    hbm = per_device_bytes(sharded2.params, sharded2.opt_state)
    max_dev_bytes = max(hbm.values())
    full_bytes = sum(
        int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(sharded2.model.params)
    )
    log(f"config5_sharded: sharded {sec_sharded:.3f} s/round, "
        f"target {target} in {r2t_sh} rounds, max dev bytes {max_dev_bytes}")

    single = SpmdFederation.from_dataset(
        tiny_transformer(seq_len=64, cfg=cfg), data, n_nodes=n,
        batch_size=4, vote=False, seed=3,
    )
    single.run_round(epochs=1)
    force_execution(single.params)
    sec_single = _steady_state(single, rounds=3)
    single.reset(seed=3)
    accs_si, r2t_si = [], None
    for r in range(cap):
        single.run_round(epochs=1)
        accs_si.append(round(single.evaluate()["test_acc"], 4))
        if accs_si[-1] >= target:
            r2t_si = r + 1
            break
    log(f"config5_sharded: single-chip {sec_single:.3f} s/round, "
        f"target {target} in {r2t_si} rounds")

    emit({
        "metric": "config5_sharded",
        "value": round(sec_sharded, 4),
        "unit": "sec_per_round",
        "cpu_anchor": True,
        "model": "2L/128d/4h(kv2) SwiGLU-344 vocab-256 seq-64 (nameplate "
                 "architecture family at CPU-anchor scale)",
        "n_nodes": n,
        "model_parallel": 4,
        "steps_per_round": sharded2._nb,
        "target_acc": target,
        "rounds_to_target": r2t_sh,
        "rounds_to_target_single_chip": r2t_si,
        "next_token_acc_curve": accs_sh,
        "next_token_acc_curve_single_chip": accs_si,
        "sec_per_round_single_chip": round(sec_single, 4),
        "mfu": None,
        "max_device_bytes": int(max_dev_bytes),
        "full_model_bytes": int(full_bytes),
        "device_bytes_fraction": round(max_dev_bytes / (3 * full_bytes), 3),
        "nameplate_1b_projection": _sharded_1b_hbm_projection(),
        "note": "CPU anchor: same seeds/steps-per-round/target as the "
                "single-chip comparison; GSPMD partitioning overhead "
                "without real ICI makes sharded wall-clock LOSE on CPU — "
                "the per-device memory split (max_device_bytes vs 3x "
                "full_model_bytes for params+adam) and the 1B projection "
                "are the accelerator-facing result. The 1B projection "
                "uses the exact config5_nameplate_1b tree (same "
                "steps/round and 0.65 target apply when run on hardware).",
        "data": "synthetic-lm (markov, vocab 256)",
        "devices": len(jax.devices()),
    })


def config6_heterogeneous_algorithms() -> None:
    """Beyond-reference breadth: FedAvg vs FedProx vs SCAFFOLD vs FedAdam on
    Dirichlet(0.3) non-IID shards (the reference ships FedAvg only).

    SCAFFOLD is an SGD-family correction (its control-variate update is
    coupled to the SGD step size, Karimireddy et al. 2020 eq. 4), so its
    honest baseline is FedAvg with the SAME local SGD — the ``fedavg_sgd``
    row. Round 4 compared it against FedAvg-with-Adam and concluded
    SCAFFOLD "loses on the setting it exists for"; the 3-seed matched
    sweep (2026-07-31) shows SCAFFOLD > FedAvg-SGD at every seed at
    lr 0.02 (mean 0.679 vs 0.433 at 1 epoch; 0.976 vs 0.934 at 2), and
    that the correction destabilizes when K·η grows (lr 0.05 × 2 epochs:
    0.922 vs 0.995) — the known large-step regime, not a bug.
    ``tests/test_fedopt_scaffold.py`` pins the matched-pair ordering.
    """
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.parallel import SpmdFederation

    n_nodes, rounds = 8, 10
    results = {}
    times = {}
    data = FederatedDataset.mnist(None, modes=8, noise=0.7, proto_scale=0.5)
    for algo, kwargs in {
        "fedavg": {},
        "fedprox": {"prox_mu": 0.1},
        "fedavg_sgd": {"optimizer": "sgd", "learning_rate": 0.02},
        "scaffold": {"scaffold": True, "optimizer": "sgd", "learning_rate": 0.02},
        "fedadam": {"server_opt": "adam", "server_lr": 0.01},
    }.items():
        fed = SpmdFederation.from_dataset(
            mlp(), data, n_nodes=n_nodes, strategy="dirichlet", alpha=0.3,
            batch_size=64, vote=False, seed=7, **kwargs,
        )
        # warm BOTH fused input layouts (fresh + evolved) and materialize —
        # one unmaterialized warm call leaves a compile inside the timer
        # (the r1 fedavg row measured 2.3 s/round vs 0.13 for its peers
        # because of exactly this)
        [float(e["test_acc"]) for e in fed.run_fused(rounds, epochs=1, eval=True)]
        [float(e["test_acc"]) for e in fed.run_fused(rounds, epochs=1, eval=True)]
        fed.reset(seed=7)
        t0 = time.monotonic()
        entries = fed.run_fused(rounds, epochs=1, eval=True)
        accs = [round(float(e["test_acc"]), 4) for e in entries]
        force_execution(fed.params)
        times[algo] = round((time.monotonic() - t0) / rounds, 4)
        results[algo] = accs
        log(f"config6 {algo}: {accs}")
        del fed
        jax.clear_caches()

    # --- scaffold fast path: before/after + per-phase profile (round 6) ---
    # same federation timed under the legacy anchor-based ci⁺ and the fused
    # grad-mean ci⁺ (Settings.SCAFFOLD_FUSED_CI — a traced-program knob, so
    # each setting gets its own warmed executable), plus the per-phase
    # breakdown that attributes whatever overhead remains
    from p2pfl_tpu.settings import Settings

    sc_kwargs = {"scaffold": True, "optimizer": "sgd", "learning_rate": 0.02}
    scaffold_split = {}
    fed = SpmdFederation.from_dataset(
        mlp(), data, n_nodes=n_nodes, strategy="dirichlet", alpha=0.3,
        batch_size=64, vote=False, seed=7, **sc_kwargs,
    )
    prior_fused_ci = Settings.SCAFFOLD_FUSED_CI
    try:
        for label, fused_ci in (("legacy_ci", False), ("fused_ci", True)):
            Settings.SCAFFOLD_FUSED_CI = fused_ci
            fed.reset(seed=7)
            [float(e["test_acc"]) for e in fed.run_fused(rounds, epochs=1, eval=True)]
            fed.reset(seed=7)
            t0 = time.monotonic()
            fed.run_fused(rounds, epochs=1, eval=True)
            force_execution(fed.params)
            scaffold_split[f"{label}_sec_per_round"] = round((time.monotonic() - t0) / rounds, 4)
    finally:
        # never leave the legacy path enabled for later configs on failure
        Settings.SCAFFOLD_FUSED_CI = prior_fused_ci
    scaffold_split["fast_path_speedup"] = round(
        scaffold_split["legacy_ci_sec_per_round"] / scaffold_split["fused_ci_sec_per_round"], 3
    )
    scaffold_split["vs_matched_fedavg_x"] = round(
        scaffold_split["fused_ci_sec_per_round"] / times["fedavg_sgd"], 3
    )
    log(f"config6 scaffold split {scaffold_split}")
    del fed
    jax.clear_caches()

    # --- 5 local epochs: the regime where drift accumulates and SCAFFOLD's
    # correction should WIN on accuracy, not just cost less (with lr scaled
    # down to keep K·η in the stable regime the 3-seed sweep mapped) ---
    ep5 = {}
    for algo in ("fedavg_sgd", "scaffold"):
        kw = {"optimizer": "sgd", "learning_rate": 0.01}
        if algo == "scaffold":
            kw["scaffold"] = True
        fed = SpmdFederation.from_dataset(
            mlp(), data, n_nodes=n_nodes, strategy="dirichlet", alpha=0.3,
            batch_size=64, vote=False, seed=7, **kw,
        )
        [float(e["test_acc"]) for e in fed.run_fused(rounds, epochs=5, eval=True)]
        fed.reset(seed=7)
        t0 = time.monotonic()
        entries = fed.run_fused(rounds, epochs=5, eval=True)
        accs5 = [round(float(e["test_acc"]), 4) for e in entries]
        force_execution(fed.params)
        ep5[algo] = {
            "curve": accs5,
            "sec_per_round": round((time.monotonic() - t0) / rounds, 4),
        }
        log(f"config6 {algo} @5 epochs: {ep5[algo]}")
        del fed
        jax.clear_caches()

    emit({
        "metric": "config6_heterogeneous_dirichlet03",
        "value": max(r[-1] for r in results.values()),
        "unit": "best_final_acc",
        "curves": results,
        "sec_per_round": times,
        "n_nodes": n_nodes,
        "partition": "dirichlet(0.3)",
        "data": "synthetic-hard",
        "scaffold_vs_matched_fedavg": round(
            results["scaffold"][-1] - results["fedavg_sgd"][-1], 4
        ),
        # SCAFFOLD hot-path overhaul: legacy vs fused ci⁺ cost, residual
        # attribution (train / correction / aggregate), and the 5-local-
        # epoch drift regime where the correction earns its keep
        "scaffold_fast_path": scaffold_split,
        "local_epochs_5": {
            **ep5,
            "scaffold_vs_fedavg_sgd_final": round(
                ep5["scaffold"]["curve"][-1] - ep5["fedavg_sgd"]["curve"][-1], 4
            ),
            "recipe": "lr 0.01 (K·η kept in the stable regime at 5x steps)",
        },
        "scaffold_note": (
            "scaffold's baseline is fedavg_sgd (same local SGD, lr 0.02) — "
            "the control-variate update is coupled to the SGD step; "
            "adam rows are a different local optimizer family"
        ),
        "devices": len(jax.devices()),
    })


def _fused_timer(fn, args, iters=30):
    """Time ``fn`` with the repeat loop fused into ONE device dispatch.

    Per-dispatch measurement carries a fixed host round-trip that dwarfs
    sub-millisecond kernels. ``fn(*args) -> carry_pytree`` must return its
    own inputs' update so iterations chain data-dependently and XLA cannot
    CSE the loop body.

    The fixed cost is removed by a two-point SLOPE, not a guessed
    subtraction (a constant 0.1 s estimate swallowed sub-ms steps whole —
    round-4's first T=512 row read 0.0 ms): the loop bound is a TRACED
    ``lax.fori_loop`` bound, so one executable runs at both ``iters`` and
    ``3·iters`` and the per-iteration time is the difference over 2·iters.
    """
    from jax import lax

    @jax.jit
    def many(a, n):
        def body(_i, c):
            out = fn(*c)
            return out if isinstance(out, tuple) else (out,)

        return lax.fori_loop(0, n, body, a)

    def run(n):
        t0 = time.monotonic()
        out = many(args, n)
        force_execution(out)
        return time.monotonic() - t0

    run(2)  # compile + warm
    # dispatch latency varies run to run; the median of repeated slopes is
    # stable where one is not
    slopes = []
    for _ in range(3):
        t_lo = run(iters)
        t_hi = run(3 * iters)
        slopes.append(max(t_hi - t_lo, 1e-9) / (2 * iters))
    slopes.sort()
    return slopes[1]


def config7_long_context_flash() -> None:
    """Long-context single-chip path: Pallas flash attention vs fused dense
    XLA attention across sequence lengths, fwd and train-step (fwd+bwd)
    measured separately (VERDICT r3 #6).

    Two structural facts this row documents:

    - timing is amortized inside one dispatch (``_fused_timer``) so the
      per-dispatch host round trip is not billed to the kernel;
    - the 4L/256d/8h model's head_dim = 32 fills only 32 of the MXU's 128
      contraction/output lanes, so NO attention kernel can exceed ~25% MFU
      at this width — the ``head_dim_scaling`` sub-row shows the same
      kernel at D=64/128 (the config-5-scale and production widths), where
      it reaches >35% fwd / >50% bwd.
    """
    import optax

    from p2pfl_tpu.models.transformer import (
        TransformerConfig,
        pick_attention,
        resolve_attention,
        tiny_transformer,
    )
    from p2pfl_tpu.settings import Settings

    cfg_kw = dict(
        vocab_size=1024, dim=256, n_layers=4, n_heads=8, n_kv_heads=8,
        ffn_hidden=688, lora_rank=0,
    )

    def measure(seq_len, attn, block=128, cfg=None):
        # dense → attn_fn None (fused XLA path); flash → explicit kernel
        # with the swept block size (attn_fn overrides tiny_transformer's
        # own block choice)
        from p2pfl_tpu.management.profiling import compiled_flops

        attn_fn = resolve_attention("flash", block=block) if attn == "flash" else None
        m = tiny_transformer(
            seq_len=seq_len, cfg=cfg or TransformerConfig(**cfg_kw), attn_fn=attn_fn
        )
        tokens = jax.random.randint(jax.random.PRNGKey(0), (8, seq_len), 0, 1024)
        targets = jnp.roll(tokens, -1, axis=1)

        def loss(p, m=m, tokens=tokens, targets=targets):
            logits = m.apply(p, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean()

        grad_fn = jax.value_and_grad(loss)

        def train_step(p):
            _l, g = grad_fn(p)
            return jax.tree.map(lambda a, b: a - 1e-4 * b.astype(a.dtype), p, g)

        def fwd_step(p):
            # chain iterations through a negligible param nudge so the scan
            # body stays data-dependent (a *0.0 chain gets algebraically
            # folded to identity and the whole loop DCE'd — measured 0.0 ms)
            l = loss(p)
            return jax.tree.map(lambda a: a + (l * 1e-30).astype(a.dtype), p)

        # no scan in the step → cost analysis counts everything exactly once.
        # Pallas kernel FLOPs may be invisible to XLA's analysis, so MFU is
        # comparable only via the DENSE program's count (reported per row).
        train_flops = compiled_flops(jax.jit(grad_fn), m.params)
        fwd_flops = compiled_flops(jax.jit(loss), m.params)
        sec_train = _fused_timer(train_step, (m.params,))
        sec_fwd = _fused_timer(fwd_step, (m.params,))
        del m
        jax.clear_caches()
        return sec_fwd, sec_train, fwd_flops, train_flops

    results = {}
    for seq_len in (512, 1024, 2048, 4096):
        d_fwd, d_train, fwd_flops, train_flops = measure(seq_len, "dense")
        row = {
            "dense_fwd_ms": round(d_fwd * 1e3, 3),
            "dense_train_ms": round(d_train * 1e3, 3),
        }
        for mfu_key, fl, sec in (
            ("dense_fwd_mfu", fwd_flops, d_fwd),
            ("dense_train_mfu", train_flops, d_train),
        ):
            v = _mfu_from(fl, sec)
            if v is not None:
                row[mfu_key] = round(v, 4)
        blocks = [b for b in (256, 512) if seq_len % b == 0] or [seq_len]
        sweep = {}
        for b in blocks:
            f_fwd, f_train, _, _ = measure(seq_len, "flash", block=b)
            sweep[b] = {"fwd_ms": round(f_fwd * 1e3, 3), "train_ms": round(f_train * 1e3, 3)}
        best_block = min(sweep, key=lambda b: sweep[b]["train_ms"])
        row["flash_block_sweep"] = sweep
        row["flash_fwd_ms"] = sweep[best_block]["fwd_ms"]
        row["flash_train_ms"] = sweep[best_block]["train_ms"]
        row["flash_best_block"] = best_block
        # flash MFU from the DENSE program's model-FLOP count (the Pallas
        # kernel's internal FLOPs are invisible to XLA's cost analysis;
        # using the same numerator keeps dense/flash comparable)
        for mfu_key, fl, ms in (
            ("flash_fwd_mfu", fwd_flops, row["flash_fwd_ms"]),
            ("flash_train_mfu", train_flops, row["flash_train_ms"]),
        ):
            v = _mfu_from(fl, ms / 1e3)
            if v is not None:
                row[mfu_key] = round(v, 4)
        row["speedup_train"] = round(d_train / (row["flash_train_ms"] / 1e3), 2)
        row["auto_picks"] = pick_attention(seq_len)
        results[f"T{seq_len}"] = row
        log(f"config7 T={seq_len}: {row}")

    # head-dim scaling of the BARE kernel at T=4096 (same total flops per
    # row: H·D = 256): shows the D=32 rows above sit on the MXU-width
    # roofline (32/128 lanes ⇒ ≤25% ceiling), not a kernel defect
    from p2pfl_tpu.ops.flash_attention import flash_attention

    head_dim_scaling = {}
    T = 4096
    for h, d in ((8, 32), (4, 64), (2, 128)):
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), (8, T, h, d), jnp.bfloat16)
            for i in range(3)
        )
        fwd = partial(flash_attention, causal=True, block_q=512, block_k=512)
        fl_fwd = 0.5 * 2 * 2 * 8 * h * T * T * d  # causal: 2 matmuls over T²/2
        fl_bwd = 2.5 * fl_fwd  # 5 block matmuls in the bwd kernels vs 2

        def fwd_chain(q, k, v):
            o = fwd(q, k, v)
            return q + (jnp.sum(o.astype(jnp.float32)) * 1e-30).astype(q.dtype), k, v

        def train_chain(q, k, v):
            # all three grads must feed the carry or XLA dead-code-eliminates
            # the dkv backward kernel entirely
            dq, dk, dv = jax.grad(
                lambda q_, k_, v_: jnp.sum(fwd(q_, k_, v_).astype(jnp.float32)),
                argnums=(0, 1, 2),
            )(q, k, v)
            return (
                q + (dq * 1e-9).astype(q.dtype),
                k + (dk * 1e-9).astype(k.dtype),
                v + (dv * 1e-9).astype(v.dtype),
            )

        s_fwd = _fused_timer(lambda q, k, v: fwd_chain(q, k, v), (q, k, v), iters=100)
        s_all = _fused_timer(lambda q, k, v: train_chain(q, k, v), (q, k, v), iters=100)
        s_bwd = max(s_all - s_fwd, 1e-9)
        head_dim_scaling[f"D{d}"] = {
            "fwd_ms": round(s_fwd * 1e3, 3),
            "fwd_mfu": round(_mfu_from(fl_fwd, s_fwd) or 0, 4),
            "bwd_ms": round(s_bwd * 1e3, 3),
            "bwd_mfu": round(_mfu_from(fl_bwd, s_bwd) or 0, 4),
        }
    log(f"config7 head_dim_scaling: {head_dim_scaling}")

    # model-level proof of the head-width ceiling: the SAME 4L/256d model
    # with 2 heads (D=128) instead of 8 (D=32) — identical params and
    # matmul FLOPs (2·128 = 8·32 per projection), only the attention head
    # shape changes. Measured (round 5, fused bwd): train step 66.0 ->
    # 17.5 ms, model MFU 20.6% -> ~68% at T=4096. The D=32 row's sub-25%
    # train MFU is the 32/128-lane geometry, not the kernel or the model
    # family. The numerator must come from the variant's OWN dense twin —
    # the 8-head dense count is ~14% higher because XLA's softmax/mask
    # bookkeeping scales with head count (verified: reusing it reads 77%).
    from p2pfl_tpu.management.profiling import compiled_flops

    cfgv = TransformerConfig(**{**cfg_kw, "n_heads": 2, "n_kv_heads": 2})
    _fv, secv, _flf, _flt = measure(4096, "flash", block=512, cfg=cfgv)
    mdv = tiny_transformer(seq_len=4096, cfg=cfgv)
    tokens_v = jax.random.randint(jax.random.PRNGKey(0), (8, 4096), 0, 1024)

    def loss_vd(p):
        logits = mdv.apply(p, tokens_v)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(tokens_v, -1, axis=1)
        ).mean()

    flv = compiled_flops(jax.jit(jax.value_and_grad(loss_vd)), mdv.params)
    variant = {
        "model": "same 4L/256d, 2 heads (D=128)",
        "train_ms": round(secv * 1e3, 1),
        "train_mfu": round(_mfu_from(flv, secv) or 0, 4),
    }
    log(f"config7 head_width_variant: {variant}")
    del mdv
    jax.clear_caches()

    emit({
        "metric": "config7_long_context_flash_vs_dense",
        "value": results["T4096"]["speedup_train"],
        "unit": "x_speedup_at_4096",
        "ms_per_train_step": results,
        "head_dim_scaling_T4096": head_dim_scaling,
        "head_width_variant_T4096": variant,
        "mxu_note": (
            "head_dim 32 fills 32/128 MXU lanes -> <=25% MFU ceiling for any "
            "attention kernel at this width; D=64/128 rows show the kernel "
            "scaling when the shape fills the array, and the head_width "
            f"variant shows the MODEL clearing 25% "
            f"({variant['train_mfu']:.0%} measured) once the heads do"
        ),
        "auto_threshold_seq_len": Settings.FLASH_MIN_SEQ_LEN,
        "batch": 8,
        "model": "4L/256d/8h transformer, bf16",
        "devices": len(jax.devices()),
    })


def config8_wire_compression() -> None:
    """(beyond reference) Gossip egress under the three wire codecs.

    The same 4-node federation over real gRPC sockets, 2 rounds × 1 epoch,
    under WIRE_COMPRESSION none / int8 / topk8 — reporting actual bytes
    that crossed the weight plane (GrpcProtocol.wire_stats) and the final
    accuracy, so the compression claims rest on measured egress, not
    per-payload arithmetic. The reference ships raw pickled float32 only.
    """
    from p2pfl_tpu.communication.grpc_transport import GrpcProtocol
    from p2pfl_tpu.communication.memory import MemoryRegistry
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import Settings, set_test_settings
    from p2pfl_tpu.utils import full_connection, wait_convergence, wait_to_finish

    set_test_settings()
    results = {}
    for mode in ("none", "int8", "topk8"):
        MemoryRegistry.reset()
        Settings.WIRE_COMPRESSION = mode
        full = FederatedDataset.synthetic_mnist(n_train=2048, n_test=512)
        nodes = []
        for i in range(4):
            learner = JaxLearner(mlp(seed=i), full.partition(i, 4), batch_size=64)
            n = Node(learner=learner, protocol=GrpcProtocol("127.0.0.1:0"))
            n.start()
            nodes.append(n)
        for n in nodes:
            full_connection(n, nodes)
        wait_convergence(nodes, 3, only_direct=True)
        nodes[0].set_start_learning(rounds=2, epochs=1)
        wait_to_finish(nodes, timeout=180)
        acc = min(float(n.learner.evaluate()["test_acc"]) for n in nodes)
        wb = sum(n.protocol.wire_stats["weights_bytes"] for n in nodes)
        wm = sum(n.protocol.wire_stats["weights_msgs"] for n in nodes)
        for n in nodes:
            n.stop()
        results[mode] = {
            "weights_MB": round(wb / 1e6, 3),
            "weights_msgs": wm,
            "min_final_acc": round(acc, 4),
        }
        log(f"config8 {mode}: {results[mode]}")
    Settings.WIRE_COMPRESSION = "none"
    emit({
        "metric": "config8_wire_compression_egress",
        "value": round(results["none"]["weights_MB"] / max(results["topk8"]["weights_MB"], 1e-9), 2),
        "unit": "x_egress_shrink_topk8_vs_float32",
        "modes": results,
        "n_nodes": 4,
        "rounds": 2,
        "transport": "grpc loopback",
        "data": "synthetic",
    })


def _moe_step_at_scale() -> dict:
    """Grad-step hardware-MFU of the MoE transformer at MXU-filling dims
    (the federation row's 4L/128d model is dispatch-bound, like config 5's
    toy row). Dense-dispatch/combine einsums execute every [E, C] expert
    slot, so XLA's FLOP count is the executed work — the standard TPU MoE
    cost model (GShard/Switch)."""
    import optax

    from p2pfl_tpu.management.profiling import compiled_flops
    from p2pfl_tpu.models.base import apply_with_aux
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer

    dim, ffn, e, layers, t, b = 512, 1408, 8, 6, 512, 16
    cfg = TransformerConfig(
        vocab_size=4096, dim=dim, n_layers=layers, n_heads=dim // 64,
        n_kv_heads=max(2, dim // 256), ffn_hidden=ffn, lora_rank=0,
        n_experts=e, moe_top_k=2,
    )
    m = tiny_transformer(seq_len=t, cfg=cfg)
    n_params = sum(x.size for x in jax.tree.leaves(m.params))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (b, t), 0, 4096)
    targets = jnp.roll(tokens, -1, axis=1)

    def loss(p, bx, by):
        logits, aux = apply_with_aux(m.module, p, bx)
        return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean() + aux

    flops = compiled_flops(jax.jit(jax.value_and_grad(loss)), m.params, tokens, targets)

    def train_step(p, bx, by):
        _l, g = jax.value_and_grad(loss)(p, bx, by)
        return jax.tree.map(lambda a, gr: a - 1e-4 * gr.astype(a.dtype), p, g), bx, by

    sec = _fused_timer(train_step, (m.params, tokens, targets), iters=20)
    return {
        "model": f"{layers}L/{dim}d MoE, {e} experts top-2, ffn {ffn}, seq {t}, batch {b}",
        "n_params": n_params,
        "step_ms": round(sec * 1e3, 1),
        "flops_per_step": flops,
        "mfu_hw": round(_mfu_from(flops, sec) or 0, 4),
        "note": "executed flops incl. all dense-dispatch expert slots",
    }


def config10_moe_gpipe_federation() -> None:
    """(beyond reference) Federations training THROUGH MoE and GPipe.

    VERDICT r2 weak #3: the ep/pp axes compiled but no federation trained
    through them. Two rows:

    - MoE: 8 nodes federate a switch-style MoE transformer (8 experts,
      top-2, aux balance losses riding the federated loss) via
      ``SpmdLmFederation`` — accuracy trajectory to a stated target plus
      steady-state sec/round. Expert parallelism is mesh-width-bound: on
      the single bench chip the ``model`` axis is 1 (the 2-way-ep layout
      is proven on the 8-device virtual mesh in tests + dryrun).
    - GPipe: pipeline stages need >1 device, so the pipelined federation
      re-execs onto the virtual 8-device CPU mesh (4 stages × 2 nodes
      time-sharing them) — provenance recorded; real-chip pp numbers need
      real multi-chip hardware.
    """
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import SpmdLmFederation

    n = 8
    cfg = TransformerConfig(
        vocab_size=512, dim=128, n_layers=4, n_heads=8, n_kv_heads=8,
        ffn_hidden=256, lora_rank=0, n_experts=8, moe_top_k=2,
    )
    model = tiny_transformer(seq_len=128, cfg=cfg)
    data = FederatedDataset.synthetic_lm(vocab_size=512, n_train=n * 256, n_test=512)
    fed = SpmdLmFederation.from_dataset(
        model, data, n_nodes=n, batch_size=16, vote=False, seed=3
    )
    target = 0.60
    curve = []
    rounds_to_target = None
    t0 = time.monotonic()
    for r in range(12):
        fed.run_round(epochs=1)
        acc = fed.evaluate()["test_acc"]
        curve.append(round(float(acc), 4))
        log(f"config10 moe round {r + 1}: acc {acc:.4f}")
        if rounds_to_target is None and acc >= target:
            rounds_to_target = r + 1
            time_to_target = time.monotonic() - t0
            break
    # one un-timed settling round: the transition out of the eval-interleaved
    # curve loop costs a ~1.4 s round (measured) that is not steady state
    fed.run_round(epochs=1)
    force_execution(fed.params)
    sec_per_round = _steady_state(fed, rounds=3)
    flops, round_mfu = _spmd_mfu(fed, sec_per_round)
    # the 4L/128d federation model is dispatch/toy-scale-bound (like the
    # config-5 toy row); the AT-SCALE step probe shows what the MoE layer's
    # dense-dispatch formulation sustains when the shapes fill the MXU.
    # NOTE the numerator is XLA-counted EXECUTED flops: dense dispatch
    # computes every [E, C] expert slot (only top-k combine per token) —
    # the standard TPU MoE cost model, reported as hardware utilization.
    moe_scale = _moe_step_at_scale()
    log(f"config10 moe_step_at_scale: {moe_scale}")

    # NOT fused: measured on the chip, run_fused SLOWS this federation
    # (0.78 -> 3.4 s/round) — full-param MoE rounds are compute-bound, so
    # the fused scan's carry costs more than the one dispatch it saves
    # (see SpmdLmFederation.run_fused's docstring; fusing pays only for
    # dispatch-dominated tiny-state rounds like config 5's adapters)
    emit({
        "metric": "config10_moe_federation",
        "moe_step_at_scale": moe_scale,
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "flops_per_round": flops,
        "mfu": round(round_mfu, 4) if round_mfu is not None else None,
        "n_nodes": n,
        "model": "4L/128d MoE transformer, 8 experts top-2, seq 128",
        "acc_curve": curve,
        "target_acc": target,
        "rounds_to_target": rounds_to_target,
        "time_to_target_s": round(time_to_target, 2) if rounds_to_target else None,
        "expert_parallel": int(fed.mesh.shape.get("model", 1)),
        "data": "synthetic_lm",
        "devices": len(jax.devices()),
    })

    # GPipe federation: re-exec on a virtual multi-device mesh when the
    # current backend cannot host >1 pipeline stage
    if len(jax.devices()) >= 4:
        _config10_gpipe_body()
    else:
        # pipeline stages need >1 device: virtual 8-device CPU mesh
        _reexec("10pipe", timeout=1500, virtual_devices=8)


def _config10_gpipe_body() -> None:
    """GPipe federation, profiled and tuned (VERDICT r3 #5).

    Round 3 reported 59.6 s/round with no breakdown. The profile (emitted
    per row) shows where it goes on this 1-core CPU-mesh simulation:

    - per-node pipelined epochs are ~all of it; host FedAvg is ~ms;
    - the pipelined step costs ≈ (M+P−1)/M × the monolithic step (every
      virtual device executes every schedule slot SERIALLY on one core —
      on real chips the P stages run in parallel, so chip time/round ≈
      serialized/P plus bubbles);
    - bf16 is software-emulated on CPU (measured 1.76× on the monolithic
      step), so this CPU row runs f32 — the dtype is a backend artifact,
      not part of the config (real-chip pp stays bf16).

    Tuning applied (round-5 ablation, VERDICT r4 #6): batch 32 with
    n_micro = 16 (mb 2) — bubble fraction (P−1)/(M+P−1) = 3/19 = 16%, and
    the measured pipe tax drops to ~1.39× (from 1.78× at b16/m8 in round
    4, of which ~0.18× was the per-node profiling sync since made opt-in).
    The ablation (ppermute→identity, no-output-collect, and a plain-scan
    "floor" running the full (M+P−1)·P schedule slots without shard_map)
    attributes the non-bubble overhead: boundary transfers ≈ 0, output
    collect ≈ 0.04×, residual ≈ scan/shard_map machinery — the serialized
    bubble/garbage floor itself measures at the GPipe bound, so the
    real-chip projection (pipe_step/P + bubbles) stands.
    """
    import optax

    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import PipelineFederation
    from p2pfl_tpu.parallel.pipeline import pipelined_lm_apply

    dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    cfg = TransformerConfig(
        vocab_size=512, dim=128, n_layers=4, n_heads=8, n_kv_heads=8,
        ffn_hidden=344, lora_rank=0, dtype=dtype,
    )
    model = tiny_transformer(seq_len=128, cfg=cfg)
    data = FederatedDataset.synthetic_lm(vocab_size=512, n_train=2 * 512, n_test=256)
    shards = [data.partition(i, 2) for i in range(2)]
    n_micro = 16
    fed = PipelineFederation(
        model, shards, n_stages=4, batch_size=32, n_micro=n_micro, seed=3
    )
    target = 0.60
    curve = []
    rounds_to_target = None
    time_to_target = None
    t0 = time.monotonic()
    for r in range(10):
        fed.run_round(epochs=1, profile=True)
        acc = fed.evaluate()["test_acc"]
        curve.append(round(float(acc), 4))
        log(f"config10 gpipe round {r + 1}: acc {acc:.4f} profile {fed.last_profile}")
        if rounds_to_target is None and acc >= target:
            rounds_to_target = r + 1
            time_to_target = time.monotonic() - t0
        if rounds_to_target is not None and r + 1 >= 5:
            break  # >=5-round curve even when the target falls early
    profile = fed.last_profile  # breakdown from the profiled curve loop above
    # steady-state timing runs UNPROFILED: per-node block_until_ready would
    # serialize dispatch and inflate the headline sec/round
    t0 = time.monotonic()
    for _ in range(2):
        fed.run_round(epochs=1)
    force_execution(fed.params)
    sec_per_round = (time.monotonic() - t0) / 2

    # pipeline tax reference points: the SAME model/batch as one monolithic
    # (unpipelined) train step vs one pipelined step on this backend
    tokens = jnp.asarray(shards[0].x_train[:32])
    targets = jnp.asarray(shards[0].y_train[:32])
    mesh = fed.mesh

    def mono_loss(p):
        logits = model.module.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean()

    def pipe_loss(p):
        logits, aux = pipelined_lm_apply(
            p, tokens, cfg, mesh, fed.axis, n_micro=n_micro, return_aux=True
        )
        return optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean() + aux

    def t_step(fn):
        g = jax.jit(jax.value_and_grad(fn))
        out = g(model.params)
        jax.block_until_ready(out)
        t0 = time.monotonic()
        for _ in range(3):
            out = g(model.params)
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / 3

    mono_ms = round(t_step(mono_loss) * 1e3, 1)
    pipe_ms = round(t_step(pipe_loss) * 1e3, 1)
    n_stages = 4
    emit({
        "metric": "config10_gpipe_federation",
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "n_nodes": 2,
        "pipeline_stages": n_stages,
        "n_micro": n_micro,
        "model": f"4L/128d transformer, GPipe 4-stage, seq 128, "
                 f"{'f32 (bf16 is CPU-emulated, 1.76x)' if dtype == jnp.float32 else 'bf16'}",
        "acc_curve": curve,
        "target_acc": target,
        "rounds_to_target": rounds_to_target,
        "time_to_target_s": round(time_to_target, 2) if rounds_to_target else None,
        "breakdown": {
            "per_node_epoch_s": profile["node_epoch_s"],
            "host_fedavg_s": profile["fedavg_s"],
            "mono_step_ms": mono_ms,
            "pipe_step_ms": pipe_ms,
            "pipe_tax_measured": round(pipe_ms / mono_ms, 2),
            "bubble_fraction": round((n_stages - 1) / (n_micro + n_stages - 1), 3),
            "note": "1-core CPU mesh serializes the P stages; real-chip "
                    "projection ~ pipe_step/P + bubbles",
        },
        "data": "synthetic_lm",
        "devices": len(jax.devices()),
        "backend": jax.default_backend(),
    })


def config9_personalization() -> None:
    """(beyond reference) FedPer vs plain FedAvg under CONCEPT SHIFT.

    4 nodes share the input distribution but each maps features to its OWN
    label semantics (a node-specific label permutation — think region-
    specific class taxonomies). One global head cannot fit contradictory
    conditionals; FedPer federates the feature body and keeps each node's
    head local. Metric: mean per-node accuracy on the node's OWN test
    shard. (Under plain label-FREQUENCY skew the global model wins — we
    measured that too; personalization is for shifted conditionals, and
    this row shows exactly that regime.)
    """
    from p2pfl_tpu.communication.memory import MemoryRegistry
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.learning.personalization import PersonalizedLearner
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import Settings, set_test_settings
    from p2pfl_tpu.utils import full_connection, wait_convergence, wait_to_finish

    set_test_settings()
    Settings.TRAIN_SET_SIZE = 4
    results = {}
    for label in ("fedavg_global", "fedper_personal"):
        MemoryRegistry.reset()
        full = FederatedDataset.synthetic_mnist(
            n_train=4096, n_test=1024, modes=4, noise=0.6, proto_scale=0.6
        )
        nodes = []
        for i in range(4):
            shard = full.partition(i, 4)
            # concept shift: node i relabels classes by its own permutation
            perm = np.random.default_rng(100 + i).permutation(shard.num_classes)
            shard.y_train = perm[shard.y_train]
            shard.y_test = perm[shard.y_test]
            if label == "fedper_personal":
                learner = PersonalizedLearner(
                    mlp(seed=i), shard, batch_size=64, personal=("Dense_2",)
                )
            else:
                learner = JaxLearner(mlp(seed=i), shard, batch_size=64)
            n = Node(learner=learner)
            n.start()
            nodes.append(n)
        for n in nodes:
            full_connection(n, nodes)
        wait_convergence(nodes, 3, only_direct=True)
        t0 = time.monotonic()
        nodes[0].set_start_learning(rounds=5, epochs=2)
        wait_to_finish(nodes, timeout=300)
        elapsed = time.monotonic() - t0
        accs = [float(n.learner.evaluate()["test_acc"]) for n in nodes]
        for n in nodes:
            n.stop()
        results[label] = {
            "mean_local_acc": round(float(np.mean(accs)), 4),
            "per_node": [round(a, 4) for a in accs],
            "wall_s": round(elapsed, 1),
        }
        log(f"config9 {label}: {results[label]}")
    emit({
        "metric": "config9_fedper_vs_global_concept_shift",
        "value": results["fedper_personal"]["mean_local_acc"],
        "unit": "mean_local_acc",
        "fedper_personal": results["fedper_personal"],
        "fedavg_global": results["fedavg_global"],
        "n_nodes": 4,
        "rounds": 5,
        "setting": "concept shift (node-specific label permutations)",
        "data": "synthetic",
    })


def config10_moe_scale() -> None:
    """MoE federation AT SCALE (VERDICT r4 #2): the 6L/512d/8-expert 110M
    model — previously only a bare grad-step probe (``_moe_step_at_scale``,
    64% hw-MFU) — run as an actual multi-round federation: N nodes,
    accuracy curve to target, steady-state sec/round, MFU. The exact
    treatment the dense 104M model got in config5_scale_lm_104m.

    Sizing: node-stacked f32 params + Adam moments are 12 B/param·node →
    4 nodes × 113M ≈ 5.4 GB; with the GShard dense-dispatch [S, E, C]
    tensors per layer the total-token budget matches the probe's
    (4 nodes × batch 4 × seq 512 = 8192 tokens in flight).

    MFU numerator is XLA-counted EXECUTED flops (dense dispatch computes
    every expert slot — the standard TPU MoE cost model, same accounting
    as the probe row), so this is hardware utilization.
    """
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import SpmdLmFederation

    n = 4
    dim, ffn, e, layers, t = 512, 1408, 8, 6, 512
    cfg = TransformerConfig(
        vocab_size=4096, dim=dim, n_layers=layers, n_heads=dim // 64,
        n_kv_heads=max(2, dim // 256), ffn_hidden=ffn, lora_rank=0,
        n_experts=e, moe_top_k=2,
    )
    model = tiny_transformer(seq_len=t, cfg=cfg)
    n_params = sum(x.size for x in jax.tree.leaves(model.params))
    log(f"config10_moe_scale: {n_params/1e6:.1f}M params")
    data = FederatedDataset.synthetic_lm(
        vocab_size=4096, seq_len=t, n_train=n * 64, n_test=32
    )
    fed = SpmdLmFederation.from_dataset(
        model, data, n_nodes=n, batch_size=4, vote=False, seed=3
    )
    # the vocab-4096 chain needs ~400 optimizer steps to lock in (the dense
    # 104M base took a 300-step central pretrain); at nb=16 steps/round a
    # 3-epoch local pass gives 48 steps/round — rounds_to_target measures
    # the FEDERATED path doing that work, no central pretrain here
    target = 0.60
    epochs_per_round = 3
    curve = []
    rounds_to_target = None
    time_to_target = None
    t0 = time.monotonic()
    for r in range(15):
        fed.run_round(epochs=epochs_per_round)
        acc = fed.evaluate()["test_acc"]
        curve.append(round(float(acc), 4))
        log(f"config10_moe_scale round {r + 1}: acc {acc:.4f}")
        if rounds_to_target is None and acc >= target:
            rounds_to_target = r + 1
            time_to_target = time.monotonic() - t0
            break
    # settling round: the eval-to-steady transition is not steady state
    fed.run_round(epochs=1)
    force_execution(fed.params)
    sec_per_round = _steady_state(fed, rounds=3)
    flops, round_mfu = _spmd_mfu(fed, sec_per_round)
    emit({
        "metric": "config10_moe_scale",
        "value": round(sec_per_round, 4),
        "unit": "sec_per_round",
        "model": f"{layers}L/{dim}d MoE, {e} experts top-2, ffn {ffn}, "
                 f"seq {t}, vocab 4096",
        "n_params": n_params,
        "n_nodes": n,
        "batch_per_node": 4,
        "steps_per_round": fed._nb,
        "epochs_per_round": epochs_per_round,
        "flops_per_round": flops,
        "mfu_hw": round(round_mfu, 4) if round_mfu is not None else None,
        "mfu_note": "XLA-counted executed flops: dense dispatch computes "
                    "every [E, C] expert slot (GShard/Switch cost model); "
                    "sec_per_round and mfu are the 1-epoch steady state",
        "acc_curve": curve,
        "target_acc": target,
        "rounds_to_target": rounds_to_target,
        "time_to_target_s": round(time_to_target, 2) if time_to_target else None,
        "data": "synthetic_lm (markov, vocab 4096)",
        "devices": len(jax.devices()),
    })


def config_async_federation() -> None:
    """ISSUE 9 row: sync round FSM vs async FedBuff vs hierarchical on the
    mnist fleet under the seeded straggler/crash plan (full measurement +
    JSON artifact live in ``bench_async.py`` / BENCH_ASYNC.json; this row
    is the suite-resident summary and CI guard)."""
    if jax.default_backend() != "cpu":
        _reexec("async", timeout=900)
        return
    import bench_async

    rows = [bench_async.run_threaded(m, rounds=2) for m in ("sync", "async", "hier")]
    sync_wall = next(r["wall_s"] for r in rows if r["mode"] == "sync")
    emit(
        {
            "metric": "async_federation_time_to_target",
            "provenance": "synthetic mnist, 10 nodes, seeded 1-slow/1-crash plan "
            "(bench_async.py; BENCH_ASYNC.json has the full row + 1k-node sim)",
            "target_acc": bench_async.TARGET_ACC,
            "rows": {
                r["mode"]: {
                    "wall_s": r["wall_s"],
                    "reached_target": r["reached_target"],
                    "speedup_vs_sync": round(sync_wall / r["wall_s"], 2),
                }
                for r in rows
            },
        }
    )


CONFIGS = {
    "1": config1_mnist_2node,
    "async": config_async_federation,
    "2": config2_resnet18_8node,
    "3": config3_resnet50_64node_dirichlet,
    "4": config4_byzantine_robust,
    "5": config5_lora_32node,
    "5scale": config5_scale_lm,
    "5b": config5_nameplate_1b,
    "5sharded": config5_sharded,
    "6": config6_heterogeneous_algorithms,
    "7": config7_long_context_flash,
    "8": config8_wire_compression,
    "9": config9_personalization,
    "10": config10_moe_gpipe_federation,
    "10moe": config10_moe_scale,
    "10pipe": _config10_gpipe_body,  # internal: config10's multi-device re-exec
}


def main() -> int:
    from p2pfl_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    wanted = sys.argv[1:] or [k for k in sorted(CONFIGS, key=lambda s: (len(s), s)) if not k.endswith("pipe")]
    if len(wanted) == 1:
        CONFIGS[wanted[0]]()
        return 0
    # one subprocess per config: an OOM (or any backend poisoning) in one
    # config must not contaminate the next measurement
    import subprocess

    failed = []
    for key in wanted:
        log(f"=== config {key} ===")
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, __file__, key], capture_output=True, text=True, timeout=1800
        )
        sys.stderr.write(proc.stderr[-2000:])
        if proc.returncode == 0 and proc.stdout.strip():
            sys.stdout.write(proc.stdout)
            sys.stdout.flush()
        else:
            emit({"metric": f"config{key}", "error": f"rc={proc.returncode}: {proc.stderr[-300:]}"})
            failed.append(key)
        log(f"=== config {key} done in {time.monotonic() - t0:.1f}s ===")
    if failed:
        log(f"configs failed: {failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
