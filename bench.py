"""Headline benchmark: 64-node federated MNIST, time to 98% test accuracy.

North star (BASELINE.json): 64 federated MNIST nodes converge to >=98% test
accuracy in <60 s wall-clock with zero gRPC traffic (weights over ICI).
The reference publishes no numbers (SURVEY §6); the target is the driver's
BASELINE.json bound, so ``vs_baseline = 60 / measured_seconds`` (>1 beats it).

Honesty notes:
- the JSON records data provenance (``data``: "idx" = real MNIST files,
  "synthetic-hard" = the Gaussian-mixture stand-in);
- the synthetic task uses 8 prototype modes per class at prototype scale
  0.5 / noise 0.7 — measured to need ~12 federated rounds to 98% (see
  ``accuracy_curve``), so "time-to-98%" measures convergence, not the
  latency of one dispatch;
- ``mfu`` is model-FLOPs-utilization of the steady-state round (compiled
  XLA FLOPs / wall-clock / chip peak), null off-TPU.

Runs the SPMD federation on whatever devices ``jax.devices()`` shows (the
TPU chips on a chip machine; the virtual CPU mesh under tests). One compile
warm-up phase runs first and is excluded — state is fully reset afterwards.

Prints exactly ONE JSON line on stdout; progress goes to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np


N_NODES = 64
TARGET_ACC = 0.98
TARGET_SECONDS = 60.0
MAX_ROUNDS = 30
CHUNK = 5  # rounds per fused dispatch (train + eval curve on device)
BATCH = 64
# Gaussian-mixture difficulty (measured: ~12 rounds to 98% at this setting)
HARD_TASK = {"modes": 8, "noise": 0.7, "proto_scale": 0.5}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    from p2pfl_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.management.profiling import force_execution, mfu
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.parallel import SpmdFederation

    log(f"devices: {jax.devices()}")
    data = FederatedDataset.mnist(os.environ.get("P2PFL_MNIST_DIR"), **HARD_TASK)
    provenance = "idx" if data.source == "idx" else "synthetic-hard"
    log(f"data: {provenance}")
    model = mlp()

    # keep_opt_state: the framework's documented improvement over the
    # reference's per-round optimizer reset (Adam moments carry across
    # rounds) — measured 12 -> 9 rounds to 98% on this task; recorded in
    # the JSON so the knob is visible
    fed = SpmdFederation.from_dataset(
        model, data, n_nodes=N_NODES, batch_size=BATCH, vote=False, seed=3,
        keep_opt_state=True,
    )

    # compile warm-up, then reset state in place (same mesh → same
    # executables). Both fused variants (eval curve + steady state) and the
    # single-round program are warmed; each warm call ends on a D2H fetch.
    t0 = time.monotonic()
    # eval chunk twice: round-1 (fresh) and rounds>=2 (evolved) input
    # layouts compile separately — one warm call would leave the second
    # timed chunk to compile inside the timer
    [float(e["test_acc"]) for e in fed.run_fused(CHUNK, epochs=1, eval=True)]
    [float(e["test_acc"]) for e in fed.run_fused(CHUNK, epochs=1, eval=True)]
    fed.run_fused(CHUNK, epochs=1)  # steady-state variant
    float(fed.evaluate()["test_acc"])
    log(f"warm-up (compile, {3 * CHUNK} rounds): {time.monotonic() - t0:.1f}s")
    t0 = time.monotonic()
    fed.reset(seed=3)
    force_execution(fed.params)
    log(f"reset: {time.monotonic() - t0:.2f}s")

    # convergence: fused chunks of CHUNK rounds, the whole chunk (train +
    # per-round eval of the aggregated model) is ONE dispatch; the accuracy
    # curve syncs once per chunk instead of once per round
    t0 = time.monotonic()
    elapsed = float("nan")
    acc = 0.0
    curve = []
    while len(curve) < MAX_ROUNDS:
        entries = fed.run_fused(CHUNK, epochs=1, eval=True)
        accs = [float(e["test_acc"]) for e in entries]
        elapsed = time.monotonic() - t0
        curve.extend(round(a, 4) for a in accs)
        log(f"rounds {len(curve) - CHUNK + 1}-{len(curve)}: acc={accs} elapsed={elapsed:.2f}s")
        if max(accs) >= TARGET_ACC:
            acc = max(accs)
            break
        acc = accs[-1]

    if acc < TARGET_ACC:
        # did not reach target: report elapsed at best acc, flagged by value
        log(f"target {TARGET_ACC} not reached (best {acc:.4f})")
    rounds_to_target = next(
        (i + 1 for i, a in enumerate(curve) if a >= TARGET_ACC), len(curve)
    )

    # steady-state throughput: one more fused span, no eval (CHUNK-shaped —
    # the only fused programs warm-up compiled; any other span length would
    # put a fresh XLA compile inside the timer)
    t1 = time.monotonic()
    fed.run_fused(CHUNK, epochs=1)
    force_execution(fed.params)
    sec_per_round = (time.monotonic() - t1) / CHUNK

    # MFU of the steady-state round (train only, no eval)
    flops = fed.round_flops()
    round_mfu = mfu(flops, sec_per_round, n_devices=len(set(fed.mesh.devices.flat)))

    print(
        json.dumps(
            {
                "metric": "mnist64_time_to_98pct",
                "value": round(elapsed, 3),
                "unit": "s",
                "vs_baseline": round(TARGET_SECONDS / elapsed, 3) if np.isfinite(elapsed) else 0.0,
                "reached_acc": round(acc, 4),
                "rounds_to_target": rounds_to_target,
                "accuracy_curve": curve,
                "sec_per_round": round(sec_per_round, 4),
                "flops_per_round": flops,
                "mfu": round(round_mfu, 4) if round_mfu is not None else None,
                "data": provenance,
                "n_nodes": N_NODES,
                "keep_opt_state": True,
                "devices": len(jax.devices()),
            }
        )
    )


if __name__ == "__main__":
    main()
