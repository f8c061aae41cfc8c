"""The measured window of the SPMD engines, shared by their drivers: rounds
back to back until the deadline, each ended by the one barrier the benchmark
can defend — the device-to-host fetch of the round's scalar loss, which cannot
return before the round's program has finished."""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from benchmark import checks as ck


def one_round(fed) -> float:
    with jax.profiler.TraceAnnotation("bench:round"):
        entry = fed.run_round(epochs=1)
        with jax.profiler.TraceAnnotation("bench:fetch_loss"):
            return float(entry["train_loss"])


def spmd_measure(fed, seconds: float, tracer, trace_rounds: int, train_nodes: int) -> dict:
    start = time.monotonic()
    deadline = start + seconds
    completions, losses = [start], []
    while completions[-1] < deadline:  # a round that straddles the deadline is finished and counted
        losses.append(one_round(fed))
        completions.append(time.monotonic())
    if tracer is not None:
        tracer.start()
        for _ in range(trace_rounds):
            losses.append(one_round(fed))
        tracer.stop()
    rounds = len(losses)
    bad = sum(not math.isfinite(x) for x in losses)
    return {
        "completions": completions,
        "intervals": [b - a for a, b in zip(completions, completions[1:])],
        "losses": losses,
        "attempted": rounds * train_nodes,
        "failed": bad * train_nodes,
    }


def spmd_final_checks(job, fed, window: dict) -> None:
    """After the window: every node holds the same aggregate, every loss is
    finite, and the loss at round k is below round 1's."""
    spread = max(
        float(np.max(np.abs(np.asarray(leaf) - np.asarray(leaf[0])[None])))
        for leaf in jax.tree.leaves(fed.params)
    )
    job.checks.at_most("final.node_param_spread", spread, ck.NODE_EQ)
    losses = window["losses"]
    job.checks.add("final.losses_finite", all(math.isfinite(x) for x in losses), rounds=len(losses))
    k = job.cell["k"]
    if len(losses) >= k:
        job.checks.add(
            "final.loss_falls", losses[k - 1] < losses[0], round_1=losses[0], round_k=losses[k - 1], k=k
        )
    else:
        job.checks.add("final.reached_round_k", False, rounds=len(losses), k=k)
