"""Telemetry smoke: drive a 2-node round with the flight recorder on,
export the Chrome trace, validate it against the trace-event schema, print
the RoundReport, and bound the recorder's overhead.

CI runs this as the `ci.yml` telemetry step:

    JAX_PLATFORMS=cpu python bench_telemetry.py --out /tmp/telemetry-smoke

The overhead assertion here is a SMOKE bound (default 20%, plus an
absolute floor for protocol-tick quantization) — shared-runner wall-clock
noise swamps the real figure; the ≤5% measurement is bench_suite
config1's `telemetry` split, averaged over more rounds on a quiet machine. This step exists to catch a regression
that makes the recorder *expensive*, not to re-measure the budget.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def run_federation(rounds: int, telemetry_on: bool) -> float:
    """One fresh 2-node DummyLearner federation; returns wall seconds."""
    from p2pfl_tpu.communication.memory import MemoryRegistry
    from p2pfl_tpu.learning.learner import DummyLearner
    from p2pfl_tpu.management.telemetry import telemetry
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import Settings
    from p2pfl_tpu.utils import full_connection, wait_convergence, wait_to_finish

    MemoryRegistry.reset()
    prev = Settings.TELEMETRY_ENABLED
    Settings.TELEMETRY_ENABLED = telemetry_on
    if telemetry_on:
        telemetry.reset_spans()
    nodes = [Node(learner=DummyLearner(value=float(i))) for i in range(2)]
    try:
        for n in nodes:
            n.start()
        for n in nodes:
            full_connection(n, nodes)
        wait_convergence(nodes, 1, only_direct=True, wait=10)
        t0 = time.monotonic()
        nodes[0].set_start_learning(rounds=rounds, epochs=1)
        wait_to_finish(nodes, timeout=120)
        return time.monotonic() - t0
    finally:
        Settings.TELEMETRY_ENABLED = prev
        for n in nodes:
            n.stop()


def main() -> int:
    from p2pfl_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/telemetry-smoke", help="trace/report output dir")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument(
        "--overhead-bound", type=float, default=20.0,
        help="max telemetry-on overhead %% (smoke bound — see module docstring)",
    )
    args = ap.parse_args()

    from p2pfl_tpu.management.logger import logger
    from p2pfl_tpu.settings import set_test_settings

    set_test_settings()
    logger.set_level("ERROR")

    from p2pfl_tpu.management.telemetry import (
        dump_flight_record,
        telemetry,
        validate_chrome_trace,
    )

    # 0. warm-up federation OUTSIDE any timer: the first run pays one-time
    # costs (eager-op compiles, thread-pool spin-up) that would otherwise
    # bill entirely to whichever mode runs first
    run_federation(1, telemetry_on=False)

    # 1. telemetry-on round loop → trace + report artifacts
    wall_on = run_federation(args.rounds, telemetry_on=True)
    paths = dump_flight_record(args.out)
    doc = json.load(open(paths[0]))
    n_events = validate_chrome_trace(doc)
    print(f"trace: {paths[0]} ({n_events} events) — schema OK")

    reports = json.load(open(paths[1]))
    if not reports:
        print("FAIL: no round reports produced", file=sys.stderr)
        return 1
    for rep in reports:
        crit = rep["critical_path"]
        print(
            f"round {rep['round']}: wall {rep['wall_s']:.2f}s, "
            f"critical node {crit['node']} ({crit['stage']})"
        )
    rep0 = telemetry.round_report(0)
    if not rep0.per_node:
        print("FAIL: round 0 report attributed no spans", file=sys.stderr)
        return 1
    print(rep0.describe())

    # sanity: wire ctx linked at least one cross-thread/cross-node edge
    spans = telemetry.spans()
    recv_linked = [s for s in spans if s.name.startswith("recv:") and s.parent_id]
    if not recv_linked:
        print("FAIL: no recv spans carried a wire trace context", file=sys.stderr)
        return 1
    print(f"wire trace ctx: {len(recv_linked)} receiver spans linked to sender spans")

    # 2. telemetry-off loop → overhead smoke bound
    wall_off = run_federation(args.rounds, telemetry_on=False)
    overhead_pct = (wall_on - wall_off) / wall_off * 100.0
    # absolute floor: at sub-second rounds a single protocol tick (50-100ms)
    # of scheduling jitter exceeds any honest percentage
    tolerance_s = max(wall_off * args.overhead_bound / 100.0, 0.5)
    print(
        f"round loop: on={wall_on:.2f}s off={wall_off:.2f}s "
        f"({overhead_pct:+.1f}%, smoke bound {args.overhead_bound:.0f}% / {tolerance_s:.2f}s)"
    )
    if wall_on - wall_off > tolerance_s:
        print("FAIL: telemetry overhead exceeded the smoke bound", file=sys.stderr)
        return 1
    print("telemetry smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
