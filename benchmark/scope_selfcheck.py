"""Checks of the reduction by scope, on the CPU, in a few seconds:

    JAX_PLATFORMS=cpu python -m benchmark.scope_selfcheck [trace.xplane.pb]

On the recorded trace ``fixtures/scoped_trace.xplane.pb`` (TPU v5 lite, PR 24:
one execution of ``fixtures/produce_scoped.py``'s round, which carries every
``p2pfl.*`` scope): every bucket and sub-share holds time, the buckets
partition the program's leaf-op time exactly (checked against a sum made here,
not ``by_bucket``'s own), ``flash_fwd + flash_bwd`` equals
``trace_reduce.mosaic_seconds`` and every Mosaic call sits under one of the
two. On ``fixtures/small_trace.xplane.pb`` (a program without scopes): all of
it is ``unscoped`` and ``scoped`` is false. The trace is copied to a temporary
directory first: xprof writes a cache file beside what it reads.

A script like ``selfcheck.py``: it exits non-zero on the first failure.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

from benchmark.selfcheck import expect

HERE = Path(__file__).resolve().parent


def reduce_copy(path: Path) -> tuple[dict, dict]:
    """(``trace_reduce.load_xplane``, ``scope_reduce.reduce_file``) of a copy of ``path``."""
    from benchmark import scope_reduce, trace_reduce

    with tempfile.TemporaryDirectory(prefix="scope_selfcheck_") as tmp:
        copy = Path(tmp) / path.name
        shutil.copyfile(path, copy)
        trace = trace_reduce.load_xplane(str(copy))
        return trace, scope_reduce.reduce_file(str(copy), trace)


def check_scoped(path: Path) -> None:
    from benchmark import trace_reduce

    trace, got = reduce_copy(path)
    expect(got["scoped"] and got["missing"] == 0, f"{path.name}: p2pfl.* scopes found, every op has an hlo_stats row")
    expect(got["executions"] >= 1, f"main program {got['program']}: {got['executions']} execution(s)")
    for kind in ("buckets", "shares"):
        for name, ns in got[kind].items():
            expect(ns > 0, f"{kind[:-1]} {name}: {ns} ns")
    inside = sum(
        op[4]
        for dev in trace["devices"].values()
        for s, e in trace_reduce.module_runs(dev, got["program"])
        for op in dev["ops"] if s <= op[3] < e
    )
    expect(sum(got["buckets"].values()) == got["total_ns"] == inside, f"buckets partition the program's {inside} ns exactly")
    seconds, calls = trace_reduce.mosaic_seconds(trace)
    flash = got["shares"]["flash_fwd"] + got["shares"]["flash_bwd"]
    expect(flash == round(seconds * 1e9) and calls > 0, f"flash_fwd + flash_bwd = the {calls} Mosaic calls' {flash} ns")
    labels = [label for label, _, _ in got["top"]]
    expect(any("p2pfl_flash_" in label for label in labels), "the kernels carry their own names in the trace")


def check_unscoped(path: Path) -> None:
    _, got = reduce_copy(path)
    expect(
        not got["scoped"] and got["buckets"]["unscoped"] == got["total_ns"] == 358917,
        f"{path.name}: no scope, all 358917 ns unscoped",
    )


def main(argv: list[str]) -> int:
    before = sorted(p.name for p in (HERE / "fixtures").iterdir())
    check_scoped(Path(argv[1]) if len(argv) > 1 else HERE / "fixtures" / "scoped_trace.xplane.pb")
    check_unscoped(HERE / "fixtures" / "small_trace.xplane.pb")
    expect(sorted(p.name for p in (HERE / "fixtures").iterdir()) == before, "nothing was written beside the fixtures")
    print("scope selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
