"""Device time of the round's program by ``p2pfl.*`` scope
(``benchmark/scope_reduce.py`` has the buckets and where the names come from).

``read(context, bucket=..., per=...)``: ``bucket`` is one of the partition
(``fwd``, ``remat``, ``bwd``, ``opt``, ``fold``, ``unscoped``) or a sub-share
(``base_cast``, ``adapter``, ``flash_fwd``, ``flash_bwd``); ``per`` is

- ``"step"``  ms per local step: ÷ (executions × ``steps_per_program_run``), the
  denominator of ``step_ms``, so the step metrics add up to it;
- ``"round"`` ms per execution of the round's program, on one device;
- ``"share"`` % of the program's leaf-op time.

The trace is reduced ONCE a run (the result is kept in ``context``); the first
call prints, on earlier lines of the output, the bucket table, the 15 longest
ops with their ``op_name``, the two identities that tie these numbers to
``step_ms`` and ``flash_ms``, and what tracing cost the traced rounds.

Returns ``None`` — and says so — where there is nothing to read: a program
that carries no ``p2pfl.*`` scope at all (one from before PR 24), or a bucket
that no op of this cell falls in.
"""

from __future__ import annotations

import statistics
from pathlib import Path

from benchmark import scope_reduce, trace_reduce

OUT = Path(__file__).resolve().parent.parent / "out" / "trace"


def trace_file(cell: str) -> Path:
    """The newest trace of the cell's traced run, found as ``run.Tracer.load`` finds it."""
    files = sorted((OUT / cell).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise SystemExit(f"benchmark: no trace file under {OUT / cell}")
    return files[-1]


def _ms(ns: float) -> float:
    return ns / 1e6


def report(context: dict, got: dict, path: Path) -> None:
    """The lines a person reads: bucket table, longest ops, identities, cost."""
    say = context["job"].say
    total, runs = got["total_ns"], got["executions"]
    steps = runs * (context["shapes"].get("steps_per_program_run") or 1)
    say(
        f"scope: program {got['program']}, {runs} execution(s), {steps} local steps, leaf-op time {total / 1e9:.6f} s"
        + (f"; {got['missing']} op(s) without an hlo_stats row" if got["missing"] else "")
    )
    if not got["scoped"]:
        say("scope: NO p2pfl.* scope on any instruction — a program from before PR 24; the scope metrics are left out")
    for kind, table in (("bucket", got["buckets"]), ("sub-share", got["shares"])):
        for name, ns in table.items():
            say(
                f"scope: {kind} {name}: {ns / 1e9:.6f} s = {100.0 * ns / total:.3f} % = "
                f"{_ms(ns) / steps:.4f} ms a step = {_ms(ns) / runs:.4f} ms an execution"
            )
    for label, op_name, ns in got["top"]:
        say(f"scope: top {ns / 1e9:.6f} s {100.0 * ns / total:.2f} % {label} <- {scope_reduce.tail(op_name)}")
    trace = context["trace"]
    busy = trace_reduce.busy_in_runs(trace, got["program"])
    if busy:
        step_ms = statistics.mean(busy) / (steps / runs) * 1e3
        say(
            f"scope: identity step: sum of buckets {_ms(total) / steps:.4f} ms a step against step_ms {step_ms:.4f} "
            f"(ratio {_ms(total) / steps / step_ms:.5f}; above 1 = asynchronous ops overlapping)"
        )
    mosaic_s, calls = trace_reduce.mosaic_seconds(trace)
    if calls:
        flash = (got["shares"]["flash_fwd"] + got["shares"]["flash_bwd"]) / 1e9
        say(
            f"scope: identity flash: flash_fwd + flash_bwd {flash:.6f} s against {calls} Mosaic calls {mosaic_s:.6f} s "
            f"(ratio {flash / mosaic_s:.5f}); other ops under the calls' names {got['beside_kernels_ns'] / 1e9:.6f} s"
        )
    starts = got["starts"]
    traced = [(b - a) / 1e9 for a, b in zip(starts, starts[1:])]
    if traced and context.get("intervals"):
        untraced = statistics.median(context["intervals"])
        say(
            f"scope: tracing on: traced round intervals {[round(x, 5) for x in traced]} s (device clock, start to start) "
            f"against the untraced median {untraced:.5f} s = {100.0 * (statistics.median(traced) / untraced - 1.0):+.3f} %; "
            f"trace file {path.stat().st_size / 1e6:.2f} MB"
        )


def reduced(context: dict) -> dict:
    if "scope" not in context:
        path = trace_file(context["job"].name)
        got = scope_reduce.reduce_file(str(path), context["trace"])
        report(context, got, path)
        context["scope"] = got
    return context["scope"]


def read(context, *, bucket: str, per: str):
    got = reduced(context)
    if not got["scoped"]:
        return None
    ns = got["buckets"][bucket] if bucket in got["buckets"] else got["shares"][bucket]
    if ns == 0:
        context["job"].say(f"scope: NOTHING under {bucket!r} in {got['program']}: the metric is left out")
        return None
    runs = got["executions"]
    if per == "step":
        return _ms(ns) / (runs * context["shapes"]["steps_per_program_run"])
    if per == "round":
        return _ms(ns) / runs
    if per == "share":
        return 100.0 * ns / got["total_ns"]
    raise ValueError(f"unknown scope reading per={per!r}")
