"""Idle time on the device between consecutive executions of the round's
program, median over the traced rounds and devices, in milliseconds."""

import statistics

from benchmark import trace_reduce


def read(context):
    trace = context["trace"]
    gaps = trace_reduce.gaps_between_runs(trace, trace_reduce.main_module(trace))
    return statistics.median(gaps) * 1e3 if gaps else None
