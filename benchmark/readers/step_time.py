"""Device-busy time of one execution of the round's program divided by the
local steps it holds (on one device), in milliseconds: what one node's local
step costs the device, amortised over the nodes in flight."""

import statistics

from benchmark import trace_reduce


def read(context):
    trace = context["trace"]
    busy = trace_reduce.busy_in_runs(trace, trace_reduce.main_module(trace))
    steps = context["shapes"].get("steps_per_program_run")
    if not busy or not steps:
        return None
    return statistics.mean(busy) / steps * 1e3
