"""Mosaic (Pallas) kernel time from the device trace. ``what="ms_per_step"``:
summed kernel time per local step, in milliseconds. ``what="roofline"``: the
kernels' executed causal-attention operations (``flops.flash_executed_flops``)
over that time over the published bf16 peak, in % — attention at these shapes
is bound by FLOP/s, not bytes. In these cells every Mosaic call is a flash
kernel; a forward/backward split needs kernel names (the tracing issue)."""

from benchmark import trace_reduce


def read(context, *, what: str):
    trace = context["trace"]
    seconds, calls = trace_reduce.mosaic_seconds(trace)
    if calls == 0:
        return None
    runs = sum(
        len(trace_reduce.module_runs(dev, trace_reduce.main_module(trace)))
        for dev in trace["devices"].values()
    )
    shapes = context["shapes"]
    if what == "ms_per_step":
        return seconds / (runs * shapes["steps_per_program_run"]) * 1e3
    if what == "roofline":
        needed = shapes["flash_flops_per_round"] * runs
        return 100.0 * needed / seconds / context["peak"]["bf16_flops_per_s"]
    raise ValueError(f"unknown mosaic reading {what!r}")
