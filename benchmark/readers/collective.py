"""Cross-chip collective time per round and device, in milliseconds:
``part="total"`` is the union of all-reduce / all-gather / reduce-scatter /
permute operations, ``part="exposed"`` the part of it in which no other
operation ran on that device."""

from benchmark import trace_reduce


def read(context, *, part: str):
    trace = context["trace"]
    total, exposed, calls = trace_reduce.collective_seconds(trace)
    if calls == 0:
        return None
    name = trace_reduce.main_module(trace)
    runs = max(len(trace_reduce.module_runs(dev, name)) for dev in trace["devices"].values())
    return {"total": total, "exposed": exposed}[part] / runs * 1e3
