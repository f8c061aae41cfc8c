"""End-to-end model-FLOP utilisation in %: operations the algorithm needs for
one round (``flops.py``; nothing recomputed counts) over the median round
interval of the untraced window, over chips x published bf16 peak. Not a
roofline share and says nothing about idle time."""

import statistics


def read(context):
    intervals = context["intervals"]
    if not intervals:
        return None
    per_s = context["shapes"]["flops_per_round"] / statistics.median(intervals)
    return 100.0 * per_s / (context["device"]["count"] * context["peak"]["bf16_flops_per_s"])
