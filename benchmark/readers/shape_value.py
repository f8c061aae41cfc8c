"""A number the engine's ``describe`` read from the PROGRAM's own counters
after the window (``shapes[key]``) — e.g. ``moe_load_max_over_mean``, the
window's mean of what every round's history entry carries. ``None`` where the
engine reports no such key (a program without the counter)."""

from __future__ import annotations


def read(context, *, key: str):
    value = context["shapes"].get(key)
    if value is None:
        context["job"].say(f"shape_value: the engine's shapes have no {key!r}: the metric is left out")
    return value
