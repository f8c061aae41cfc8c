"""A kernel's share of its roofline, in %: a floor NAMED in the arguments —
``shapes[floor]``, the least seconds one sequence-step of that work could take
on this chip, computed from shapes by the engine's ``describe`` (the larger of
operations over the bf16 peak and bytes over the HBM peak) — over the device
time measured under the named ``p2pfl.*`` scopes (``readers/scope_named.py``
has the reduction: forward, remat's re-forward and backward all count in the
time, so remat lowers the share, as it should).

``readers/scope_named.py``'s own ``"roofline"`` reading is wired to one floor
(``ssm_scan_floor_s_per_step``); this reader takes any.

Returns ``None`` — and says so — where no op of the traced program carries any
of the scopes (a program from before the scope existed), or where ``describe``
gave no such floor.
"""

from __future__ import annotations

from benchmark.readers import scope_named


def read(context, *, scopes: list[str], floor: str):
    floor_s = context["shapes"].get(floor)
    if floor_s is None:
        context["job"].say(f"scope_floor: the engine's shapes have no {floor!r}: the metric is left out")
        return None
    ms = scope_named.read(context, scopes=scopes, what="ms_per_step")
    if ms is None:
        return None
    context["job"].say(
        f"scope_floor: {floor} {floor_s * 1e3:.4f} ms a step against {ms:.4f} ms measured under {scopes}"
    )
    return 100.0 * floor_s * 1e3 / ms
