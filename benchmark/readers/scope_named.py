"""Device time of the round's program under NAMED ``p2pfl.*`` scopes — any
name, where ``readers/scope.py`` reads the fixed table of
``scope_reduce.SUB_SHARES``. An op counts under a scope when the scope is on
its ``op_name`` path, wherever (forward, remat's re-forward, backward), and
once however many of the asked-for scopes it sits under.

``read(context, scopes=[...], what=...)``:

- ``"ms_per_step"``  summed leaf-op time ÷ (executions × ``steps_per_program_run``),
  the denominator of ``step_ms``;
- ``"roofline"``     ``shapes["ssm_scan_floor_s_per_step"]`` — the least time the
  chip could take over one sequence-step's scans, the larger of bytes over the
  HBM peak and operations over the bf16 peak (``flops_ssm.scan_floor_seconds``)
  — over that measured time, in %. Re-forwards count in the time and not in the
  floor, so remat lowers the share, as it should.

Returns ``None`` — and says so — where no op of the traced program carries any
of the scopes (a program from before the scope existed).
"""

from __future__ import annotations

import bisect

from benchmark import scope_reduce, trace_reduce
from benchmark.readers.scope import trace_file


def by_scope(trace: dict, names: dict, runs: dict) -> tuple[dict[frozenset, int], int]:
    """(ns by the SET of scopes on an op, executions) over the round's program,
    every device; an op belongs to the execution it starts in."""
    program = trace_reduce.main_module(trace)
    by_set: dict[frozenset, int] = {}
    executions = 0
    for dev_id, dev in trace["devices"].items():
        ops = dev["ops"]
        starts = [op[3] for op in ops]
        for prog, ident, start, end in runs.get(dev_id, []):
            if prog != program:
                continue
            executions += 1
            for op in ops[bisect.bisect_left(starts, start):bisect.bisect_left(starts, end)]:
                found = frozenset(scope_reduce.scopes_of(names.get((ident, op[0])) or ""))
                if found:
                    by_set[found] = by_set.get(found, 0) + op[4]
    return by_set, executions


def reduced(context: dict) -> tuple[dict, int]:
    if "scope_named" not in context:
        path = str(trace_file(context["job"].name))
        context["scope_named"] = by_scope(context["trace"], scope_reduce.op_names(path), scope_reduce.module_runs(path))
    return context["scope_named"]


def read(context, *, scopes: list[str], what: str):
    by_set, executions = reduced(context)
    ns = sum(t for found, t in by_set.items() if found & set(scopes))
    if not ns or not executions:
        context["job"].say(f"scope_named: NOTHING under {scopes} in the traced program: the metric is left out")
        return None
    seconds_per_step = ns / 1e9 / (executions * context["shapes"]["steps_per_program_run"])
    if what == "ms_per_step":
        return seconds_per_step * 1e3
    if what == "roofline":
        floor = context["shapes"].get("ssm_scan_floor_s_per_step")
        if floor is None:
            return None
        context["job"].say(
            f"scope_named: scans' floor {floor * 1e3:.4f} ms a step (shapes: {context['shapes'].get('ssm_scan_min_bytes')} "
            f"bytes a layer) against {seconds_per_step * 1e3:.4f} ms measured under {scopes}"
        )
        return 100.0 * floor / seconds_per_step
    raise ValueError(f"unknown scope_named reading {what!r}")
