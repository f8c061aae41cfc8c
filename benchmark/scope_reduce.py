"""Device time by the program's own names: the ``p2pfl.*`` scopes.

The program wraps the phases of its round in ``jax.named_scope("p2pfl.<name>")``
(``p2pfl_tpu/management/profiling.py``: ``DEVICE_SCOPES``). A scope is HLO
metadata: every instruction of the compiled round carries an ``op_name`` path
such as ``jit(spmd_lora_round)/…/p2pfl.grad/transpose(jvp())/…/dot_general``.

Where the names are in a trace (jax 0.9.0 / libtpu 0.0.34, read at PR 24): NOT
on the events — an ``XLA Ops`` event carries its HLO text without
``metadata=``, and ``jax.profiler.ProfileData`` shows nothing else. The trace
file does hold the executed programs' HLO, and ``xprof``'s ``hlo_stats`` tool
reads it: one row per instruction with ``program_id`` (the fingerprint in the
``XLA Modules`` event name, ``jit_f(<id>)``), ``hlo_op_name`` (the name
``trace_reduce.parse_hlo`` extracts) and ``tf_op_name`` (the ``op_name`` plus
``:<type>``). Instruction names are unique within one program only, hence the
key ``(program_id, instruction)``. The conversion writes a cache file
(``ALL_HOSTS.op_stats.pb``) BESIDE the trace: fine under ``benchmark/out/``;
anything that reads a fixture copies it to a scratch directory first.

Buckets PARTITION the leaf-op time of the round's program (sum of buckets ==
sum of leaf-op durations, in whole nanoseconds; :func:`by_bucket` asserts it):

- ``fwd``    under ``p2pfl.grad``, neither ``transpose(jvp`` nor ``rematted_computation``
- ``remat``  under ``p2pfl.grad`` with ``rematted_computation`` (remat's re-forward)
- ``bwd``    under ``p2pfl.grad`` with ``transpose(jvp``, without ``rematted_computation``
- ``opt``    under ``p2pfl.optimizer``
- ``fold``   under ``p2pfl.fold``
- ``unscoped`` everything else (loop counters, the scans' slices and stacking
  outside any scope, parameter copies)

Sub-shares overlap the partition and are reported beside it: ``base_cast``,
``base_matmul``, ``adapter`` (an op under ``p2pfl.<that name>``, wherever it
sits) and ``flash_fwd``, ``flash_bwd`` — the Mosaic KERNELS under that scope.
Under ``vmap`` the batching rule of ``pallas_call`` puts squeeze / slice passes
under the call's own name (2.6 % on top of the kernels in ``lora_silo4_seq4096``,
PR 24); they are summed apart (``beside_kernels_ns``) so that ``flash_fwd +
flash_bwd`` is ``flash_ms``.

A fusion carries ONE ``op_name`` — its root instruction's. A cast that XLA fuses
into the matmul that consumes it costs no pass of its own and rightly shows no
``base_cast`` time; a cast that stays a pass of its own does.
"""

from __future__ import annotations

import bisect
import json
import re
from typing import Optional

from benchmark import trace_reduce

PARTITION = ("fwd", "remat", "bwd", "opt", "fold", "unscoped")
SUB_SHARES = ("base_cast", "base_matmul", "adapter", "flash_fwd", "flash_bwd")
KERNEL_SHARES = ("flash_fwd", "flash_bwd")  # Mosaic calls only

_SCOPE = re.compile(r"p2pfl\.([a-z_]+)")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_OUTER = {"optimizer": "opt", "fold": "fold"}


def scopes_of(op_name: str) -> list[str]:
    """The ``p2pfl.*`` scopes on an ``op_name`` path, outermost first."""
    return _SCOPE.findall(op_name)


def classify(op_name: str) -> str:
    """The partition bucket of one instruction, from its ``op_name``. The
    outermost of ``p2pfl.grad`` / ``p2pfl.optimizer`` / ``p2pfl.fold`` decides."""
    for scope in scopes_of(op_name):
        if scope == "grad":
            if "rematted_computation" in op_name:
                return "remat"
            return "bwd" if "transpose(jvp" in op_name else "fwd"
        if scope in _OUTER:
            return _OUTER[scope]
    return "unscoped"


def op_names(xplane_path: str) -> dict[tuple[str, str], str]:
    """``{(program_id, instruction): op_name}`` of every instruction the trace
    timed, from xprof's ``hlo_stats``. ``xprof`` is imported here, never at
    module level: a reader is imported after the window, and set-up must not
    pay for it. No ``xprof`` or no rows is an error with a message, not an
    empty answer."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError as e:
        raise SystemExit(f"benchmark: the scope metrics need the installed 'xprof' package to read op_name metadata: {e}")
    data, _ = raw_to_tool_data.xspace_to_tool_data([xplane_path], "hlo_stats", {})
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    pid, name, tf_op = cols.index("program_id"), cols.index("hlo_op_name"), cols.index("tf_op_name")
    out = {}
    for row in table["rows"]:
        cells = [c["v"] if c else None for c in row["c"]]
        full = cells[tf_op] or ""
        out[(str(cells[pid]), cells[name])] = full.rpartition(":")[0] if ":" in full else full
    if not out:
        raise SystemExit(f"benchmark: xprof's hlo_stats has no row for {xplane_path}")
    return out


def module_runs(xplane_path: str) -> dict[int, list[tuple[str, str, int, int]]]:
    """``{device: [(program, program_id, start_ns, end_ns)]}`` from the
    ``XLA Modules`` lines — what ``trace_reduce.load_xplane`` cuts the
    fingerprint off."""
    from jax.profiler import ProfileData

    out: dict[int, list] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        dev = trace_reduce._DEVICE.match(plane.name)
        if dev is None:
            continue
        runs = out.setdefault(int(dev.group(1)), [])
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                m = _MODULE.match(ev.name)
                program, ident = (m.group(1), m.group(2)) if m else (ev.name, "")
                runs.append((program, ident, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
        runs.sort(key=lambda r: r[2])
    return out


def by_bucket(trace: dict, names: dict, runs: dict, top: int = 15) -> dict:
    """Leaf-op time of the round's program (``trace_reduce.main_module``) by
    bucket and sub-share, summed over its executions on every device.

    ``trace`` is ``trace_reduce.load_xplane``'s; ``names`` :func:`op_names`';
    ``runs`` :func:`module_runs`'. An op belongs to the execution it starts in.
    Returns nanoseconds: ``{"program", "executions", "total_ns", "buckets",
    "shares", "beside_kernels_ns", "scoped", "missing", "top": [(label,
    op_name, ns)], "starts"}`` — ``scoped`` says whether any ``p2pfl.*`` scope
    was met at all (a program from before PR 24 has none), ``missing`` counts
    ops without a row in ``names``, ``starts`` the executions' start times on
    the first device."""
    program = trace_reduce.main_module(trace)
    buckets = dict.fromkeys(PARTITION, 0)
    shares = dict.fromkeys(SUB_SHARES, 0)
    per_op: dict[tuple[str, str], int] = {}
    executions = total = missing = beside_kernels = 0
    scoped = False
    for dev_id, dev in trace["devices"].items():
        ops = dev["ops"]
        starts = [op[3] for op in ops]
        for prog, ident, start, end in runs.get(dev_id, []):
            if prog != program:
                continue
            executions += 1
            for op in ops[bisect.bisect_left(starts, start):bisect.bisect_left(starts, end)]:
                op_name = names.get((ident, op[0]))
                if op_name is None:
                    missing += 1
                    op_name = ""
                dur = op[4]
                total += dur
                buckets[classify(op_name)] += dur
                for scope in set(scopes_of(op_name)):
                    scoped = True
                    if scope in KERNEL_SHARES and not op[2]:
                        beside_kernels += dur
                    elif scope in shares:
                        shares[scope] += dur
                key = (op[5], op_name)
                per_op[key] = per_op.get(key, 0) + dur
    if sum(buckets.values()) != total:
        raise AssertionError(f"scope buckets {buckets} do not partition the program's {total} ns")
    first = min(trace["devices"])
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "program": program, "executions": executions, "total_ns": total, "buckets": buckets,
        "shares": shares, "beside_kernels_ns": beside_kernels, "scoped": scoped, "missing": missing,
        "top": [(label, op_name, ns) for (label, op_name), ns in ranked],
        "starts": [s for prog, _, s, _ in runs.get(first, []) if prog == program],
    }


def tail(op_name: str, parts: int = 4) -> str:
    """An ``op_name`` path for a printed line: from its first ``p2pfl.*`` scope
    on, or its last ``parts`` components where it has none."""
    at = op_name.find("p2pfl.")
    return op_name[at:] if at >= 0 else "/".join(op_name.split("/")[-parts:])


def reduce_file(xplane_path: str, trace: Optional[dict] = None) -> dict:
    """:func:`by_bucket` of one trace file."""
    trace = trace if trace is not None else trace_reduce.load_xplane(xplane_path)
    return by_bucket(trace, op_names(xplane_path), module_runs(xplane_path))
