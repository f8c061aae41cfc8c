"""Reduction from a JAX profiler trace to the benchmark's device numbers.

One place, kept with the benchmark, so every PR computes the same number the
same way. What a TPU trace looks like on this installation (jax 0.9.0 /
libtpu 0.0.34, read by hand at PR 22 — the recorded example is
``fixtures/small_trace.xplane.pb``):

- one plane ``/device:TPU:<n>`` per chip. Its line ``XLA Ops`` holds one event
  per executed HLO instruction, named by the instruction's full HLO text
  (``%name = shape opcode(operands), attrs``); ``XLA Modules`` holds one event
  per program execution, named ``jit_<fn>(<fingerprint>)``. Times are
  nanoseconds from the start of the trace, on the device's clock;
- the plane ``/host:CPU`` holds host threads. ``jax.profiler.TraceAnnotation``
  spans (the program's ``p2pfl:<site>`` and the benchmark's ``bench:<what>``)
  land on the line ``python``. The host clock runs 1-2 ms ahead of the
  device's (an execution shows on the device before its enqueue shows on the
  host), so a gap is labelled by the annotation covering its midpoint and
  nothing finer than a millisecond is read from the alignment.

A Mosaic (Pallas) kernel is an ``XLA Ops`` event whose HLO text carries
``custom_call_target="tpu_custom_call"``. Control-flow instructions (``while``,
``conditional``, ``call``) enclose their bodies' events; they are dropped, so
busy time is the union of LEAF operations and a loop's bubbles count as idle.

Everything below :func:`load_xplane` works on the normalised dictionary it
returns, which is plain data (and what ``selfcheck.py`` checks by brute force).
"""

from __future__ import annotations

import re
from typing import Iterable, Optional

ENCLOSING_OPCODES = frozenset({"while", "conditional", "call"})
COLLECTIVE_OPCODES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
    "collective-broadcast",
)
HOST_PREFIXES = ("p2pfl:", "bench:")

_NAME = re.compile(r"^%(\S+) = ")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def parse_hlo(text: str) -> tuple[str, str, bool]:
    """(instruction name, opcode, is a Mosaic kernel) from an ``XLA Ops``
    event name. Text that is not an HLO line keeps its first word as both."""
    m = _NAME.match(text)
    if m is None:
        word = text.split(" ", 1)[0]
        return word, word, False
    op = _OPCODE.search(text, m.end() - 1)
    opcode = op.group(1) if op else "unknown"
    return m.group(1), opcode, 'custom_call_target="tpu_custom_call"' in text


def describe_hlo(text: str) -> str:
    """A label for the breakdown that says more than ``fusion.123``: the
    instruction's name without its clone suffix, its opcode (and fusion kind),
    and its result type without layouts — ``fusion (fusion kOutput ->
    bf16[4096,14336])``. XLA numbers fusions; the result type tells which
    matrix product or convolution one is."""
    name, opcode, mosaic = parse_hlo(text)
    m = _NAME.match(text)
    result = ""
    if m is not None:
        op = _OPCODE.search(text, m.end() - 1)
        result = re.sub(r"\{[^{}]*\}", "", text[m.end():op.start() if op else m.end()]).strip()
    kind = re.search(r"kind=(k[A-Za-z]+)", text)
    what = ("mosaic " if mosaic else "") + opcode + (f" {kind.group(1)}" if kind else "")
    label = f"{re.sub(r'[.][0-9]+$', '', name)} ({what}" + (f" -> {result[:60]}" if result else "") + ")"
    return label


def load_xplane(path: str) -> dict:
    """Normalise one ``.xplane.pb``:

    ``{"devices": {id: {"ops": [(name, opcode, mosaic, start_ns, dur_ns, label)],
    "modules": [(name, start_ns, dur_ns)]}}, "host": [(name, start_ns,
    dur_ns)]}`` — ops are leaf operations in start order; host spans are the
    ``p2pfl:``/``bench:`` annotations of every thread.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    host: list[tuple] = []
    for plane in data.planes:
        dev = _DEVICE.match(plane.name)
        if dev is not None:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name, opcode, mosaic = parse_hlo(ev.name)
                        if opcode not in ENCLOSING_OPCODES:
                            ops.append((
                                name, opcode, mosaic, int(ev.start_ns), int(ev.duration_ns),
                                describe_hlo(ev.name),
                            ))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((ev.name.split("(", 1)[0], int(ev.start_ns), int(ev.duration_ns)))
            ops.sort(key=lambda e: e[3])
            modules.sort(key=lambda e: e[1])
            devices[int(dev.group(1))] = {"ops": ops, "modules": modules}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


# ---- interval arithmetic -----------------------------------------------------


def merge(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(merged: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def length(merged: Iterable[tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


def complement(merged: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of ``[lo, hi]`` that ``merged`` (disjoint, sorted) leaves."""
    out, edge = [], lo
    for s, e in clip(merged, lo, hi):
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def subtract(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """``a`` minus ``b``, both disjoint and sorted."""
    out: list[tuple[int, int]] = []
    for s, e in a:
        out.extend(complement(b, s, e))
    return out


def op_intervals(dev: dict, keep=None) -> list[tuple[int, int]]:
    return merge((s, s + d) for n, o, m, s, d, _ in dev["ops"] if keep is None or keep(n, o, m))


# ---- the numbers -------------------------------------------------------------


def window(trace: dict) -> tuple[int, int]:
    """First device-op start to last device-op end over all devices: the
    traced window every share below is taken over."""
    starts = [d["ops"][0][3] for d in trace["devices"].values() if d["ops"]]
    ends = [max(op[3] + op[4] for op in d["ops"]) for d in trace["devices"].values() if d["ops"]]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(trace: dict) -> tuple[float, float]:
    """(busy_s, window_s): seconds in which a leaf operation ran, averaged
    over the devices in the trace, and the traced window's length."""
    lo, hi = window(trace)
    per_dev = [length(clip(op_intervals(d), lo, hi)) for d in trace["devices"].values()]
    return sum(per_dev) / len(per_dev) / 1e9, (hi - lo) / 1e9


def main_module(trace: dict) -> str:
    """The program that holds most device time: the round's program."""
    total: dict[str, int] = {}
    for dev in trace["devices"].values():
        for name, _, dur in dev["modules"]:
            total[name] = total.get(name, 0) + dur
    if not total:
        raise ValueError("the trace holds no program execution")
    return max(total, key=total.get)


def module_runs(dev: dict, name: str) -> list[tuple[int, int]]:
    return [(s, s + d) for n, s, d in dev["modules"] if n == name]


def busy_in_runs(trace: dict, name: str) -> list[float]:
    """Per execution of program ``name`` (all devices): seconds of leaf-op
    union inside it."""
    out = []
    for dev in trace["devices"].values():
        ops = op_intervals(dev)
        out.extend(length(clip(ops, s, e)) / 1e9 for s, e in module_runs(dev, name))
    return out


def gaps_between_runs(trace: dict, name: str) -> list[float]:
    """Per device and consecutive pair of executions of ``name``: idle
    seconds between the end of one and the start of the next (time there in
    which no leaf op of any program ran)."""
    out = []
    for dev in trace["devices"].values():
        ops = op_intervals(dev)
        runs = module_runs(dev, name)
        for (_, end), (start, _) in zip(runs, runs[1:]):
            out.append(length(complement(ops, end, start)) / 1e9 if start > end else 0.0)
    return out


def mosaic_seconds(trace: dict) -> tuple[float, int]:
    """(seconds, calls) of Mosaic kernels, summed over devices."""
    durs = [op[4] for dev in trace["devices"].values() for op in dev["ops"] if op[2]]
    return sum(durs) / 1e9, len(durs)


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVE_OPCODES)


def collective_seconds(trace: dict) -> tuple[float, float, int]:
    """(total, exposed, calls) per device on average: union of collective
    operations, and the part of it in which no other operation ran there."""
    total = exposed = calls = 0
    for dev in trace["devices"].values():
        coll = op_intervals(dev, lambda n, o, m: is_collective(o))
        rest = op_intervals(dev, lambda n, o, m: not is_collective(o))
        total += length(coll)
        exposed += length(subtract(coll, rest))
        calls += sum(is_collective(op[1]) for op in dev["ops"])
    n = len(trace["devices"])
    return total / n / 1e9, exposed / n / 1e9, calls


def idle_outside(trace: dict, prefix: str) -> float:
    """Share of the window in which the device ran nothing AND no host span
    whose name starts with ``prefix`` was open (averaged over devices)."""
    lo, hi = window(trace)
    spans = merge((s, s + d) for n, s, d in trace["host"] if n.startswith(prefix))
    shares = []
    for dev in trace["devices"].values():
        idle = complement(op_intervals(dev), lo, hi)
        shares.append(length(subtract(idle, spans)) / (hi - lo))
    return sum(shares) / len(shares)


def top_device_ops(trace: dict, limit: int = 10) -> list[list]:
    """``[[label, seconds], ...]``: leaf operations by summed time over the
    devices, grouped under :func:`describe_hlo`'s label (the names the trace
    prints, clone suffixes dropped, with opcode and result type)."""
    total: dict[str, int] = {}
    for dev in trace["devices"].values():
        for *_, dur, label in dev["ops"]:
            total[label] = total.get(label, 0) + dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9] for k, v in ranked]


def label_at(host: list[tuple], t: int) -> Optional[str]:
    """Innermost (latest-started) host annotation open at time ``t``."""
    best = None
    for name, start, dur in host:
        if start > t:
            break
        if start + dur >= t:
            best = name
    return best


def longest_idle_gaps(trace: dict, limit: int = 10) -> list[list]:
    """``[[label, seconds], ...]``: the longest idle gaps on the first device,
    each labelled by the host annotation covering its midpoint (``host``
    where none was open)."""
    lo, hi = window(trace)
    dev = trace["devices"][min(trace["devices"])]
    gaps = sorted(complement(op_intervals(dev), lo, hi), key=lambda g: g[0] - g[1])
    # bubbles under 10 us between back-to-back operations are not the host's
    gaps = [g for g in gaps if g[1] - g[0] >= 10_000][:limit] or gaps[:3]
    return [[label_at(trace["host"], (s + e) // 2) or "host", (e - s) / 1e9] for s, e in gaps]
