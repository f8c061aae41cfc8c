"""Round flight recorder: spans, unified counters/histograms, Perfetto export.

The round FSM (vote → train → gossip partials → diffuse) is a distributed
causal process, but observability used to be disconnected accumulators —
``record_dispatch`` site counters, ``log_comm_metric`` tallies, ``Stopwatch``
sections, the stall watchdog — none of which could answer *which peer/edge/
stage gated this round*. This module is the Dapper-style fix (Sigelman et
al., 2010): request-scoped **spans** with trace context propagated on the
wire, so one round forms one causal tree across every node in the process.

Three layers, one registry (the module-level :data:`telemetry` singleton):

- **Spans** — ``with telemetry.span(node, name, kind=..., attrs=...)``
  records monotonic-ns start/end into a bounded per-node ring buffer
  (``Settings.TELEMETRY_RING_SPANS`` entries; old spans fall off — a flight
  recorder, not an archive). Nesting is tracked per thread; an explicit
  ``parent`` (a wire ``(trace_id, span_id)`` pair) overrides it, which is
  how a receiver's span becomes the child of the sender's.
  :meth:`Telemetry.event` records instant (zero-duration) spans — fault
  injections, breaker transitions, evictions — parented the same way.
- **Counters + histograms** — the single registry behind
  ``logger.log_comm_metric`` (group ``"comm"``) and
  ``profiling.record_dispatch`` (group ``"dispatch"``); the old accessors
  are thin views. ``snapshot_and_reset`` reads and clears atomically, so a
  bench cannot lose increments landing between a ``get_*`` and a
  ``reset_*``. Span durations auto-feed log-bucket latency histograms
  (p50/p95/p99 via :meth:`LatencyHistogram.percentile`).
- **Exports** — :meth:`Telemetry.export_chrome_trace` emits Chrome
  trace-event JSON (one ``pid`` per node, one ``tid`` per plane:
  stages/gossip/heartbeat/dispatch/retry/fault) loadable in Perfetto
  (ui.perfetto.dev → *Open trace file*); :meth:`Telemetry.round_report`
  walks the span tree of one round and attributes its wall-clock to
  stages, peers, retry/backoff waits and aggregation-wait burn, naming the
  critical-path node/stage/edge.

Wire contract: ``Message``/``WeightsEnvelope`` carry an optional
``trace_ctx=(trace_id, parent_span_id)``; ``protocol.build_msg/build_weights``
stamp the sender's current context, the single ``_do_send`` seam wraps the
transport send in a span, and the receive dispatch opens the receiver's span
with the wire context as parent. A frame without the field decodes exactly
as before (old wire format stays valid).

Setting ``P2PFL_TELEMETRY_DUMP=<dir>`` dumps ``trace.json`` + per-round
``round_reports.json`` + ``startup_report.json`` at process exit — CI uploads
these as artifacts when a chaos run fails, so every failure is self-explaining.

**What happens before the first round** is recorded on the same registry, in a
ring of its own (node :data:`PROCESS_NODE`, so a long run's dispatch spans
cannot push the start of the run out of the recorder): kind ``"setup"`` — a
federation's ``fed_init`` ⊃ ``data_put``, ``stage_state``, and ``reset``
(``profiling.setup_span``) — and kind ``"compile"`` — one span per JAX
compile-path event, ``trace`` / ``lower`` / ``backend`` with the program's
``fun_name``, committed by ``compile_cache``'s bridge under the span that was
open on the compiling thread (:meth:`Telemetry.record_span`). Neither kind
emits a profiler annotation. :meth:`Telemetry.startup_report` reduces them.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from p2pfl_tpu.settings import Settings

TraceCtx = Tuple[str, str]  # (trace_id, span_id)

#: span kinds → Chrome trace ``tid`` (one timeline lane per plane)
PLANES: Dict[str, int] = {
    "stage": 1,
    "gossip": 2,
    "heartbeat": 3,
    "dispatch": 4,
    "retry": 5,
    "fault": 6,
    "setup": 7,
    "compile": 8,
}
_OTHER_PLANE = 9

#: the ring (and Chrome-trace process) of set-up and compile spans
PROCESS_NODE = "process"
#: the kinds whose spans are the program's own work, as opposed to JAX's
#: compile-path events committed under them
_PROGRAM_KINDS = ("setup", "dispatch")

#: the round FSM's top-level stage names — RoundReport attributes per-stage
#: time from these only, so nested sub-spans (aggregation_wait, diffusion)
#: never double-count into the stage split
FSM_STAGES = (
    "StartLearningStage",
    "VoteTrainSetStage",
    "TrainStage",
    "WaitAggregatedModelsStage",
    "GossipModelStage",
    "RoundFinishedStage",
)

_seq = itertools.count(1)
# per-process entropy in every id: trace ids are DELIBERATELY identical
# across all nodes of a round (the coordination-free cross-node trace), so
# a bare sequential span id would collide when flight records from
# separate gRPC node PROCESSES are merged into one timeline
_proc_tag = f"{os.getpid():x}-{os.urandom(3).hex()}"


def _new_id(prefix: str = "s") -> str:
    return f"{prefix}{_proc_tag}-{next(_seq):x}"



def process_start_ns() -> Optional[int]:
    """The kernel's record of when this process began, on
    ``time.monotonic_ns``'s clock: ``/proc/self/stat`` field 22 (clock ticks
    after boot) against ``CLOCK_BOOTTIME`` now. ``None`` where there is no
    ``/proc`` to read."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        began_ns = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        age_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - began_ns
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic_ns() - age_ns


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Length of the union of ``(start, end)`` intervals: nested and
    overlapping spans (a jit traced inside another's trace) count once."""
    total, edge = 0, None
    for start, end in sorted(intervals):
        if edge is None or start > edge:
            total += end - start
            edge = end
        elif end > edge:
            total += end - edge
            edge = end
    return total


class Span:
    """One recorded operation: [t0_ns, t1_ns) on one node, one plane."""

    __slots__ = (
        "trace_id",
        "span_id",
        "parent_id",
        "node",
        "name",
        "kind",
        "t0_ns",
        "t1_ns",
        "attrs",
    )

    def __init__(
        self,
        node: str,
        name: str,
        kind: str,
        trace_id: str,
        parent_id: Optional[str],
        attrs: Optional[dict],
    ) -> None:
        self.node = node
        self.name = name
        self.kind = kind
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.t0_ns = time.monotonic_ns()
        self.t1_ns = self.t0_ns
        self.attrs: dict = attrs if attrs is not None else {}

    @property
    def duration_ns(self) -> int:
        return self.t1_ns - self.t0_ns

    @property
    def ctx(self) -> TraceCtx:
        return (self.trace_id, self.span_id)

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "node": self.node,
            "name": self.name,
            "kind": self.kind,
            "t0_ns": self.t0_ns,
            "t1_ns": self.t1_ns,
            "attrs": dict(self.attrs),
        }


class _SpanHandle:
    """Context manager returned by :meth:`Telemetry.span` (enabled path)."""

    __slots__ = ("_registry", "span")

    def __init__(self, registry: "Telemetry", span: Span) -> None:
        self._registry = registry
        self.span = span

    def __enter__(self) -> Span:
        self._registry._push(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        span = self.span
        span.t1_ns = time.monotonic_ns()
        if exc_type is not None:
            span.attrs.setdefault("error", repr(exc))
        self._registry._pop(span)
        self._registry._commit(span)
        return False


class _NoopHandle:
    """Shared do-nothing handle — the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOOP = _NoopHandle()


class LatencyHistogram:
    """Log2-bucketed latency histogram (thread-safe).

    Buckets are powers of two in nanoseconds (bucket ``i`` holds samples
    with ``bit_length == i``), so 60 buckets cover 1 ns → 36 years with
    ≤2× quantile error — the standard trade for lock-cheap histograms.
    """

    __slots__ = ("_lock", "counts", "count", "sum_ns", "max_ns")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counts: Dict[int, int] = {}
        self.count = 0
        self.sum_ns = 0
        self.max_ns = 0

    def record(self, ns: int) -> None:
        ns = max(int(ns), 0)
        bucket = ns.bit_length()
        with self._lock:
            self.counts[bucket] = self.counts.get(bucket, 0) + 1
            self.count += 1
            self.sum_ns += ns
            if ns > self.max_ns:
                self.max_ns = ns

    def percentile(self, q: float) -> float:
        """Approximate q-th percentile in ns (geometric bucket midpoint)."""
        with self._lock:
            if self.count == 0:
                return 0.0
            target = q / 100.0 * self.count
            seen = 0
            for bucket in sorted(self.counts):
                seen += self.counts[bucket]
                if seen >= target:
                    lo = 0 if bucket <= 1 else 1 << (bucket - 1)
                    hi = (1 << bucket) - 1 if bucket > 0 else 0
                    return (lo + hi) / 2.0
            return float(self.max_ns)

    def summary(self) -> dict:
        with self._lock:
            count, sum_ns = self.count, self.sum_ns
        if count == 0:
            return {"count": 0}
        return {
            "count": count,
            "total_s": round(sum_ns / 1e9, 6),
            "mean_ms": round(sum_ns / count / 1e6, 4),
            "p50_ms": round(self.percentile(50) / 1e6, 4),
            "p95_ms": round(self.percentile(95) / 1e6, 4),
            "p99_ms": round(self.percentile(99) / 1e6, 4),
            "max_ms": round(self.max_ns / 1e6, 4),
        }


class ValueHistogram:
    """Exact counts over small non-negative integer values (thread-safe).

    The unit-agnostic sibling of :class:`LatencyHistogram` for quantities
    with a naturally tiny support — e.g. the async federation's staleness
    τ in *model versions* (0, 1, 2, …, bounded by
    ``Settings.ASYNC_MAX_STALENESS``) — where log2 latency buckets would
    both blur the distribution and mislabel the units as time.
    """

    __slots__ = ("_lock", "counts", "count", "total")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counts: Dict[int, int] = {}
        self.count = 0
        self.total = 0

    def record(self, value: int) -> None:
        value = max(int(value), 0)
        with self._lock:
            self.counts[value] = self.counts.get(value, 0) + 1
            self.count += 1
            self.total += value

    def summary(self) -> dict:
        with self._lock:
            if self.count == 0:
                return {"count": 0}
            values = sorted(self.counts)
            cum, p50, p95 = 0, values[-1], values[-1]
            for v in values:
                cum += self.counts[v]
                if p50 == values[-1] and cum >= 0.50 * self.count:
                    p50 = v
                if cum >= 0.95 * self.count:
                    p95 = v
                    break
            return {
                "count": self.count,
                "mean": round(self.total / self.count, 4),
                "p50": p50,
                "p95": p95,
                "max": values[-1],
                "counts": {str(v): self.counts[v] for v in values},
            }


class Telemetry:
    """Process-wide registry. Use the module-level :data:`telemetry`."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # node → bounded ring of completed spans (append is atomic; the
        # lock only guards ring creation so writers never serialize)
        self._rings: Dict[str, deque] = {}
        # group → node → name → value (group "comm" backs
        # logger.get_comm_metrics, "dispatch" backs get_dispatch_counts)
        self._counters: Dict[str, Dict[str, Dict[str, float]]] = {}
        # (node, name) → LatencyHistogram (span durations auto-feed these)
        self._hists: Dict[Tuple[str, str], LatencyHistogram] = {}
        # (node, name) → ValueHistogram (e.g. async staleness per merge)
        self._value_hists: Dict[Tuple[str, str], ValueHistogram] = {}
        # (monotonic_ns, time_ns) pairs: spans are on the monotonic clock, a
        # profiler capture on the realtime clock (:meth:`anchor_clock`)
        self.clock_anchors: List[Tuple[int, int]] = []
        self._tls = threading.local()

    # ---- span API ----

    @staticmethod
    def enabled() -> bool:
        return bool(Settings.TELEMETRY_ENABLED)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        elif span in stack:  # defensive: exits out of order never corrupt
            stack.remove(span)

    def _ring(self, node: str) -> deque:
        ring = self._rings.get(node)
        if ring is None:
            with self._lock:
                ring = self._rings.setdefault(
                    node, deque(maxlen=max(int(Settings.TELEMETRY_RING_SPANS), 1))
                )
        return ring

    def _commit(self, span: Span) -> None:
        self._ring(span.node).append(span)
        self.observe(span.node, f"{span.kind}.{span.name}", span.duration_ns)

    def span(
        self,
        node: str,
        name: str,
        kind: str = "stage",
        attrs: Optional[dict] = None,
        parent: Optional[TraceCtx] = None,
        trace_id: Optional[str] = None,
    ):
        """Open a span. ``parent`` is an explicit wire ``(trace_id,
        span_id)`` (overrides this thread's current span); ``trace_id``
        forces the trace identity (the workflow pins one deterministic id
        per round so every node's round tree shares it). Returns a context
        manager yielding the live :class:`Span` (attrs may be mutated
        until exit) — or a no-op handle when telemetry is off."""
        if not self.enabled():
            return _NOOP
        tid, parent_id = parent if parent is not None else self._parent_ctx()
        if trace_id is not None:
            tid = trace_id
        return _SpanHandle(self, Span(node, name, kind, tid, parent_id, attrs))

    def event(
        self,
        node: str,
        name: str,
        kind: str = "fault",
        attrs: Optional[dict] = None,
    ) -> None:
        """Record an instant (zero-duration) span — breaker transitions,
        fault-plan decisions, evictions. Parented to this thread's current
        span when one is active, so a fault injected inside a send shows
        up on that edge's timeline."""
        if not self.enabled():
            return
        span = Span(node, name, kind, *self._parent_ctx(), attrs)
        self._ring(node).append(span)

    def record_span(
        self,
        node: str,
        name: str,
        kind: str,
        t0_ns: int,
        t1_ns: int,
        attrs: Optional[dict] = None,
    ) -> Optional[Span]:
        """Commit a span that has already ended — one whose start is known
        only at its end, as JAX reports a compilation — under this thread's
        current span. ``t0_ns`` / ``t1_ns`` are on ``time.monotonic_ns``'s
        clock; a start converted from another clock is held inside the
        parent's interval. Returns the span, or ``None`` with telemetry off."""
        if not self.enabled():
            return None
        span = Span(node, name, kind, *self._parent_ctx(), attrs)
        top = self.current_span()
        span.t0_ns = t0_ns if top is None else max(t0_ns, top.t0_ns)
        span.t1_ns = max(t1_ns, span.t0_ns)
        self._commit(span)
        return span

    def current_span(self) -> Optional[Span]:
        """The calling thread's innermost open span (its ``attrs`` may be
        added to until it exits), or ``None``."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def _parent_ctx(self) -> Tuple[str, Optional[str]]:
        """``(trace_id, parent_span_id)`` for a span opened now on this
        thread: the current span's, or a fresh trace with no parent."""
        top = self.current_span()
        return (top.trace_id, top.span_id) if top is not None else (_new_id("t"), None)

    def current_ctx(self) -> Optional[TraceCtx]:
        """The calling thread's active ``(trace_id, span_id)`` — what
        ``build_msg``/``build_weights`` stamp onto outgoing envelopes."""
        top = self.current_span()
        return top.ctx if top is not None else None

    def anchor_clock(self) -> Tuple[int, int]:
        """Record and return one ``(monotonic_ns, time_ns)`` pair, read back
        to back: what lays a flight record (monotonic) on a profiler capture
        (realtime). Taken when the compile bridge is installed and at every
        :meth:`export_chrome_trace`."""
        pair = (time.monotonic_ns(), time.time_ns())
        self.clock_anchors.append(pair)
        return pair

    def spans(self, node: Optional[str] = None) -> List[Span]:
        """Snapshot of the recorded spans (all nodes, or one)."""
        with self._lock:
            rings = [self._rings[node]] if node in self._rings else []
            if node is None:
                rings = list(self._rings.values())
        out: List[Span] = []
        for ring in rings:
            out.extend(list(ring))
        out.sort(key=lambda s: s.t0_ns)
        return out

    def reset_spans(self) -> None:
        with self._lock:
            self._rings.clear()

    # ---- counters (the one registry behind comm metrics + dispatch counts) ----

    def inc(self, group: str, node: str, name: str, value: float = 1.0) -> None:
        with self._lock:
            per_node = self._counters.setdefault(group, {}).setdefault(node, {})
            per_node[name] = per_node.get(name, 0.0) + value

    def counters(self, group: str, node: Optional[str] = None) -> Dict:
        """Snapshot: ``{name: value}`` for one node, or ``{node: {...}}``."""
        with self._lock:
            g = self._counters.get(group, {})
            if node is not None:
                return dict(g.get(node, {}))
            return {n: dict(d) for n, d in g.items()}

    def reset_counters(self, group: str) -> None:
        with self._lock:
            self._counters.pop(group, None)

    def snapshot_and_reset(self, group: str, node: Optional[str] = None) -> Dict:
        """Atomically read *and clear* a counter group (or one node's slice)
        under one lock hold — increments landing between a ``get`` and a
        ``reset`` can no longer be lost."""
        with self._lock:
            g = self._counters.get(group)
            if g is None:
                return {}
            if node is not None:
                return dict(g.pop(node, {}))
            self._counters.pop(group, None)
            return {n: dict(d) for n, d in g.items()}

    # ---- histograms ----

    def observe(self, node: str, name: str, ns: int) -> None:
        if not self.enabled():
            return
        key = (node, name)
        hist = self._hists.get(key)
        if hist is None:
            with self._lock:
                hist = self._hists.setdefault(key, LatencyHistogram())
        hist.record(ns)

    def histograms(self, node: Optional[str] = None) -> Dict[str, dict]:
        """``{name: {count, mean_ms, p50_ms, p95_ms, p99_ms, ...}}`` —
        one node's, or all nodes' keyed ``node/name``."""
        with self._lock:
            items = list(self._hists.items())
        out: Dict[str, dict] = {}
        for (n, name), hist in items:
            if node is not None:
                if n == node:
                    out[name] = hist.summary()
            else:
                out[f"{n}/{name}"] = hist.summary()
        return out

    def observe_value(self, node: str, name: str, value: int) -> None:
        """Record a raw (unit-agnostic, small non-negative integer) sample
        into a :class:`ValueHistogram` — always on, like counters: the
        async staleness distribution is load-bearing for tests/benches."""
        key = (node, name)
        hist = self._value_hists.get(key)
        if hist is None:
            with self._lock:
                hist = self._value_hists.setdefault(key, ValueHistogram())
        hist.record(value)

    def value_histograms(self, node: Optional[str] = None) -> Dict[str, dict]:
        """Like :meth:`histograms` but for the raw-value family."""
        with self._lock:
            items = list(self._value_hists.items())
        out: Dict[str, dict] = {}
        for (n, name), hist in items:
            if node is not None:
                if n == node:
                    out[name] = hist.summary()
            else:
                out[f"{n}/{name}"] = hist.summary()
        return out

    def reset_histograms(self) -> None:
        with self._lock:
            self._hists.clear()
            self._value_hists.clear()

    def reset(self) -> None:
        """Full wipe: spans, every counter group, histograms."""
        with self._lock:
            self._rings.clear()
            self._counters.clear()
            self._hists.clear()
            self._value_hists.clear()

    # ---- Chrome trace-event export (Perfetto-loadable) ----

    def export_chrome_trace(
        self, path: Optional[str] = None, nodes: Optional[List[str]] = None
    ) -> dict:
        """Chrome trace-event JSON: one ``pid`` per node, one ``tid`` per
        plane, ``X`` complete events for spans, ``i`` instants for events.
        Open at ui.perfetto.dev (or chrome://tracing). Returns the document;
        also writes it to ``path`` when given."""
        spans = self.spans()
        if nodes is not None:
            wanted = set(nodes)
            spans = [s for s in spans if s.node in wanted]
        pid_of = {n: i + 1 for i, n in enumerate(sorted({s.node for s in spans}))}
        events: List[dict] = []
        for node, pid in pid_of.items():
            events.append(
                {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                 "args": {"name": node}}
            )
        named_lanes = set()
        for s in spans:
            pid = pid_of[s.node]
            tid = PLANES.get(s.kind, _OTHER_PLANE)
            if (pid, tid) not in named_lanes:
                named_lanes.add((pid, tid))
                events.append(
                    {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                     "args": {"name": s.kind}}
                )
            args = {k: v for k, v in s.attrs.items() if v is not None}
            args["trace_id"] = s.trace_id
            args["span_id"] = s.span_id
            if s.parent_id:
                args["parent_span_id"] = s.parent_id
            base = {
                "name": s.name,
                "pid": pid,
                "tid": tid,
                "ts": s.t0_ns / 1000.0,  # trace-event timestamps are µs
                "args": args,
                "cat": s.kind,
            }
            if s.duration_ns > 0:
                base["ph"] = "X"
                base["dur"] = s.duration_ns / 1000.0
            else:
                base["ph"] = "i"
                base["s"] = "t"  # thread-scoped instant
            events.append(base)
        # timestamps are monotonic; the anchors say what realtime that was
        self.anchor_clock()
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"clock_anchors_mono_ns_time_ns": [list(pair) for pair in self.clock_anchors]},
        }
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    # ---- per-round attribution ----

    def round_report(
        self, round_no: int, experiment: Optional[str] = None
    ) -> "RoundReport":
        """Walk the span tree of one round and say what gated it.

        Stage spans carry ``attrs={"round", "experiment"}`` (stamped by the
        workflow); everything else — gossip sends, retry events, faults —
        is attributed by falling inside the round's time window. The
        critical path names (a) the node whose round wall-clock is
        longest, (b) its longest stage, and (c) the edge that burned the
        most send time + retry backoff (+ a failure ranking that surfaces
        crashed peers, whose sends fail *fast* but repeatedly)."""
        spans = self.spans()
        stage_spans = [
            s
            for s in spans
            if s.kind == "stage"
            and s.attrs.get("round") == round_no
            and (experiment is None or s.attrs.get("experiment") == experiment)
        ]
        per_node: Dict[str, dict] = {}
        for s in stage_spans:
            info = per_node.setdefault(
                s.node, {"t0_ns": s.t0_ns, "t1_ns": s.t1_ns, "stages": {}, "waits": {}}
            )
            info["t0_ns"] = min(info["t0_ns"], s.t0_ns)
            info["t1_ns"] = max(info["t1_ns"], s.t1_ns)
            bucket = "stages" if s.name in FSM_STAGES else "waits"
            info[bucket][s.name] = info[bucket].get(s.name, 0) + s.duration_ns
        if not per_node:
            return RoundReport(round_no=round_no, experiment=experiment)
        if experiment is None:
            experiment = next(
                (s.attrs.get("experiment") for s in stage_spans if s.attrs.get("experiment")),
                None,
            )

        w0 = min(i["t0_ns"] for i in per_node.values())
        w1 = max(i["t1_ns"] for i in per_node.values())

        edges: Dict[Tuple[str, str], dict] = {}
        retry_wait: Dict[str, float] = {}
        faults: Dict[str, int] = {}
        for s in spans:
            if s.t0_ns > w1 or s.t1_ns < w0:
                continue
            if s.kind == "gossip" and s.name.startswith("send:"):
                peer = s.attrs.get("peer")
                if peer is None:
                    continue
                e = edges.setdefault(
                    (s.node, peer), {"busy_ns": 0, "sends": 0, "failures": 0}
                )
                e["busy_ns"] += s.duration_ns
                e["sends"] += 1
                if s.attrs.get("ok") is False:
                    e["failures"] += 1
            elif s.kind == "retry":
                peer = s.attrs.get("peer")
                if peer is not None:
                    retry_wait[peer] = retry_wait.get(peer, 0.0) + float(
                        s.attrs.get("delay_s", 0.0)
                    )
            elif s.kind == "fault":
                faults[s.name] = faults.get(s.name, 0) + 1

        critical_node = max(per_node, key=lambda n: per_node[n]["t1_ns"] - per_node[n]["t0_ns"])
        crit = per_node[critical_node]
        critical_stage = (
            max(crit["stages"], key=crit["stages"].get) if crit["stages"] else None
        )
        critical_edge = None
        if edges:
            src, dst = max(
                edges,
                key=lambda e: edges[e]["busy_ns"] + retry_wait.get(e[1], 0.0) * 1e9,
            )
            e = edges[(src, dst)]
            # same units as the edges table below (seconds) — one document,
            # one unit, whichever entry a consumer reads
            critical_edge = {
                "src": src,
                "dst": dst,
                "busy_s": round(e["busy_ns"] / 1e9, 4),
                "sends": e["sends"],
                "failures": e["failures"],
                "retry_wait_s": round(retry_wait.get(dst, 0.0), 4),
            }
        most_failed_peer = None
        fail_by_dst: Dict[str, int] = {}
        for (_src, dst), e in edges.items():
            fail_by_dst[dst] = fail_by_dst.get(dst, 0) + e["failures"]
        for peer in retry_wait:
            fail_by_dst.setdefault(peer, 0)
        if fail_by_dst and max(fail_by_dst.values()) > 0:
            most_failed_peer = max(fail_by_dst, key=fail_by_dst.get)

        return RoundReport(
            round_no=round_no,
            experiment=experiment,
            wall_s=round((w1 - w0) / 1e9, 4),
            per_node={
                n: {
                    "wall_s": round((i["t1_ns"] - i["t0_ns"]) / 1e9, 4),
                    "stages_s": {k: round(v / 1e9, 4) for k, v in i["stages"].items()},
                    "waits_s": {k: round(v / 1e9, 4) for k, v in i["waits"].items()},
                }
                for n, i in per_node.items()
            },
            edges={
                f"{src}->{dst}": {
                    "busy_s": round(e["busy_ns"] / 1e9, 4),
                    "sends": e["sends"],
                    "failures": e["failures"],
                }
                for (src, dst), e in edges.items()
            },
            retry_wait_s={k: round(v, 4) for k, v in retry_wait.items()},
            faults=faults,
            critical_node=critical_node,
            critical_stage=critical_stage,
            critical_edge=critical_edge,
            most_failed_peer=most_failed_peer,
        )

    def observed_rounds(self) -> List[Tuple[Optional[str], int]]:
        """Distinct ``(experiment, round)`` pairs with stage spans — what
        the at-exit dump iterates."""
        seen = set()
        for s in self.spans():
            if s.kind == "stage" and isinstance(s.attrs.get("round"), int):
                seen.add((s.attrs.get("experiment"), s.attrs["round"]))
        return sorted(seen, key=lambda er: (er[0] or "", er[1]))


    # ---- what happened before the first round ----

    def startup_report(
        self, since_ns: Optional[int] = None, until_ns: Optional[int] = None
    ) -> dict:
        """The set-up, dispatch and compile spans that lie in ``[since_ns,
        until_ns]`` (monotonic ns; spans across an edge are cut at it),
        reduced. Seconds are floats, unrounded.

        - ``process_start_ns``: :func:`process_start_ns`;
        - ``phases``: every set-up span, and every dispatch span under which
          something compiled, in start order — ``duration_s``, ``self_s``
          (duration less what its child spans cover) and ``compile_s`` (the
          union of the compile spans anywhere under it);
        - ``programs``: one row a ``fun_name`` — times traced and brought to
          the backend, ``trace_s`` / ``lower_s`` / ``backend_s`` summed, the
          persistent cache's answers, the spans it compiled under — costliest
          first;
        - ``compile``: the unions — ``all_s``, ``in_program_s`` (under a
          program span) and ``outside_s`` (under none: a jit the caller made
          outside every span) — beside the per-kind sums and counts, and the
          traces the bridge dropped as too short to keep (``short_traces_n``,
          ``short_trace_s``: counted since the process started, not cut at the
          edges — what the unions can be short of);
        - ``spans``: the rows themselves, for a reader that wants another cut.
        """
        lo = since_ns if since_ns is not None else -(1 << 62)
        hi = until_ns if until_ns is not None else 1 << 62
        rows = []
        for sp in self.spans():
            if sp.kind != "compile" and sp.kind not in _PROGRAM_KINDS:
                continue
            t0, t1 = max(sp.t0_ns, lo), min(sp.t1_ns, hi)
            if t1 < t0:
                continue
            rows.append({
                "id": sp.span_id, "parent": sp.parent_id, "kind": sp.kind, "name": sp.name,
                "t0_ns": t0, "t1_ns": t1, "attrs": dict(sp.attrs),
            })
        by_id = {r["id"]: r for r in rows}
        children: Dict[str, list] = {}
        under: Dict[str, list] = {}  # program span → compile rows anywhere below it
        compiles = [r for r in rows if r["kind"] == "compile"]
        for r in rows:
            if r["parent"] in by_id:
                children.setdefault(r["parent"], []).append(r)
        for r in compiles:
            up = r["parent"]
            while up in by_id:
                under.setdefault(up, []).append(r)
                up = by_id[up]["parent"]

        def within(row: dict, others: list) -> int:
            return union_ns(
                (max(o["t0_ns"], row["t0_ns"]), min(o["t1_ns"], row["t1_ns"]))
                for o in others
                if o["t1_ns"] > row["t0_ns"] and o["t0_ns"] < row["t1_ns"]
            )

        def name_of(span_id: Optional[str]) -> Optional[str]:
            return by_id[span_id]["name"] if span_id in by_id else None

        phases = []
        for r in rows:
            if r["kind"] == "setup" or (r["kind"] == "dispatch" and "compiled" in r["attrs"]):
                duration = r["t1_ns"] - r["t0_ns"]
                phases.append({
                    "name": r["name"], "kind": r["kind"], "id": r["id"], "parent": name_of(r["parent"]),
                    "t0_ns": r["t0_ns"], "duration_s": duration / 1e9,
                    "self_s": (duration - within(r, children.get(r["id"], []))) / 1e9,
                    "compile_s": within(r, under.get(r["id"], [])) / 1e9,
                    "attrs": r["attrs"],
                })

        table: Dict[str, dict] = {}
        for r in compiles:
            row = table.setdefault(str(r["attrs"].get("fun_name", "?")), {
                "traced_n": 0, "backend_n": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                "cache": set(), "parents": set(),
            })
            row[r["name"] + "_s"] += (r["t1_ns"] - r["t0_ns"]) / 1e9
            row["parents"].add(name_of(r["parent"]) or "")
            if r["name"] == "trace":
                row["traced_n"] += 1
            elif r["name"] == "backend":
                row["backend_n"] += 1
                row["cache"].add(r["attrs"].get("cache", "off"))
        programs = [
            {
                "fun_name": fun, **row, "cache": "+".join(sorted(row["cache"])),
                "parents": sorted(row["parents"]),
                "total_s": row["trace_s"] + row["lower_s"] + row["backend_s"],
            }
            for fun, row in table.items()
        ]
        programs.sort(key=lambda row: row["total_s"], reverse=True)

        def spans_of(picked: list) -> list:
            return [(r["t0_ns"], r["t1_ns"]) for r in picked]

        inside = [r for r in compiles if r["parent"] is not None]
        backends = [r for r in compiles if r["name"] == "backend"]
        summary = {
            "all_s": union_ns(spans_of(compiles)) / 1e9,
            "in_program_s": union_ns(spans_of(inside)) / 1e9,
            "outside_s": union_ns(spans_of([r for r in compiles if r["parent"] is None])) / 1e9,
            "backend_n": len(backends),
            "cache_hits": sum(r["attrs"].get("cache") == "hit" for r in backends),
            "cache_misses": sum(r["attrs"].get("cache") == "miss" for r in backends),
        }
        dropped = self.counters("compile", "")
        summary["short_traces_n"] = int(dropped.get("short_traces", 0))
        summary["short_trace_s"] = float(dropped.get("short_trace_s", 0.0))
        for kind in ("trace", "lower", "backend"):
            summary[kind + "_s"] = sum(r["t1_ns"] - r["t0_ns"] for r in compiles if r["name"] == kind) / 1e9
        return {
            "process_start_ns": process_start_ns(),
            "since_ns": since_ns,
            "until_ns": until_ns,
            "clock_anchors": [list(pair) for pair in self.clock_anchors],
            "phases": phases,
            "programs": programs,
            "compile": summary,
            "spans": rows,
        }


class RoundReport:
    """One round's wall-clock attribution (see :meth:`Telemetry.round_report`)."""

    def __init__(
        self,
        round_no: int,
        experiment: Optional[str] = None,
        wall_s: float = 0.0,
        per_node: Optional[dict] = None,
        edges: Optional[dict] = None,
        retry_wait_s: Optional[dict] = None,
        faults: Optional[dict] = None,
        critical_node: Optional[str] = None,
        critical_stage: Optional[str] = None,
        critical_edge: Optional[dict] = None,
        most_failed_peer: Optional[str] = None,
    ) -> None:
        self.round_no = round_no
        self.experiment = experiment
        self.wall_s = wall_s
        self.per_node = per_node or {}
        self.edges = edges or {}
        self.retry_wait_s = retry_wait_s or {}
        self.faults = faults or {}
        self.critical_node = critical_node
        self.critical_stage = critical_stage
        self.critical_edge = critical_edge
        self.most_failed_peer = most_failed_peer

    def to_dict(self) -> dict:
        return {
            "round": self.round_no,
            "experiment": self.experiment,
            "wall_s": self.wall_s,
            "per_node": self.per_node,
            "edges": self.edges,
            "retry_wait_s": self.retry_wait_s,
            "faults": self.faults,
            "critical_path": {
                "node": self.critical_node,
                "stage": self.critical_stage,
                "edge": self.critical_edge,
                "most_failed_peer": self.most_failed_peer,
            },
        }

    def describe(self) -> str:
        """One-paragraph human summary — what a failed chaos run prints."""
        if not self.per_node:
            return f"round {self.round_no}: no spans recorded"
        lines = [
            f"round {self.round_no} ({self.experiment or 'unknown-exp'}): "
            f"wall {self.wall_s:.2f}s across {len(self.per_node)} node(s)"
        ]
        if self.critical_node is not None:
            node = self.per_node[self.critical_node]
            lines.append(
                f"  critical node: {self.critical_node} "
                f"({node['wall_s']:.2f}s, longest stage: {self.critical_stage})"
            )
        if self.critical_edge is not None:
            e = self.critical_edge
            lines.append(
                f"  critical edge: {e['src']}->{e['dst']} "
                f"({e['busy_s']:.2f}s busy, {e['failures']} failure(s))"
            )
        if self.most_failed_peer is not None:
            lines.append(f"  most-failed peer: {self.most_failed_peer}")
        if self.faults:
            lines.append(f"  injected faults: {self.faults}")
        return "\n".join(lines)


def validate_chrome_trace(doc: dict) -> int:
    """Structural check against the Chrome trace-event schema; returns the
    event count or raises ``ValueError`` (used by tests and the CI smoke)."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("missing traceEvents")
    events = doc["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        ph = ev.get("ph")
        if ph not in ("X", "i", "M", "B", "E"):
            raise ValueError(f"event {i}: unknown ph {ph!r}")
        for key in ("name", "pid", "tid"):
            if key not in ev:
                raise ValueError(f"event {i}: missing {key}")
        if ph == "X":
            if not isinstance(ev.get("ts"), (int, float)) or not isinstance(
                ev.get("dur"), (int, float)
            ):
                raise ValueError(f"event {i}: X event needs numeric ts/dur")
            if ev["dur"] < 0:
                raise ValueError(f"event {i}: negative dur")
        elif ph == "i":
            if not isinstance(ev.get("ts"), (int, float)):
                raise ValueError(f"event {i}: instant needs numeric ts")
    json.dumps(doc)  # must be serializable as-is
    return len(events)


#: the process-wide registry
telemetry = Telemetry()


# ---- at-exit flight-recorder dump (chaos CI artifact) ----


def dump_flight_record(out_dir: str) -> List[str]:
    """Write ``trace.json``, ``round_reports.json`` and
    ``startup_report.json`` under ``out_dir``."""
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    trace_path = os.path.join(out_dir, "trace.json")
    telemetry.export_chrome_trace(path=trace_path)
    paths.append(trace_path)
    reports = [
        telemetry.round_report(rnd, experiment=exp).to_dict()
        for exp, rnd in telemetry.observed_rounds()
    ]
    report_path = os.path.join(out_dir, "round_reports.json")
    with open(report_path, "w") as f:
        json.dump(reports, f, indent=1)
    paths.append(report_path)
    startup_path = os.path.join(out_dir, "startup_report.json")
    with open(startup_path, "w") as f:
        json.dump(telemetry.startup_report(), f, indent=1)
    paths.append(startup_path)
    # async runs: the per-node staleness distribution (empty dict on sync
    # runs — written only when something was observed, keeping sync-mode
    # artifacts byte-stable)
    value_hists = telemetry.value_histograms()
    if value_hists:
        vh_path = os.path.join(out_dir, "value_histograms.json")
        with open(vh_path, "w") as f:
            json.dump(value_hists, f, indent=1)
        paths.append(vh_path)
    return paths


def _install_exit_dump() -> None:
    import atexit

    out_dir = os.environ.get("P2PFL_TELEMETRY_DUMP")
    if not out_dir:
        return

    def _dump() -> None:
        try:
            dump_flight_record(out_dir)
        except Exception:  # noqa: BLE001 — an exit dump must never mask the exit code
            pass

    atexit.register(_dump)


_install_exit_dump()
