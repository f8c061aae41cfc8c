"""What happened before the window, from the PROGRAM's own record of it: the
flight recorder's ``"setup"`` spans (``fed_init`` > ``data_put``,
``stage_state``; ``reset``), its dispatch spans, and the ``"compile"`` spans
that ``compile_cache``'s bridge commits under them for every JAX trace,
lowering and backend event (``management/telemetry.py``). All on
``time.monotonic``'s clock, the clock the window's instants are on.

``read(context, field=...)`` calls ``telemetry.startup_report(until_ns=<the
window's first instant>)`` once a run, keeps it in ``context`` and prints
``startup:`` lines (the phases, the ten costliest programs with the spans they
compiled under, the reference check's own federation apart, three identities,
and the offset between the profiler's host timeline and the anchored flight
record). Fields (``reduce``):

- ``round_trace_lower_s``  union of the ``trace`` + ``lower`` spans of the
  round's program (``shapes["round_program"]`` less ``jit_``), the window's
  federation: what a start pays again with a warm cache;
- ``round_backend_s``      its ``backend`` spans: retrieval when warm,
  compilation when cold;
- ``round_compiles_n``     how many times it reached the backend: the count
  of those ``backend`` spans (the counter ``backend:<fun_name>`` counts the
  whole process, the check's federation too, and only identity 3 reads it);
- ``setup_small_s``        union of the compile spans under a program span
  that are not the round's program, less what lies inside the round's own
  spans (a jit traced inside the round's trace is the round's tracing);
- ``stage_s``              ``fed_init`` + ``reset`` of the window's federation,
  compile spans under them taken out: the HOST's time to enqueue the placing
  of data, state and base. ``device_put`` returns when the copy is enqueued,
  so this does not grow when the copy itself gets slower: that time is waited
  for in the first round's fetch (``device wait`` below);
- ``setup_outside_s``      process start to the window's first instant, less
  the union of every program span in it (set-up, dispatch, compile spans
  under either): interpreter, imports, device start-up, the harness's weights
  and traffic, the reference's own programs, AND the waits for the device. A
  dispatch span ends at enqueue too, so the device time of the warm-up rounds
  (and of the placing before them) is waited for in the harness's fetch,
  outside every program span: it is the program's, not the harness's. The
  ``startup: outside:`` line takes it apart (``reduce``'s ``warm_wait_s``:
  from the window's federation's first dispatch to its ``reset``, or to the
  window's first instant without one, less the program spans in between), so
  a ``setup_s`` claim is sized by the two parts and not by their sum.

**The window's federation** is the one that dispatched the last round before
the window (``fed`` in the spans' attrs is the federation object's ``id``; its
``fed_init`` is the latest with that id): an engine may build the window's
federation before or after the reference check's reduced one.

Returns ``None`` — every field — where the program has no ``startup_report``
(one from before PR 35): the line then leaves the six out.
"""

from __future__ import annotations

import statistics
import sys

from benchmark import trace_reduce

PROGRAM_KINDS = ("setup", "dispatch")


def union(intervals) -> int:
    return sum(end - start for start, end in trace_reduce.merge(intervals))


def _spans(rows) -> list[tuple[int, int]]:
    return [(r["t0_ns"], r["t1_ns"]) for r in rows]


def reduce(report: dict, round_fun: str) -> dict:
    """The six fields (seconds and a count) and what the lines print, from one
    ``startup_report``. Pure: tested on a recorded report."""
    rows = report["spans"]
    by_id = {r["id"]: r for r in rows}
    compiles = [r for r in rows if r["kind"] == "compile"]
    of_round = [r for r in compiles if r["attrs"].get("fun_name") == round_fun]

    def owner(row: dict):
        """The federation a span belongs to: the nearest ``fed`` up its chain."""
        while row is not None:
            if "fed" in row["attrs"]:
                return row["attrs"]["fed"]
            row = by_id.get(row["parent"])
        return None

    dispatches = [r for r in rows if r["kind"] == "dispatch" and r["name"] == round_fun]
    fed = owner(dispatches[-1]) if dispatches else None
    inits = [r for r in rows if r["kind"] == "setup" and r["name"] == "fed_init" and r["attrs"].get("fed") == fed]
    born = inits[-1]["t0_ns"] if inits else -(1 << 62)

    def windows(row: dict) -> bool:
        return row["t0_ns"] >= born and owner(row) == fed

    mine = [r for r in of_round if windows(r)]
    backends = [r for r in mine if r["name"] == "backend"]
    small = [r for r in compiles if r["parent"] is not None and r["attrs"].get("fun_name") != round_fun]
    staged = [
        p for p in report["phases"]
        if p["kind"] == "setup" and p["name"] in ("fed_init", "reset") and p["attrs"].get("fed") == fed and p["t0_ns"] >= born
    ]
    program = [r for r in rows if r["kind"] in PROGRAM_KINDS or r["parent"] is not None]
    out = {
        "round_trace_lower_s": union(_spans(r for r in mine if r["name"] != "backend")) / 1e9,
        "round_backend_s": union(_spans(backends)) / 1e9,
        "round_compiles_n": len(backends),
        "setup_small_s": (union(_spans(small) + _spans(of_round)) - union(_spans(of_round))) / 1e9,
        "stage_s": sum(p["duration_s"] - p["compile_s"] for p in staged),
        "setup_outside_s": None,
        "warm_s": None,
        "warm_wait_s": None,
        "program_s": union(_spans(program)) / 1e9,
        "setup_s": None,
        "check_round": [r for r in of_round if not windows(r)],
        "fed": fed,
    }
    warm = [r for r in dispatches if windows(r)]
    if warm:  # the warm-up: from the window's federation's first dispatch to its reset (rows are cut at until_ns)
        resets = [p["t0_ns"] for p in staged if p["name"] == "reset"]
        lo, hi = warm[0]["t0_ns"], resets[0] if resets else max(r["t1_ns"] for r in rows)
        out["warm_s"] = (hi - lo) / 1e9
        out["warm_wait_s"] = (hi - lo - union((max(a, lo), min(b, hi)) for a, b in _spans(program) if b > lo and a < hi)) / 1e9
    if report["process_start_ns"] is not None and report["until_ns"] is not None:
        start = report["process_start_ns"]
        out["setup_s"] = (report["until_ns"] - start) / 1e9
        out["program_s"] = union((max(a, start), b) for a, b in _spans(program) if b > start) / 1e9
        out["setup_outside_s"] = out["setup_s"] - out["program_s"]
    return out


def clock_offset(context: dict, telemetry) -> None:
    """Point 4: the traced rounds' dispatches are on both timelines — a
    ``p2pfl:<site>`` annotation in the profiler's capture (nanoseconds after
    the capture's ``profile_start_time``, a realtime stamp) and a dispatch span
    of the flight recorder (monotonic), which one ``(monotonic, realtime)``
    anchor lays on realtime. Prints the difference, start against start."""
    from jax.profiler import ProfileData

    from benchmark.readers.scope import trace_file

    say = context["job"].say
    data = ProfileData.from_file(str(trace_file(context["job"].name)))
    begun = [v for plane in data.planes if plane.name == "Task Environment" for k, v in plane.stats if k == "profile_start_time"]
    if not begun:
        say("startup: clock: the capture has no profile_start_time; its host timeline cannot be anchored")
        return
    mono, real = telemetry.anchor_clock()
    hosts: dict[str, list[int]] = {}
    for name, start, _dur in context["trace"]["host"]:
        if name.startswith("p2pfl:"):
            hosts.setdefault(name[len("p2pfl:"):], []).append(int(begun[0]) + start)
    offsets, sites = [], []
    for site, stamps in hosts.items():  # round_perm and round_put are annotations only: no span of that name
        spans = [s for s in telemetry.spans() if s.kind == "dispatch" and s.name == site][-len(stamps):]
        offsets += [stamp - (s.t0_ns + real - mono) for stamp, s in zip(stamps[-len(spans):], spans)]
        sites += [site] * bool(spans)
    if not offsets:
        say("startup: clock: no p2pfl:<site> annotation of the capture has a dispatch span of that name")
        return
    say(
        f"startup: clock: profiler host timeline (profile_start_time {int(begun[0])} + event offset) minus the flight "
        f"record anchored at (monotonic {mono}, realtime {real}), over {len(offsets)} traced dispatch(es) of {sites}: "
        f"median {statistics.median(offsets) / 1e3:+.1f} us, min {min(offsets) / 1e3:+.1f}, max {max(offsets) / 1e3:+.1f} "
        "(the annotation opens inside the span, so a few us above 0 is the two being the same clock)"
    )


def report_lines(context: dict, report: dict, got: dict, round_fun: str, telemetry) -> None:
    say = context["job"].say
    origin = report["process_start_ns"] if report["process_start_ns"] is not None else report["spans"][0]["t0_ns"]
    for p in report["phases"]:
        whose = "the window's" if p["attrs"].get("fed") == got["fed"] else "another federation's (the reference check's)"
        say(
            f"startup: phase {p['name']} ({p['kind']}, under {p['parent']}; {whose}) at {(p['t0_ns'] - origin) / 1e9:.3f} s: "
            f"{p['duration_s']:.3f} s, self {p['self_s']:.3f}, compile spans under it {p['compile_s']:.3f}; "
            + ", ".join(f"{k}={v}" for k, v in p["attrs"].items() if k != "fed")
        )
    for row in report["programs"][:10]:
        say(
            f"startup: program {row['fun_name']}: traced {row['traced_n']}x, backend {row['backend_n']}x ({row['cache'] or 'none'}); "
            f"trace {row['trace_s']:.3f} + lower {row['lower_s']:.3f} + backend {row['backend_s']:.3f} = {row['total_s']:.3f} s; "
            f"under {[p or 'no span' for p in row['parents']]}"
        )
    c = report["compile"]
    say(
        f"startup: compile spans: union {c['all_s']:.3f} s = under a program span {c['in_program_s']:.3f} + under none "
        f"{c['outside_s']:.3f} (less overlap); {c['backend_n']} backend events, {c['cache_hits']} cache hits, {c['cache_misses']} written; "
        f"{c.get('short_traces_n', 0)} trace(s) under 1 ms dropped by the bridge, {c.get('short_trace_s', 0.0):.3f} s of them "
        "(since the process started: the most the unions can be short of)"
    )
    apart = got["check_round"]
    say(
        f"startup: {round_fun} outside the window's federation (the reference check's reduced one): "
        f"{sum(r['name'] == 'backend' for r in apart)} backend event(s), trace + lower "
        f"{union(_spans(r for r in apart if r['name'] != 'backend')) / 1e9:.3f} s, backend "
        f"{union(_spans(r for r in apart if r['name'] == 'backend')) / 1e9:.3f} s"
    )
    # identity 1: the three compile metrics are disjoint pieces of what the benchmark's own listener heard
    parts = got["round_trace_lower_s"] + got["round_backend_s"] + got["setup_small_s"]
    heard = context["setup_split"]["compile_s"]
    say(
        f"startup: identity compile: round_trace_lower_s + round_backend_s + setup_small_s = {parts:.3f} s against the "
        f"benchmark's compile_s {heard:.3f} ({'holds' if parts <= heard + 0.05 else 'BROKEN'}: the rest is the harness's "
        "weights, the reference and its federation's round)"
    )
    # identity 2: this reader's set-up is run.py's
    harness = next((m for m in (sys.modules.get("__main__"), sys.modules.get("benchmark.run")) if hasattr(m, "_IMPORTED_MONO")), None)
    if got["setup_s"] is not None and harness is not None:
        theirs = harness.seconds_before_import() + context["window"]["completions"][0] - harness._IMPORTED_MONO
        say(
            f"startup: identity set-up: setup_outside_s {got['setup_outside_s']:.3f} + program spans {got['program_s']:.3f} = "
            f"{got['setup_s']:.3f} s against run.py's setup_s {theirs:.3f} "
            f"({'holds' if abs(got['setup_s'] - theirs) <= 0.1 else 'BROKEN'}: to 0.1 s)"
        )
    if got["setup_outside_s"] is not None and got["warm_wait_s"] is not None:
        say(
            f"startup: outside: setup_outside_s {got['setup_outside_s']:.3f} s = device wait {got['warm_wait_s']:.3f} (the warm-up, "
            f"{got['warm_s']:.3f} s from the window's federation's first dispatch to its reset, less the program spans in it: "
            "the rounds' and the placing's device time, waited for in the harness's fetch because a dispatch span and "
            f"device_put end at enqueue; the PROGRAM's) + the rest {got['setup_outside_s'] - got['warm_wait_s']:.3f} "
            "(interpreter, imports, device start-up, the harness's weights and traffic, the reference check); "
            f"stage_s {got['stage_s']:.3f} is host enqueue time only"
        )
    # identity 3: nothing of the round's program reached the backend inside the window
    total = int(telemetry.counters("compile", "").get(f"backend:{round_fun}", 0))
    inside = total - got["round_compiles_n"] - sum(r["name"] == "backend" for r in apart)
    say(
        f"startup: identity round compiles: counter backend:{round_fun} {total} = the window's federation "
        f"{got['round_compiles_n']} + the check's {total - got['round_compiles_n'] - inside} + after the window's first instant {inside} "
        f"({'holds' if inside == 0 else 'BROKEN'}: window.compiled_nothing's sense)"
    )
    compiled = {k: int(v) for k, v in telemetry.counters("compile", "").items() if k.endswith(":compiled")}
    say(f"startup: dispatches that compiled, by site: {compiled}")
    clock_offset(context, telemetry)


def reduced(context: dict):
    if "startup" not in context:
        from p2pfl_tpu.management.telemetry import telemetry

        if not hasattr(telemetry, "startup_report"):
            context["job"].say("startup: the program has no startup_report (from before PR 35): the six metrics are left out")
            context["startup"] = None
            return None
        round_fun = context["shapes"]["round_program"].removeprefix("jit_")
        report = telemetry.startup_report(until_ns=int(context["window"]["completions"][0] * 1e9))
        context["startup"] = reduce(report, round_fun)
        report_lines(context, report, context["startup"], round_fun, telemetry)
    return context["startup"]


def read(context, *, field: str):
    got = reduced(context)
    return None if got is None else got[field]
