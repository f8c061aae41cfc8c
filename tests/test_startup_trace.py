"""What happens before the first round, recorded from inside: JAX's compile
events as ``"compile"`` spans of the flight recorder under the span that caused
them, the federation's set-up phases as ``"setup"`` spans, the dispatch that
recompiled marked, ``startup_report`` and the benchmark's reader of it.

Nothing here compares a duration with a constant: order, parentage, counts and
identities only.
"""

import itertools
import json
import time

import jax
import jax.numpy as jnp
import pytest

from p2pfl_tpu import compile_cache
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.management import telemetry as telemetry_module
from p2pfl_tpu.management.telemetry import PLANES, PROCESS_NODE, telemetry, union_ns, validate_chrome_trace
from p2pfl_tpu.models import mlp
from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
from p2pfl_tpu.parallel import SpmdFederation, SpmdLoraFederation
from p2pfl_tpu.settings import Settings


@pytest.fixture(autouse=True)
def _recorder(monkeypatch):
    """A clean recorder with the bridge on, keeping every trace however short
    (the 1 ms floor has a test of its own)."""
    monkeypatch.setattr(compile_cache, "_MIN_TRACE_NS", 0)
    compile_cache.install_compile_bridge()
    telemetry.reset()
    yield
    telemetry.reset()


def _fresh_jit(name: str):
    """A jitted function no earlier test can have compiled."""

    def fn(x):
        return jnp.tanh(x) * 3.0 + 1.0

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _compile_spans(fun_name=None):
    return [
        s for s in telemetry.spans(PROCESS_NODE)
        if s.kind == "compile" and (fun_name is None or s.attrs["fun_name"] == fun_name)
    ]


# a learning rate no federation of this process has had: the optimizer is a
# static argument of the round, so each federation's first round compiles
_RATES = (1e-3 + i * 1e-6 for i in itertools.count())


def _spmd():
    data = FederatedDataset.synthetic_mnist(n_train=256, n_test=64)
    fed = SpmdFederation.from_dataset(
        mlp(), data, n_nodes=2, batch_size=64, vote=False, seed=3, learning_rate=next(_RATES)
    )
    return fed, "spmd_round"


def _lora():
    cfg = TransformerConfig(vocab_size=128, dim=32, n_layers=1, n_heads=2, n_kv_heads=1, ffn_hidden=64)
    data = FederatedDataset.synthetic_lm(vocab_size=cfg.vocab_size, seq_len=16, n_train=64, n_test=16)
    model = tiny_transformer(seq_len=16, cfg=cfg)
    fed = SpmdLoraFederation.from_dataset(
        model, data, n_nodes=2, batch_size=8, vote=False, learning_rate=next(_RATES)
    )
    return fed, "spmd_lora_round"


ENGINES = pytest.mark.parametrize("make", [_spmd, _lora], ids=["spmd", "lora"])


# ---- the bridge ---------------------------------------------------------------


def test_bridge_installs_once_however_often_configured(monkeypatch):
    monkeypatch.setattr(jax.config, "update", lambda *a: None)  # keep the test session off the persistent cache
    anchors = len(telemetry.clock_anchors)
    for _ in range(3):
        compile_cache.configure_compile_cache()
    assert len(telemetry.clock_anchors) == anchors  # installed by the fixture already: no second anchor
    f, x = _fresh_jit("installs_once"), jnp.ones(3)
    with telemetry.span("t", "outer", kind="setup") as outer:
        f(x)
    assert [s.name for s in _compile_spans("installs_once")] == ["trace", "lower", "backend"]
    assert outer.attrs["compiled"] == 1
    assert telemetry.counters("compile", "")["backend:installs_once"] == 1
    assert telemetry.startup_report()["compile"]["backend_n"] >= 1


def test_jit_inside_a_span_compiles_under_it():
    f, x = _fresh_jit("inside_a_span"), jnp.ones(3)
    with telemetry.span("t", "outer", kind="setup") as outer:
        f(x)
    trace, lower, backend = _compile_spans("inside_a_span")
    assert (trace.name, lower.name, backend.name) == ("trace", "lower", "backend")
    for s in (trace, lower, backend):
        assert s.parent_id == outer.span_id and s.trace_id == outer.trace_id and s.node == PROCESS_NODE
    assert backend.attrs["cache"] in ("hit", "miss", "off") and "cache" not in trace.attrs
    assert trace.t1_ns <= lower.t1_ns <= backend.t1_ns  # committed at their ends, in JAX's order


def test_jit_outside_any_span_has_no_parent():
    _fresh_jit("outside_any_span")(jnp.ones(3))
    spans = _compile_spans("outside_any_span")
    assert [s.name for s in spans] == ["trace", "lower", "backend"]
    assert all(s.parent_id is None for s in spans)


def test_second_call_of_the_same_jit_adds_no_compile_span():
    f, x = _fresh_jit("called_twice"), jnp.ones(3)
    f(x)
    before = len(_compile_spans())
    with telemetry.span("t", "again", kind="setup") as again:
        f(x)
    assert len(_compile_spans()) == before and "compiled" not in again.attrs
    f(jnp.ones(5))  # another shape is another program
    assert [s.name for s in _compile_spans("called_twice")].count("backend") == 2


def test_converted_starts_lie_inside_the_enclosing_span():
    f = _fresh_jit("converted_starts")
    with telemetry.span("t", "outer", kind="setup") as outer:
        f(jnp.ones(3))
    for s in _compile_spans("converted_starts"):
        assert outer.t0_ns <= s.t0_ns <= s.t1_ns <= outer.t1_ns
    # a start that converts to before its parent's is held at the parent's
    with telemetry.span("t", "tight", kind="setup") as tight:
        early = telemetry.record_span(PROCESS_NODE, "backend", "compile", tight.t0_ns - 10**9, time.monotonic_ns())
    assert early.t0_ns == tight.t0_ns and early.parent_id == tight.span_id


def test_short_traces_are_counted_not_kept(monkeypatch):
    monkeypatch.setattr(compile_cache, "_MIN_TRACE_NS", 1 << 62)
    _fresh_jit("short_traces")(jnp.ones(3))
    assert [s.name for s in _compile_spans("short_traces")] == ["lower", "backend"]
    dropped = telemetry.startup_report()["compile"]
    assert dropped["short_traces_n"] >= 1 and dropped["short_trace_s"] > 0


# ---- the federation's set-up phases ------------------------------------------------


@ENGINES
def test_fed_init_holds_data_put_and_stage_state(make):
    fed, _site = make()
    setup = [s for s in telemetry.spans(PROCESS_NODE) if s.kind == "setup"]
    assert [s.name for s in setup] == ["fed_init", "data_put", "stage_state"]
    init, data, stage = setup
    assert init.parent_id is None and data.parent_id == stage.parent_id == init.span_id
    assert init.t0_ns <= data.t0_ns <= data.t1_ns <= stage.t0_ns <= stage.t1_ns <= init.t1_ns
    assert {s.attrs["fed"] for s in setup} == {id(fed)}
    assert data.attrs["nodes"] == stage.attrs["nodes"] == 2
    state = [fed.params, fed.opt_state] + ([fed.base] if hasattr(fed, "base") else [])
    assert stage.attrs["bytes"] == sum(x.nbytes for x in jax.tree.leaves(state))
    assert data.attrs["bytes"] >= fed.x_all.nbytes + fed.y_all.nbytes
    # the state's placing program compiled under stage_state, and says so
    staged = [s for s in _compile_spans("stage") if s.name == "backend"]
    assert len(staged) == 1 and staged[0].parent_id == stage.span_id and stage.attrs["compiled"] >= 1


@ENGINES
def test_first_round_is_marked_and_a_steady_one_is_not(make):
    fed, site = make()
    for _ in range(3):
        fed.run_round(epochs=1)
    first, *steady = [s for s in telemetry.spans("spmd") if s.kind == "dispatch"]
    assert first.name == site and first.attrs["compiled"] >= 1 and first.attrs["fed"] == id(fed)
    assert all("compiled" not in s.attrs for s in steady)
    assert telemetry.counters("compile", "")[f"{site}:compiled"] == 1
    backends = [s for s in _compile_spans(site) if s.name == "backend"]
    assert len(backends) == first.attrs["compiled"] or first.attrs["compiled"] > len(backends) >= 1
    assert all(s.parent_id == first.span_id for s in backends)
    assert telemetry.counters("dispatch", "") == {site: 3}  # the dispatch counts hear nothing of it


@ENGINES
def test_a_round_with_changed_epochs_is_marked(make):
    fed, site = make()
    fed.run_round(epochs=1)
    fed.run_round(epochs=1)
    fed.run_round(epochs=2)  # a static argument of the round's program: a second program
    fed.run_round(epochs=2)
    marked = ["compiled" in s.attrs for s in telemetry.spans("spmd") if s.kind == "dispatch"]
    assert marked == [True, False, True, False]
    assert telemetry.counters("compile", "")[f"{site}:compiled"] == 2
    assert telemetry.counters("compile", "")[f"backend:{site}"] == 2


def test_reset_is_a_span_around_its_stage_state():
    fed, _site = _spmd()
    telemetry.reset_spans()
    fed.reset(seed=3)
    reset, stage = [s for s in telemetry.spans(PROCESS_NODE) if s.kind == "setup"]
    assert (reset.name, stage.name) == ("reset", "stage_state") and stage.parent_id == reset.span_id
    assert reset.attrs["fed"] == stage.attrs["fed"] == id(fed)


def test_disabled_telemetry_records_nothing_and_breaks_nothing():
    Settings.TELEMETRY_ENABLED = False
    fed, site = _spmd()
    loss = float(fed.run_round(epochs=1)["train_loss"])
    fed.reset(seed=3)
    assert loss == loss and telemetry.spans() == []
    report = telemetry.startup_report()
    assert report["phases"] == [] and report["programs"] == [] and report["compile"]["all_s"] == 0
    assert telemetry.counters("dispatch", "") == {site: 1}


# ---- the report -----------------------------------------------------------------


def _started():
    fed, site = _lora()
    fed.run_round(epochs=1)
    fed.run_round(epochs=1)
    fed.reset(seed=0)
    return fed, site


def test_startup_report_self_times_and_unions():
    _fed, site = _started()
    report = telemetry.startup_report()
    by_id = {r["id"]: r for r in report["spans"]}
    names = [p["name"] for p in report["phases"]]
    assert names == ["fed_init", "data_put", "stage_state", site, "reset", "stage_state"]
    for p in report["phases"]:
        row = by_id[p["id"]]
        kids = [(r["t0_ns"], r["t1_ns"]) for r in report["spans"] if r["parent"] == p["id"]]
        assert all(row["t0_ns"] <= a <= b <= row["t1_ns"] for a, b in kids)
        assert p["self_s"] * 1e9 + union_ns(kids) == pytest.approx(p["duration_s"] * 1e9, abs=1)
        assert 0 <= p["compile_s"] <= p["duration_s"]
    c = report["compile"]
    assert c["all_s"] <= c["trace_s"] + c["lower_s"] + c["backend_s"] + 1e-9
    assert max(c["in_program_s"], c["outside_s"]) <= c["all_s"] <= c["in_program_s"] + c["outside_s"] + 1e-9
    per_fun = telemetry.counters("compile", "")
    assert c["backend_n"] == sum(n for name, n in per_fun.items() if name.startswith("backend:"))
    table = {row["fun_name"]: row for row in report["programs"]}
    assert table[site]["backend_n"] == 1 and table[site]["parents"] == [site]
    assert table["stage"]["backend_n"] == 2 and table["stage"]["parents"] == ["stage_state"]
    totals = [row["total_s"] for row in report["programs"]]
    assert totals == sorted(totals, reverse=True)
    assert report["process_start_ns"] is None or report["process_start_ns"] < report["spans"][0]["t0_ns"]
    json.dumps(report)


def test_startup_report_clips_at_until_and_since():
    _started()
    spans = telemetry.spans(PROCESS_NODE)
    init = next(s for s in spans if s.name == "fed_init")
    cut = (init.t0_ns + init.t1_ns) // 2
    early = telemetry.startup_report(until_ns=cut)
    assert all(r["t0_ns"] <= r["t1_ns"] <= cut for r in early["spans"])
    assert [p["name"] for p in early["phases"]][0] == "fed_init" and "reset" not in [p["name"] for p in early["phases"]]
    assert early["phases"][0]["duration_s"] * 1e9 == pytest.approx(cut - init.t0_ns, abs=1)
    late = telemetry.startup_report(since_ns=cut)
    assert all(cut <= r["t0_ns"] <= r["t1_ns"] for r in late["spans"])
    whole = telemetry.startup_report()
    assert len(early["spans"]) + len(late["spans"]) >= len(whole["spans"])  # a span across the cut is in both, cut
    assert early["compile"]["all_s"] + late["compile"]["all_s"] == pytest.approx(whole["compile"]["all_s"], abs=1e-6)


def test_chrome_trace_has_the_two_new_lanes(tmp_path):
    _started()
    doc = telemetry.export_chrome_trace(path=str(tmp_path / "trace.json"))
    assert validate_chrome_trace(doc) == validate_chrome_trace(json.loads((tmp_path / "trace.json").read_text()))
    pid = next(e["pid"] for e in doc["traceEvents"] if e["name"] == "process_name" and e["args"]["name"] == PROCESS_NODE)
    lanes = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"] if e["name"] == "thread_name" and e["pid"] == pid}
    assert lanes == {"setup": PLANES["setup"], "compile": PLANES["compile"]}
    compiled = [e for e in doc["traceEvents"] if e.get("cat") == "compile" and e["name"] == "backend"]
    assert compiled and all("fun_name" in e["args"] and "parent_span_id" in e["args"] for e in compiled if e["args"]["fun_name"] == "stage")
    mono, real = doc["otherData"]["clock_anchors_mono_ns_time_ns"][-1]
    assert abs((time.time_ns() - real) - (time.monotonic_ns() - mono)) < 5 * 10**9  # the pair was read together


def test_flight_record_dump_holds_the_startup_report(tmp_path):
    _started()
    paths = telemetry_module.dump_flight_record(str(tmp_path))
    assert str(tmp_path / "startup_report.json") in paths
    report = json.loads((tmp_path / "startup_report.json").read_text())
    assert {"process_start_ns", "phases", "programs", "compile", "spans", "clock_anchors"} <= set(report)


# ---- the benchmark's reader ---------------------------------------------------------


def _row(id_, parent, kind, name, t0, t1, **attrs):
    return {"id": id_, "parent": parent, "kind": kind, "name": name, "t0_ns": t0 * 10**9, "t1_ns": t1 * 10**9, "attrs": attrs}


def _recorded_report():
    """A start written out by hand, seconds on a clock whose zero is the
    process start: a harness jit (no span), the check's reduced federation
    (fed 1) with one round, then the window's (fed 2) with two, a reset, and
    the window at 100."""
    rows = [
        _row("h", None, "compile", "backend", 2, 6, fun_name="init", cache="hit"),
        _row("i1", None, "setup", "fed_init", 10, 12, fed=1),
        _row("s1", "i1", "setup", "stage_state", 10, 12, fed=1, nodes=2),
        _row("c1", "s1", "compile", "backend", 11, 12, fun_name="stage", cache="hit"),
        _row("d1", None, "dispatch", "the_round", 12, 20, fed=1, nodes=2, compiled=1),
        _row("t1", "d1", "compile", "trace", 12, 16, fun_name="the_round"),
        _row("b1", "d1", "compile", "backend", 16, 20, fun_name="the_round", cache="hit"),
        _row("i2", None, "setup", "fed_init", 40, 46, fed=2),
        _row("s2", "i2", "setup", "stage_state", 41, 45, fed=2, nodes=4),
        _row("c2", "s2", "compile", "backend", 42, 44, fun_name="stage", cache="hit"),
        _row("d2", None, "dispatch", "the_round", 50, 70, fed=2, nodes=4, compiled=1),
        _row("t2", "d2", "compile", "trace", 50, 60, fun_name="the_round"),
        _row("n2", "d2", "compile", "trace", 52, 54, fun_name="inner"),  # traced inside the round's trace
        _row("l2", "d2", "compile", "lower", 60, 63, fun_name="the_round"),
        _row("b2", "d2", "compile", "backend", 63, 70, fun_name="the_round", cache="hit"),
        _row("d3", None, "dispatch", "the_round", 80, 85, fed=2, nodes=4, compiled=1),
        _row("b3", "d3", "compile", "backend", 81, 85, fun_name="the_round", cache="hit"),
        _row("r2", None, "setup", "reset", 90, 93, fed=2),
        _row("s3", "r2", "setup", "stage_state", 90, 93, fed=2, nodes=4),
        _row("c3", "s3", "compile", "backend", 91, 92, fun_name="stage", cache="hit"),
    ]
    phases = [
        {"name": "fed_init", "kind": "setup", "id": "i1", "t0_ns": 10 * 10**9, "duration_s": 2.0, "compile_s": 1.0, "attrs": {"fed": 1}},
        {"name": "fed_init", "kind": "setup", "id": "i2", "t0_ns": 40 * 10**9, "duration_s": 6.0, "compile_s": 2.0, "attrs": {"fed": 2}},
        {"name": "stage_state", "kind": "setup", "id": "s2", "t0_ns": 41 * 10**9, "duration_s": 4.0, "compile_s": 2.0, "attrs": {"fed": 2}},
        {"name": "reset", "kind": "setup", "id": "r2", "t0_ns": 90 * 10**9, "duration_s": 3.0, "compile_s": 1.0, "attrs": {"fed": 2}},
    ]
    return {"process_start_ns": 0, "until_ns": 100 * 10**9, "spans": rows, "phases": phases}


def test_reader_six_fields_on_a_recorded_report():
    from benchmark.readers import startup

    got = startup.reduce(_recorded_report(), "the_round")
    assert got["fed"] == 2
    assert got["round_trace_lower_s"] == 13.0  # 50-60 and 60-63; the check's 12-16 is not the window's
    assert got["round_backend_s"] == 11.0  # 63-70 and 81-85
    assert got["round_compiles_n"] == 2
    assert got["setup_small_s"] == 4.0  # stage 11-12, 42-44, 91-92; `inner` lies inside the round's trace; `init` is under no span
    assert got["stage_s"] == 6.0  # fed_init 6 - 2 and reset 3 - 1, the window's federation's only
    assert got["program_s"] == 2 + 8 + 6 + 20 + 5 + 3
    assert got["setup_outside_s"] == 100.0 - 44.0 and got["setup_s"] == 100.0
    # the warm-up, first dispatch to reset (50-90), less the two dispatches in it: what the harness's fetch waited
    assert got["warm_s"] == 40.0 and got["warm_wait_s"] == 15.0
    assert [r["id"] for r in got["check_round"]] == ["t1", "b1"]
    # the engine that builds the window's federation BEFORE the check's: still the one that dispatched last
    report = _recorded_report()
    for r in report["spans"]:
        if "fed" in r["attrs"]:
            r["attrs"]["fed"] = 3 - r["attrs"]["fed"]
    for p in report["phases"]:
        p["attrs"]["fed"] = 3 - p["attrs"]["fed"]
    assert startup.reduce(report, "the_round")["fed"] == 1


def test_reader_reads_the_live_report_and_leaves_old_programs_out(monkeypatch):
    from benchmark.readers import startup

    _fed, site = _started()
    window = {"completions": [time.monotonic()]}
    context = {"shapes": {"round_program": "jit_" + site}, "window": window}
    monkeypatch.setattr(startup, "report_lines", lambda *a: None)  # the lines need a trace and a job: the chip's
    fields = ("round_trace_lower_s", "round_backend_s", "round_compiles_n", "setup_small_s", "stage_s", "setup_outside_s")
    values = {f: startup.read(context, field=f) for f in fields}
    assert values["round_compiles_n"] == 1 and values["round_trace_lower_s"] > 0 and values["round_backend_s"] > 0
    assert values["stage_s"] > 0 and values["setup_small_s"] >= 0
    got = context["startup"]
    assert 0 <= got["warm_wait_s"] <= got["warm_s"]
    if got["setup_s"] is not None:
        assert values["setup_outside_s"] + got["program_s"] == pytest.approx(got["setup_s"])
        assert 0 < values["setup_outside_s"] < got["setup_s"]
    # a program from before this PR has no startup_report: every field is left out, nothing raises
    monkeypatch.delattr(telemetry_module.Telemetry, "startup_report")
    said = []
    old = {"shapes": context["shapes"], "window": window, "job": type("Job", (), {"say": staticmethod(said.append)})}
    assert [startup.read(old, field=f) for f in fields] == [None] * 6
    assert len(said) == 1 and "no startup_report" in said[0]
