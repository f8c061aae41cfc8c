"""Each fault of ``benchmark/planted_faults_window.py`` turns ``correct`` false
at the rehearsal size: the cell ``trinity_silo4_seq8192`` built once as
``benchmark.rehearse`` builds it, its engine's WHOLE reference check run under
each fault (a file of its own: the cases take a worker's minutes)."""

import pytest

from benchmark import checks as ck
from benchmark import engines, run
from benchmark.planted_faults_window import FAULTS, planted

CELL = "trinity_silo4_seq8192"
# the comparison that names the fault's address, besides the step's gradients
ADDRESS = {
    "window_off_by_one": "window.edge_rel_l2", "no_gate": "full_experts.attention_out_rel_l2",
    "rotated_full": "full_experts.attention_out_rel_l2", "no_post_norm": "step.grad_rel_l2",
    "held_normalised": "full_experts.layer.worst_agreeing_token_rel", "tied_head": "step.grad_cosine",
    "float8_attention": "window.edge_rel_l2",
}


@pytest.fixture(scope="module")
def built():
    import jax

    _, cell, cfg, traffic = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), CELL, rehearsal=True)
    job = run.Job(CELL, cell, cfg, traffic, 1, False, devices=jax.devices(), checks=ck.Checks(), say=lambda msg: None)
    engine = engines.load(cell["engine"])
    return job, engine, engine.build(job)


@pytest.mark.parametrize("fault", ["none", *FAULTS])
def test_the_reference_check_sees_the_fault(built, fault):
    job, engine, state = built
    job.checks = ck.Checks()
    with planted(fault):
        engine.check(job, state)
    failed = [row["check"] for row in job.checks.rows if not row["ok"]]
    if fault == "none":
        assert not failed and len(job.checks.rows) == 21
        return
    assert ADDRESS[fault] in failed, failed
    if fault not in ("no_gate", "rotated_full"):  # a fault inside attention is seen by the layer's own comparison too
        assert not any(name.endswith("attention_out_rel_l2") for name in failed) or fault == "window_off_by_one"
