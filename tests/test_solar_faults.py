"""Each fault of ``benchmark/planted_faults_kda.py`` turns ``correct`` false at
the rehearsal size: the cell ``solar_silo4_seq4096`` built once as
``benchmark.rehearse`` builds it, its engine's layer comparisons — the delta
rule alone, a KDA and the GQA mixer alone, the first expert layer — run under
each fault (the whole check, step and round included, is the chip's; the CPU
rehearsal reads the same layer comparisons first)."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import checks as ck
from benchmark import engines, run
from benchmark.planted_faults_kda import FAULTS, planted

CELL = "solar_silo4_seq4096"
# the comparison that names the fault's address
ADDRESS = {
    "beta_without_2": "kda_experts.mixer_out_rel_l2", "scalar_decay": "kda.op_out_rel_l2",
    "no_boundary_decay": "kda.op_out_rel_l2", "no_l2_norm": "kda_experts.mixer_out_rel_l2",
    "acausal_conv": "kda_experts.mixer_out_rel_l2", "ungated_norm": "kda_experts.mixer_out_rel_l2",
    "bf16_state": "kda.op_out_rel_l2",
}


@pytest.fixture(scope="module")
def built():
    _, cell, cfg, traffic = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), CELL, rehearsal=True)
    job = run.Job(CELL, cell, cfg, traffic, 1, False, devices=jax.devices(), checks=ck.Checks(), say=lambda msg: None)
    engine = engines.load(cell["engine"])
    state = engine.build(job)
    tokens = jnp.asarray(state["shards"][0]["x"][:1])
    return job, engine, state, tokens


@pytest.mark.parametrize("fault", ["none", *FAULTS])
def test_the_layer_comparisons_see_the_fault(built, fault):
    job, engine, state, tokens = built
    job.checks = ck.Checks()
    with planted(fault):
        engine.check_layers(job, state["module"], state["model"].params, tokens)
    failed = [row["check"] for row in job.checks.rows if not row["ok"]]
    if fault == "none":
        assert not failed and len(job.checks.rows) == 11
        return
    assert ADDRESS[fault] in failed, failed
    assert "full_experts.mixer_out_rel_l2" not in failed  # the GQA layer runs before any fault can reach it
