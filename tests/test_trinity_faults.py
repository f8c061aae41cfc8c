"""Each fault of ``benchmark/planted_faults_window.py`` turns ``correct`` false
at the rehearsal size: the cell ``trinity_silo4_seq8192`` built once as
``benchmark.rehearse`` builds it, and each fault shown by the comparison that
names its address. Five are layer or window comparisons: the engine's layer
comparisons and the window's edge run under them, as under no fault. The sandwich's
second norms and the head are reached only through a step's gradients, so those
two faults run the engine's whole check. The cell's sound whole check runs in
tier-1 once: in ``test_the_cell_rehearses`` of ``tests/test_trinity_cell.py``."""

import pytest

from benchmark import checks as ck
from benchmark.planted_faults_window import FAULTS, planted
from tests.shared import rehearsal_cell

CELL = "trinity_silo4_seq8192"
# the comparison that names the fault's address
ADDRESS = {
    "window_off_by_one": "window.edge_rel_l2", "no_gate": "full_experts.attention_out_rel_l2",
    "rotated_full": "full_experts.attention_out_rel_l2", "no_post_norm": "step.grad_rel_l2",
    "held_normalised": "full_experts.layer.worst_agreeing_token_rel", "tied_head": "step.grad_cosine",
    "float8_attention": "window.edge_rel_l2",
}
WHOLE = ("no_post_norm", "tied_head")  # addressed by the step: only the whole check reaches them


@pytest.fixture(scope="module")
def built():
    return rehearsal_cell(CELL)


@pytest.mark.parametrize("fault", ["none", *FAULTS])
def test_the_reference_check_sees_the_fault(built, fault):
    job, engine, state, tokens = built
    job.checks = ck.Checks()
    module = state["module"]
    with planted(fault):
        if fault in WHOLE:
            engine.check(job, state)
        else:
            engine.check_layers(job, module, state["model"].params, tokens)
            engine.check_window_edge(job, module.cfg, module.attn_fn, tokens.shape[1])
    failed = [row["check"] for row in job.checks.rows if not row["ok"]]
    if fault == "none":
        assert not failed and len(job.checks.rows) == 9
        return
    assert ADDRESS[fault] in failed, failed
    if fault not in ("no_gate", "rotated_full"):  # a fault inside attention is seen by the layer's own comparison too
        assert not any(name.endswith("attention_out_rel_l2") for name in failed) or fault == "window_off_by_one"
