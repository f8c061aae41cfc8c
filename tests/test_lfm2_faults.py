"""A routing fault of ``benchmark/planted_faults.py`` and a convolution fault of
``benchmark/planted_faults_conv.py`` turn ``correct`` false at the rehearsal
size: the cell ``lfm2_silo4_seq4096`` built once as ``benchmark.rehearse``
builds it, its engine's layer comparisons — the first conv layer's gate products
and taps, the first expert layer of each kind — run under each. The cell's whole
check runs in tier-1 once: in ``test_the_cell_rehearses`` of
``tests/test_lfm2_model.py``."""

import pytest

from benchmark import checks as ck
from benchmark import planted_faults, planted_faults_conv
from tests.shared import rehearsal_cell

CELL = "lfm2_silo4_seq4096"
# the fault module that plants it, and the comparison that names its address
FAULTS = {
    "none": (planted_faults.planted, None),
    "choose_without_bias": (planted_faults.planted, "layer.routing_agreement"),
    "bf16_conv": (planted_faults_conv.planted, "conv.scope_rel_l2"),
}


@pytest.fixture(scope="module")
def built():
    return rehearsal_cell(CELL)


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_layer_comparisons_see_the_fault(built, fault):
    job, engine, state, tokens = built
    planted, address = FAULTS[fault]
    job.checks = ck.Checks()
    with planted(fault):
        engine.check_layers(job, state["module"], state["model"].params, tokens)
    failed = [row["check"] for row in job.checks.rows if not row["ok"]]
    if address is None:
        assert not failed and len(job.checks.rows) == 7
        return
    assert any(name.endswith(address) for name in failed), failed
