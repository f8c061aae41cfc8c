"""The cell ``trinity_silo4_seq8192`` through the benchmark's scripts: it
rehearses — the one run of its whole reference check in tier-1 — and the flop
counter's script passes (the faults are ``tests/test_trinity_faults.py``'s, the
model's comparisons and the configuration file ``tests/test_trinity_model.py``'s)."""

from tests.shared import rehearse

REHEARSE_S = 540  # the rehearse case took 196 s under the suite's six workers


def test_the_cell_rehearses():
    """``benchmark.rehearse``: build, the cell's whole reference check, warm-up
    and measure at the rehearsal's sizes (the one run of that check in tier-1;
    its faults are shown where they land, ``tests/test_trinity_faults.py``)."""
    out = rehearse("trinity_silo4_seq8192", timeout=REHEARSE_S)
    assert "held share of the step's assignments" in out


def test_the_flop_counters_script_passes():
    """``python -m benchmark.flops_window_moe`` exits with ``main``'s code: 0 when
    every count meets its hand count."""
    from benchmark import flops_window_moe

    assert flops_window_moe.main() == 0
