"""``python -m benchmark.selfcheck`` — the yardstick's checks of itself, and every
file ``BENCHMARK.json`` names resolving — run once for the module, as the
benchmark's command runs it: a fresh interpreter with the checkout on its path
and nothing else. Each cell whose model has a test file of its own reads its
lines: the cell's files, and the per-layer metrics it names."""

import json

import pytest

from tests.shared import ROOT, run_module

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def selfcheck():
    return run_module("benchmark.selfcheck", timeout=18)  # 4-6 s under the suite's six workers


@pytest.mark.parametrize("cell", ["jamba_silo4_seq4096", "glm_silo4_seq4096", "lfm2_silo4_seq4096", "trinity_silo4_seq8192"])
def test_the_cells_files_resolve(selfcheck, cell):
    assert selfcheck.returncode == 0, selfcheck.stdout[-2000:] + selfcheck.stderr[-2000:]
    lines = selfcheck.stdout.splitlines()
    assert sum(line.startswith(f"ok: cell {cell}: ") for line in lines) == 3  # file, config and traffic, metrics
    cells = {w["name"] for w in BENCH["workloads"]}
    metrics = [m["name"] for m in BENCH["per_layer"] if cell in m.get("workloads", cells)]
    assert metrics
    for name in metrics:
        assert sum(line.startswith(f"ok: metric {name}: ") for line in lines) == 3, name  # file, reader, moves and cells
