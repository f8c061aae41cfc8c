"""Each fault module's ``planted(<fault>)`` puts back every name it patched when
its block exits, and also when the block raises. The fault cases of a cell
share one process and one built cell (``tests/test_*_faults.py``), so a patch
that leaked would poison every case after it."""

import importlib

import pytest

MODULES = ["planted_faults", "planted_faults_conv", "planted_faults_window", "planted_faults_kda"]
CASES = [(m, f) for m in MODULES for f in ("none", *importlib.import_module(f"benchmark.{m}").FAULTS)]


def _names() -> dict:
    """Every name a fault may patch: the model's module, its ``CausalLM``, the delta rule's two modules."""
    from p2pfl_tpu.models import transformer as tf
    from p2pfl_tpu.ops import delta_rule, delta_rule_kernel

    spaces = {"transformer": tf, "CausalLM": tf.CausalLM, "delta_rule": delta_rule, "delta_rule_kernel": delta_rule_kernel}
    return {(space, name): value for space, obj in spaces.items() for name, value in vars(obj).items()}


def _changed(before: dict, after: dict) -> set:
    return {key for key in before.keys() | after.keys() if before.get(key) is not after.get(key)}


@pytest.mark.parametrize("module,fault", CASES)
def test_a_planted_fault_is_taken_out_when_its_block_exits(module, fault):
    planted = importlib.import_module(f"benchmark.{module}").planted
    sound = _names()
    with planted(fault):
        assert bool(_changed(sound, _names())) == (fault != "none")  # the fault is in; no fault patches nothing
    assert not _changed(sound, _names())
    with pytest.raises(RuntimeError, match="inside the block"):
        with planted(fault):
            raise RuntimeError("inside the block")
    assert not _changed(sound, _names())
