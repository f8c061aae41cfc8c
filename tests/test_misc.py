"""Profiling helpers, stage factory, ResNet smoke, 16-node overlay scale."""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_low_latency_profile_preserves_semantic_knobs():
    """The documented low-latency profile only shrinks clocks; semantic
    knobs (train-set size, TTL, stall-exit tick count, vote formula) stay
    untouched so round outcomes match the defaults."""
    from p2pfl_tpu.settings import Settings, set_low_latency_settings

    semantic_before = (
        Settings.TRAIN_SET_SIZE,
        Settings.TTL,
        Settings.GOSSIP_EXIT_ON_X_EQUAL_ROUNDS,
        Settings.VOTE_EVERY_ROUND,
        Settings.WIRE_COMPRESSION,
    )
    set_low_latency_settings()
    try:
        assert (
            Settings.TRAIN_SET_SIZE,
            Settings.TTL,
            Settings.GOSSIP_EXIT_ON_X_EQUAL_ROUNDS,
            Settings.VOTE_EVERY_ROUND,
            Settings.WIRE_COMPRESSION,
        ) == semantic_before
        assert Settings.GOSSIP_MODELS_PERIOD <= 0.1
        assert Settings.HEARTBEAT_PERIOD <= 0.5
        assert Settings.VOTE_TIMEOUT < 60.0
    finally:
        from p2pfl_tpu.settings import set_test_settings

        set_test_settings()


def test_stopwatch_sections():
    from p2pfl_tpu.management.profiling import Stopwatch

    sw = Stopwatch()
    with sw.section("a"):
        time.sleep(0.01)
    with sw.section("a"):
        time.sleep(0.01)
    s = sw.summary()
    assert s["a"]["calls"] == 2 and s["a"]["total_s"] >= 0.02


def test_profiler_trace_writes_files(tmp_path):
    from p2pfl_tpu.management.profiling import scope, trace

    d = str(tmp_path / "trace")
    with trace(d):
        with jax.profiler.TraceAnnotation("p2pfl:matmul"), scope("grad"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(x @ x)
    assert glob.glob(d + "/**/*.pb", recursive=True) or glob.glob(
        d + "/**/*.json.gz", recursive=True
    )


def test_stage_factory():
    from p2pfl_tpu.stages.stage_factory import StageFactory
    from p2pfl_tpu.stages.learning_stages import TrainStage

    assert StageFactory.get_stage("TrainStage") is TrainStage
    with pytest.raises(KeyError):
        StageFactory.get_stage("NoSuchStage")


@pytest.mark.slow
def test_resnet_forward_and_grad():
    from p2pfl_tpu.models import resnet18

    model = resnet18()
    x = jnp.ones((2, 32, 32, 3))
    logits = model.apply(model.params, x)
    assert logits.shape == (2, 10)

    def loss(p):
        return jnp.sum(model.module.apply({"params": p}, x) ** 2)

    g = jax.grad(loss)(model.params)
    assert np.isfinite(float(jax.tree.leaves(g)[0].sum()))


def test_sixteen_node_overlay():
    """Overlay scale: 16 nodes, partial topology, full federation round."""
    from p2pfl_tpu.communication.memory import MemoryRegistry
    from p2pfl_tpu.learning.learner import DummyLearner
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import Settings
    from p2pfl_tpu.utils import wait_convergence, wait_to_finish, check_equal_models

    MemoryRegistry.reset()
    Settings.TRAIN_SET_SIZE = 4
    nodes = [Node(learner=DummyLearner(value=float(i))) for i in range(16)]
    for n in nodes:
        n.start()
    # ring + chords topology (not full mesh): discovery must flood
    for i, n in enumerate(nodes):
        n.connect(nodes[(i + 1) % 16].addr)
        if i % 4 == 0:
            n.connect(nodes[(i + 7) % 16].addr)
    wait_convergence(nodes, 15, only_direct=False, wait=15)
    nodes[0].set_start_learning(rounds=1, epochs=1)
    wait_to_finish(nodes, timeout=90)
    check_equal_models(nodes, atol=1e-6)
    for n in nodes:
        n.stop()
    MemoryRegistry.reset()
