"""Fused overlay round (ISSUE 6): one donated dispatch per node per round.

Pins the four contracts of the Train→Aggregate seam refactor:

- BIT PARITY: the fused program (``parallel/spmd.py fused_node_round``,
  driven by ``JaxLearner.fused_round``) matches the staged
  ``evaluate()`` + per-epoch ``fit()`` path — params, opt state, the fp32
  partial accumulator and the batch-rng stream — on a fixed seed. The
  staged path stays reachable behind ``Settings.ROUND_FUSED=False``.
- DISPATCH BUDGET: the fused round issues ≤ 2 model-plane device
  dispatches per node per round (fused program + one aggregate) where the
  staged path issues ≥ 1 + epochs + 1.
- DEVICE SEAM: the own contribution carries ``partial_acc`` and FedAvg's
  fold from it matches the restack path.
- FAILURE HYGIENE: a failed fused dispatch restores the rng stream,
  rebuilds the donated opt state and degrades to the staged path;
  ``SpmdFederation`` likewise restores rng on a failed profile and
  rebuilds donated state instead of leaving deleted arrays in the store.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.learner import JaxLearner
from p2pfl_tpu.management.profiling import (
    reset_dispatch_counts,
    snapshot_and_reset_dispatch_counts,
)
from p2pfl_tpu.models import mlp
from p2pfl_tpu.settings import Settings, wire_compression_device


def _max_diff(a, b) -> float:
    return max(
        float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _learner(seed_data, addr: str, epochs: int = 2) -> JaxLearner:
    return JaxLearner(
        mlp(seed=0), seed_data, addr=addr, batch_size=64, epochs=epochs, seed=11
    )


@pytest.fixture()
def data():
    return FederatedDataset.synthetic_mnist(n_train=512, n_test=128)


class TestFusedParity:
    def test_fused_matches_staged_bitwise(self, data):
        """Same seed → identical params, opt state, accumulator and rng."""
        staged = _learner(data, "staged")
        fused = _learner(data, "fused")

        staged_metrics = staged.evaluate()
        staged.fit()

        own = fused.fused_round()
        assert own is not None
        assert own.partial_acc is not None

        assert _max_diff(staged.params, fused.params) <= 1e-6
        assert _max_diff(staged.opt_state, fused.opt_state) <= 1e-6
        # partial accumulator == weight × trained params in fp32
        psum, wsum = own.partial_acc
        expect = jax.tree.map(
            lambda p: p.astype(jnp.float32) * float(data.num_samples), staged.params
        )
        assert _max_diff(expect, psum) <= 1e-4
        assert float(wsum) == float(data.num_samples)
        # both paths drew the same batch stream
        assert (
            staged._rng.bit_generator.state == fused._rng.bit_generator.state
        )
        # metrics parity: the stash holds what the staged path floated,
        # including the per-epoch train_loss series at fit()'s step numbers
        stash = fused.pop_round_metrics()
        assert float(stash["test_loss"]) == pytest.approx(
            staged_metrics["test_loss"], abs=1e-6
        )
        assert float(stash["test_acc"]) == pytest.approx(
            staged_metrics["test_acc"], abs=1e-6
        )
        losses, steps = stash["train_loss_series"]
        assert len(np.asarray(losses)) == fused.epochs == len(steps)
        assert steps[-1] == fused._steps_done

    def test_fold_respects_agg_dtype(self, data, monkeypatch):
        """A non-default AGG_DTYPE reaches the fused fold, not just the
        staged fedavg kernel — the accumulator is built in that dtype."""
        monkeypatch.setattr(Settings, "AGG_DTYPE", "float64")
        jax.config.update("jax_enable_x64", True)
        try:
            learner = _learner(data, "dtyped")
            own = learner.fused_round()
            assert own is not None and own.partial_acc is not None
            psum, wsum = own.partial_acc
            assert all(
                leaf.dtype == jnp.float64 for leaf in jax.tree.leaves(psum)
            )
            assert wsum.dtype == jnp.float64
        finally:
            jax.config.update("jax_enable_x64", False)

    def test_interrupt_during_batch_draw_aborts(self, data):
        """interrupt_fit() landing before the dispatch aborts the fused
        round side-effect-free (rng rewound, params untouched)."""
        learner = _learner(data, "interrupted")
        rng_before = learner._rng.bit_generator.state
        params_before = learner.params

        orig = learner.data.epoch_batches

        def draw_then_interrupt(*a, **k):
            learner.interrupt_fit()
            return orig(*a, **k)

        learner.data.epoch_batches = draw_then_interrupt
        try:
            assert learner.fused_round() is None
        finally:
            learner.data.epoch_batches = orig
        assert learner._rng.bit_generator.state == rng_before
        assert learner.params is params_before

    def test_fedavg_fold_matches_restack(self, data):
        """FedAvg from the device accumulator == FedAvg from restacked params."""
        from p2pfl_tpu.learning.aggregators.fedavg import FedAvg
        from p2pfl_tpu.learning.weights import ModelUpdate

        own_learner = _learner(data, "own")
        own = own_learner.fused_round()
        assert own is not None and own.partial_acc is not None
        peer_params = jax.tree.map(lambda p: p + 0.25, own_learner.params)
        peer = ModelUpdate(peer_params, ["peer"], 300)

        agg = FedAvg("own")
        folded = agg.aggregate([own, peer])

        plain_own = ModelUpdate(own.params, own.contributors, own.num_samples)
        restacked = agg.aggregate([plain_own, peer])
        assert _max_diff(folded.params, restacked.params) <= 1e-5
        assert folded.num_samples == restacked.num_samples
        assert folded.contributors == restacked.contributors

    def test_staged_path_reachable_behind_flag(self, data, monkeypatch):
        """ROUND_FUSED=False routes TrainStage through evaluate()+fit()."""
        calls = []
        learner = _learner(data, "flagged")
        monkeypatch.setattr(Settings, "ROUND_FUSED", False)

        orig = JaxLearner.fused_round
        monkeypatch.setattr(
            JaxLearner, "fused_round", lambda self: calls.append("x") or orig(self)
        )
        # the stage-level gate: with the flag off the learner entry point
        # must not even be consulted
        from p2pfl_tpu.node import Node
        from p2pfl_tpu.utils import wait_to_finish

        nodes = []
        full = FederatedDataset.synthetic_mnist(n_train=256, n_test=64)
        for i in range(2):
            n = Node(learner=_learner(full.partition(i, 2), f"n{i}", epochs=1))
            n.start()
            nodes.append(n)
        try:
            nodes[0].connect(nodes[1].addr)
            time.sleep(0.5)
            nodes[0].set_start_learning(rounds=1, epochs=1)
            wait_to_finish(nodes, timeout=60)
        finally:
            for n in nodes:
                n.stop()
        assert calls == []
        assert _max_diff(
            nodes[0].learner.get_parameters(), nodes[1].learner.get_parameters()
        ) <= 1e-6


class TestDispatchBudget:
    def test_fused_round_two_dispatches_vs_staged(self, data):
        """Fused: ≤ 2 model-plane dispatches/round. Staged: ≥ epochs + 2."""
        from p2pfl_tpu.learning.aggregators.fedavg import FedAvg
        from p2pfl_tpu.learning.weights import ModelUpdate

        # 5 local epochs (the flagship bench's dispatch-split config): the
        # staged path pays 1 eval + 5 train + 1 aggregate dispatches, the
        # fused path 1 program + 1 aggregate — the ≥ 3× CI guard
        epochs = 5

        def one_round(learner, agg, fused: bool):
            agg.set_nodes_to_aggregate([learner.addr, "peer"])
            own = learner.fused_round() if fused else None
            if own is None:
                learner.evaluate()
                learner.fit()
                own = learner.get_model_update()
            agg.add_model(own)
            peer = ModelUpdate(
                jax.tree.map(lambda p: p + 0.1, learner.params), ["peer"], 100
            )
            agg.add_model(peer)
            return agg.wait_and_get_aggregation(timeout=1)

        staged = _learner(data, "staged-n", epochs=epochs)
        reset_dispatch_counts()
        one_round(staged, FedAvg("staged-n"), fused=False)
        # atomic harvest (telemetry registry): read-and-clear in one lock
        # hold, so the next mode's window cannot swallow late increments
        staged_counts = snapshot_and_reset_dispatch_counts()
        staged_total = sum(staged_counts.values())
        assert staged_total >= epochs + 2, staged_counts

        fused = _learner(data, "fused-n", epochs=epochs)
        one_round(fused, FedAvg("fused-n"), fused=True)
        fused_counts = snapshot_and_reset_dispatch_counts()
        fused_total = sum(fused_counts.values())
        assert fused_total <= 2, fused_counts
        # the CI smoke guard: ≥ 3× fewer dispatches than the staged round
        assert staged_total >= 3 * fused_total, (staged_counts, fused_counts)

    def test_per_node_dispatch_comm_metric(self, data):
        from p2pfl_tpu.management.logger import logger

        learner = _learner(data, "metered")
        logger.reset_comm_metrics()
        assert learner.fused_round() is not None
        assert logger.get_comm_metrics("metered").get("device_dispatch") == 1.0


class TestFailureHygiene:
    def test_failed_fused_dispatch_degrades_to_staged(self, data, monkeypatch):
        """A dying fused dispatch must not poison opt state or the rng."""
        learner = _learner(data, "crashy")
        rng_before = learner._rng.bit_generator.state

        def boom(*a, **k):
            # simulate a dispatch that consumed its donated input
            for leaf in jax.tree.leaves(learner.opt_state):
                if isinstance(leaf, jax.Array):
                    leaf.delete()
            raise RuntimeError("XLA mid-dispatch failure")

        import p2pfl_tpu.parallel.spmd as spmd

        monkeypatch.setattr(spmd, "fused_node_round", boom)
        assert learner.fused_round() is None  # degraded, not raised
        assert learner._rng.bit_generator.state == rng_before
        # opt state was rebuilt: the staged fallback trains normally
        monkeypatch.undo()
        learner.fit()
        assert all(
            not leaf.is_deleted()
            for leaf in jax.tree.leaves(learner.opt_state)
            if isinstance(leaf, jax.Array)
        )

    def test_aborted_round_still_flushes_metrics(self, data):
        """A round that trained but dies before RoundFinishedStage must not
        drop its metrics — the workflow's exit flush publishes the stash."""
        from p2pfl_tpu.management.logger import logger
        from p2pfl_tpu.node import Node

        node = Node(learner=_learner(data, "unused-addr", epochs=1))
        node.start()
        try:

            def boom(_n, stage_name):
                if stage_name == "RoundFinishedStage":
                    raise RuntimeError("injected stage failure")

            node.stage_hooks.append(boom)
            node.set_start_learning(rounds=1, epochs=1)
            deadline = time.monotonic() + 60
            time.sleep(0.3)
            while node.learning_active() and time.monotonic() < deadline:
                time.sleep(0.1)
            assert not node.learning_active()
            per_round = logger.get_local_logs().get("experiment", {})
            found = [
                series
                for per_node in per_round.values()
                for addr, metrics in per_node.items()
                if addr == node.addr
                for name, series in metrics.items()
                if name == "train_loss"
            ]
            assert found, "aborted round's train_loss series was dropped"
        finally:
            node.stop()

    def test_spmd_failed_round_rebuilds_donated_state(self, monkeypatch):
        import p2pfl_tpu.parallel.spmd as spmd
        from p2pfl_tpu.parallel.spmd import SpmdFederation

        full = FederatedDataset.synthetic_mnist(n_train=256, n_test=64)
        fed = SpmdFederation.from_dataset(
            mlp(), full, n_nodes=2, batch_size=64, vote=False, seed=5
        )

        def boom(params, opt_state, *a, **k):
            for leaf in jax.tree.leaves((params, opt_state)):
                if isinstance(leaf, jax.Array):
                    leaf.delete()
            raise RuntimeError("mid-dispatch death after donation")

        monkeypatch.setattr(spmd, "spmd_round", boom)
        with pytest.raises(RuntimeError):
            fed.run_round()
        monkeypatch.undo()
        # the store holds live (rebuilt) buffers, not deleted ones...
        assert all(
            not leaf.is_deleted()
            for leaf in jax.tree.leaves((fed.params, fed.opt_state))
            if isinstance(leaf, jax.Array)
        )
        # ...and the federation remains usable
        entry = fed.run_round()
        assert np.isfinite(float(entry["train_loss"]))


class TestWireCompressionAutoSelect:
    def test_auto_selects_by_backend(self, monkeypatch):
        monkeypatch.setattr(Settings, "WIRE_COMPRESSION_DEVICE", None)
        # CPU backend (the test environment): host producer wins
        assert wire_compression_device() is False
        # explicit override beats the auto-select either way
        monkeypatch.setattr(Settings, "WIRE_COMPRESSION_DEVICE", True)
        assert wire_compression_device() is True
        monkeypatch.setattr(Settings, "WIRE_COMPRESSION_DEVICE", False)
        assert wire_compression_device() is False

    def test_auto_select_still_encodes_and_decodes(self, monkeypatch):
        """The resolved flag routes the codec; frames stay cross-decodable."""
        from p2pfl_tpu.learning.weights import decode_params, encode_params

        monkeypatch.setattr(Settings, "WIRE_COMPRESSION_DEVICE", None)
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        payload = encode_params(tree, compression="int8")
        flat = decode_params(payload)
        np.testing.assert_allclose(
            np.asarray(flat["w"]), np.asarray(tree["w"]), atol=0.5
        )


class TestFusedFederationE2E:
    def test_two_node_fused_round_converges(self):
        """Full overlay federation on the fused path: rounds complete, both
        nodes hold the identical aggregate, metrics flushed once per round."""
        from p2pfl_tpu.management.logger import logger
        from p2pfl_tpu.node import Node
        from p2pfl_tpu.utils import wait_to_finish

        assert Settings.ROUND_FUSED  # test-settings default
        full = FederatedDataset.synthetic_mnist(n_train=512, n_test=128)
        nodes = []
        for i in range(2):
            n = Node(learner=_learner(full.partition(i, 2), f"e2e{i}", epochs=2))
            n.start()
            nodes.append(n)
        try:
            nodes[0].connect(nodes[1].addr)
            time.sleep(0.5)
            logger.reset_comm_metrics()
            reset_dispatch_counts()
            nodes[0].set_start_learning(rounds=2, epochs=2)
            wait_to_finish(nodes, timeout=90)
            # nodes are still running here — harvest atomically so nothing
            # lands between a get and a reset
            counts = snapshot_and_reset_dispatch_counts()
            # 2 nodes × 2 rounds of fused programs, no staged train epochs
            assert counts.get("fused_round") == 4, counts
            assert counts.get("train_epoch") is None, counts
            assert _max_diff(
                nodes[0].learner.get_parameters(),
                nodes[1].learner.get_parameters(),
            ) <= 1e-6
            # batched flush happened: train_loss landed in the local store
            local = logger.get_local_logs()
            found = {
                metric
                for rounds in local.values()
                for per_node in rounds.values()
                for metrics in per_node.values()
                for metric in metrics
            }
            assert "train_loss" in found
        finally:
            for n in nodes:
                n.stop()
