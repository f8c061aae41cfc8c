"""SPMD federation tests on the 8-device virtual CPU mesh (SURVEY §4 note:
``xla_force_host_platform_device_count`` replaces "multi-node without a
cluster")."""

import jax
import numpy as np
import pytest

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.models import mlp
from p2pfl_tpu.parallel import SpmdFederation, federation_mesh
from p2pfl_tpu.parallel.spmd import spmd_round  # noqa: F401


def _dataset(n_train=2048, n_test=512):
    return FederatedDataset.synthetic_mnist(n_train=n_train, n_test=n_test)


def test_mesh_shapes():
    mesh = federation_mesh()
    assert mesh.devices.size == len(jax.devices())
    # fewer slots than devices needs an explicit device subset — bare
    # n_nodes used to silently strand the trailing devices (ISSUE 10
    # satellite: the node-folding edge case now raises, pinned in
    # tests/test_submesh.py)
    mesh2 = federation_mesh(n_nodes=4, devices=jax.devices()[:4])
    assert mesh2.shape["nodes"] == 4


@pytest.mark.slow
def test_spmd_federation_learns():
    fed = SpmdFederation.from_dataset(
        mlp(), _dataset(), n_nodes=8, batch_size=64, vote=False
    )
    before = fed.evaluate()["test_acc"]
    fed.run(rounds=3, epochs=1)
    after = fed.evaluate()["test_acc"]
    assert after > before
    assert after > 0.9  # synthetic task is easy


@pytest.mark.slow
def test_spmd_nodes_all_equal_after_round():
    """Diffusion: after a round every node holds the same aggregated model."""
    fed = SpmdFederation.from_dataset(mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False)
    fed.run_round()
    p0 = jax.tree.leaves(fed.node_params(0))
    p3 = jax.tree.leaves(fed.node_params(3))
    for a, b in zip(p0, p3):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6)


def test_spmd_vote_masks_nodes():
    """With TRAIN_SET_SIZE < N, only elected nodes contribute."""
    from p2pfl_tpu.settings import Settings

    Settings.TRAIN_SET_SIZE = 2
    fed = SpmdFederation.from_dataset(mlp(), _dataset(), n_nodes=4, batch_size=64, vote=True)
    fed.run_round()
    assert int(fed.train_mask.sum()) == 2


def test_spmd_keep_opt_state():
    """Optimizer-moment carry-over across rounds (improvement knob) runs."""
    fed = SpmdFederation.from_dataset(
        mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False, keep_opt_state=True
    )
    fed.run(rounds=2)
    assert fed.round == 2 and fed.evaluate()["test_acc"] > 0.9


def test_spmd_nondivisible_node_count():
    """5 nodes on 8 devices: folds onto a smaller mesh, still works."""
    fed = SpmdFederation.from_dataset(mlp(), _dataset(), n_nodes=5, batch_size=32, vote=False)
    fed.run_round()
    assert fed.round == 1


@pytest.mark.parametrize("agg", ["median", "trimmed_mean", "krum"])
def test_spmd_robust_aggregators_resist_byzantine(agg):
    """A poisoned node (garbage weights) must not destroy the aggregate."""
    fed = SpmdFederation.from_dataset(
        mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False, aggregator=agg, trim=1
    )
    # poison node 0's params with huge noise
    poisoned = jax.tree.map(
        lambda x: x.at[0].set(jax.random.normal(jax.random.PRNGKey(0), x.shape[1:]) * 100.0),
        fed.params,
    )
    fed.params = poisoned
    fed.run_round()
    acc = fed.evaluate()["test_acc"]
    assert acc > 0.5  # fedavg would collapse to ~0.1 here


@pytest.mark.slow
def test_spmd_robust_agg_with_partial_mask_trains():
    """Regression (ADVICE r1 high): with TRAIN_SET_SIZE < N, robust
    aggregators must see elected rows only — stale non-elected copies
    would otherwise dominate the coordinate-wise median and freeze training."""
    from p2pfl_tpu.settings import Settings

    Settings.TRAIN_SET_SIZE = 4
    fed = SpmdFederation.from_dataset(
        mlp(), _dataset(), n_nodes=8, batch_size=64, vote=True, aggregator="median"
    )
    before = [np.asarray(x, np.float32) for x in jax.tree.leaves(fed.node_params(0))]
    fed.run(rounds=3)
    after = [np.asarray(x, np.float32) for x in jax.tree.leaves(fed.node_params(0))]
    delta = max(float(np.max(np.abs(a - b))) for a, b in zip(before, after))
    assert delta > 0.0, "aggregate never moved — robust agg saw stale slots"
    assert fed.evaluate()["test_acc"] > 0.5


def test_spmd_trimmed_mean_trim_clamped():
    """Regression (ADVICE r1): 2*trim >= K must clamp, not produce NaN params."""
    fed = SpmdFederation.from_dataset(
        mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False,
        aggregator="trimmed_mean", trim=3,
    )
    fed.run_round()
    assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in jax.tree.leaves(fed.params))


@pytest.mark.slow
def test_spmd_unequal_shards_sample_weighting():
    """Regression (ADVICE r1): unequal shards shuffle over their OWN sample
    range (not the truncated min), so FedAvg's sample-count weights match the
    data each node actually trains on."""
    data = _dataset()
    shards = [data.partition(i, 4, strategy="dirichlet", alpha=0.3) for i in range(4)]
    sizes = [s.num_samples for s in shards]
    assert len(set(sizes)) > 1, "dirichlet partition should produce unequal shards"
    fed = SpmdFederation(mlp(), shards, batch_size=16, vote=False)
    assert fed._tr_size == max(sizes)
    perm = fed._make_perm_np(epochs=1)
    for i, size in enumerate(sizes):
        assert perm[i].max() < size  # indices stay inside the node's own shard
    fed.run(rounds=2)
    assert fed.evaluate()["test_acc"] > 0.5


@pytest.mark.slow
def test_spmd_matches_node_mode_fedavg():
    """SPMD round == Node-mode round semantics: FedAvg of locally-trained models.

    Both paths start from identical params and see identical data; with
    epochs=0-style no-op training removed, we instead verify the aggregate
    equals the hand-computed weighted mean of per-node trained params.
    """
    from p2pfl_tpu.learning.learner import adam
    from p2pfl_tpu.ops.tree import tree_stack, tree_weighted_mean

    model = mlp()
    data = _dataset(n_train=1024)
    shards = [data.partition(i, 2) for i in range(2)]
    fed = SpmdFederation(model, shards, batch_size=64, vote=False, seed=7)

    # replay: train each node independently with the same shuffles
    rng = np.random.default_rng(7)
    perms = [
        rng.permutation(fed._tr_size)[: fed._nb * fed.batch_size].reshape(fed._nb, fed.batch_size)
        for _ in range(2)
    ]
    import jax.numpy as jnp

    from p2pfl_tpu.parallel.spmd import _local_epoch

    tx = adam(1e-3)
    manual = []
    for i, shard in enumerate(shards):
        p = model.params
        o = tx.init(p)
        xs = jnp.asarray(shard.x_train[: fed._tr_size][perms[i]])
        ys = jnp.asarray(shard.y_train[: fed._tr_size][perms[i]])
        p, o, _ = _local_epoch(p, o, xs, ys, model.module, tx)
        manual.append(p)
    expected = tree_weighted_mean(manual, [s.num_samples for s in shards])

    fed.run_round()
    got = fed.node_params(0)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-2
        )


@pytest.mark.slow
def test_run_fused_matches_sequential_rounds():
    """R fused rounds (one dispatch) == R sequential run_round calls with
    the same RNG seed — identical math, amortized dispatch."""
    fa = SpmdFederation.from_dataset(mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False, seed=3)
    fb = SpmdFederation.from_dataset(mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False, seed=3)
    for _ in range(3):
        fa.run_round(epochs=1)
    entries = fb.run_fused(3, epochs=1, eval=True)
    assert fb.round == 3 and len(entries) == 3
    assert float(entries[-1]["test_acc"]) > 0.5
    for a, b in zip(jax.tree.leaves(fa.params), jax.tree.leaves(fb.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-5, rtol=1e-4
        )


@pytest.mark.slow
def test_run_fused_composes_with_scaffold_and_fedopt():
    fed = SpmdFederation.from_dataset(
        mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False,
        scaffold=True, optimizer="sgd", learning_rate=0.05,
        server_opt="adam", server_lr=0.01,
    )
    entries = fed.run_fused(3, epochs=1, eval=True)
    assert fed._server_t == 3
    assert float(entries[-1]["test_acc"]) > float(entries[0]["test_acc"]) or (
        float(entries[0]["test_acc"]) > 0.9
    )


def test_run_fused_rejects_per_round_election():
    from p2pfl_tpu.settings import Settings

    fed = SpmdFederation.from_dataset(mlp(), _dataset(), n_nodes=4, batch_size=64, vote=True)
    Settings.VOTE_EVERY_ROUND = True
    try:
        with pytest.raises(ValueError, match="fixed mask"):
            fed.run_fused(2)
    finally:
        Settings.VOTE_EVERY_ROUND = False


@pytest.mark.slow
def test_spmd_bulyan_survives_byzantine_noise():
    """Bulyan in the jitted round (iterated Krum + trimmed mean): 8 nodes,
    1 Byzantine slot overwritten with large noise each round — training
    still converges. K=8 satisfies N >= 4f+3 for f=1."""
    fed = SpmdFederation.from_dataset(
        mlp(), _dataset(), n_nodes=8, batch_size=64, vote=False,
        aggregator="bulyan", trim=1,
    )
    key = jax.random.PRNGKey(0)
    for _ in range(3):
        key, sub = jax.random.split(key)  # fresh garbage every round
        fed.params = jax.tree.map(
            lambda x, sub=sub: x.at[:1].set(jax.random.normal(sub, x.shape[1:], x.dtype) * 10.0),
            fed.params,
        )
        fed.run_round(epochs=1)
    assert fed.evaluate()["test_acc"] > 0.8

    with pytest.raises(ValueError, match="4f"):
        bad = SpmdFederation.from_dataset(
            mlp(), _dataset(), n_nodes=4, batch_size=64, vote=False,
            aggregator="bulyan", trim=1,
        )
        bad.run_round()


def test_spmd_deterministic_across_runs():
    """Same seed, same data → bit-identical federations after 2 rounds.

    Reproducibility is a real capability claim: per-round shuffles come
    from the host rng (seeded), initialization from the model seed, and
    XLA executes deterministically on a fixed device set.
    """
    data = _dataset(n_train=512, n_test=128)

    def run():
        fed = SpmdFederation.from_dataset(
            mlp(), data, n_nodes=4, batch_size=64, vote=True, seed=11
        )
        fed.run(rounds=2, epochs=1)
        return [np.asarray(x) for x in jax.tree.leaves(fed.params)]

    a, b = run(), run()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
