"""Federation round hot-path overhaul (ISSUE 3): SCAFFOLD fast path,
per-phase round profiling, and the SPMD secure-aggregation design pin.

The chunked overlapped-staging parity lives in ``tests/test_chunked.py``;
together these suites are the CI smoke guard for the round pipeline
(.github/workflows/round_bench.yml).
"""

import jax
import jax.numpy as jnp
import pytest

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.models import mlp
from p2pfl_tpu.parallel import SpmdFederation
from p2pfl_tpu.settings import Settings


@pytest.fixture(autouse=True)
def _restore_knobs():
    yield
    Settings.SCAFFOLD_FUSED_CI = True
    Settings.SECURE_AGGREGATION = False


def _max_diff(a, b):
    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _scaffold_fed(data, **kw):
    return SpmdFederation.from_dataset(
        mlp(), data, n_nodes=4, batch_size=64, vote=False,
        scaffold=True, optimizer="sgd", learning_rate=0.05, seed=3, **kw,
    )


def test_scaffold_fused_ci_matches_legacy():
    """The fast path derives c_i⁺ from the scan's fp32 grad mean; under
    plain SGD that is ALGEBRAICALLY identical to the legacy
    (x − y_i)/(K·η) anchor formula (option II, Karimireddy et al. 2020).
    Numerically the two differ only by fp32 rounding — the legacy formula
    divides a difference of large-magnitude params, the fused one never
    forms it — so the tolerance is rounding-scale, not algorithmic."""
    data = FederatedDataset.synthetic_mnist(n_train=512, n_test=128)

    def run(fused_ci):
        Settings.SCAFFOLD_FUSED_CI = fused_ci
        fed = _scaffold_fed(data)
        fed.run(rounds=2, epochs=2)
        return fed

    fast, legacy = run(True), run(False)
    assert _max_diff(fast.params, legacy.params) < 5e-3
    assert _max_diff(fast.c_global, legacy.c_global) < 5e-3
    assert _max_diff(fast.c_local, legacy.c_local) < 5e-3
    # and the variates actually moved off zero on both paths
    assert max(float(jnp.abs(x).max()) for x in jax.tree.leaves(fast.c_global)) > 0


def test_scaffold_fused_ci_matches_legacy_fused_span():
    """Same parity through spmd_rounds_fused (the scan-over-rounds program
    with the donated c_global/c_local carry)."""
    data = FederatedDataset.synthetic_mnist(n_train=512, n_test=128)

    def run(fused_ci):
        Settings.SCAFFOLD_FUSED_CI = fused_ci
        fed = _scaffold_fed(data)
        fed.run_fused(3, epochs=1)
        return fed

    fast, legacy = run(True), run(False)
    assert _max_diff(fast.params, legacy.params) < 5e-3
    assert _max_diff(fast.c_local, legacy.c_local) < 5e-3


def test_scaffold_fused_ci_partial_train_set_keeps_zero_variates():
    """Non-elected nodes' variates must stay exactly zero on the fast path
    too (the masked-commit logic is shared, but the fused ci⁺ flows through
    a different producer)."""
    import numpy as np

    old = Settings.TRAIN_SET_SIZE
    Settings.TRAIN_SET_SIZE = 2
    try:
        data = FederatedDataset.synthetic_mnist(n_train=512, n_test=128)
        fed = SpmdFederation.from_dataset(
            mlp(), data, n_nodes=4, batch_size=64, vote=True,
            scaffold=True, optimizer="sgd", learning_rate=0.05, seed=3,
        )
        fed.run_round(epochs=1)
        out_idx = np.flatnonzero(fed.train_mask == 0)
        assert len(out_idx) == 2
        for x in jax.tree.leaves(fed.c_local):
            assert float(jnp.abs(jnp.asarray(x)[out_idx]).max()) == 0.0
    finally:
        Settings.TRAIN_SET_SIZE = old


def test_spmd_rejects_secure_aggregation():
    """Design pin (docs/design.md, "Secure aggregation and the SPMD
    runtime"): one mesh is one trust domain — SECURE_AGGREGATION is a
    gossip-plane protocol and the SPMD runtime must refuse it loudly
    instead of silently training unmasked."""
    data = FederatedDataset.synthetic_mnist(n_train=256, n_test=64)
    Settings.SECURE_AGGREGATION = True
    with pytest.raises(ValueError, match="trust domain"):
        SpmdFederation.from_dataset(mlp(), data, n_nodes=2, batch_size=64)
    Settings.SECURE_AGGREGATION = False
    SpmdFederation.from_dataset(mlp(), data, n_nodes=2, batch_size=64)
