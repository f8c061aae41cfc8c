"""The delta-rule model end to end: the kernels' path in the whole model, a
federation round that carries ``kda_neg_beta_share``, and the older kinds'
programs held to the parent's text (``tests/test_solar_model.py`` holds the
model against the reference; a file of its own for the worker's minute)."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.lora import _lm_forward
from p2pfl_tpu.models.transformer import tiny_transformer
from p2pfl_tpu.parallel import SpmdLoraFederation
from tests.shared import ROOT, solar  # noqa: F401 (the fixture)
from tests.shared import solar_batch as batch, solar_config as config, window_expert_federation


def test_the_kernels_path_is_the_xla_path_in_the_model(solar, monkeypatch):
    """The model through the delta rule's Pallas kernels (interpreted; the op picks
    them by backend, so the test steers that) and through its XLA path."""
    from p2pfl_tpu.ops import delta_rule

    model, lora, base = solar
    x, y = batch(n=1)
    run = jax.jit(jax.value_and_grad(lambda lo: _lm_forward(lo, base, model.module, x, y)[0]))
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(delta_rule, "_use_kernel", lambda impl, dk, dv: True)
        got = run(lora)
        run = jax.jit(jax.value_and_grad(lambda lo: _lm_forward(lo, base, model.module, x, y)[0]))
        monkeypatch.setattr(delta_rule, "_use_kernel", lambda impl, dk, dv: False)
        want = run(lora)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    assert ck.rel_l2(got[1], want[1]) < 1e-5


def test_a_federation_round_carries_the_negative_beta_share():
    cfg = config(dtype=jnp.bfloat16, n_layers=4)
    model = tiny_transformer(seq_len=32, cfg=cfg)
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=32, n_train=16, n_test=4)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=2, batch_size=2, vote=False, node_chunk=1)
    entry = fed.run_round(epochs=1)
    assert 0.0 < float(entry["kda_neg_beta_share"]) < 1.0 and 0.0 < float(entry["moe_held_share"]) < 1.0
    assert np.isfinite(float(entry["train_loss"]))


def test_the_window_expert_round_lowers_the_parents_text():
    """The sliding / full attention expert pattern's lowered round (sandwich
    norms, gate, untied head, a held share) is the parent commit's, text for
    text: the delta-rule layer kind, its configuration fields and the
    ``kda_stats`` collection change no other kind's program."""
    want = json.loads((ROOT / "tests" / "fixtures" / "lm_round_window_parent.json").read_text())["window_experts"]
    text = window_expert_federation().lower_round(epochs=1).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) == (want["round_sha256"], want["chars"])
