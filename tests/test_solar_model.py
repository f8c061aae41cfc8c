"""Gated delta-rule linear attention (``KDAMixer``) 3 : 1 with gated NoPE GQA in
one period, every layer an expert layer holding a share of the experts —
through ``CausalLM`` and ``SpmdLoraFederation``, against the plain reference
``benchmark/reference/solar_open2_lm.py`` (the delta rule token by token) on
seeded weights."""

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import solar_open2_lm
from p2pfl_tpu.learning.lora import _lm_forward, merge_params
from p2pfl_tpu.models.transformer import LAYER_KINDS, CausalLM, KDAMixer, sown_by_layer
from tests.shared import SOLAR_PATTERN as PATTERN, SOLAR_REF as REF, SOLAR_SEQ as SEQ, solar  # noqa: F401 (the fixture)
from tests.shared import solar_batch as batch, solar_config as config

ROOT = Path(__file__).resolve().parent.parent


def _system(module, lora, base, x, y):
    """(loss, adapter gradients, ``[B, layers, T, k]`` assignments in layer order, statistics)."""

    def loss_of(lo):
        loss, _, stats, routing = _lm_forward(lo, base, module, x, y)
        chosen = sown_by_layer(module.cfg, routing)
        return loss, (jnp.swapaxes(chosen.reshape(chosen.shape[0], *x.shape, -1), 0, 1), stats)

    (loss, (chosen, stats)), grads = jax.value_and_grad(loss_of, has_aux=True)(lora)
    return loss, grads, chosen, stats


def test_the_kda_layer_kind_and_its_tree():
    assert LAYER_KINDS["kda_experts"] == ("kda", "experts")
    cfg = config()
    x = jnp.zeros((1, SEQ, 64))
    params = KDAMixer(cfg).init(jax.random.PRNGKey(0), x)["params"]
    shapes = {k: jax.tree.map(jnp.shape, v) for k, v in params.items()}
    assert shapes["wq"]["kernel"] == shapes["wk"]["kernel"] == shapes["wv"]["kernel"] == (64, 64)
    assert shapes["wq"]["lora_a"] == (64, 4) and "lora_a" not in shapes["f_a"] and "lora_a" not in shapes["g_b"]
    assert shapes["f_a"]["kernel"] == (64, 16) and shapes["f_b"]["kernel"] == (16, 64) and shapes["b_proj"]["kernel"] == (64, 4)
    assert shapes["q_conv"] == (4, 64) and shapes["A_log"] == (4,) and shapes["dt_bias"] == (64,) and shapes["o_norm"] == (16,)


@pytest.mark.parametrize("remat", [True, False])
def test_loss_and_every_adapter_gradient_match_the_token_recurrence_reference(solar, remat):
    model, lora, base = solar
    x, y = batch()
    module = CausalLM(dataclasses.replace(model.module.cfg, remat=remat))
    with jax.default_matmul_precision("highest"):
        loss, grads, chosen, stats = _system(module, lora, base, x, y)
        assert chosen.shape == (2, 4, SEQ, 2)
        (want_loss, own), want = jax.value_and_grad(solar_open2_lm.loss_and_routing, has_aux=True)(
            lora, base, x, y, REF, lora_scale=2.0, forced=chosen
        )
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), np.sort(np.asarray(own), -1))
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(ref))) > 0, jax.tree_util.keystr(path)
        assert ck.rel_l2(got, ref) < 2e-4, jax.tree_util.keystr(path)
    names = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(lora)}
    assert sum("'wq'" in n for n in names) == 2 * 2 and any("'wg'" in n for n in names)  # GQA and KDA runs, a and b
    assert not any(w in n for n in names for w in ("f_a", "f_b", "g_a", "g_b", "b_proj", "conv", "A_log", "router"))
    share = float(stats["kda_neg_beta_share"])
    assert 0.0 < share < 1.0  # the traffic reaches beta > 1
    held = np.mean((np.asarray(chosen) >= 2) & (np.asarray(chosen) < 6))
    assert float(stats["moe_held_share"]) == pytest.approx(float(held), rel=1e-6)


def test_the_logits_match_and_beta_is_not_clamped(solar):
    """Logits against the reference; the reference with β in (0, 1)
    (``kda_allow_neg_eigval`` false) is another model, far from them — so the
    program does not clamp β."""
    model, lora, base = solar
    x, _ = batch()
    params = merge_params(base, lora)
    with jax.default_matmul_precision("highest"):
        got = model.module.apply({"params": params}, x)
        want = jnp.stack([solar_open2_lm.logits(params, tok, REF, lora_scale=2.0) for tok in x])
        clamped = jnp.stack([solar_open2_lm.logits(params, tok, dict(REF, kda_allow_neg_eigval=False), lora_scale=2.0) for tok in x])
    assert ck.rel_l2(got, want) < 1e-4 < 1e-2 < ck.rel_l2(clamped, want)


def test_the_configuration_file_states_the_published_model_and_the_share():
    """Every key of the catalog row as published but the four that are cut (one
    period, 40 held experts, an eighth of the vocabulary), the hand counts of
    ``flops_linear_moe``, and the cell on one chip."""
    import json

    from benchmark import flops_linear_moe

    cfg = json.loads((ROOT / "benchmark" / "configs" / "solar_open2_lora.json").read_text())
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    for row in [json.loads(line) for line in catalog.read_text().splitlines()] if catalog.is_file() else []:
        if row.get("name") == "Solar-Open2-250B":
            assert cfg["source"] == row["source_url"]
            assert {k for k, v in row["config"].items() if cfg[k] != v} == set(cfg["reduced"])
            assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["gqa_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, [0], 40, 24_576)
    assert cfg["share"]["router_experts"] == cfg["published"]["n_routed_experts"] == 320 and cfg["share"]["chips"] == 8
    assert solar_open2_lm.layer_kinds(cfg) == list(PATTERN)
    assert not [what for what, got, want in flops_linear_moe.hand_counts() if got != pytest.approx(want, rel=1e-12)]
    assert f"{flops_linear_moe.lora_params(cfg):,}" in cfg["assumed"]["lora.targets"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == "solar_silo4_seq4096"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("solar_open2_lora", "silo4_seq4096", 1)
