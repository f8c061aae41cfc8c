"""The gated short-convolution mixer, per-head q / k norms, layer kinds as a
product of mixer and feed-forward, and "leading dense layers, then periods of
expert runs" through ``CausalLM`` and ``SpmdLoraFederation`` — against the plain
reference ``benchmark/reference/lfm2_moe_lm.py`` on seeded weights. The cell's
whole reference check runs in tier-1 once: in ``test_the_cell_rehearses``."""

import dataclasses
import hashlib
import json
import re
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import lfm2_moe_lm
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.lora import _lm_forward, merge_params, split_lora
from p2pfl_tpu.models.transformer import (
    LAYER_KINDS, Attention, CausalLM, ShortConvMixer, TransformerConfig, layer_runs, sown_by_layer, tiny_transformer,
)
from p2pfl_tpu.parallel import SpmdLoraFederation
from tests.shared import draw, expert_federation, hybrid_federation, lora_federation, perturbed_layer, rehearse

ROOT = Path(__file__).resolve().parent.parent
REHEARSE_S = 270  # the rehearse case took 93 s under the suite's six workers
SEQ = 32
LEADING = ("conv_dense", "conv_dense")
PATTERN = ("attention_experts", "conv_experts", "conv_experts", "conv_experts")
# the reference reads Hugging Face's keys: two dense conv layers, then two periods a c c c
REF = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "norm_eps": 1e-5, "rope_theta": 1e6, "conv_L_cache": 3,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2, "num_hidden_layers": 10,
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2, "routed_scaling_factor": 1.0, "vocab_size": 256,
}


def config(**kw):
    base = dict(
        vocab_size=256, dim=64, n_layers=10, n_heads=4, n_kv_heads=2, ffn_hidden=160, rope_theta=1e6,
        leading_pattern=LEADING, layer_pattern=PATTERN, qk_norm=True, lora_rank=4, lora_alpha=8.0, lora_mlp=True,
        dtype=jnp.float32, remat=True, scan_layers=True, remat_policy=None, norm_eps=1e-5, routed_experts=8,
        experts_per_token=2, expert_hidden=32, expert_tile_m=8,
    )
    base.update(kw)
    return TransformerConfig(**base)


def seeded(cfg, seed=0):
    model = tiny_transformer(seq_len=SEQ, seed=seed, cfg=cfg)
    model.params = draw(model.params, seed + 1)
    return (model, *split_lora(model.params))


def batch(seed=0, n=2):
    x = jax.random.randint(jax.random.PRNGKey(seed), (n, SEQ + 1), 0, 256)
    return x[:, :-1], x[:, 1:]


def unrolled_tree(tree: dict, periods: int = 2) -> dict:
    """The scanned tree (or its adapter half) as the unrolled model holds it:
    ``layer_<i>`` in the concatenated order, an expert layer owning bank
    ``period * count + j`` of its run's stacks."""
    out = {k: tree[k] for k in ("embed", "final_norm") if k in tree}
    layers = []
    for i, (kind, count) in enumerate(layer_runs(LEADING)):
        run = tree[f"lead{i}_{kind}"]
        layers += [run if count == 1 else jax.tree.map(lambda a: a[j], run["block"]) for j in range(count)]
    for period in range(periods):
        for i, (kind, count) in enumerate(layer_runs(PATTERN)):
            run = jax.tree.map(lambda a: a[period], tree["layers"][f"run{i}_{kind}"])
            for j in range(count):
                layer = run if count == 1 else jax.tree.map(lambda a: a[j], run["block"])
                if f"experts_w13_run{i}" in tree:
                    bank = {w: tree[f"{w}_run{i}"][period * count + j] for w in ("experts_w13", "experts_w2")}
                    layer = dict(layer, mlp=dict(layer["mlp"], **bank))
                layers.append(layer)
    out.update({f"layer_{i}": layer for i, layer in enumerate(layers)})
    return out


@pytest.fixture(scope="module")
def lfm2():
    return seeded(config())


# ---- (a) the gated short convolution ---------------------------------------------


def test_short_conv_mixer_forward_and_adapter_gradients_match_the_reference():
    cfg = config()
    layer = ShortConvMixer(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    lora, base = perturbed_layer(layer, h)
    assert base["in_proj"]["kernel"].shape == (64, 192) and base["conv_kernel"].shape == (3, 64)
    assert sorted(lora) == ["in_proj", "out_proj"] and "conv_bias" not in base  # adapters on both projections; no bias
    probe = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    ours = lambda lo: jnp.sum(layer.apply({"params": merge_params(base, lo)}, h) * probe)  # noqa: E731
    theirs = lambda lo: jnp.sum(lfm2_moe_lm.short_conv(h[0], merge_params(base, lo), REF, 2.0) * probe[0])  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(theirs)(lora)
    got, grads = jax.value_and_grad(ours)(lora)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0 and ck.rel_l2(g, w) < 1e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_sees_its_taps_and_nothing_later(taps):
    """Position ``t`` reads ``t - (K-1) .. t``: a change at ``t + 1`` leaves it
    alone, a change at ``t - (K-1)`` reaches it, one at ``t - K`` does not."""
    cfg = config(conv_taps=taps)
    layer = ShortConvMixer(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), h)["params"]
    assert params["conv_kernel"].shape == (taps, 64)
    t = 17
    out = layer.apply({"params": params}, h)

    def moved(at):
        return np.abs(np.asarray(layer.apply({"params": params}, h.at[0, at].add(1.0)) - out))[0].max(-1)

    later = moved(t + 1)
    assert not later[:t + 1].any() and later[t + 1] > 0  # nothing before t + 1 moves
    assert moved(t - (taps - 1))[t] > 0 and moved(t - taps)[t] == 0.0


# ---- (b) per-head q / k norms -----------------------------------------------------


def test_qk_normed_gqa_forward_and_adapter_gradients_match_the_reference():
    cfg = config()  # 4 query heads on 2 key/value heads of 16
    layer = Attention(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    lora, base = perturbed_layer(layer, h)
    assert base["q_norm"]["scale"].shape == base["k_norm"]["scale"].shape == (16,) and base["wk"]["kernel"].shape == (64, 32)
    probe = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    ours = lambda lo: jnp.sum(layer.apply({"params": merge_params(base, lo)}, h) * probe)  # noqa: E731
    theirs = lambda lo: jnp.sum(lfm2_moe_lm.attention(h[0], merge_params(base, lo), REF, 2.0) * probe[0])  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(theirs)(lora)
    got, grads = jax.value_and_grad(ours)(lora)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0 and ck.rel_l2(g, w) < 1e-4, jax.tree_util.keystr(path)
    plain = Attention(dataclasses.replace(cfg, qk_norm=False))
    assert "q_norm" not in plain.init(jax.random.PRNGKey(1), h)["params"]  # off: the parent's tree


# ---- (c) (d) the whole model ------------------------------------------------------


def test_layer_kinds_are_a_product_and_the_stack_is_leading_layers_then_periods():
    assert {LAYER_KINDS[k] for k in ("conv_dense", "conv_experts", "attention_experts")} == {
        ("short_conv", "mlp"), ("short_conv", "experts"), ("attention", "experts")
    }
    assert lfm2_moe_lm.layer_kinds(REF) == list(LEADING) + list(PATTERN) * 2
    assert lfm2_moe_lm.stack(REF) == (list(LEADING), list(PATTERN), 2)
    assert layer_runs(PATTERN) == lfm2_moe_lm.runs(PATTERN) == [("attention_experts", 1), ("conv_experts", 3)]
    with pytest.raises(ValueError, match="leading_pattern"):
        config(n_layers=9)  # 2 leading + 7: no whole number of periods
    with pytest.raises(ValueError, match="an expert layer belongs to the period"):
        config(leading_pattern=("conv_experts",), n_layers=9)
    assert hash(config(leading_pattern=list(LEADING))) == hash(config())  # a list is made a tuple


def _system(module, lora, base, x, y):
    """(loss, adapter gradients, ``[B, expert layers, T, k]`` assignments in layer order)."""

    def loss_of(lo):
        loss, _, _, routing = _lm_forward(lo, base, module, x, y)
        chosen = sown_by_layer(module.cfg, routing)
        return loss, jnp.swapaxes(chosen.reshape(chosen.shape[0], *x.shape, -1), 0, 1)

    (loss, chosen), grads = jax.value_and_grad(loss_of, has_aux=True)(lora)
    return loss, grads, chosen


# unrolled: the scanned case holds the model to the reference, and
# test_scanned_layers_equal_the_unrolled_layers_on_mapped_parameters the unrolled stack to the scanned one
@pytest.mark.parametrize("layers", ["scanned", pytest.param("unrolled", marks=pytest.mark.slow)])
def test_loss_and_every_adapter_gradient_match_the_reference_held_to_the_programs_assignments(lfm2, layers):
    model, lora, base = lfm2
    x, y = batch()
    if layers == "scanned":
        loss, grads, chosen = _system(model.module, lora, base, x, y)
    else:
        module = CausalLM(dataclasses.replace(model.module.cfg, scan_layers=False))
        loss, grads, chosen = _system(module, unrolled_tree(lora), unrolled_tree(base), x, y)
    assert chosen.shape == (2, 8, SEQ, 2)
    with jax.default_matmul_precision("highest"):
        (want_loss, own), want = jax.value_and_grad(lfm2_moe_lm.loss_and_routing, has_aux=True)(
            lora, base, x, y, REF, lora_scale=2.0, forced=chosen
        )
    if layers == "unrolled":
        want = unrolled_tree(want)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), np.sort(np.asarray(own), -1))  # float32 both: one choice
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(ref))) > 0, jax.tree_util.keystr(path)  # every adapter is reached
        assert ck.rel_l2(got, ref) < 2e-4, jax.tree_util.keystr(path)
    names = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(lora)}
    assert not any(word in n for n in names for word in ("experts_w", "router", "conv_kernel", "q_norm"))  # frozen base leaves


def test_scanned_layers_equal_the_unrolled_layers_on_mapped_parameters(lfm2):
    """Three scan bodies whatever the depth (the leading run, the attention-expert
    block, the conv-expert run); layer ``j`` of period ``p`` reads bank
    ``p * count + j`` of its run's stack, declared beside ``layers``."""
    model, lora, base = lfm2
    params = merge_params(base, lora)
    x, _ = batch()
    jaxpr = str(jax.make_jaxpr(lambda p: model.module.apply({"params": p}, x))(params))
    assert jaxpr.count("top_k[") == 2  # one per expert body
    assert params["experts_w13_run0"].shape == (2, 8, 64, 64) and params["experts_w13_run1"].shape == (6, 8, 64, 64)
    assert params["lead0_conv_dense"]["block"]["conv"]["conv_kernel"].shape == (2, 3, 64)
    unrolled = CausalLM(dataclasses.replace(model.module.cfg, scan_layers=False))
    assert jax.tree.structure(unrolled.init(jax.random.PRNGKey(0), x)["params"]) == jax.tree.structure(unrolled_tree(params))
    np.testing.assert_allclose(
        model.module.apply({"params": params}, x), unrolled.apply({"params": unrolled_tree(params)}, x), rtol=1e-5, atol=1e-5
    )
    # every bank matters: the output moves when ANY one layer's bank is swapped for another's
    for run, layer in (("experts_w13_run0", 1), ("experts_w13_run1", 4)):
        swapped = dict(params, **{run: params[run].at[layer].set(params[run][0])})
        assert float(jnp.max(jnp.abs(model.module.apply({"params": swapped}, x) - model.module.apply({"params": params}, x)))) > 1e-4


# ---- (e) the counters -------------------------------------------------------------


@pytest.mark.parametrize("layers", ["scanned", "unrolled"])
def test_statistics_and_routing_come_out_in_layer_order_over_both_expert_runs(lfm2, layers):
    model, lora, base = lfm2
    x, y = batch()
    params = merge_params(base, lora)
    scanned = model.module
    module = scanned if layers == "scanned" else CausalLM(dataclasses.replace(scanned.cfg, scan_layers=False))
    tree = params if layers == "scanned" else unrolled_tree(params)
    _, mut = module.apply({"params": tree}, x, mutable=["moe_stats", "moe_routing"])
    chosen, load = sown_by_layer(module.cfg, mut["moe_routing"]), sown_by_layer(module.cfg, mut["moe_stats"], "load_max_over_mean")
    assert chosen.shape == (8, 2 * SEQ, 2) and load.shape == (8,)
    # layer order: the reference's own choice, a layer at a time, is the same choice in float32
    with jax.default_matmul_precision("highest"):
        _, want = lfm2_moe_lm.loss_and_routing(lora, base, x, y, REF, lora_scale=2.0)
    got = jnp.swapaxes(chosen.reshape(8, 2, SEQ, 2), 0, 1)
    np.testing.assert_array_equal(np.sort(np.asarray(got), -1), np.sort(np.asarray(want), -1))
    sizes = np.stack([np.bincount(np.asarray(c).ravel(), minlength=8) for c in chosen])
    np.testing.assert_allclose(load, sizes.max(-1) / (2 * SEQ * 2 / 8), rtol=1e-6)
    assert len({tuple(row) for row in sizes}) > 4  # the layers route differently: an order can be told
    if layers == "scanned":
        stats = _lm_forward(lora, base, scanned, x, y)[2]
        assert set(stats) == {"moe_load_max_over_mean", "moe_rows_used_share"} and 0.5 < float(stats["moe_rows_used_share"]) <= 1.0
        assert float(stats["moe_load_max_over_mean"]) == pytest.approx(float(load.mean()), rel=1e-6)  # 2 + 6 layers, each once


# ---- (f) (g) the older patterns ---------------------------------------------------


@pytest.mark.parametrize("model", ["dense", "hybrid", "experts"])
def test_older_patterns_keep_the_parents_tree_and_lowered_texts(model):
    """The default pattern's, the hybrid's and the latent-attention expert
    model's parameter trees, lowered rounds and lowered forwards are the parent
    commit's, text for text (``qk_norm=False``, an empty ``leading_pattern``, a
    convolution WITH its bias): recorded on that commit before
    ``transformer.py`` was edited (``tests/fixtures/lm_rounds_parent.json``)."""
    want = json.loads((ROOT / "tests" / "fixtures" / "lm_rounds_parent.json").read_text())[model]
    fed = {"dense": lora_federation, "hybrid": hybrid_federation, "experts": expert_federation}[model]()
    assert fed.module.cfg.leading_pattern == () and not fed.module.cfg.qk_norm
    text = fed.lower_round(epochs=1).as_text()
    ops = Counter(re.findall(r"\b(stablehlo\.[a-z_]+|func\.call|sdy\.[a-z_]+)\b", text))
    params = merge_params(fed.base, jax.tree.map(lambda a: a[0], fed.params))
    paths = sorted(jax.tree_util.keystr(p) + str(tuple(leaf.shape)) for p, leaf in jax.tree_util.tree_leaves_with_path(params))
    assert paths == want["paths"]
    assert dict(sorted(ops.items())) == want["ops"]
    assert hashlib.sha256(text.encode()).hexdigest() == want["round_sha256"]
    forward = jax.jit(lambda p, t: fed.module.apply({"params": p}, t)).lower(params, jnp.zeros((2, 128), jnp.int32)).as_text()
    assert hashlib.sha256(forward.encode()).hexdigest() == want["forward_sha256"]


# ---- the federation and the benchmark's files -------------------------------------


def test_one_federated_round_carries_the_counter_and_leaves_banks_and_taps_in_the_base(lfm2):
    model, _, _ = lfm2
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=SEQ, n_train=16, n_test=4)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=2, batch_size=2, vote=False, seed=0, node_chunk=1)
    assert fed.base["experts_w13_run1"].dtype == jnp.bfloat16 and fed.base["experts_w13_run1"].shape == (6, 8, 64, 64)
    assert fed.base["lead0_conv_dense"]["block"]["conv"]["conv_kernel"].dtype == jnp.float32
    entry = fed.run_round(epochs=1)
    assert np.isfinite(float(entry["train_loss"])) and 1.0 <= float(entry["moe_load_max_over_mean"]) <= 8 / 2
    assert all(np.array_equal(np.asarray(leaf[0]), np.asarray(leaf[1])) for leaf in jax.tree.leaves(fed.params))


def test_flops_conv_moe_counts_the_published_model():
    from benchmark import flops, flops_conv_moe

    cfg = json.loads((ROOT / "benchmark" / "configs" / "lfm2_8b_a1b_lora.json").read_text())
    assert sum(i * o for _, i, o in flops_conv_moe.conv_matrices(cfg)) + 3 * 2048 == 16_783_360
    assert sum(i * o for _, i, o in flops_conv_moe.attention_matrices(cfg)) + 2 * 64 == 10_485_888
    assert flops_conv_moe.bank_params(cfg) == 352_321_536 == 32 * 3 * 2048 * 1792
    assert flops_conv_moe.layer_params(cfg, "conv_dense") == 16_783_360 + 3 * 2048 * 7168 + 2 * 2048
    assert flops_conv_moe.layer_params(cfg, "attention_experts") == 10_485_888 + 65_568 + 2 * 2048
    params = flops_conv_moe.model_params(cfg)
    assert params["bfloat16"] == 12 * 352_321_536 and params["float32"] == 439_218_944
    assert flops_conv_moe.lora_params(cfg) == 1_843_200
    assert lfm2_moe_lm.layer_kinds(cfg) == ["conv_dense"] * 2 + ["attention_experts", "conv_experts", "conv_experts", "conv_experts"] * 3
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:14] and cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    ops, moved = flops_conv_moe.gmm_pass(cfg, 4096)
    assert ops == 2.0 * 4096 * 4 * 3 * 2048 * 1792  # four experts a token, not executed tiles
    peak = flops.peaks("TPU v5 lite")
    assert ops / peak["bf16_flops_per_s"] > moved / peak["hbm_bytes_per_s"]  # compute-bound, where GLM's is bytes-bound
    fwd, bwd = flops.causal_attention_flops(4096, 32, 64)
    assert flops_conv_moe.gqa_flash_floor_seconds(cfg, 4096, peak) == pytest.approx(3 * (fwd + bwd) / peak["bf16_flops_per_s"])


def test_the_cell_rehearses():
    """``benchmark.rehearse``: build, the cell's whole reference check, warm-up
    and measure at the rehearsal's sizes (the one run of that check in tier-1;
    its faults are shown at their layer comparisons, ``tests/test_lfm2_faults.py``)."""
    rehearse("lfm2_silo4_seq4096", timeout=REHEARSE_S)
