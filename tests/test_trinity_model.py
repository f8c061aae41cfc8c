"""Sliding-window and full attention in one period, the gated attention output,
sandwich norms, the embedding multiplier, an untied head, and an expert layer
that holds a share of the experts — through ``CausalLM`` and
``SpmdLoraFederation``, against the plain reference
``benchmark/reference/afmoe_lm.py`` on seeded weights."""

import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import afmoe_lm
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.lora import _lm_forward, merge_params, split_lora
from p2pfl_tpu.models.transformer import (
    LAYER_KINDS, Attention, Block, CausalLM, ExpertFFN, TransformerConfig, layer_runs, rope, sown_by_layer,
    tiny_transformer,
)
from p2pfl_tpu.ops.grouped_matmul import n_row_tiles
from p2pfl_tpu.parallel import SpmdLoraFederation
from tests.test_lfm2_model import _draw  # lora_b perturbed, a router bias that changes choices, norm scales off one

ROOT = Path(__file__).resolve().parent.parent
SEQ = 48
WINDOW = 16
LEADING = ("swa_dense", "swa_dense")
PATTERN = ("swa_experts", "full_experts", "swa_experts", "swa_experts")
# the reference reads Hugging Face's keys: two dense sliding layers, then two periods s f s s;
# 4 heads of 32 on a residual of 64 (heads x head_dim = 2 x hidden, as published); experts 2..5 of 8 held
REF = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "rms_norm_eps": 1e-5, "rope_theta": 1e4, "sliding_window": WINDOW,
    "layer_types": ["sliding_attention"] * 2 + ["sliding_attention", "full_attention", "sliding_attention", "sliding_attention"] * 2,
    "num_hidden_layers": 10, "num_dense_layers": 2, "num_experts": 4, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "route_scale": 2.826, "mup_enabled": True, "tie_word_embeddings": False, "vocab_size": 256,
    "share": {"first_expert": 2, "router_experts": 8},
}


def config(**kw):
    base = dict(
        vocab_size=256, dim=64, n_layers=10, n_heads=4, n_kv_heads=2, head_dim=32, ffn_hidden=160, rope_theta=1e4,
        leading_pattern=LEADING, layer_pattern=PATTERN, qk_norm=True, attn_window=WINDOW, attn_gate=True, post_norms=True,
        embed_scale=8.0, tie_head=False, lora_rank=4, lora_alpha=8.0, lora_mlp=True, dtype=jnp.float32, remat=True,
        scan_layers=True, remat_policy=None, norm_eps=1e-5, routed_experts=8, experts_held=4, first_expert=2,
        experts_per_token=2, expert_hidden=32, shared_experts=1, routed_scale=2.826, expert_tile_m=8,
    )
    base.update(kw)
    return TransformerConfig(**base)


def seeded(cfg, seed=0, attn="dense"):
    model = tiny_transformer(seq_len=SEQ, seed=seed, cfg=cfg, attn=attn)
    model.params = _draw(model.params, seed + 1)
    return (model, *split_lora(model.params))


def batch(seed=0, n=2):
    x = jax.random.randint(jax.random.PRNGKey(seed), (n, SEQ + 1), 0, 256)
    return x[:, :-1], x[:, 1:]


def unrolled_tree(tree: dict, periods: int = 2) -> dict:
    """The scanned tree (or its adapter half) as the unrolled model holds it:
    ``layer_<i>`` in the concatenated order, an expert layer owning bank
    ``period * count + j`` of its run's stacks."""
    out = {k: tree[k] for k in ("embed", "lm_head", "final_norm") if k in tree}
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
def trinity():
    return seeded(config())


def _perturbed_layer(layer, *args, seed=1):
    params = _draw(layer.init(jax.random.PRNGKey(seed), *args)["params"], seed + 1)
    return split_lora(params)


def _same_gradients(grads, want, tol=2e-4):
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(ref))) > 0, jax.tree_util.keystr(path)  # every adapter is reached
        assert ck.rel_l2(got, ref) < tol, jax.tree_util.keystr(path)


# ---- (a) the attention layer: window and rotation by KIND, the gate ---------------


@pytest.mark.parametrize("mixer,attn", [("swa", "dense"), ("full", "dense"), ("swa", "flash"), ("full", "flash")])
def test_sliding_and_full_attention_forward_and_adapter_gradients_match_the_reference(mixer, attn):
    from p2pfl_tpu.models.transformer import resolve_attention
    from p2pfl_tpu.ops.flash_attention import FlashConfig

    cfg = config()
    attn_fn = resolve_attention("flash", config=FlashConfig(16, 16), window_config=FlashConfig(16, 8)) if attn == "flash" else None
    layer = Attention(cfg, attn_fn, mixer)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    lora, base = _perturbed_layer(layer, h)
    assert base["wq"]["kernel"].shape == base["wg"]["kernel"].shape == (64, 128) and base["wo"]["kernel"].shape == (128, 64)
    assert base["wk"]["kernel"].shape == (64, 64) and base["q_norm"]["scale"].shape == (32,)
    assert sorted(lora) == ["wg", "wk", "wo", "wq", "wv"]  # the gate is a fifth adapted projection
    probe = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    ours = lambda lo: jnp.sum(layer.apply({"params": merge_params(base, lo)}, h) * probe)  # noqa: E731
    kind = "sliding" if mixer == "swa" else "full"
    theirs = lambda lo: jnp.sum(afmoe_lm.attention(h[0], merge_params(base, lo), REF, 2.0, kind) * probe[0])  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(theirs)(lora)
        got, grads = jax.value_and_grad(ours)(lora)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    _same_gradients(grads, want_grads)


def test_a_sliding_layer_sees_its_window_and_a_full_layer_every_earlier_key():
    """Row ``t`` of a sliding layer reads keys ``t - W + 1 .. t``: a change at
    ``t - W + 1`` reaches it, one at ``t - W`` does not; a full layer is reached
    from the first position on; nothing later reaches either."""
    cfg = config()
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    t = 40
    for mixer, reach in (("swa", WINDOW), ("full", t + 1)):
        layer = Attention(cfg, None, mixer)
        params = layer.init(jax.random.PRNGKey(1), h)["params"]
        out = layer.apply({"params": params}, h)

        def moved(at):
            return np.abs(np.asarray(layer.apply({"params": params}, h.at[0, at].add(1.0)) - out))[0].max(-1)

        assert moved(t - reach + 1)[t] > 0
        if reach <= t:
            assert moved(t - reach)[t] == 0.0
        later = moved(t + 1)
        assert not later[:t + 1].any() and later[t + 1] > 0


def test_each_switch_alone_against_a_hand_computation():
    """The gate, the unrotated full layer, the sandwich norms, the embedding
    multiplier and the untied head, one at a time on a model that has none of
    the others, each against the arithmetic written out here."""
    plain = dict(
        vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=1, ffn_hidden=48, lora_rank=0, dtype=jnp.float32,
        norm_eps=1e-5,
    )
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 32), jnp.float32)
    norm = lambda x, g: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * g  # noqa: E731

    def attention_by_hand(p, x, rotate, gate):
        q = (x @ p["wq"]["kernel"]).reshape(1, 12, 2, 16)
        k = jnp.repeat((x @ p["wk"]["kernel"]).reshape(1, 12, 1, 16), 2, axis=2)
        v = jnp.repeat((x @ p["wv"]["kernel"]).reshape(1, 12, 1, 16), 2, axis=2)
        if rotate:
            q, k = rope(q, 1e4), rope(k, 1e4)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
        logits = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), logits, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v).reshape(1, 12, 32)
        if gate:
            out = out * jax.nn.sigmoid(x @ p["wg"]["kernel"])
        return out @ p["wo"]["kernel"]

    with jax.default_matmul_precision("highest"):
        # the gate: (P v) * sigmoid(x W_g) before wo, on the layer's INPUT
        cfg = TransformerConfig(**plain, attn_gate=True)
        p = Attention(cfg).init(jax.random.PRNGKey(1), h)["params"]
        assert p["wg"]["kernel"].shape == (32, 32)
        np.testing.assert_allclose(Attention(cfg).apply({"params": p}, h), attention_by_hand(p, h, True, True), rtol=1e-4, atol=1e-5)
        assert "wg" not in Attention(TransformerConfig(**plain)).init(jax.random.PRNGKey(1), h)["params"]  # off: the parent's tree
        # a full layer is not rotated, whatever the config's rope_theta; a sliding one is
        cfg = TransformerConfig(**plain, attn_window=64)
        p = Attention(cfg).init(jax.random.PRNGKey(1), h)["params"]
        np.testing.assert_allclose(Attention(cfg, None, "full").apply({"params": p}, h), attention_by_hand(p, h, False, False), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(Attention(cfg, None, "swa").apply({"params": p}, h), attention_by_hand(p, h, True, False), rtol=1e-4, atol=1e-5)
        # the sandwich: x + N(attn(N(x))), then x + N(ffn(N(x)))
        cfg = TransformerConfig(**plain, post_norms=True)
        p = _draw(Block(cfg).init(jax.random.PRNGKey(1), h)["params"], 5)
        assert sorted(p) == ["attn", "attn_norm", "attn_post_norm", "mlp", "mlp_norm", "mlp_post_norm"]
        a = h + norm(attention_by_hand(p["attn"], norm(h, p["attn_norm"]["scale"]), True, False), p["attn_post_norm"]["scale"])
        u = norm(a, p["mlp_norm"]["scale"])
        ffn = (jax.nn.silu(u @ p["mlp"]["w1"]["kernel"]) * (u @ p["mlp"]["w3"]["kernel"])) @ p["mlp"]["w2"]["kernel"]
        np.testing.assert_allclose(Block(cfg).apply({"params": p}, h), a + norm(ffn, p["mlp_post_norm"]["scale"]), rtol=1e-4, atol=1e-5)
        assert sorted(Block(TransformerConfig(**plain)).init(jax.random.PRNGKey(1), h)["params"]) == ["attn", "attn_norm", "mlp", "mlp_norm"]
        # the embedding multiplier and the untied head: logits of a ZERO-layer model are N(scale * E[tokens]) W_head^T
        tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, 64)
        for scale, tied in ((5.0, True), (1.0, False), (5.0, False)):
            cfg = TransformerConfig(**dict(plain, n_layers=0), layer_pattern=("attention",), embed_scale=scale, tie_head=tied)
            p = _draw(CausalLM(cfg).init(jax.random.PRNGKey(1), tokens)["params"], 7)
            assert ("lm_head" in p) == (not tied)
            head = p["embed"] if tied else p["lm_head"]
            want = norm(scale * p["embed"][tokens], p["final_norm"]["scale"]) @ head.T
            np.testing.assert_allclose(CausalLM(cfg).apply({"params": p}, tokens), want, rtol=1e-4, atol=1e-5)
            hidden, matrix = CausalLM(cfg).apply({"params": p}, tokens, head=False)
            assert matrix is head or np.array_equal(matrix, head)  # head=False hands out the matrix the head would take


# ---- (b) the whole model ----------------------------------------------------------


def test_layer_kinds_are_a_product_and_the_new_switches_are_hashed():
    assert {LAYER_KINDS[k] for k in ("swa_dense", "swa_experts", "full_experts")} == {
        ("swa", "mlp"), ("swa", "experts"), ("full", "experts")
    }
    assert afmoe_lm.layer_kinds(REF) == list(LEADING) + list(PATTERN) * 2
    assert afmoe_lm.stack(REF) == (list(LEADING), list(PATTERN), 2)
    assert layer_runs(PATTERN) == [("swa_experts", 1), ("full_experts", 1), ("swa_experts", 2)]
    with pytest.raises(ValueError, match="attn_window"):
        config(attn_window=None)
    with pytest.raises(ValueError, match="no share"):
        config(first_expert=6)  # 6 + 4 > 8
    changed = [config(attn_window=8), config(attn_gate=False), config(post_norms=False), config(embed_scale=1.0),
               config(tie_head=True), config(first_expert=0), config(experts_held=2), config(head_dim=16)]
    assert len({hash(c) for c in changed} | {hash(config())}) == len(changed) + 1
    assert config().head_width == 32 and TransformerConfig().head_width == 32 and config().held_experts == 4
    with pytest.raises(ValueError, match="no sliding window"):
        tiny_transformer(seq_len=SEQ, cfg=config(), attn="ring")


def _system(module, lora, base, x, y):
    """(loss, adapter gradients, ``[B, expert layers, T, k]`` assignments in layer order, statistics)."""

    def loss_of(lo):
        loss, _, stats, routing = _lm_forward(lo, base, module, x, y)
        chosen = sown_by_layer(module.cfg, routing)
        return loss, (jnp.swapaxes(chosen.reshape(chosen.shape[0], *x.shape, -1), 0, 1), stats)

    (loss, (chosen, stats)), grads = jax.value_and_grad(loss_of, has_aux=True)(lora)
    return loss, grads, chosen, stats


@pytest.mark.parametrize("layers,remat,attn", [
    ("scanned", True, "dense"), ("scanned", False, "dense"), ("unrolled", True, "dense"), ("unrolled", False, "dense"),
    ("scanned", True, "flash"),
])
def test_loss_and_every_adapter_gradient_match_the_reference_under_the_same_share(trinity, layers, remat, attn):
    model, lora, base = trinity
    x, y = batch()
    cfg = dataclasses.replace(model.module.cfg, scan_layers=layers == "scanned", remat=remat)
    from p2pfl_tpu.models.transformer import resolve_attention
    from p2pfl_tpu.ops.flash_attention import FlashConfig

    attn_fn = None if attn == "dense" else resolve_attention("flash", config=FlashConfig(16, 16), window_config=FlashConfig(16, 8))
    module = CausalLM(cfg, attn_fn)
    with jax.default_matmul_precision("highest"):
        if layers == "scanned":
            loss, grads, chosen, stats = _system(module, lora, base, x, y)
        else:
            loss, grads, chosen, stats = _system(module, unrolled_tree(lora), unrolled_tree(base), x, y)
        assert chosen.shape == (2, 8, SEQ, 2)
        (want_loss, own), want = jax.value_and_grad(afmoe_lm.loss_and_routing, has_aux=True)(
            lora, base, x, y, REF, lora_scale=2.0, forced=chosen
        )
    if layers == "unrolled":
        want = unrolled_tree(want)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), np.sort(np.asarray(own), -1))  # float32 both: one choice
    _same_gradients(grads, want)
    names = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(lora)}
    assert not any(word in n for n in names for word in ("experts_w", "router", "lm_head", "embed", "norm"))  # frozen base leaves
    assert any("'wg'" in n for n in names) and any("'shared'" in n for n in names)
    held = np.mean((np.asarray(chosen) >= 2) & (np.asarray(chosen) < 6))
    assert float(stats["moe_held_share"]) == pytest.approx(float(held), rel=1e-6) and 0.3 < held < 0.7


def test_logits_match_the_reference_and_the_head_is_lm_head(trinity):
    model, lora, base = trinity
    x, _ = batch()
    params = merge_params(base, lora)
    assert params["lm_head"].shape == params["embed"].shape == (256, 64) and "lm_head" in base
    with jax.default_matmul_precision("highest"):
        got = model.module.apply({"params": params}, x)
        want = jnp.stack([afmoe_lm.logits(params, tok, REF, lora_scale=2.0) for tok in x])
        tied = jnp.stack([afmoe_lm.logits(dict(params, lm_head=params["embed"]), tok, REF, lora_scale=2.0) for tok in x])
    assert ck.rel_l2(got, want) < 1e-4 < 0.5 < ck.rel_l2(got, tied)
    # lm_head is read and gets no gradient under LoRA: it is no adapter
    grads = jax.grad(lambda lo: _lm_forward(lo, base, model.module, x, x)[0])(lora)
    assert "lm_head" not in grads and "embed" not in grads


def test_scanned_layers_equal_the_unrolled_layers_on_mapped_parameters(trinity):
    """Four scan bodies whatever the depth (the leading run, and runs of 1, 1, 2
    in the period); banks ``[layers of the run, HELD experts, ...]`` beside ``layers``."""
    model, lora, base = trinity
    params = merge_params(base, lora)
    x, _ = batch()
    jaxpr = str(jax.make_jaxpr(lambda p: model.module.apply({"params": p}, x))(params))
    assert jaxpr.count("top_k[") == 3  # one per expert body
    assert params["experts_w13_run0"].shape == params["experts_w13_run1"].shape == (2, 4, 64, 64)
    assert params["experts_w13_run2"].shape == (4, 4, 64, 64) and params["experts_w2_run2"].shape == (4, 4, 32, 64)
    assert params["layers"]["run1_full_experts"]["mlp"]["router"].shape == (2, 64, 8)  # the router keeps every expert's score
    unrolled = CausalLM(dataclasses.replace(model.module.cfg, scan_layers=False))
    assert jax.tree.structure(unrolled.init(jax.random.PRNGKey(0), x)["params"]) == jax.tree.structure(unrolled_tree(params))
    np.testing.assert_allclose(
        model.module.apply({"params": params}, x), unrolled.apply({"params": unrolled_tree(params)}, x), rtol=1e-5, atol=1e-5
    )


def test_the_short_convolution_expert_round_lowers_the_parents_text():
    """The fourth older pattern — leading layers, two expert runs a period, q / k
    norms — beside the three of ``tests/fixtures/lm_rounds_parent.json`` (held by
    ``test_lfm2_model``): its lowered round is the parent commit's, text for text,
    with ``head_dim``, the window, the gate, the post-norms, the embedding
    multiplier, the untied head and the held share all at their defaults."""
    import hashlib

    from tests.test_scope_trace import _conv_expert_federation

    want = json.loads((ROOT / "tests" / "fixtures" / "lm_round_conv_parent.json").read_text())["conv_experts"]
    text = _conv_expert_federation().lower_round(epochs=1).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) == (want["round_sha256"], want["chars"])


# ---- (c) the held share -----------------------------------------------------------


def _share_case(model: str):
    """(the program's config maker, the reference's keys, the key that counts the
    held experts, the reference's expert layer) of one configuration."""
    if model == "trinity":
        return config, REF, "num_experts", afmoe_lm.experts
    from benchmark.reference import solar_open2_lm
    from tests import test_solar_model

    return test_solar_model.config, test_solar_model.REF, "n_routed_experts", solar_open2_lm.experts


@pytest.mark.parametrize("model", ["trinity", "solar"])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(model):
    """Four chips' shares (experts 0-1, 2-3, 4-5, 6-7 of 8) of ONE expert layer,
    each its program: their routed parts + the shared expert counted once are
    the uncut reference's layer output, their held shares sum to 1 — and each
    share is what the reference gives under the same share. Both configurations
    that hold a share: Trinity's (route_scale 2.826) and Solar's (1, 320 wide)."""
    make, ref_cfg, count, experts = _share_case(model)
    cfg = make(experts_held=8, first_expert=0)
    uncut = ExpertFFN(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    params = _draw(uncut.init(jax.random.PRNGKey(1), h)["params"], 3)
    assert params["experts_w13"].shape == (8, 64, 64)
    whole = dict(ref_cfg, **{count: 8}, share={"first_expert": 0, "router_experts": 8})
    with jax.default_matmul_precision("highest"):
        want, want_chosen = experts(h[0], params, whole, 2.0)
        shared = afmoe_lm.swiglu(h[0], params["shared"], 2.0)
        total, shares = jnp.zeros_like(want), []
        for first in (0, 2, 4, 6):
            part = ExpertFFN(make(experts_held=2, first_expert=first))
            mine = dict(params, experts_w13=params["experts_w13"][first:first + 2], experts_w2=params["experts_w2"][first:first + 2])
            y, mut = part.apply({"params": mine}, h, mutable=["moe_stats", "moe_routing"])
            np.testing.assert_array_equal(np.sort(mut["moe_routing"]["chosen"][0], -1), np.sort(want_chosen, -1))  # the whole model's choice
            ref, _ = experts(h[0], mine, dict(ref_cfg, **{count: 2}, share={"first_expert": first, "router_experts": 8}), 2.0)
            assert ck.rel_l2(y[0], ref) < 1e-5
            total = total + (y[0] - shared)
            shares.append(float(mut["moe_stats"]["held_share"][0]))
            assert float(mut["moe_stats"]["load_max_over_mean"][0]) >= 0.0
    assert ck.rel_l2(total + shared, want) < 1e-5
    assert sum(shares) == pytest.approx(1.0, abs=1e-6) and len(set(shares)) > 1
    # uncut, the layer sows no held share (the rows in use it sows held or not)
    _, mut = uncut.apply({"params": params}, h, mutable=["moe_stats"])
    assert sorted(mut["moe_stats"]) == ["load_max_over_mean", "rows_used_share"]


@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("tile", [8, 16])
def test_an_absent_assignment_has_no_row_and_costs_no_tile(tile, k):
    """An absent assignment reads a row of the spare tile — padding whatever the
    split — picked by its token: consecutive tokens read consecutive rows, so a
    slab's absent indices are spread over the tile and not one address."""
    from p2pfl_tpu.ops.grouped_matmul import group_layout, n_row_tiles, tiles_and_fetches

    rng = np.random.default_rng(0)
    m = 48 * k
    group_of = jnp.asarray(rng.integers(0, 8, size=m), jnp.int32)
    layout = group_layout(group_of, 3, tile, 2, True, per_token=k)  # groups 2, 3, 4 of 8 are held
    sizes = np.bincount(np.asarray(group_of), minlength=8)[2:5]
    np.testing.assert_array_equal(layout.group_sizes, sizes)
    assert layout.rows == tile * (n_row_tiles(m, 3, tile) + 1)  # the static worst case + the spare tile
    used, fetches = tiles_and_fetches(layout.group_sizes, tile)
    assert int(used) == int(np.sum(-(-sizes // tile))) and int(fetches) == 3  # tiles for the held rows only
    slot, back = np.asarray(layout.slot_of_assignment), np.asarray(layout.assignment_of_slot)
    present = (np.asarray(group_of) >= 2) & (np.asarray(group_of) < 5)
    spare = layout.rows - tile
    assert (slot[~present] >= spare).all() and (back[spare:] == m).all()  # absent: a row of the spare tile, all of it padding
    row_of_token = spare + np.arange(m // k) % tile  # what an absent assignment of each token reads
    np.testing.assert_array_equal(slot[~present], np.repeat(row_of_token, k)[~present])
    assert (row_of_token[1:] != row_of_token[:-1]).all()  # consecutive tokens' absent rows differ
    assert len(set(slot[~present].tolist())) == tile  # the whole tile is used, not one address
    assert (slot[present] < int(used) * tile).all() and (back[slot[present]] == np.flatnonzero(present)).all()
    assert (back < m).sum() == present.sum()  # no row belongs to an absent assignment
    # every group held: the layout every older model has (no spare tile, nothing absent)
    all_held, same = group_layout(group_of, 8, tile), group_layout(group_of, 8, tile, 0, False, per_token=k)
    assert all_held.rows == tile * n_row_tiles(m, 8, tile)
    np.testing.assert_array_equal(all_held.slot_of_assignment, same.slot_of_assignment)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_held_share_through_both_grouped_matmul_paths_and_its_gradient(impl):
    """The absent assignments' zero row: zero out, zero cotangent, both ways
    (the interpreted kernel writes the spare tile as zeros; ragged_dot leaves
    rows past the groups zero)."""
    cfg = config(expert_impl=impl)
    layer = ExpertFFN(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    lora, base = _perturbed_layer(layer, h)
    probe = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    ours = lambda lo, h_: jnp.sum(layer.apply({"params": merge_params(base, lo)}, h_) * probe)  # noqa: E731
    theirs = lambda lo, h_: jnp.sum(afmoe_lm.experts(h_[0], merge_params(base, lo), REF, 2.0)[0] * probe[0])  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(theirs, (0, 1))(lora, h)
        got, grads = jax.value_and_grad(ours, (0, 1))(lora, h)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    _same_gradients(grads[0], want_grads[0])
    assert ck.rel_l2(grads[1], want_grads[1]) < 2e-4  # the input's cotangent: held rows' only


# ---- (d) the federation and the benchmark's files ---------------------------------


def test_one_federated_round_carries_both_counters_and_lm_head_is_in_no_payload(trinity):
    model, _, _ = trinity
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=SEQ, n_train=16, n_test=4)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=2, batch_size=2, vote=False, seed=0, node_chunk=1)
    assert fed.base["lm_head"].shape == (256, 64) and fed.base["experts_w13_run2"].shape == (4, 4, 64, 64)
    payload = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(fed.params)}
    assert not any("lm_head" in n or "embed" in n or "experts_w" in n for n in payload) and any("'wg'" in n for n in payload)
    head = np.asarray(fed.base["lm_head"])
    entry = fed.run_round(epochs=1)
    assert np.isfinite(float(entry["train_loss"])) and 0.3 < float(entry["moe_held_share"]) < 0.7
    assert 0.0 < float(entry["moe_load_max_over_mean"]) <= 8 / 2
    # the third counter: the share of each grouped-matmul call's tiles that are written (at least the held rows')
    rows = 8 * (n_row_tiles(2 * SEQ * 2, 4, 8) + 1)
    assert float(entry["moe_held_share"]) * 2 * SEQ * 2 / rows <= float(entry["moe_rows_used_share"]) < 1.0
    assert np.array_equal(head, np.asarray(fed.base["lm_head"]))  # frozen
    assert all(np.array_equal(np.asarray(leaf[0]), np.asarray(leaf[1])) for leaf in jax.tree.leaves(fed.params))


def test_the_configuration_file_states_the_published_model_and_the_share():
    from benchmark import flops_window_moe

    cfg = json.loads((ROOT / "benchmark" / "configs" / "trinity_mini_lora.json").read_text())
    rows = [json.loads(line) for line in Path("/opt/skills/guides/model-configs/architectures.jsonl").read_text().splitlines()] \
        if Path("/opt/skills/guides/model-configs/architectures.jsonl").is_file() else []
    for row in rows:
        if row.get("name") == "Trinity-Mini":  # every key as published, but the four that are reduced
            assert cfg["source"] == row["source_url"]
            assert {k for k, v in row["config"].items() if cfg[k] != v} == set(cfg["reduced"])
            assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:10] and cfg["num_experts"] == 32 and cfg["vocab_size"] == 50_048
    assert cfg["share"]["router_experts"] == cfg["published"]["num_experts"] == 128 and cfg["share"]["chips"] == 4
    assert afmoe_lm.layer_kinds(cfg) == ["swa_dense"] * 2 + ["swa_experts", "full_experts", "swa_experts", "swa_experts"] * 2
    assert not [what for what, got, want in flops_window_moe.hand_counts() if got != pytest.approx(want, rel=1e-12)]
    assert f"{flops_window_moe.lora_params(cfg):,}" in cfg["assumed"]["lora.targets"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert "trinity_silo4_seq8192" in {w["name"] for w in bench["workloads"]} and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    traffic = json.loads((ROOT / "benchmark" / "traffic" / "silo4_seq8192.json").read_text())
    assert (traffic["n_nodes"], traffic["local_steps"], traffic["batch_size"], traffic["seq_len"], traffic["participation"]) == (4, 2, 1, 8192, 1.0)


@pytest.mark.parametrize("module", ["benchmark.selfcheck", "benchmark.rehearse", "benchmark.planted_faults_window", "benchmark.flops_window_moe"])
def test_benchmark_files_resolve_and_the_cell_rehearses(module):
    """``rehearse`` drives the engine's build -> check -> warm -> measure ->
    finish at the ``rehearsal`` sizes; ``planted_faults_window`` runs the cell's
    WHOLE reference check there with a fault planted: exit 0 = it was seen."""
    cell = ["--workload", "trinity_silo4_seq8192"]
    args = {
        "benchmark.selfcheck": [],
        "benchmark.rehearse": [*cell, "--seconds", "1"],
        "benchmark.planted_faults_window": [*cell, "--seed", "1", "--fault", "held_normalised", "--rehearsal"],
        "benchmark.flops_window_moe": [],
    }[module]
    done = subprocess.run(
        [sys.executable, "-m", module, *args], cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT)},
    )
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    if module.endswith("selfcheck"):
        for name in ("cell trinity_silo4_seq8192", "metric flash_win_roofline", "metric moe_held_share", "metric post_norm_ms"):
            assert f"ok: {name}" in done.stdout
    if module.endswith("rehearse"):
        assert '"correct": true' in done.stdout and "rehearsal finished" in done.stdout
        assert "held share of the step's assignments" in done.stdout
    if module.endswith("planted_faults_window"):
        assert "layer.worst_agreeing_token_rel'" in done.stdout.splitlines()[-1]
