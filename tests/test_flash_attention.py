"""Flash-attention kernel tests (pallas interpret mode on CPU).

The kernel schedule is the static :class:`FlashConfig` — block shapes,
q ownership and backward mode all ride explicit config objects here (the
old module-global ``BWD_MODE`` is gone; see test_kernel_config.py for the
jit cache-key / staleness coverage).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.ops.attention import causal_attention
from p2pfl_tpu.ops.flash_attention import FlashConfig, flash_attention


def _qkv(b=2, t=128, h=4, d=32, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in keys)


def test_flash_matches_dense_causal():
    q, k, v = _qkv()
    want = causal_attention(q, k, v)
    got = flash_attention(q, k, v, True, FlashConfig(32, 32), True)  # interpret
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-5)


def test_flash_non_causal():
    q, k, v = _qkv(t=64)
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d**-0.5)
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    got = flash_attention(q, k, v, False, FlashConfig(32, 32), True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-5)


def test_flash_uneven_blocks():
    """block_q != block_k and T not equal to block sizes."""
    q, k, v = _qkv(t=96)
    want = causal_attention(q, k, v)
    got = flash_attention(q, k, v, True, FlashConfig(32, 48), True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-5)


def test_flash_default_config_resolves():
    """config=None resolves through the autotune lookup chain (defaults
    table on this platform) and still matches dense."""
    q, k, v = _qkv(t=64)
    want = causal_attention(q, k, v)
    got = flash_attention(q, k, v, True, None, True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-5)


@pytest.mark.parametrize("q_span", [2, 4])
def test_flash_q_span_matches_dense(q_span):
    """Wider q ownership per program is a pure schedule change."""
    q, k, v = _qkv(t=128)
    want = causal_attention(q, k, v)
    got = flash_attention(q, k, v, True, FlashConfig(16, 32, q_span=q_span), True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-5)


@pytest.mark.slow
def test_flash_gradient_matches_dense():
    q, k, v = _qkv(b=1, t=32, h=2, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, FlashConfig(16, 16), True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.slow
def test_flash_in_transformer():
    """The attn="flash" selector wires the kernel into the model."""
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer

    cfg = TransformerConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=2, ffn_hidden=64)
    m_flash = tiny_transformer(seq_len=32, cfg=cfg, attn="flash", seed=4)
    m_dense = tiny_transformer(seq_len=32, cfg=cfg, seed=4)
    toks = (jnp.arange(32, dtype=jnp.int32) % 64)[None]
    a = m_flash.apply(m_flash.params, toks)
    b = m_dense.apply(m_dense.params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-2)


def test_auto_attention_picks_by_length():
    """attn="auto" (VERDICT r2 #8): on TPU, dense below the measured
    crossover (Settings.FLASH_MIN_SEQ_LEN, from bench config 7) and flash
    at/above; on every OTHER backend always dense — interpret-mode Pallas
    is a correctness path, not a performance one."""
    from p2pfl_tpu.models.transformer import (
        TransformerConfig,
        pick_attention,
        resolve_attention,
        tiny_transformer,
    )
    from p2pfl_tpu.settings import Settings

    t = Settings.FLASH_MIN_SEQ_LEN
    assert pick_attention(t - 1, backend="tpu") == "dense"
    assert pick_attention(t, backend="tpu") == "flash"
    assert pick_attention(t * 8, backend="cpu") == "dense"  # non-TPU gate
    with pytest.raises(ValueError, match="seq_len"):
        resolve_attention("auto")
    # this suite runs on the CPU backend: auto resolves to the dense path
    # (None) at any length, and the model builds/runs
    assert resolve_attention("auto", seq_len=t * 8) is None
    cfg = TransformerConfig(
        vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=2, ffn_hidden=64
    )
    m_auto = tiny_transformer(seq_len=32, cfg=cfg, attn="auto", seed=4)
    m_dense = tiny_transformer(seq_len=32, cfg=cfg, seed=4)
    toks = (jnp.arange(32, dtype=jnp.int32) % 64)[None]
    np.testing.assert_allclose(
        np.asarray(m_auto.apply(m_auto.params, toks)),
        np.asarray(m_dense.apply(m_dense.params, toks)),
        atol=5e-2,
    )


@pytest.mark.slow
def test_flash_transformer_training_grads_match_dense():
    """Training the transformer with flash attention: full LM-loss gradients
    match the dense model's (pattern of test_ring_training.py)."""
    import optax

    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer

    cfg = TransformerConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, ffn_hidden=64,
        dtype=jnp.float32,
    )
    seq = 32
    m_flash = tiny_transformer(seq_len=seq, cfg=cfg, attn="flash", seed=9)
    m_dense = tiny_transformer(seq_len=seq, cfg=cfg, seed=9)

    def loss_fn(model):
        def loss(params, x, y):
            logits = model.module.apply({"params": params}, x)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        return loss

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, seq)), jnp.int32)
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, seq)), jnp.int32)
    g_flash = jax.grad(loss_fn(m_flash))(m_flash.params, x, y)
    g_dense = jax.grad(loss_fn(m_dense))(m_dense.params, x, y)
    for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-3
        )


def test_flash_resolver_rejects_unknown():
    from p2pfl_tpu.models.transformer import resolve_attention

    with pytest.raises(ValueError):
        resolve_attention("nope")
    with pytest.raises(ValueError):
        resolve_attention("ring")  # needs a mesh


@pytest.mark.slow
def test_bwd_specific_blocks_match_shared_blocks():
    """block_q_bwd/block_k_bwd change only the backward SCHEDULE: gradients
    must match the shared-block configuration (the saved lse's [B, H, 1, T]
    row layout is block-size independent — no relayout either way)."""
    q, k, v = _qkv(t=256, h=2)

    def loss(config):
        def f(q_, k_, v_):
            o = flash_attention(q_, k_, v_, True, config, True)
            return jnp.sum(o * o)

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_shared = loss(FlashConfig(64, 64))  # bwd uses the fwd's 64-blocks
    g_bwd128 = loss(FlashConfig(64, 64, block_q_bwd=128, block_k_bwd=128))
    for a, bb in zip(g_shared, g_bwd128):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_fused_bwd_matches_split(causal):
    """The single-pass dkvq kernel (persistent dQ scratch across k-block
    grid steps) must produce the SAME gradients as the split dq/dkv pair —
    it only removes the S/dP recompute, not any math. bwd_mode is now an
    explicit static config knob, not a module global."""
    q, k, v = _qkv(b=2, t=128, h=2, d=16)

    def grads(mode):
        def f(q_, k_, v_):
            o = flash_attention(
                q_, k_, v_, causal, FlashConfig(32, 64, bwd_mode=mode), True
            )
            return jnp.sum(o * o)

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_split = grads("split")
    g_fused = grads("fused")
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fused_bwd_matches_dense_gradient():
    q, k, v = _qkv(b=1, t=64, h=2, d=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, True, FlashConfig(16, 32, bwd_mode="fused"), True)
        return jnp.sum(o**2)

    def loss_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_fused_bwd_offs_matches_split():
    """Offset-variant single-pass backward == split pair, including the
    lse cotangent path the ring merge differentiates through."""
    from p2pfl_tpu.ops import flash_attention as fa

    q, k, v = _qkv(b=1, t=64, h=2, d=16)

    def grads(q_off, k_off, mode):
        def f(q_, k_, v_):
            o, lse = fa.flash_attention_block(
                q_, k_, v_, jnp.int32(q_off), jnp.int32(k_off),
                FlashConfig(16, 32, bwd_mode=mode), True,
            )
            # touch BOTH outputs so the lse cotangent is non-trivial
            return jnp.sum(o * o) + jnp.sum(jnp.where(lse <= -5e29, 0.0, lse)) * 1e-3

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for q_off, k_off in ((0, 0), (64, 0), (0, 64), (64, 64)):
        g_split = grads(q_off, k_off, "split")
        g_fused = grads(q_off, k_off, "fused")
        for a, b in zip(g_fused, g_split):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5,
                err_msg=f"offsets ({q_off}, {k_off})",
            )


# ---- sliding window: blocks outside it skipped, the two edges masked ---------------


def _window_grads(t, window, block_q, block_k, mode, q_span=1, heads=2, kv_heads=None, d=16):
    """(loss, gradients) of windowed flash and of dense masked attention in
    float32, against a random cotangent; ``kv_heads``: GQA, K/V repeated as
    ``Attention`` repeats them."""
    kv_heads = kv_heads or heads
    keys = jax.random.split(jax.random.PRNGKey(t + window), 4)
    q, g = (jax.random.normal(s, (1, t, heads, d), jnp.float32) for s in keys[:2])
    k, v = (jax.random.normal(s, (1, t, kv_heads, d), jnp.float32) for s in keys[2:])
    cfg = FlashConfig(block_q=block_q, block_k=block_k, q_span=q_span, bwd_mode=mode)
    rep = lambda a: jnp.repeat(a, heads // kv_heads, axis=2)  # noqa: E731
    flash = lambda q_, k_, v_: jnp.sum(flash_attention(q_, rep(k_), rep(v_), True, cfg, True, window) * g)  # noqa: E731
    dense = lambda q_, k_, v_: jnp.sum(causal_attention(q_, rep(k_), rep(v_), window=window) * g)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(flash, (0, 1, 2))(q, k, v), jax.value_and_grad(dense, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("mode", ["fused", "split"])
@pytest.mark.parametrize(
    "t,window,block_q,block_k",
    [
        (64, 8, 16, 16),  # W < block
        (64, 1, 16, 16),  # a row sees itself alone
        (64, 24, 16, 16),  # W no multiple of a block
        (64, 16, 16, 16),  # W = one block
        (64, 17, 16, 32),  # unlike blocks
        (48, 20, 16, 16),  # T = 3 blocks
        (32, 5, 16, 16),  # T = 2 blocks
        (64, 64, 16, 16),  # W = T: plain causal
        (64, 100, 16, 16),  # W > T: plain causal
    ],
)
def test_windowed_flash_matches_dense_masked_attention(t, window, block_q, block_k, mode):
    (got, got_grads), (want, want_grads) = _window_grads(t, window, block_q, block_k, mode)
    assert abs(float(got - want)) < 1e-3
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("mode", ["fused", "split"])
def test_windowed_flash_under_gqa_8_to_1_and_a_q_span(mode):
    (got, got_grads), (want, want_grads) = _window_grads(64, 33, 16, 16, mode, q_span=2, heads=8, kv_heads=1)
    assert abs(float(got - want)) < 1e-3
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (16, 32), (32, 16), (8, 24)])
@pytest.mark.parametrize("window", [1, 5, 16, 24, 33, 47])
def test_window_bounds_skip_every_invisible_block_and_mask_only_the_edges(window, block_q, block_k):
    """The loop bounds of both sides against a brute-force ``[T, T]`` mask: a
    block is visited iff a pair of it is visible (nothing outside the window is
    streamed), and it runs unmasked iff every pair of it is visible — or was
    conservatively sent to a masked loop, never the other way round."""
    from p2pfl_tpu.ops.flash_attention import _k_side_bounds, _q_side_bounds

    t = 96
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (cols <= rows) & (cols > rows - window)
    nq, nk = t // block_q, t // block_k
    tile = lambda i, j: seen[i * block_q:(i + 1) * block_q, j * block_k:(j + 1) * block_k]  # noqa: E731
    for i in range(nq):
        lo, lo_full, n_full, n_all = (int(b) for b in _q_side_bounds(jnp.int32(i), block_q, block_k, window))
        assert lo <= lo_full <= n_full <= n_all
        assert [j for j in range(nk) if tile(i, j).any()] == list(range(lo, min(n_all, nk)))
        assert all(tile(i, j).all() for j in range(lo_full, n_full))
    for j in range(nk):
        start, full, hi_full, end = (int(b) for b in _k_side_bounds(jnp.int32(j), block_q, block_k, window, nq))
        assert start <= full <= hi_full <= end <= nq
        assert [i for i in range(nq) if tile(i, j).any()] == list(range(start, end))
        assert all(tile(i, j).all() for i in range(full, hi_full))
    # and the pairs a sliding layer sees are what the benchmark's floor counts
    from benchmark.flops_window_moe import visible_pairs

    assert int(seen.sum()) == visible_pairs(t, window)


def test_a_window_is_causal_static_and_named():
    q, k, v = _qkv(t=64)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, FlashConfig(16, 16), True, 8)
    with pytest.raises(ValueError, match="itself"):
        flash_attention(q, k, v, True, FlashConfig(16, 16), True, 0)
    grad = lambda w: jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v, True, FlashConfig(16, 16), True, w)))  # noqa: E731
    names = {w: sorted(set(re.findall(r"p2pfl_flash_[a-z_]+", str(jax.make_jaxpr(grad(w))(q))))) for w in (None, 8, 64)}
    assert names[None] == names[64] == ["p2pfl_flash_bwd_fused", "p2pfl_flash_fwd"]  # W >= T is plain causal
    assert names[8] == ["p2pfl_flash_win_bwd_fused", "p2pfl_flash_win_fwd"]  # the scopes: tests/test_scope_trace.py


def test_the_window_keys_the_schedule():
    from p2pfl_tpu.ops import autotune

    autotune.clear_memory_cache()
    try:
        assert autotune._key("cpu", 64, 256, jnp.float32, True) == "cpu|d=64|t=256|float32|causal"  # as it has always been
        assert autotune._key("cpu", 64, 256, jnp.float32, True, 32) == "cpu|d=64|t=256|float32|causal|w=32"
        pinned = FlashConfig(block_q=64, block_k=32)
        autotune.pin_flash_config(256, 64, pinned, kind="cpu", window=32)
        assert autotune.flash_config_source(256, 64, kind="cpu", window=32) == (pinned, "pin")
        assert autotune.flash_config_source(256, 64, kind="cpu")[1] == "defaults"  # the pin is the window's alone
        assert autotune.flash_config_source(8192, 128, kind="TPU v5 lite", window=2048)[1] == "defaults"
    finally:
        autotune.clear_memory_cache()


def test_ring_attention_refuses_a_window():
    from p2pfl_tpu.ops.attention import ring_attention

    q, k, v = _qkv(t=64)
    with pytest.raises(NotImplementedError, match="sliding window"):
        ring_attention(q, k, v, mesh=None, axis_name="model", window=16)


@pytest.mark.parametrize("mode", ["fused", "split"])
def test_without_a_window_the_lowered_text_is_the_parents(mode):
    """``window=None`` is the program there was: forward and both backwards,
    interpreted (the kernels' own jaxprs as XLA ops), hash for hash what the
    parent commit lowered (``tests/fixtures/flash_lowered_parent.json``,
    recorded on that commit before ``flash_attention.py`` was edited). The
    Mosaic lowering of the same jaxprs carries source lines in its payload and
    cannot be compared this way."""
    import hashlib
    import json
    from pathlib import Path

    want = json.loads((Path(__file__).parent / "fixtures" / "flash_lowered_parent.json").read_text())[mode]
    cfg = FlashConfig(block_q=128, block_k=128, bwd_mode=mode)
    q = jnp.zeros((1, 512, 2, 64), jnp.bfloat16)

    def loss(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, True, cfg, True).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) == (want["sha256"], want["chars"])
