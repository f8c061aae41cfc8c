"""Reference decoder-only LM: pre-RMSNorm, RoPE, grouped-query causal attention,
SwiGLU, no biases — the Mistral-7B block (mistralai/Mistral-7B-v0.3), with LoRA
adapters ``y = xW + (alpha/r) x A B`` on the projections that carry them.

Two departures from the published model, both the program's and both stated in
``configs/mistral7b_lora.json``: the output head is the embedding transposed
(published: a separate ``lm_head``), and the norm's epsilon is the value the
configuration file gives under ``rms_norm_eps`` as run (1e-6; published 1e-5).

Parameters arrive as the program's tree: ``embed``, ``final_norm/scale`` and,
under ``layers/block``, every per-layer array stacked on a leading layer axis.
Everything is float32; attention is dense, so a sequence must be short enough
to hold its ``heads x T x T`` logits (1024 tokens: 128 MB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def merge(base: dict, lora: dict) -> dict:
    """Adapter leaves laid over the frozen tree (same nesting)."""
    out = dict(base)
    for key, val in lora.items():
        out[key] = merge(base.get(key, {}), val) if isinstance(val, dict) else val
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary embedding on [T, H, D], half-split (rotate_half) convention."""
    t, _, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def project(x, p, scale):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (x @ p["lora_a"]) @ p["lora_b"] * scale
    return y


def block(x, p, cfg, scale):
    t = x.shape[0]
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h = rms_norm(x, p["attn_norm"]["scale"], cfg["rms_norm_eps"])
    q = rope(project(h, p["attn"]["wq"], scale).reshape(t, heads, hd), cfg["rope_theta"])
    k = rope(project(h, p["attn"]["wk"], scale).reshape(t, kv_heads, hd), cfg["rope_theta"])
    v = project(h, p["attn"]["wv"], scale).reshape(t, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    logits = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], logits, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1), v).reshape(t, heads * hd)
    x = x + project(out, p["attn"]["wo"], scale)
    h = rms_norm(x, p["mlp_norm"]["scale"], cfg["rms_norm_eps"])
    gate, up = project(h, p["mlp"]["w1"], scale), project(h, p["mlp"]["w3"], scale)
    return x + project(jax.nn.silu(gate) * up, p["mlp"]["w2"], scale)


def logits(params: dict, tokens, cfg: dict, *, lora_scale: float):
    """[T] int tokens -> [T, vocab] float32 logits."""
    x = params["embed"][tokens]
    stacked = params["layers"]["block"]
    # jax.checkpoint changes memory, not arithmetic: the backward pass
    # recomputes a block instead of keeping its heads x T x T logits
    one = jax.checkpoint(lambda x_, p_: block(x_, p_, cfg, lora_scale))
    for layer in range(cfg["num_hidden_layers"]):
        x = one(x, jax.tree.map(lambda a: a[layer], stacked))
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ params["embed"].T  # tied head (departure, see module docstring)


def loss(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float):
    """Mean next-token cross-entropy over a [B, T] batch of sequences."""
    params = merge(base, lora)

    def one(tok, tgt):
        logp = jax.nn.log_softmax(logits(params, tok, cfg, lora_scale=lora_scale), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tgt[:, None], axis=-1))

    return jnp.mean(jnp.stack([one(tok, tgt) for tok, tgt in zip(tokens, targets)]))
