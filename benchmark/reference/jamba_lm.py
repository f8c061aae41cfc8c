"""Reference hybrid state-space / attention LM: the Jamba block stack
(ai21labs/AI21-Jamba2-3B, ``model_type: jamba``), with LoRA adapters
``y = xW + (alpha/r) x A B`` on the projections that carry them. Plain
``jax.numpy``, float32, the recurrence one token at a time (``lax.scan`` over
time: no chunks, no kernels), dense masked attention. Independent of
``p2pfl_tpu/ops/selective_scan.py``.

Layer ``i`` (``x`` is ``[T, hidden]``; norms are RMSNorm with a learned scale)::

    x = x + mixer_i(norm1(x));   x = x + W2( silu(W1 h) * (W3 h) ),  h = norm2(x)
    mixer_i = attention if i % attn_layer_period == attn_layer_offset else mamba
    attention: q = h Wq [T,H,hd], k = h Wk [T,KV,hd], v = h Wv [T,KV,hd]; NO positional
               rotation; causal softmax(q k^T / sqrt(hd)) v; · Wo; no bias
    mamba:  [u, z] = h W_in                                  # hidden -> 2 · inner, no bias
            u = silu( causal_depthwise_conv1d(u; kernel mamba_d_conv, bias) )
            [δ, B, C] = u W_x                                 # inner -> dt_rank + N + N, no bias
            δ, B, C = norm_dt(δ), norm_B(B), norm_C(C)        # Jamba's inner RMSNorms
            Δ = softplus(δ W_dt + b_dt)                       # dt_rank -> inner, with bias
            A = −exp(A_log)                                   # [inner, N]
            h_t = exp(Δ_t ⊗ A) * h_{t−1} + (Δ_t * u_t) ⊗ B_t,   h_0 = 0     # state [inner, N]
            y_t = h_t · C_t + D * u_t;   out = (y * silu(z)) W_out         # inner -> hidden, no bias
    logits = norm_f(x) E^T  (tied, as published), loss = mean next-token cross-entropy

No departure from the published model: the tied head and eps 1e-6 are Jamba's
own. The order of the layer kinds is not a key of the published config; it
follows from ``attn_layer_period`` / ``attn_layer_offset`` as above.

Parameters arrive as the program's tree: ``embed``, ``final_norm/scale`` and,
under ``layers``, one entry per maximal run of same-kind layers of a period,
``run<i>_<kind>``, whose arrays carry a leading period axis and — under
``block``, where the run is longer than one layer — a run-position axis. The
state is kept as ``[N, inner]`` (a TPU pads a minor dimension of 16 to 128).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.causal_lm import merge, project, rms_norm


def layer_kinds(cfg: dict) -> list[str]:
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba" for i in range(cfg["num_hidden_layers"])]


def period_runs(cfg: dict) -> list[tuple[str, int]]:
    """(kind, count) of the maximal runs of same-kind layers in one period."""
    runs: list[list] = []
    for kind in layer_kinds(cfg)[: cfg["attn_layer_period"]]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [(kind, count) for kind, count in runs]


def attention(h, p, cfg, scale):
    t = h.shape[0]
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = project(h, p["wq"], scale).reshape(t, heads, hd)
    k = jnp.repeat(project(h, p["wk"], scale).reshape(t, kv_heads, hd), heads // kv_heads, axis=1)
    v = jnp.repeat(project(h, p["wv"], scale).reshape(t, kv_heads, hd), heads // kv_heads, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    logits = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], logits, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1), v).reshape(t, heads * hd)
    return project(out, p["wo"], scale)


def mamba(h, p, cfg, scale):
    t = h.shape[0]
    inner, n, rank = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    taps, eps = cfg["mamba_d_conv"], cfg["rms_norm_eps"]
    xz = project(h, p["in_proj"], scale)
    u, z = xz[:, :inner], xz[:, inner:]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(p["conv_kernel"][k] * padded[k:k + t] for k in range(taps)) + p["conv_bias"])
    dbc = project(u, p["x_proj"], scale)
    delta = rms_norm(dbc[:, :rank], p["dt_norm"]["scale"], eps)
    b = rms_norm(dbc[:, rank:rank + n], p["b_norm"]["scale"], eps)
    c = rms_norm(dbc[:, rank + n:], p["c_norm"]["scale"], eps)
    delta = jax.nn.softplus(delta @ p["dt_proj"]["kernel"] + p["dt_bias"])
    a = -jnp.exp(p["A_log"]).T  # [N, inner]

    def step(state, xs):
        d_t, u_t, b_t, c_t = xs
        state = jnp.exp(d_t[None, :] * a) * state + b_t[:, None] * (d_t * u_t)[None, :]
        return state, c_t @ state

    _, ys = jax.lax.scan(step, jnp.zeros((n, inner), jnp.float32), (delta, u, b, c))
    return project((ys + p["D"] * u) * jax.nn.silu(z), p["out_proj"], scale)


def block(x, kind, p, cfg, scale):
    eps = cfg["rms_norm_eps"]
    if kind == "attention":
        x = x + attention(rms_norm(x, p["attn_norm"]["scale"], eps), p["attn"], cfg, scale)
    else:
        x = x + mamba(rms_norm(x, p["mamba_norm"]["scale"], eps), p["mamba"], cfg, scale)
    h = rms_norm(x, p["mlp_norm"]["scale"], eps)
    gate, up = project(h, p["mlp"]["w1"], scale), project(h, p["mlp"]["w3"], scale)
    return x + project(jax.nn.silu(gate) * up, p["mlp"]["w2"], scale)


def logits(params: dict, tokens, cfg: dict, *, lora_scale: float):
    """[T] int tokens -> [T, vocab] float32 logits."""
    x = params["embed"][tokens]
    layers = params["layers"]

    def one(kind):
        # jax.checkpoint changes memory, not arithmetic: the backward pass
        # recomputes a block instead of keeping its T x N x inner states
        return jax.checkpoint(lambda x_, p_: block(x_, kind, p_, cfg, lora_scale))

    for period in range(cfg["num_hidden_layers"] // cfg["attn_layer_period"]):
        for i, (kind, count) in enumerate(period_runs(cfg)):
            tree = jax.tree.map(lambda a: a[period], layers[f"run{i}_{kind}"])
            if count == 1:
                x = one(kind)(x, tree)
            else:  # the run's layers one after the other: a loop, so that they compile once
                x, _ = jax.lax.scan(lambda x_, p_, kind=kind: (one(kind)(x_, p_), None), x, tree["block"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x @ params["embed"].T  # tied head, as published


def loss(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float):
    """Mean next-token cross-entropy over a [B, T] batch of sequences."""
    params = merge(base, lora)

    def one(tok, tgt):
        logp = jax.nn.log_softmax(logits(params, tok, cfg, lora_scale=lora_scale), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tgt[:, None], axis=-1))

    return jnp.mean(jnp.stack([one(tok, tgt) for tok, tgt in zip(tokens, targets)]))
