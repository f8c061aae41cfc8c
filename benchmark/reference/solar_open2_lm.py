"""Reference hybrid linear-attention sparse-expert LM: the block of
``upstage/Solar-Open2-250B`` (``model_type: solar_open2``), with LoRA adapters
``y = xW + (alpha/r) x A B`` on the projections that carry them. Plain
``jax.numpy``, float32, NO kernel: the delta rule one TOKEN at a time (not the
chunked form the program runs), attention a block of queries at a time under a
mask built from positions, experts one at a time over ALL rows, the head in
blocks. Independent of ``p2pfl_tpu/models/transformer.py`` and of
``p2pfl_tpu/ops/delta_rule.py``.

``N(x; g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``. ``x0 = E[tokens]``.
Layer ``i`` (``x`` is ``[T, hidden]``)::

    x = x + mixer_i(N(x; g1));    x = x + moe_i(N(x; g2))
    mixer_i, i in gqa_layers (GQA, NoPE, gated):
        q = u W_q (H heads of hd), k = u W_k, v = u W_v (KV heads), gate = u W_g (H * hd);  NO rotation
        softmax(q k^T / sqrt(hd)), causal, in float32; H / KV query heads share a key/value head
        y = ((P v) * sigmoid(gate)) W_o
    mixer_i, otherwise (KDA: gated delta rule, channel-wise decay), per head h of d channels:
        conv4: a causal depthwise convolution, 4 taps, no bias, one per channel of the H * d projection
        q = L2N(silu(conv4(u W_q)))_h / sqrt(d);  k = L2N(silu(conv4(u W_k)))_h;  v = silu(conv4(u W_v))_h
        log a = -exp(A_log_h) * softplus((u W_fa) W_fb + dt_bias)_h        (d decays, one a key channel)
        beta = 2 * sigmoid(u W_b)_h                                          (kda_allow_neg_eigval)
        S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T,  S_0 = 0;   o_t = S_t^T q_t
        y = concat_h[N_d(o_t; w_norm) * sigmoid((u W_ga) W_gb + b_g)_h] W_o
    moe_i (every layer: first_k_dense_replace = 0):
        s = sigmoid(h W_r) (float32, one score a routed expert of the WHOLE model)
        chosen = top-k of (s + e_score_correction_bias)              # the bias CHOOSES only
        w_e = s_e / (sum_{chosen} s + 1e-20) * routed_scaling_factor  # norm_topk_prob: over ALL k chosen
        y = sum_{e chosen AND HELD} w_e W2_e( silu(W1_e h) * (W3_e h) )  +  shared(h)
    logits = N(x; g_f) W_head^T,  W_head = lm_head [vocab, hidden] (untied);  loss = mean next-token CE

``L2N(x) = x / sqrt(sum x^2 + 1e-6)`` over a head's channels. A HELD SHARE
(``share(cfg)``), the vocabulary slice and the parameter tree are as in
``afmoe_lm.py`` (whose ``experts`` and ``attend`` this file calls): experts
``first_expert ..`` of the router's ``router_experts``; the tree under
``scan_layers``, one entry a run of the period under ``layers`` (a period
``[GQA, KDA, KDA, KDA]`` is ``run0_full_experts`` and ``run1_kda_experts``, the
latter with a run-length axis), the banks beside it.

The delta rule runs in blocks of ``TOKEN_BLOCK`` tokens under ``jax.checkpoint``:
the gradient keeps one ``[H, d, d]`` state a block (268 MB a layer at 4096
tokens and 64 heads of 128) and recomputes the block's token states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import afmoe_lm
from benchmark.reference.causal_lm import merge, project, rms_norm
from benchmark.reference.glm_moe_lm import _nll_sum
from benchmark.reference.lfm2_moe_lm import runs

# program's layer kind -> mixer
KINDS = {"full_experts": ("gqa", "experts"), "kda_experts": ("kda", "experts")}
TOKEN_BLOCK = 64
_HI = jax.lax.Precision.HIGHEST


def layer_kinds(cfg: dict) -> list[str]:
    """The program's kind of every layer that is run: GQA on ``gqa_layers``, KDA elsewhere."""
    if cfg["first_k_dense_replace"] != 0:
        raise ValueError("every layer of this family is an expert layer (first_k_dense_replace 0)")
    return ["full_experts" if i in cfg["gqa_layers"] else "kda_experts" for i in range(cfg["num_hidden_layers"])]


def stack(cfg: dict) -> tuple[list[str], int]:
    """(one period's kinds, periods): the shortest pattern that repeats a whole number of times."""
    kinds = layer_kinds(cfg)
    for size in range(1, len(kinds) + 1):
        if len(kinds) % size == 0 and kinds == kinds[:size] * (len(kinds) // size):
            return kinds[:size], len(kinds) // size
    raise AssertionError("unreachable")


def share(cfg: dict) -> tuple[int, int, int]:
    """(first expert held, experts held, the router's width). ``n_routed_experts``
    is what the BANK holds; a configuration that holds a share says so under ``share``."""
    part = cfg.get("share", {})
    return part.get("first_expert", 0), cfg["n_routed_experts"], part.get("router_experts", cfg["n_routed_experts"])


def _expert_cfg(cfg: dict) -> dict:
    """The keys ``afmoe_lm.experts`` reads, under its names."""
    first, held, width = share(cfg)
    return {
        "moe_intermediate_size": cfg["moe_intermediate_size"], "num_experts_per_tok": cfg["num_experts_per_tok"],
        "route_scale": cfg["routed_scaling_factor"], "num_shared_experts": cfg["n_shared_experts"],
        "num_experts": held, "share": {"first_expert": first, "router_experts": width},
    }


def layer_trees(params: dict, cfg: dict) -> list[tuple[str, dict]]:
    """(kind, that layer's own parameter subtree) for every layer in order, as
    ``afmoe_lm.layer_trees`` gives them (``mlp`` with its run's whole stacks and
    ``bank_layer``)."""
    pattern, periods = stack(cfg)
    out = []
    for period in range(periods):
        for i, (kind, count) in enumerate(runs(pattern)):
            run = jax.tree.map(lambda a: a[period], params["layers"][f"run{i}_{kind}"])
            for j in range(count):
                layer = run if count == 1 else jax.tree.map(lambda a: a[j], run["block"])
                bank = {w: params[f"{w}_run{i}"] for w in ("experts_w13", "experts_w2")}
                out.append((kind, dict(layer, mlp=dict(layer["mlp"], bank_layer=period * count + j, **bank))))
    return out


def gqa(h, p, cfg, scale):
    t = h.shape[0]
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = project(h, p["wq"], scale).reshape(t, heads, hd)
    k = project(h, p["wk"], scale).reshape(t, kv_heads, hd)
    v = project(h, p["wv"], scale).reshape(t, kv_heads, hd)
    out = afmoe_lm.attend(q, k, v).reshape(t, heads * hd)  # use_rope false: no rotation
    return project(out * jax.nn.sigmoid(project(h, p["wg"], scale)), p["wo"], scale)


def conv4(u, taps):
    """``out[t] = Σ_j taps[j] · u[t − (K−1) + j]``, zeros before the start; ``u`` ``[T, C]``, ``taps`` ``[K, C]``."""
    k, t = taps.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u])
    return sum(taps[j] * padded[j:j + t] for j in range(k))


def l2n(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def recurrence(q, k, v, log_a, beta, state_dtype=jnp.float32):
    """The delta rule token by token: ``q, k, log_a`` ``[T, H, dk]``, ``v``
    ``[T, H, dv]``, ``beta`` ``[T, H]`` -> ``o`` ``[T, H, dv]``. ``state_dtype``
    rounds the state every token (the planted fault's comparison)."""
    t, heads, dk = k.shape
    size = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    def token(s, xs):  # s: [H, dk, dv]
        q_t, k_t, v_t, la_t, b_t = xs
        s = jnp.exp(la_t)[:, :, None] * s
        s = s - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum("hk,hkv->hv", k_t, s, precision=_HI)[:, None, :]
        s = s + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        s = s.astype(state_dtype).astype(jnp.float32)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=_HI)

    @jax.checkpoint  # one state a block is kept; the block's token states are recomputed
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    blocked = tuple(x.reshape(t // size, size, *x.shape[1:]) for x in (q, k, v, log_a, beta))
    _, o = jax.lax.scan(block, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), blocked)
    return o.reshape(t, heads, v.shape[-1])


def kda_inputs(h, p, cfg, scale):
    """(q, k, v, log a, beta) of a KDA layer on its normed input ``h`` ``[T, hidden]``, as ``recurrence`` takes them."""
    t = h.shape[0]
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    split = lambda x: x.reshape(t, heads, d)  # noqa: E731
    q = l2n(split(jax.nn.silu(conv4(project(h, p["wq"], scale), p["q_conv"])))) / jnp.sqrt(jnp.float32(d))
    k = l2n(split(jax.nn.silu(conv4(project(h, p["wk"], scale), p["k_conv"]))))
    v = split(jax.nn.silu(conv4(project(h, p["wv"], scale), p["v_conv"])))
    decay = project(project(h, p["f_a"], scale), p["f_b"], scale) + p["dt_bias"]
    log_a = -jnp.exp(p["A_log"])[None, :, None] * split(jax.nn.softplus(decay))
    beta = jax.nn.sigmoid(project(h, p["b_proj"], scale)) * (2.0 if cfg["kda_allow_neg_eigval"] else 1.0)
    return q, k, v, log_a, beta


def kda(h, p, cfg, scale, state_dtype=jnp.float32):
    t = h.shape[0]
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    o = recurrence(*kda_inputs(h, p, cfg, scale), state_dtype=state_dtype)
    gate = jax.nn.sigmoid(project(project(h, p["g_a"], scale), p["g_b"], scale) + p["g_bias"]).reshape(t, heads, d)
    y = rms_norm(o, p["o_norm"], cfg["rms_norm_eps"]) * gate
    return project(y.reshape(t, heads * d), p["wo"], scale)


def experts(h, p, cfg, scale, forced=None):
    """``afmoe_lm.experts`` under this configuration's keys: (output, ``[T, k]`` chosen)."""
    return afmoe_lm.experts(h, p, _expert_cfg(cfg), scale, forced)


def block(x, kind, p, cfg, scale, forced=None):
    """(the layer's output, the ``[T, k]`` experts its rows chose)."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, p["attn_norm"]["scale"], eps)
    x = x + (gqa(u, p["attn"], cfg, scale) if KINDS[kind][0] == "gqa" else kda(u, p["attn"], cfg, scale))
    y, chosen = experts(rms_norm(x, p["mlp_norm"]["scale"], eps), p["mlp"], cfg, scale, forced)
    return x + y, chosen


def _run(x, kind, count, p, cfg, scale, bank, first, forced=None):
    """One run of ``count`` same-kind layers on its stacked parameters ``p``:
    (output, ``[count, T, k]`` experts chosen). ``forced``: ``[count, T, k]`` or ``None``."""

    @jax.checkpoint  # the backward pass recomputes a layer instead of keeping its interior
    def one(x_, layer, index, forced_):
        layer = dict(layer, mlp=dict(layer["mlp"], bank_layer=index, **bank))
        return block(x_, kind, layer, cfg, scale, forced_)

    if count == 1:
        x, chosen = one(x, p, first, None if forced is None else forced[0])
        return x, chosen[None]

    def step(x_, xs):
        layer, j, forced_ = xs
        return one(x_, layer, first + j, forced_)

    return jax.lax.scan(step, x, (p["block"], jnp.arange(count), forced))


def hidden(params: dict, tokens, cfg: dict, *, lora_scale: float, forced=None):
    """[T] int tokens -> (final-normed ``[T, hidden]``, ``[layers, T, k]``
    experts the reference chose, in layer order). ``forced``: the same shape,
    ``afmoe_lm.route`` says why."""
    pattern, periods = stack(cfg)
    x = params["embed"][tokens]
    pattern_runs = runs(pattern)
    banks = {i: {w: params[f"{w}_run{i}"] for w in ("experts_w13", "experts_w2")} for i in range(len(pattern_runs))}
    if forced is not None:
        forced = forced.reshape(periods, len(pattern), *forced.shape[1:])

    def period(x_, xs):
        index, layers, forced_ = xs
        chosen, at = [], 0
        for i, (kind, count) in enumerate(pattern_runs):
            use = None if forced_ is None else forced_[at:at + count]
            x_, picked = _run(x_, kind, count, layers[f"run{i}_{kind}"], cfg, lora_scale, banks[i], index * count, use)
            chosen.append(picked)
            at += count
        return x_, jnp.concatenate(chosen)

    x, chosen = jax.lax.scan(period, x, (jnp.arange(periods), params["layers"], forced))
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x, chosen.reshape(periods * len(pattern), *chosen.shape[2:])


def loss_and_routing(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float, forced=None):
    """(mean next-token cross-entropy over a [B, T] batch of sequences, the
    ``[B, layers, T, k]`` experts the reference chose). ``forced``: the same shape."""
    params = merge(base, lora)
    losses, chosen = [], []
    for b, (tok, tgt) in enumerate(zip(tokens, targets)):
        x, picked = hidden(params, tok, cfg, lora_scale=lora_scale, forced=None if forced is None else forced[b])
        losses.append(_nll_sum(x, params["lm_head"], tgt) / tok.shape[0])
        chosen.append(picked)
    return jnp.mean(jnp.stack(losses)), jnp.stack(chosen)


def logits(params: dict, tokens, cfg: dict, *, lora_scale: float):
    """[T] int tokens -> [T, vocab] float32 logits."""
    return hidden(params, tokens, cfg, lora_scale=lora_scale)[0] @ params["lm_head"].T
