"""Reference short-convolution / attention / sparse-expert LM: the block of
``LiquidAI/LFM2-8B-A1B`` (``model_type: lfm2_moe``), with LoRA adapters
``y = xW + (alpha/r) x A B`` on the projections that carry them. Plain
``jax.numpy``, float32, NO kernel and NO sort; its own convolution (shifted
copies, no padding trick), attention dense and masked one key/value head at a
time, experts one at a time over ALL rows (``glm_moe_lm.experts``: the routing
rule of the two published models is the same one). Independent of
``p2pfl_tpu/models/transformer.py``.

Layer ``i`` (``x`` is ``[T, hidden]``; every norm an RMSNorm with a learned
scale, eps ``norm_eps``)::

    x = x + mixer_i(norm1(x));    x = x + ffn_i(norm2(x))
    conv mixer:       [B | C | u] = h W_in (hidden -> 3 hidden, no bias);   g = B * u
                      c[t] = sum_{k < K} w[k] * g[t - (K-1) + k]   per channel, K = conv_L_cache, zeros before the sequence
                      y = (C * c) W_out;   no activation, no bias
    attention mixer:  q = h W_q (H heads), k = h W_k, v = h W_v (KV heads), head width head_dim
                      q = norm_hd(q), k = norm_hd(k)   per head, each with its own scale, BEFORE RoPE
                      rotate-half RoPE at rope_theta on all head_dim dims; causal softmax(q k^T / sqrt(head_dim)) v
                      with each key/value head shared by H / KV query heads;   y = o W_o
    ffn_i, i < num_dense_layers:  W2( silu(W1 h) * (W3 h) ),  width intermediate_size
    ffn_i, otherwise (use_expert_bias, norm_topk_prob):
                      s = sigmoid(h W_g)                                  # float32
                      chosen = top-k of (s + expert_bias)                 # the bias CHOOSES only
                      w_e = s_e / sum_{chosen} s * routed_scaling_factor, 0 for e not chosen
                      y = sum_e w_e W2_e( silu(W1_e h) * (W3_e h) ),  width moe_intermediate_size;  NO shared expert
    logits = norm_f(x) E^T  (tied head),  loss = mean next-token cross-entropy

One departure from the published arithmetic, the program's (its shared
``routing_weights``) and stated in ``configs/lfm2_8b_a1b_lora.json``: the
normaliser's epsilon is ``+ 1e-20`` (published ``+ 1e-6``): four sigmoid scores
sum to about 2, so a weight moves by under 1e-6 relative.

Parameters arrive as the program's tree under ``scan_layers``: ``embed``,
``final_norm/scale``; the leading layers as runs of their own
(``lead0_conv_dense/block/...`` with a leading run-length axis); under
``layers`` one entry a run of the period (``run0_attention_experts/...`` with a
leading period axis, ``run1_conv_experts/block/...`` with period and run-length
axes); the expert banks beside ``layers``, one stack a run over ALL its layers:
``experts_w13_run<i>`` is ``[periods * count, E, hidden, 2 F]`` (gate | up),
``experts_w2_run<i>`` ``[periods * count, E, F, hidden]``; layer ``j`` of the
run in period ``p`` owns bank ``p * count + j``.

Runs and periods are ``lax.scan``s over those axes with a ``jax.checkpoint`` a
layer, so compile time and memory do not grow with depth (memory, not
arithmetic); the cross-entropy is taken over blocks of tokens likewise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import glm_moe_lm
from benchmark.reference.causal_lm import merge, project, rms_norm, rope
from benchmark.reference.glm_moe_lm import _nll_sum, swiglu

# program's layer kind -> (mixer, feed-forward)
KINDS = {
    "conv_dense": ("conv", "dense"), "conv_experts": ("conv", "experts"),
    "attention": ("attention", "dense"), "attention_experts": ("attention", "experts"),
}


def layer_kinds(cfg: dict) -> list[str]:
    """The program's kind of every layer that is run, from ``layer_types`` and ``num_dense_layers``."""
    out = []
    for i, mixer in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        dense = i < cfg["num_dense_layers"]
        if mixer == "conv":
            out.append("conv_dense" if dense else "conv_experts")
        elif mixer == "full_attention":
            out.append("attention" if dense else "attention_experts")
        else:
            raise ValueError(f"layer_types[{i}] = {mixer!r}: 'conv' or 'full_attention'")
    return out


def runs(kinds) -> list[tuple[str, int]]:
    """(kind, count) of the maximal runs of same-kind layers, in order."""
    out: list[list] = []
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(kind, count) for kind, count in out]


def stack(cfg: dict) -> tuple[list[str], list[str], int]:
    """(leading kinds, one period's kinds, periods): the dense layers lead, and
    what follows them is the shortest pattern that repeats a whole number of times."""
    kinds = layer_kinds(cfg)
    leading, rest = kinds[:cfg["num_dense_layers"]], kinds[cfg["num_dense_layers"]:]
    for size in range(1, len(rest) + 1):
        if len(rest) % size == 0 and rest == rest[:size] * (len(rest) // size):
            return leading, rest[:size], len(rest) // size
    raise ValueError("no expert layer follows the dense ones")


def layer_trees(params: dict, cfg: dict) -> list[tuple[str, dict]]:
    """(kind, that layer's own parameter subtree) for every layer in order, the
    period and run axes taken off. An expert layer's ``mlp`` gets its run's
    WHOLE stacks as ``experts_w13`` / ``experts_w2`` and its place in them as
    ``bank_layer`` (``glm_moe_lm.experts`` reads one expert at a time out of the stack)."""
    leading, pattern, periods = stack(cfg)
    out = []
    for i, (kind, count) in enumerate(runs(leading)):
        run = params[f"lead{i}_{kind}"]
        out += [(kind, run if count == 1 else jax.tree.map(lambda a: a[j], run["block"])) for j in range(count)]
    for period in range(periods):
        for i, (kind, count) in enumerate(runs(pattern)):
            run = jax.tree.map(lambda a: a[period], params["layers"][f"run{i}_{kind}"])
            for j in range(count):
                layer = run if count == 1 else jax.tree.map(lambda a: a[j], run["block"])
                if KINDS[kind][1] == "experts":
                    bank = {w: params[f"{w}_run{i}"] for w in ("experts_w13", "experts_w2")}
                    layer = dict(layer, mlp=dict(layer["mlp"], bank_layer=period * count + j, **bank))
                out.append((kind, layer))
    return out


def gated_conv(bcu, taps):
    """``C * conv(B * u)`` of ``bcu`` = ``[B | C | u]`` (``[T, 3 hidden]``);
    ``taps`` ``[K, hidden]``: tap ``k`` weighs the input ``K - 1 - k`` positions back."""
    t = bcu.shape[0]
    b, c, u = jnp.split(bcu, 3, axis=-1)
    g = b * u
    conv = jnp.zeros_like(g)
    for k in range(taps.shape[0]):
        back = taps.shape[0] - 1 - k
        conv = conv + taps[k] * jnp.concatenate([jnp.zeros((back, g.shape[1]), g.dtype), g[:t - back]])
    return c * conv


def short_conv(h, p, cfg, scale):
    if p["conv_kernel"].shape[0] != cfg["conv_L_cache"]:
        raise ValueError(f"{p['conv_kernel'].shape[0]} taps in the tree, conv_L_cache {cfg['conv_L_cache']}")
    return project(gated_conv(project(h, p["in_proj"], scale), p["conv_kernel"]), p["out_proj"], scale)


def attention(h, p, cfg, scale):
    t = h.shape[0]
    heads, kv_heads, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["norm_eps"]
    q = project(h, p["wq"], scale).reshape(t, heads, hd)
    k = project(h, p["wk"], scale).reshape(t, kv_heads, hd)
    v = project(h, p["wv"], scale).reshape(t, kv_heads, hd)
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), cfg["rope_theta"])
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint  # one key/value head's [H / KV, T, T] logits at a time, in the backward pass too
    def one_kv_head(xs):
        q_, k_, v_ = xs  # [T, H / KV, hd], [T, hd], [T, hd]: query heads r * KV.. share this key/value head
        logits = jnp.einsum("qrd,kd->rqk", q_, k_) / jnp.sqrt(jnp.float32(hd))
        logits = jnp.where(mask[None], logits, -jnp.inf)
        return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(logits, axis=-1), v_)

    # query head i reads key/value head i // (H / KV): what repeating k and v H / KV times gives
    grouped = q.reshape(t, kv_heads, heads // kv_heads, hd).transpose(1, 0, 2, 3)
    out = jax.lax.map(one_kv_head, (grouped, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return project(out.transpose(1, 0, 2, 3).reshape(t, heads * hd), p["wo"], scale)


def experts(h, p, cfg, scale, forced=None):
    """``glm_moe_lm.experts`` under this configuration's keys: no shared expert."""
    return glm_moe_lm.experts(h, p, dict(cfg, n_shared_experts=0), scale, forced)


def block(x, kind, p, cfg, scale, forced=None):
    """(the layer's output, the ``[T, k]`` experts its rows chose — ``None`` for a dense layer)."""
    mixer, ffn = KINDS[kind]
    eps = cfg["norm_eps"]
    if mixer == "conv":
        x = x + short_conv(rms_norm(x, p["conv_norm"]["scale"], eps), p["conv"], cfg, scale)
    else:
        x = x + attention(rms_norm(x, p["attn_norm"]["scale"], eps), p["attn"], cfg, scale)
    h = rms_norm(x, p["mlp_norm"]["scale"], eps)
    if ffn == "dense":
        return x + swiglu(h, p["mlp"], scale), None
    y, chosen = experts(h, p["mlp"], cfg, scale, forced)
    return x + y, chosen


def _run(x, kind, count, p, cfg, scale, bank=None, first=None, forced=None):
    """One run of ``count`` same-kind layers on its stacked parameters ``p``:
    (output, ``[count, T, k]`` experts chosen or ``None``). ``bank`` / ``first``:
    an expert run's whole stacks and the index of its first layer in them;
    ``forced``: ``[count, T, k]`` or ``None``."""
    is_experts = KINDS[kind][1] == "experts"

    @jax.checkpoint  # the backward pass recomputes a layer instead of keeping its interior
    def one(x_, layer, index, forced_):
        if is_experts:
            layer = dict(layer, mlp=dict(layer["mlp"], bank_layer=index, **bank))
        return block(x_, kind, layer, cfg, scale, forced_)

    if count == 1:
        x, chosen = one(x, p, first, None if forced is None else forced[0])
        return x, (chosen[None] if is_experts else None)

    def step(x_, xs):
        layer, j, forced_ = xs
        return one(x_, layer, None if first is None else first + j, forced_)

    return jax.lax.scan(step, x, (p["block"], jnp.arange(count), forced))


def hidden(params: dict, tokens, cfg: dict, *, lora_scale: float, forced=None):
    """[T] int tokens -> (final-normed ``[T, hidden]``, ``[expert layers, T, k]``
    experts the reference chose, in layer order). ``forced``: the same shape,
    see ``glm_moe_lm.route``."""
    leading, pattern, periods = stack(cfg)
    x = params["embed"][tokens]
    for i, (kind, count) in enumerate(runs(leading)):
        x, _ = _run(x, kind, count, params[f"lead{i}_{kind}"], cfg, lora_scale)
    pattern_runs = runs(pattern)
    expert_runs = [(i, kind, count) for i, (kind, count) in enumerate(pattern_runs) if KINDS[kind][1] == "experts"]
    per_period = sum(count for _, _, count in expert_runs)
    banks = {i: {w: params[f"{w}_run{i}"] for w in ("experts_w13", "experts_w2")} for i, _, _ in expert_runs}
    if forced is not None:
        forced = forced.reshape(periods, per_period, *forced.shape[1:])

    def period(x_, xs):
        index, layers, forced_ = xs
        chosen, at = [], 0
        for i, (kind, count) in enumerate(pattern_runs):
            p = layers[f"run{i}_{kind}"]
            if i not in banks:
                x_, _ = _run(x_, kind, count, p, cfg, lora_scale)
                continue
            use = None if forced_ is None else forced_[at:at + count]
            x_, picked = _run(x_, kind, count, p, cfg, lora_scale, banks[i], index * count, use)
            chosen.append(picked)
            at += count
        return x_, jnp.concatenate(chosen)

    x, chosen = jax.lax.scan(period, x, (jnp.arange(periods), params["layers"], forced))
    x = rms_norm(x, params["final_norm"]["scale"], cfg["norm_eps"])
    return x, chosen.reshape(periods * per_period, *chosen.shape[2:])


def loss_and_routing(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float, forced=None):
    """(mean next-token cross-entropy over a [B, T] batch of sequences, the
    ``[B, expert layers, T, k]`` experts the reference chose). ``forced``: the
    same shape, see ``glm_moe_lm.route``."""
    params = merge(base, lora)
    losses, chosen = [], []
    for b, (tok, tgt) in enumerate(zip(tokens, targets)):
        x, picked = hidden(params, tok, cfg, lora_scale=lora_scale, forced=None if forced is None else forced[b])
        losses.append(_nll_sum(x, params["embed"], tgt) / tok.shape[0])
        chosen.append(picked)
    return jnp.mean(jnp.stack(losses)), jnp.stack(chosen)


def loss(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float):
    """Mean next-token cross-entropy over a [B, T] batch of sequences."""
    return loss_and_routing(lora, base, tokens, targets, cfg, lora_scale=lora_scale)[0]
