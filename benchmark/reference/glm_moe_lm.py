"""Reference latent-attention / sparse-expert LM: the DeepSeek-V3 block as
``zai-org/GLM-4.7-Flash`` runs it (``model_type: glm4_moe_lite``), with LoRA
adapters ``y = xW + (alpha/r) x A B`` on the projections that carry them. Plain
``jax.numpy``, float32, dense masked attention, NO kernel and NO sort: every
expert is applied to ALL rows and weighed by that row's routing weight for it
(zero where the row did not choose it), one expert at a time — a ``lax.scan``
that reads one expert of the bfloat16 bank and widens that one to float32, so
the reference fits the chip beside the program's arguments. Independent of
``p2pfl_tpu/ops/grouped_matmul.py`` and of ``models/transformer.ExpertFFN``.

Layer ``i`` (``x`` is ``[T, hidden]``; norms are RMSNorm with a learned scale,
eps ``rms_norm_eps``; ``H`` heads)::

    x = x + mla(norm1(x));    x = x + ffn_i(norm2(x))
    mla:   cq = norm_q(h W_qa);  q = cq W_qb                      -> [T, H, nope + rope]
           (ckv, kr) = split_{kv_lora_rank, rope}(h W_kva)
           (k_nope, v) = split_{nope, v_head_dim}(norm_kv(ckv) W_kvb -> [T, H, nope + v])
           q = [q_nope | rope(q_rope)];  k = [k_nope | rope(kr)]  (kr: ONE head, shared by all H)
           causal softmax(q k^T / sqrt(nope + rope)) v;  · W_o;  no bias;  rope_scaling null
    ffn_i, i < first_k_dense_replace:  W2( silu(W1 h) * (W3 h) ),  width intermediate_size
    ffn_i, otherwise (topk_method noaux_tc, n_group = topk_group = 1):
           s = sigmoid(h W_g)                                     # float32
           chosen = top-k of (s + e_score_correction_bias)         # the bias CHOOSES only
           w_e = s_e / Σ_{chosen} s · routed_scaling_factor  (norm_topk_prob), 0 for e not chosen
           y = Σ_e w_e · W2_e( silu(W1_e h) * (W3_e h) )  +  shared(h)      # widths moe_intermediate_size
    logits = norm_f(x) E^T,  loss = mean next-token cross-entropy

One departure from the published model, the program's and stated in
``configs/glm47_flash_lora.json``: the output head is the embedding transposed
(published: a separate ``lm_head``). The multi-token-prediction block
(``num_nextn_predict_layers``) is not run, as the Hugging Face modelling code
does not instantiate it. RoPE is the half-split (rotate-half) layout on the
``qk_rope_head_dim`` rotary dims.

Parameters arrive as the program's tree: ``embed``, ``final_norm/scale`` and,
under ``layers``, ``run0_mla_dense`` and ``run1_mla_experts`` with a leading
period axis (of one); a run of several layers holds ``block/...`` with a second
leading axis over its layers. The expert banks lie beside ``layers``, one stack
a run over all its layers: ``experts_w13_run1`` is ``[layers, E, hidden, 2 F]``
(gate | up), ``experts_w2_run1`` ``[layers, E, F, hidden]``
(:func:`layer_trees` takes the tree apart).

The cross-entropy is taken over blocks of ``LOSS_BLOCK`` tokens under
``jax.checkpoint`` (memory, not arithmetic: ``[T, vocab]`` float32 logits with
their cotangent are 5 GB at 4096 x 154,880).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.causal_lm import merge, project, rms_norm, rope

LOSS_BLOCK = 512


def layer_kinds(cfg: dict) -> list[str]:
    dense = cfg["first_k_dense_replace"]
    return ["mla_dense"] * dense + ["mla_experts"] * (cfg["num_hidden_layers"] - dense)


def runs(cfg: dict) -> list[tuple[str, int]]:
    """(kind, count) of the maximal runs of same-kind layers, in order."""
    out: list[list] = []
    for kind in layer_kinds(cfg):
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(kind, count) for kind, count in out]


def layer_trees(params: dict, cfg: dict) -> list[tuple[str, dict]]:
    """(kind, that layer's own parameter subtree) for every layer in order, the
    period and run axes taken off. An expert layer's ``mlp`` gets its run's
    WHOLE stacks as ``experts_w13`` / ``experts_w2`` and its place in them as
    ``bank_layer`` (a slice would be a 1.2 GB copy a layer, kept for the
    backward pass; :func:`experts` reads one expert at a time out of the stack)."""
    out = []
    for i, (kind, count) in enumerate(runs(cfg)):
        run = jax.tree.map(lambda a: a[0], params["layers"][f"run{i}_{kind}"])  # the one period
        for j in range(count):
            layer = run if count == 1 else jax.tree.map(lambda a: a[j], run["block"])
            if kind == "mla_experts":
                bank = {w: params[f"{w}_run{i}"] for w in ("experts_w13", "experts_w2")}
                layer = dict(layer, mlp=dict(layer["mlp"], bank_layer=j, **bank))
            out.append((kind, layer))
    return out


def mla(h, p, cfg, scale):
    t = h.shape[0]
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rot, vd, rank = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    cq = rms_norm(project(h, p["q_a"], scale), p["q_norm"]["scale"], eps)
    q = project(cq, p["q_b"], scale).reshape(t, heads, nope + rot)
    ckv_kr = project(h, p["kv_a"], scale)
    ckv, kr = ckv_kr[:, :rank], ckv_kr[:, rank:]
    kv = project(rms_norm(ckv, p["kv_norm"]["scale"], eps), p["kv_b"], scale).reshape(t, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg["rope_theta"])], axis=-1)
    kr = rope(kr[:, None, :], cfg["rope_theta"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr, (t, heads, rot))], axis=-1)
    v = kv[..., nope:]
    logits = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(nope + rot))
    logits = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], logits, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1), v).reshape(t, heads * vd)
    return project(out, p["o"], scale)


def swiglu(h, p, scale):
    return project(jax.nn.silu(project(h, p["w1"], scale)) * project(h, p["w3"], scale), p["w2"], scale)


def route(h, p, cfg, forced=None):
    """(``[T, k]`` experts the reference chooses, ``[T, E]`` weight of every
    expert for every row: zero where not chosen). ``forced``: ``[T, k]`` experts
    to WEIGH AND USE instead of the reference's own choice (still returned) —
    the comparison's way of holding both sides to one set of assignments where
    bfloat16 activations flipped a near-tie (the scores stay the reference's)."""
    s = jax.nn.sigmoid(h @ p["router"])
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
    used = chosen if forced is None else forced
    picked = jnp.take_along_axis(s, used, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    dense = jnp.sum(jax.nn.one_hot(used, s.shape[-1], dtype=jnp.float32) * weights[..., None], axis=1)
    return chosen, dense


def experts(h, p, cfg, scale, forced=None):
    """``p``: ``router``, ``router_bias``, ``shared`` and the bank — one layer's
    ``experts_w13`` ``[E, hidden, 2 F]`` / ``experts_w2`` ``[E, F, hidden]``, or
    stacks of them ``[layers, E, ...]`` with ``bank_layer`` naming this layer's."""
    f = cfg["moe_intermediate_size"]
    chosen, dense = route(h, p, cfg, forced)
    w13, w2 = p["experts_w13"], p["experts_w2"]
    if w13.ndim == 3:
        w13, w2, layer = w13[None], w2[None], 0
    else:
        layer = p["bank_layer"]

    @jax.checkpoint  # one expert's [T, 2F] activations at a time, in the backward pass too
    def one_expert(h_, w13_, w2_, expert, weight):
        up = h_ @ w13_[layer, expert].astype(jnp.float32)
        return weight[:, None] * ((jax.nn.silu(up[:, :f]) * up[:, f:]) @ w2_[layer, expert].astype(jnp.float32))

    def add(acc, xs):
        return acc + one_expert(h, w13, w2, *xs), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (jnp.arange(dense.shape[1]), dense.T))
    if cfg["n_shared_experts"]:
        y = y + swiglu(h, p["shared"], scale)
    return y, chosen


def block(x, kind, p, cfg, scale, forced=None):
    """(the layer's output, the ``[T, k]`` experts its rows chose — ``None`` for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(rms_norm(x, p["attn_norm"]["scale"], eps), p["attn"], cfg, scale)
    h = rms_norm(x, p["mlp_norm"]["scale"], eps)
    if kind == "mla_dense":
        return x + swiglu(h, p["mlp"], scale), None
    y, chosen = experts(h, p["mlp"], cfg, scale, forced)
    return x + y, chosen


def hidden(params: dict, tokens, cfg: dict, *, lora_scale: float, forced=None):
    """[T] int tokens -> (final-normed ``[T, hidden]``, ``[expert layers, T, k]``
    experts the reference chose). ``forced``: ``[expert layers, T, k]``, see :func:`route`."""
    x = params["embed"][tokens]
    chosen = []
    # jax.checkpoint changes memory, not arithmetic: the backward pass
    # recomputes a block instead of keeping its heads x T x T logits. One
    # jitted function a kind: the run's layers trace and compile once.
    one = {
        kind: jax.jit(jax.checkpoint(lambda x_, p_, f_, kind=kind: block(x_, kind, p_, cfg, lora_scale, f_)))
        for kind in set(layer_kinds(cfg))
    }
    for kind, layer in layer_trees(params, cfg):
        use = None if forced is None or kind == "mla_dense" else forced[len(chosen)]
        x, picked = one[kind](x, layer, use)
        if picked is not None:
            chosen.append(picked)
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x, (jnp.stack(chosen) if chosen else None)


def logits(params: dict, tokens, cfg: dict, *, lora_scale: float):
    """[T] int tokens -> [T, vocab] float32 logits."""
    return hidden(params, tokens, cfg, lora_scale=lora_scale)[0] @ params["embed"].T  # tied head (departure)


def _nll_sum(x, embed, targets):
    """Summed next-token negative log-likelihood, ``LOSS_BLOCK`` rows at a time."""
    t = x.shape[0]
    size = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t

    @jax.checkpoint
    def one(x_, tgt):
        logits_ = x_ @ embed.T
        return jnp.sum(jax.nn.logsumexp(logits_, axis=-1) - jnp.take_along_axis(logits_, tgt[:, None], axis=-1)[:, 0])

    def add(acc, xs):
        return acc + one(*xs), None

    total, _ = jax.lax.scan(add, jnp.float32(0.0), (x.reshape(t // size, size, -1), targets.reshape(t // size, size)))
    return total


def loss_and_routing(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float, forced=None):
    """(mean next-token cross-entropy over a [B, T] batch of sequences, the
    ``[B, expert layers, T, k]`` experts the reference chose). ``forced``:
    the same shape, see :func:`route`."""
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
