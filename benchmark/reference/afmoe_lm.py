"""Reference sliding-window / full-attention sparse-expert LM: the block of
``arcee-ai/Trinity-Mini`` (``model_type: afmoe``), with LoRA adapters
``y = xW + (alpha/r) x A B`` on the projections that carry them. Plain
``jax.numpy``, float32, NO kernel and NO sort: attention a block of queries at a
time under an explicit ``[q, k]`` mask built from positions, experts one at a
time over ALL rows, the head in blocks. Independent of
``p2pfl_tpu/models/transformer.py`` and of ``ops/flash_attention.py``.

``N(x; g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``. ``x0 = E[tokens] *
sqrt(hidden_size)`` (``mup_enabled``). Layer ``i`` (``x`` is ``[T, hidden]``;
four norms a layer: the sandwich)::

    x = x + N(attn_i(N(x; g1)); g2);    x = x + N(ffn_i(N(x; g3)); g4)
    attn_i(u):  q = u W_q (H heads), k = u W_k, v = u W_v (KV heads), gate = u W_g (H * head_dim)
                q = N_head(q; g_q), k = N_head(k; g_k)   over the head_dim dims of a head, one scale vector each
                layer_types[i] == "sliding_attention": rotate-half RoPE at rope_theta on all head_dim dims,
                    query i sees key j  iff  j <= i  and  j > i - sliding_window   (sliding_window keys, itself included)
                layer_types[i] == "full_attention":    NO rotation,  query i sees key j iff j <= i
                softmax(q k^T / sqrt(head_dim)) in float32, H / KV query heads share a key/value head
                y = ((P v) * sigmoid(gate)) W_o;   no bias anywhere
    ffn_i, i < num_dense_layers:  W2( silu(W1 h) * (W3 h) ),  width intermediate_size
    ffn_i, otherwise:  s = sigmoid(h W_r)  (float32, one score a routed expert of the WHOLE model)
                chosen = top-k of (s + expert_bias)                  # the bias CHOOSES only
                w_e = s_e / (sum_{chosen} s + 1e-20) * route_scale   # route_norm: over ALL k chosen
                y = sum_{e chosen AND HELD} w_e W2_e( silu(W1_e h) * (W3_e h) )  +  shared(h)
    logits = N(x; g_f) W_head^T,  W_head = lm_head [vocab, hidden], NOT the embedding;  loss = mean next-token CE

A HELD SHARE (``share(cfg)``: ``first_expert``, experts held, the router's
width): the bank holds experts ``first_expert .. first_expert + held - 1`` of
the model's; an assignment to any other expert contributes NOTHING here — no
exchange, no stand-in — while choice and weights are the whole model's. Uncut
(``held`` = the router's width) it is the published layer. The vocabulary slice
is the configuration's ``vocab_size`` rows of embedding and head.

Parameters arrive as the program's tree under ``scan_layers``: ``embed``,
``lm_head``, ``final_norm/scale``; the leading layers as runs of their own
(``lead0_swa_dense/block/...`` with a leading run-length axis); under ``layers``
one entry a run of the period with a leading period axis (a run of several
layers: ``block/...`` with a run-length axis too); the expert banks beside
``layers``, one stack a run over ALL its layers: ``experts_w13_run<i>`` is
``[periods * count, held, hidden, 2 F]`` (gate | up), ``experts_w2_run<i>``
``[periods * count, held, F, hidden]``; layer ``j`` of the run in period ``p``
owns bank ``p * count + j``.

Runs and periods are ``lax.scan``s over those axes with a ``jax.checkpoint`` a
layer (memory, not arithmetic); so are the blocks of queries, the experts and
the head's blocks of tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.causal_lm import merge, project, rms_norm, rope
from benchmark.reference.glm_moe_lm import _nll_sum, swiglu
from benchmark.reference.lfm2_moe_lm import runs

# program's layer kind -> (mixer, feed-forward)
KINDS = {
    "swa_dense": ("sliding", "dense"), "swa_experts": ("sliding", "experts"), "full_experts": ("full", "experts"),
}
QUERY_BLOCK = 512  # queries a block of attention: [H / KV, 512, T] logits a key/value head


def layer_kinds(cfg: dict) -> list[str]:
    """The program's kind of every layer that is run, from ``layer_types`` and ``num_dense_layers``."""
    mixers = {"sliding_attention": "swa", "full_attention": "full"}
    out = []
    for i, mixer in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        if mixer not in mixers:
            raise ValueError(f"layer_types[{i}] = {mixer!r}: 'sliding_attention' or 'full_attention'")
        kind = f"{mixers[mixer]}_{'dense' if i < cfg['num_dense_layers'] else 'experts'}"
        if kind not in KINDS:
            raise ValueError(f"layer {i} would be {kind!r}: the program has no such kind (has: {sorted(KINDS)})")
        out.append(kind)
    return out


def stack(cfg: dict) -> tuple[list[str], list[str], int]:
    """(leading kinds, one period's kinds, periods): the dense layers lead, and
    what follows them is the shortest pattern that repeats a whole number of times."""
    kinds = layer_kinds(cfg)
    leading, rest = kinds[:cfg["num_dense_layers"]], kinds[cfg["num_dense_layers"]:]
    for size in range(1, len(rest) + 1):
        if len(rest) % size == 0 and rest == rest[:size] * (len(rest) // size):
            return leading, rest[:size], len(rest) // size
    raise ValueError("no expert layer follows the dense ones")


def share(cfg: dict) -> tuple[int, int, int]:
    """(first expert held, experts held, the router's width). ``num_experts`` is
    what the BANK holds; a configuration that holds a share says so under ``share``."""
    part = cfg.get("share", {})
    return part.get("first_expert", 0), cfg["num_experts"], part.get("router_experts", cfg["num_experts"])


def layer_trees(params: dict, cfg: dict) -> list[tuple[str, dict]]:
    """(kind, that layer's own parameter subtree) for every layer in order, the
    period and run axes taken off. An expert layer's ``mlp`` gets its run's
    WHOLE stacks as ``experts_w13`` / ``experts_w2`` and its place in them as
    ``bank_layer`` (:func:`experts` reads one expert at a time out of the stack)."""
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


def visible(rows, cols, window=None):
    """``[q, k]`` bool from positions: causal, and under a sliding ``window``
    only the ``window`` keys up to the query itself."""
    mask = cols[None, :] <= rows[:, None]
    return mask if window is None else mask & (cols[None, :] > rows[:, None] - window)


def attend(q, k, v, window=None):
    """Masked softmax attention of ``q`` ``[T, H, hd]`` over ``k``, ``v``
    ``[T, KV, hd]``, a block of queries of one key/value head at a time; query
    head ``i`` reads key/value head ``i // (H / KV)``. ``[T, H, hd]``."""
    t, heads, hd = q.shape
    kv_heads = k.shape[1]
    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    cols = jnp.arange(t)

    @jax.checkpoint  # one block's [H / KV, size, T] logits at a time, in the backward pass too
    def one(q_, rows, keys, values, head):  # [size, H / KV, hd], [size], [KV, T, hd] twice, the key/value head
        logits = jnp.einsum("qrd,kd->rqk", q_, keys[head]) / jnp.sqrt(jnp.float32(hd))
        logits = jnp.where(visible(rows, cols, window)[None], logits, -jnp.inf)
        return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(logits, axis=-1), values[head])

    blocks = t // size
    keys, values = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    grouped = q.reshape(blocks, size, kv_heads, heads // kv_heads, hd).transpose(2, 0, 1, 3, 4)  # [KV, blocks, size, r, hd]
    rows = jnp.broadcast_to(cols.reshape(blocks, size), (kv_heads, blocks, size))
    head = jnp.repeat(jnp.arange(kv_heads), blocks)
    flat = lambda a: a.reshape(kv_heads * blocks, *a.shape[2:])  # noqa: E731
    out = jax.lax.map(lambda xs: one(xs[0], xs[1], keys, values, xs[2]), (flat(grouped), flat(rows), head))
    return out.reshape(kv_heads, t, heads // kv_heads, hd).transpose(1, 0, 2, 3).reshape(t, heads, hd)


def attention(h, p, cfg, scale, mixer: str):
    t = h.shape[0]
    heads, kv_heads, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    q = rms_norm(project(h, p["wq"], scale).reshape(t, heads, hd), p["q_norm"]["scale"], eps)
    k = rms_norm(project(h, p["wk"], scale).reshape(t, kv_heads, hd), p["k_norm"]["scale"], eps)
    v = project(h, p["wv"], scale).reshape(t, kv_heads, hd)
    window = None
    if mixer == "sliding":  # a full layer is neither rotated nor windowed
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    out = attend(q, k, v, window).reshape(t, heads * hd)
    return project(out * jax.nn.sigmoid(project(h, p["wg"], scale)), p["wo"], scale)


def route(h, p, cfg, forced=None):
    """(``[T, k]`` experts the reference chooses, ``[T, router width]`` weight of
    every expert of the WHOLE model for every row: zero where not chosen).
    ``forced``: ``[T, k]`` experts to WEIGH AND USE instead (``glm_moe_lm.route``
    says why); the scores stay the reference's. Normalised over all ``k``."""
    s = jax.nn.sigmoid(h @ p["router"])
    _, chosen = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
    used = chosen if forced is None else forced
    picked = jnp.take_along_axis(s, used, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * cfg["route_scale"]
    return chosen, jnp.sum(jax.nn.one_hot(used, s.shape[-1], dtype=jnp.float32) * weights[..., None], axis=1)


def experts(h, p, cfg, scale, forced=None):
    """``p``: ``router`` (as wide as the whole model's experts), ``router_bias``,
    ``shared`` and the bank of the HELD experts — one layer's ``experts_w13``
    ``[held, hidden, 2 F]`` / ``experts_w2`` ``[held, F, hidden]``, or stacks of
    them ``[layers, held, ...]`` with ``bank_layer`` naming this layer's. Held
    expert ``e`` of the bank is expert ``first_expert + e`` of the router."""
    f = cfg["moe_intermediate_size"]
    first, held, width = share(cfg)
    chosen, dense = route(h, p, cfg, forced)
    if dense.shape[1] != width:
        raise ValueError(f"the router scores {dense.shape[1]} experts, the configuration says {width}")
    w13, w2 = p["experts_w13"], p["experts_w2"]
    if w13.ndim == 3:
        w13, w2, layer = w13[None], w2[None], 0
    else:
        layer = p["bank_layer"]
    if w13.shape[1] != held:
        raise ValueError(f"the bank holds {w13.shape[1]} experts, the configuration says {held}")

    @jax.checkpoint  # one expert's [T, 2F] activations at a time, in the backward pass too
    def one_expert(h_, w13_, w2_, expert, weight):
        up = h_ @ w13_[layer, expert].astype(jnp.float32)
        return weight[:, None] * ((jax.nn.silu(up[:, :f]) * up[:, f:]) @ w2_[layer, expert].astype(jnp.float32))

    def add(acc, xs):
        return acc + one_expert(h, w13, w2, *xs), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (jnp.arange(held), dense[:, first:first + held].T))
    if cfg["num_shared_experts"]:
        y = y + swiglu(h, p["shared"], scale)
    return y, chosen


def block(x, kind, p, cfg, scale, forced=None):
    """(the layer's output, the ``[T, k]`` experts its rows chose — ``None`` for a dense layer)."""
    mixer, ffn = KINDS[kind]
    eps = cfg["rms_norm_eps"]
    a = attention(rms_norm(x, p["attn_norm"]["scale"], eps), p["attn"], cfg, scale, mixer)
    x = x + rms_norm(a, p["attn_post_norm"]["scale"], eps)
    h = rms_norm(x, p["mlp_norm"]["scale"], eps)
    if ffn == "dense":
        y, chosen = swiglu(h, p["mlp"], scale), None
    else:
        y, chosen = experts(h, p["mlp"], cfg, scale, forced)
    return x + rms_norm(y, p["mlp_post_norm"]["scale"], eps), chosen


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
    see :func:`route`."""
    leading, pattern, periods = stack(cfg)
    x = params["embed"][tokens]
    if cfg["mup_enabled"]:
        x = x * jnp.sqrt(jnp.float32(cfg["hidden_size"]))
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
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return x, chosen.reshape(periods * per_period, *chosen.shape[2:])


def head_matrix(params: dict, cfg: dict):
    """``[vocab, hidden]``: ``lm_head``, a matrix of its own (``tie_word_embeddings`` false)."""
    return params["embed"] if cfg["tie_word_embeddings"] else params["lm_head"]


def logits(params: dict, tokens, cfg: dict, *, lora_scale: float):
    """[T] int tokens -> [T, vocab] float32 logits."""
    return hidden(params, tokens, cfg, lora_scale=lora_scale)[0] @ head_matrix(params, cfg).T


def loss_and_routing(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float, forced=None):
    """(mean next-token cross-entropy over a [B, T] batch of sequences, the
    ``[B, expert layers, T, k]`` experts the reference chose). ``forced``: the
    same shape, see :func:`route`."""
    params = merge(base, lora)
    losses, chosen = [], []
    for b, (tok, tgt) in enumerate(zip(tokens, targets)):
        x, picked = hidden(params, tok, cfg, lora_scale=lora_scale, forced=None if forced is None else forced[b])
        losses.append(_nll_sum(x, head_matrix(params, cfg), tgt) / tok.shape[0])
        chosen.append(picked)
    return jnp.mean(jnp.stack(losses)), jnp.stack(chosen)


def loss(lora: dict, base: dict, tokens, targets, cfg: dict, *, lora_scale: float):
    """Mean next-token cross-entropy over a [B, T] batch of sequences."""
    return loss_and_routing(lora, base, tokens, targets, cfg, lora_scale=lora_scale)[0]
