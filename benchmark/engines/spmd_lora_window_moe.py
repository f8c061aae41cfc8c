"""``SpmdLoraFederation`` over a sliding-window / full-attention sparse-expert LM
of which this chip holds a SHARE (``afmoe``, Trinity-Mini: two leading dense
layers, then periods of expert layers under sliding and full attention —
``TransformerConfig.leading_pattern`` + ``layer_pattern`` of ``swa_dense`` /
``swa_experts`` / ``full_experts``; gated attention between sandwich norms, an
untied head, 32 of 128 experts of every layer). Same federation, window and
round checks as ``spmd_lora``; its own model construction, reference
(``reference/afmoe_lm.py``), kernel expectations and shape functions
(``flops_window_moe.py``).

The comparison is ``spmd_lora_moe``'s (its docstring says why): the reference is
HELD TO THE PROGRAM'S ASSIGNMENTS for the loss, the gradients and the round —
read from the forward the gradient was taken through, every expert run's choices
in layer order (``models/transformer.sown_by_layer``) — and the share of
assignments on which the two sides agree is compared on its own. Both sides hold
the SAME share: an assignment to an expert that is not held contributes nothing
on either. Besides, each on the program's own input so that depth does not enter:

- one expert layer of each kind (``swa_experts``, ``full_experts``: two scan
  bodies) on the program's own ``mlp_norm`` output, as
  ``spmd_lora_moe.check_expert_layer`` does, against ``afmoe_lm.experts`` under
  the same share (its two limits are that engine's, with their reasons there);
- ONE SLIDING AND ONE FULL ATTENTION LAYER ALONE — the program's ``Attention``
  module (its kernels, its dtypes, gate included) on the program's own
  ``attn_norm`` output, against ``afmoe_lm.attention``: a program that dropped
  the window, rotated a full layer or left the gate out misses ``checks.GRAD_REL``
  there by far (readings beside ``planted_faults_window.py``'s faults in PERF.md
  section 6);
- THE WINDOW'S EDGE (``check_window_edge``): a layer on its own input cannot tell
  a window of 2047 or 2049 keys from one of 2048 (one key in 2048 carries 0.05 %
  of a row's weight). The program's attention callable is given q, k, v made so
  that query ``i`` puts nearly all its weight on keys ``i - W`` and ``i - W + 1``:
  the first is just OUTSIDE the window, the second the LAST key inside. Right,
  the output is ``v[i - W + 1]``; a window one key wider averages in ``v[i - W]``
  (relative distance 0.707 = 1 / sqrt 2), one key narrower loses both.
  ``WINDOW_EDGE_REL`` lies between the sound reading and the float8 one;
- the program's own counter ``moe_held_share`` against the count of its own
  assignments (they are one number: equal to float32 rounding), and against the
  share the REFERENCE's float32 router gives on the same tokens — what the
  seeded router's imbalance explains; near-ties that flip across the held
  boundary move it by less than ``HELD_SHARE_ABS``.

Tolerances this engine brings (the loss, gradient and round limits are
``checks.py``'s, shared with every LM cell):

- ``STEP_ROUTING_AGREE``: the whole first step, all EIGHT expert layers, top 8 of
  128, each side on its own activations (program bfloat16, reference float32).
  Readings and the fault readings that bound it are beside the constant.
- ``WINDOW_EDGE_REL``, ``HELD_SHARE_ABS``: above, readings beside the constants.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks as ck
from benchmark import flops, flops_window_moe, traffic, window
from benchmark.engines.spmd_lora import _federation, measure, reset, warm  # noqa: F401 (the engine's functions)
from benchmark.engines.spmd_lora_conv_moe import _Named
from benchmark.engines.spmd_lora_moe import LAYER_ROUTING_AGREE, LAYER_TOKEN_REL, _agreement, kernels_in, seeded_params
from benchmark.reference import afmoe_lm, fedavg

# Whole first step over 8 expert layers (top 8 of 128), published widths, 8192
# tokens (my chip runs, PR 37): seven seeds read 0.98665-0.98745 overall; by layer
# 0.990 in the first expert layer falling to 0.984-0.986 in the eighth. A fault
# that moves a layer's output moves every later layer's choices: a window one key
# wider read 0.98028 (the step's gradients do not see that fault, the edge probe
# does), attention from float8 operands 0.95211, the weights normalised over
# the held experts only 0.73745, a dropped post-norm 0.16254. The floor lies
# between 0.98028 and 0.98665 and leaves the sound readings four times their own
# range (0.0008) of room.
STEP_ROUTING_AGREE = 0.9835
# the edge probe's relative distance (my chip runs, PR 37): 0.001660-0.001663 on
# seven seeds (bfloat16 rounding of v and of the output), 0.70677 with a window one
# key wider (the average of two values), 0.026694 with q, k and v rounded to
# float8 (e4m3) — the nearest precision below the configuration's, which this
# limit has to refuse. The geometric mean of 0.001661 and 0.026694: four times
# of room either way (the CPU rehearsal reads 0.00166 / 0.0270 in bfloat16).
WINDOW_EDGE_REL = 0.0067
# |program's held share - the reference router's| on the check's 524,288
# assignments (my chip runs, PR 37): 0.000004-0.000179 on seven seeds — near-ties
# flip 1.3 % of the assignments, three in eight of those across the held boundary,
# as often in as out: a random walk of about 50 assignments, 0.0001. Faults that
# move the router's input read 0.0027 (a dropped post-norm) and 0.0032 (weights
# over the held only), float8 attention 0.0008; the shares themselves read
# 0.2370-0.2694 over seeds. A share is a count over the assignments: at the CPU
# rehearsal's 2,048 one flip is 0.0005, so the ceiling is never under 8 flips.
HELD_SHARE_ABS = 0.001


def _transformer_config(cfg: dict, args: dict):
    from p2pfl_tpu.models.transformer import TransformerConfig

    want = {"wq", "wk", "wv", "wo", "wg", "w1", "w2", "w3"}
    if set(cfg["lora"]["targets"]) != want:
        raise SystemExit(f"benchmark: spmd_lora_window_moe adapts {sorted(want)}, the configuration asks for {sorted(cfg['lora']['targets'])}")
    if (
        cfg["score_func"] != "sigmoid" or not cfg["route_norm"] or cfg["n_group"] != 1 or cfg["topk_group"] != 1
        or cfg["tie_word_embeddings"] or not cfg["mup_enabled"] or cfg["rope_scaling"] is not None
        or len(cfg["layer_types"]) != cfg["num_hidden_layers"]
    ):
        raise SystemExit(
            "benchmark: spmd_lora_window_moe runs sigmoid routing chosen with the expert bias and weighed without it, "
            "weights normalised over the chosen, no group limit, an untied head, the embedding multiplier, unscaled "
            "RoPE, and one entry of layer_types a layer"
        )
    if args["gmm"] not in ("pallas", "xla"):
        raise SystemExit("benchmark: engine_args.gmm is 'pallas' (the Mosaic kernel on a TPU) or 'xla'")
    leading, pattern, _ = afmoe_lm.stack(cfg)
    first, held, width = afmoe_lm.share(cfg)
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"], rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        leading_pattern=tuple(leading), layer_pattern=tuple(pattern), qk_norm=True,
        attn_window=cfg["sliding_window"], attn_gate=True, post_norms=True,
        embed_scale=float(cfg["hidden_size"]) ** 0.5, tie_head=False,
        routed_experts=width, experts_held=held, first_expert=first,
        experts_per_token=cfg["num_experts_per_tok"], expert_hidden=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"], routed_scale=cfg["route_scale"], expert_tile_m=args["gmm_tile_m"],
        expert_impl=None if args["gmm"] == "pallas" else "xla",  # None: the kernel on a TPU, XLA on the CPU rehearsal
        lora_rank=cfg["lora"]["rank"], lora_alpha=cfg["lora"]["alpha"], lora_mlp=True,
        remat=True, scan_layers=args["scan_layers"], remat_policy=args["remat_policy"],
    )


def _attention(job, seq_len: int, head_dim: int, sliding_window: int):
    """The attention callable of the cell, and its name: ``spmd_lora._attention``
    with the sliding layers' schedule beside the full layers'. Both come from
    the shipped defaults table or the run is refused."""
    from p2pfl_tpu.models.transformer import pick_attention, resolve_attention
    from p2pfl_tpu.ops.autotune import flash_config_source

    attn = job.cell["engine_args"]["attn"]
    if attn == "auto":
        attn = pick_attention(seq_len)
    if attn != "flash":
        return resolve_attention(attn), attn
    found = {}
    for name, win in (("full", None), ("sliding", sliding_window if sliding_window < seq_len else None)):
        config, source = flash_config_source(seq_len, head_dim, dtype=jnp.bfloat16, window=win)
        if source != "defaults":
            raise SystemExit(
                f"benchmark: flash config for (T={seq_len}, D={head_dim}, window={win}) comes from {source!r}, "
                "not the shipped defaults table; remove the tune file or pin"
            )
        found[name] = config
    job.say(f"flash config (T={seq_len}, D={head_dim}): full layers {found['full']}, window {sliding_window} {found['sliding']}, from defaults")
    return resolve_attention("flash", config=found["full"], window_config=found["sliding"]), attn


def build(job) -> dict:
    from p2pfl_tpu.models import transformer
    from p2pfl_tpu.models.base import FlaxModel

    cfg, tr = job.cfg, job.traffic
    if "swa_experts" not in transformer.LAYER_KINDS:
        raise SystemExit("benchmark: this program has no sliding-window layer kinds (LAYER_KINDS lacks 'swa_experts')")
    if not job.cell["engine_args"]["scan_layers"]:
        raise SystemExit("benchmark: spmd_lora_window_moe reads the scanned parameter tree (engine_args.scan_layers)")
    tcfg = _transformer_config(cfg, job.cell["engine_args"])
    attn_fn, attn = _attention(job, tr["seq_len"], cfg["head_dim"], cfg["sliding_window"])
    module = transformer.CausalLM(tcfg, attn_fn)
    # weights: ONE jitted call from the seed, on the device, each leaf in the
    # dtype it is kept in (the expert banks bfloat16, everything else float32)
    params = jax.jit(lambda key: seeded_params(tcfg, key, cfg["router_bias_std"]))(jax.random.PRNGKey(job.seed))
    model = FlaxModel(module, params, (tr["seq_len"],), cfg["vocab_size"])
    model.extra["config"] = tcfg
    shards = traffic.generate(tr, cfg, job.seed)
    by_dtype: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        by_dtype[leaf.dtype.name] = by_dtype.get(leaf.dtype.name, 0) + leaf.size
    leading, pattern, periods = afmoe_lm.stack(cfg)
    first, held, width = afmoe_lm.share(cfg)
    job.say(
        f"model: {sum(by_dtype.values()) / 1e9:.3f} B parameters by dtype {by_dtype} ({' '.join(leading)} | {periods} x "
        f"({' '.join(pattern)}); experts {first}..{first + held - 1} of {width} held; shape functions say "
        f"{flops_window_moe.model_params(cfg)} + {flops_window_moe.lora_params(cfg)} adapters), attn={attn}, "
        f"gmm={job.cell['engine_args']['gmm']}, {tr['n_nodes']} nodes x {tr['local_steps']} steps x {tr['batch_size']} x {tr['seq_len']} tokens"
    )
    return {"fed": None, "model": model, "module": module, "attn": attn, "shards": shards}


def _reference_grad(job):
    """``(lora, base, x, y, forced) -> ((loss, the reference's own choice), grads)``,
    the reference held to the ``[B, expert layers, T, k]`` assignments ``forced``."""
    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]

    @jax.jit
    def grad(lora, base, x, y, forced):
        return jax.value_and_grad(afmoe_lm.loss_and_routing, has_aux=True)(
            lora, base, x, y, cfg, lora_scale=scale, forced=forced
        )

    return grad


def check_expert_layer(job, tcfg, mlp: dict, h) -> float:
    """``spmd_lora_moe.check_expert_layer`` under a held share: ONE expert layer
    of the timed path (``ExpertFFN``, its kernel) against "every held expert on
    every row, masked" (``afmoe_lm.experts``), both on the same input ``h``.
    Returns the share of the reference's assignments that are held."""
    from p2pfl_tpu.models.transformer import ExpertFFN

    cfg = job.cfg
    lone = ExpertFFN(dataclasses.replace(tcfg, shared_experts=0))

    @jax.jit
    def program(p, h_):
        own = {"router": p["router"], "router_bias": p["router_bias"]}
        y, mut = lone.apply({"params": own}, h_, (p["bank_layer"], p["experts_w13"], p["experts_w2"]), mutable=["moe_routing"])
        return y[0].astype(jnp.float32), jax.tree.leaves(mut)[0]

    @jax.jit
    def reference(p, h_):
        return afmoe_lm.experts(h_[0].astype(jnp.float32), p, dict(cfg, num_shared_experts=0), 0.0)

    routed = {k: v for k, v in mlp.items() if k != "shared"}
    got, got_chosen = program(routed, h)
    with jax.default_matmul_precision("highest"):
        want, want_chosen = reference(routed, h)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    same = (np.sort(np.asarray(got_chosen), -1) == np.sort(np.asarray(want_chosen), -1)).all(-1)
    # a token none of whose experts is held has a zero routed output on both sides
    token_rel = np.linalg.norm(got - want, axis=-1) / np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    token_rel = np.where(np.linalg.norm(want, axis=-1) > 0, token_rel, np.linalg.norm(got, axis=-1))
    job.checks.at_least("layer.routing_agreement", _agreement(got_chosen, want_chosen), LAYER_ROUTING_AGREE)
    job.checks.at_most("layer.worst_agreeing_token_rel", float(token_rel[same].max()), LAYER_TOKEN_REL)
    job.checks.at_most("layer.out_rel_l2", ck.rel_l2(got[same], want[same]), ck.GRAD_REL)
    return _held_share(want_chosen, cfg)


def _held_share(chosen, cfg: dict) -> float:
    first, held, _ = afmoe_lm.share(cfg)
    chosen = np.asarray(chosen)
    return float(np.mean((chosen >= first) & (chosen < first + held)))


def check_attention_layer(job, tcfg, attn_fn, mixer: str, p: dict, h, name: str) -> None:
    """ONE attention layer alone: the program's ``Attention`` (``mixer``:
    ``"swa"`` or ``"full"``; its kernels, its dtypes, gate included) against
    ``afmoe_lm.attention``, both on ``h`` — the program's own ``attn_norm``
    output ``[1, T, hidden]`` in front of that layer. ``p``: its ``attn`` subtree."""
    from p2pfl_tpu.models.transformer import Attention

    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    program = jax.jit(lambda p_, h_: Attention(tcfg, attn_fn, mixer).apply({"params": p_}, h_)[0].astype(jnp.float32))
    reference = jax.jit(
        lambda p_, h_: afmoe_lm.attention(h_[0].astype(jnp.float32), p_, cfg, scale, "sliding" if mixer == "swa" else "full")
    )
    got = program(p, h)
    with jax.default_matmul_precision("highest"):
        want = reference(p, h)
    job.checks.at_most(f"{name}.attention_out_rel_l2", ck.rel_l2(got, want), ck.GRAD_REL)


def check_window_edge(job, tcfg, attn_fn, seq: int) -> None:
    """The window's convention, at its edge (the module docstring says how):
    the program's attention callable under ``window=W`` on q, k, v made so that
    query ``i`` weighs keys ``i - W`` (outside) and ``i - W + 1`` (the last
    inside) alone."""
    w, hd, heads = tcfg.attn_window, tcfg.head_width, 2
    if w >= seq:
        return job.say(f"window edge: the window ({w}) covers the check's {seq} tokens; nothing to probe")
    from p2pfl_tpu.models.transformer import _attend_fn

    key_k, key_v = jax.random.split(jax.random.PRNGKey(job.seed + 3))
    k = jax.random.normal(key_k, (1, seq, heads, hd), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(key_v, (1, seq, heads, hd), jnp.float32)
    back = lambda a, n: jnp.pad(a, ((0, 0), (n, 0), (0, 0), (0, 0)))[:, :seq]  # noqa: E731  a[i - n], zeros before the start
    # logits: 30 on keys i - W and i - W + 1, about N(0, 30^2 * 2 / hd) on the others (hd = 128: +-3.75)
    q = 30.0 * hd ** 0.5 * (back(k, w) + back(k, w - 1))
    attend = _attend_fn(tcfg, attn_fn)
    got = jax.jit(lambda q_, k_, v_: attend(q_, k_, v_, window=w))(q.astype(tcfg.dtype), k.astype(tcfg.dtype), v.astype(tcfg.dtype))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q_, k_, v_: jnp.stack([
            afmoe_lm.attend(q_[0, :, h:h + 1], k_[0, :, h:h + 1], v_[0, :, h:h + 1], w) for h in range(heads)
        ], axis=1)[:, :, 0])(q, k, v)
    rows = slice(w, seq)  # the rows whose window has an edge inside the sequence
    job.checks.at_most("window.edge_rel_l2", ck.rel_l2(got[0, rows].astype(jnp.float32), want[rows]), WINDOW_EDGE_REL)


def check_layers(job, module, params: dict, tokens) -> None:
    """The first sliding and the first full attention layer alone, and the first
    expert layer of each kind: the PROGRAM's layer code one layer at a time on
    the parameters of ``afmoe_lm.layer_trees`` down to the layer's ``attn_norm``
    / ``mlp_norm`` output, which both sides are then given."""
    from p2pfl_tpu.models.transformer import LAYER_KINDS, Attention, Block, RMSNorm

    tcfg, attn_fn = module.cfg, module.attn_fn
    norm = RMSNorm(tcfg.dtype, tcfg.norm_eps)

    def own(p):  # the subtree Block owns: the bank is handed in beside it
        return dict(p, mlp={k: v for k, v in p["mlp"].items() if k in ("router", "router_bias", "shared")}) if "router" in p["mlp"] else p

    def bank(p):
        return (p["mlp"]["bank_layer"], p["mlp"]["experts_w13"], p["mlp"]["experts_w2"]) if "router" in p["mlp"] else None

    whole = {
        kind: jax.jit(lambda p, x, b, kind=kind: Block(tcfg, attn_fn, kind).apply({"params": p}, x, b))
        for kind in set(tcfg.leading_pattern + tcfg.layer_pattern)
    }
    attn_input = jax.jit(lambda p, x: norm.apply({"params": p["attn_norm"]}, x))

    def router_input(kind):
        mixer = Attention(tcfg, attn_fn, LAYER_KINDS[kind][0])

        @jax.jit
        def fn(p, x):
            a = mixer.apply({"params": p["attn"]}, norm.apply({"params": p["attn_norm"]}, x))
            a = x + norm.apply({"params": p["attn_post_norm"]}, a)
            return norm.apply({"params": p["mlp_norm"]}, a)

        return fn

    x = (params["embed"][tokens] * tcfg.embed_scale).astype(tcfg.dtype)
    seen, wanted = set(), {"swa", "full"} | {k for k in tcfg.layer_pattern if LAYER_KINDS[k][1] == "experts"}
    for kind, p in afmoe_lm.layer_trees(params, job.cfg):
        mixer = LAYER_KINDS[kind][0]
        if mixer not in seen:
            seen.add(mixer)
            check_attention_layer(job, tcfg, attn_fn, mixer, p["attn"], attn_input(p, x), kind)
        if LAYER_KINDS[kind][1] == "experts" and kind not in seen:
            seen.add(kind)
            named = SimpleNamespace(cfg=job.cfg, checks=_Named(job.checks, f"{kind}."))
            share = check_expert_layer(named, tcfg, p["mlp"], router_input(kind)(own(p), x))
            job.say(f"{kind}: the reference's router holds {share:.4f} of this layer's assignments here (even share {flops_window_moe.even_share(job.cfg)})")
        if seen >= wanted:
            return
        x = whole[kind](own(p), x, bank(p))
    raise SystemExit("benchmark: the configuration lacks a sliding layer, a full layer or an expert layer")


def check(job, state) -> None:
    """(0) one sliding and one full attention layer and one expert layer of each
    kind, each on the same input, and the window's edge; (1) one node's first
    local step — loss, every adapter gradient, the share of assignments on which
    program and reference agree, the held share — and (2) one federated round
    of a reduced job, against the float32 reference at the published widths and
    the timed sequence length."""
    from p2pfl_tpu.learning.lora import _lm_forward, split_lora
    from p2pfl_tpu.models.transformer import sown_by_layer

    spec, cfg = job.cell["check"], job.cfg
    seq, n_nodes, steps = spec["seq_len"], spec["n_nodes"], spec["local_steps"]
    small = dict(job.traffic, seq_len=seq, n_nodes=n_nodes)
    small["data"] = dict(job.traffic["data"], docs_per_node=steps)
    shards = traffic.generate(small, cfg, job.seed + 1)
    lora, base = split_lora(state["model"].params)
    # lora_b starts at zero, which makes every lora_a gradient exactly zero:
    # the step check perturbs it (seeded) so both halves of every adapter count
    n_b = sum("lora_b" in jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(lora))
    keys = iter(jax.random.split(jax.random.PRNGKey(job.seed + 2), n_b))
    probe = jax.tree_util.tree_map_with_path(
        lambda path, a: 0.02 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "lora_b" in jax.tree_util.keystr(path) else a,
        lora,
    )
    x, y = jnp.asarray(shards[0]["x"][:1]), jnp.asarray(shards[0]["y"][:1])
    module = state["module"]
    if seq != job.traffic["seq_len"]:  # the flash schedule is per length
        from p2pfl_tpu.models.transformer import CausalLM

        module = CausalLM(module.cfg, _attention(job, seq, cfg["head_dim"], cfg["sliding_window"])[0])
    check_layers(job, module, state["model"].params, x)
    check_window_edge(job, module.cfg, module.attn_fn, seq)

    @jax.jit
    def system_step(lo, base_, bx, by):
        """(loss, adapter gradients, ``[B, expert layers, T, k]`` assignments in
        layer order, the sown statistics) of the timed path's loss — the
        assignments from THE forward that the gradient was taken through."""

        def loss_of(lo_):
            loss, _, stats, routing = _lm_forward(lo_, base_, module, bx, by)
            chosen = sown_by_layer(module.cfg, routing)  # [expert layers, B T, k]
            chosen = chosen.reshape(chosen.shape[0], *bx.shape, chosen.shape[-1])
            return loss, (jnp.swapaxes(chosen, 0, 1), stats)

        (loss, (chosen, stats)), grads = jax.value_and_grad(loss_of, has_aux=True)(lo)
        return loss, grads, chosen, stats

    ref_grad = _reference_grad(job)
    got_loss, got, got_chosen, stats = system_step(probe, base, x, y)
    with jax.default_matmul_precision("highest"):
        (want_loss, want_chosen), want = ref_grad(probe, base, x, y, got_chosen)
    job.checks.close("step.loss", float(got_loss), float(want_loss), ck.LOSS_REL)
    job.checks.gradients("step", got, want)
    for kind in ("attn", "mlp"):  # and by part, so that a fault has an address
        pick = lambda tree: [leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree) if f"'{kind}'" in jax.tree_util.keystr(p)]  # noqa: E731
        job.checks.gradients(f"step.{kind}", pick(got), pick(want))
    job.checks.at_least("step.routing_agreement", _agreement(got_chosen, want_chosen), STEP_ROUTING_AGREE)
    kinds = [k for k in afmoe_lm.layer_kinds(cfg) if afmoe_lm.KINDS[k][1] == "experts"]
    by_layer = [(k.split("_")[0], round(_agreement(got_chosen[:, j], want_chosen[:, j]), 5)) for j, k in enumerate(kinds)]
    job.say(f"routing agreement by expert layer (program on bf16 activations, reference on float32): {by_layer}")
    # the program's counter is the count of its own held assignments, and what the reference's router explains
    counted, explained = _held_share(got_chosen, cfg), _held_share(want_chosen, cfg)
    job.checks.close("step.held_share_counted", float(stats["moe_held_share"]), counted, 1e-5)
    job.checks.at_most("step.held_share_off_reference", abs(counted - explained), max(HELD_SHARE_ABS, 8.0 / np.asarray(got_chosen).size))
    job.say(f"held share of the step's assignments: program {counted:.5f}, reference's router {explained:.5f}, even share {flops_window_moe.even_share(cfg)}")

    # (2) the reduced federation: same base buffers, the check's own length
    model = state["model"]
    if module is not state["module"]:
        from p2pfl_tpu.models.base import FlaxModel

        model = FlaxModel(module, model.params, (seq,), cfg["vocab_size"])
    fed = _federation(job, model, shards, n_nodes)
    start = jax.tree.map(np.asarray, lora)
    loss = float(fed.run_round(epochs=1)["train_loss"])
    got_round = jax.tree.map(lambda a: np.asarray(a[0]), fed.params)
    order = np.random.default_rng(job.seed)  # the federation's own batch-order stream
    opt = job.cell["engine_args"]["optimizer"]

    def held(lo, base_, bx, by, forced):
        (ref_loss, _), grads = ref_grad(lo, base_, bx, by, forced)
        return ref_loss, grads

    ref_step = fedavg.adam_step(held)
    trained, ref_losses = [], []
    for shard in shards:
        perm = order.permutation(len(shard["y"]))[:steps]
        node = lora
        m = v = jax.tree.map(jnp.zeros_like, lora)
        losses = []
        for i, doc in enumerate(perm):  # fedavg.adam_train's loop, with the program's assignments AT these adapters
            bx, by = jnp.asarray(shard["x"][doc:doc + 1]), jnp.asarray(shard["y"][doc:doc + 1])
            forced = system_step(node, base, bx, by)[2]
            with jax.default_matmul_precision("highest"):
                node, m, v, step_loss = ref_step(node, m, v, float(i + 1), fedavg.learning_rate(opt, i), base, bx, by, forced)
            losses.append(float(step_loss))
        trained.append(jax.tree.map(np.asarray, node))
        ref_losses.append(float(np.mean(losses)))
    want_round = fedavg.weighted_mean(trained, [len(s["y"]) for s in shards])
    job.checks.close("round.loss", loss, float(np.mean(ref_losses)), ck.LOSS_REL)
    job.checks.at_least(
        "round.delta_cosine", ck.cosine(ck.tree_sub(got_round, start), ck.tree_sub(want_round, start)),
        ck.ROUND_COS,
    )
    del fed
    # unload the check's executables: the round needs nearly all of the chip
    jax.clear_caches()


def finish(job, state, win: dict) -> None:
    window.spmd_final_checks(job, state["fed"], win)
    if job.trace:
        got = kernels_in(state["fed"].lower_round(epochs=1).as_text())
        want = job.cell["expect"]["kernels_in_round"]
        job.checks.add("round.kernels", got == want, got=got, want=want)


def _window_mean(fed, name: str):
    """The window's mean of a device scalar every round's history entry carries
    (read AFTER the window: nothing fetched it before), and how many rounds had it."""
    values = [float(e[name]) for e in fed.history if name in e]
    return (float(np.mean(values)) if values else None), len(values)


def describe(job, state) -> dict:
    cfg, tr = job.cfg, job.traffic
    seq = tr["seq_len"]
    load, rounds = _window_mean(state["fed"], "moe_load_max_over_mean")
    held, _ = _window_mean(state["fed"], "moe_held_share")
    # operations and floors over the rows this chip computed: the counted share, the even one without a counter
    step = flops_window_moe.lora_step_flops(cfg, seq, held)
    node_steps = tr["n_nodes"] * tr["local_steps"] * tr["batch_size"]
    peak = flops.peaks("TPU v5 lite")
    ops, moved = flops_window_moe.gmm_pass(cfg, seq, held)
    kinds = afmoe_lm.layer_kinds(cfg)
    return {
        "train_nodes": tr["n_nodes"],
        "steps_per_program_run": node_steps // len(job.devices),
        "flops_per_round": step["total"] * node_steps,
        "flops_per_sequence_step": step,
        "flash_flops_per_round": 0.0,  # flash_roofline is not this cell's: p2pfl_gmm runs beside flash
        "round_program": "jit_spmd_lora_round",
        "fold_bytes": flops.fedavg_fold_bytes(tr["n_nodes"], flops_window_moe.lora_params(cfg)),
        "layer_kinds": kinds,
        "expert_layers": sum(afmoe_lm.KINDS[k][1] == "experts" for k in kinds),
        "visible_pairs": {m: flops_window_moe.visible_pairs(seq, cfg["sliding_window"] if m == "sliding" else None) for m in ("sliding", "full")},
        "gmm_pass": {"flops": ops, "bytes": moved},
        "gmm_floor_s_per_step": flops_window_moe.gmm_floor_seconds(cfg, seq, peak, held),
        "flash_win_floor_s_per_step": flops_window_moe.flash_win_floor_seconds(cfg, seq, peak),
        "flash_full_floor_s_per_step": flops_window_moe.flash_floor_seconds(cfg, seq, peak, "full"),
        "moe_load_max_over_mean": load,
        "moe_held_share": held,
        "moe_load_rounds": rounds,
    }
