"""``SpmdLoraFederation`` over a short-convolution / attention / sparse-expert LM
(``lfm2_moe``: leading dense layers, then periods of expert layers under two
mixers — ``TransformerConfig.leading_pattern`` + ``layer_pattern`` of
``conv_dense`` / ``attention_experts`` / ``conv_experts``). Same federation,
window and round checks as ``spmd_lora``; its own model construction, reference
(``reference/lfm2_moe_lm.py``), kernel expectations and shape functions
(``flops_conv_moe.py``).

The comparison is ``spmd_lora_moe``'s (its docstring says why): the reference is
HELD TO THE PROGRAM'S ASSIGNMENTS for the loss, the gradients and the round —
read from the forward the gradient was taken through, every expert run's choices
in layer order (``models/transformer.sown_by_layer``) — the share of assignments
on which the two sides agree is compared on its own, and ONE expert layer is
compared on the program's own ``mlp_norm`` output in front of it
(``spmd_lora_moe.check_expert_layer``, its limits with their reasons there) —
here twice, under the first ``attention_experts`` and the first ``conv_experts``
layer, since the two kinds are two scan bodies.

Tolerances (readings in PERF.md section 6; the faults are
``benchmark/planted_faults.py``'s and ``planted_faults_conv.py``'s, planted
through this ``check``): the loss, gradient and round limits are ``checks.py``'s,
shared with every LM cell; the layer check's two are ``spmd_lora_moe``'s (one
layer on one input: depth does not enter); this engine's own:

- ``STEP_ROUTING_AGREE``: the whole first step, all TWELVE expert layers, each
  side on its own activations (program bfloat16, reference float32): near-ties
  flip, more so deeper in the stack, and a flip in one layer moves the inputs of
  every layer after it (``glm_silo4_seq4096`` read 0.988 in its first expert
  layer and 0.978 in its fourth, so its 0.975 floor cannot hold over twelve).
  The readings, by layer and overall, and the fault readings that bound the
  floor from below are beside the constant (0.965).
- ``CONV_SCOPE_REL``: what lies between a conv layer's two projections —
  ``C * conv(B * u)``, ``models/transformer.gated_short_conv`` as the mixer calls
  it — against the reference's float32 arithmetic, both given the SAME input:
  the program's own bfloat16 ``in_proj`` output of the first conv layer on the
  check's tokens. The configuration states float32 there with ONE rounding of
  the result to bfloat16: relative L2 0.00166 (rounding alone; three seeds on
  the CPU at ``[4096, 6144]``, the chip's reading in PERF.md section 6). With
  every product and partial sum rounded to bfloat16 it reads 0.00395-0.0040
  (``planted_faults_conv.py``'s ``bf16_conv``; the step's gradient limits do not
  see that fault: bfloat16 matmuls around it leave more). The ceiling is the
  geometric mean of the two, 1.55 x either way; both readings are rounding
  statistics over 8 M elements and repeat to 1 %.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks as ck
from benchmark import flops, flops_conv_moe, traffic, window
from benchmark.engines.spmd_lora import _attention, _federation, measure, reset, warm  # noqa: F401 (the engine's functions)
from benchmark.engines.spmd_lora_moe import _agreement, check_expert_layer, kernels_in, seeded_params
from benchmark.reference import fedavg, lfm2_moe_lm

# Whole first step over 12 expert layers, published widths, 4096 tokens (my chip
# runs, PR 33): eleven seeds read 0.97105-0.97214 overall; by layer 0.986-0.988
# in the first expert layer, falling to 0.954-0.960 in the twelfth. A fault that
# moves a layer's output moves every later layer's choices: the bfloat16
# convolution read 0.96885 (seen by CONV_SCOPE_REL, not here); a choice made
# without the bias read 0.94184 (by layer 0.932-0.951; the layer check 0.9436 /
# 0.9465). The floor lies between 0.94184 and 0.97105 and leaves the sound
# readings five times their own range (0.0011) of room.
STEP_ROUTING_AGREE = 0.965
# 0.001657-0.001661 on eleven seeds on the chip (the CPU's 0.00166); bf16_conv 0.003953
CONV_SCOPE_REL = 0.0026


def _transformer_config(cfg: dict, args: dict):
    from p2pfl_tpu.models.transformer import TransformerConfig

    want = {"wq", "wk", "wv", "wo", "in_proj", "out_proj", "w1", "w2", "w3"}
    if set(cfg["lora"]["targets"]) != want:
        raise SystemExit(f"benchmark: spmd_lora_conv_moe adapts {sorted(want)}, the configuration asks for {sorted(cfg['lora']['targets'])}")
    if (
        not cfg["use_expert_bias"] or not cfg["norm_topk_prob"] or cfg["conv_bias"] or not cfg["tie_word_embeddings"]
        or cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]
        or len(cfg["layer_types"]) != cfg["num_hidden_layers"]
    ):
        raise SystemExit(
            "benchmark: spmd_lora_conv_moe runs sigmoid routing chosen with the expert bias and weighed without it, "
            "normalised weights, no convolution bias, a tied head, head_dim = hidden_size / num_attention_heads, "
            "and one entry of layer_types a layer"
        )
    if args["gmm"] not in ("pallas", "xla"):
        raise SystemExit("benchmark: engine_args.gmm is 'pallas' (the Mosaic kernel on a TPU) or 'xla'")
    leading, pattern, _ = lfm2_moe_lm.stack(cfg)
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden=cfg["intermediate_size"], rope_theta=cfg["rope_theta"], norm_eps=cfg["norm_eps"],
        leading_pattern=tuple(leading), layer_pattern=tuple(pattern), qk_norm=True, conv_taps=cfg["conv_L_cache"],
        routed_experts=cfg["num_experts"], experts_per_token=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"], shared_experts=0,
        routed_scale=cfg["routed_scaling_factor"], expert_tile_m=args["gmm_tile_m"],
        expert_impl=None if args["gmm"] == "pallas" else "xla",  # None: the kernel on a TPU, XLA on the CPU rehearsal
        lora_rank=cfg["lora"]["rank"], lora_alpha=cfg["lora"]["alpha"], lora_mlp=True,
        remat=True, scan_layers=args["scan_layers"], remat_policy=args["remat_policy"],
    )


def build(job) -> dict:
    from p2pfl_tpu.models import transformer
    from p2pfl_tpu.models.base import FlaxModel

    cfg, tr = job.cfg, job.traffic
    if "conv_experts" not in transformer.LAYER_KINDS or not hasattr(transformer, "sown_by_layer"):
        raise SystemExit("benchmark: this program has no short-convolution / expert layer kinds (LAYER_KINDS lacks 'conv_experts')")
    if not job.cell["engine_args"]["scan_layers"]:
        raise SystemExit("benchmark: spmd_lora_conv_moe reads the scanned parameter tree (engine_args.scan_layers)")
    tcfg = _transformer_config(cfg, job.cell["engine_args"])
    attn_fn, attn = _attention(job, tr["seq_len"], cfg["head_dim"])
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
    leading, pattern, periods = lfm2_moe_lm.stack(cfg)
    job.say(
        f"model: {sum(by_dtype.values()) / 1e9:.3f} B parameters by dtype {by_dtype} ({' '.join(leading)} | {periods} x "
        f"({' '.join(pattern)}); shape functions say {flops_conv_moe.model_params(cfg)} + {flops_conv_moe.lora_params(cfg)} "
        f"adapters), attn={attn}, gmm={job.cell['engine_args']['gmm']}, "
        f"{tr['n_nodes']} nodes x {tr['local_steps']} steps x {tr['batch_size']} x {tr['seq_len']} tokens"
    )
    return {"fed": None, "model": model, "module": module, "attn": attn, "shards": shards}


def _reference_grad(job):
    """``(lora, base, x, y, forced) -> ((loss, the reference's own choice), grads)``,
    the reference held to the ``[B, expert layers, T, k]`` assignments ``forced``."""
    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]

    @jax.jit
    def grad(lora, base, x, y, forced):
        return jax.value_and_grad(lfm2_moe_lm.loss_and_routing, has_aux=True)(
            lora, base, x, y, cfg, lora_scale=scale, forced=forced
        )

    return grad


class _Named:
    """``job.checks`` with a prefix on every comparison's name."""

    def __init__(self, checks, prefix: str) -> None:
        self.checks, self.prefix = checks, prefix

    def at_least(self, name, *a):
        self.checks.at_least(self.prefix + name, *a)

    def at_most(self, name, *a):
        self.checks.at_most(self.prefix + name, *a)


def check_conv_scope(job, tcfg, p: dict, x) -> None:
    """One conv layer's gate products and taps (``gated_short_conv``, looked up
    as ``ShortConvMixer`` does) against the reference's, both on the program's
    own ``in_proj`` output for the layer input ``x``; ``p``: that layer's subtree."""
    from p2pfl_tpu.models import transformer as tf

    norm = tf.RMSNorm(tcfg.dtype, tcfg.norm_eps)
    in_proj = tf.LoRADense(3 * tcfg.dim, rank=tcfg.lora_rank, alpha=tcfg.lora_alpha, dtype=tcfg.dtype)

    @jax.jit
    def program(p_, x_):
        bcu = in_proj.apply({"params": p_["conv"]["in_proj"]}, norm.apply({"params": p_["conv_norm"]}, x_))
        return bcu, tf.gated_short_conv(bcu, p_["conv"]["conv_kernel"], tcfg.dtype)

    bcu, got = program(p, x)
    want = jax.jit(lfm2_moe_lm.gated_conv)(bcu[0].astype(jnp.float32), p["conv"]["conv_kernel"])
    job.checks.at_most("conv.scope_rel_l2", ck.rel_l2(got[0].astype(jnp.float32), want), CONV_SCOPE_REL)


def check_layers(job, module, params: dict, tokens) -> None:
    """The first conv layer's arithmetic (:func:`check_conv_scope`), and
    ``spmd_lora_moe.check_expert_layer`` under the first layer of each expert
    kind: the PROGRAM's layer code (its modules, its kernels, its dtypes) one
    layer at a time on the parameters of ``lfm2_moe_lm.layer_trees`` down to
    that layer's ``mlp_norm`` output, which both sides are then given."""
    from p2pfl_tpu.models.transformer import LAYER_KINDS, Attention, Block, RMSNorm, ShortConvMixer

    tcfg, attn_fn = module.cfg, module.attn_fn
    norm = RMSNorm(tcfg.dtype, tcfg.norm_eps)
    mixers = {"attention": ("attn", Attention(tcfg, attn_fn)), "short_conv": ("conv", ShortConvMixer(tcfg))}

    def own(p):  # the subtree Block owns: the bank is handed in beside it
        return dict(p, mlp={k: v for k, v in p["mlp"].items() if k in ("router", "router_bias")}) if "router" in p["mlp"] else p

    def bank(p):
        return (p["mlp"]["bank_layer"], p["mlp"]["experts_w13"], p["mlp"]["experts_w2"]) if "router" in p["mlp"] else None

    whole = {
        kind: jax.jit(lambda p, x, b, kind=kind: Block(tcfg, attn_fn, kind).apply({"params": p}, x, b))
        for kind in set(tcfg.leading_pattern + tcfg.layer_pattern)
    }

    def router_input(kind):
        name, mixer = mixers[LAYER_KINDS[kind][0]]

        @jax.jit
        def fn(p, x):
            a = x + mixer.apply({"params": p[name]}, norm.apply({"params": p[f"{name}_norm"]}, x))
            return norm.apply({"params": p["mlp_norm"]}, a)

        return fn

    x = params["embed"][tokens].astype(tcfg.dtype)
    seen = set()
    for kind, p in lfm2_moe_lm.layer_trees(params, job.cfg):
        if LAYER_KINDS[kind][0] == "short_conv" and "conv" not in seen:
            seen.add("conv")
            check_conv_scope(job, tcfg, p, x)
        if LAYER_KINDS[kind][1] == "experts" and kind not in seen:
            seen.add(kind)
            named = SimpleNamespace(cfg=job.cfg, checks=_Named(job.checks, f"{kind}."))
            check_expert_layer(named, tcfg, p["mlp"], router_input(kind)(own(p), x))
            if seen >= {"conv"} | {k for k in tcfg.layer_pattern if LAYER_KINDS[k][1] == "experts"}:
                return
        x = whole[kind](own(p), x, bank(p))
    raise SystemExit("benchmark: the configuration lacks a conv layer or an expert layer")


def check(job, state) -> None:
    """(0) one conv layer's arithmetic and one expert layer of each kind, each on
    the same input; (1) one node's first
    local step — loss, every adapter gradient, the share of assignments on which
    program and reference agree — and (2) one federated round of a reduced job,
    against the float32 reference at the published widths and the timed
    sequence length."""
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

        module = CausalLM(module.cfg, _attention(job, seq, cfg["head_dim"])[0])
    check_layers(job, module, state["model"].params, x)

    @jax.jit
    def system_step(lo, base_, bx, by):
        """(loss, adapter gradients, ``[B, expert layers, T, k]`` assignments in
        layer order) of the timed path's loss — the assignments from THE forward
        that the gradient was taken through, not from a second program."""

        def loss_of(lo_):
            loss, _, _, routing = _lm_forward(lo_, base_, module, bx, by)
            chosen = sown_by_layer(module.cfg, routing)  # [expert layers, B T, k]
            chosen = chosen.reshape(chosen.shape[0], *bx.shape, chosen.shape[-1])
            return loss, jnp.swapaxes(chosen, 0, 1)

        (loss, chosen), grads = jax.value_and_grad(loss_of, has_aux=True)(lo)
        return loss, grads, chosen

    ref_grad = _reference_grad(job)
    got_loss, got, got_chosen = system_step(probe, base, x, y)
    with jax.default_matmul_precision("highest"):
        (want_loss, want_chosen), want = ref_grad(probe, base, x, y, got_chosen)
    job.checks.close("step.loss", float(got_loss), float(want_loss), ck.LOSS_REL)
    job.checks.gradients("step", got, want)
    for kind in ("attn", "conv", "mlp"):  # and by part, so that a fault has an address
        pick = lambda tree: [leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree) if f"'{kind}'" in jax.tree_util.keystr(p)]  # noqa: E731
        job.checks.gradients(f"step.{kind}", pick(got), pick(want))
    job.checks.at_least("step.routing_agreement", _agreement(got_chosen, want_chosen), STEP_ROUTING_AGREE)
    kinds = [k for k in lfm2_moe_lm.layer_kinds(cfg) if lfm2_moe_lm.KINDS[k][1] == "experts"]
    by_layer = [(k.split("_")[0], round(_agreement(got_chosen[:, j], want_chosen[:, j]), 5)) for j, k in enumerate(kinds)]
    job.say(f"routing agreement by expert layer (program on bf16 activations, reference on float32): {by_layer}")

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


def describe(job, state) -> dict:
    cfg, tr = job.cfg, job.traffic
    seq = tr["seq_len"]
    step = flops_conv_moe.lora_step_flops(cfg, seq)
    node_steps = tr["n_nodes"] * tr["local_steps"] * tr["batch_size"]
    peak = flops.peaks("TPU v5 lite")
    ops, moved = flops_conv_moe.gmm_pass(cfg, seq)
    # the program's counter, read AFTER the window: every round's history entry
    # carries the device scalar; nothing fetched it before now
    loads = [float(e["moe_load_max_over_mean"]) for e in state["fed"].history if "moe_load_max_over_mean" in e]
    kinds = lfm2_moe_lm.layer_kinds(cfg)
    return {
        "train_nodes": tr["n_nodes"],
        "steps_per_program_run": node_steps // len(job.devices),
        "flops_per_round": step["total"] * node_steps,
        "flops_per_sequence_step": step,
        "flash_flops_per_round": 0.0,  # flash_roofline is not this cell's: p2pfl_gmm runs beside flash
        "round_program": "jit_spmd_lora_round",
        "fold_bytes": flops.fedavg_fold_bytes(tr["n_nodes"], flops_conv_moe.lora_params(cfg)),
        "layer_kinds": kinds,
        "expert_layers": sum(lfm2_moe_lm.KINDS[k][1] == "experts" for k in kinds),
        "gmm_pass": {"flops": ops, "bytes": moved},
        "gmm_floor_s_per_step": flops_conv_moe.gmm_floor_seconds(cfg, seq, peak),
        "gqa_flash_floor_s_per_step": flops_conv_moe.gqa_flash_floor_seconds(cfg, seq, peak),
        "moe_load_max_over_mean": float(np.mean(loads)) if loads else None,
        "moe_load_rounds": len(loads),
    }
