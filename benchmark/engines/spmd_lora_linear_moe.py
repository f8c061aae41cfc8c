"""``SpmdLoraFederation`` over a hybrid linear-attention sparse-expert LM of which
this chip holds a SHARE (``solar_open2``, Solar-Open2-250B: periods of one gated
NoPE GQA layer and three gated delta-rule KDA layers, every layer an expert
layer — ``TransformerConfig.layer_pattern`` of ``full_experts`` /
``kda_experts``; an untied head, 40 of 320 experts of every layer). Same
federation, window and round checks as ``spmd_lora``; the expert comparison,
its held share and the round are ``spmd_lora_window_moe``'s (imported, not
copied, where they are functions); its own model construction, reference
(``reference/solar_open2_lm.py``, the delta rule one TOKEN at a time), kernel
expectations and shape functions (``flops_linear_moe.py``).

The reference is HELD TO THE PROGRAM'S ASSIGNMENTS for the loss, the gradients
and the round (``spmd_lora_moe`` says why), and the share of assignments on
which the two sides agree is compared on its own. Besides, each on the
program's own input so that depth does not enter:

- THE DELTA RULE ALONE (``check_delta_rule``): the program's
  ``ops/delta_rule.gated_delta_rule`` as the timed path runs it (the kernels on a
  TPU, the cell's chunk) against ``solar_open2_lm.recurrence``, both on the SAME
  float32 q, k, v, decay and β — the reference's own, from the first KDA layer's
  parameters on the program's ``attn_norm`` output — forward and the gradient of
  a seeded cotangent. Nothing else differs, so the limit ``KDA_OP_REL`` is set
  against the state carried in bfloat16, the precision just below the
  configuration's float32 state;
- one KDA and the GQA layer alone: the program's ``KDAMixer`` / ``Attention``
  (kernels, dtypes, gates) on the program's ``attn_norm`` output against
  ``solar_open2_lm.kda`` / ``gqa`` (``checks.GRAD_REL``: the projections are
  bfloat16 on one side only);
- the first expert layer, as ``spmd_lora_window_moe.check_expert_layer``;
- the program's counters: ``moe_held_share`` against the count of its own
  assignments and against the reference router's share (the window engine's
  ``HELD_SHARE_ABS``); ``kda_neg_beta_share`` between 0 and 1 — that the traffic
  drives β above 1 at all.

Tolerances this engine brings (the loss, gradient and round limits are
``checks.py``'s): ``KDA_OP_REL``, ``KDA_OP_GRAD_REL`` and ``STEP_ROUTING_AGREE``,
readings beside the constants.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks as ck
from benchmark import flops, flops_linear_moe, traffic
from benchmark.engines.spmd_lora import _attention, _federation, measure, reset, warm  # noqa: F401 (the engine's functions)
from benchmark.engines.spmd_lora_conv_moe import _Named
from benchmark.engines.spmd_lora_moe import _agreement, seeded_params
from benchmark.engines.spmd_lora_window_moe import HELD_SHARE_ABS, _window_mean, check_expert_layer, finish  # noqa: F401
from benchmark.reference import fedavg, solar_open2_lm

# the delta rule alone, program (kernels, chunk 64) against the token recurrence on the same float32
# inputs (my chip runs, PR 39): the output read 2.7e-5-3.3e-5 sound on six seeds, 6.8e-4 / 7.8e-4 with the
# state carried across chunks in bfloat16 (the precision just below the configuration's float32 state,
# which this limit has to refuse; 5.35e-4 on the probe's inputs). About the geometric mean: four times of
# room above the sound readings, five below the fault's
KDA_OP_REL = 1.3e-4
# the gradients of a seeded cotangent, each input's (my chip runs, PR 39): sound 3.2e-5-6.6e-5 for q, k,
# v, beta and 9.9e-5-1.27e-4 for the decay (its cotangent sums O(1) terms that cancel); with the bfloat16
# state q 9.1e-4 / 1.04e-3, k 4.6e-4 / 5.5e-4, the decay 1.23e-3 / 1.40e-3, beta 3.3e-4 / 3.9e-4 (under
# the limit: beta's alone would not refuse it), v unmoved. Three times the decay's sound reading
KDA_OP_GRAD_REL = 4e-4
# the whole first step, all four expert layers (top 8 of 320), each side on its own activations (my chip
# runs, PR 39): 0.98067-0.98188 on six seeds, by layer 0.991-0.993 (GQA) falling to 0.971-0.973 (the
# third KDA layer). A fault that moves a layer's output moves every later layer's choices (the other
# expert cells read 0.95-0.97 for their subtlest such faults; none was read here); the floor leaves the
# sound readings 0.0107 of room, nine times their range
STEP_ROUTING_AGREE = 0.97


def _transformer_config(cfg: dict, args: dict):
    from p2pfl_tpu.models.transformer import TransformerConfig

    want = {"wq", "wk", "wv", "wo", "wg", "w1", "w2", "w3"}
    if set(cfg["lora"]["targets"]) != want:
        raise SystemExit(f"benchmark: spmd_lora_linear_moe adapts {sorted(want)}, the configuration asks for {sorted(cfg['lora']['targets'])}")
    lin = cfg["linear_attn_config"]
    if (
        cfg["use_rope"] or not cfg["use_gqa_gate"] or cfg["kda_use_full_proj"] or not cfg["norm_topk_prob"]
        or cfg["tie_word_embeddings"] or cfg["first_k_dense_replace"] != 0 or lin["num_kv_heads"] is not None
        or cfg["state_dtype"] != "float32" or not cfg["kda_allow_neg_eigval"]
        or (lin["num_heads"], lin["head_dim"], cfg["kda_low_rank"]) != (cfg["num_attention_heads"], cfg["head_dim"], cfg["head_dim"])
    ):
        raise SystemExit(
            "benchmark: spmd_lora_linear_moe runs NoPE gated GQA, KDA with beta in (0, 2), as many heads of the same width "
            "as the GQA layers, as many key heads as value heads, decay and gate projections of rank head_dim, a float32 "
            "state, weights normalised over the chosen, an untied head, and an expert layer in every layer"
        )
    if args["gmm"] not in ("pallas", "xla"):
        raise SystemExit("benchmark: engine_args.gmm is 'pallas' (the Mosaic kernel on a TPU) or 'xla'")
    pattern, _ = solar_open2_lm.stack(cfg)
    first, held, width = solar_open2_lm.share(cfg)
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"], rope_theta=None, norm_eps=cfg["rms_norm_eps"],
        layer_pattern=tuple(pattern), attn_gate=True, tie_head=False,
        routed_experts=width, experts_held=held, first_expert=first,
        experts_per_token=cfg["num_experts_per_tok"], expert_hidden=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"], routed_scale=cfg["routed_scaling_factor"], expert_tile_m=args["gmm_tile_m"],
        expert_impl=None if args["gmm"] == "pallas" else "xla",  # None: the kernel on a TPU, XLA on the CPU rehearsal
        conv_taps=lin["short_conv_kernel_size"], kda_chunk=cfg["kda_chunk"],
        lora_rank=cfg["lora"]["rank"], lora_alpha=cfg["lora"]["alpha"], lora_mlp=True,
        remat=True, scan_layers=args["scan_layers"], remat_policy=args["remat_policy"],
    )


def build(job) -> dict:
    from p2pfl_tpu.models import transformer
    from p2pfl_tpu.models.base import FlaxModel

    cfg, tr = job.cfg, job.traffic
    if "kda_experts" not in transformer.LAYER_KINDS:
        raise SystemExit("benchmark: this program has no delta-rule layer kind (LAYER_KINDS lacks 'kda_experts')")
    if not job.cell["engine_args"]["scan_layers"]:
        raise SystemExit("benchmark: spmd_lora_linear_moe reads the scanned parameter tree (engine_args.scan_layers)")
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
    pattern, periods = solar_open2_lm.stack(cfg)
    first, held, width = solar_open2_lm.share(cfg)
    job.say(
        f"model: {sum(by_dtype.values()) / 1e9:.3f} B parameters by dtype {by_dtype} ({periods} x ({' '.join(pattern)}); "
        f"experts {first}..{first + held - 1} of {width} held; shape functions say {flops_linear_moe.model_params(cfg)} + "
        f"{flops_linear_moe.lora_params(cfg)} adapters), attn={attn}, gmm={job.cell['engine_args']['gmm']}, delta-rule "
        f"chunk {tcfg.kda_chunk}, {tr['n_nodes']} nodes x {tr['local_steps']} steps x {tr['batch_size']} x {tr['seq_len']} tokens"
    )
    return {"fed": None, "model": model, "module": module, "attn": attn, "shards": shards}


def _reference_grad(job):
    """``(lora, base, x, y, forced) -> ((loss, the reference's own choice), grads)``,
    the reference held to the ``[B, layers, T, k]`` assignments ``forced``."""
    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]

    @jax.jit
    def grad(lora, base, x, y, forced):
        return jax.value_and_grad(solar_open2_lm.loss_and_routing, has_aux=True)(
            lora, base, x, y, cfg, lora_scale=scale, forced=forced
        )

    return grad


def check_delta_rule(job, tcfg, p: dict, h) -> float:
    """The op alone (the module docstring says how). ``p``: the first KDA
    layer's ``attn`` subtree; ``h``: the program's ``attn_norm`` output in front
    of it. Returns the share of (token, head) with β > 1 there."""
    from p2pfl_tpu.ops import delta_rule

    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    heads, d = tcfg.n_heads, tcfg.head_width
    with jax.default_matmul_precision("highest"):
        inputs = jax.jit(lambda p_, h_: solar_open2_lm.kda_inputs(h_[0].astype(jnp.float32), p_, cfg, scale))(p, h)
    t = h.shape[1]
    flat = tuple(x.reshape(1, t, -1) for x in inputs[:4]) + (inputs[4][None],)
    probe = jax.random.normal(jax.random.PRNGKey(job.seed + 4), (1, t, heads * d), jnp.float32)

    def with_grad(fn):
        @jax.jit
        def run(*a):
            out, pull = jax.vjp(fn, *a)
            return out, pull(probe)

        return run

    got, got_grads = with_grad(lambda *a: delta_rule.gated_delta_rule(*a, chunk=tcfg.kda_chunk))(*flat)
    with jax.default_matmul_precision("highest"):
        want, want_grads = with_grad(
            lambda q, k, v, la, b: solar_open2_lm.recurrence(
                *(x[0].reshape(t, heads, d) for x in (q, k, v, la)), b[0]
            ).reshape(1, t, heads * d)
        )(*flat)
    job.checks.at_most("kda.op_out_rel_l2", ck.rel_l2(got, want), KDA_OP_REL)
    for name, g_got, g_want in zip(("q", "k", "v", "decay", "beta"), got_grads, want_grads):
        job.checks.at_most(f"kda.op_grad_{name}_rel_l2", ck.rel_l2(g_got, g_want), KDA_OP_GRAD_REL)
    return float(np.mean(np.asarray(inputs[4]) > 1.0))


def check_mixer(job, tcfg, attn_fn, kind: str, p: dict, h) -> None:
    """ONE mixer alone: the program's ``KDAMixer`` or ``Attention("full")`` on
    ``h`` against ``solar_open2_lm.kda`` / ``gqa``."""
    from p2pfl_tpu.models.transformer import Attention, KDAMixer

    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    mixer = KDAMixer(tcfg) if kind == "kda_experts" else Attention(tcfg, attn_fn, "full")
    ref = solar_open2_lm.kda if kind == "kda_experts" else solar_open2_lm.gqa
    program = jax.jit(lambda p_, h_: mixer.apply({"params": p_}, h_, mutable=["kda_stats"])[0][0].astype(jnp.float32))
    got = program(p, h)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p_, h_: ref(h_[0].astype(jnp.float32), p_, cfg, scale))(p, h)
    job.checks.at_most(f"{kind}.mixer_out_rel_l2", ck.rel_l2(got, want), ck.GRAD_REL)


def check_layers(job, module, params: dict, tokens) -> None:
    """The GQA and the first KDA mixer alone, the delta rule alone, and the first
    expert layer: the PROGRAM's layer code one layer at a time on the parameters
    of ``solar_open2_lm.layer_trees`` down to the layer's ``attn_norm`` /
    ``mlp_norm`` output, which both sides are then given."""
    from p2pfl_tpu.models.transformer import Attention, Block, RMSNorm

    tcfg, attn_fn = module.cfg, module.attn_fn
    norm = RMSNorm(tcfg.dtype, tcfg.norm_eps)

    def own(p):  # the subtree Block owns: the bank is handed in beside it
        return dict(p, mlp={k: v for k, v in p["mlp"].items() if k in ("router", "router_bias", "shared")})

    def bank(p):
        return p["mlp"]["bank_layer"], p["mlp"]["experts_w13"], p["mlp"]["experts_w2"]

    whole = {
        kind: jax.jit(lambda p, x, b, kind=kind: Block(tcfg, attn_fn, kind).apply({"params": p}, x, b, mutable=["kda_stats"])[0])
        for kind in set(tcfg.layer_pattern)
    }
    attn_input = jax.jit(lambda p, x: norm.apply({"params": p["attn_norm"]}, x))

    @jax.jit
    def router_input(p, x):  # the first layer is GQA
        a = x + Attention(tcfg, attn_fn, "full").apply({"params": p["attn"]}, norm.apply({"params": p["attn_norm"]}, x))
        return norm.apply({"params": p["mlp_norm"]}, a)

    x = params["embed"][tokens].astype(tcfg.dtype)
    seen = set()
    for kind, p in solar_open2_lm.layer_trees(params, job.cfg):
        if kind not in seen:
            seen.add(kind)
            h = attn_input(p, x)
            check_mixer(job, tcfg, attn_fn, kind, p["attn"], h)
            if kind == "kda_experts":
                share = check_delta_rule(job, tcfg, p["attn"], h)
                job.say(f"delta rule alone: beta > 1 on {share:.4f} of (token, head) in the first KDA layer")
            else:
                named = SimpleNamespace(cfg=solar_open2_lm._expert_cfg(job.cfg), checks=_Named(job.checks, f"{kind}."))
                held = check_expert_layer(named, tcfg, p["mlp"], router_input(own(p), x))
                job.say(f"{kind}: the reference's router holds {held:.4f} of this layer's assignments here (even share {flops_linear_moe.even_share(job.cfg)})")
        if seen >= {"kda_experts", "full_experts"}:
            return
        x = whole[kind](own(p), x, bank(p))
    raise SystemExit("benchmark: the configuration lacks a GQA or a KDA layer")


def _held_share(chosen, cfg: dict) -> float:
    first, held, _ = solar_open2_lm.share(cfg)
    chosen = np.asarray(chosen)
    return float(np.mean((chosen >= first) & (chosen < first + held)))


def check(job, state) -> None:
    """(0) the delta rule alone, one KDA and the GQA mixer alone, the first
    expert layer; (1) one node's first local step — loss, every adapter
    gradient, the routing agreement, the held share, the negative-β share — and
    (2) one federated round of a reduced job, against the float32 reference at
    the published widths and the timed sequence length."""
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
        """(loss, adapter gradients, ``[B, layers, T, k]`` assignments in layer
        order, the sown statistics) of the timed path's loss."""

        def loss_of(lo_):
            loss, _, stats, routing = _lm_forward(lo_, base_, module, bx, by)
            chosen = sown_by_layer(module.cfg, routing)  # [layers, B T, k]
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
    kinds = solar_open2_lm.layer_kinds(cfg)
    by_layer = [(k.split("_")[0], round(_agreement(got_chosen[:, j], want_chosen[:, j]), 5)) for j, k in enumerate(kinds)]
    job.say(f"routing agreement by layer (program on bf16 activations, reference on float32): {by_layer}")
    counted, explained = _held_share(got_chosen, cfg), _held_share(want_chosen, cfg)
    job.checks.close("step.held_share_counted", float(stats["moe_held_share"]), counted, 1e-5)
    job.checks.at_most("step.held_share_off_reference", abs(counted - explained), max(HELD_SHARE_ABS, 8.0 / np.asarray(got_chosen).size))
    neg = float(stats["kda_neg_beta_share"])
    job.checks.add("step.neg_beta_share_inside", 0.0 < neg < 1.0, got=neg)
    job.say(
        f"held share of the step's assignments: program {counted:.5f}, reference's router {explained:.5f}, even share "
        f"{flops_linear_moe.even_share(cfg)}; beta > 1 on {neg:.5f} of (token, head) over the KDA layers"
    )

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


def describe(job, state) -> dict:
    cfg, tr = job.cfg, job.traffic
    seq = tr["seq_len"]
    load, rounds = _window_mean(state["fed"], "moe_load_max_over_mean")
    held, _ = _window_mean(state["fed"], "moe_held_share")
    neg, _ = _window_mean(state["fed"], "kda_neg_beta_share")
    step = flops_linear_moe.lora_step_flops(cfg, seq, held)
    node_steps = tr["n_nodes"] * tr["local_steps"] * tr["batch_size"]
    peak = flops.peaks("TPU v5 lite")
    ops, moved = flops_linear_moe.gmm_pass(cfg, seq, held)
    return {
        "train_nodes": tr["n_nodes"],
        "steps_per_program_run": node_steps // len(job.devices),
        "flops_per_round": step["total"] * node_steps,
        "flops_per_sequence_step": step,
        "flash_flops_per_round": 0.0,  # flash_roofline is not this cell's: the delta-rule and gmm kernels run beside flash
        "round_program": "jit_spmd_lora_round",
        "fold_bytes": flops.fedavg_fold_bytes(tr["n_nodes"], flops_linear_moe.lora_params(cfg)),
        "layer_kinds": solar_open2_lm.layer_kinds(cfg),
        "kda_pass": flops_linear_moe.kda_pass(cfg, seq),
        "kda_floor_s_per_step": flops_linear_moe.kda_floor_seconds(cfg, seq, peak),
        "gmm_pass": {"flops": ops, "bytes": moved},
        "gmm_floor_s_per_step": flops_linear_moe.gmm_floor_seconds(cfg, seq, peak, held),
        "moe_load_max_over_mean": load,
        "moe_held_share": held,
        "kda_neg_beta_share": neg,
        "moe_load_rounds": rounds,
    }
