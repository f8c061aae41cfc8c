"""``SpmdLoraFederation`` over a latent-attention / sparse-expert LM (one
leading dense layer, then a scanned run of expert layers:
``TransformerConfig.layer_pattern`` of ``mla_dense`` / ``mla_experts``). Same
federation, window and round checks as ``spmd_lora``; its own model
construction, reference (``reference/glm_moe_lm.py``), kernel expectations and
shape functions (``flops_moe.py``).

Discrete routing and the comparison. Program and reference run on different
activations (bfloat16 against float32), so a token whose fourth and fifth
scores lie closer than the rounding chooses differently on the two sides — a
few assignments in a hundred, more in deeper layers — and such a token's
gradient differs by a whole expert, not by rounding (first reading, PR 31: with
each side on its own choice the gradient distance read 0.25 and the round's
cosine 0.85 on a correct program). So the reference is HELD TO THE PROGRAM'S
ASSIGNMENTS (``glm_moe_lm.route(forced=...)``: the scores stay its own) for
the loss, the gradients and the round, and the share of assignments on which
the two sides agree is compared on its own. The assignments are read from the
forward that the program's gradient was taken through (``_lm_forward``'s fourth
result): a second program of the same model rounds near-ties differently on a
TPU, and a token with ONE of its 16 assignments (4 a layer, 4 layers) changed
has another gradient — two assignments in a hundred move a quarter of the
tokens (second reading, PR 31: held to a SEPARATE forward's assignments the
distance still read 0.23 at 4096 tokens).

Tolerances this engine brings (the gradient, loss and round limits are
``checks.py``'s, shared with every LM cell; readings in PERF.md section 6; the
faults are ``benchmark/planted_faults.py``'s, planted through this ``check``):

- ``LAYER_ROUTING_AGREE``: ONE expert layer, program and reference given the
  SAME input — the program's own ``mlp_norm`` output in front of its first
  expert layer on the check's tokens. The router is float32 on both sides, so
  they choose alike except where two scores differ by float32 rounding of a
  2048-term sum (~1e-6: under one token in 4096 expected; the floor allows
  eight assignments of 16,384; every reading was 1.0). A bfloat16 router — or
  float32 operands at the TPU's default matmul precision, which is one
  bfloat16 pass — quantises the scores to about 1e-3 and read 0.9980-0.9986;
  a choice made without the bias read 0.679 with a bias of +-0.1 (chip) and
  0.920 with N(0, 0.01) (CPU, float32, published widths).
- ``LAYER_TOKEN_REL``: on the tokens whose four experts agree, the worst
  token's relative distance between the program's routed output and the
  reference's. bfloat16 products through two matrices leave 0.0042-0.0045
  (nine chip readings). Weights taken WITH the bias read 0.022 at the
  configuration's N(0, 0.01) (CPU, float32; 0.116 on the chip with a bias of
  +-0.1), a token that lost ONE of its four assignments 0.46-0.50: the ceiling
  lies between 0.0045 and 0.022.
- ``STEP_ROUTING_AGREE``: the whole first step, all expert layers, each side on
  its own activations: near-ties flip, more so deeper in the stack (0.988 in
  the first expert layer, 0.978 in the fourth; 0.9824-0.9834 overall on seven
  seeds). A fault that changes a layer's OUTPUT shows in the layers after it:
  weights taken with a bias of +-0.1 read 0.9668 (by layer 0.988, 0.969, 0.957,
  0.953), a choice without it 0.674. The floor lies between 0.9668 and 0.9824;
  a fault too small for it is the layer check's to find.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks as ck
from benchmark import flops, flops_moe, traffic, window
from benchmark.engines.spmd_lora import _attention, _federation, measure, reset, warm  # noqa: F401 (the engine's functions)
from benchmark.engines.spmd_lora_hybrid import _KERNEL  # the Mosaic kernel's name in a lowered program
from benchmark.reference import fedavg, glm_moe_lm

LAYER_ROUTING_AGREE = 0.9995
LAYER_TOKEN_REL = 0.012
STEP_ROUTING_AGREE = 0.975


def _transformer_config(cfg: dict, args: dict):
    from p2pfl_tpu.models.transformer import TransformerConfig

    want = {"q_a", "q_b", "kv_a", "kv_b", "o", "w1", "w2", "w3"}
    if set(cfg["lora"]["targets"]) != want:
        raise SystemExit(f"benchmark: spmd_lora_moe adapts {sorted(want)}, the configuration asks for {sorted(cfg['lora']['targets'])}")
    if (
        cfg["n_group"] != 1 or cfg["topk_group"] != 1 or not cfg["norm_topk_prob"] or cfg["topk_method"] != "noaux_tc"
        or not cfg["tie_word_embeddings"] or cfg["rope_scaling"] is not None or cfg["partial_rotary_factor"] != 1
        or cfg["num_key_value_heads"] != cfg["num_attention_heads"] or cfg["attention_bias"]
    ):
        raise SystemExit(
            "benchmark: spmd_lora_moe runs noaux_tc routing without a group limit, normalised weights, a tied head, "
            "unscaled RoPE on the whole rotary part, one key/value head a query head, no attention bias"
        )
    if args["gmm"] not in ("pallas", "xla"):
        raise SystemExit("benchmark: engine_args.gmm is 'pallas' (the Mosaic kernel on a TPU) or 'xla'")
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden=cfg["intermediate_size"], rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        layer_pattern=tuple(glm_moe_lm.layer_kinds(cfg)),
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        routed_experts=cfg["n_routed_experts"], experts_per_token=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"], shared_experts=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"], expert_tile_m=args["gmm_tile_m"],
        expert_impl=None if args["gmm"] == "pallas" else "xla",  # None: the kernel on a TPU, XLA on the CPU rehearsal
        lora_rank=cfg["lora"]["rank"], lora_alpha=cfg["lora"]["alpha"], lora_mlp=True,
        remat=True, scan_layers=args["scan_layers"], remat_policy=args["remat_policy"],
    )


def seeded_params(tcfg, key, bias_std: float):
    """The module's own initialisers, and ``e_score_correction_bias`` drawn
    N(0, ``bias_std``) (the configuration file's ``assumed.weights``: zeros
    would hide the choose / weigh split). Traceable: the caller jits it."""
    from p2pfl_tpu.models.transformer import CausalLM

    params = CausalLM(tcfg, None).init(key, jnp.zeros((1, 16), jnp.int32))["params"]
    bias_key = jax.random.fold_in(key, 0x62696173)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: bias_std * jax.random.normal(bias_key, a.shape, a.dtype)
        if "router_bias" in jax.tree_util.keystr(path) else a,
        params,
    )


def first_router_input(module, params: dict, cfg: dict, tokens):
    """(``mlp`` subtree of the first expert layer, its router's input on
    ``tokens`` ``[B, T]``): the PROGRAM's layer code (its modules, its kernels,
    its dtypes) one layer at a time on the parameters of
    :func:`glm_moe_lm.layer_trees`, down to the first expert layer's ``mlp_norm``."""
    from p2pfl_tpu.models.transformer import Block, MLAttention, RMSNorm

    tcfg, attn_fn = module.cfg, module.attn_fn
    norm = RMSNorm(tcfg.dtype, tcfg.norm_eps)
    dense_layer = jax.jit(lambda p, x: Block(tcfg, attn_fn, "mla_dense").apply({"params": p}, x))

    @jax.jit
    def router_input(p, x):
        a = x + MLAttention(tcfg, attn_fn).apply({"params": p["attn"]}, norm.apply({"params": p["attn_norm"]}, x))
        return norm.apply({"params": p["mlp_norm"]}, a)

    x = params["embed"][tokens].astype(tcfg.dtype)
    for kind, p in glm_moe_lm.layer_trees(params, cfg):
        if kind == "mla_experts":
            return p["mlp"], router_input(p, x)
        x = dense_layer(p, x)
    raise SystemExit("benchmark: the configuration has no expert layer")


def build(job) -> dict:
    from p2pfl_tpu.models.base import FlaxModel
    from p2pfl_tpu.models.transformer import CausalLM

    cfg, tr = job.cfg, job.traffic
    width = flops_moe.head_width(cfg)
    if cfg["v_head_dim"] != width:
        raise SystemExit("benchmark: the flash kernels take q.k and v heads of one width")
    from p2pfl_tpu.models.transformer import LAYER_KINDS

    if "mla_experts" not in LAYER_KINDS:  # LAYER_KINDS is a tuple before the kinds name a feed-forward
        raise SystemExit("benchmark: this program has no latent-attention / expert layer kinds (LAYER_KINDS lacks 'mla_experts')")
    tcfg = _transformer_config(cfg, job.cell["engine_args"])
    attn_fn, attn = _attention(job, tr["seq_len"], width)
    module = CausalLM(tcfg, attn_fn)
    # weights: ONE jitted call from the seed, on the device, each leaf in the
    # dtype it is kept in (the expert bank bfloat16, everything else float32)
    params = jax.jit(lambda key: seeded_params(tcfg, key, cfg["router_bias_std"]))(jax.random.PRNGKey(job.seed))
    model = FlaxModel(module, params, (tr["seq_len"],), cfg["vocab_size"])
    model.extra["config"] = tcfg
    shards = traffic.generate(tr, cfg, job.seed)
    by_dtype: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        by_dtype[leaf.dtype.name] = by_dtype.get(leaf.dtype.name, 0) + leaf.size
    kinds = glm_moe_lm.layer_kinds(cfg)
    job.say(
        f"model: {sum(by_dtype.values()) / 1e9:.3f} B parameters by dtype {by_dtype} ({kinds.count('mla_dense')} dense + "
        f"{kinds.count('mla_experts')} expert layers; shape functions say {flops_moe.model_params(cfg)} + "
        f"{flops_moe.lora_params(cfg)} adapters), attn={attn}, gmm={job.cell['engine_args']['gmm']}, "
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
        return jax.value_and_grad(glm_moe_lm.loss_and_routing, has_aux=True)(
            lora, base, x, y, cfg, lora_scale=scale, forced=forced
        )

    return grad


def _agreement(got, want) -> float:
    """Share of assignments on which two ``[..., k]`` choices agree (as sets a token)."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.mean((got[..., :, None] == want[..., None, :]).any(-1)))


def check_expert_layer(job, tcfg, mlp: dict, h) -> None:
    """ONE expert layer of the timed path (``ExpertFFN`` under the cell's
    config, its kernel) against "every expert on every row, masked", both given
    the same input ``h`` — ``[1, T, hidden]`` in the compute dtype, the
    program's own ``mlp_norm`` output in front of that layer on the check's
    tokens (:func:`first_router_input`): routing must agree, and no
    agreeing token may be far off — which a dropped assignment is, and a weight
    taken with the bias. ``mlp``: that layer's subtree as ``layer_trees`` gives it."""
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
        return glm_moe_lm.experts(h_[0].astype(jnp.float32), p, dict(cfg, n_shared_experts=0), 0.0)

    routed = {k: v for k, v in mlp.items() if k != "shared"}
    got, got_chosen = program(routed, h)
    with jax.default_matmul_precision("highest"):
        want, want_chosen = reference(routed, h)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    same = (np.sort(np.asarray(got_chosen), -1) == np.sort(np.asarray(want_chosen), -1)).all(-1)
    token_rel = np.linalg.norm(got - want, axis=-1) / (np.linalg.norm(want, axis=-1) + 1e-300)
    job.checks.at_least("layer.routing_agreement", _agreement(got_chosen, want_chosen), LAYER_ROUTING_AGREE)
    job.checks.at_most("layer.worst_agreeing_token_rel", float(token_rel[same].max()), LAYER_TOKEN_REL)
    job.checks.at_most("layer.out_rel_l2", ck.rel_l2(got[same], want[same]), ck.GRAD_REL)


def check(job, state) -> None:
    """(0) one expert layer on the same input; (1) one node's first local step —
    loss, every adapter gradient, the share of assignments on which program and
    reference agree — and (2) one federated round of a reduced job, against the
    float32 reference at the published widths and the timed sequence length."""
    from p2pfl_tpu.learning.lora import _lm_forward, split_lora

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

        module = CausalLM(module.cfg, _attention(job, seq, flops_moe.head_width(cfg))[0])
    check_expert_layer(job, module.cfg, *first_router_input(module, state["model"].params, cfg, x))

    @jax.jit
    def system_step(lo, base_, bx, by):
        """(loss, adapter gradients, ``[B, expert layers, T, k]`` assignments) of
        the timed path's loss — the assignments from THE forward that the
        gradient was taken through, not from a second program."""

        def loss_of(lo_):
            loss, _, _, routing = _lm_forward(lo_, base_, module, bx, by)
            # ONE expert run (layer_kinds): its sown choices, stacked along the scans
            # [periods = 1, layers, B T, k] (a run of one layer: [1, B T, k])
            (chosen,) = jax.tree.leaves(routing)
            chosen = chosen.reshape(-1, *bx.shape, chosen.shape[-1])
            return loss, jnp.swapaxes(chosen, 0, 1)

        (loss, chosen), grads = jax.value_and_grad(loss_of, has_aux=True)(lo)
        return loss, grads, chosen

    ref_grad = _reference_grad(job)
    got_loss, got, got_chosen = system_step(probe, base, x, y)
    with jax.default_matmul_precision("highest"):
        (want_loss, want_chosen), want = ref_grad(probe, base, x, y, got_chosen)
    job.checks.close("step.loss", float(got_loss), float(want_loss), ck.LOSS_REL)
    job.checks.gradients("step", got, want)
    for kind in ("attn", "mlp"):  # and by part, so that a fault has an address
        pick = lambda tree: [leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree) if f"'{kind}'" in jax.tree_util.keystr(p)]  # noqa: E731
        job.checks.gradients(f"step.{kind}", pick(got), pick(want))
    job.checks.at_least("step.routing_agreement", _agreement(got_chosen, want_chosen), STEP_ROUTING_AGREE)
    by_layer = [round(_agreement(got_chosen[:, j], want_chosen[:, j]), 5) for j in range(got_chosen.shape[1])]
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


def kernels_in(text: str) -> dict[str, int]:
    """Mosaic calls of a lowered program by the kernel's own name."""
    got: dict[str, int] = {}
    for name in _KERNEL.findall(text):
        got[name] = got.get(name, 0) + 1
    return got


def finish(job, state, win: dict) -> None:
    window.spmd_final_checks(job, state["fed"], win)
    if job.trace:
        got = kernels_in(state["fed"].lower_round(epochs=1).as_text())
        want = job.cell["expect"]["kernels_in_round"]
        job.checks.add("round.kernels", got == want, got=got, want=want)


def describe(job, state) -> dict:
    cfg, tr = job.cfg, job.traffic
    seq = tr["seq_len"]
    step = flops_moe.lora_step_flops(cfg, seq)
    node_steps = tr["n_nodes"] * tr["local_steps"] * tr["batch_size"]
    peak = flops.peaks("TPU v5 lite")
    ops, moved = flops_moe.gmm_pass(cfg, seq)
    # the program's counter, read AFTER the window: every round's history entry
    # carries the device scalar; nothing fetched it before now
    loads = [float(e["moe_load_max_over_mean"]) for e in state["fed"].history if "moe_load_max_over_mean" in e]
    return {
        "train_nodes": tr["n_nodes"],
        "steps_per_program_run": node_steps // len(job.devices),
        "flops_per_round": step["total"] * node_steps,
        "flops_per_sequence_step": step,
        "flash_flops_per_round": 0.0,  # flash_roofline is not this cell's: p2pfl_gmm runs beside flash
        "round_program": "jit_spmd_lora_round",
        "fold_bytes": flops.fedavg_fold_bytes(tr["n_nodes"], flops_moe.lora_params(cfg)),
        "expert_layers": glm_moe_lm.layer_kinds(cfg).count("mla_experts"),
        "gmm_pass": {"flops": ops, "bytes": moved},
        "gmm_floor_s_per_step": flops_moe.gmm_floor_seconds(cfg, seq, peak),
        "mla_flash_floor_s_per_step": flops_moe.mla_flash_floor_seconds(cfg, seq, peak),
        "moe_load_max_over_mean": float(np.mean(loads)) if loads else None,
        "moe_load_rounds": len(loads),
    }
