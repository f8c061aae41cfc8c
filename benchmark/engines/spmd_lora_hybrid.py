"""``SpmdLoraFederation`` over a hybrid state-space / attention LM (a period of
unlike layers: ``TransformerConfig.layer_pattern``). Same federation, window
and checks as ``spmd_lora``; its own model construction, reference
(``reference/jamba_lm.py``), kernel expectations and shape functions
(``flops_ssm.py``)."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks as ck
from benchmark import flops, flops_ssm, traffic, window
from benchmark.engines.spmd_lora import _attention, _federation, measure, reset, warm  # noqa: F401 (the engine's functions)
from benchmark.reference import fedavg, jamba_lm


def _transformer_config(cfg: dict, args: dict):
    from p2pfl_tpu.models.transformer import TransformerConfig

    targets = set(cfg["lora"]["targets"])
    want = {"in_proj", "x_proj", "out_proj", "wq", "wk", "wv", "wo", "w1", "w2", "w3"}
    if targets != want:
        raise SystemExit(f"benchmark: spmd_lora_hybrid adapts {sorted(want)}, the configuration asks for {sorted(targets)}")
    if cfg["num_experts"] != 1 or not cfg["tie_word_embeddings"] or cfg["rms_norm_eps"] != 1e-6:
        raise SystemExit("benchmark: spmd_lora_hybrid runs dense feed-forwards, a tied head and eps 1e-6 (the program's)")
    period = cfg["attn_layer_period"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden=cfg["intermediate_size"], rope_theta=cfg.get("rope_theta"),
        layer_pattern=tuple(flops_ssm.layer_kinds(dict(cfg, num_hidden_layers=period))),
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"], ssm_expand=cfg["mamba_expand"],
        ssm_dt_rank=cfg["mamba_dt_rank"],
        lora_rank=cfg["lora"]["rank"], lora_alpha=cfg["lora"]["alpha"], lora_mlp=True,
        remat=True, scan_layers=args["scan_layers"], remat_policy=args["remat_policy"],
    )


def build(job) -> dict:
    from p2pfl_tpu.models.base import FlaxModel
    from p2pfl_tpu.models.transformer import CausalLM

    cfg, tr = job.cfg, job.traffic
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise SystemExit("benchmark: the program's head_dim is hidden_size / num_attention_heads")
    tcfg = _transformer_config(cfg, job.cell["engine_args"])
    attn_fn, attn = _attention(job, tr["seq_len"], cfg["head_dim"])
    module = CausalLM(tcfg, attn_fn)

    # weights: ONE jitted call from the seed, on the device, float32; the
    # state-space leaves (A_log, D, dt_bias) come out of the module's own
    # initialisers as the configuration file's "assumed.weights" says
    @jax.jit
    def init(key):
        return CausalLM(tcfg, None).init(key, jnp.zeros((1, 16), jnp.int32))["params"]

    params = init(jax.random.PRNGKey(job.seed))
    model = FlaxModel(module, params, (tr["seq_len"],), cfg["vocab_size"])
    model.extra["config"] = tcfg
    shards = traffic.generate(tr, cfg, job.seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    kinds = flops_ssm.layer_kinds(cfg)
    job.say(
        f"model: {n_params / 1e9:.3f} B parameters ({kinds.count('mamba')} mamba + {kinds.count('attention')} attention "
        f"layers; shape functions say {flops_ssm.model_params(cfg) + flops_ssm.lora_params(cfg)}), attn={attn}, "
        f"{tr['n_nodes']} nodes x {tr['local_steps']} steps x {tr['batch_size']} x {tr['seq_len']} tokens"
    )
    return {"fed": None, "model": model, "module": module, "attn": attn, "shards": shards}


def _reference_grad(job):
    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]

    @jax.jit
    def grad(lora, base, x, y):
        return jax.value_and_grad(jamba_lm.loss)(lora, base, x, y, cfg, lora_scale=scale)

    return grad


def check(job, state) -> None:
    """(1) one node's first local step — loss and every adapter gradient — and
    (2) one federated round of a reduced job, against the float32 reference at
    the published widths, on the longest sequence the reference's per-token
    states fit beside the base (the cell's file says which and why)."""
    from p2pfl_tpu.learning.lora import _lm_loss, split_lora

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
    if seq != job.traffic["seq_len"]:  # the attention layer's schedule is per length
        from p2pfl_tpu.models.transformer import CausalLM

        module = CausalLM(module.cfg, _attention(job, seq, cfg["head_dim"])[0])

    @jax.jit
    def system_grad(lo, base_, bx, by):
        (loss, _), grads = jax.value_and_grad(_lm_loss, has_aux=True)(lo, base_, module, bx, by)
        return loss, grads

    ref_grad = _reference_grad(job)
    got_loss, got = system_grad(probe, base, x, y)
    with jax.default_matmul_precision("highest"):
        want_loss, want = ref_grad(probe, base, x, y)
    job.checks.close("step.loss", float(got_loss), float(want_loss), ck.LOSS_REL)
    job.checks.gradients("step", got, want)
    for kind in ("mamba", "attn", "mlp"):  # and by part, so that a fault has an address
        pick = lambda tree: [leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree) if f"'{kind}'" in jax.tree_util.keystr(p)]  # noqa: E731
        job.checks.gradients(f"step.{kind}", pick(got), pick(want))

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
    ref_step = fedavg.adam_step(ref_grad)
    trained, ref_losses = [], []
    with jax.default_matmul_precision("highest"):
        for shard in shards:
            perm = order.permutation(len(shard["y"]))[:steps]
            batches = [(base, jnp.asarray(shard["x"][i:i + 1]), jnp.asarray(shard["y"][i:i + 1])) for i in perm]
            node, losses = fedavg.adam_train(lora, batches, ref_step, opt)
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


_KERNEL = re.compile(r'kernel_name\s*=\s*"([A-Za-z0-9_]+)"')


def finish(job, state, win: dict) -> None:
    window.spmd_final_checks(job, state["fed"], win)
    if job.trace:
        # Mosaic calls of the lowered round by the kernel's own name
        text = state["fed"].lower_round(epochs=1).as_text()
        got: dict[str, int] = {}
        for name in _KERNEL.findall(text):
            got[name] = got.get(name, 0) + 1
        want = job.cell["expect"]["kernels_in_round"]
        job.checks.add("round.kernels", got == want, got=got, want=want)


def describe(job, state) -> dict:
    cfg, tr = job.cfg, job.traffic
    seq = tr["seq_len"]
    step = flops_ssm.lora_step_flops(cfg, seq)
    node_steps = tr["n_nodes"] * tr["local_steps"] * tr["batch_size"]
    fwd_bytes, bwd_bytes = flops_ssm.scan_min_bytes(cfg, seq)
    fwd_flops, bwd_flops = flops_ssm.scan_flops(cfg, seq)
    return {
        "train_nodes": tr["n_nodes"],
        "steps_per_program_run": node_steps // len(job.devices),
        "flops_per_round": step["total"] * node_steps,
        "flops_per_sequence_step": step,
        "flash_flops_per_round": 0.0,  # flash_roofline is not this cell's: other Mosaic calls run beside flash
        "round_program": "jit_spmd_lora_round",
        "fold_bytes": flops.fedavg_fold_bytes(tr["n_nodes"], flops_ssm.lora_params(cfg)),
        "ssm_layers": flops_ssm.layer_kinds(cfg).count("mamba"),
        "ssm_scan_min_bytes": {"fwd": fwd_bytes, "bwd": bwd_bytes},
        "ssm_scan_flops": {"fwd": fwd_flops, "bwd": bwd_flops},
        "ssm_scan_floor_s_per_step": flops_ssm.scan_floor_seconds(cfg, seq, flops.peaks("TPU v5 lite")),
    }
