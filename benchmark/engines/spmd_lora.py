"""``SpmdLoraFederation``: N nodes' adapters stacked, the frozen base stored
once, one program a round (``parallel/spmd_lora.py``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks as ck
from benchmark import flops, traffic, window
from benchmark.reference import causal_lm, fedavg


def _lora_mlp(cfg: dict) -> bool:
    return "w1" in cfg["lora"]["targets"]


def _transformer_config(cfg: dict, args: dict):
    from p2pfl_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        lora_rank=cfg["lora"]["rank"], lora_alpha=cfg["lora"]["alpha"],
        lora_mlp=_lora_mlp(cfg), remat=True, scan_layers=args["scan_layers"],
        remat_policy=args["remat_policy"],
    )


def _attention(job, seq_len: int, head_dim: int):
    """The attention callable of the cell, and its name. A flash schedule that
    does not come from the shipped defaults table (a tune file under HOME, a
    pin) is refused: it would change what compiles from machine to machine."""
    from p2pfl_tpu.models.transformer import pick_attention, resolve_attention
    from p2pfl_tpu.ops.autotune import flash_config_source

    attn = job.cell["engine_args"]["attn"]
    if attn == "auto":
        attn = pick_attention(seq_len)
    if attn != "flash":
        return resolve_attention(attn), attn
    config, source = flash_config_source(seq_len, head_dim, dtype=jnp.bfloat16)
    if source != "defaults":
        raise SystemExit(
            f"benchmark: flash config for (T={seq_len}, D={head_dim}) comes from {source!r}, "
            "not the shipped defaults table; remove the tune file or pin"
        )
    job.say(f"flash config (T={seq_len}, D={head_dim}): {config} from {source}")
    return resolve_attention("flash", config=config), attn


def _federation(job, model, shards, n_nodes: int):
    from p2pfl_tpu.parallel import SpmdLoraFederation

    args = job.cell["engine_args"]
    opt = args["optimizer"]
    if opt["name"] != "adam" or opt["schedule"] != "constant":
        raise SystemExit("benchmark: spmd_lora drives Adam at a constant rate (the federation's own)")
    return SpmdLoraFederation(
        model, traffic.as_datasets(shards[:n_nodes], job.cfg["vocab_size"]),
        batch_size=job.traffic["batch_size"], learning_rate=opt["learning_rate"], vote=False,
        seed=job.seed, keep_opt_state=args["keep_opt_state"], node_chunk=min(args["node_chunk"], n_nodes),
    )


def build(job) -> dict:
    from p2pfl_tpu.models.base import FlaxModel
    from p2pfl_tpu.models.transformer import CausalLM

    cfg, tr = job.cfg, job.traffic
    tcfg = _transformer_config(cfg, job.cell["engine_args"])
    attn_fn, attn = _attention(job, tr["seq_len"], cfg["head_dim"])
    module = CausalLM(tcfg, attn_fn)

    # weights: ONE jitted call from the seed, on the device, in the type they
    # are kept in (float32). Initialised through a dense-attention twin at a
    # short length: parameters do not depend on either.
    @jax.jit
    def init(key):
        return CausalLM(tcfg, None).init(key, jnp.zeros((1, 16), jnp.int32))["params"]

    params = init(jax.random.PRNGKey(job.seed))
    model = FlaxModel(module, params, (tr["seq_len"],), cfg["vocab_size"])
    model.extra["config"] = tcfg
    shards = traffic.generate(tr, cfg, job.seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    job.say(
        f"model: {n_params / 1e9:.3f} B parameters ({cfg['num_hidden_layers']} layers), attn={attn}, "
        f"{tr['n_nodes']} nodes x {tr['local_steps']} steps x {tr['batch_size']} x {tr['seq_len']} tokens"
    )
    # the federation itself is built in ``warm``, after the reference check:
    # the check's programs need the memory the node-stacked state would hold
    return {"fed": None, "model": model, "module": module, "attn": attn, "shards": shards}


def _reference_grad(job):
    cfg = job.cfg
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]

    @jax.jit
    def grad(lora, base, x, y):
        return jax.value_and_grad(causal_lm.loss)(lora, base, x, y, cfg, lora_scale=scale)

    return grad


def check(job, state) -> None:
    """(1) one node's first local step and (2) one federated round of a
    reduced job, against the float32 reference — at the published widths, on a
    sequence dense float32 attention can hold."""
    from p2pfl_tpu.learning.lora import _lm_loss, split_lora

    spec, cfg = job.cell["check"], job.cfg
    seq, n_nodes, steps = spec["seq_len"], spec["n_nodes"], spec["local_steps"]
    small = dict(job.traffic, seq_len=seq, n_nodes=n_nodes)
    small["data"] = dict(job.traffic["data"], docs_per_node=steps)
    shards = traffic.generate(small, cfg, job.seed + 1)
    lora, base = split_lora(state["model"].params)
    # lora_b starts at zero, which makes every lora_a gradient exactly zero:
    # the step check perturbs it (seeded) so both halves of every adapter count
    keys = iter(jax.random.split(jax.random.PRNGKey(job.seed + 2), 64))
    probe = jax.tree_util.tree_map_with_path(
        lambda path, a: 0.02 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "lora_b" in jax.tree_util.keystr(path) else a,
        lora,
    )
    x, y = jnp.asarray(shards[0]["x"][:1]), jnp.asarray(shards[0]["y"][:1])
    module = state["module"]

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

    # (2) the reduced federation: same model object, so same base buffers
    fed = _federation(job, state["model"], shards, n_nodes)
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
    # unload the check's executables (the float32 reference is 170 MB of
    # program): the round needs nearly all of the chip
    jax.clear_caches()


def warm(job, state) -> None:
    state["fed"] = _federation(job, state["model"], state["shards"], job.traffic["n_nodes"])
    job.say(f"federation mesh {dict(state['fed'].mesh.shape)}")
    # two rounds: the first takes freshly staged state, the second the round's
    # own outputs; the two input layouts compile apart (bench.py's finding)
    for _ in range(2):
        window.one_round(state["fed"])


def reset(job, state) -> None:
    state["fed"].reset(job.seed)


def measure(job, state, seconds: float, tracer) -> dict:
    return window.spmd_measure(
        state["fed"], seconds, tracer, job.cell["trace_rounds"], job.traffic["n_nodes"]
    )


def finish(job, state, win: dict) -> None:
    window.spmd_final_checks(job, state["fed"], win)
    if job.trace:
        calls = state["fed"].lower_round(epochs=1).as_text().count("tpu_custom_call")
        want = job.cell["expect"]["mosaic_calls_in_round"]
        job.checks.add("round.mosaic_calls", calls == want, got=calls, want=want)


def describe(job, state) -> dict:
    cfg, tr = job.cfg, job.traffic
    rank, lora_mlp = cfg["lora"]["rank"], _lora_mlp(cfg)
    step = flops.lora_step_flops(cfg, tr["seq_len"], rank=rank, lora_mlp=lora_mlp)
    node_steps = tr["n_nodes"] * tr["local_steps"] * tr["batch_size"]
    return {
        "train_nodes": tr["n_nodes"],
        "steps_per_program_run": node_steps // len(job.devices),
        "flops_per_round": step["total"] * node_steps,
        "flops_per_sequence_step": step,
        "flash_flops_per_round": (
            flops.flash_executed_flops(cfg, tr["seq_len"], remat_forwards=1) * node_steps
            if state["attn"] == "flash" else 0.0
        ),
        "round_program": "jit_spmd_lora_round",
        "fold_bytes": flops.fedavg_fold_bytes(
            tr["n_nodes"], flops.lora_params(cfg, rank=rank, lora_mlp=lora_mlp)
        ),
    }
