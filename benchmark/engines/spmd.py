"""``SpmdFederation``: N nodes' full models and optimizer states stacked on a
leading axis that is sharded over the ``nodes`` mesh axis; one program a round,
FedAvg as one cross-chip reduction (``parallel/spmd.py``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks as ck
from benchmark import flops, traffic, window
from benchmark.reference import fedavg, resnet


def make_model(job):
    """The configuration's ResNet as the program builds it, initialised in ONE
    jitted call from the seed (``models.resnet50()`` runs flax's init op by op)."""
    from p2pfl_tpu.models.base import FlaxModel
    from p2pfl_tpu.models.vision import ResNet

    cfg = job.cfg
    module = ResNet(stage_sizes=tuple(cfg["stage_sizes"]), bottleneck=True, num_classes=cfg["num_classes"])
    shape = tuple(cfg["input_shape"])

    @jax.jit
    def init(key):
        return module.init(key, jnp.zeros((1, *shape), jnp.float32))["params"]

    return FlaxModel(module, init(jax.random.PRNGKey(job.seed)), shape, cfg["num_classes"])


def make_tx(opt: dict):
    import optax

    if opt["name"] != "adam":
        raise SystemExit(f"benchmark: optimizer {opt['name']!r} is not adam")
    if opt["schedule"] == "constant":
        return optax.adam(opt["learning_rate"])
    if opt["schedule"] == "warmup_cosine":
        return optax.adam(
            optax.warmup_cosine_decay_schedule(
                opt["init_value"], opt["peak_value"], warmup_steps=opt["warmup_steps"],
                decay_steps=opt["decay_steps"], end_value=opt["end_value"],
            )
        )
    raise SystemExit(f"benchmark: unknown schedule {opt['schedule']!r}")


def _federation(job, model, shards, tx):
    from p2pfl_tpu.parallel import SpmdFederation

    args = job.cell["engine_args"]
    return SpmdFederation(
        model, traffic.as_datasets(shards, job.cfg["num_classes"]),
        batch_size=job.traffic["batch_size"], vote=False, seed=job.seed, remat=args["remat"],
        tx=tx, keep_opt_state=args["keep_opt_state"],
    )


def build(job) -> dict:
    tr = job.traffic
    model = make_model(job)
    shards = traffic.generate(tr, job.cfg, job.seed)
    tx = make_tx(job.cell["engine_args"]["optimizer"])
    fed = _federation(job, model, shards, tx)
    mesh_devices = set(fed.mesh.devices.flat)
    if mesh_devices != set(job.devices):
        raise SystemExit(f"benchmark: mesh {dict(fed.mesh.shape)} strands devices")
    leaf = jax.tree.leaves(fed.params)[0]
    per_device = {s.device.id: s.data.shape[0] for s in leaf.addressable_shards}
    job.say(
        f"model: {model.param_count / 1e6:.2f} M parameters, mesh {dict(fed.mesh.shape)}, "
        f"nodes per device {per_device}, {tr['n_nodes']} nodes x {tr['local_steps']} steps x batch {tr['batch_size']}"
    )
    if sorted(per_device.values()) != [tr["n_nodes"] // len(job.devices)] * len(job.devices):
        raise SystemExit(f"benchmark: node-stacked parameters are not sharded evenly: {per_device}")
    return {"fed": fed, "model": model, "tx": tx}


def reference_grad(cfg: dict):
    @jax.jit
    def grad(params, x, y):
        return jax.value_and_grad(resnet.loss)(params, x, y, cfg)

    return grad


def check_step(job, model, shard: dict, ref_grad) -> None:
    """(1) the first local step's loss and gradients on one seeded batch:
    the program's own loss function and module against the reference."""
    from p2pfl_tpu.learning.learner import _loss

    bs = job.traffic["batch_size"]
    x, y = jnp.asarray(shard["x"][:bs]), jnp.asarray(shard["y"][:bs])
    module = model.module

    @jax.jit
    def system_grad(p, bx, by):
        return jax.value_and_grad(lambda p_: _loss(p_, module, bx, by)[0])(p)

    got_loss, got = system_grad(model.params, x, y)
    with jax.default_matmul_precision("highest"):
        want_loss, want = ref_grad(model.params, x, y)
    job.checks.close("step.loss", float(got_loss), float(want_loss), ck.LOSS_REL)
    job.checks.gradients("step", got, want)


def reference_round(job, start, shards: list, perms: list, ref_step, opt: dict):
    """Train each node with the reference from ``start`` over its batches in
    the given order, then the sample-weighted mean. Returns (mean, mean loss)."""
    bs = job.traffic["batch_size"]
    trained, losses = [], []
    with jax.default_matmul_precision("highest"):
        for shard, perm in zip(shards, perms):
            batches = [
                (jnp.asarray(shard["x"][idx]), jnp.asarray(shard["y"][idx]))
                for idx in np.asarray(perm).reshape(-1, bs)
            ]
            node, node_losses = fedavg.adam_train(start, batches, ref_step, opt)
            trained.append(jax.tree.map(np.asarray, node))
            losses.append(float(np.mean(node_losses)))
    return fedavg.weighted_mean(trained, [len(s["y"]) for s in shards]), float(np.mean(losses))


def check(job, state) -> None:
    spec, cfg = job.cell["check"], job.cfg
    n_nodes, steps, bs = spec["n_nodes"], spec["local_steps"], job.traffic["batch_size"]
    small = dict(job.traffic, n_nodes=n_nodes)
    small["data"] = dict(job.traffic["data"], samples_per_node=steps * bs)
    shards = traffic.generate(small, cfg, job.seed + 1)
    ref_grad = reference_grad(cfg)
    check_step(job, state["model"], shards[0], ref_grad)

    fed = _federation(job, state["model"], shards, state["tx"])
    start = jax.tree.map(np.asarray, state["model"].params)
    loss = float(fed.run_round(epochs=1)["train_loss"])
    got = jax.tree.map(lambda a: np.asarray(a[0]), fed.params)
    order = np.random.default_rng(job.seed)  # the federation's own batch-order stream
    perms = [order.permutation(len(s["y"]))[: steps * bs] for s in shards]
    want, want_loss = reference_round(
        job, state["model"].params, shards, perms, fedavg.adam_step(ref_grad),
        job.cell["engine_args"]["optimizer"],
    )
    job.checks.close("round.loss", loss, want_loss, ck.LOSS_REL)
    job.checks.at_least(
        "round.delta_cosine", ck.cosine(ck.tree_sub(got, start), ck.tree_sub(want, start)), ck.ROUND_COS
    )


def warm(job, state) -> None:
    for _ in range(2):  # freshly staged state, then the round's own outputs: two input layouts
        window.one_round(state["fed"])


def reset(job, state) -> None:
    state["fed"].reset(job.seed)


def measure(job, state, seconds: float, tracer) -> dict:
    return window.spmd_measure(
        state["fed"], seconds, tracer, job.cell["trace_rounds"], job.traffic["n_nodes"]
    )


def finish(job, state, win: dict) -> None:
    window.spmd_final_checks(job, state["fed"], win)
    devices = {d for leaf in jax.tree.leaves(state["fed"].params) for d in leaf.sharding.device_set}
    job.checks.add("final.params_on_every_device", devices == set(job.devices), devices=len(devices))


def describe(job, state) -> dict:
    tr = job.traffic
    step = flops.resnet_step_flops(job.cfg, tr["batch_size"])
    node_steps = tr["n_nodes"] * tr["local_steps"]
    return {
        "train_nodes": tr["n_nodes"],
        "steps_per_program_run": node_steps // len(job.devices),
        "flops_per_round": step * node_steps,
        "flops_per_step": step,
        "round_program": "jit_spmd_round",
        "fold_bytes": flops.fedavg_fold_bytes(tr["n_nodes"], flops.resnet_params(job.cfg)),
    }
