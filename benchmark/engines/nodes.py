"""The ``Node`` stack: N ``Node(JaxLearner)`` objects in one process over the
in-memory transport, running the reference's own round — vote, train set,
partial-aggregation gossip, diffusion (``node.py``, ``stages/``,
``communication/``, ``learning/learner.py``).

Rounds are observed from outside: the logger's public ``round_finished`` hook
gives each node's completion instants, the logger's metric store the losses.
Nothing in the program is edited.
"""

from __future__ import annotations

import math
import threading
import time

import jax
import numpy as np

from benchmark import checks as ck
from benchmark import flops, traffic
from benchmark.engines import spmd as spmd_engine
from benchmark.reference import fedavg


class RoundWatch:
    """Records ``(addr, instant)`` at every ``logger.round_finished`` call."""

    def __init__(self) -> None:
        from p2pfl_tpu.management.logger import logger

        self._logger = logger
        self._lock = threading.Lock()
        self.finished: dict[str, list[float]] = {}
        self._original = logger.round_finished

        def hook(node: str) -> None:
            now = time.monotonic()
            with self._lock:
                self.finished.setdefault(node, []).append(now)
            self._original(node)

        logger.round_finished = hook

    def close(self) -> None:
        # drop the instance attribute: the class's method shows through again
        del self._logger.round_finished

    def rounds_done(self, addrs) -> int:
        with self._lock:
            return min((len(self.finished.get(a, ())) for a in addrs), default=0)

    def completions(self, addrs) -> list[float]:
        """Instants at which ALL of ``addrs`` had finished round r, r = 1.."""
        with self._lock:
            series = [list(self.finished.get(a, ())) for a in addrs]
        return [max(ts) for ts in zip(*series)]


def _make_nodes(job, model, shards, seeds):
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.models.base import FlaxModel
    from p2pfl_tpu.node import Node

    opt = job.cell["engine_args"]["optimizer"]
    nodes = []
    for data, seed in zip(traffic.as_datasets(shards, job.cfg["num_classes"]), seeds):
        # every learner starts from the same seeded parameters (the overlay's
        # initiator would diffuse its own anyway); the buffers are shared, the
        # fused round donates only optimizer state
        own = FlaxModel(model.module, model.params, model.input_shape, model.num_classes)
        nodes.append(
            Node(learner=JaxLearner(
                own, data, batch_size=job.traffic["batch_size"],
                learning_rate=opt["learning_rate"], seed=seed,
            ))
        )
    return nodes


def _connect(nodes) -> None:
    from p2pfl_tpu.utils import full_connection, wait_convergence

    for node in nodes:
        node.start()
    for node in nodes:
        full_connection(node, nodes)
    wait_convergence(nodes, len(nodes) - 1, only_direct=True, wait=30.0)


def _stop(nodes) -> None:
    for node in nodes:
        node.stop()


def _train_losses(exp_logs: dict, addrs) -> list[float]:
    """Mean over ``addrs`` of each round's logged ``train_loss``. The local
    metric store is round -> node -> metric -> [(step, value)]; a training node
    logs one value an epoch, so its values in (round, step) order are its
    rounds in order whatever round number the flush stamped on them. A round
    counts once every one of ``addrs`` has logged it."""
    series = []
    for addr in addrs:
        points = [
            (rnd, step, value)
            for rnd, nodes in exp_logs.items()
            for step, value in nodes.get(addr, {}).get("train_loss", [])
        ]
        series.append([value for _, _, value in sorted(points)])
    return [float(np.mean(values)) for values in zip(*series)]


def _experiment_logs(addr: str) -> dict:
    """The local metric log of the experiment in which ``addr`` logged most
    rounds (a value flushed after the stop lands in a stray experiment)."""
    from p2pfl_tpu.management.logger import logger

    def rounds_with(rounds: dict) -> int:
        return sum(addr in nodes for nodes in rounds.values())

    logs = logger.get_local_logs()
    return max(logs.values(), key=rounds_with, default={})


def build(job) -> dict:
    from p2pfl_tpu.settings import Settings, set_low_latency_settings

    if job.cell["engine_args"]["optimizer"]["schedule"] != "constant":
        raise SystemExit("benchmark: JaxLearner takes only a constant Adam learning rate")
    set_low_latency_settings()
    Settings.TRAIN_SET_SIZE = job.traffic["train_set_size"]
    for key, value in job.traffic.get("settings", {}).items():
        if not hasattr(Settings, key):
            raise SystemExit(f"benchmark: the program has no setting {key!r}")
        setattr(Settings, key, value)
    model = spmd_engine.make_model(job)
    job.say(
        f"model: {model.param_count / 1e6:.2f} M parameters; {job.traffic['n_nodes']} nodes, "
        f"train set {Settings.TRAIN_SET_SIZE}, {job.traffic['local_steps']} steps x batch {job.traffic['batch_size']}"
    )
    return {"model": model, "nodes": None, "watch": None}


def check(job, state) -> None:
    """(1) the first local step against the reference. (2) is made in
    ``warm``: the warm-up round of the throw-away nodes is the reduced job."""
    shards = traffic.generate(dict(job.traffic, n_nodes=1), job.cfg, job.seed + 1)
    state["ref_grad"] = spmd_engine.reference_grad(job.cfg)
    spmd_engine.check_step(job, state["model"], shards[0], state["ref_grad"])


def warm(job, state) -> None:
    """One whole round on throw-away nodes of the measured shapes: compiles the
    fused node round, the aggregation programs for this train-set size and the
    final evaluation — and is compared with the reference loop."""
    from p2pfl_tpu.management.profiling import get_dispatch_counts, reset_dispatch_counts
    from p2pfl_tpu.utils import wait_to_finish

    tr = job.traffic
    t0 = time.monotonic()
    _warm_aggregation(state["model"].params, tr["train_set_size"])
    t_agg = time.monotonic()
    shards = traffic.generate(tr, job.cfg, job.seed + 1)
    seeds = [job.seed + 1000 + i for i in range(tr["n_nodes"])]
    nodes = _make_nodes(job, state["model"], shards, seeds)
    reset_dispatch_counts()
    try:
        _connect(nodes)
        t_connected = time.monotonic()
        nodes[0].set_start_learning(rounds=1, epochs=1)
        by_addr = {n.addr: i for i, n in enumerate(nodes)}
        train = []
        deadline = time.monotonic() + 60.0
        while not train and time.monotonic() < deadline:
            train = sorted(nodes[0].state.train_set or [])
            time.sleep(0.02)
        # the train set's round is what is compared and what compiles; peers
        # outside it are not waited for (after a one-round experiment they
        # can sit out their whole aggregation timeout)
        trainers = [nodes[by_addr[a]] for a in train]
        wait_to_finish(trainers, timeout=600.0)
        t_round = time.monotonic()
        got = [jax.tree.map(np.asarray, n.learner.get_parameters()) for n in trainers]
    finally:
        _stop(nodes)
    t_stopped = time.monotonic()
    counts = get_dispatch_counts()
    job.say(f"warm-up round: train set {len(train)} of {len(nodes)}, dispatch counts {counts}")
    job.checks.add("round.train_set_size", len(train) == tr["train_set_size"], got=len(train))
    job.checks.add(
        "round.fused_not_staged",
        counts.get("fused_round", 0) == len(train) and counts.get("train_epoch", 0) == 0, **counts,
    )
    spread = max(
        float(np.max(np.abs(a - b))) for g in got[1:] for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(got[0]))
    )
    job.checks.at_most("round.node_param_spread", spread, ck.NODE_EQ)
    # the reference loop over the elected nodes, in each learner's own batch order
    steps, bs = tr["local_steps"], tr["batch_size"]
    picked = [by_addr[a] for a in train]
    perms = [
        np.random.default_rng(seeds[i]).permutation(len(shards[i]["y"]))[: steps * bs] for i in picked
    ]
    want, _ = spmd_engine.reference_round(
        job, state["model"].params, [shards[i] for i in picked], perms,
        fedavg.adam_step(state["ref_grad"]), job.cell["engine_args"]["optimizer"],
    )
    job.say(
        "warm-up split (s): " + ", ".join(f"{k} {v:.2f}" for k, v in {
            "aggregation kernels": t_agg - t0, "nodes built and connected": t_connected - t_agg,
            "one round and final evaluation": t_round - t_connected, "fetch and stop": t_stopped - t_round,
            "reference loop": time.monotonic() - t_stopped,
        }.items())
    )
    start = jax.tree.map(np.asarray, state["model"].params)
    job.checks.at_least(
        "round.delta_cosine", ck.cosine(ck.tree_sub(got[0], start), ck.tree_sub(want, start)), ck.ROUND_COS
    )


def _warm_aggregation(params, train_set_size: int) -> None:
    """The FedAvg kernels compile once per number of models folded, and which
    numbers a round meets depends on gossip timing: run every one the train
    set can produce, on the model's own shapes, so none compiles in the window
    (``FedAvg._aggregate``: the fused fold over 0..n-1 peers, the stacked mean
    over 1..n models)."""
    import jax.numpy as jnp

    from p2pfl_tpu.ops.aggregation import fedavg as fedavg_kernel
    from p2pfl_tpu.ops.aggregation import fedavg_fold_acc
    from p2pfl_tpu.ops.tree import tree_stack
    from p2pfl_tpu.settings import Settings

    psum = jax.tree.map(lambda a: a.astype(Settings.AGG_DTYPE), params)
    wsum = jnp.asarray(1.0, Settings.AGG_DTYPE)
    for k in range(train_set_size):
        out = fedavg_fold_acc(
            psum, wsum, (params,) * k, jnp.asarray([1.0] * k, jnp.float32), params, Settings.AGG_DTYPE
        )
        out = fedavg_kernel(tree_stack([params] * (k + 1)), jnp.asarray([1.0] * (k + 1)), Settings.AGG_DTYPE)
    jax.block_until_ready(out)


def reset(job, state) -> None:
    """The measured nodes, built from the seed (the throw-away ones are gone)."""
    from p2pfl_tpu.management.logger import logger
    from p2pfl_tpu.management.profiling import reset_dispatch_counts

    tr = job.traffic
    shards = traffic.generate(tr, job.cfg, job.seed)
    nodes = _make_nodes(job, state["model"], shards, [job.seed + i for i in range(tr["n_nodes"])])
    _connect(nodes)
    logger.reset_comm_metrics()
    reset_dispatch_counts()
    state["nodes"] = nodes
    state["watch"] = RoundWatch()


def measure(job, state, seconds: float, tracer) -> dict:
    from p2pfl_tpu.management.logger import logger
    from p2pfl_tpu.management.profiling import get_dispatch_counts

    nodes, watch = state["nodes"], state["watch"]
    start = time.monotonic()
    deadline = start + seconds
    # more rounds than any window holds: the deadline ends the experiment
    nodes[0].set_start_learning(rounds=100000, epochs=1)
    train: list[str] = []
    while not train and time.monotonic() < deadline:
        train = sorted(nodes[0].state.train_set or [])
        time.sleep(0.005)
    trace_from = job.cell["trace_from_round"]
    trace_to = trace_from + job.cell["trace_rounds"]
    tracing = False
    while time.monotonic() < deadline:
        if tracer is not None:
            done = watch.rounds_done(train)
            if not tracing and trace_from <= done < trace_to:
                tracer.start()
                tracing = True
            elif tracing and done >= trace_to:
                tracer.stop()
                tracing, tracer = False, None
        # a watcher that wakes often takes the GIL from the nodes' threads
        time.sleep(0.01 if tracer is not None else 0.05)
    if tracing:
        tracer.stop()
    rounds_done = watch.rounds_done(train)
    state["dispatch_counts"] = get_dispatch_counts()
    state["rounds_done"] = rounds_done
    for node in nodes:  # a peer that lost its neighbours would not hear a broadcast
        if node.state.round is not None:
            node.set_stop_learning()
    completions = [start] + watch.completions(train)
    losses = _train_losses(_experiment_logs(train[0]), train)[:rounds_done] if train else []
    comm = [logger.get_comm_metrics(n.addr) for n in nodes]
    evicted = sum(int(m.get("neighbor_evicted", 0)) for m in comm)
    pauses = sum(int(m.get("local_pause", 0)) for m in comm)
    bad = sum(not math.isfinite(x) for x in losses)
    state.update(train=train, evicted=evicted, local_pauses=pauses)
    return {
        "completions": completions,
        # between consecutive "every train-set node finished round r" instants;
        # the start-up interval (vote, first diffusion) is not a round interval
        "intervals": [b - a for a, b in zip(completions[1:], completions[2:])],
        "losses": losses,
        # node-rounds of the finished rounds; the round cut by the deadline is
        # neither attempted nor failed
        "attempted": rounds_done * len(train),
        "failed": bad * len(train) + evicted,
    }


def finish(job, state, win: dict) -> None:
    from p2pfl_tpu.utils import wait_to_finish

    nodes = state["nodes"]
    try:
        wait_to_finish(nodes, timeout=120.0)
        losses = win["losses"]
        job.checks.add("final.no_live_peer_evicted", state["evicted"] == 0, evicted=state["evicted"],
                       local_pauses=state["local_pauses"])
        job.checks.add("final.losses_finite", bool(losses) and all(math.isfinite(x) for x in losses),
                       rounds=len(losses))
        k = job.cell["k"]
        if len(losses) >= k:
            job.checks.add("final.loss_falls", losses[k - 1] < losses[0], round_1=losses[0],
                           round_k=losses[k - 1], k=k)
        else:
            job.checks.add("final.reached_round_k", False, rounds=len(losses), k=k)
        counts, rounds = state["dispatch_counts"], max(1, state["rounds_done"])
        per_round = {site: n / rounds for site, n in counts.items()}
        job.say(f"dispatch counts per round over {rounds} rounds: {per_round}; train set {len(state['train'])}")
        job.checks.add("final.fused_not_staged", counts.get("train_epoch", 0) == 0, **counts)
    finally:
        state["watch"].close()
        _stop(nodes)


def describe(job, state) -> dict:
    tr = job.traffic
    step = flops.resnet_step_flops(job.cfg, tr["batch_size"])
    node_steps = tr["train_set_size"] * tr["local_steps"]
    return {
        "train_nodes": tr["train_set_size"],
        "steps_per_program_run": tr["local_steps"],
        "flops_per_round": step * node_steps,
        "flops_per_step": step,
        "round_program": "jit_fused_node_round",
        "dispatch_counts": state.get("dispatch_counts"),
        "rounds_done": state.get("rounds_done"),
    }
