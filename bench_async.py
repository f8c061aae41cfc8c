"""BENCH_ASYNC: time-to-accuracy, sync round FSM vs async vs hierarchical.

The ISSUE-9 acceptance measurement: the same mnist fleet, the same seeded
straggler/crash FaultPlan, the same total local-training budget — driven
three ways:

- ``sync``  — the barrier-synchronized round FSM (stages/learning_stages),
- ``async`` — flat FedBuff (one global BufferedAggregator, no barrier),
- ``hier``  — FedBuff + HierarchicalTopology (edge clusters → regional →
  global).

Each threaded row reports wall-clock to complete the budget with the
final global model at/above the target accuracy — the async rows must
beat the sync row on the same fleet, because the sync barrier pays the
slow peer's inbound-weights latency (and the crash's eviction window)
once per round while the async planes pay it only on that node's own
contributions.

The threaded fleet is small (10 real nodes), so the "10% slow / 1%
crash" plan quantizes to 1 slow node and 1 crash; the 1k-node SIMULATED
section runs the exact fractions through
:class:`p2pfl_tpu.federation.simfleet.SimulatedAsyncFleet` (virtual
clock, bit-identical replay) and compares against the sync fleet's
analytic floor — a barrier fleet cannot finish a round faster than its
slowest member trains.

The ``churn_1k`` section (ISSUE 11) drives the same 1k-node simulated
fleet under a seeded elastic-churn plan — 5% leaves (graceful + abrupt),
5% joins, one mid-convergence GLOBAL-ROOT kill — against the static
fleet, so the disruption cost of membership churn is a measured
time-to-target ratio, not a claim.

The ``byzantine_1k`` section (ISSUE 14) sweeps ADVERSARIES instead of
failures: 5/10/20% of the fleet running ``ByzantineSpec`` attacks
(sign-flip, scale, noise) against the hierarchical plane, defense off
(the FedBuff weighted mean folds whatever arrives) vs on
(``ASYNC_ROBUST_AGG="trimmed-mean"`` + the admission screen +
suspicion-EWMA quarantine) — time-to-target, final loss, and how many
attackers the eviction machinery removed, per cell.

The ``megafleet_1m`` section (ISSUE 15) drives the VECTORIZED engine
(:class:`p2pfl_tpu.federation.megafleet.MegaFleet` — the simulator as one
jitted ``lax.scan``) at ≥1M clients through the hierarchical plane, with
the Bonawitz production knobs (pace steering, selection
over-provisioning, per-tier rate limits) swept as array-level controls —
a parameter sweep no Python event loop could produce — plus honest
wall-clock/clients-per-second rows for the heap driver at 1k/10k next to
the vectorized engine at the same and at 1M, and the 1k heap-parity
check (merge count + version sequence exact).

The ``megafleet_chunks`` section (ISSUE 16) sweeps the chunked engine's
``MEGAFLEET_CHUNK`` knob at the 1M scale against the per-event reference
scan — clients/second per chunk size, with an inline bit-identity check
(flat chunked results must equal the per-event scan EXACTLY) — and the
``megafleet_robust`` section runs the full-fault-algebra sweep the array
engine exists for: attacker fraction (5–20%) × corruption kind
(sign_flip/scale/noise) × window fold (fedavg/trimmed-mean/median) at
1M clients, with one cell tolerance-pinned against the heap driver at
1k.

Usage: ``JAX_PLATFORMS=cpu python bench_async.py [--smoke]
[--sections a,b,...] [--out BENCH_ASYNC.json]``

``--sections`` (any of ``threaded,simulated,churn,restart,byzantine,
megafleet,megafleet_chunks,megafleet_robust``) runs a subset and MERGES
it into the existing ``--out`` document, leaving the other sections' rows
untouched — so CI can refresh one section without paying for the full
grid.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

SEED = 1905
TARGET_ACC = 0.80


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _fleet_settings():
    from p2pfl_tpu.settings import Settings, set_low_latency_settings

    set_low_latency_settings()
    Settings.TRAIN_SET_SIZE = 10
    Settings.VOTE_TIMEOUT = 30.0
    Settings.AGGREGATION_TIMEOUT = 60.0
    Settings.FEDBUFF_K = 4
    Settings.FEDBUFF_ALPHA = 0.5
    Settings.FEDBUFF_SERVER_LR = 1.0
    Settings.ASYNC_MAX_STALENESS = 16
    Settings.ASYNC_DRAIN_TIMEOUT = 20.0


def _make_plan(addrs: list, slow_s: float, async_mode: bool):
    """1 slow + 1 crash over a 10-node fleet (the small-fleet quantization
    of the 10%/1% plan; the simulated section runs the exact fractions).
    Deterministic: same seed, same victim indices in every mode."""
    from p2pfl_tpu.communication.faults import CrashSpec, EdgeFault, FaultPlan

    slow_addr = addrs[-1]
    crash_addr = addrs[-2]
    stage = "AsyncTrainStage" if async_mode else "TrainStage"
    return FaultPlan(
        seed=SEED,
        default=EdgeFault(drop=0.01),
        slow_nodes={slow_addr: slow_s},
        crashes={crash_addr: CrashSpec(stage=stage, round_no=1)},
    )


def run_threaded(mode: str, *, n_nodes: int = 10, rounds: int = 4, slow_s: float = 0.5) -> dict:
    """One fresh federation in the given mode; returns the row dict.

    ``rounds`` is the per-node local-update budget in every mode (sync
    rounds == async local updates: identical total training work).
    """
    from p2pfl_tpu.communication.memory import MemoryRegistry
    from p2pfl_tpu.communication.faults import install_fault_plan, remove_fault_plan
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.learner import JaxLearner, eval_step
    from p2pfl_tpu.management.logger import logger
    from p2pfl_tpu.management.telemetry import telemetry
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import Settings
    from p2pfl_tpu.utils import full_connection, wait_convergence, wait_to_finish

    MemoryRegistry.reset()
    logger.reset_comm_metrics()
    telemetry.reset()
    _fleet_settings()
    Settings.FEDERATION_MODE = "async" if mode != "sync" else "sync"
    Settings.HIER_CLUSTER_SIZE = 4 if mode == "hier" else 0

    full = FederatedDataset.synthetic_mnist(n_train=8192, n_test=2048, seed=3)
    x_test, y_test = full.test_arrays()

    # jit warm-up outside the timers (shared cache: same module/shapes)
    warm = JaxLearner(mlp(seed=99), full.partition(0, n_nodes), batch_size=64, epochs=1)
    warm.fused_round()
    warm.evaluate()

    nodes = []
    for i in range(n_nodes):
        learner = JaxLearner(mlp(seed=i), full.partition(i, n_nodes), batch_size=64)
        nodes.append(Node(learner=learner))
    for n in nodes:
        n.start()
    for n in nodes:
        full_connection(n, nodes)
    wait_convergence(nodes, n_nodes - 1, only_direct=True, wait=15)
    plan = _make_plan([n.addr for n in nodes], slow_s, mode != "sync")
    install_fault_plan(nodes, plan)
    victim_addr = [n.addr for n in nodes][-2]
    survivors = [n for n in nodes if n.addr != victim_addr]
    try:
        t0 = time.monotonic()
        nodes[0].set_start_learning(rounds=rounds, epochs=1)
        wait_to_finish(survivors, timeout=300)
        wall = time.monotonic() - t0
        # final accuracy of the fleet model (survivor consensus / latest
        # global), evaluated on the full held-out test set
        accs = []
        for n in survivors:
            _loss, acc = eval_step(
                n.learner.get_parameters(), np.asarray(x_test), np.asarray(y_test),
                n.learner.model.module,
            )
            accs.append(float(acc))
        comm = {}
        for d in logger.get_comm_metrics().values():
            for k, v in d.items():
                if k.startswith("async") or k in ("train_set_repair",):
                    comm[k] = comm.get(k, 0) + v
        stale = {
            k.split("/")[0]: v
            for k, v in telemetry.value_histograms().items()
            if k.endswith("/staleness")
        }
        return {
            "mode": mode,
            "wall_s": round(wall, 3),
            "final_acc_min": round(min(accs), 4),
            "final_acc_max": round(max(accs), 4),
            "reached_target": min(accs) >= TARGET_ACC,
            "comm": {k: int(v) for k, v in sorted(comm.items())},
            "staleness": stale,
        }
    finally:
        remove_fault_plan(nodes)
        for n in nodes:
            n.stop()
        MemoryRegistry.reset()


def run_simulated(n: int = 1000, updates: int = 6, smoke: bool = False) -> dict:
    """Exact 10% slow / 1% crash at 1k nodes on the virtual clock.

    Time-to-loss-target is the comparison (makespan would unfairly bill
    the async planes for stragglers finishing their own budgets after
    the model already converged). The sync baseline is an EXACT
    simulation of barrier rounds on the same task and population: every
    round, all live nodes train from the global, the fleet averages all
    of them, and the round's wall-clock is the slowest live member's
    train duration — the barrier's defining cost.
    """
    from p2pfl_tpu.communication.faults import CrashSpec, EdgeFault, FaultPlan
    from p2pfl_tpu.federation.simfleet import SimulatedAsyncFleet

    if smoke:
        n, updates = 100, 4
    base, slow_factor = 1.0, 10.0
    addrs = [f"sim-{i:04d}" for i in range(n)]
    plan = FaultPlan(
        seed=SEED,
        default=EdgeFault(drop=0.01),
        slow_nodes={},  # slow durations modeled via slow_frac (train time)
        crashes={
            a: CrashSpec(stage="AsyncTrainStage", round_no=2)
            for a in addrs[7::100][: max(1, n // 100)]
        },
    )

    def make_fleet(cluster_size: int) -> SimulatedAsyncFleet:
        return SimulatedAsyncFleet(
            n,
            seed=SEED,
            cluster_size=cluster_size,
            updates_per_node=updates,
            base_duration=base,
            slow_frac=0.10,
            slow_factor=slow_factor,
            plan=plan,
            local_lr=0.7,
        )

    # the loss target every mode must reach: 5% of the cold-start loss
    probe = make_fleet(0)
    dim = len(np.asarray(probe.nodes[addrs[0]].model["w"]))
    start_loss = probe.loss_fn({"w": np.zeros(dim, np.float32)})
    target = float(start_loss) * 0.05

    def drive(cluster_size: int) -> dict:
        fleet = make_fleet(cluster_size)
        fleet.target_loss = target
        res = fleet.run()
        return {
            "time_to_target_s": round(res.time_to_target, 3) if res.time_to_target else None,
            "makespan_virtual_s": round(res.virtual_time, 3),
            "global_versions": res.version,
            "merges": res.merges,
            "updates_sent": res.updates_sent,
            "updates_dropped_wire": res.updates_dropped_wire,
            "crashed": len(res.crashed),
            "final_loss": round(res.final_loss(), 5),
        }

    def sync_baseline() -> dict:
        """Exact barrier rounds on the same task/population/faults."""
        params = {"w": np.zeros(dim, np.float32)}
        durations = {a: probe.nodes[a].duration for a in addrs}
        weights = {a: probe.nodes[a].num_samples for a in addrs}
        crashed = set()
        t, rounds, t_target = 0.0, 0, None
        loss = float(start_loss)
        while rounds < updates:
            live = [a for a in addrs if a not in crashed]
            trained, w = [], []
            for a in live:
                node = probe.nodes[a]
                rng = np.random.default_rng([SEED, 13, node.idx, rounds])
                trained.append(np.asarray(
                    probe.train_fn(node.idx, params, rng)["w"], np.float32))
                w.append(float(weights[a]))
            w = np.asarray(w, np.float32)
            params = {"w": (w[:, None] * np.stack(trained)).sum(0) / w.sum()}
            t += max(durations[a] for a in live)  # the barrier
            rounds += 1
            loss = float(probe.loss_fn(params))
            if t_target is None and loss <= target:
                t_target = t
            if rounds == 2:  # same crash schedule as the async plan
                crashed |= set(plan.crashes)
        return {
            "time_to_target_s": round(t_target, 3) if t_target else None,
            "rounds": rounds,
            "final_loss": round(loss, 5),
        }

    flat = drive(0)
    hier = drive(32)
    sync = sync_baseline()

    def speedup(row):
        if row["time_to_target_s"] and sync["time_to_target_s"]:
            return round(sync["time_to_target_s"] / row["time_to_target_s"], 2)
        return None

    return {
        "n_nodes": n,
        "updates_per_node": updates,
        "plan": {"slow_frac": 0.10, "slow_factor": slow_factor, "crash_frac": 0.01,
                 "drop": 0.01, "seed": SEED},
        "start_loss": round(float(start_loss), 5),
        "target_loss": round(target, 5),
        "sync_barrier": sync,
        "async_flat": flat,
        "hier_cluster32": hier,
        "speedup_vs_sync": {
            "async_flat": speedup(flat),
            "hier_cluster32": speedup(hier),
        },
    }


def run_byzantine(n: int = 1000, updates: int = 6, smoke: bool = False) -> dict:
    """ISSUE 14: the cost of lying nodes, and what the defenses buy back.

    Every cell is the same seeded 1k-node hierarchical consensus fleet
    (cluster 32, K=4) with ``frac`` of the members armed with one
    ``ByzantineSpec`` attack, driven twice: defenses OFF (the stock
    FedBuff weighted merge — one poisoned update lands at full staleness
    weight) and ON (``ASYNC_ROBUST_AGG="trimmed-mean"`` + the admission
    screen whose suspicion EWMA drives quarantine-by-eviction). Replay
    is bit-exact per cell — the attack rides the plan's per-edge streams.
    """
    from p2pfl_tpu.communication.faults import ByzantineSpec, FaultPlan
    from p2pfl_tpu.federation.simfleet import SimulatedAsyncFleet
    from p2pfl_tpu.settings import Settings

    if smoke:
        n, updates = 100, 4
    fracs = [0.10] if smoke else [0.05, 0.10, 0.20]
    kinds = ["sign_flip"] if smoke else ["sign_flip", "scale", "noise"]
    cluster = 32

    def make_fleet():
        return SimulatedAsyncFleet(
            n, seed=SEED, cluster_size=cluster, updates_per_node=updates,
            local_lr=0.7,
        )

    probe = make_fleet()
    dim = len(np.asarray(probe.nodes["sim-0000"].model["w"]))
    start_loss = float(probe.loss_fn({"w": np.zeros(dim, np.float32)}))
    target = start_loss * 0.05

    old = (Settings.BYZ_SCREEN, Settings.ASYNC_ROBUST_AGG)
    rows = []
    try:
        for kind in kinds:
            for frac in fracs:
                stride = max(1, int(round(1 / frac)))
                attackers = {
                    f"sim-{i:04d}": ByzantineSpec(kind=kind, lam=10.0, noise_std=20.0)
                    for i in range(0, n, stride)
                }
                cell = {"kind": kind, "attacker_frac": frac, "attackers": len(attackers)}
                for defend in (False, True):
                    Settings.BYZ_SCREEN = defend
                    Settings.ASYNC_ROBUST_AGG = "trimmed-mean" if defend else "fedavg"
                    fleet = make_fleet()
                    fleet.plan = FaultPlan(seed=SEED, byzantine=attackers)
                    fleet.target_loss = target
                    res = fleet.run()
                    final = res.final_loss()
                    cell["defended" if defend else "undefended"] = {
                        "time_to_target_s": round(res.time_to_target, 3)
                        if res.time_to_target
                        else None,
                        # a scale attack through the undefended mean can
                        # blow the consensus to inf: keep the JSON strict
                        "final_loss": round(final, 5) if np.isfinite(final) else None,
                        "diverged": not np.isfinite(final),
                        "merges": res.merges,
                        "corrupted_payloads": res.byz_corrupted,
                        "screen_rejects": res.screen_rejects,
                        "quarantined": len(res.quarantined),
                    }
                log(json.dumps(cell))
                rows.append(cell)
    finally:
        Settings.BYZ_SCREEN, Settings.ASYNC_ROBUST_AGG = old

    return {
        "n_nodes": n,
        "updates_per_node": updates,
        "cluster_size": cluster,
        "start_loss": round(start_loss, 5),
        "target_loss": round(target, 5),
        "attack": {"lam": 10.0, "noise_std": 20.0, "seed": SEED},
        "defense_on": {
            "robust_agg": "trimmed-mean",
            "screen": {
                "norm_gate": 4.0,
                "cos_gate": 0.5,
                "suspicion_beta": 0.5,
                "suspicion_threshold": 0.7,
            },
        },
        "rows": rows,
    }


def run_churn(n: int = 1000, updates: int = 6, smoke: bool = False) -> dict:
    """ISSUE 11: the disruption cost of elastic churn as a number.

    The same 1k-node hierarchical consensus fleet driven twice — static
    membership vs a seeded churn plan (5% graceful+abrupt leaves, 5%
    joins, one GLOBAL-ROOT kill) — comparing time-to-loss-target and
    merge counts. The churn fleet must still reach the target: successor
    roots self-elect, buffers migrate, joiners bootstrap from the
    current global, and version minting stays monotone through the
    failover (federation/routing.py).
    """
    from p2pfl_tpu.communication.faults import FaultPlan, JoinSpec, LeaveSpec
    from p2pfl_tpu.federation.simfleet import SimulatedAsyncFleet

    if smoke:
        n, updates = 100, 4
    addrs = [f"sim-{i:04d}" for i in range(n)]
    n_churn = max(2, n // 20)  # 5%
    leaves = {
        a: LeaveSpec(at_s=0.4 + 0.02 * j, graceful=(j % 2 == 0))
        for j, a in enumerate(addrs[3 :: max(1, n // n_churn)][:n_churn])
    }
    # the ROOT KILL, time-targeted mid-convergence: an abrupt
    # (graceful=False) leave is a killed process — no announcement,
    # survivors discover it a full evict_delay later. t=0.9 lands in the
    # middle of the first convergence waterfall while the root is the
    # only node minting globals, so the measured disruption is the real
    # failover cost: a stall of ~evict_delay, then the successor root
    # resumes minting from the version high-water mark.
    leaves[addrs[0]] = LeaveSpec(at_s=0.9, graceful=False)
    plan = FaultPlan(
        seed=SEED,
        leaves=leaves,
        joins={f"sim-j{j:03d}": JoinSpec(at_s=0.6 + 0.02 * j) for j in range(n_churn)},
    )

    def make_fleet(churn: bool) -> SimulatedAsyncFleet:
        # local_lr 0.3 (vs run_simulated's 0.7): convergence then takes
        # several merge generations instead of one wave, so the churn
        # window (leaves/joins from 0.4s, the root kill at 0.9s) sits
        # INSIDE the measured time-to-target interval — at 0.7 every
        # target tight enough to matter is hit in the first wave and the
        # disruption ratio is vacuously 1.0
        return SimulatedAsyncFleet(
            n, seed=SEED, cluster_size=32, updates_per_node=updates,
            local_lr=0.3, plan=plan if churn else None,
        )

    probe = make_fleet(False)
    dim = len(np.asarray(probe.nodes[addrs[0]].model["w"]))
    start_loss = probe.loss_fn({"w": np.zeros(dim, np.float32)})
    target = float(start_loss) * 0.05

    def drive(churn: bool) -> dict:
        fleet = make_fleet(churn)
        fleet.target_loss = target
        res = fleet.run()
        versions = [v for _t, v, _l in res.loss_curve]
        return {
            "time_to_target_s": round(res.time_to_target, 3) if res.time_to_target else None,
            "makespan_virtual_s": round(res.virtual_time, 3),
            "global_versions": res.version,
            "merges": res.merges,
            "final_loss": round(res.final_loss(), 5),
            "joined": len(res.joined),
            "left": len(res.left),
            "crashed": len(res.crashed),
            "root_failovers": res.failovers,
            "version_monotone": versions == sorted(versions) and len(set(versions)) == len(versions),
        }

    static, churn = drive(False), drive(True)
    disruption = None
    if static["time_to_target_s"] and churn["time_to_target_s"]:
        disruption = round(churn["time_to_target_s"] / static["time_to_target_s"], 3)
    return {
        "n_nodes": n,
        "updates_per_node": updates,
        "plan": {"leave_frac": 0.05, "join_frac": 0.05, "root_kill": True, "seed": SEED},
        "start_loss": round(float(start_loss), 5),
        "target_loss": round(target, 5),
        "static": static,
        "churn": churn,
        "disruption_time_to_target_ratio": disruption,
    }


def run_restart(n: int = 1000, updates: int = 6, smoke: bool = False) -> dict:
    """ISSUE 20: what crash-resurrection buys, as a number.

    The same 1k-node hierarchical consensus fleet driven three ways —
    static membership, 5% of nodes crashed mid-run (CrashSpec: the
    pre-durability world, their remaining update budget forfeited), and
    the same 5% crashed then RESURRECTED after a restart delay
    (RestartSpec: each victim re-enters with its retained state and
    finishes its budget) — comparing time-to-loss-target and how many of
    the crash-forfeited merges the restart path recovers. The restart
    drive is run twice from the same ``(seed, plan)`` and must replay
    bit-exact (same loss curve, same restart order, identical final
    params), the determinism contract every chaos feature carries.
    """
    from p2pfl_tpu.communication.faults import CrashSpec, FaultPlan, RestartSpec
    from p2pfl_tpu.federation.simfleet import SimulatedAsyncFleet

    if smoke:
        n, updates = 100, 4
    addrs = [f"sim-{i:04d}" for i in range(n)]
    n_victims = max(2, n // 20)  # 5%
    victims = addrs[3 :: max(1, n // n_victims)][:n_victims]
    restart_plan = lambda: FaultPlan(  # noqa: E731 — plans hold run RNG state
        seed=SEED,
        restarts={
            a: RestartSpec(round_no=1, resume_after_s=1.0 + 0.05 * (j % 7))
            for j, a in enumerate(victims)
        },
    )
    crash_plan = lambda: FaultPlan(  # noqa: E731
        seed=SEED,
        crashes={a: CrashSpec("AsyncTrainStage", round_no=1) for a in victims},
    )

    def make_fleet(plan) -> SimulatedAsyncFleet:
        # local_lr 0.3 for the same reason as run_churn: the crash window
        # must sit INSIDE the measured time-to-target interval
        return SimulatedAsyncFleet(
            n, seed=SEED, cluster_size=32, updates_per_node=updates,
            local_lr=0.3, plan=plan,
        )

    probe = make_fleet(None)
    dim = len(np.asarray(probe.nodes[addrs[0]].model["w"]))
    start_loss = probe.loss_fn({"w": np.zeros(dim, np.float32)})
    target = float(start_loss) * 0.05

    def drive(plan) -> tuple:
        fleet = make_fleet(plan)
        fleet.target_loss = target
        res = fleet.run()
        versions = [v for _t, v, _l in res.loss_curve]
        return res, {
            "time_to_target_s": round(res.time_to_target, 3) if res.time_to_target else None,
            "makespan_virtual_s": round(res.virtual_time, 3),
            "global_versions": res.version,
            "merges": res.merges,
            "updates_sent": res.updates_sent,
            "final_loss": round(res.final_loss(), 5),
            "crashed": len(res.crashed),
            "restarted": len(res.restarted),
            "version_monotone": versions == sorted(versions) and len(set(versions)) == len(versions),
        }

    _res_static, static = drive(None)
    _res_crash, crash = drive(crash_plan())
    res_a, restart = drive(restart_plan())
    res_b, _restart_b = drive(restart_plan())
    replay_exact = bool(
        res_a.loss_curve == res_b.loss_curve
        and res_a.restarted == res_b.restarted
        and np.array_equal(np.asarray(res_a.params["w"]), np.asarray(res_b.params["w"]))
    )
    # the headline: of the update budget a crash-only fleet forfeits,
    # how much does crash-and-restart claw back?
    forfeited = static["updates_sent"] - crash["updates_sent"]
    recovered = restart["updates_sent"] - crash["updates_sent"]
    return {
        "n_nodes": n,
        "updates_per_node": updates,
        "plan": {"crash_frac": 0.05, "restart_delay_s": [1.0, 1.3], "seed": SEED},
        "start_loss": round(float(start_loss), 5),
        "target_loss": round(target, 5),
        "static": static,
        "crash_only": crash,
        "crash_and_restart": restart,
        "updates_forfeited_by_crash": forfeited,
        "updates_recovered_by_restart": recovered,
        "recovery_frac": round(recovered / forfeited, 3) if forfeited else None,
        "restart_replay_bit_exact": replay_exact,
    }


def run_megafleet(smoke: bool = False) -> dict:
    """ISSUE 15: the vectorized engine at fleet scale.

    Three parts: (a) honest wall-clock rows — the heap driver at 1k and
    10k vs the vectorized engine at 1k, 10k, 100k and 1M clients (heap
    events grow as merges × fan-out, which is why its wall-clock
    explodes where the scan's per-event cost stays flat). The mega rows
    are megafleet-native ``FleetSpec.synth`` populations with matching
    STATISTICS, not the heap's exported population, so compare
    throughput across rows, not losses; (b) the same-task anchor is the
    inline 1k heap-parity check (``from_sim`` export, merge count +
    version sequence exact, final loss within the documented tolerance)
    run against the event-exact driver in the same process; (c) the
    ≥1M-client hierarchical drive with Bonawitz-knob sweeps — pace
    steering and selection over-provisioning against time-to-target and
    the staleness profile, a grid only an array engine can afford.
    """
    from p2pfl_tpu.federation.megafleet import FleetSpec, MegaFleet
    from p2pfl_tpu.federation.simfleet import SimulatedAsyncFleet

    heap_sizes = [1000] if smoke else [1000, 10_000]
    mega_sizes = [1000, 20_000] if smoke else [1000, 10_000, 100_000, 1_000_000]
    big_n = mega_sizes[-1]
    updates = 4

    def heap_fleet(n):
        return SimulatedAsyncFleet(
            n, seed=SEED, cluster_size=32, updates_per_node=updates,
            slow_frac=0.10, local_lr=0.7,
        )

    # -- heap rows + the 1k parity anchor --
    heap_rows, parity = [], None
    for n in heap_sizes:
        fleet = heap_fleet(n)
        t0 = time.monotonic()
        heap = fleet.run()
        wall = time.monotonic() - t0
        heap_rows.append({
            "driver": "heap", "n_clients": n, "wall_s": round(wall, 2),
            "clients_per_sec": int(n / wall), "merges": heap.merges,
            "final_loss": round(heap.final_loss(), 5),
        })
        log(json.dumps(heap_rows[-1]))
        if n == 1000:
            mega = MegaFleet(
                FleetSpec.from_sim(fleet), cluster_size=32,
                updates_per_node=updates, local_lr=0.7,
            ).run()
            hl = heap.final_loss()
            parity = {
                "merge_count_exact": mega.merges == heap.merges,
                "version_sequence_exact": [v for _t, v, _l in mega.loss_curve]
                == [v for _t, v, _l in heap.loss_curve],
                "final_loss_rel_diff": round(
                    abs(mega.final_loss() - hl) / max(hl, 1e-12), 6
                ),
            }
            log(json.dumps({"parity_1k": parity}))

    # -- vectorized rows (megafleet-native population at every scale);
    # the sweep below reuses the big row's run as its pace=0 baseline,
    # so target_loss is threaded through (host-side post-processing
    # only: the scan is identical) --
    big_spec = FleetSpec.synth(big_n, seed=SEED, slow_frac=0.10)
    start_loss = big_spec.loss(big_spec.init)
    target = start_loss * 0.05
    mega_rows, big_res, big_cluster, big_k = [], None, 0, None
    for n in mega_sizes:
        spec = big_spec if n == big_n else FleetSpec.synth(
            n, seed=SEED, slow_frac=0.10
        )
        cluster = 32 if n <= 10_000 else 1024
        k = None if n <= 10_000 else 64
        res = MegaFleet(
            spec, cluster_size=cluster, k=k, updates_per_node=updates,
            local_lr=0.7, target_loss=target if n == big_n else 0.0,
        ).run()
        if n == big_n:
            big_res, big_cluster, big_k = res, cluster, k
        mega_rows.append({
            "driver": "megafleet", "n_clients": n, "cluster_size": cluster,
            "wall_s": round(res.wall_s, 2),
            "clients_per_sec": int(res.clients_per_sec),
            "events": res.n_events, "merges": res.merges,
            "regional_merges": res.regional_merges,
            "final_loss": round(res.final_loss(), 6),
        })
        log(json.dumps(mega_rows[-1]))

    # -- the 1M knob sweep: pace steering × selection, plus a rate-limit
    # cell — time-to-target (5% of cold-start loss) per cell, every cell
    # at the big row's exact (cluster, k) config --

    def cell_stats(res, **kw):
        hist = res.staleness_hist_edge
        tot = max(sum(hist), 1)
        mean_tau = sum(i * c for i, c in enumerate(hist)) / tot
        return {
            **kw,
            "time_to_target_s": round(res.time_to_target, 3)
            if res.time_to_target
            else None,
            "final_loss": round(res.final_loss(), 6),
            "merges": res.merges,
            "mean_staleness": round(mean_tau, 3),
            "stale_dropped": res.stale_dropped,
            "rate_limited": res.rate_limited,
            "unselected": res.unselected,
            "wall_s": round(res.wall_s, 2),
        }

    def cell(**kw):
        return cell_stats(
            MegaFleet(
                big_spec, cluster_size=big_cluster, k=big_k,
                updates_per_node=updates, local_lr=0.7, target_loss=target,
                **kw,
            ).run(),
            **kw,
        )

    # pace=0 is the big wall-clock row's exact config — reuse its run
    sweep = [cell_stats(big_res, pace_window=0.0)]
    log(json.dumps(sweep[-1]))
    for pace in [0.5] if smoke else [0.5, 1.0]:
        sweep.append(cell(pace_window=pace))
        log(json.dumps(sweep[-1]))
    for frac in ([0.5] if smoke else [0.75, 0.5]):
        sweep.append(cell(select_frac=frac))
        log(json.dumps(sweep[-1]))
    sweep.append(cell(rate_limit_regional=0.02, rate_limit_global=0.005))
    log(json.dumps(sweep[-1]))

    return {
        "engine": "federation/megafleet.py (one jitted lax.scan, "
                  "ops/fleet_kernels.py)",
        "task": "consensus least-squares, hierarchical FedBuff, "
                f"{updates} updates/client, 10% stragglers at 10x",
        "parity_1k": parity,
        "parity_note": "flat merge count/version sequence/staleness "
                       "decisions are event-exact vs the heap; "
                       "hierarchical merge counts exact with loss "
                       "trajectory tolerance-bounded (aggregate "
                       "interleaving within one link_delay window) — "
                       "see docs/design.md 'megafleet'",
        "wall_clock": {"heap": heap_rows, "megafleet": mega_rows},
        "sweep_1m": {
            "n_clients": big_n,
            "start_loss": round(start_loss, 5),
            "target_loss": round(target, 5),
            "cells": sweep,
        },
        "smoke": smoke,
    }


def run_megafleet_chunks(smoke: bool = False) -> dict:
    """ISSUE 16: the chunked-event engine vs the per-event reference.

    Two parts: (a) an inline BIT-IDENTITY check on a flat fleet — the
    chunked engine's batched gather → segment-fold → predicated scatter
    must reproduce the per-event scan's every float (this is the pinned
    invariant, run here at a scale the test suite doesn't pay for); (b)
    the chunk-size sweep at the big hierarchical scale: clients/second
    per ``MEGAFLEET_CHUNK``, including the ``chunk=1`` per-event
    baseline row the ≥2× acceptance is measured against.
    """
    from p2pfl_tpu.federation.megafleet import FleetSpec, MegaFleet

    big_n = 50_000 if smoke else 1_000_000
    updates = 4

    # -- (a) flat bit-identity at 20k --
    pn = 5000 if smoke else 20_000
    pspec = FleetSpec.synth(pn, seed=SEED, slow_frac=0.10)

    def flat(chunk):
        return MegaFleet(
            pspec, cluster_size=0, k=32, updates_per_node=updates,
            local_lr=0.7, chunk=chunk,
        ).run()

    ref, got = flat(1), flat(256)
    identity = {
        "n_clients": pn,
        "merges_equal": got.merges == ref.merges,
        "loss_curve_bit_equal": got.loss_curve == ref.loss_curve,
        "params_bit_equal": bool(
            np.array_equal(got.params["w"], ref.params["w"])
        ),
    }
    log(json.dumps({"chunked_bit_identity": identity}))

    # -- (b) the chunk sweep at scale --
    spec = FleetSpec.synth(big_n, seed=SEED, slow_frac=0.10)
    rows = []
    chunks = [1, 64, 256] if smoke else [1, 64, 256, 512]
    for chunk in chunks:
        res = MegaFleet(
            spec, cluster_size=1024, k=64, updates_per_node=updates,
            local_lr=0.7, chunk=chunk,
        ).run()
        rows.append({
            "chunk": chunk, "n_clients": big_n,
            "wall_s": round(res.wall_s, 2),
            "clients_per_sec": int(res.clients_per_sec),
            "events_per_sec": int(res.n_events / max(res.wall_s, 1e-9)),
            "merges": res.merges, "regional_merges": res.regional_merges,
        })
        log(json.dumps(rows[-1]))
    base = rows[0]["clients_per_sec"]
    best = max(rows[1:], key=lambda r: r["clients_per_sec"])
    return {
        "engine": "run_fleet_program_chunked (ops/fleet_kernels.py)",
        "bit_identity_flat": identity,
        "sweep": rows,
        "speedup_best_vs_per_event": round(
            best["clients_per_sec"] / max(base, 1), 2
        ),
        "smoke": smoke,
    }


def run_megafleet_robust(smoke: bool = False) -> dict:
    """ISSUE 16: the robust-aggregation attacker sweep at fleet scale.

    Attacker fraction × corruption kind × window fold, every cell a full
    1M-client hierarchical drive with the attackers spread across
    clusters (stride placement, so elected regionals corrupt their
    aggregate sends too). The defense claim is measured, not asserted:
    trimmed-mean/median final losses vs fedavg's under the same attack.
    One cell re-runs at 1k against the heap driver (which flushes
    through ``Settings.ASYNC_ROBUST_AGG``) as the tolerance pin.
    """
    from p2pfl_tpu.communication.faults import ByzantineSpec, FaultPlan
    from p2pfl_tpu.federation.megafleet import FleetSpec, MegaFleet
    from p2pfl_tpu.federation.simfleet import SimulatedAsyncFleet
    from p2pfl_tpu.settings import Settings

    big_n = 20_000 if smoke else 1_000_000
    updates = 4
    width = max(4, len(str(big_n - 1)))
    spec = FleetSpec.synth(big_n, seed=SEED, slow_frac=0.10)

    def attack_plan(frac, kind):
        step = max(1, round(1.0 / frac))
        spec_kw = {"scale": {"lam": 50.0}, "noise": {"noise_std": 5.0}}.get(
            kind, {}
        )
        byz = {
            f"sim-{i:0{width}d}": ByzantineSpec(kind=kind, **spec_kw)
            for i in range(0, big_n, step)
        }
        return FaultPlan(seed=SEED, byzantine=byz)

    fracs = [0.10] if smoke else [0.05, 0.10, 0.20]
    kinds = ["sign_flip"] if smoke else ["sign_flip", "scale", "noise"]
    folds = ["fedavg", "median"] if smoke else [
        "fedavg", "trimmed-mean", "median"
    ]
    cells = []
    for frac in fracs:
        for kind in kinds:
            plan = attack_plan(frac, kind)
            for fold in folds:
                res = MegaFleet(
                    spec, cluster_size=1024, k=64, updates_per_node=updates,
                    local_lr=0.7, plan=plan, fold=fold,
                ).run()
                fl = res.final_loss()
                cells.append({
                    "attacker_frac": frac, "kind": kind, "fold": fold,
                    "final_loss": round(fl, 6) if np.isfinite(fl) else None,
                    "diverged": not bool(np.isfinite(fl)),
                    "merges": res.merges,
                    "byz_corrupted": res.byz_corrupted,
                    "wall_s": round(res.wall_s, 2),
                    "clients_per_sec": int(res.clients_per_sec),
                })
                log(json.dumps(cells[-1]))

    # -- the 1k heap pin: one cell, both drivers, same plan+fold --
    pin_kind, pin_fold, pin_frac = kinds[0], folds[-1], fracs[0]
    step = max(1, round(1.0 / pin_frac))
    pin_byz = {
        f"sim-{i:04d}": ByzantineSpec(kind=pin_kind)
        for i in range(0, 1000, step)
    }
    pin_plan = FaultPlan(seed=SEED, byzantine=pin_byz)
    old_fold = Settings.ASYNC_ROBUST_AGG
    try:
        Settings.ASYNC_ROBUST_AGG = pin_fold
        fleet = SimulatedAsyncFleet(
            1000, seed=SEED, cluster_size=32, updates_per_node=updates,
            slow_frac=0.10, local_lr=0.7, plan=pin_plan,
        )
        pspec = FleetSpec.from_sim(fleet)
        heap = fleet.run()
        mega = MegaFleet(
            pspec, cluster_size=32, updates_per_node=updates, local_lr=0.7,
            plan=pin_plan, fold=pin_fold,
        ).run()
    finally:
        Settings.ASYNC_ROBUST_AGG = old_fold
    hl = heap.final_loss()
    pin = {
        "n_clients": 1000, "kind": pin_kind, "fold": pin_fold,
        "attacker_frac": pin_frac,
        "merge_count_exact": mega.merges == heap.merges,
        "byz_corrupted_exact": mega.byz_corrupted == heap.byz_corrupted,
        "final_loss_rel_diff": round(
            abs(mega.final_loss() - hl) / max(hl, 1e-12), 6
        ),
    }
    log(json.dumps({"robust_pin_1k": pin}))
    return {
        "engine": "fold_window kind=trimmed-mean/median "
                  "(ops/fleet_kernels.py) == ops/aggregation."
                  "buffered_robust_merge's rank statistics",
        "attack": "stride-placed attackers (regionals corrupt aggregate "
                  "sends), scale lam=50, noise std=5",
        "cells": cells,
        "heap_pin_1k": pin,
        "smoke": smoke,
    }


ALL_SECTIONS = (
    "threaded", "simulated", "churn", "restart", "byzantine", "megafleet",
    "megafleet_chunks", "megafleet_robust",
)


def main() -> int:
    from p2pfl_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    smoke = "--smoke" in sys.argv
    out_path = "BENCH_ASYNC.json"
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    sections = ALL_SECTIONS
    if "--sections" in sys.argv:
        sections = tuple(sys.argv[sys.argv.index("--sections") + 1].split(","))
        unknown = set(sections) - set(ALL_SECTIONS)
        if unknown:
            log(f"unknown sections: {sorted(unknown)} (known: {ALL_SECTIONS})")
            return 2

    # partial runs merge into the existing document instead of dropping
    # the sections they didn't pay for
    doc = {}
    if sections != ALL_SECTIONS:
        try:
            with open(out_path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
    doc["bench"] = "async_federation_time_to_accuracy"
    if sections == ALL_SECTIONS:
        # partial runs must not relabel the merged document's untouched
        # sections; section_smoke below records each section's own grid
        doc["smoke"] = smoke
    for s in sections:
        doc.setdefault("section_smoke", {})[s] = smoke

    if "threaded" in sections:
        rows = []
        for mode in ("sync", "async", "hier"):
            log(f"=== threaded {mode} ===")
            row = run_threaded(mode, rounds=2 if smoke else 4)
            log(json.dumps(row))
            rows.append(row)
        sync_wall = next(r["wall_s"] for r in rows if r["mode"] == "sync")
        for r in rows:
            r["speedup_vs_sync"] = round(sync_wall / r["wall_s"], 2)
        doc["fleet"] = {
            "n_nodes": 10, "rounds": 2 if smoke else 4, "epochs": 1,
            "model": "mnist mlp (synthetic_mnist 8192/2048)",
            "plan": "seed=1905: 1 slow node (0.5s inbound weights), 1 crash "
                    "(round 1), 1% drop — small-fleet quantization of 10%/1%",
            "target_acc": TARGET_ACC,
            "budget_note": "rounds == async local updates: identical total "
                           "local training in every mode",
        }
        doc["threaded"] = rows

    if "simulated" in sections:
        log("=== simulated 1k ===")
        doc["simulated_1k"] = run_simulated(smoke=smoke)

    if "churn" in sections:
        log("=== churn 1k ===")
        doc["churn_1k"] = run_churn(smoke=smoke)

    if "restart" in sections:
        log("=== restart 1k ===")
        doc["restart_1k"] = run_restart(smoke=smoke)

    if "byzantine" in sections:
        log("=== byzantine 1k ===")
        doc["byzantine_1k"] = run_byzantine(smoke=smoke)

    if "megafleet" in sections:
        log("=== megafleet ===")
        doc["megafleet_1m"] = run_megafleet(smoke=smoke)

    if "megafleet_chunks" in sections:
        log("=== megafleet chunk sweep ===")
        doc["megafleet_chunks"] = run_megafleet_chunks(smoke=smoke)

    if "megafleet_robust" in sections:
        log("=== megafleet robust-agg attacker sweep ===")
        doc["megafleet_robust"] = run_megafleet_robust(smoke=smoke)

    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    log(f"wrote {out_path}")
    summary = {"metric": "bench_async", "sections": list(sections)}
    if "threaded" in doc:
        summary.update({r["mode"]: r["wall_s"] for r in doc["threaded"]})
    if "megafleet_1m" in doc:
        mrows = doc["megafleet_1m"]["wall_clock"]["megafleet"]
        summary["megafleet_clients_per_sec"] = mrows[-1]["clients_per_sec"]
    if "megafleet_chunks" in doc:
        summary["chunked_speedup"] = (
            doc["megafleet_chunks"]["speedup_best_vs_per_event"]
        )
    if "megafleet_robust" in doc:
        summary["robust_cells"] = len(doc["megafleet_robust"]["cells"])
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
