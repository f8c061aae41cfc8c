"""Megafleet: the async fleet simulator vectorized to ≥1M clients.

:class:`~p2pfl_tpu.federation.simfleet.SimulatedAsyncFleet` is a Python
event heap pushing real per-node buffers through ``heapq`` — exact and
churn/adversary-capable, but ~10⁴ events/sec caps it at 1k–10k nodes.
This module re-expresses the same run as dense arrays advanced by one
jitted ``lax.scan`` (:mod:`~p2pfl_tpu.ops.fleet_kernels`): per-client
``(params, adopted version, train schedule, fault stream)`` state, the
regional tier as vectorized scatter-addressed windows, and the REAL math
as inner functions — the FedBuff ``w(τ)`` weighting, the
``(origin, seq)``-sorted K-flush fold (the very
:func:`~p2pfl_tpu.ops.aggregation.fedavg` /
:func:`~p2pfl_tpu.ops.aggregation.server_merge` kernels the live
:class:`~p2pfl_tpu.federation.buffer.BufferedAggregator` calls), and
:class:`~p2pfl_tpu.federation.routing.TierRouter`'s membership→tier
derivation (clusters, regional election, K clamps come from a real
router over the same addresses).

The default engine processes events in fixed-size CHUNKS
(``Settings.MEGAFLEET_CHUNK`` events per scan step — the
``run_fleet_program_chunked`` four-pass decomposition documented in
``docs/design.md``), amortizing XLA:CPU's per-op dispatch over a whole
chunk; ``chunk=1`` selects the per-event reference scan, and the two
engines are BIT-IDENTICAL on flat topologies (a pinned invariant).

**The heap driver stays the bit-parity anchor.** At 1k nodes on the
consensus task, the flat vectorized engine reproduces the heap's merge
count, version sequence and staleness decisions EXACTLY (the scan's
chronological order is the heap's pop order — see
``ops/fleet_kernels.py``), with the loss trajectory matching to float
reassociation tolerance (the heap weights in Python f64, the scan in
f32; XLA may fuse the consensus step's multiply-add). The hierarchical
engine additionally approximates aggregate-arrival interleaving within
one ``link_delay`` window (documented in ``docs/design.md``); its parity
anchor pins merge counts exactly under a staleness bound wide enough
that boundary reorderings cannot flip an admission.

**Fault contract.** A :class:`~p2pfl_tpu.communication.faults.FaultPlan`
is consumed through counter-based seed-derived streams — dense verdict
grids indexed by ``(node, send index)`` and generated in one vectorized
draw from ``(plan.seed, stream id)`` — instead of the heap's per-edge
Python ``random.Random`` streams, so a plan replays bit-exact from
``(seed, plan)`` without a million generator objects (the verdict
streams therefore differ from the heap's: plan-parity between the
drivers is statistical, not per-send). Supported: ``default``
drop/delay/jitter/duplicate on upward sends — both the client→aggregator
hop and the regional→root aggregate hop, each from its own stream
(downward model pushes are delivered reliably with delay only; the heap
can also drop those — a documented divergence under drop plans),
``slow_nodes`` (inbound latency of the aggregator / the push-down hops),
``crashes`` (``AsyncTrainStage`` → the client stops producing after
``round_no`` updates; megafleet does NOT model the eviction/K-repair
that follows — at fleet scale K ≪ cluster fan-in and no buffer wedges),
``byzantine`` payload attacks for the stateless vectorized kinds
(``sign_flip``/``scale``/``noise`` — applied to the SENT copy at both
send seams, never the honest local model; stateful per-edge kinds raise
toward the heap), and ``joins``/``leaves`` churn as time-indexed
liveness: the schedule is windowed by per-client ``(start, stop)``
times, and a real :class:`TierRouter` is re-derived at every membership
boundary, so election, K clamps and failovers come from the production
derivation (joiners must occupy the top address block; duplicate
injection is a counted no-op at the edge — the version vector dedups it
— and a counted verdict grid at the aggregate seam). Combinations that
interact statefully (churn × byzantine, churn × robust folds,
churn × ``slow_nodes``) and per-edge ``edges`` overrides / ``partitions``
raise loudly: the heap driver remains the authority there; megafleet
exists for the phenomena that only appear at fleet scale (Bonawitz et
al., MLSys'19) — staleness distributions, pace steering, selection
over-provisioning, per-tier rate limits, robust-aggregation sweeps
under attack — which it exposes as array-level controls no per-edge
Python loop could sweep.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from p2pfl_tpu.federation.routing import TierRouter
from p2pfl_tpu.federation.simfleet import FleetResult

Pytree = Any

#: dedicated stream ids for the counter-based draws — one sub-seed per
#: concern, FaultPlan-style, so arming one knob never shifts another's
#: verdicts (e.g. enabling selection must not move drop outcomes)
_STREAM_POP = 17  #: population shape (durations, slow membership)
_STREAM_TARGET = 7  #: per-client consensus targets (matches simfleet)
_STREAM_SELECT = 19
_STREAM_DROP = 23
_STREAM_JITTER = 29
_STREAM_PACE = 31
_STREAM_AGG_DROP = 37  #: regional→root aggregate send verdicts
_STREAM_AGG_JIT = 41
_STREAM_DUP = 43  #: edge duplicate verdicts (counted; version-vector no-op)
_STREAM_BYZ = 47  #: byzantine "noise" payload rows for edge sends
_STREAM_AGG_NOISE = 53  #: byzantine "noise" rows at the aggregate seam
_STREAM_AGG_DUP = 59  #: aggregate duplicate verdicts (counted)

#: window folds megafleet can run in-array (krum-screen scores each
#: contribution against the others' pairwise distances — stateful per
#: contribution set, heap-only)
_VECTOR_FOLDS = ("fedavg", "trimmed-mean", "median")


@dataclass(frozen=True)
class GradTask:
    """The vmapped real-gradient workload: every client trains a tiny
    model (``linear``: one dense layer; ``mlp``: dense→relu→dense) with
    REAL ``jax.grad`` SGD steps on softmax cross-entropy, batched inside
    the chunk body by :func:`~p2pfl_tpu.ops.fleet_kernels.make_grad_fns`.

    Data is counter-keyed per ``(client, round)`` — a Gaussian cloud
    around the client's private ``mu`` (the ``hetero`` non-IID knob)
    labeled by a fixed teacher — so the heap twin and the scan derive
    identical batches from the fold key alone, and the per-client update
    is bit-identical to :class:`~p2pfl_tpu.learning.learner.JaxLearner`'s
    ``optax.sgd`` epoch (the parity pin). The global loss curve is the
    teacher-labeled eval set's cross-entropy.
    """

    kind: str = "linear"  #: "linear" | "mlp"
    d_in: int = 8
    n_out: int = 4
    hidden: int = 0  #: MLP hidden width (0 for linear)
    batch: int = 8
    steps: int = 2  #: SGD steps per local round
    data_seed: int = 0
    hetero: float = 1.0  #: client-mean spread (0 = IID)
    n_eval: int = 256

    def param_dim(self) -> int:
        from p2pfl_tpu.ops.fleet_kernels import grad_param_dim

        return grad_param_dim(self.kind, self.d_in, self.n_out, self.hidden)

    def arrays(self, n: int):
        """Host draws: ``(mu [n, d_in], tw, tb, x_eval, y_eval)`` — the
        client means, the labeling teacher and the eval set, each from
        its own counter stream of ``data_seed``."""
        mu = (
            np.random.default_rng([self.data_seed, 3, n])
            .normal(size=(n, self.d_in))
            .astype(np.float32)
            * np.float32(self.hetero)
        )
        trng = np.random.default_rng([self.data_seed, 1])
        tw = trng.normal(size=(self.d_in, self.n_out)).astype(np.float32)
        tb = trng.normal(size=(self.n_out,)).astype(np.float32)
        erng = np.random.default_rng([self.data_seed, 2])
        xe = erng.normal(size=(self.n_eval, self.d_in)).astype(np.float32)
        ye = np.argmax(xe @ tw + tb, axis=-1).astype(np.int32)
        return mu, tw, tb, xe, ye


@dataclass
class FleetSpec:
    """The dense edge population: everything per-client as one array.

    Built two ways: :meth:`from_sim` exports a live heap fleet's exact
    population (durations, sample weights, targets — the parity hook:
    both drivers then simulate the SAME fleet), and :meth:`synth`
    derives a population of any size from vectorized counter-based
    streams (the ≥1M path — deterministic in ``(n, seed)``, but not the
    heap's per-idx streams, which would cost one Python generator per
    client).
    """

    durations: np.ndarray  #: [N] f64 — per-update train duration
    num_samples: np.ndarray  #: [N] f32 — sample weights (FedAvg numerators)
    targets: np.ndarray  #: [N, dim] f32 — consensus-task private targets
    slow: np.ndarray  #: [N] f64 — extra inbound latency when aggregator
    init: np.ndarray  #: [dim] f32 — shared initial model
    seed: int
    #: the exporting fleet's wire latency (None: engine default). Carried
    #: so a from_sim spec drives the vectorized twin with the SAME clock
    #: without the caller re-passing it.
    link_delay: Optional[float] = None

    @property
    def n(self) -> int:
        return int(self.durations.shape[0])

    @property
    def dim(self) -> int:
        return int(self.targets.shape[1])

    def target_mean(self) -> np.ndarray:
        """The fleet's consensus fixed point: the sample-weighted target
        mean (the heap's ``_default_loss`` reference over full
        membership)."""
        w = self.num_samples.astype(np.float32)
        return (w[:, None] * self.targets).sum(0) / w.sum()

    def loss(self, params: np.ndarray) -> float:
        d = np.asarray(params, np.float32) - self.target_mean()
        return float((d * d).sum())

    @classmethod
    def from_sim(cls, fleet, extra: int = 0, allow_custom: bool = False) -> "FleetSpec":
        """Export a :class:`SimulatedAsyncFleet`'s population via its
        :meth:`~p2pfl_tpu.federation.simfleet.SimulatedAsyncFleet.
        export_spec` hook (sorted address order == index order — the two
        drivers' fold keys agree). ``extra`` appends pending-joiner rows
        (churn parity: the vectorized twin needs the joiners' population
        before they exist in the heap); ``allow_custom`` admits a heap
        fleet driven by a vectorized-twin ``train_fn`` (the gradient-task
        parity pin)."""
        d = fleet.export_spec(extra=extra, allow_custom=allow_custom)
        return cls(
            durations=d["durations"],
            num_samples=d["num_samples"],
            targets=d["targets"],
            slow=d["slow"],
            init=d["init"],
            seed=d["seed"],
            link_delay=d["link_delay"],
        )

    @classmethod
    def synth(
        cls,
        n: int,
        *,
        seed: int = 0,
        dim: int = 16,
        base_duration: float = 1.0,
        slow_frac: float = 0.0,
        slow_factor: float = 10.0,
    ) -> "FleetSpec":
        """A megafleet-native population: same statistics as the heap's
        (duration jitter U[0.8, 1.2]·base, a ``slow_frac`` straggler
        population at ``slow_factor``×, samples ``1 + i mod 3``, targets
        = shared offset + private noise), drawn in three vectorized
        batches instead of N per-idx streams."""
        rng = np.random.default_rng([seed, _STREAM_POP])
        durations = base_duration * (0.8 + 0.4 * rng.random(n))
        if slow_frac > 0.0:
            durations = np.where(
                rng.random(n) < slow_frac, durations * slow_factor, durations
            )
        base = np.random.default_rng([seed, 5]).normal(size=dim).astype(np.float32) * 2.0
        noise = np.random.default_rng([seed, _STREAM_TARGET, n]).normal(
            size=(n, dim)
        ).astype(np.float32)
        return cls(
            durations=durations.astype(np.float64),
            num_samples=(1 + np.arange(n) % 3).astype(np.float32),
            targets=base[None, :] + noise,
            slow=np.zeros(n, np.float64),
            init=np.zeros(dim, np.float32),
            seed=int(seed),
        )


@dataclass
class MegaFleetResult(FleetResult):
    """A :class:`FleetResult` (the heap drivers' determinism-test
    surface — parity tests compare the shared fields directly) plus the
    array engine's fleet-scale statistics."""

    regional_merges: int = 0
    buffered: int = 0  #: client contributions admitted into a window
    stale_dropped: int = 0  #: τ > max_staleness at any admission gate
    rate_limited: int = 0  #: rejected by a per-tier rate limit
    unselected: int = 0  #: update slots skipped by selection
    staleness_hist_edge: List[int] = field(default_factory=list)
    staleness_hist_global: List[int] = field(default_factory=list)
    n_events: int = 0  #: scan length (trained updates incl. dropped sends)
    wall_s: float = 0.0  #: host wall-clock of the whole run
    clients_per_sec: float = 0.0  #: n_clients / wall_s


class MegaFleet:
    """One vectorized fleet; :meth:`run` compiles and drives it.

    Mirrors :class:`SimulatedAsyncFleet`'s constructor surface where the
    semantics coincide (seed/cluster_size/k/alpha/server_lr/
    max_staleness/updates_per_node/link_delay/local_lr/target_loss/plan/
    evict_delay) and adds the Bonawitz array-level production knobs:

    - ``pace_window`` — pace steering: each client's whole schedule is
      offset by a seeded uniform draw in ``[0, pace_window)``, spreading
      the thundering-herd first wave (and with it the staleness
      distribution — the histograms make the effect measurable);
    - ``select_frac`` — selection: each ``(client, update)`` slot
      participates with this probability (an unselected device idles
      that period, Bonawitz §4). Over-provisioning is selecting more
      than the buffers need and measuring the wasted work;
    - ``rate_limit_regional`` / ``rate_limit_global`` — per-tier rate
      limits: a tier refuses offers arriving within the gap of its last
      accepted one (counted, never raising);
    - ``chunk`` — events per scan step, an integer ≥ 1 (1 = the
      per-event reference engine, unless a feature below needs the
      chunked one; >1 = the chunked engine, bit-identical on flat
      topologies);
    - ``task`` — a :class:`GradTask` swaps the consensus step for real
      vmapped-gradient local rounds;
    - ``fold`` / ``trim`` — the window fold family (``fedavg`` /
      ``trimmed-mean`` / ``median``), the robust-aggregation sweep knob.

    Defaults for the knobs come from ``Settings.MEGAFLEET_PACE_WINDOW``
    / ``_SELECT_FRAC`` / ``_REGIONAL_RATE_S`` / ``_GLOBAL_RATE_S`` /
    ``_SCAN_UNROLL`` / ``_CHUNK`` (and ``Settings.ASYNC_ROBUST_AGG`` /
    ``ASYNC_TRIM`` for the fold) at construction time (never read inside
    the program — the jit-staleness contract).

    :meth:`run` picks the engine from these arguments alone: the
    per-event scan when ``chunk == 1`` and nothing asks for chunking,
    the chunked scan otherwise (``chunk > 1``, a ``task``, a robust
    ``fold``, a Byzantine or churn plan, duplicates on a hierarchy).
    """

    def __init__(
        self,
        spec: FleetSpec,
        *,
        cluster_size: int = 0,
        k: Optional[int] = None,
        alpha: Optional[float] = None,
        server_lr: Optional[float] = None,
        max_staleness: Optional[int] = None,
        updates_per_node: int = 4,
        link_delay: Optional[float] = None,
        local_lr: float = 0.5,
        target_loss: float = 0.0,
        plan=None,
        pace_window: Optional[float] = None,
        select_frac: Optional[float] = None,
        rate_limit_regional: Optional[float] = None,
        rate_limit_global: Optional[float] = None,
        unroll: Optional[int] = None,
        chunk: Optional[int] = None,
        task: Optional[GradTask] = None,
        fold: Optional[str] = None,
        trim: Optional[int] = None,
        evict_delay: float = 0.5,
    ) -> None:
        from p2pfl_tpu.settings import Settings

        self.spec = spec
        self.n = spec.n
        self.dim = spec.dim
        self.seed = int(spec.seed)
        self.cluster_size = int(cluster_size)
        self.updates_per_node = int(updates_per_node)
        if link_delay is None:
            link_delay = spec.link_delay if spec.link_delay is not None else 0.01
        self.link_delay = float(link_delay)
        self.local_lr = float(local_lr)
        self.target_loss = float(target_loss)
        self.k = max(1, int(Settings.FEDBUFF_K if k is None else k))
        self.alpha = float(Settings.FEDBUFF_ALPHA if alpha is None else alpha)
        self.server_lr = float(
            Settings.FEDBUFF_SERVER_LR if server_lr is None else server_lr
        )
        self.max_staleness = int(
            Settings.ASYNC_MAX_STALENESS if max_staleness is None else max_staleness
        )
        self.pace_window = float(
            Settings.MEGAFLEET_PACE_WINDOW if pace_window is None else pace_window
        )
        self.select_frac = float(
            Settings.MEGAFLEET_SELECT_FRAC if select_frac is None else select_frac
        )
        self.rate_limit_regional = float(
            Settings.MEGAFLEET_REGIONAL_RATE_S
            if rate_limit_regional is None
            else rate_limit_regional
        )
        self.rate_limit_global = float(
            Settings.MEGAFLEET_GLOBAL_RATE_S
            if rate_limit_global is None
            else rate_limit_global
        )
        self.unroll = max(1, int(Settings.MEGAFLEET_SCAN_UNROLL if unroll is None else unroll))
        chunk = Settings.MEGAFLEET_CHUNK if chunk is None else chunk
        if not isinstance(chunk, (int, np.integer)) or chunk < 1:
            raise ValueError(
                "chunk is the number of events per scan step, an integer "
                f">= 1 (1 = the per-event engine); got {chunk!r}"
            )
        self.chunk = int(chunk)
        self.task = task
        self.fold = str(Settings.ASYNC_ROBUST_AGG if fold is None else fold)
        self.trim = int(Settings.ASYNC_TRIM if trim is None else trim)
        self.evict_delay = float(evict_delay)
        if self.fold not in _VECTOR_FOLDS:
            raise ValueError(
                f"megafleet folds are {'/'.join(_VECTOR_FOLDS)}; {self.fold!r} "
                "scores contributions statefully and needs the heap driver"
            )
        if task is not None:
            pd = task.param_dim()
            if self.dim != pd:
                raise ValueError(
                    f"GradTask({task.kind!r}) flattens to {pd} parameters; "
                    f"the spec carries dim={self.dim} — build the spec with "
                    "dim=task.param_dim()"
                )
        self.plan = plan

        # membership → tiers through the REAL router: clusters, regional
        # election and K clamps are TierRouter's derivation, not a
        # re-implementation (sorted zero-padded addresses == index order,
        # so cluster slices are contiguous index ranges)
        width = max(4, len(str(self.n - 1)))
        self.addrs = [f"sim-{i:0{width}d}" for i in range(self.n)]
        self.router = TierRouter(self.addrs, self.cluster_size)
        self._addr_idx = {a: j for j, a in enumerate(self.addrs)}
        self.hier = not self.router.topo.is_flat()
        self._byz: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
        self._task_cache = None
        self._check_plan(plan)
        self._churn = self._derive_churn()

    def _check_plan(self, plan) -> None:
        if plan is None:
            return
        unsupported = [
            name
            for name, val in (("edges", plan.edges), ("partitions", plan.partitions))
            if val
        ]
        if unsupported:
            raise ValueError(
                "MegaFleet's fault algebra is counter-grid based — verdict "
                "streams are keyed by (node, send index), so per-edge "
                f"overrides and pairwise cuts ({'/'.join(unsupported)}) "
                "need the heap driver (SimulatedAsyncFleet)"
            )
        if plan.byzantine:
            from p2pfl_tpu.communication.faults import byz_payload_grid

            # raises toward the heap for the stateful per-edge kinds
            # (equivocate / random_scale)
            self._byz = byz_payload_grid(plan, self.addrs)
        churn = bool(plan.joins or plan.leaves)
        if churn:
            if plan.byzantine:
                raise ValueError(
                    "churn × byzantine re-elects attackers mid-run (the "
                    "aggregate corruption grid would go stale); the "
                    "combination needs the heap driver"
                )
            if self.fold != "fedavg":
                raise ValueError(
                    "churn × robust folds shrinks windows mid-run (rank "
                    "statistics over a re-clamped K); the combination "
                    "needs the heap driver"
                )
            if plan.slow_nodes or bool(np.any(self.spec.slow != 0.0)):
                raise ValueError(
                    "churn × slow_nodes re-prices every hop per election; "
                    "the combination needs the heap driver"
                )

    def _derive_churn(self) -> Optional[Dict[str, Any]]:
        """The time-indexed liveness table: per-client ``(start, stop)``
        schedule windows plus one REAL :class:`TierRouter` per membership
        boundary (election, K clamps and failovers come from the
        production derivation, not a re-implementation)."""
        plan = self.plan
        if plan is None or not (plan.joins or plan.leaves):
            return None
        n = self.n
        join_at: Dict[int, float] = {}
        for a in sorted(plan.joins):
            j = self._addr_idx.get(a)
            if j is not None:
                join_at[j] = float(plan.joins[a].at_s)
        founders = n - len(join_at)
        if join_at and sorted(join_at) != list(range(founders, n)):
            raise ValueError(
                "megafleet joiners must occupy the top address block "
                "(sorted-address order == index order keeps founder "
                "clusters stable as they arrive); scattered join "
                "addresses need the heap driver"
            )
        ats = [join_at[j] for j in range(founders, n)]
        if any(b < a for a, b in zip(ats, ats[1:])):
            raise ValueError(
                "megafleet join times must be nondecreasing in address "
                "order (the heap assigns population streams in join "
                "order; reordered joins need the heap driver)"
            )
        start = np.zeros(n, np.float64)
        stop = np.full(n, np.inf, np.float64)
        joined: List[str] = []
        for j in range(founders, n):
            # the heap joiner's first training completes at
            # at_s + link_delay + duration (bootstrap hop, then train)
            start[j] = join_at[j] + self.link_delay
            joined.append(self.addrs[j])
        dead_at: Dict[int, float] = {}
        left: List[str] = []
        for a in sorted(plan.leaves):
            j = self._addr_idx.get(a)
            if j is None:
                continue
            sp = plan.leaves[a]
            stop[j] = min(stop[j], float(sp.at_s))
            # graceful: announced, topology re-derives at at_s; abrupt:
            # discovered like a crash, one eviction window later
            dead_at[j] = float(sp.at_s) + (0.0 if sp.graceful else self.evict_delay)
            left.append(a)
        bounds = sorted({0.0} | set(join_at.values()) | set(dead_at.values()))
        routers: List[Tuple[float, TierRouter]] = []
        failovers = 0
        prev_root: Optional[str] = None
        for T in bounds:
            members = [
                self.addrs[j]
                for j in range(n)
                if j < founders or join_at[j] <= T
            ]
            dead = [self.addrs[j] for j, td in dead_at.items() if td <= T]
            rt = TierRouter(members, self.cluster_size, dead=dead)
            if prev_root is not None and rt.root != prev_root:
                failovers += 1
            prev_root = rt.root
            routers.append((T, rt))
        return {
            "routers": routers,
            "start": start,
            "stop": stop,
            "joined": joined,
            "left": left,
            "failovers": failovers,
        }

    # ---- array derivation (host, vectorized numpy) ----

    def _tier_arrays(self):
        """Per-client and per-regional routing arrays, one row per churn
        epoch (a single row when the plan has no churn). Cluster geometry
        is the FULL population's (joiners occupy the top address block,
        so an epoch's clusters are a prefix of it); what varies per epoch
        is the election, the hop prices and the K clamps."""
        n, L = self.n, self.link_delay
        plan_delay = float(self.plan.default.delay) if self.plan is not None else 0.0
        slow = self.spec.slow
        if self.plan is not None and self.plan.slow_nodes:
            # fold the plan's inbound latencies into the population by
            # max: idempotent whether or not the spec already carries
            # them (export_spec folds the same plan; synth exports zeros)
            plan_slow = np.zeros(n, np.float64)
            for addr, extra in self.plan.slow_nodes.items():
                j = self._addr_idx.get(addr)
                if j is not None:
                    plan_slow[j] = float(extra)
            slow = np.maximum(slow, plan_slow)
        clusters = self.router.topo.clusters
        R = len(clusters)
        regional_of = np.zeros(n, np.int32)
        for ci, cluster in enumerate(clusters):
            for a in cluster:
                regional_of[self._addr_idx[a]] = ci
        epoch_routers = (
            self._churn["routers"] if self._churn is not None else [(0.0, self.router)]
        )
        bounds = np.asarray([t for t, _ in epoch_routers], np.float64)
        n_ep = len(epoch_routers)
        reg_node = np.full((n_ep, R), -1, np.int32)
        k_reg = np.ones((n_ep, R), np.int32)
        reg_adopt = np.zeros((n_ep, R), np.float64)
        is_regional = np.zeros((n_ep, n), bool)
        arr_delay = np.zeros((n_ep, n), np.float64)
        adopt_delay = np.zeros((n_ep, n), np.float64)
        root_is = np.zeros(n_ep, np.int64)
        k_globals: List[int] = []
        idx_arange = np.arange(n)
        for e_i, (_, rt) in enumerate(epoch_routers):
            root_i = self._addr_idx[rt.root]
            root_is[e_i] = root_i
            for ci, cluster in enumerate(rt.topo.clusters):
                a = next((m for m in cluster if m not in rt.dead), None)
                if a is None:
                    continue  # fully dead cluster: no live events route here
                reg_node[e_i, ci] = self._addr_idx[a]
                k_reg[e_i, ci] = rt.buffer_plan(a, self.k).regional_k or 1
            k_globals.append(int(rt.buffer_plan(rt.root, self.k).global_k or 1))
            rn = reg_node[e_i]
            rsafe = np.clip(rn, 0, None)
            reg_adopt[e_i] = np.where(
                (rn >= 0) & (rn != root_i), L + plan_delay + slow[rsafe], 0.0
            )
            my_reg = rn[regional_of]  # [n] my cluster's elected regional
            is_reg = idx_arange == my_reg
            is_regional[e_i] = is_reg
            hop_reg = L + plan_delay + slow[np.clip(my_reg, 0, None)]
            arr_delay[e_i] = np.where(is_reg, 0.0, hop_reg)
            hop_down_self = L + plan_delay + slow
            root_cluster = regional_of[root_i]
            ad = np.where(
                regional_of == root_cluster,
                hop_down_self,
                reg_adopt[e_i][regional_of] + hop_down_self,
            )
            ad = np.where(is_reg, reg_adopt[e_i][regional_of], ad)
            ad[root_i] = 0.0
            adopt_delay[e_i] = ad
        k_global = k_globals[0]
        if any(kg != k_global for kg in k_globals):
            raise ValueError(
                "churn re-clamps the global K mid-run; that repair path "
                "needs the heap driver"
            )
        root_cluster0 = int(regional_of[root_is[0]])
        if any(int(regional_of[ri]) != root_cluster0 for ri in root_is):
            raise ValueError(
                "churn moved the global root to another cluster (a fully "
                "dead root cluster); that failover needs the heap driver"
            )
        is_root_reg = np.arange(R) == root_cluster0
        agg_delay = np.where(
            is_root_reg, 0.0, L + plan_delay + slow[root_is[0]]
        )
        return {
            "bounds": bounds,
            "n_ep": n_ep,
            "regional_of": regional_of,
            "reg_node": reg_node,
            "is_regional": is_regional,
            "arr_delay": arr_delay,
            "adopt_delay": adopt_delay,
            "reg_adopt": reg_adopt,
            "agg_delay": agg_delay,
            "is_root_reg": is_root_reg,
            "k_reg": k_reg,
            "k_global": int(k_global),
        }

    def _agg_grids(self, tiers, stride: int) -> Dict[str, np.ndarray]:
        """Per-(regional, up_seq) verdict grids for the regional→root
        aggregate sends — the heap routes these through ``_edge_verdict``
        (and ``byz_corrupt_update``) too, so the plan's default
        drop/jitter/duplicate and a regional attacker's corruption must
        reach this seam (counter-based streams; the root's own cluster
        offers directly and bypasses the wire, heap semantics)."""
        R = tiers["k_reg"].shape[1]
        out: Dict[str, np.ndarray] = {
            "ok": np.ones((R, stride), bool),
            "jit": np.zeros((R, stride), np.float32),
            "dup": np.zeros((R, stride), bool),
        }
        plan = self.plan
        if plan is None or not self.hier:
            return out
        irr = tiers["is_root_reg"]
        if plan.default.drop > 0.0:
            ok = (
                np.random.default_rng([self.seed, _STREAM_AGG_DROP]).random(
                    (R, stride)
                )
                >= plan.default.drop
            )
            ok[irr, :] = True
            out["ok"] = ok
        if plan.default.jitter > 0.0:
            jit = (
                np.random.default_rng([self.seed, _STREAM_AGG_JIT])
                .random((R, stride))
                .astype(np.float32)
                * np.float32(plan.default.jitter)
            )
            jit[irr, :] = 0.0
            out["jit"] = jit
        if plan.default.duplicate > 0.0:
            dup = (
                np.random.default_rng([self.seed, _STREAM_AGG_DUP]).random(
                    (R, stride)
                )
                < plan.default.duplicate
            )
            dup[irr, :] = False
            out["dup"] = dup
        if self._byz is not None:
            # churn × byzantine raises in _check_plan, so the election is
            # static: epoch 0's elected regionals are THE regionals
            code, lam, std = self._byz
            rn = tiers["reg_node"][0]
            rsafe = np.clip(rn, 0, None)
            akind = np.where((rn >= 0) & ~irr, code[rsafe], 0).astype(np.int32)
            alam = np.where(akind > 0, lam[rsafe], 1.0).astype(np.float32)
            out["akind"] = akind
            out["alam"] = alam
            att_r = np.nonzero(akind == 3)[0]
            nrow = int(att_r.shape[0]) * stride
            agg_noise = np.zeros((nrow + 1, self.dim), np.float32)
            idxg = np.zeros((R, stride), np.int64)
            if nrow:
                draws = (
                    np.random.default_rng([self.seed, _STREAM_AGG_NOISE])
                    .normal(size=(nrow, self.dim))
                    .astype(np.float32)
                )
                agg_noise[1:] = draws * std[rn[att_r]].repeat(stride)[:, None]
                idxg[att_r] = 1 + np.arange(nrow).reshape(-1, stride)
            out["agg_noise_idx"] = idxg.astype(np.int32)
            out["agg_noise"] = agg_noise
        return out

    def _events(self, tiers) -> Dict[str, Any]:
        """The sorted arrival rows + verdict columns (counter-based).

        Fold keys are TWO int32 words — ``key_hi`` the origin index,
        ``key_lo`` the 1-based update seq — lexsorted ``(hi, lo)`` inside
        the fold, which IS the heap's ``(origin addr, seq)`` tuple sort
        (zero-padded addresses sort as indices). No product key, so
        ``n_clients × updates`` can never overflow the fold ordering.
        """
        n, M = self.n, self.updates_per_node
        d = self.spec.durations
        seed = self.seed
        crash_limit = np.full(n, M, np.int64)
        if self.plan is not None:
            for addr, spec in self.plan.crashes.items():
                j = self._addr_idx.get(addr)
                if j is not None and spec.stage == "AsyncTrainStage":
                    crash_limit[j] = min(M, spec.round_no or 0)
        pace = np.zeros(n, np.float64)
        if self.pace_window > 0.0:
            pace = (
                np.random.default_rng([seed, _STREAM_PACE]).random(n)
                * self.pace_window
            )
        churn = self._churn
        start = churn["start"] if churn is not None else np.zeros(n, np.float64)
        stop = churn["stop"] if churn is not None else np.full(n, np.inf)
        m = np.arange(1, M + 1)
        alive = m[None, :] <= crash_limit[:, None]  # [N, M]
        t_train = start[:, None] + pace[:, None] + m[None, :] * d[:, None]
        alive &= t_train < stop[:, None]  # a leaver stops producing at at_s
        selected = np.ones((n, M), bool)
        if self.select_frac < 1.0:
            selected = (
                np.random.default_rng([seed, _STREAM_SELECT]).random((n, M))
                < self.select_frac
            )
        unselected = int((alive & ~selected).sum())
        mask = alive & selected
        plan = self.plan
        ii, mm = np.nonzero(mask)
        tt = t_train[ii, mm]
        ep = np.searchsorted(tiers["bounds"], tt, side="right") - 1
        ep = np.clip(ep, 0, tiers["n_ep"] - 1)
        isreg = tiers["is_regional"][ep, ii]
        ta = tt + tiers["arr_delay"][ep, ii]
        if plan is not None and plan.default.jitter > 0.0:
            jit = (
                np.random.default_rng([seed, _STREAM_JITTER]).random((n, M))
                * plan.default.jitter
            )
            # regionals self-offer — no wire, no jitter (src==dst bypass;
            # keyed on the election mask, not arr_delay, which collapses
            # to 0 for everyone at link_delay=0)
            ta = ta + np.where(isreg, 0.0, jit[ii, mm])
        ok = np.ones(ii.shape[0], bool)
        if plan is not None and plan.default.drop > 0.0:
            dropped = (
                np.random.default_rng([seed, _STREAM_DROP]).random((n, M))
                < plan.default.drop
            )
            ok = ~(dropped[ii, mm] & ~isreg)  # src==dst bypass
        wire_dropped = int((~ok).sum())
        lost = 0
        if churn is not None:
            # arrivals at an aggregator that stopped before t_arr are
            # discarded (the heap's crashed-node arrival gate) — in-flight
            # updates to a not-yet-evicted leaver die with it
            tgt = tiers["reg_node"][ep, tiers["regional_of"][ii]]
            dead_arrival = ~isreg & (ta >= stop[np.clip(tgt, 0, None)])
            lost = int((ok & dead_arrival).sum())
            ok = ok & ~dead_arrival
        order = np.lexsort((mm, ii, ta))
        ii, mm, tt, ta, ok, ep, isreg = (
            x[order] for x in (ii, mm, tt, ta, ok, ep, isreg)
        )
        tt32 = tt.astype(np.float32)
        out: Dict[str, Any] = {
            "client": ii.astype(np.int32),
            "key_hi": ii.astype(np.int32),
            "key_lo": (mm + 1).astype(np.int32),
            "t_train": tt32,
            "t_arr": ta.astype(np.float32),
            # f32 subtraction of the f32 operands — exactly the per-event
            # kernel's in-scan arithmetic, so both engines see identical
            # adoption thresholds
            "t_adopt": tt32 - tiers["adopt_delay"][ep, ii].astype(np.float32),
            "send_ok": ok,
            "ep": ep.astype(np.int32),
            "is_reg": isreg,
            "_unselected": unselected,
            "_wire_dropped": wire_dropped,
            "_lost": lost,
        }
        if self._byz is not None:
            code, lam, std = self._byz
            bkind = np.where(isreg, 0, code[ii]).astype(np.int32)
            out["bkind"] = bkind
            out["blam"] = lam[ii].astype(np.float32)
            out["bstd"] = std[ii].astype(np.float32)
            # the heap counts corruption at the send seam, BEFORE the
            # drop verdict — every attacker wire send counts
            out["_byz_edge"] = int((bkind > 0).sum())
        if plan is not None and plan.default.duplicate > 0.0:
            du = np.random.default_rng([seed, _STREAM_DUP]).random((n, M))
            dup_e = ok & ~isreg & (du[ii, mm] < plan.default.duplicate)
            # duplicates never reach the math: the receiver's version
            # vector dedups the replayed (origin, seq) triple — counted
            # here, exactly the heap's injected-then-rejected semantics
            out["_dup_edge"] = int(dup_e.sum())
        return out

    # ---- chunk layout (host) ----

    def _chunk_layout(self, client: np.ndarray, C: int) -> np.ndarray:
        """``[S, C]`` row indices into the sorted event columns (−1 =
        pad). Fast path: a straight reshape when no client repeats inside
        any aligned group — the fleet-scale regime, where a chunk spans
        far less virtual time than one train period. Fallback: greedy
        chunking that closes the chunk at the first repeated client (the
        pass-A scatter needs each client at most once per chunk)."""
        E = int(client.shape[0])
        S = -(-E // C)
        rows = np.full(S * C, -1, np.int64)
        rows[:E] = np.arange(E)
        gid = np.arange(S * C) // C
        cl = np.where(rows >= 0, client[np.clip(rows, 0, None)], -1)
        o = np.lexsort((cl, gid))
        gs, cs = gid[o], cl[o]
        collide = (gs[1:] == gs[:-1]) & (cs[1:] == cs[:-1]) & (cs[1:] >= 0)
        if not collide.any():
            return rows.reshape(S, C)
        out: List[int] = []
        cur: List[int] = []
        seen: set = set()
        for j in range(E):
            cj = int(client[j])
            if cj in seen or len(cur) == C:
                cur.extend([-1] * (C - len(cur)))
                out.extend(cur)
                cur, seen = [], set()
            cur.append(j)
            seen.add(cj)
        if cur:
            cur.extend([-1] * (C - len(cur)))
            out.extend(cur)
        return np.asarray(out, np.int64).reshape(-1, C)

    @staticmethod
    def _chain_cols(rows: np.ndarray, r_e: np.ndarray, R: int):
        """Per-event chunk-local regional chains: ``prev_r`` links an
        event to the previous same-regional event's chunk offset (−1 =
        none — read the carry), ``last_r`` marks each regional's final
        in-chunk event (whose state the writeback scatters)."""
        S, C = rows.shape
        flat = rows.ravel()
        valid = flat >= 0
        rcol = np.where(valid, r_e[np.clip(flat, 0, None)], R)
        cid = np.repeat(np.arange(S), C)
        off = np.tile(np.arange(C), S)
        o = np.lexsort((off, rcol, cid))
        vv = valid[o]
        same = (
            (cid[o][1:] == cid[o][:-1])
            & (rcol[o][1:] == rcol[o][:-1])
            & vv[1:]
            & vv[:-1]
        )
        prev = np.full(S * C, -1, np.int32)
        prev[o[1:][same]] = off[o[:-1][same]].astype(np.int32)
        last = valid.copy()
        last[o[:-1][same]] = False
        return prev.reshape(S, C), last.reshape(S, C)

    def _task_arrays(self):
        if self._task_cache is None:
            self._task_cache = self.task.arrays(self.n)
        return self._task_cache

    def _grad_losses(self, G: np.ndarray) -> np.ndarray:
        """Eval-set cross-entropy per global version (the gradient task's
        loss curve — the heap twin's custom ``loss_fn`` computes the
        same quantity)."""
        import jax
        import jax.numpy as jnp
        import optax

        from p2pfl_tpu.ops import fleet_kernels as fk

        t = self.task
        _, _, _, xe, ye = self._task_arrays()
        xs, ys = jnp.asarray(xe), jnp.asarray(ye)

        def ce(g):
            lg = fk.grad_logits(t.kind, t.d_in, t.n_out, t.hidden, g, xs)
            return optax.softmax_cross_entropy_with_integer_labels(lg, ys).mean()

        return np.asarray(jax.vmap(ce)(jnp.asarray(G)), np.float64)

    def _chunk_grids(self, fk, jnp, cfg, tiers, ev, clients, agg, rows):
        """Build the ``[S, C]`` chronological event grids + per-regional
        grids from a chunk layout (pads carry trash values that every
        in-kernel gate masks: client=N, PAD keys, live=False)."""
        C = cfg.chunk
        PAD = int(fk.PAD_KEY)
        live = rows >= 0

        def col(vals, pad, dtype):
            grid = np.full(rows.shape, pad, dtype)
            grid[live] = np.asarray(vals)[rows[live]].astype(dtype)
            return jnp.asarray(grid)

        events = {
            "client": col(ev["client"], self.n, np.int32),
            "key_hi": col(ev["key_hi"], PAD, np.int32),
            "key_lo": col(ev["key_lo"], PAD, np.int32),
            "t_adopt": col(ev["t_adopt"], -np.inf, np.float32),
            "t_arr": col(ev["t_arr"], 0.0, np.float32),
            "send_ok": col(ev["send_ok"], False, bool),
            "live": jnp.asarray(live),
        }
        R = cfg.n_regionals
        if cfg.hier:
            r_e = tiers["regional_of"][ev["client"]]
            k_e = tiers["k_reg"][ev["ep"], r_e]
            t_rad = ev["t_arr"] - tiers["reg_adopt"][ev["ep"], r_e].astype(np.float32)
            events["r"] = col(r_e, R, np.int32)
            events["k_r"] = col(k_e, 1, np.int32)
            events["t_radopt"] = col(t_rad, -np.inf, np.float32)
            prev_r, last_r = self._chain_cols(rows, r_e, R)
            events["prev_r"] = jnp.asarray(prev_r)
            events["last_r"] = jnp.asarray(last_r)
        if cfg.byz:
            events["bkind"] = col(ev["bkind"], 0, np.int32)
            events["blam"] = col(ev["blam"], 1.0, np.float32)
            att = ev["bkind"] == 3
            if att.any():
                nz = int(att.sum())
                noise = np.zeros((nz + 1, cfg.dim), np.float32)
                noise[1:] = (
                    np.random.default_rng([self.seed, _STREAM_BYZ])
                    .normal(size=(nz, cfg.dim))
                    .astype(np.float32)
                    * ev["bstd"][att][:, None]
                )
                bn = np.zeros(ev["bkind"].shape[0], np.int64)
                bn[att] = 1 + np.arange(nz)
                events["bnoise"] = col(bn, 0, np.int32)
                clients["noise"] = jnp.asarray(noise)
        reg = {}
        if cfg.hier:

            def pad_row(a, v):
                return np.concatenate(
                    [a, np.full((1,) + a.shape[1:], v, a.dtype)], axis=0
                )

            # one trash row per grid: pad lanes gather r=R harmlessly
            reg = {
                "send_ok": jnp.asarray(pad_row(agg["ok"], True)),
                "jit": jnp.asarray(pad_row(agg["jit"], 0.0)),
                "agg_delay": jnp.asarray(
                    pad_row(tiers["agg_delay"].astype(np.float32), 0.0)
                ),
            }
            if cfg.dup:
                reg["dup"] = jnp.asarray(pad_row(agg["dup"], False))
            if cfg.byz:
                reg["akind"] = jnp.asarray(pad_row(agg["akind"], 0))
                reg["alam"] = jnp.asarray(pad_row(agg["alam"], 1.0))
                reg["agg_noise_idx"] = jnp.asarray(pad_row(agg["agg_noise_idx"], 0))
                reg["agg_noise"] = jnp.asarray(agg["agg_noise"])
        return events, reg

    # ---- the drive ----

    def run(self) -> MegaFleetResult:
        import jax.numpy as jnp

        from p2pfl_tpu.ops import fleet_kernels as fk

        t0 = time.monotonic()
        tiers = self._tier_arrays()
        ev = self._events(tiers)
        unselected = ev.pop("_unselected")
        dropped_wire = ev.pop("_wire_dropped")
        lost = ev.pop("_lost")
        dup_edge = ev.pop("_dup_edge", 0)
        byz_edge = ev.pop("_byz_edge", 0)
        E = int(ev["client"].shape[0])
        plan = self.plan

        # capacity bounds (exact: every flush consumes K distinct
        # accepted events / aggregates; churn shrinks K, never grows it
        # past the epoch-min clamp)
        R = int(tiers["k_reg"].shape[1])
        k_glob = tiers["k_global"]
        if self.hier:
            k_min = np.maximum(tiers["k_reg"].min(axis=0), 1)
            counts = np.bincount(
                tiers["regional_of"][ev["client"]], minlength=R
            )
            per_reg = counts // k_min
            agg_cap = int(per_reg.sum()) + 1
            v_cap = agg_cap // k_glob + 2
            stride = int(per_reg.max(initial=0)) + 2
        else:
            v_cap = E // k_glob + 2
            stride = 2
        use_chunked = (
            self.chunk > 1
            or self.task is not None
            or self.fold != "fedavg"
            or self._byz is not None
            or self._churn is not None
            or (self.hier and plan is not None and plan.default.duplicate > 0.0)
        )
        task = self.task

        C = self.chunk if use_chunked else 1
        cfg = fk.FleetConfig(
            hier=self.hier,
            n_clients=self.n,
            dim=self.dim,
            n_regionals=R,
            k_global=k_glob,
            k_reg_max=int(tiers["k_reg"].max(initial=1)) if self.hier else 1,
            v_cap=max(v_cap, 2),
            alpha=self.alpha,
            server_lr=self.server_lr,
            local_lr=self.local_lr,
            max_staleness=self.max_staleness,
            rate_gap_reg=self.rate_limit_regional,
            rate_gap_glob=self.rate_limit_global,
            hist_bins=self.max_staleness + 2,
            agg_key_stride=stride,
            unroll=self.unroll,
            chunk=C,
            gf_cap=(C // k_glob + 2) if use_chunked else 0,
            fold_kind=self.fold,
            trim=self.trim,
            task=(task.kind if task is not None else "consensus"),
            t_din=(task.d_in if task is not None else 0),
            t_nout=(task.n_out if task is not None else 0),
            t_hidden=(task.hidden if task is not None else 0),
            t_bs=(task.batch if task is not None else 0),
            t_steps=(task.steps if task is not None else 0),
            data_seed=(task.data_seed if task is not None else 0),
            byz=bool("bkind" in ev and use_chunked),
            dup=bool(
                self.hier
                and plan is not None
                and plan.default.duplicate > 0.0
                and use_chunked
            ),
        )
        clients = {
            "targets": jnp.asarray(self.spec.targets, jnp.float32),
            "samples": jnp.asarray(self.spec.num_samples, jnp.float32),
        }
        if task is not None:
            mu, tw, tb, _, _ = self._task_arrays()
            clients["mu"] = jnp.asarray(mu)
            clients["tw"] = jnp.asarray(tw)
            clients["tb"] = jnp.asarray(tb)
        agg = self._agg_grids(tiers, stride)
        init = jnp.asarray(self.spec.init, jnp.float32)
        if use_chunked:
            rows = self._chunk_layout(ev["client"], cfg.chunk)
            events, reg = self._chunk_grids(fk, jnp, cfg, tiers, ev, clients, agg, rows)
            out = fk.run_fleet_program_chunked(cfg, events, clients, reg, init)
        else:
            events = {
                "client": jnp.asarray(ev["client"]),
                "key_hi": jnp.asarray(ev["key_hi"]),
                "key_lo": jnp.asarray(ev["key_lo"]),
                "t_train": jnp.asarray(ev["t_train"]),
                "t_arr": jnp.asarray(ev["t_arr"]),
                "send_ok": jnp.asarray(ev["send_ok"]),
            }
            clients["adopt_delay"] = jnp.asarray(
                tiers["adopt_delay"][0], jnp.float32
            )
            clients["regional_of"] = jnp.asarray(tiers["regional_of"])
            reg = {
                "k": jnp.asarray(tiers["k_reg"][0]),
                "adopt_delay": jnp.asarray(tiers["reg_adopt"][0], jnp.float32),
                "agg_delay": jnp.asarray(tiers["agg_delay"], jnp.float32),
                "send_ok": jnp.asarray(agg["ok"]),
                "jit": jnp.asarray(agg["jit"]),
            }
            out = fk.run_fleet_program(cfg, events, clients, reg, init)

        version = int(out["version"])
        G = np.asarray(out["G"][: version + 1])
        mint = np.asarray(out["mint"][:version], np.float64)
        if task is not None:
            losses = self._grad_losses(G)
        else:
            t_mean = self.spec.target_mean()
            diffs = G - t_mean[None, :]
            losses = (diffs * diffs).sum(axis=1).astype(np.float64)
        curve = [(float(mint[v - 1]), v, float(losses[v])) for v in range(1, version + 1)]
        ttt = next(
            (t for t, _v, loss in curve if loss <= self.target_loss), None
        )
        wall = time.monotonic() - t0
        res = MegaFleetResult(
            params={"w": G[version].copy()},
            version=version,
            virtual_time=float(ev["t_arr"][-1]) if E else 0.0,
            time_to_target=ttt,
            loss_curve=curve,
            updates_sent=E,
            updates_delivered=E - dropped_wire - lost,
            # the heap's counter includes dropped regional→root aggregates
            updates_dropped_wire=dropped_wire + int(out.get("agg_drop", 0)),
            duplicates_injected=dup_edge + int(out.get("dup_agg", 0)),
            byz_corrupted=byz_edge + int(out.get("byz_agg", 0)),
            merges=int(out["merges"]),
            regional_merges=int(out.get("rmerges", 0)),
            buffered=int(np.asarray(out["hist_edge"]).sum()),
            stale_dropped=int(out["stale_edge"]) + int(out["stale_agg"]),
            rate_limited=int(out["rate_edge"]) + int(out["rate_agg"]),
            unselected=unselected,
            staleness_hist_edge=[int(x) for x in np.asarray(out["hist_edge"])],
            staleness_hist_global=[int(x) for x in np.asarray(out["hist_glob"])],
            n_events=E,
            wall_s=wall,
            clients_per_sec=self.n / wall if wall > 0 else 0.0,
        )
        if self._churn is not None:
            res.joined = list(self._churn["joined"])
            res.left = list(self._churn["left"])
            res.failovers = int(self._churn["failovers"])
        if plan is not None:
            # heap parity: only crashes that actually FIRE are recorded —
            # a round_no past the schedule never enters AsyncTrainStage
            res.crashed = [
                a
                for a, s in plan.crashes.items()
                if a in self._addr_idx
                and s.stage == "AsyncTrainStage"
                and (s.round_no or 0) < self.updates_per_node
            ]
        return res
