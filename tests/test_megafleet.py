"""Megafleet: heap-vs-vectorized parity, bit-exact replay, fleet knobs.

The contract under test (ISSUE 15 acceptance): at 1k nodes on the
consensus task the vectorized engine reproduces the heap driver's merge
count and monotone version sequence EXACTLY, with the loss trajectory
inside a documented tolerance (flat: float-reassociation level — the
heap weights in Python f64 where the scan weights in f32; hierarchical:
the aggregate-interleaving tolerance, a few percent mid-waterfall,
<1e-2 relative at the tail); a run replays bit-exact from
``(seed, plan)``; a different seed diverges; and the Bonawitz knobs
(pace steering, selection, per-tier rate limits) have measurable,
deterministic effects.
"""

import numpy as np
import pytest

from p2pfl_tpu.communication.faults import (
    ByzantineSpec,
    CrashSpec,
    EdgeFault,
    FaultPlan,
    JoinSpec,
    LeaveSpec,
)
from p2pfl_tpu.federation.megafleet import FleetSpec, GradTask, MegaFleet
from p2pfl_tpu.federation.simfleet import SimulatedAsyncFleet

SEED = 1905


def _curves(res):
    t = np.asarray([x[0] for x in res.loss_curve])
    v = [x[1] for x in res.loss_curve]
    l = np.asarray([x[2] for x in res.loss_curve])
    return t, v, l


def _pair(n, cluster_size, **kw):
    """The same fleet through both drivers (export_spec parity hook)."""
    fleet = SimulatedAsyncFleet(
        n, seed=SEED, cluster_size=cluster_size, updates_per_node=4,
        slow_frac=0.1, local_lr=0.7, **kw,
    )
    spec = FleetSpec.from_sim(fleet)
    assert spec.link_delay == fleet.link_delay  # from_sim carries the clock
    mega = MegaFleet(
        spec, cluster_size=cluster_size, updates_per_node=4, local_lr=0.7, **kw,
    )
    assert mega.link_delay == fleet.link_delay
    return fleet.run(), mega.run()


# ---- kernel parity with the live buffer math ----


def test_staleness_weight_arr_matches_scalar():
    from p2pfl_tpu.federation.staleness import staleness_weight
    from p2pfl_tpu.ops.fleet_kernels import staleness_weight_arr

    taus = np.asarray([-3, 0, 1, 2, 7, 16, 100], np.int32)
    for alpha in (0.0, 0.5, 1.0, 2.0):
        arr = np.asarray(staleness_weight_arr(np.asarray(taus), alpha))
        ref = np.asarray(
            [staleness_weight(t, alpha) for t in taus], np.float32
        )
        np.testing.assert_allclose(arr, ref, rtol=1e-6)


def test_fold_window_matches_buffered_aggregator():
    """fold_window IS the live flush: same (origin,seq) sort, same
    fedavg/server_merge kernels — bit-identical on a real buffer, pad
    slots (weight 0, key PAD) folding as exact no-ops."""
    import jax.numpy as jnp

    from p2pfl_tpu.federation.buffer import BufferedAggregator
    from p2pfl_tpu.learning.weights import ModelUpdate
    from p2pfl_tpu.ops.fleet_kernels import PAD_KEY, fold_window

    dim, k = 8, 3
    rng = np.random.default_rng(7)
    init = rng.normal(size=dim).astype(np.float32)
    buf = BufferedAggregator(
        "t", {"p": init.copy()}, k=k, alpha=0.5, server_lr=0.7,
    )
    rows, weights, keys = [], [], []
    res = None
    # deliberately unsorted origins: the flush must sort, and so must we
    for j, (origin, samples) in enumerate([("b", 2), ("a", 5), ("c", 1)]):
        params = {"p": rng.normal(size=dim).astype(np.float32)}
        upd = ModelUpdate(params, [origin], samples)
        upd.version = (origin, 1, 0)  # τ = 0 everywhere: weight = samples
        rows.append(params["p"])
        weights.append(float(samples))
        keys.append(ord(origin))
        res = buf.offer(upd)
    assert res is not None and res.version == 1
    # pad to a wider window: zero weight + PAD_KEY must change nothing
    pad = 2
    rows = np.stack(rows + [np.zeros(dim, np.float32)] * pad)
    weights = np.asarray(weights + [0.0] * pad, np.float32)
    keys = np.asarray(keys + [int(PAD_KEY)] * pad, np.int32)
    out = np.asarray(
        fold_window(
            jnp.asarray(rows), jnp.asarray(weights), jnp.asarray(keys),
            jnp.asarray(init), 0.7,
        )
    )
    np.testing.assert_array_equal(out, np.asarray(res.params["p"]))


# ---- the 1k heap-parity anchor ----


def test_flat_parity_1k():
    heap, mega = _pair(1000, 0)
    assert mega.merges == heap.merges
    ht, hv, hl = _curves(heap)
    mt, mv, ml = _curves(mega)
    assert mv == hv  # monotone version sequence, exactly the heap's
    assert mv == sorted(mv) and len(set(mv)) == len(mv)
    # mint times agree to f32 time resolution; losses to reassociation
    # tolerance (measured 2e-7 relative — pinned with margin)
    np.testing.assert_allclose(mt, ht, atol=1e-4)
    np.testing.assert_allclose(ml, hl, rtol=0, atol=float(hl.max()) * 1e-5)
    assert abs(mega.final_loss() - heap.final_loss()) <= 1e-5 * heap.final_loss()
    np.testing.assert_allclose(
        np.asarray(mega.params["w"]), np.asarray(heap.params["w"]),
        rtol=0, atol=1e-5,
    )


def test_hier_parity_1k():
    heap, mega = _pair(1000, 32)
    assert mega.merges == heap.merges
    ht, hv, hl = _curves(heap)
    mt, mv, ml = _curves(mega)
    assert mv == hv
    assert mv == sorted(mv) and len(set(mv)) == len(mv)
    # the documented hierarchical tolerance: aggregate arrivals may
    # interleave differently within one link_delay in-flight window, so
    # mid-waterfall losses differ at the few-percent level while the
    # tail converges (measured: maxrel 0.09 mid-curve, 3.5e-4 final)
    np.testing.assert_allclose(ml, hl, rtol=0, atol=float(hl.max()) * 0.15)
    assert (
        abs(mega.final_loss() - heap.final_loss())
        <= 1e-2 * max(heap.final_loss(), 1e-9)
    )


def test_hier_parity_is_exact_under_wide_staleness_bound():
    """With the staleness bound too wide for boundary reorderings to
    flip an admission, hier merge counts stay exact at default settings
    too — this pins that the counts do not depend on the bound."""
    heap, mega = _pair(300, 16, max_staleness=10**6)
    assert mega.merges == heap.merges
    assert [x[1] for x in mega.loss_curve] == [x[1] for x in heap.loss_curve]


# ---- replay determinism ----


def test_replay_bit_exact_and_seed_divergence():
    plan = FaultPlan(seed=SEED, default=EdgeFault(drop=0.05, jitter=0.002))
    spec = FleetSpec.synth(2000, seed=SEED, slow_frac=0.1)

    def drive(s):
        return MegaFleet(
            s, cluster_size=64, k=8, updates_per_node=4, local_lr=0.7,
            plan=plan,
        ).run()

    a, b = drive(spec), drive(spec)
    assert a.merges == b.merges
    assert a.loss_curve == b.loss_curve  # float-equal: bit-exact replay
    assert a.updates_dropped_wire == b.updates_dropped_wire > 0
    assert a.staleness_hist_edge == b.staleness_hist_edge
    np.testing.assert_array_equal(a.params["w"], b.params["w"])

    c = drive(FleetSpec.synth(2000, seed=SEED + 1, slow_frac=0.1))
    assert c.loss_curve != a.loss_curve  # a different seed must diverge


def test_fault_plan_mapping():
    spec = FleetSpec.synth(400, seed=SEED)
    crash = {
        "sim-0007": CrashSpec(stage="AsyncTrainStage", round_no=2),
        "sim-0011": CrashSpec(stage="TrainStage", round_no=1),  # sync: inert
        # past the schedule: never enters AsyncTrainStage, never fires
        "sim-0013": CrashSpec(stage="AsyncTrainStage", round_no=9),
    }
    plan = FaultPlan(seed=SEED, default=EdgeFault(drop=0.1), crashes=crash)
    res = MegaFleet(
        spec, cluster_size=0, k=8, updates_per_node=4, plan=plan
    ).run()
    # the async-stage victim stops after 2 of 4 updates; the sync-stage
    # spec never fires (heap semantics); drops hit the counter
    assert res.n_events == 400 * 4 - 2
    assert res.updates_dropped_wire > 0
    assert res.crashed == ["sim-0007"]

    # the full vectorized fault algebra CONSTRUCTS (byzantine payload
    # kinds, duplicates, churn — each runs through counter grids now)...
    n = spec.n
    for good in (
        FaultPlan(seed=SEED, default=EdgeFault(duplicate=0.5)),
        FaultPlan(
            seed=SEED, byzantine={"sim-0002": ByzantineSpec(kind="sign_flip")}
        ),
        FaultPlan(seed=SEED, joins={f"sim-{n - 1:04d}": JoinSpec(at_s=3.0)}),
        FaultPlan(seed=SEED, leaves={"sim-0005": LeaveSpec(at_s=2.0)}),
    ):
        MegaFleet(spec, plan=good)

    # ...while per-edge overrides, pairwise cuts, stateful attacker
    # kinds and the stateful churn combinations still route to the heap
    with pytest.raises(ValueError, match="per-edge"):
        MegaFleet(spec, plan=FaultPlan(seed=SEED, edges={("a", "b"): EdgeFault(drop=1.0)}))
    with pytest.raises(ValueError, match="heap driver"):
        MegaFleet(
            spec, plan=FaultPlan(seed=SEED, partitions=[("sim-0001", "sim-0002")])
        )
    with pytest.raises(ValueError, match="heap driver"):
        MegaFleet(
            spec,
            plan=FaultPlan(
                seed=SEED, byzantine={"sim-0002": ByzantineSpec(kind="equivocate")}
            ),
        )
    churny = dict(joins={f"sim-{n - 1:04d}": JoinSpec(at_s=3.0)})
    with pytest.raises(ValueError, match="heap driver"):
        MegaFleet(
            spec,
            plan=FaultPlan(
                seed=SEED,
                byzantine={"sim-0002": ByzantineSpec(kind="sign_flip")},
                **churny,
            ),
        )
    with pytest.raises(ValueError, match="heap driver"):
        MegaFleet(spec, plan=FaultPlan(seed=SEED, **churny), fold="median")
    with pytest.raises(ValueError, match="heap driver"):
        MegaFleet(
            spec,
            plan=FaultPlan(seed=SEED, slow_nodes={"sim-0003": 5.0}, **churny),
        )
    with pytest.raises(ValueError, match="heap driver"):
        MegaFleet(spec, fold="krum-screen")


def test_slow_nodes_apply_on_synth_specs():
    """plan.slow_nodes must reach the vectorized engine even when the
    spec doesn't carry them (synth exports zeros) — and fold
    idempotently (by max) when it does (export_spec already folded the
    same plan)."""
    spec = FleetSpec.synth(200, seed=SEED)
    plan = FaultPlan(seed=SEED, slow_nodes={"sim-0001": 5.0, "sim-0003": 2.0})
    base = MegaFleet(spec, cluster_size=16, k=4, local_lr=0.7).run()
    slowed = MegaFleet(spec, cluster_size=16, k=4, local_lr=0.7, plan=plan).run()
    assert slowed.loss_curve != base.loss_curve
    again = MegaFleet(spec, cluster_size=16, k=4, local_lr=0.7, plan=plan).run()
    assert again.loss_curve == slowed.loss_curve


def test_aggregate_sends_see_the_fault_plan():
    """With every client its own regional (cluster_size=1), client
    self-offers bypass the wire and ALL traffic is regional→root
    aggregate sends — the heap routes that hop through _edge_verdict,
    so the scan's drop verdicts must reach it too."""
    spec = FleetSpec.synth(64, seed=SEED)
    plan = FaultPlan(seed=SEED, default=EdgeFault(drop=0.5))
    base = MegaFleet(spec, cluster_size=1, k=4, local_lr=0.7).run()
    res = MegaFleet(spec, cluster_size=1, k=4, local_lr=0.7, plan=plan).run()
    assert res.updates_dropped_wire > 0  # aggregate drops, not client ones
    assert res.merges < base.merges
    again = MegaFleet(spec, cluster_size=1, k=4, local_lr=0.7, plan=plan).run()
    assert again.loss_curve == res.loss_curve  # still replay-exact


def test_fault_verdicts_survive_zero_link_delay():
    """The src==dst bypass keys on the regional mask, not on a delay
    value — at link_delay=0 every hop collapses to 0 but edge sends must
    still see the plan's drop verdicts."""
    spec = FleetSpec.synth(300, seed=SEED)
    plan = FaultPlan(seed=SEED, default=EdgeFault(drop=0.5))
    res = MegaFleet(
        spec, cluster_size=16, k=4, local_lr=0.7, link_delay=0.0, plan=plan
    ).run()
    assert res.updates_dropped_wire > 0


# ---- the Bonawitz fleet knobs ----


def test_pace_steering_spreads_the_first_wave():
    spec = FleetSpec.synth(2000, seed=SEED)
    base = MegaFleet(spec, cluster_size=64, k=8, local_lr=0.7).run()
    paced = MegaFleet(
        spec, cluster_size=64, k=8, local_lr=0.7, pace_window=1.0
    ).run()
    # same work, staggered: the first mint lands later, the run is
    # deterministic, and the staleness profile shifts measurably
    assert paced.merges > 0
    assert paced.loss_curve[0][0] > base.loss_curve[0][0]
    assert paced.staleness_hist_edge != base.staleness_hist_edge
    again = MegaFleet(
        spec, cluster_size=64, k=8, local_lr=0.7, pace_window=1.0
    ).run()
    assert again.loss_curve == paced.loss_curve


def test_selection_over_provisioning_gate():
    spec = FleetSpec.synth(2000, seed=SEED)
    full = MegaFleet(spec, cluster_size=64, k=8, local_lr=0.7).run()
    half = MegaFleet(
        spec, cluster_size=64, k=8, local_lr=0.7, select_frac=0.5
    ).run()
    assert half.unselected > 0
    assert half.n_events < full.n_events
    assert half.merges < full.merges
    # unselected slots idle the device: nothing else may shift
    assert half.rate_limited == 0 and half.updates_dropped_wire == 0


def test_per_tier_rate_limit():
    spec = FleetSpec.synth(2000, seed=SEED)
    free = MegaFleet(spec, cluster_size=64, k=8, local_lr=0.7).run()
    limited = MegaFleet(
        spec, cluster_size=64, k=8, local_lr=0.7,
        rate_limit_regional=0.05, rate_limit_global=0.05,
    ).run()
    assert limited.rate_limited > 0
    assert limited.merges < free.merges
    assert limited.buffered < free.buffered


# ---- scale + structure smoke ----


def test_scale_smoke_20k():
    """A 20k-client hierarchical drive: structure invariants at a scale
    the heap cannot reach in test time (the 1M row lives in
    BENCH_ASYNC; this pins the same engine path at CI cost)."""
    spec = FleetSpec.synth(20_000, seed=SEED, slow_frac=0.1)
    res = MegaFleet(
        spec, cluster_size=512, k=32, updates_per_node=4, local_lr=0.7
    ).run()
    t, v, l = _curves(res)
    assert res.merges == res.version == v[-1]
    assert v == sorted(v) and len(set(v)) == len(v)
    assert np.all(np.diff(t) >= 0)  # mint times monotone
    assert l[-1] < l[0] * 0.05  # the fleet actually converges
    assert res.regional_merges > res.merges
    # every regional flush consumed exactly K=32 admitted contributions;
    # anything left over is an unflushed partial window per regional
    n_regionals = len(MegaFleet(spec, cluster_size=512, k=32).router.regionals)
    assert 32 * res.regional_merges <= res.buffered
    assert res.buffered < 32 * res.regional_merges + 32 * n_regionals
    assert res.clients_per_sec > 0


# ---- satellites: copy-on-write + the parity hook ----


def test_simfleet_copy_on_write_aliases_deliveries():
    """Pass-through sites alias: two edges that adopted the same global
    hold the SAME tree object (pre-CoW every delivery deep-copied), and
    the final result aliases the root buffer's params."""
    fleet = SimulatedAsyncFleet(
        8, seed=3, cluster_size=0, updates_per_node=3, local_lr=0.7
    )
    res = fleet.run()
    root = fleet.router.root
    edges = [
        a for a, n in fleet.nodes.items()
        if a != root and n.global_params is not None and n.known_version == res.version
    ]
    assert len(edges) >= 2
    first = fleet.nodes[edges[0]].global_params
    assert all(fleet.nodes[a].global_params is first for a in edges[1:])
    assert res.params is fleet._buffers[root]["global"].snapshot()[0]


def test_export_spec_matches_population():
    fleet = SimulatedAsyncFleet(
        32, seed=SEED, cluster_size=8, updates_per_node=2, slow_frac=0.25
    )
    spec = fleet.export_spec()
    addrs = sorted(fleet.nodes)
    assert spec["durations"].shape == (32,)
    for j, a in enumerate(addrs):
        assert spec["durations"][j] == fleet.nodes[a].duration
        assert spec["num_samples"][j] == fleet.nodes[a].num_samples
    np.testing.assert_array_equal(
        spec["targets"][5], fleet._target(fleet.nodes[addrs[5]].idx)
    )
    fleet._init = {"w": np.zeros(4, np.float32), "b": np.zeros(2, np.float32)}
    with pytest.raises(ValueError, match="consensus-task layout"):
        fleet.export_spec()

    custom = SimulatedAsyncFleet(
        8, seed=SEED, cluster_size=0, train_fn=lambda i, p, r: p
    )
    with pytest.raises(ValueError, match="no vectorized twin"):
        custom.export_spec()

    big = SimulatedAsyncFleet(10_001, seed=SEED, cluster_size=32)
    with pytest.raises(ValueError, match="4-digit address"):
        big.export_spec()


# ---- the chunked engine (ISSUE 16): bit-identity, fold keys, faults ----


def _assert_bit_identical(ref, got):
    """Counters EXACT, loss curve and final params BITWISE equal."""
    assert got.merges == ref.merges and got.version == ref.version
    assert got.regional_merges == ref.regional_merges
    assert got.stale_dropped == ref.stale_dropped
    assert got.loss_curve == ref.loss_curve
    np.testing.assert_array_equal(got.params["w"], ref.params["w"])


@pytest.mark.parametrize(
    "cluster,chunk",
    [(0, 7), (0, 48), (0, 50), (0, 256), (32, 48)],
    ids=["flat-7", "flat-48", "flat-50", "flat-256", "hier-48"],
)
def test_chunked_engine_bit_identical_to_per_event(cluster, chunk):
    """The chunked engine's batched gather → segment-fold → predicated
    scatter decomposition must change NOTHING: flat results are
    bit-identical to the per-event reference scan at a chunk size that
    divides the 2000 events (50: no pad lane anywhere) and at sizes
    that leave a masked tail (7, 48, 256: 2, 16 and 48 pad lanes), and
    the hierarchical engine matches bitwise too on this geometry."""
    spec = FleetSpec.synth(500, seed=SEED, dim=8)

    def run(c):
        return MegaFleet(
            spec, cluster_size=cluster, k=8, updates_per_node=4,
            local_lr=0.7, chunk=c,
        ).run()

    _assert_bit_identical(run(1), run(chunk))


@pytest.mark.parametrize("chunk", [48, 64])
def test_chunked_greedy_fallback_layout(chunk):
    """A tiny fleet with several updates each: 40 clients cannot fill a
    chunk of 48 or 64 without one of them repeating, so the aligned
    reshape of ``_chunk_layout`` is rejected and the greedy layout —
    which closes a chunk at the first repeated client — must feed the
    chunked engine the same run the per-event reference scans."""
    spec = FleetSpec.synth(40, seed=SEED, dim=6)

    def fleet(c):
        return MegaFleet(spec, k=4, updates_per_node=3, chunk=c)

    mf = fleet(chunk)
    ev = mf._events(mf._tier_arrays())
    rows = mf._chunk_layout(ev["client"], chunk)
    # the fast path pads only the tail of the LAST row; a pad in an
    # earlier row is a chunk the greedy path closed early
    assert (rows[:-1] == -1).any(), "layout took the aligned fast path"
    for row in rows:  # and what it closed on: no client twice per chunk
        cl = ev["client"][row[row >= 0]]
        assert len(set(cl.tolist())) == len(cl)
    assert sorted(rows[rows >= 0].tolist()) == list(range(len(ev["client"])))
    _assert_bit_identical(fleet(1).run(), mf.run())


@pytest.mark.parametrize("chunk", [0, -1, "auto"])
def test_chunk_below_one_is_refused(chunk):
    """``chunk`` is events per scan step. 0 and "auto" once meant
    "measure candidates and write the winner under ~/.cache"; a value
    from outside the program must not pick an engine by accident."""
    spec = FleetSpec.synth(10, seed=SEED, dim=4)
    with pytest.raises(ValueError, match="chunk"):
        MegaFleet(spec, chunk=chunk)


def test_fold_key_two_word_order_at_int32_boundary():
    """Regression for the retired product fold key ``ii*(M+1)+mm+1``:
    past ``n·(M+1) > 2^31`` it overflowed int32 (the engine used to
    REFUSE such populations). The two-word ``(key_hi, key_lo)`` lexsort
    must reproduce the heap's (origin, seq) tuple order verbatim at
    indices where the product formula wraps negative."""
    import jax.numpy as jnp

    from p2pfl_tpu.ops.fleet_kernels import fold_window

    dim, M = 4, 4
    # client indices deep in the would-overflow regime: ii*(M+1)+mm+1
    # exceeds int32 for every row here
    his = np.asarray(
        [2**31 - 2, 2**30 + 5, 2**31 - 2, 2**30 + 5, 2**29], np.int64
    )
    los = np.asarray([3, 1, 1, 2, 4], np.int64)
    assert ((his * (M + 1) + los) > np.iinfo(np.int32).max).all()
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(5, dim)).astype(np.float32)
    weights = rng.uniform(1, 2, size=5).astype(np.float32)
    prev = np.zeros(dim, np.float32)

    out = np.asarray(
        fold_window(
            jnp.asarray(rows), jnp.asarray(weights),
            jnp.asarray(los.astype(np.int32)), jnp.asarray(prev), 0.7,
            keys_hi=jnp.asarray((his - 2**31).astype(np.int32)),
        )
    )
    # reference: fold in the heap's tuple order via small rank-compressed
    # keys (tuple order is all the fold may depend on)
    order = sorted(range(5), key=lambda j: (his[j], los[j]))
    ranks = np.empty(5, np.int32)
    ranks[order] = np.arange(5, dtype=np.int32)
    ref = np.asarray(
        fold_window(
            jnp.asarray(rows), jnp.asarray(weights), jnp.asarray(ranks),
            jnp.asarray(prev), 0.7,
        )
    )
    np.testing.assert_array_equal(out, ref)


def test_megafleet_accepts_overflow_scale_key_space():
    """The engine itself must not refuse populations whose
    ``n × (updates+1)`` product passes int32 (the old raise at
    megafleet.py) — key words stay per-field int32 regardless of n."""
    spec = FleetSpec.synth(500, seed=SEED, dim=4)
    mf = MegaFleet(spec, cluster_size=0, k=8, updates_per_node=4)
    # simulated: the old product key for the LAST event of a 600M-client
    # fleet would overflow; the two-word key never multiplies
    n_huge, M = 600_000_000, 4
    assert n_huge * (M + 1) > np.iinfo(np.int32).max
    assert mf.run().version > 0  # and the real engine runs unchanged


def test_byzantine_parity_1k():
    """Deterministic corruption kinds (sign_flip / scale) at the edge
    seam: the vectorized payload transforms must reproduce the heap's
    byz_corrupt_update runs exactly — corruption counts and merge
    decisions EXACT, losses at reassociation tolerance."""
    byz = {
        "sim-0003": ByzantineSpec(kind="sign_flip"),
        "sim-0007": ByzantineSpec(kind="sign_flip"),
        "sim-0011": ByzantineSpec(kind="scale", lam=25.0),
    }
    heap, mega = _pair(1000, 0, plan=FaultPlan(seed=SEED, byzantine=byz))
    assert mega.byz_corrupted == heap.byz_corrupted > 0
    assert mega.merges == heap.merges
    _, hv, hl = _curves(heap)
    _, mv, ml = _curves(mega)
    assert mv == hv
    np.testing.assert_allclose(ml, hl, rtol=0, atol=float(hl.max()) * 1e-5)


def test_byzantine_noise_parity_1k():
    """The noise kind draws from driver-specific streams, so payloads
    differ — but admission never reads the payload: corruption counts
    and merge decisions stay EXACT, and both drivers land on the same
    fixed point (the zero-mean noise washes out of the tail)."""
    byz = {
        "sim-0003": ByzantineSpec(kind="noise", noise_std=5.0),
        "sim-0007": ByzantineSpec(kind="noise", noise_std=5.0),
    }
    heap, mega = _pair(1000, 0, plan=FaultPlan(seed=SEED, byzantine=byz))
    assert mega.byz_corrupted == heap.byz_corrupted > 0
    assert mega.merges == heap.merges
    assert [x[1] for x in mega.loss_curve] == [x[1] for x in heap.loss_curve]
    assert (
        abs(mega.final_loss() - heap.final_loss())
        <= 5e-2 * max(heap.final_loss(), 1e-9)
    )


def test_byzantine_hier_aggregate_seam():
    """An ATTACKER ELECTED REGIONAL corrupts its regional→root aggregate
    sends (the heap routes those through the same byz_corrupt_update
    seam); honest self-offers stay honest. Counts exact, tail within the
    hier tolerance."""
    byz = {
        "sim-0000": ByzantineSpec(kind="sign_flip"),  # elected regional
        "sim-0030": ByzantineSpec(kind="sign_flip"),
        "sim-0055": ByzantineSpec(kind="sign_flip"),
    }
    heap, mega = _pair(200, 25, plan=FaultPlan(seed=SEED, byzantine=byz))
    assert mega.byz_corrupted == heap.byz_corrupted > 0
    assert mega.merges == heap.merges
    assert (
        abs(mega.final_loss() - heap.final_loss())
        <= 1e-2 * max(heap.final_loss(), 1e-9)
    )


def test_robust_folds_parity_and_defense_1k():
    """The window fold swapped to buffered_robust_merge's trimmed-mean /
    median under a 10% scale-attacker population: parity with the heap
    (which flushes through Settings.ASYNC_ROBUST_AGG) stays at
    reassociation tolerance, and median actually DEFENDS — its final
    loss beats fedavg's under the same attack."""
    from p2pfl_tpu.settings import Settings

    byz = {
        f"sim-{i:04d}": ByzantineSpec(kind="scale", lam=50.0)
        for i in range(0, 1000, 10)
    }
    plan = FaultPlan(seed=SEED, byzantine=byz)
    finals = {}
    try:
        for fold in ("fedavg", "trimmed-mean", "median"):
            Settings.ASYNC_ROBUST_AGG = fold
            heap, mega = _pair(1000, 0, plan=plan)
            assert mega.merges == heap.merges
            _, hv, hl = _curves(heap)
            _, mv, ml = _curves(mega)
            assert mv == hv
            np.testing.assert_allclose(
                ml, hl, rtol=0, atol=float(hl.max()) * 1e-5
            )
            finals[fold] = mega.final_loss()
    finally:
        Settings.ASYNC_ROBUST_AGG = "fedavg"
    assert finals["median"] < finals["fedavg"]
    assert finals["trimmed-mean"] < finals["fedavg"]


def test_duplicates_are_counted_noops_1k():
    """default.duplicate injects replayed (origin, seq) triples; the
    version vector dedups every one, so a duplicate plan must be
    RESULT-INVARIANT in both drivers while the injection counters
    record the chaos actually exercised."""
    plan = FaultPlan(seed=SEED, default=EdgeFault(duplicate=0.3))
    h0, m0 = _pair(1000, 0)
    h1, m1 = _pair(1000, 0, plan=plan)
    assert h1.duplicates_injected > 0 and m1.duplicates_injected > 0
    assert h1.merges == h0.merges and m1.merges == m0.merges
    assert h1.loss_curve == h0.loss_curve
    assert m1.loss_curve == m0.loss_curve
    np.testing.assert_array_equal(m1.params["w"], m0.params["w"])


def test_duplicates_hit_the_aggregate_seam():
    """Hierarchical: the regional→root hop runs the same duplicate
    verdicts (per-(regional, up_seq) grid) — counted, still no-ops."""
    plan = FaultPlan(seed=SEED, default=EdgeFault(duplicate=0.5))
    h0, m0 = _pair(300, 16)
    h1, m1 = _pair(300, 16, plan=plan)
    assert h1.duplicates_injected > 0 and m1.duplicates_injected > 0
    assert m1.merges == m0.merges and m1.loss_curve == m0.loss_curve
    assert h1.merges == h0.merges and h1.loss_curve == h0.loss_curve


def _churn_pair(n, cluster, plan, extra, dim=16, **kw):
    fleet = SimulatedAsyncFleet(
        n, seed=SEED, cluster_size=cluster, updates_per_node=4,
        local_lr=0.7, plan=plan, dim=dim, **kw,
    )
    spec = FleetSpec.from_sim(fleet, extra=extra)  # BEFORE run: joiners pend
    return fleet.run(), MegaFleet(
        spec, cluster_size=cluster, updates_per_node=4, local_lr=0.7,
        plan=plan, **kw,
    ).run()


def test_churn_parity_1k():
    """joins/leaves as time-indexed liveness with TierRouter re-derived
    at every membership boundary: joined/left rosters EXACT, failovers
    EXACT, merge count and version sequence EXACT on this geometry
    (non-regional leavers), loss tail inside the churn tolerance
    (documented divergences: joiner bootstrap adoption, in-flight loss
    at a leaver)."""
    n = 1000
    joins = {
        f"sim-{i:04d}": JoinSpec(at_s=2.0 + 0.1 * (i - n))
        for i in range(n, n + 8)
    }
    leaves = {
        "sim-0005": LeaveSpec(at_s=2.5, graceful=True),
        "sim-0033": LeaveSpec(at_s=3.0, graceful=False),
    }
    plan = FaultPlan(seed=SEED, joins=joins, leaves=leaves)
    heap, mega = _churn_pair(n, 32, plan, extra=8)
    assert mega.joined == heap.joined
    assert mega.left == heap.left
    assert mega.failovers == heap.failovers
    assert mega.merges == heap.merges
    assert [x[1] for x in mega.loss_curve] == [x[1] for x in heap.loss_curve]
    assert (
        abs(mega.final_loss() - heap.final_loss())
        <= 5e-2 * max(heap.final_loss(), 1e-9)
    )


def test_churn_root_failover_parity():
    """The global root leaving gracefully: both drivers re-elect (ONE
    failover) and mint the same number of globals. The heap additionally
    hands the in-flight global buffer to the successor — a documented
    divergence in the merge COUNTER, not the version sequence."""
    plan = FaultPlan(
        seed=SEED, leaves={"sim-0000": LeaveSpec(at_s=2.2, graceful=True)}
    )
    heap, mega = _churn_pair(200, 25, plan, extra=0, k=4, dim=8)
    assert mega.failovers == heap.failovers == 1
    assert mega.left == heap.left == ["sim-0000"]
    assert mega.version == heap.version
    assert (
        abs(mega.final_loss() - heap.final_loss())
        <= 0.2 * max(heap.final_loss(), 1e-9)
    )


def test_churn_flat_parity():
    """Flat topology churn (joiners stream into the single buffer):
    merges and version sequence EXACT."""
    n = 300
    joins = {
        f"sim-{i:04d}": JoinSpec(at_s=1.5 + 0.2 * (i - n))
        for i in range(n, n + 5)
    }
    plan = FaultPlan(seed=SEED, joins=joins)
    heap, mega = _churn_pair(n, 0, plan, extra=5)
    assert mega.joined == heap.joined
    assert mega.merges == heap.merges
    assert [x[1] for x in mega.loss_curve] == [x[1] for x in heap.loss_curve]


# ---- the vmapped real-gradient learner (GradTask) ----


def test_grad_train_one_matches_jax_learner_epoch():
    """fk.make_grad_fns' train_one IS JaxLearner's epoch math: the same
    scan of SGD steps train_epoch compiles (optax.sgd + apply_updates on
    a Dense stack), here on the flat parameter layout. Bit-close on the
    same seeded batches."""
    import flax.linen as nn
    import jax.numpy as jnp

    from p2pfl_tpu.learning.learner import sgd, train_epoch
    from p2pfl_tpu.ops import fleet_kernels as fk

    din, nout, bs, steps, lr = 6, 3, 4, 3, 0.5

    class _Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(nout)(x)

    gen_batch, train_one, _ = fk.make_grad_fns(
        "linear", din, nout, 0, bs, steps, lr, data_seed=5
    )
    task = GradTask(kind="linear", d_in=din, n_out=nout, batch=bs,
                    steps=steps, data_seed=5)
    mu, tw, tb, _, _ = task.arrays(1)
    xs, ys = gen_batch(0, 1, jnp.asarray(mu[0]), jnp.asarray(tw), jnp.asarray(tb))

    rng = np.random.default_rng(11)
    w0 = rng.normal(size=(din, nout)).astype(np.float32)
    b0 = rng.normal(size=nout).astype(np.float32)
    flat0 = jnp.asarray(np.concatenate([w0.ravel(), b0]))
    out_flat = np.asarray(train_one(flat0, xs, ys))

    module = _Lin()
    params = {"Dense_0": {"kernel": jnp.asarray(w0), "bias": jnp.asarray(b0)}}
    tx = sgd(lr)
    params, _, _ = train_epoch(params, tx.init(params), xs, ys, module, tx)
    ref = np.concatenate([
        np.asarray(params["Dense_0"]["kernel"]).ravel(),
        np.asarray(params["Dense_0"]["bias"]),
    ])
    np.testing.assert_allclose(out_flat, ref, atol=1e-6)


def test_grad_task_single_client_chunked_trajectory():
    """One client, K=1, server_lr=1, α=0: every mint IS the client's
    next local round, so the chunked engine's G trajectory must follow
    the sequential train_one chain on the same counter-keyed batches
    (1-based round == the fold key's key_lo)."""
    import jax.numpy as jnp

    from p2pfl_tpu.ops import fleet_kernels as fk

    task = GradTask(kind="linear", d_in=6, n_out=3, batch=4, steps=3,
                    data_seed=5)
    spec = FleetSpec.synth(1, seed=3, dim=task.param_dim())
    res = MegaFleet(
        spec, cluster_size=0, k=1, updates_per_node=4, alpha=0.0,
        server_lr=1.0, task=task, link_delay=0.0, chunk=48,
    ).run()
    assert res.version == 4

    gen_batch, train_one, _ = fk.make_grad_fns(
        "linear", 6, 3, 0, 4, 3, 0.5, data_seed=5
    )
    mu, tw, tb, _, _ = task.arrays(1)
    p = jnp.zeros(task.param_dim(), jnp.float32)
    for m in range(1, 5):
        xs, ys = gen_batch(0, m, jnp.asarray(mu[0]), jnp.asarray(tw), jnp.asarray(tb))
        p = train_one(p, xs, ys)
    np.testing.assert_allclose(res.params["w"], np.asarray(p), atol=1e-6)


def test_grad_task_mlp_runs_and_learns():
    """The mlp task kind wires through the same engine and learns: both
    layers receive gradient, and the eval-set CE ends well under what
    the best constant predictor scores.

    The start is a seeded NONZERO point. ``FleetSpec.synth`` starts every
    model at zeros (right for the consensus task and harmless for
    ``linear``), but zeros are a dead point of dense→relu→dense: h = 0
    and W2 = 0 make every gradient but the output bias's vanish, so the
    fleet can only learn the label prior. This test used to start there
    and compare the last of 40 versions with the first: the curve
    reached the prior's entropy (1.013 on this eval set) at version 1
    and both points were noise around it (1.043 against 1.039). With
    the start off the dead point the same fleet, step size and schedule
    go from 1.78 at the start through 0.88 (mean of the first quarter
    of the curve) to 0.22 (mean of the last quarter, max 0.29)."""
    task = GradTask(kind="mlp", d_in=6, n_out=3, hidden=5, batch=4,
                    steps=2, data_seed=9)
    pd = task.param_dim()
    spec = FleetSpec.synth(40, seed=3, dim=pd)
    spec.init = (
        np.random.default_rng(7).normal(size=pd) * 0.5
    ).astype(np.float32)
    res = MegaFleet(
        spec, cluster_size=0, k=4, updates_per_node=4, task=task,
        local_lr=0.7,
    ).run()
    losses = np.asarray([x[2] for x in res.loss_curve])
    assert len(losses) == res.version == 40  # 40 clients x 4 updates / k

    n_hidden = task.d_in * task.hidden + task.hidden  # W1 and b1
    moved = np.abs(res.params["w"] - spec.init)
    assert moved[:n_hidden].max() > 0.1  # the first layer trained too

    _, _, _, _, ye = task.arrays(spec.n)
    prior = np.bincount(ye, minlength=task.n_out) / len(ye)
    prior_ce = float(-(prior * np.log(prior)).sum())
    q = len(losses) // 4
    first, last = losses[:q].mean(), losses[-q:].mean()
    # halves, not hairs: the margins are far outside the curve's jitter
    assert last < 0.5 * first
    assert last < 0.5 * prior_ce


def test_grad_task_heap_parity_1k():
    """The 1k heap-parity pin for the gradient grid: the heap driver
    runs a vectorized-twin train_fn (same make_grad_fns kernels, 1-based
    per-node round counters matching key_lo) and the chunked engine must
    reproduce its merge decisions exactly with params at float
    tolerance."""
    from collections import defaultdict

    import jax
    import jax.numpy as jnp
    import optax

    from p2pfl_tpu.ops import fleet_kernels as fk

    task = GradTask(kind="linear", d_in=6, n_out=3, batch=4, steps=2,
                    data_seed=5)
    pd = task.param_dim()
    gen_batch, train_one, _ = fk.make_grad_fns(
        "linear", 6, 3, 0, 4, 2, 0.7, data_seed=5
    )
    t1j = jax.jit(train_one)
    mu, tw, tb, xe, ye = task.arrays(1000)
    muj, twj, tbj = jnp.asarray(mu), jnp.asarray(tw), jnp.asarray(tb)
    counters: dict = defaultdict(int)

    def train_fn(idx, params, rng):
        counters[idx] += 1
        xs, ys = gen_batch(idx, counters[idx], muj[idx], twj, tbj)
        return {"w": np.asarray(t1j(jnp.asarray(params["w"]), xs, ys))}

    def loss_fn(params):
        lg = fk.grad_logits("linear", 6, 3, 0, jnp.asarray(params["w"]),
                            jnp.asarray(xe))
        return float(
            optax.softmax_cross_entropy_with_integer_labels(
                lg, jnp.asarray(ye)
            ).mean()
        )

    fleet = SimulatedAsyncFleet(
        1000, seed=SEED, cluster_size=0, updates_per_node=4, k=8,
        local_lr=0.7, dim=pd, train_fn=train_fn, loss_fn=loss_fn,
        init_params={"w": np.zeros(pd, np.float32)},
    )
    spec = FleetSpec.from_sim(fleet, allow_custom=True)
    heap = fleet.run()
    mega = MegaFleet(
        spec, cluster_size=0, k=8, updates_per_node=4, local_lr=0.7,
        task=task,
    ).run()
    assert mega.merges == heap.merges
    _, hv, hl = _curves(heap)
    _, mv, ml = _curves(mega)
    assert mv == hv
    np.testing.assert_allclose(ml, hl, rtol=0, atol=float(max(hl.max(), 1e-9)) * 1e-4)
    np.testing.assert_allclose(
        np.asarray(mega.params["w"]), np.asarray(heap.params["w"]), atol=1e-5
    )


def test_grad_task_dim_mismatch_raises():
    task = GradTask(kind="linear", d_in=6, n_out=3)
    spec = FleetSpec.synth(10, seed=3, dim=4)
    with pytest.raises(ValueError, match="param"):
        MegaFleet(spec, task=task)
