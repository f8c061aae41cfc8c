"""Chaos suite: seeded fault injection + self-healing rounds (ISSUE 5).

Everything here runs N real ``Node`` objects over the in-memory transport
with a committed :class:`FaultPlan` seed, so each scenario replays the same
chaos on every run:

- fault-plan determinism and edge semantics (drop/partition/scope),
- retry/backoff for failed control sends (silent message loss is gone),
- circuit-breaker suspects accelerating heartbeat eviction,
- stale-beat rejection (a relayed beat must not resurrect a dead node),
- mid-round train-set repair (survivors aggregate without burning the
  full ``AGGREGATION_TIMEOUT``),
- the pinned round-0 wedge regression (stale ``models_aggregated``
  redeliveries must not regress coverage views — see
  ``commands/control.py`` ModelsAggregatedCommand).
"""

import time

import numpy as np
import pytest

from p2pfl_tpu.communication.faults import (
    CrashSpec,
    EdgeFault,
    FaultInjector,
    FaultPlan,
    hard_crash,
    install_fault_plan,
    remove_fault_plan,
)
from p2pfl_tpu.communication.gossiper import Gossiper
from p2pfl_tpu.communication.heartbeater import BEAT_CMD
from p2pfl_tpu.communication.memory import MemoryRegistry
from p2pfl_tpu.communication.message import Message, WeightsEnvelope
from p2pfl_tpu.learning.aggregators import FedAvg
from p2pfl_tpu.learning.learner import DummyLearner
from p2pfl_tpu.learning.weights import ModelUpdate
from p2pfl_tpu.management.logger import logger
from p2pfl_tpu.node import Node
from p2pfl_tpu.settings import Settings
from p2pfl_tpu.utils import full_connection, wait_convergence, wait_to_finish


@pytest.fixture(autouse=True)
def _clean():
    MemoryRegistry.reset()
    logger.reset_comm_metrics()
    yield
    MemoryRegistry.reset()


def _mk_nodes(n: int) -> list[Node]:
    nodes = [Node(learner=DummyLearner(value=float(i))) for i in range(n)]
    for node in nodes:
        node.start()
    for node in nodes:
        full_connection(node, nodes)
    wait_convergence(nodes, n - 1, only_direct=True, wait=10)
    return nodes


def _stop_all(nodes):
    for n in nodes:
        n.stop()


def _sum_metric(metric: str) -> float:
    return sum(d.get(metric, 0.0) for d in logger.get_comm_metrics().values())


# ---------------------------------------------------------------------------
# fault plan semantics
# ---------------------------------------------------------------------------


def test_fault_plan_replayable():
    """Same seed → the k-th send on an edge sees the same verdict; edges
    draw from independent streams."""

    def verdicts(plan, src, dst, k=64):
        inj = FaultInjector(plan, src)
        out = []
        for _ in range(k):
            ok = inj(dst, Message(src, "x"), False, lambda *a, **kw: True)
            out.append(ok)
        return out

    fault = EdgeFault(drop=0.5)
    a = verdicts(FaultPlan(seed=7, default=fault), "n1", "n2")
    b = verdicts(FaultPlan(seed=7, default=fault), "n1", "n2")
    assert a == b
    assert True in a and False in a  # p=0.5 over 64 draws
    other_edge = verdicts(FaultPlan(seed=7, default=fault), "n1", "n3")
    other_seed = verdicts(FaultPlan(seed=8, default=fault), "n1", "n2")
    assert a != other_edge and a != other_seed


def test_partition_and_scope():
    sent = []

    def transport(nei, env, create_connection=False):
        sent.append(env)
        return True

    # one-way partition: n1→n2 blocked, nothing reaches the transport
    plan = FaultPlan(seed=1, partitions=[("n1", "n2")])
    inj = FaultInjector(plan, "n1")
    assert inj("n2", Message("n1", "x"), False, transport) is False
    assert not sent
    # the reverse direction is untouched
    rev = FaultInjector(plan, "n2")
    assert rev("n1", Message("n2", "x"), False, transport) is True
    assert len(sent) == 1

    # scope="weights": control messages pass even at drop=1.0
    plan = FaultPlan(seed=1, default=EdgeFault(drop=1.0, scope="weights"))
    inj = FaultInjector(plan, "n1")
    assert inj("n2", Message("n1", "x"), False, transport) is True
    env = WeightsEnvelope("n1", 0, "add_model", ModelUpdate({"w": np.ones(2)}, ["n1"], 1))
    assert inj("n2", env, False, transport) is False


def test_duplicate_control_redelivery_has_fresh_id_and_ttl1():
    """A duplicated control message models a post-dedup-ring stale relay:
    fresh msg id (always re-accepted), ttl=1 (cannot re-amplify)."""
    delivered = []

    def transport(nei, env, create_connection=False):
        delivered.append(env)
        return True

    plan = FaultPlan(
        seed=3, default=EdgeFault(duplicate=1.0, duplicate_delay=0.05)
    )
    inj = FaultInjector(plan, "n1")
    orig = Message("n1", "models_aggregated", ("a", "b"), round=0, ttl=5)
    assert inj("n2", orig, False, transport) is True
    deadline = time.monotonic() + 2.0
    while len(delivered) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(delivered) == 2, "duplicate copy never delivered"
    copy = delivered[1]
    assert copy.msg_id != orig.msg_id
    assert copy.ttl == 1
    assert copy.args == orig.args and copy.cmd == orig.cmd


# ---------------------------------------------------------------------------
# control-plane reliability: retry/backoff + circuit breaker
# ---------------------------------------------------------------------------


def test_message_retry_recovers_transient_failure():
    """A send that fails transiently is retried with backoff and delivered;
    the old behavior silently lost it."""
    attempts = []
    fail_first = 2

    def send_fn(nei, env, create_connection=False):
        attempts.append(nei)
        return len(attempts) > fail_first

    g = Gossiper("me", send_fn)
    g.start()
    try:
        g.add_message(Message("me", "vote", ("x", "1")), ["peer"])
        deadline = time.monotonic() + 5.0
        # the delivery is counted after the send returns: wait for the count too
        while (
            len(attempts) < fail_first + 1 or not logger.get_comm_metrics("me").get("msg_retry_ok")
        ) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(attempts) == fail_first + 1, "retries never delivered the message"
        m = logger.get_comm_metrics("me")
        assert m.get("msg_retry_scheduled", 0) == fail_first
        assert m.get("msg_retry_ok", 0) == 1
    finally:
        g.stop()


def test_message_retry_bounded_and_loud():
    """Retries are BOUNDED: a permanently failing neighbor costs exactly
    1 + MESSAGE_RETRY_MAX transport attempts, then the drop is counted."""
    attempts = []

    def send_fn(nei, env, create_connection=False):
        attempts.append(nei)
        return False

    g = Gossiper("me", send_fn)
    g.start()
    try:
        g.add_message(Message("me", "vote", ("x", "1")), ["peer"])
        deadline = time.monotonic() + 6.0
        while (
            logger.get_comm_metrics("me").get("msg_retry_exhausted", 0) < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        m = logger.get_comm_metrics("me")
        assert m.get("msg_retry_exhausted", 0) == 1, "exhaustion never reported"
        assert len(attempts) == 1 + Settings.MESSAGE_RETRY_MAX
    finally:
        g.stop()


def test_beat_sends_never_enter_retry_queue():
    """Beats are exempt from the retry path at its single funnel
    (``schedule_retry``): a beat is superseded every HEARTBEAT_PERIOD, so
    retrying one would deliver stale liveness while crowding the per-tick
    budget during exactly the failure windows that matter."""
    g = Gossiper("me", lambda nei, env, create_connection=False: False)
    g.start()
    try:
        beat = Message("me", BEAT_CMD, (str(time.time()),))
        g.add_message(beat, ["peer"])
        deadline = time.monotonic() + 2.0
        while (
            logger.get_comm_metrics("me").get("gossip_send_fail", 0) < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        g.schedule_retry("peer", beat, attempt=1)  # direct funnel: also exempt
        time.sleep(0.3)  # room for any (wrong) retry to get scheduled
        m = logger.get_comm_metrics("me")
        assert m.get("gossip_send_fail", 0) >= 1
        assert m.get("msg_retry_scheduled", 0) == 0
    finally:
        g.stop()


def test_breaker_suspect_accelerates_eviction():
    """Send failures open the per-neighbor breaker; a suspect is evicted
    after BREAKER_SUSPECT_TIMEOUT of beat silence instead of the full
    HEARTBEAT_TIMEOUT."""
    old_timeout = Settings.HEARTBEAT_TIMEOUT
    Settings.HEARTBEAT_TIMEOUT = 30.0  # make the slow path obviously slow
    nodes = _mk_nodes(2)
    a, b = nodes
    try:
        t0 = time.monotonic()
        hard_crash(b)  # no goodbyes: a finds out through send failures
        deadline = time.monotonic() + 10.0
        while b.addr in a.get_neighbors() and time.monotonic() < deadline:
            time.sleep(0.05)
        elapsed = time.monotonic() - t0
        assert b.addr not in a.get_neighbors(), "suspect never evicted"
        assert elapsed < 10.0 < Settings.HEARTBEAT_TIMEOUT
        m = logger.get_comm_metrics(a.addr)
        assert m.get("breaker_open", 0) >= 1
        assert m.get("breaker_suspect_evict", 0) >= 1
        assert m.get("neighbor_evicted", 0) >= 1
    finally:
        Settings.HEARTBEAT_TIMEOUT = old_timeout
        _stop_all(nodes)


def test_one_way_partition_evicts_despite_beats():
    """A neighbor we cannot send to — but whose beats still arrive — is
    evicted after a full HEARTBEAT_TIMEOUT of breaker-open: silence-based
    sweeps never fire for a one-way partition, so reachability has to be
    its own eviction clock.

    Three nodes, not two: with only a↔b, b would lose a's beats, evict a
    by silence, stop beating back — and a's *suspect* fast path would race
    the unreachable clock on the fresh silence. The third node keeps the
    flood alive (a's beats reach b via c), so b never goes silent toward a
    and the reachability clock is the only path that can fire. The suspect
    window is pinned above HEARTBEAT_TIMEOUT for the same reason: on a
    loaded box one beat delivery slipping past the (sub-second) suspect
    window would let the silence fast path fire first, turning the
    breaker_suspect_evict == 0 assertion into a scheduling race.
    """
    old_sus = Settings.BREAKER_SUSPECT_TIMEOUT
    Settings.BREAKER_SUSPECT_TIMEOUT = Settings.HEARTBEAT_TIMEOUT + 5.0
    nodes = _mk_nodes(3)
    a, b, c = nodes
    plan = FaultPlan(seed=5, partitions=[(a.addr, b.addr)])
    install_fault_plan([a], plan)  # only the a→b edge is severed
    try:
        deadline = time.monotonic() + Settings.HEARTBEAT_TIMEOUT + 8.0
        while (
            logger.get_comm_metrics(a.addr).get("breaker_unreachable_evict", 0) < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        m = logger.get_comm_metrics(a.addr)
        assert m.get("breaker_unreachable_evict", 0) >= 1, (
            "one-way-partitioned peer never evicted"
        )
        # b's beats kept arriving at a the whole time — this was not a
        # silence eviction (neither the suspect fast path nor the plain
        # HEARTBEAT_TIMEOUT sweep fired for b)
        assert m.get("breaker_suspect_evict", 0) == 0
        assert c.addr in a.get_neighbors()  # the healthy edge is untouched
    finally:
        Settings.BREAKER_SUSPECT_TIMEOUT = old_sus
        remove_fault_plan([a])
        _stop_all(nodes)


def test_breaker_closes_on_success():
    from p2pfl_tpu.communication.reliability import CircuitBreaker

    br = CircuitBreaker("me")
    for _ in range(Settings.BREAKER_THRESHOLD):
        br.record("peer", False)
    assert br.is_suspect("peer")
    br.record("peer", True)
    assert not br.is_suspect("peer")
    m = logger.get_comm_metrics("me")
    assert m.get("breaker_open", 0) == 1 and m.get("breaker_close", 0) == 1


# ---------------------------------------------------------------------------
# heartbeater stale-beat rejection (satellite)
# ---------------------------------------------------------------------------


def test_stale_beat_rejected_fresh_beat_accepted():
    """A TTL-flooded beat relayed after its origin died must not refresh
    ``last_beat`` — regression test for the stale-beat fix."""
    nodes = _mk_nodes(2)
    a, b = nodes
    try:
        info = a.protocol.neighbors.get(b.addr)
        assert info is not None

        # stale origin stamp: rejected, last_beat untouched
        before = info.last_beat
        time.sleep(0.05)
        a.protocol.heartbeater.beat(
            b.addr, time.time() - Settings.HEARTBEAT_TIMEOUT - 1.0
        )
        assert a.protocol.neighbors.get(b.addr).last_beat == before
        assert logger.get_comm_metrics(a.addr).get("stale_beat_rejected", 0) >= 1

        # fresh origin stamp: accepted, last_beat refreshed
        a.protocol.heartbeater.beat(b.addr, time.time())
        assert a.protocol.neighbors.get(b.addr).last_beat > before

        # legacy beat with no origin info (t<=0): accepted for compatibility
        before = a.protocol.neighbors.get(b.addr).last_beat
        time.sleep(0.05)
        a.protocol.heartbeater.beat(b.addr, 0.0)
        assert a.protocol.neighbors.get(b.addr).last_beat > before
    finally:
        _stop_all(nodes)


def test_local_pause_is_not_peer_silence(monkeypatch):
    """A process frozen for longer than HEARTBEAT_TIMEOUT (VM pause, a C call
    holding the GIL) must not evict live peers for the beats it could not
    hear: the heartbeater's own overrun is discounted from every neighbor's
    silence clock before the sweep. Seen on the TPU machine, where a freeze
    during the first round made two in-process nodes evict each other and
    finish with different params (PR 21)."""
    from p2pfl_tpu.communication.heartbeater import Heartbeater
    from p2pfl_tpu.communication.neighbors import Neighbors
    from p2pfl_tpu.communication.reliability import CircuitBreaker

    monkeypatch.setattr(Settings, "HEARTBEAT_PERIOD", 0.1)
    monkeypatch.setattr(Settings, "HEARTBEAT_TIMEOUT", 0.5)
    freeze = 1.0  # two timeouts

    class Proto:
        neighbors = Neighbors("me")
        breaker = CircuitBreaker("me")

        def build_msg(self, cmd, args):
            return None

        def broadcast(self, msg):
            pass

    class FrozenOnce:
        """``Event.wait`` stand-in: the second sleep overruns by ``freeze``
        (so the next sweep tick sees the whole freeze), the fifth ends the run."""

        calls = 0

        def is_set(self):
            return False

        def wait(self, timeout):
            self.calls += 1
            time.sleep(timeout + (freeze if self.calls == 2 else 0.0))
            return self.calls >= 5

    proto = Proto()
    proto.neighbors.add("peer", non_direct=True)
    hb = Heartbeater("me", proto)
    hb._stop = FrozenOnce()
    hb._run()  # ticks 1..5; sweeps at 2 and 4; the freeze sits between them
    # the peer "beat" for the last time before the freeze and is still a
    # member: freeze + 3 periods of silence, minus the discounted freeze
    assert proto.neighbors.get("peer") is not None, "live peer evicted after a local pause"
    assert logger.get_comm_metrics("me").get("local_pause", 0) == 1
    # the discount is the overrun and no more: real silence still evicts
    time.sleep(Settings.HEARTBEAT_TIMEOUT)
    assert proto.neighbors.evict_stale(Settings.HEARTBEAT_TIMEOUT) == ["peer"]


# ---------------------------------------------------------------------------
# mid-round train-set repair
# ---------------------------------------------------------------------------


def _update(addrs, value=1.0):
    return ModelUpdate({"w": np.full(4, value)}, list(addrs), len(addrs))


def test_discard_member_shrinks_target():
    agg = FedAvg(node_name="me")
    agg.set_nodes_to_aggregate(["a", "b", "c"])
    agg.add_model(_update(["a"]))
    # c died before contributing: target shrinks to {a, b}
    assert agg.discard_member("c") == ["a"]
    assert agg.add_model(_update(["b"])) == ["a", "b"]
    result = agg.wait_and_get_aggregation(timeout=1.0)
    assert set(result.contributors) == {"a", "b"}
    assert logger.get_comm_metrics("me").get("train_set_repair", 0) == 1


def test_discard_member_keeps_arrived_contribution():
    agg = FedAvg(node_name="me")
    agg.set_nodes_to_aggregate(["a", "b", "c"])
    agg.add_model(_update(["c"]))
    # c's training happened and its update is here — only ABSENCE is repaired
    assert agg.discard_member("c") is None
    agg.add_model(_update(["a"]))
    agg.add_model(_update(["b"]))
    result = agg.wait_and_get_aggregation(timeout=1.0)
    assert set(result.contributors) == {"a", "b", "c"}


def test_discard_member_closes_window_when_survivors_already_covered():
    agg = FedAvg(node_name="me")
    agg.set_nodes_to_aggregate(["a", "b", "c"])
    agg.add_model(_update(["a"]))
    agg.add_model(_update(["b"]))
    assert not agg._complete.is_set()
    assert agg.discard_member("c") == ["a", "b"]
    assert agg._complete.is_set()
    result = agg.wait_and_get_aggregation(timeout=1.0)
    assert set(result.contributors) == {"a", "b"}


def test_discard_member_widens_waiting_acceptance():
    agg = FedAvg(node_name="me")
    agg.set_waiting_aggregated_model(["a", "b", "c"])
    # survivors-only aggregate rejected while c is still a live member
    assert agg.add_model(_update(["a", "b"])) == []
    assert agg.discard_member("c") is None  # widened, nothing to announce
    assert agg.add_model(_update(["a", "b"])) == ["a", "b"]


def test_waiting_all_members_discarded_still_requires_full_coverage():
    """Degenerate repair: every train-set member evicted while waiting must
    not collapse the acceptance interval to "anything" — a lone member's
    partial is still rejected; only a (post-partition-heal) full aggregate
    passes."""
    agg = FedAvg(node_name="me")
    agg.set_waiting_aggregated_model(["a", "b", "c"])
    for member in ("a", "b", "c"):
        agg.discard_member(member)
    assert agg.add_model(_update(["a"])) == []
    assert agg.add_model(_update(["a", "b"])) == []
    assert agg.add_model(_update(["a", "b", "c"])) == ["a", "b", "c"]


# ---------------------------------------------------------------------------
# crash-at-stage + end-to-end self-healing federation
# ---------------------------------------------------------------------------


def test_crash_at_stage_no_goodbyes():
    """A CrashSpec kills the node like a killed process: peers still list
    it right after the crash and only evict via failure detection."""
    nodes = _mk_nodes(3)
    plan = FaultPlan(
        seed=11, crashes={nodes[2].addr: CrashSpec(stage="VoteTrainSetStage", round_no=0)}
    )
    install_fault_plan(nodes, plan)
    try:
        nodes[0].set_start_learning(rounds=1, epochs=0)
        deadline = time.monotonic() + 10.0
        while nodes[2]._running and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not nodes[2]._running, "crash spec never fired"
        # no disconnect messages went out: survivors still list the corpse
        # until heartbeat/breaker eviction does its job
        assert _sum_metric("fault_crash") == 1
        survivors = nodes[:2]
        wait_to_finish(survivors, timeout=30)
        deadline = time.monotonic() + 10.0
        while any(
            nodes[2].addr in n.get_neighbors() for n in survivors
        ) and time.monotonic() < deadline:
            time.sleep(0.05)
        for n in survivors:
            assert nodes[2].addr not in n.get_neighbors()
    finally:
        remove_fault_plan(nodes)
        _stop_all(nodes)


@pytest.mark.parametrize("n_nodes", [6, 8])
def test_chaos_federation_survives_slow_peer_and_midround_crash(n_nodes):
    """ISSUE 5 acceptance: N-node federation under 5% drop, one slow peer,
    one train-set member hard-crashing entering TrainStage. Every surviving
    node must finish every round — survivors aggregate via train-set repair
    within roughly one heartbeat-eviction window, nowhere near the full
    AGGREGATION_TIMEOUT — with bounded retries and zero stalls. The 6-node
    variant is the CI chaos smoke (chaos_smoke.yml); 8 nodes is the bench
    shape whose wedge started all of this."""
    Settings.TRAIN_SET_SIZE = n_nodes
    Settings.AGGREGATION_TIMEOUT = 60.0  # a repair failure would burn this
    Settings.STALL_WATCHDOG_S = 8.0  # make the zero-stall assertion real
    rounds = 2
    nodes = _mk_nodes(n_nodes)
    victim, slow = nodes[3], nodes[-1]
    plan = FaultPlan(
        seed=1905,
        default=EdgeFault(drop=0.05),
        slow_nodes={slow.addr: 0.3},
        crashes={victim.addr: CrashSpec(stage="TrainStage", round_no=0)},
    )
    install_fault_plan(nodes, plan)
    survivors = [n for n in nodes if n is not victim]
    try:
        t0 = time.monotonic()
        nodes[0].set_start_learning(rounds=rounds, epochs=1)
        wait_to_finish(survivors, timeout=45)
        elapsed = time.monotonic() - t0
        # the crash was repaired, not waited out: well under the 60 s
        # aggregation timeout (the wall budget covers 2 full rounds plus
        # eviction latency under 5% drop + a 0.3 s/model slow peer)
        assert elapsed < 45.0
        assert not victim._running
        for n in survivors:
            assert n.state.round is None  # finished, back to idle
        assert _sum_metric("train_set_repair") >= 1, "no node repaired the train set"
        assert _sum_metric("stall_detected") == 0
        # retries are bounded, not a storm: every scheduled retry is backed
        # 1:1 by a definitive send failure (5% injected drop + sends to the
        # corpse until eviction — the latter surfacing as gossip_send_fail
        # on the dispatch path or send_fail_direct on protocol.send's), and
        # permanent failures exhaust after MESSAGE_RETRY_MAX instead of
        # climbing without bound
        failures = (
            _sum_metric("gossip_send_fail")
            + _sum_metric("send_fail_direct")
            + _sum_metric("fault_drop")
        )
        assert 0 < _sum_metric("msg_retry_scheduled") <= failures
        # the breaker saw the crash: suspects opened and fed early eviction
        assert _sum_metric("breaker_open") >= 1
        # survivors converged on the same repaired-aggregate parameters
        params = [np.asarray(n.learner.get_parameters()["w"]) for n in survivors]
        for p in params[1:]:
            np.testing.assert_allclose(p, params[0], atol=1e-5)
    finally:
        from p2pfl_tpu.management.watchdog import StallWatchdog

        remove_fault_plan(nodes)
        _stop_all(nodes)
        StallWatchdog.shutdown()
        Settings.STALL_WATCHDOG_S = 0.0


# ---------------------------------------------------------------------------
# the pinned round-0 wedge regression
# ---------------------------------------------------------------------------

#: committed chaos seed reproducing the PR-4 "8-node slow-peer bench
#: federation occasionally wedges at round 0" flake on demand: stale
#: ``models_aggregated`` redeliveries (duplicates with fresh message ids —
#: exactly what TTL relays look like once the bounded dedup ring has
#: flooded out) arrive while a slow peer stretches the partial-gossip
#: phase. Under the pre-fix overwrite semantics the stale views regress
#: peers' coverage and the convergence detector never sees a static
#: status; with monotone union-merges the same chaos converges every run.
WEDGE_SEED = 1905


def test_round0_wedge_regression():
    old_ring = Settings.AMOUNT_LAST_MESSAGES_SAVED
    Settings.TRAIN_SET_SIZE = 6
    # small dedup ring: relays flood it out fast, like the 8-node bench
    Settings.AMOUNT_LAST_MESSAGES_SAVED = 20
    nodes = _mk_nodes(6)
    plan = FaultPlan(
        seed=WEDGE_SEED,
        default=EdgeFault(duplicate=0.5, duplicate_delay=0.4, scope="control"),
        slow_nodes={nodes[5].addr: 0.4},
    )
    install_fault_plan(nodes, plan)
    try:
        nodes[0].set_start_learning(rounds=1, epochs=1)
        wait_to_finish(nodes, timeout=40)
        for n in nodes:
            assert n.state.round is None
    finally:
        remove_fault_plan(nodes)
        Settings.AMOUNT_LAST_MESSAGES_SAVED = old_ring
        _stop_all(nodes)


# ---------------------------------------------------------------------------
# the pinned kill-a-node-mid-startup wedge regression (ISSUE 9)
# ---------------------------------------------------------------------------

#: chaos seed for the startup-kill regression. The wedge needed no edge
#: faults at all — any node hard-crashing AFTER start_learning but BEFORE
#: casting its vote reproduced it: every survivor's VoteTrainSetStage
#: waited out the full VOTE_TIMEOUT (60 s at defaults) for the corpse's
#: vote, because the vote-collection loop snapshotted its candidate set at
#: stage entry and never re-checked liveness — while the PR-7 flight
#: record showed neighbor_evicted landing within the first two seconds
#: and 9+ s of retry backoff burned against the dead peer. ~1/3 of manual
#: probe runs hit it because the kill had to land in the pre-vote window.
STARTUP_WEDGE_SEED = 2206


def test_startup_kill_wedge_regression():
    """A node killed mid-startup (entering VoteTrainSetStage, i.e. before
    it votes) must delay the survivors by roughly one eviction window —
    NOT by VOTE_TIMEOUT. Pre-fix this takes > VOTE_TIMEOUT wall-clock;
    the bound asserts the whole 2-round run completes well inside it."""
    old_vote = Settings.VOTE_TIMEOUT
    Settings.VOTE_TIMEOUT = 30.0  # the pre-fix burn — generous vs the bound below
    nodes = _mk_nodes(5)
    victim = nodes[2]
    plan = FaultPlan(
        seed=STARTUP_WEDGE_SEED,
        crashes={victim.addr: CrashSpec(stage="VoteTrainSetStage", round_no=0)},
    )
    install_fault_plan(nodes, plan)
    survivors = [n for n in nodes if n is not victim]
    try:
        t0 = time.monotonic()
        nodes[0].set_start_learning(rounds=2, epochs=1)
        wait_to_finish(survivors, timeout=25)
        elapsed = time.monotonic() - t0
        assert not victim._running, "crash spec never fired"
        # eviction window (~breaker suspect + heartbeat) + 2 fast rounds:
        # an order of magnitude under the 30 s VOTE_TIMEOUT the corpse's
        # vote would otherwise have burned
        assert elapsed < 15.0, f"startup kill still gates the vote ({elapsed:.1f}s)"
        for n in survivors:
            assert n.state.round is None
    finally:
        Settings.VOTE_TIMEOUT = old_vote
        remove_fault_plan(nodes)
        _stop_all(nodes)


# ---------------------------------------------------------------------------
# StartLearningStage graceful abort (satellite)
# ---------------------------------------------------------------------------


def test_init_model_timeout_aborts_gracefully():
    """A node whose initial model never arrives clears the experiment and
    keeps serving — no TimeoutError escapes, and it can join the next
    start_learning normally."""
    old = Settings.AGGREGATION_TIMEOUT
    Settings.AGGREGATION_TIMEOUT = 1.0
    nodes = _mk_nodes(2)
    a, b = nodes
    try:
        # b learns it should start, but the initiator's init_model never
        # comes (nobody sends one): StartLearningStage must time out into a
        # graceful abort, not an escaping TimeoutError
        b._start_learning_thread(rounds=1, epochs=0)
        deadline = time.monotonic() + 10.0
        while b.state.status == "Learning" and time.monotonic() < deadline:
            time.sleep(0.05)
        assert b.state.status == "Idle" and b.state.round is None
        assert b._running, "node stopped serving after init timeout"
        assert b.addr in a.get_neighbors()

        # and it joins the next experiment normally
        Settings.AGGREGATION_TIMEOUT = old
        a.set_start_learning(rounds=1, epochs=0)
        wait_to_finish(nodes, timeout=30, min_experiments=1)
    finally:
        Settings.AGGREGATION_TIMEOUT = old
        _stop_all(nodes)


def test_early_init_model_stash_consumed():
    """An init_model that arrives BEFORE start_learning (the weights plane
    can beat the TTL-flooded control broadcast) is stashed and consumed
    when the experiment starts — not dropped on the floor: the initiator's
    push loop exits once its status view stops changing, so a dropped
    early init may never be redelivered."""
    nodes = _mk_nodes(2)
    a, b = nodes
    try:
        upd = a.learner.get_model_update()
        # the init races ahead of b's start_learning: stashed, NOT latched
        b.protocol._commands["init_model"].execute(a.addr, 0, update=upd)
        assert not b.state.model_initialized_event.is_set()
        # the experiment starts: the stash seeds it instead of a timeout
        b._start_learning_thread(rounds=1, epochs=0)
        deadline = time.monotonic() + 5.0
        while (
            not b.state.model_initialized_event.is_set()
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert b.state.model_initialized_event.is_set(), "stash never consumed"
        expect = np.asarray(a.learner.get_parameters()["w"])
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if np.allclose(np.asarray(b.learner.get_parameters()["w"]), expect):
                break
            time.sleep(0.02)
        np.testing.assert_allclose(
            np.asarray(b.learner.get_parameters()["w"]), expect
        )
    finally:
        _stop_all(nodes)


def test_init_during_teardown_window_stashed_not_latched():
    """``state.clear()`` can run while the learning thread is still
    unwinding (the graceful abort clears before the workflow loop returns;
    ``stop_learning`` clears on the command thread mid-stage). A straggler
    ``init_model`` landing in that window must be STASHED, not latched —
    the thread-liveness gate alone passes there, and a latch after the
    clear would poison the next experiment, whose ``set_experiment``
    cannot re-clear the event (the initiator legitimately pre-sets it)."""
    old = Settings.AGGREGATION_TIMEOUT
    Settings.AGGREGATION_TIMEOUT = 3.0
    nodes = _mk_nodes(2)
    a, b = nodes
    try:
        b._start_learning_thread(rounds=1, epochs=0)
        deadline = time.monotonic() + 5.0
        while b.state.round is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b.state.round == 0, "experiment never entered StartLearningStage"
        # the teardown's clear() lands while the learning thread is alive
        b.state.clear()
        assert b.learning_active(), "window under test requires a live thread"
        b.protocol._commands["init_model"].execute(
            a.addr, 0, update=a.learner.get_model_update()
        )
        assert not b.state.model_initialized_event.is_set(), (
            "straggler init_model latched into a cleared experiment"
        )
        # the graceful abort then drains the stash, so the dead
        # experiment's init cannot seed the next one either
        deadline = time.monotonic() + 8.0
        while b.learning_active() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not b.learning_active()
        assert b.take_early_init() is None
    finally:
        Settings.AGGREGATION_TIMEOUT = old
        _stop_all(nodes)


# ---------------------------------------------------------------------------
# eviction quarantine vs deliberate reconnects
# ---------------------------------------------------------------------------


def test_failed_direct_connect_preserves_quarantine():
    """A deliberate direct connect overrides quarantine only when it
    SUCCEEDS: a failed attempt must leave the quarantine entry in place,
    or the unreachable peer's very next beat re-admits it — the exact
    evict/re-add flap quarantine exists to prevent."""
    nodes = _mk_nodes(2)
    a, b = nodes
    na = a.protocol.neighbors
    try:
        na.evict(b.addr, quarantine=True)
        assert na.get(b.addr) is None
        # beats alone must not re-admit a quarantined peer
        na.heartbeat(b.addr)
        assert na.get(b.addr) is None
        # b vanishes (hard crash: unregistered, no goodbyes) — the connect
        # attempt fails and must NOT clear the quarantine
        b.protocol.crash()
        assert not a.protocol.connect(b.addr)
        na.heartbeat(b.addr)
        assert na.get(b.addr) is None, "failed connect cleared the quarantine"
    finally:
        _stop_all(nodes)


def test_successful_direct_connect_overrides_quarantine():
    nodes = _mk_nodes(2)
    a, b = nodes
    na = a.protocol.neighbors
    try:
        na.evict(b.addr, quarantine=True)
        na.heartbeat(b.addr)
        assert na.get(b.addr) is None
        # b is still reachable: the deliberate reconnect succeeds and lifts
        # the quarantine
        assert a.protocol.connect(b.addr)
        assert na.get(b.addr) is not None
        na.heartbeat(b.addr)
        assert na.get(b.addr) is not None
    finally:
        _stop_all(nodes)


def test_stale_breaker_evidence_does_not_evict():
    """The unreachable-despite-beats eviction requires ONGOING failure
    evidence: a breaker left open because the peer simply fell out of
    every send path (e.g. a non-direct gossip target the model plane
    converged away from) must not evict a live, beating neighbor on a
    stale burst — only fresh failures spanning the window count."""
    from p2pfl_tpu.communication.reliability import CircuitBreaker

    br = CircuitBreaker("me")
    for _ in range(Settings.BREAKER_THRESHOLD):
        br.record("peer", False)
    assert br.is_suspect("peer")
    time.sleep(0.3)
    # open for >= 0.25s, but the last failure is 0.3s old: with a 0.1s
    # freshness bound the evidence is stale — no eviction
    assert br.suspects_older_than(0.25, fresh_within=0.1) == set()
    # a fresh failure re-arms it
    br.record("peer", False)
    assert br.suspects_older_than(0.25, fresh_within=0.1) == {"peer"}
    # and without a freshness bound the old (pre-fix) semantics remain
    assert br.suspects_older_than(0.25) == {"peer"}


def test_models_aggregated_concurrent_merges_lose_nothing():
    """The union-merge must be atomic: handlers run on whatever thread
    delivers the message (sender gossip workers, duplicate timers), and
    an unlocked read-merge-write could clobber a concurrent merge for the
    same source — losing a sender's FINAL coverage announcement, which
    its exited push loop never repeats (the round-0 wedge, resurrected as
    a race)."""
    import threading as _threading

    nodes = _mk_nodes(1)
    (n,) = nodes
    try:
        n.state.round = 0
        cmd = n.protocol._commands["models_aggregated"]
        members = [f"m{i}" for i in range(8)]
        start = _threading.Barrier(4)

        def deliver(subset):
            start.wait()
            for _ in range(200):
                cmd.execute("peer", 0, *subset)

        threads = [
            _threading.Thread(target=deliver, args=(members[i * 2 : i * 2 + 2],))
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(n.state.models_aggregated["peer"]) == sorted(members)
    finally:
        n.state.round = None
        _stop_all(nodes)


def test_early_init_stash_expires_without_experiment():
    """A node that never starts an experiment must not hold a stashed
    init_model's parameters forever — the TTL fires on a timer, not only
    at take time."""
    old = Settings.EARLY_INIT_TTL
    Settings.EARLY_INIT_TTL = 0.2
    nodes = _mk_nodes(2)
    a, b = nodes
    try:
        b.protocol._commands["init_model"].execute(
            a.addr, 0, update=a.learner.get_model_update()
        )
        with b._early_init_lock:
            assert b._early_init is not None
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            with b._early_init_lock:
                if b._early_init is None:
                    break
            time.sleep(0.05)
        with b._early_init_lock:
            assert b._early_init is None, "stash never expired on an idle node"
    finally:
        Settings.EARLY_INIT_TTL = old
        _stop_all(nodes)
