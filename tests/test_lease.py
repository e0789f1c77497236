"""Leader-lease reads (ISSUE 17 tentpole): scalar conformance, kernel
differential, and the clock-fault degradation path.

Four layers:

  * scalar-core conformance — a quorum of tag-matched heartbeat acks
    grants a lease bounded STRICTLY below the minimum election timeout
    minus the skew margin; any _reset (step-down, new term), an
    in-flight transfer, or a host clock-anomaly report revokes it; a
    live lease serves a linearizable read locally (no quorum round) and
    an expired/suspect lease falls back to ReadIndex — degradation, not
    danger;
  * lease-off bit-identity guard — with `Config.lease_read` at its
    default the kernel's lease tensors never move and the heartbeat
    wire tag stays 0 (the whole pre-existing differential suite pins
    the rest of the off-path);
  * kernel differential — the vectorized kernel with leases ON agrees
    with the scalar oracle replica-for-replica (roles/terms/commit AND
    lease validity + served/fallback counters) across seeded randomized
    fault schedules;
  * the NodeHost tick plane — a ClockPlane step-jump on a live leader
    is detected as a CLOCK fault (not a scheduling stall): the lease
    goes on suspect hold (reads degrade to ReadIndex and still
    linearize), the fairness gauge is not tripped, and the phantom tick
    backlog is shed instead of burst-replayed.
"""
import os
import time

import numpy as np
import pytest

from conftest import ENGINE_KINDS, engine_kw, host_of_kind
from dragonboat_tpu.config import Config, ConfigError, EngineConfig, NodeHostConfig
from dragonboat_tpu.core.logentry import InMemLogDB
from dragonboat_tpu.core.raft import Raft
from dragonboat_tpu.core.remote import Remote
from dragonboat_tpu.faults import ClockPlane, FaultPlane
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.ops.loopback import LoopbackCluster
from dragonboat_tpu.ops.state import ROLE, _mix
from dragonboat_tpu.requests import ErrLeaseExpired, ErrSystemBusy
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
from dragonboat_tpu.types import Entry, Message, MessageType as MT, is_local_message

N = 3
ELECTION = 10
HEARTBEAT = 2


def mk_raft(nid, lease_read=True, full=(1, 2, 3), **kw):
    r = Raft(
        Config(
            node_id=nid, cluster_id=1, election_rtt=ELECTION,
            heartbeat_rtt=HEARTBEAT, lease_read=lease_read, **kw,
        ),
        InMemLogDB(),
    )
    for p in full:
        r.remotes[p] = Remote(next=1)
    return r


def mk_leader(lease_read=True, **kw):
    r = mk_raft(1, lease_read=lease_read, **kw)
    r.handle(Message(type=MT.ELECTION, from_=1))
    for p in (2, 3):
        r.handle(Message(type=MT.REQUEST_VOTE_RESP, from_=p, to=1, term=r.term))
    assert r.is_leader()
    # commit the leader noop so ReadIndex is legal at this term
    for p in (2, 3):
        r.handle(
            Message(
                type=MT.REPLICATE_RESP, from_=p, to=1, term=r.term,
                log_index=r.log.last_index(),
            )
        )
    r.msgs.clear()
    return r


def heartbeat_round(r):
    """Tick until the periodic heartbeat fires; return the round's tag."""
    for _ in range(2 * HEARTBEAT + 1):
        r.tick()
        hbs = [m for m in r.msgs if m.type == MT.HEARTBEAT]
        if hbs:
            r.msgs.clear()
            return hbs[0].log_index
    raise AssertionError("no heartbeat fired")


def ack(r, frm, tag):
    r.handle(
        Message(type=MT.HEARTBEAT_RESP, from_=frm, to=1, term=r.term,
                log_index=tag)
    )


class TestScalarLease:
    def test_quorum_of_tagged_acks_grants_bounded_lease(self):
        r = mk_leader()
        tag = heartbeat_round(r)
        assert tag == r.tick_count  # the round opens at the current tick
        assert not r.lease_valid()  # no acks yet
        ack(r, 2, tag)
        assert r.lease_valid()  # quorum = leader + one voter
        # bounded strictly below the MINIMUM randomized election timeout
        # minus the margin: no rival can win an election inside the lease
        assert r.lease_until == tag + ELECTION - r.lease_margin
        assert r.lease_margin == HEARTBEAT  # default margin = heartbeat_rtt
        assert r.lease_until - tag < ELECTION

    def test_stale_round_tag_does_not_count(self):
        r = mk_leader()
        tag = heartbeat_round(r)
        ack(r, 2, tag - 1)  # echo of an older round
        ack(r, 2, 0)  # leases-off echo
        assert not r.lease_valid()
        ack(r, 2, tag)
        assert r.lease_valid()

    def test_lease_expires_at_bound(self):
        r = mk_leader()
        tag = heartbeat_round(r)
        ack(r, 2, tag)
        while r.tick_count < r.lease_until - 1:
            r.tick()
            r.msgs.clear()
        assert r.lease_valid()
        r.tick()
        assert not r.lease_valid()

    def test_step_down_and_transfer_revoke(self):
        r = mk_leader()
        ack(r, 2, heartbeat_round(r))
        assert r.lease_valid()
        r.handle(
            Message(type=MT.LEADER_TRANSFER, from_=1, to=1, hint=2)
        )
        assert r.leader_transfering() and not r.lease_valid()
        r2 = mk_leader()
        ack(r2, 2, heartbeat_round(r2))
        # a higher-term message forces step-down: _reset clears the lease
        r2.handle(
            Message(type=MT.HEARTBEAT, from_=3, to=1, term=r2.term + 5)
        )
        assert not r2.is_leader()
        assert r2.lease_until == 0 and r2.lease_round_tick == 0
        assert not r2.lease_valid()

    def test_clock_suspect_revokes_and_blocks_regrant(self):
        r = mk_leader()
        ack(r, 2, heartbeat_round(r))
        assert r.lease_valid()
        r.set_clock_suspect(100)
        assert not r.lease_valid()
        # a fresh quorum round inside the hold must NOT re-grant
        ack(r, 2, heartbeat_round(r))
        assert not r.lease_valid()
        # after the hold expires, the next full round re-earns the lease
        while r.tick_count < r.clock_suspect_until:
            r.tick()
            r.msgs.clear()
        ack(r, 2, heartbeat_round(r))
        assert r.lease_valid()

    def test_live_lease_serves_read_locally(self):
        r = mk_leader()
        ack(r, 2, heartbeat_round(r))
        r.handle(Message(type=MT.READ_INDEX, from_=1, hint=7))
        assert r.lease_served == 1 and r.lease_fallback == 0
        assert [rr.system_ctx.low for rr in r.ready_to_read] == [7]
        # no quorum round was opened for the read
        assert not [m for m in r.msgs if m.type == MT.HEARTBEAT]

    def test_expired_lease_falls_back_to_readindex(self):
        r = mk_leader()  # lease never granted
        r.handle(Message(type=MT.READ_INDEX, from_=1, hint=9))
        assert r.lease_served == 0 and r.lease_fallback == 1
        assert r.ready_to_read == []  # quorum confirmation pending
        hbs = [m for m in r.msgs if m.type == MT.HEARTBEAT]
        assert hbs and hbs[0].hint == 9  # the ReadIndex round went out
        # the fallback still completes: quorum of ctx echoes releases it
        r.handle(
            Message(type=MT.HEARTBEAT_RESP, from_=2, to=1, term=r.term,
                    hint=9)
        )
        assert [rr.system_ctx.low for rr in r.ready_to_read] == [9]

    def test_lease_off_heartbeats_carry_no_tag(self):
        r = mk_leader(lease_read=False)
        for _ in range(HEARTBEAT + 1):
            r.tick()
        hbs = [m for m in r.msgs if m.type == MT.HEARTBEAT]
        assert hbs and all(m.log_index == 0 for m in hbs)
        ack(r, 2, 0)
        assert not r.lease_valid() and r.lease_until == 0

    def test_config_rejects_bad_lease_shapes(self):
        def cfg(**kw):
            return Config(node_id=1, cluster_id=1, election_rtt=10,
                          heartbeat_rtt=2, lease_read=True, **kw)

        with pytest.raises(ConfigError):
            cfg(lease_margin_rtt=9).validate()
        with pytest.raises(ConfigError):
            cfg(lease_margin_rtt=-1).validate()
        with pytest.raises(ConfigError):
            cfg(is_witness=True).validate()
        with pytest.raises(ConfigError):
            cfg(is_observer=True).validate()
        cfg().validate()  # margin defaults to heartbeat_rtt: legal
        cfg(lease_margin_rtt=7).validate()  # < election - heartbeat


# --------------------------------------------------------------------------
# kernel: lease-off bit-identity guard + lease-on behavior
# --------------------------------------------------------------------------


def _elect(kc, max_rounds=300):
    for _ in range(max_rounds):
        kc.step()
        kc.settle()
        lead = kc.leader_of(0)
        if lead is not None:
            return lead
    raise AssertionError("no leader elected")


def test_kernel_lease_off_tensors_never_move():
    """Default-off guard: a full election + heartbeat + read workload
    leaves every lease tensor at zero and every heartbeat tag at 0 —
    the off-path is bit-identical to a pre-lease kernel."""
    kc = LoopbackCluster(
        n_replicas=N, n_groups=1, election=ELECTION, heartbeat=HEARTBEAT,
    )
    lead = _elect(kc)
    kc.propose(lead, 0, 2)
    kc.settle()
    kc.read_index(lead, 0, ctx=5)
    for _ in range(3 * HEARTBEAT):
        kc.step()
        kc.settle()
    for h in range(N):
        st = kc.states[h]
        for name in ("lease_on", "lease_until", "hb_round_tick",
                     "hb_ack_bits", "lease_margin"):
            assert not np.asarray(getattr(st, name)).any(), name
        o = kc.last_outputs[h]
        assert not np.asarray(o.lease_round).any()
        assert not np.asarray(o.lease_ok).any()
        assert not np.asarray(o.lease_served).any()
        assert not np.asarray(o.lease_fallback).any()


def test_kernel_lease_grant_and_local_read():
    """Lease ON: the periodic heartbeat round earns the lease from
    quorum acks; a ReadIndex then rides the immediate-ready path (served
    in the SAME step, no quorum round) and the served counter moves."""
    kc = LoopbackCluster(
        n_replicas=N, n_groups=1, election=ELECTION, heartbeat=HEARTBEAT,
        lease_read=True, lease_margin=HEARTBEAT,
    )
    lead = _elect(kc)
    kc.propose(lead, 0, 1)
    kc.settle()
    # run heartbeat rounds until the acks land and the lease is granted
    for _ in range(4 * HEARTBEAT):
        kc.step()
        kc.settle()
        if bool(np.asarray(kc.last_outputs[lead].lease_ok)[0]):
            break
    st = kc.states[lead]
    assert bool(np.asarray(kc.last_outputs[lead].lease_ok)[0])
    assert int(np.asarray(st.lease_until)[0]) > int(np.asarray(st.tick_count)[0])
    margin = int(np.asarray(st.lease_margin)[0])
    round_tick = int(np.asarray(st.hb_round_tick)[0])
    assert int(np.asarray(st.lease_until)[0]) <= round_tick + ELECTION - margin
    kc.ready_reads[lead].clear()
    kc.read_index(lead, 0, ctx=42)
    served_before = 0
    kc.step(tick=False)  # ONE step: no heartbeat round may be needed
    served = int(np.asarray(kc.last_outputs[lead].lease_served)[0])
    assert served == served_before + 1
    assert [ctx for (_g, ctx, _i, _c2) in kc.ready_reads[lead]] == [42]


# --------------------------------------------------------------------------
# kernel differential with leases ON (mirrors test_prevote's structure)
# --------------------------------------------------------------------------


class ScalarLeaseCluster:
    def __init__(self, seed_of_group):
        self.rafts = {}
        for nid in range(1, N + 1):
            r = Raft(
                Config(
                    node_id=nid, cluster_id=1, election_rtt=ELECTION,
                    heartbeat_rtt=HEARTBEAT, lease_read=True,
                ),
                InMemLogDB(),
            )
            for p in range(1, N + 1):
                r.remotes[p] = Remote(next=1)
            slot = nid - 1

            def patched(r=r, slot=slot):
                r.randomized_election_timeout = r.election_timeout + _mix(
                    seed_of_group, r.term, slot
                ) % r.election_timeout

            r.set_randomized_election_timeout = patched
            patched()
            self.rafts[nid] = r
        self.dropped_links = set()
        self.isolated = set()

    def tick_all(self):
        for r in self.rafts.values():
            r.tick()

    def _deliverable(self, m) -> bool:
        f, t = m.from_ - 1, m.to - 1
        if (f, t) in self.dropped_links:
            return False
        return f not in self.isolated and t not in self.isolated

    def settle(self, rounds=20):
        for _ in range(rounds):
            msgs = []
            for r in self.rafts.values():
                msgs.extend(m for m in r.msgs if not is_local_message(m.type))
                r.msgs = []
            if not msgs:
                return
            for m in msgs:
                if m.to in self.rafts and self._deliverable(m):
                    self.rafts[m.to].handle(m)

    def propose(self, nid, n=1):
        self.rafts[nid].handle(
            Message(
                type=MT.PROPOSE, from_=nid,
                entries=[Entry(cmd=b"p%d" % i) for i in range(n)],
            )
        )

    def read(self, nid, ctx):
        self.rafts[nid].handle(
            Message(type=MT.READ_INDEX, from_=nid, hint=ctx)
        )

    def observables(self):
        res = []
        for nid in range(1, N + 1):
            r = self.rafts[nid]
            res.append(
                {
                    "role": int(r.state),
                    "term": r.term,
                    "leader": r.leader_id - 1 if r.leader_id else -1,
                    "committed": r.log.committed,
                    "last": r.log.last_index(),
                    "lease": r.lease_valid(),
                }
            )
        return res

    def lease_counters(self):
        served = sum(r.lease_served for r in self.rafts.values())
        fb = sum(r.lease_fallback for r in self.rafts.values())
        return served, fb


def _kernel_lease_valid(st, g=0):
    return bool(
        np.asarray(st.lease_on)[g]
        and np.asarray(st.clock_ok)[g]
        and int(np.asarray(st.role)[g]) == ROLE.LEADER
        and int(np.asarray(st.tick_count)[g]) < int(np.asarray(st.lease_until)[g])
        and int(np.asarray(st.transfer_to)[g]) == 0
    )


def _kernel_observables(kc, g=0):
    res = []
    for h in range(kc.n_replicas):
        st = kc.states[h]
        res.append(
            {
                "role": int(np.asarray(st.role)[g]),
                "term": int(np.asarray(st.term)[g]),
                "leader": int(np.asarray(st.leader)[g]) - 1,
                "committed": int(np.asarray(st.committed)[g]),
                "last": int(np.asarray(st.last_index)[g]),
                "lease": _kernel_lease_valid(st, g),
            }
        )
    return res


@pytest.mark.parametrize("seed", [5, 23])
def test_differential_lease_randomized_faults(seed):
    """Kernel (lease ON) vs scalar oracle under a seeded schedule of
    link faults, isolation windows, proposals and reads: roles, terms,
    commit state, LEASE VALIDITY and the served/fallback counters must
    agree replica-for-replica after every settled round."""
    import random

    rng = random.Random(seed)
    kc = LoopbackCluster(
        n_replicas=N, n_groups=1, election=ELECTION, heartbeat=HEARTBEAT,
        lease_read=True, lease_margin=HEARTBEAT, seed=0,
    )
    seed_of_group = int(np.asarray(kc.states[0].seed)[0])
    sc = ScalarLeaseCluster(seed_of_group)
    totals = {"served": 0, "fallback": 0}
    orig_step = kc.step

    def counting_step(tick=True):
        orig_step(tick=tick)
        for h in range(N):
            o = kc.last_outputs[h]
            totals["served"] += int(np.asarray(o.lease_served).sum())
            totals["fallback"] += int(np.asarray(o.lease_fallback).sum())

    kc.step = counting_step
    next_ctx = [100]

    def run_round(proposals=0, reads=0):
        kc.step(tick=True)
        kc.settle()
        sc.tick_all()
        sc.settle()
        lead = kc.leader_of(0)
        if lead is not None:
            if proposals:
                kc.propose(lead, 0, proposals)
                sc.propose(lead + 1, proposals)
            for _ in range(reads):
                next_ctx[0] += 1
                kc.read_index(lead, 0, ctx=next_ctx[0])
                sc.read(lead + 1, next_ctx[0])
            if proposals or reads:
                kc.settle()
                sc.settle()

    for step in range(120):
        if rng.random() < 0.08:
            a, b = rng.sample(range(N), 2)
            kc.dropped_links.add((a, b))
            sc.dropped_links.add((a, b))
        if rng.random() < 0.08:
            kc.dropped_links.clear()
            sc.dropped_links.clear()
        if rng.random() < 0.04 and not kc.isolated:
            v = rng.randrange(N)
            kc.isolated.add(v)
            sc.isolated.add(v)
        if rng.random() < 0.10:
            kc.isolated.clear()
            sc.isolated.clear()
        run_round(
            proposals=1 if rng.random() < 0.25 else 0,
            reads=1 if rng.random() < 0.35 else 0,
        )
        ko = _kernel_observables(kc)
        so = sc.observables()
        assert ko == so, f"seed {seed} diverged at step {step}:\n{ko}\n{so}"
        assert (totals["served"], totals["fallback"]) == sc.lease_counters(), (
            f"seed {seed} lease counters diverged at step {step}"
        )
    # the schedule must actually have exercised the lease read path
    assert totals["served"] + totals["fallback"] > 0


# --------------------------------------------------------------------------
# NodeHost: the lease probe API + clock-fault degradation end to end
# --------------------------------------------------------------------------


class _KV(IStateMachine):
    def __init__(self, cluster_id, node_id):
        self.d = {}

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.d[k] = v
        return Result(value=1)

    def lookup(self, q):
        return self.d.get(q)

    def save_snapshot(self, w, files, done):
        import json

        w.write(json.dumps(self.d).encode())

    def recover_from_snapshot(self, r, files, done):
        import json

        self.d = json.loads(r.read().decode())


def _mk_host(nid, reg, workdir, engine_kind, cp=None, rtt_ms=5):
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=1,
            rtt_millisecond=rtt_ms,
            raft_address=f"lease:{nid}",
            nodehost_dir=os.path.join(workdir, f"nh{nid}"),
            raft_rpc_factory=lambda a: loopback_factory(a, reg),
            engine=EngineConfig(
                **engine_kw(engine_kind), max_groups=8, max_peers=4,
                log_window=64,
                share_scope="lease-test" if engine_kind != "scalar" else None,
            ),
        )
    )
    host_of_kind(nh, engine_kind)
    if cp is not None:
        nh.set_tick_clock(cp.clock_fn(str(nid)))
    return nh


def _start_cluster(hosts, lease_read=True):
    members = {nid: f"lease:{nid}" for nid in hosts}
    for nid, nh in hosts.items():
        nh.start_cluster(
            dict(members), False, lambda c, n: _KV(c, n),
            Config(
                node_id=nid, cluster_id=1, election_rtt=20, heartbeat_rtt=4,
                lease_read=lease_read,
            ),
        )


def _wait(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


def _leader_host(hosts):
    for nid, nh in hosts.items():
        lid, ok = nh.get_leader_id(1)
        if ok and lid in hosts:
            return lid
    return None


@pytest.mark.parametrize("engine_kind", ENGINE_KINDS)
def test_lease_probe_api_and_fallback(tmp_path, engine_kind):
    """`NodeHost.lease_read` (the explicit lease-only probe): serves off
    a live leader lease, raises the typed ErrLeaseExpired (an
    ErrSystemBusy: transient, retriable) everywhere else — while plain
    sync_read NEVER fails for lease reasons, it just falls back."""
    reg = _Registry()
    hosts = {n: _mk_host(n, reg, str(tmp_path), engine_kind) for n in (1, 2, 3)}
    try:
        _start_cluster(hosts)
        assert _wait(lambda: _leader_host(hosts) is not None)
        lead = _leader_host(hosts)
        sess = hosts[lead].get_noop_session(1)
        hosts[lead].sync_propose(sess, b"k=v", timeout_s=10.0)
        assert _wait(
            lambda: hosts[lead].engine.lease_valid(1), timeout=10.0
        ), "leader never earned its lease from quorum heartbeat acks"
        assert hosts[lead].lease_read(1, "k", timeout_s=10.0) == "v"
        follower = next(n for n in hosts if n != lead)
        with pytest.raises(ErrLeaseExpired) as ei:
            hosts[follower].lease_read(1, "k")
        assert isinstance(ei.value, ErrSystemBusy)
        assert ei.value.retry_after_s > 0
        # the non-probe read path on the same follower degrades, never
        # fails: it rides ReadIndex through the leader
        assert hosts[follower].sync_read(1, "k", timeout_s=10.0) == "v"
    finally:
        for nh in hosts.values():
            nh.stop()


def test_clock_jump_sheds_backlog_and_degrades_lease(tmp_path):
    """A ClockPlane step-jump on the leader's tick clock is detected as
    a clock ANOMALY: the lease goes on suspect hold (reads degrade to
    ReadIndex, still linearizable), the fairness gauge is NOT tripped
    (no phantom stall), and the phantom tick backlog is shed rather
    than burst-replayed through the election timers."""
    reg = _Registry()
    fp = FaultPlane(0xC10C)
    cp = ClockPlane(fp)
    hosts = {
        n: _mk_host(n, reg, str(tmp_path), "scalar", cp=cp) for n in (1, 2, 3)
    }
    try:
        _start_cluster(hosts)
        assert _wait(lambda: _leader_host(hosts) is not None)
        lead = _leader_host(hosts)
        nh = hosts[lead]
        nh.sync_propose(nh.get_noop_session(1), b"k=v1", timeout_s=10.0)
        assert _wait(lambda: nh.engine.lease_valid(1), timeout=10.0)
        ticks_before = nh.engine._nodes[1].peer.raft.tick_count
        term_before = nh.engine._nodes[1].peer.raft.term
        # +5s at rtt 5ms is a 1000-tick phantom backlog; the divergence
        # detector must fire LONG before the burst clamp would matter
        cp.step_jump(str(lead), 5.0)
        assert _wait(lambda: nh._clock_anomalies >= 1, timeout=5.0)
        assert not nh.engine.lease_valid(1)  # suspect hold revoked it
        time.sleep(0.3)
        ticks_after = nh.engine._nodes[1].peer.raft.tick_count
        # backlog shed: tick advance stays wall-clock-ish, nowhere near
        # the 1000 phantom ticks a naive replay would mint
        assert ticks_after - ticks_before < 300
        wd = nh.engine.fairness_stats()
        assert wd["clock_anomalies"] >= 1
        # the phantom gap was discarded from the stall gauge window
        assert wd["recent_max_gap_s"] < 1.0
        # no election was provoked: the quorum never saw a stall
        assert nh.engine._nodes[1].peer.raft.term == term_before
        # reads still linearize (served via ReadIndex fallback)
        assert hosts[lead].sync_read(1, "k", timeout_s=10.0) == "v1"
        # the healed clock re-earns the lease after the suspect hold
        cp.clear(str(lead))
        assert _wait(lambda: nh.engine.lease_valid(1), timeout=10.0)
        assert nh.lease_read(1, "k", timeout_s=10.0) == "v1"
    finally:
        for nh in hosts.values():
            nh.stop()
