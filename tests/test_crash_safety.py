"""Crash-safety disciplines (round 3): nodehost dir locks
(cf. internal/server/context.go:72-333) and ref-counted SM offload
(cf. internal/rsm/offload.go:48-133)."""
import pytest

from conftest import VECTOR_KINDS, engine_kw, host_of_kind
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import ErrDirLocked, NodeHost
from dragonboat_tpu.rsm.manager import From, OffloadedStatus
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import loopback_factory, _Registry


def _cfg(tmp_path, addr="L:1"):
    reg = _Registry()
    return NodeHostConfig(
        deployment_id=88, rtt_millisecond=5, raft_address=addr,
        nodehost_dir=str(tmp_path),
        raft_rpc_factory=lambda l: loopback_factory(l, reg),
        engine=EngineConfig(max_groups=8, max_peers=4, log_window=64),
    )


def test_second_nodehost_same_dir_fails_fast(tmp_path):
    nh = NodeHost(_cfg(tmp_path))
    try:
        with pytest.raises(ErrDirLocked):
            NodeHost(_cfg(tmp_path))
    finally:
        nh.stop()
    # the lock dies with the holder: reopening after stop works
    nh2 = NodeHost(_cfg(tmp_path))
    nh2.stop()


def test_different_dirs_do_not_conflict(tmp_path):
    nh1 = NodeHost(_cfg(tmp_path / "a", addr="L:1"))
    nh2 = NodeHost(_cfg(tmp_path / "b", addr="L:2"))
    nh1.stop()
    nh2.stop()


def test_offloaded_status_refcounting():
    st = OffloadedStatus()
    st.set_loaded(From.COMMIT_WORKER)
    st.set_loaded(From.SNAPSHOT_WORKER)
    # teardown requested while workers still hold references: no destroy
    assert st.set_offloaded(From.NODEHOST) is False
    assert st.set_offloaded(From.COMMIT_WORKER) is False
    # the LAST release triggers the destroy, exactly once
    assert st.set_offloaded(From.SNAPSHOT_WORKER) is True
    assert st.set_offloaded(From.SNAPSHOT_WORKER) is False
    assert st.set_offloaded(From.NODEHOST) is False


def test_offload_before_teardown_never_destroys():
    st = OffloadedStatus()
    st.set_loaded(From.COMMIT_WORKER)
    assert st.set_offloaded(From.COMMIT_WORKER) is False
    st.set_loaded(From.COMMIT_WORKER)
    assert st.set_offloaded(From.COMMIT_WORKER) is False
    assert st.set_offloaded(From.NODEHOST) is True


class DestroySM(IStateMachine):
    destroyed = 0

    def __init__(self, cluster_id, node_id):
        pass

    def update(self, data):
        return Result(value=1)

    def lookup(self, q):
        return None

    def save_snapshot(self, w, fc, done):
        w.write(b"\x00")

    def recover_from_snapshot(self, r, fc, done):
        pass

    def close(self):
        DestroySM.destroyed += 1


def test_sm_destroyed_exactly_once_on_stop(tmp_path):
    DestroySM.destroyed = 0
    nh = NodeHost(_cfg(tmp_path))
    nh.start_cluster(
        {1: "L:1"}, False, DestroySM,
        Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=2),
    )
    s = nh.get_noop_session(1)
    nh.sync_propose(s, b"x", 10.0)
    nh.stop()
    # one live SM instance destroyed once (the type-probe instance is
    # closed at start_cluster separately, see nodehost.start_cluster)
    assert DestroySM.destroyed >= 1


# ---------------------------------------------------------------------------
# restart-while-snapshotting (ISSUE 7 satellite): crash a node MID
# save_snapshot, restart it in process, and the rejoined node must come
# back clean — the half-written snapshot never becomes the recovery
# point, the abandoned save thread cannot corrupt the restarted node,
# and the recorded client history stays linearizable.
# ---------------------------------------------------------------------------
import json
import threading
import time

from dragonboat_tpu.lincheck import HistoryRecorder, check_kv_history
from dragonboat_tpu.requests import RequestError


class SlowSnapSM(IStateMachine):
    """KV SM whose save_snapshot parks on a gate so the test can crash
    the node while the save is provably in flight."""

    gate = threading.Event()
    saving = threading.Event()

    def __init__(self, cluster_id=0, node_id=0):
        self.d = {}

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.d[k] = v
        return Result(value=1)

    def lookup(self, q):
        return self.d.get(q)

    def get_hash(self):
        import zlib

        return zlib.crc32(json.dumps(sorted(self.d.items())).encode())

    def save_snapshot(self, w, files, done):
        SlowSnapSM.saving.set()
        SlowSnapSM.gate.wait(timeout=10.0)  # never hang the suite
        w.write(json.dumps(self.d).encode())

    def recover_from_snapshot(self, r, files, done):
        self.d = json.loads(r.read().decode())


def _mk_snap_host(nid, reg, tmp, members, engine_kind):
    cfg = NodeHostConfig(
        deployment_id=88, rtt_millisecond=5, raft_address=f"s{nid}:1",
        nodehost_dir=f"{tmp}/h{nid}",
        raft_rpc_factory=lambda l, reg=reg: loopback_factory(l, reg),
        engine=EngineConfig(
            **engine_kw(engine_kind), max_groups=32, max_peers=4,
            log_window=64,
        ),
    )
    nh = host_of_kind(NodeHost(cfg), engine_kind)
    nh.start_cluster(
        members, False, lambda c, n: SlowSnapSM(c, n),
        Config(cluster_id=1, node_id=nid, election_rtt=20, heartbeat_rtt=4),
    )
    return nh


@pytest.mark.parametrize("engine_kind", VECTOR_KINDS)
def test_crash_mid_save_snapshot_then_restart_rejoins(tmp_path, engine_kind):
    SlowSnapSM.gate.clear()
    SlowSnapSM.saving.clear()
    reg = _Registry()
    members = {n: f"s{n}:1" for n in (1, 2, 3)}
    hosts = {
        n: _mk_snap_host(n, reg, str(tmp_path), members, engine_kind)
        for n in (1, 2, 3)
    }
    rec = HistoryRecorder()

    def put(i):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            for nid, nh in hosts.items():
                try:
                    lid, ok = nh.get_leader_id(1)
                except Exception:
                    continue
                if not ok or lid != nid:
                    continue
                op = rec.invoke(0, ("put", "k", f"v{i}"))
                try:
                    s = nh.get_noop_session(1)
                    nh.sync_propose(s, f"k=v{i}".encode(), 2.0)
                    rec.complete(op, None)
                    return
                except RequestError:
                    rec.unknown(op)
            time.sleep(0.05)
        raise AssertionError(f"put {i} never committed")

    try:
        for i in range(5):
            put(i)
        # park a user snapshot save on the victim, then crash it mid-save
        leader, _ = hosts[1].get_leader_id(1)
        victim = next(n for n in (1, 2, 3) if n != leader)
        hosts[victim].request_snapshot(1, timeout_s=10.0)
        assert SlowSnapSM.saving.wait(timeout=20.0), "save never started"
        hosts[victim].crash_cluster(1)
        for i in range(5, 10):
            put(i)
        # restart with the save STILL parked: the rejoin must not depend
        # on (or be corrupted by) the abandoned save thread
        hosts[victim].restart_cluster(1)
        SlowSnapSM.gate.set()  # release the zombie save
        for i in range(10, 13):
            put(i)
        deadline = time.monotonic() + 45
        while time.monotonic() < deadline:
            idx = {n: hosts[n].get_applied_index(1) for n in (1, 2, 3)}
            if len(set(idx.values())) == 1:
                break
            time.sleep(0.05)
        else:
            raise AssertionError(f"rejoiner never converged: {idx}")
        hashes = {hosts[n].get_sm_hash(1) for n in (1, 2, 3)}
        assert len(hashes) == 1, "replica SMs diverged after mid-save crash"
        # the half-written snapshot must never surface as a recovery
        # point: whatever snapshot exists on the victim must be loadable
        node = hosts[victim]._get_node(1)
        ss = node.snapshotter.get_most_recent_snapshot()
        assert ss is None or ss.is_empty() or ss.index >= 0
        assert check_kv_history(rec.history(), max_states=2_000_000)
    finally:
        SlowSnapSM.gate.set()
        for nh in hosts.values():
            nh.stop()
