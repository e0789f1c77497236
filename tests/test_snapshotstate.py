"""Snapshot FSM tests (cf. snapshotstate.go:28-214): slot discipline,
flags, index bookkeeping, and the node-level behaviors the FSM drives —
duplicate snapshot requests ignored, periodic saves finalized through the
step loop, recovery gating."""
import time

import pytest

from conftest import ENGINE_KINDS, engine_kw, host_of_kind
from dragonboat_tpu.engine.snapshotstate import SnapshotState, TaskSlot


class TestTaskSlot:
    def test_set_take(self):
        s = TaskSlot()
        assert not s.occupied()
        assert s.set("a")
        assert s.occupied()
        assert not s.set("b")  # occupied: rejected, not overwritten
        task, had = s.take()
        assert had and task == "a"
        task, had = s.take()
        assert not had and task is None
        assert s.set("b")  # free again


class TestSnapshotState:
    def test_flags(self):
        ss = SnapshotState()
        assert not ss.busy()
        ss.set_taking_snapshot()
        assert ss.taking_snapshot() and ss.busy()
        ss.clear_taking_snapshot()
        ss.set_recovering_from_snapshot()
        assert ss.recovering_from_snapshot() and ss.busy()
        ss.clear_recovering_from_snapshot()
        # streaming is a counter: overlapping lanes to different peers
        ss.begin_stream()
        ss.begin_stream()
        assert ss.streaming_snapshot() and not ss.busy()
        ss.end_stream()
        assert ss.streaming_snapshot()
        ss.end_stream()
        assert not ss.streaming_snapshot()
        assert not ss.busy()

    def test_compact_log_to_swap_read(self):
        ss = SnapshotState()
        assert not ss.has_compact_log_to()
        ss.set_compact_log_to(42)
        assert ss.has_compact_log_to()
        assert ss.get_compact_log_to() == 42
        assert ss.get_compact_log_to() == 0  # swap cleared it

    def test_indexes(self):
        ss = SnapshotState()
        ss.set_snapshot_index(7)
        ss.set_req_snapshot_index(9)
        assert ss.get_snapshot_index() == 7
        assert ss.get_req_snapshot_index() == 9


def _counter_sm():
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class SM(IStateMachine):
        def __init__(self):
            self.n = 0

        def update(self, data):
            self.n += 1
            return Result(value=self.n)

        def lookup(self, q):
            return self.n

        def save_snapshot(self, w, fc, done):
            w.write(self.n.to_bytes(8, "little"))

        def recover_from_snapshot(self, r, fc, done):
            self.n = int.from_bytes(r.read(8), "little")

        def close(self):
            pass

    return SM


@pytest.mark.parametrize("engine", ENGINE_KINDS)
def test_duplicate_snapshot_request_ignored(tmp_path, engine):
    """A second user snapshot request with nothing newly applied is
    rejected instead of writing an identical image (cf. node.go:1085-1091
    reqSnapshotIndex check)."""
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.requests import ErrRejected
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

    SM = _counter_sm()
    reg = _Registry()
    nh = host_of_kind(NodeHost(NodeHostConfig(
        deployment_id=91, rtt_millisecond=5, raft_address="ssf1:1",
        nodehost_dir=str(tmp_path / "nh1"),
        raft_rpc_factory=lambda l: loopback_factory(l, reg),
        engine=EngineConfig(**engine_kw(engine), max_groups=4, max_peers=4,
                            log_window=64),
    )), engine)
    try:
        nh.start_cluster({1: "ssf1:1"}, False, lambda c, n: SM(),
                         Config(cluster_id=1, node_id=1, election_rtt=20,
                                heartbeat_rtt=2))
        deadline = time.time() + 60
        while time.time() < deadline:
            _, ok = nh.get_leader_id(1)
            if ok:
                break
            time.sleep(0.02)
        assert ok
        s = nh.get_noop_session(1)
        for i in range(5):
            nh.sync_propose(s, b"x", timeout_s=5.0)

        idx = nh.sync_request_snapshot(1, timeout_s=15.0)
        assert idx > 0
        with pytest.raises(ErrRejected):
            nh.sync_request_snapshot(1, timeout_s=15.0)
        # new applies make the next request meaningful again
        nh.sync_propose(s, b"y", timeout_s=5.0)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                idx2 = nh.sync_request_snapshot(1, timeout_s=15.0)
                break
            except ErrRejected:
                time.sleep(0.1)  # applied cursor catching up
        assert idx2 > idx
        # FSM settled: flags clear, snapshot index recorded
        node = nh._get_node(1)
        assert not node.ss.busy()
        assert node.ss.get_snapshot_index() == idx2
    finally:
        nh.stop()


def test_periodic_snapshot_finalizes_through_step_loop(tmp_path):
    """snapshot_entries-triggered saves must finish through the completed
    slot: pending request acked, taking flag cleared, log compacted, and
    a restart recovers from the image."""
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

    SM = _counter_sm()
    reg = _Registry()

    def mk(restart=False):
        nh = NodeHost(NodeHostConfig(
            deployment_id=92, rtt_millisecond=5, raft_address="ssp1:1",
            nodehost_dir=str(tmp_path / "nh1"),
            raft_rpc_factory=lambda l, reg=reg: loopback_factory(l, reg),
            engine=EngineConfig(kind="scalar", max_groups=4, max_peers=4,
                                log_window=64),
        ))
        nh.start_cluster({} if restart else {1: "ssp1:1"}, False,
                         lambda c, n: SM(),
                         Config(cluster_id=1, node_id=1, election_rtt=20,
                                heartbeat_rtt=2, snapshot_entries=10,
                                compaction_overhead=3))
        return nh

    nh = mk()
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            _, ok = nh.get_leader_id(1)
            if ok:
                break
            time.sleep(0.02)
        assert ok
        s = nh.get_noop_session(1)
        for i in range(25):  # crosses snapshot_entries twice
            nh.sync_propose(s, b"x", timeout_s=5.0)
        node = nh._get_node(1)
        deadline = time.time() + 30
        while time.time() < deadline:
            if node.ss.get_snapshot_index() > 0 and not node.ss.busy():
                break
            time.sleep(0.05)
        assert node.ss.get_snapshot_index() > 0
        assert not node.ss.taking_snapshot()
    finally:
        nh.stop()

    nh = mk(restart=True)
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                if nh.stale_read(1, None) == 25:
                    break
            except Exception:
                pass
            time.sleep(0.05)
        assert nh.stale_read(1, None) == 25
    finally:
        nh.stop()
