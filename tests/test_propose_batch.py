"""Batch proposal API tests (propose_batch: one lock round-trip per wave;
the engines already replicate/persist/apply in batches — this extends
batching to the client boundary)."""
import time

import pytest

from conftest import ENGINE_KINDS, engine_kw, host_of_kind
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.requests import ErrInvalidSession
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory


class CounterSM(IStateMachine):
    def __init__(self, *a):
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


@pytest.mark.parametrize("engine", ENGINE_KINDS)
def test_propose_batch_commits_in_order(tmp_path, engine):
    reg = _Registry()
    nh = host_of_kind(NodeHost(NodeHostConfig(
        deployment_id=88, rtt_millisecond=5, raft_address="pb1:1",
        nodehost_dir=str(tmp_path / "nh"),
        raft_rpc_factory=lambda l: loopback_factory(l, reg),
        engine=EngineConfig(**engine_kw(engine), max_groups=4, max_peers=4,
                            log_window=64),
    )), engine)
    try:
        nh.start_cluster({1: "pb1:1"}, False, lambda c, n: CounterSM(),
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
        rss = nh.propose_batch(s, [b"x%d" % i for i in range(50)], 30.0)
        assert len(rss) == 50
        results = [rs.wait(30.0) for rs in rss]
        assert all(r.completed for r in results)
        # applied in submission order: update counter is sequential
        values = [r.result.value for r in results]
        assert values == sorted(values)
        assert nh.stale_read(1, None) == 50

        # a registered session may NOT batch: at-most-once bookkeeping is
        # strictly sequential
        sess = nh.sync_get_session(1, timeout_s=10.0)
        with pytest.raises(ErrInvalidSession):
            nh.propose_batch(sess, [b"a", b"b"], 10.0)
        nh.sync_close_session(sess, timeout_s=10.0)
    finally:
        nh.stop()


def test_propose_batch_overflow_drops_tail(tmp_path):
    """Past the incoming-queue capacity the tail completes as DROPPED
    (ErrClusterNotReady on unwrap) instead of failing the whole batch."""
    from dragonboat_tpu.settings import soft

    reg = _Registry()
    nh = NodeHost(NodeHostConfig(
        deployment_id=89, rtt_millisecond=5, raft_address="pb2:1",
        raft_rpc_factory=lambda l: loopback_factory(l, reg),
        engine=EngineConfig(kind="scalar", max_groups=4, max_peers=4,
                            log_window=64),
    ))
    try:
        nh.start_cluster({1: "pb2:1"}, False, lambda c, n: CounterSM(),
                         Config(cluster_id=1, node_id=1, election_rtt=20,
                                heartbeat_rtt=2))
        deadline = time.time() + 60
        while time.time() < deadline:
            _, ok = nh.get_leader_id(1)
            if ok:
                break
            time.sleep(0.02)
        assert ok, "no leader elected"
        s = nh.get_noop_session(1)
        n = soft.incoming_proposal_queue_length + 64
        rss = nh.propose_batch(s, [b"y"] * n, 30.0)
        assert len(rss) == n
        dropped = sum(
            1 for rs in rss if rs.wait(60.0).dropped
        )
        completed = sum(1 for rs in rss if rs.result and rs.result.completed)
        assert dropped > 0
        assert completed > 0
        assert dropped + completed == n
    finally:
        nh.stop()


@pytest.mark.parametrize("engine", ENGINE_KINDS)
def test_propose_batch_async_handle(tmp_path, engine):
    """propose_batch_async: ONE BatchRequestState for the whole batch,
    completion counted in runs (batch keys route by (batch_id, seq))."""
    reg = _Registry()
    nh = host_of_kind(NodeHost(NodeHostConfig(
        deployment_id=89, rtt_millisecond=5, raft_address="pba1:1",
        nodehost_dir=str(tmp_path / "nh"),
        raft_rpc_factory=lambda l: loopback_factory(l, reg),
        engine=EngineConfig(**engine_kw(engine), max_groups=4, max_peers=4,
                            log_window=64),
    )), engine)
    try:
        nh.start_cluster({1: "pba1:1"}, False, lambda c, n: CounterSM(),
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
        h = nh.propose_batch_async(s, [b"y%d" % i for i in range(200)], 30.0)
        assert h.wait(30.0)
        assert h.completed == 200
        assert h.dropped == 0
        assert nh.stale_read(1, None) == 200
        # a second batch reuses nothing from the first
        h2 = nh.propose_batch_async(s, [b"z"] * 10, 30.0)
        assert h2.wait(30.0)
        assert h2.completed == 10
        assert nh.stale_read(1, None) == 210
        # registered sessions may not batch
        sess = nh.sync_get_session(1, timeout_s=10.0)
        with pytest.raises(ErrInvalidSession):
            nh.propose_batch_async(sess, [b"a", b"b"], 5.0)
        nh.sync_close_session(sess, timeout_s=10.0)
    finally:
        nh.stop()
