"""Events, metrics, profiler, and logger subsystem tests
(cf. reference event.go, trace.go, logger/logger.go surfaces)."""
import io
import threading
import time

from dragonboat_tpu.events import MetricsRegistry, RaftEventAggregator
from dragonboat_tpu.logger import ILogger, get_logger, set_logger_factory
from dragonboat_tpu.raftio import IRaftEventListener, LeaderInfo
from dragonboat_tpu.trace import Profiler, Sample


def test_metrics_registry_counters_and_gauges():
    m = MetricsRegistry()
    m.inc("raftnode_campaign_launched_total", (1, 2))
    m.inc("raftnode_campaign_launched_total", (1, 2))
    m.set_gauge("raftnode_term", (1, 2), 7)
    assert m.counter_value("raftnode_campaign_launched_total", (1, 2)) == 2
    assert m.gauge_value("raftnode_term", (1, 2)) == 7
    out = io.StringIO()
    m.write(out)
    text = out.getvalue()
    assert (
        'dragonboat_tpu_raftnode_campaign_launched_total{clusterid="1",nodeid="2"} 2'
        in text
    )
    assert "# TYPE dragonboat_tpu_raftnode_term gauge" in text


def test_aggregator_updates_metrics_and_forwards_leader():
    got = []
    done = threading.Event()

    class L(IRaftEventListener):
        def leader_updated(self, info: LeaderInfo) -> None:
            got.append(info)
            done.set()

    m = MetricsRegistry()
    agg = RaftEventAggregator(m, user_listener=L(), enable_metrics=True)
    agg.leader_updated(9, 3, 2, 5)
    agg.campaign_launched(9, 3, 5)
    agg.proposal_dropped(9, 3, [1, 2, 3])
    assert done.wait(2)
    agg.stop()
    assert got[0].cluster_id == 9 and got[0].leader_id == 2 and got[0].term == 5
    assert m.gauge_value("raftnode_has_leader", (9, 3)) == 1.0
    assert m.counter_value("raftnode_campaign_launched_total", (9, 3)) == 1
    assert m.counter_value("raftnode_proposal_dropped_total", (9, 3)) == 3


def test_aggregator_survives_listener_exception():
    class Bad(IRaftEventListener):
        def leader_updated(self, info):
            raise RuntimeError("boom")

    m = MetricsRegistry()
    agg = RaftEventAggregator(m, user_listener=Bad())
    agg.leader_updated(1, 1, 1, 1)
    time.sleep(0.05)
    agg.leader_updated(1, 1, 2, 2)  # dispatcher still alive
    time.sleep(0.05)
    agg.stop()
    assert m.gauge_value("raftnode_leader_id", (1, 1)) == 2.0


def test_metrics_disabled():
    m = MetricsRegistry()
    agg = RaftEventAggregator(m, enable_metrics=False)
    agg.campaign_launched(1, 1, 1)
    assert m.counter_value("raftnode_campaign_launched_total", (1, 1)) == 0
    agg.stop()


def test_sample_percentiles():
    s = Sample("x")
    for v in range(1, 101):
        s.record(float(v))
    assert s.percentile(0.5) == 51.0
    assert s.percentile(0.99) == 100.0
    assert 50.0 <= s.mean() <= 51.0


def test_profiler_samples_at_ratio():
    p = Profiler(sample_ratio=4)
    for _ in range(16):
        p.new_iteration()
        p.start()
        p.end("step")
    assert len(p.samples["step"]) == 4


def test_logger_factory_swap_retroactive():
    lines = []

    class Rec(ILogger):
        def __init__(self, pkg):
            self.pkg = pkg

        def set_level(self, level):
            pass

        def debugf(self, fmt, *a):
            lines.append(("D", self.pkg, fmt % a if a else fmt))

        def infof(self, fmt, *a):
            lines.append(("I", self.pkg, fmt % a if a else fmt))

        def warningf(self, fmt, *a):
            lines.append(("W", self.pkg, fmt % a if a else fmt))

        def errorf(self, fmt, *a):
            lines.append(("E", self.pkg, fmt % a if a else fmt))

        def panicf(self, fmt, *a):
            raise RuntimeError(fmt)

    log = get_logger("testpkg")  # handed out BEFORE the swap
    try:
        set_logger_factory(Rec)
        log.infof("hello %d", 42)
        assert lines == [("I", "testpkg", "hello 42")]
    finally:
        from dragonboat_tpu.logger import StdLogger

        set_logger_factory(StdLogger)


def test_nodehost_health_metrics_end_to_end():
    from dragonboat_tpu.config import Config, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
    from tests.test_nodehost import KVSM as KVStateMachine

    reg = _Registry()
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=1, rtt_millisecond=5, raft_address="m1:1",
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            enable_metrics=True,
        )
    )
    try:
        nh.start_cluster(
            {1: "m1:1"}, False, lambda c, n: KVStateMachine(c, n),
            Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=2),
        )
        deadline = time.time() + 40
        while time.time() < deadline:
            lid, ok = nh.get_leader_id(1)
            if ok and lid == 1:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("no leader")
        out = io.StringIO()
        nh.write_health_metrics(out)
        text = out.getvalue()
        assert 'raftnode_has_leader{clusterid="1",nodeid="1"} 1' in text
        assert "transport_" in text
    finally:
        nh.stop()


def test_engine_profiler_disabled_by_default_enabled_on_request():
    from dragonboat_tpu.engine.execengine import ExecEngine
    from dragonboat_tpu.storage.logdb import ShardedLogDB

    db = ShardedLogDB()
    eng = ExecEngine(db)  # soft.latency_sample_ratio defaults to 0
    assert eng.profilers == []
    eng.stop()

    eng2 = ExecEngine(db, sample_ratio=4)
    assert len(eng2.profilers) == len(eng2._threads) - eng2._n_task - eng2._n_snap
    eng2.exec_nodes([], worker=0)
    eng2.stop()
    db.close()
