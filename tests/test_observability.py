"""Observability plane tests: latency histograms + Prometheus exposition
conformance, the flight recorder, reservoir sampling, the narrowed event
aggregator, and the end-to-end proposal-lifecycle instrumentation.

The exposition conformance test (minimal text-format parser) is the
regression net for the `_bucket`/`_sum`/`_count` contract: no duplicate
`# TYPE` lines, sorted label keys, monotone cumulative buckets, and a
`+Inf` bucket equal to `_count`.
"""
import io
import json
import os
import re
import time

import pytest

from dragonboat_tpu.events import (
    DEFAULT_LATENCY_BUCKETS,
    Histogram,
    MetricsRegistry,
    RaftEventAggregator,
)
from dragonboat_tpu.trace import (
    FlightRecorder,
    LatencySampler,
    Sample,
    flight_recorder,
)


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------


def test_histogram_observe_and_quantile():
    h = Histogram()
    for v in (0.001, 0.002, 0.004, 0.008, 0.016):
        h.observe(v)
    assert h.count == 5
    assert abs(h.sum - 0.031) < 1e-9
    q50 = h.quantile(0.5)
    q99 = h.quantile(0.99)
    assert 0.001 <= q50 <= 0.008
    assert q50 <= q99 <= 0.032
    # values beyond the last bound land in the +Inf overflow bucket and
    # quantiles saturate at the last finite bound
    h2 = Histogram()
    h2.observe(10_000.0)
    assert h2.quantile(0.99) == DEFAULT_LATENCY_BUCKETS[-1]
    assert Histogram().quantile(0.5) == 0.0


def test_histogram_merge():
    a, b = Histogram(), Histogram()
    for v in (0.001, 0.002):
        a.observe(v)
    for v in (0.004, 0.008):
        b.observe(v)
    a.merge(b)
    assert a.count == 4
    assert abs(a.sum - 0.015) < 1e-9
    with pytest.raises(ValueError):
        a.merge(Histogram(bounds=(1.0, 2.0)))


# ---------------------------------------------------------------------------
# Prometheus exposition conformance (satellite: minimal text-format parser)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? (\S+)$")


def _parse_exposition(text):
    """Minimal Prometheus text-format parser: returns (types, samples)
    where samples are (name, labels_dict, value, raw_label_keys)."""
    types = {}
    samples = []
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# TYPE "):
            _, _, rest = ln.partition("# TYPE ")
            name, kind = rest.rsplit(" ", 1)
            assert name not in types, f"duplicate # TYPE line for {name}"
            types[name] = kind
            continue
        assert not ln.startswith("#"), f"unexpected comment line: {ln}"
        m = _SAMPLE_RE.match(ln)
        assert m, f"unparseable sample line: {ln}"
        name, _, labelblock, value = m.groups()
        labels = {}
        keys = []
        if labelblock:
            for part in labelblock.split(","):
                k, _, v = part.partition("=")
                assert v.startswith('"') and v.endswith('"'), ln
                labels[k] = v.strip('"')
                keys.append(k)
        samples.append((name, labels, value, keys))
    return types, samples


def _populated_registry():
    m = MetricsRegistry()
    m.inc("raftnode_campaign_launched_total", (1, 2), 3)
    m.set_gauge("raftnode_term", (1, 2), 7)
    m.set_gauge("raftnode_term", (2, 1), 9)
    for v in (0.0001, 0.001, 0.01, 0.1, 1.5, 500.0):
        m.observe("proposal_commit_latency_seconds", (1, 2), v)
    for v in (0.002, 0.004):
        m.observe("fsync_latency_seconds", (0, 0), v)
    return m


def test_exposition_conformance():
    m = _populated_registry()
    out = io.StringIO()
    m.write(out)
    types, samples = _parse_exposition(out.getvalue())
    # every sample's family has exactly one TYPE line
    fams = set(types)
    for name, labels, value, keys in samples:
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in fams or base in fams, f"sample {name} missing # TYPE"
        # sorted label keys
        assert keys == sorted(keys), f"unsorted label keys in {name}{keys}"
    # histogram contract per label set
    hist = "dragonboat_tpu_proposal_commit_latency_seconds"
    assert types[hist] == "histogram"
    buckets = [
        (float("inf") if lb["le"] == "+Inf" else float(lb["le"]), float(v))
        for n, lb, v, _ in samples
        if n == hist + "_bucket"
    ]
    assert buckets == sorted(buckets), "buckets not in increasing le order"
    counts = [c for _, c in buckets]
    assert counts == sorted(counts), "cumulative bucket counts not monotone"
    count_v = [float(v) for n, _, v, _ in samples if n == hist + "_count"]
    sum_v = [float(v) for n, _, v, _ in samples if n == hist + "_sum"]
    assert len(count_v) == 1 and len(sum_v) == 1
    assert buckets[-1][0] == float("inf")
    assert buckets[-1][1] == count_v[0], "+Inf bucket != _count"
    assert count_v[0] == 6
    assert abs(sum_v[0] - 501.6111) < 1e-3


# ---------------------------------------------------------------------------
# reservoir Sample (satellite: long-run percentile bias fix)
# ---------------------------------------------------------------------------


def test_sample_reservoir_covers_whole_run():
    s = Sample("bias", cap=1000)
    n = 50_000
    for v in range(n):
        s.record(float(v))
    assert len(s) == n
    # the old fill-then-freeze cap kept only the first 1000 values, so the
    # p50 estimate would be ~500; reservoir sampling keeps it near n/2
    p50 = s.percentile(0.5)
    assert 0.4 * n < p50 < 0.6 * n, p50
    assert abs(s.mean() - (n - 1) / 2) < 1.0  # exact running mean


def test_sample_reservoir_deterministic():
    def run():
        s = Sample("det", cap=100)
        for v in range(10_000):
            s.record(float(v))
        return s.percentile(0.5), s.percentile(0.99)

    assert run() == run()


def test_latency_sampler_ratio():
    s = LatencySampler(4)
    got = sum(1 for _ in range(64) if s.sample())
    assert got == 16
    assert all(LatencySampler(1).sample() for _ in range(5))


# ---------------------------------------------------------------------------
# event aggregator __getattr__ narrowing (satellite)
# ---------------------------------------------------------------------------


def test_aggregator_optional_callbacks_are_noops():
    agg = RaftEventAggregator(MetricsRegistry())
    assert agg.membership_changed(1, 2) is None
    assert agg.connection_established("a", False) is None
    agg.stop()


def test_aggregator_rejects_typod_callbacks():
    agg = RaftEventAggregator(MetricsRegistry())
    try:
        with pytest.raises(AttributeError):
            agg.leader_updatd  # typo'd name must not resolve to a noop
        assert not hasattr(agg, "campaign_lunched")
        assert hasattr(agg, "campaign_launched")
        assert hasattr(agg, "membership_changed")
    finally:
        agg.stop()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_jsonl():
    rec = FlightRecorder(capacity=4)
    for i in range(6):
        rec.record("evt", i=i)
    assert len(rec) == 4  # bounded: oldest overwritten
    dump = rec.dump()
    assert [d["i"] for d in dump] == [2, 3, 4, 5]
    assert all("t" in d and d["event"] == "evt" for d in dump)
    ts = [d["t"] for d in dump]
    assert ts == sorted(ts)
    lines = rec.to_jsonl().splitlines()
    assert len(lines) == 4
    for ln in lines:
        json.loads(ln)  # every line parses as JSON
    rec.reset()
    assert len(rec) == 0 and rec.to_jsonl() == ""


def test_global_recorder_collects_fault_and_leader_events():
    from dragonboat_tpu.faults import FaultPlane, FaultSpec

    rec = flight_recorder()
    rec.reset()
    fp = FaultPlane(1234, FaultSpec(drop=1.0))
    assert fp.decide("wire:test", "drop", fp.spec.drop)
    agg = RaftEventAggregator(MetricsRegistry())
    agg.leader_updated(7, 1, 2, 3)
    agg.stop()
    events = {d["event"] for d in rec.dump()}
    assert "fault_injected" in events
    assert "leader_changed" in events
    by_kind = {d["event"]: d for d in rec.dump()}
    assert by_kind["fault_injected"]["site"] == "wire:test"
    assert by_kind["fault_injected"]["seed"] == 1234
    assert by_kind["leader_changed"]["cluster"] == 7
    assert by_kind["leader_changed"]["term"] == 3
    rec.reset()


def test_request_state_on_complete_chains():
    """The latency sampler registers on_complete on sampled reads BEFORE
    the caller sees the RequestState; a second (user/ABI) registration
    must chain, not replace — both callbacks fire exactly once, in
    registration order."""
    from dragonboat_tpu.requests import (
        REQUEST_COMPLETED,
        RequestResult,
        RequestState,
    )

    rs = RequestState()
    got = []
    rs.on_complete(lambda r: got.append(1))
    rs.on_complete(lambda r: got.append(2))
    rs.notify(RequestResult(code=REQUEST_COMPLETED))
    assert got == [1, 2]
    rs.on_complete(lambda r: got.append(3))  # late: fires immediately
    assert got == [1, 2, 3]


def test_faultykv_observer_measures_injected_stall():
    """fsync_latency must reflect the EFFECTIVE barrier including chaos
    stalls — the wrapper times (fault + inner sync), so a stall window
    shows up as the histogram spike the README's worked example promises."""
    from dragonboat_tpu.faults import FaultPlane, FaultSpec
    from dragonboat_tpu.storage.kv import MemKV, WriteBatch

    fp = FaultPlane(5, FaultSpec(fsync_stall=1.0, fsync_stall_s=(0.05, 0.05)))
    kv = fp.wrap_kv(MemKV(), "fs")
    seen = []
    kv.set_fsync_observer(seen.append)
    wb = WriteBatch()
    wb.put(b"k", b"v")
    kv.commit_write_batch(wb)
    kv.sync()
    assert len(seen) == 2
    assert all(dt >= 0.045 for dt in seen), seen


def test_breaker_and_sendq_record_transitions():
    from dragonboat_tpu.transport.transport import _Breaker, _SendQueue
    from dragonboat_tpu.types import Message, MessageType

    rec = flight_recorder()
    rec.reset()
    b = _Breaker(name="peer:1")
    b.fail()
    b.success()
    sq = _SendQueue(maxlen=1, name="peer:1")
    assert sq.try_put(Message(type=MessageType.REPLICATE, to=1, from_=2))
    # queue full of bulk: an urgent arrival evicts the oldest bulk
    assert sq.try_put(Message(type=MessageType.HEARTBEAT, to=1, from_=2))
    events = [d["event"] for d in rec.dump()]
    assert "breaker_open" in events
    assert "breaker_closed" in events
    assert "sendq_evicted_bulk" in events
    rec.reset()


# ---------------------------------------------------------------------------
# end-to-end: proposal lifecycle histograms + step stats + exposition
# ---------------------------------------------------------------------------


@pytest.fixture
def single_host(tmp_path):
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
    from tests.test_nodehost import KVSM

    reg = _Registry()
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=1,
            rtt_millisecond=5,
            raft_address="obs1:1",
            nodehost_dir=str(tmp_path),  # WAL-backed: real fsync barriers
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            enable_metrics=True,
            engine=EngineConfig(
                kind="vector",
                max_groups=8,
                max_peers=4,
                log_window=64,
                profile_sample_ratio=1,  # sample EVERY step
            ),
        )
    )
    # and EVERY request: the engine's own request sampler stops at 1 in
    # vector.REQUEST_SAMPLE_FLOOR
    nh.engine.request_sampler.ratio = 1
    try:
        nh.start_cluster(
            {1: "obs1:1"},
            False,
            lambda c, n: KVSM(c, n),
            Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=2),
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            lid, ok = nh.get_leader_id(1)
            if ok and lid == 1:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("no leader")
        yield nh
    finally:
        nh.stop()


def test_e2e_latency_histograms_and_step_stats(single_host):
    nh = single_host
    sess = nh.get_noop_session(1)
    for i in range(8):
        nh.sync_propose(sess, f"k{i}=v".encode(), timeout_s=10.0)
    rs = nh.read_index(1, 5.0)
    assert rs.wait(10.0).completed
    m = nh.metrics
    commit = m.histogram("proposal_commit_latency_seconds", (1, 1))
    apply_ = m.histogram("proposal_apply_latency_seconds", (1, 1))
    reads = m.histogram("readindex_latency_seconds", (1, 1))
    assert commit is not None and commit.count >= 8
    assert apply_ is not None and apply_.count >= 8
    assert reads is not None and reads.count >= 1
    # commit happens no later than the apply-side notify
    assert commit.quantile(0.5) <= apply_.quantile(0.99) + 1e-6
    assert 0 < commit.quantile(0.99) < 60.0
    # WAL fsync barriers were observed into the host-level histogram
    fsync = m.histogram("fsync_latency_seconds", (0, 0))
    assert fsync is not None and fsync.count > 0
    # vector step stats flowed through the engine facade
    st = nh.engine.step_stats()
    assert st["steps"] > 0
    assert st["lanes_commit_advanced"] > 0
    assert st["entries_applied"] >= 8
    nh._export_health_gauges()
    assert m.gauge_value("engine_step_steps", (0, 0)) > 0
    # and the whole plane renders as conformant Prometheus text
    out = io.StringIO()
    nh.write_health_metrics(out)
    text = out.getvalue()
    assert "proposal_commit_latency_seconds_bucket" in text
    assert "fsync_latency_seconds_count" in text
    types, samples = _parse_exposition(
        "\n".join(
            ln for ln in text.splitlines()
            if not ln.startswith("# TYPE dragonboat_tpu_transport_")
            and not ln.startswith("dragonboat_tpu_transport_")
        )
    )
    for name, labels, value, keys in samples:
        assert keys == sorted(keys)


def test_scalar_engine_lane_stats_parity(tmp_path):
    """ROADMAP PR-4 headroom item: ExecEngine.lane_stats() returns the
    same per-lane shape as VectorEngine.lane_stats(), so the
    engine_lane_* gauges cover the scalar engine too."""
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
    from tests.test_nodehost import KVSM

    reg = _Registry()
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=1,
            rtt_millisecond=5,
            raft_address="scl1:1",
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            enable_metrics=True,
            engine=EngineConfig(kind="scalar", max_groups=4, max_peers=4),
        )
    )
    try:
        nh.start_cluster(
            {1: "scl1:1"},
            False,
            lambda c, n: KVSM(c, n),
            Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=2),
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            lid, ok = nh.get_leader_id(1)
            if ok and lid == 1:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("no leader")
        sess = nh.get_noop_session(1)
        for i in range(4):
            nh.sync_propose(sess, f"k{i}=v".encode(), timeout_s=10.0)
        stats = nh.engine.lane_stats()
        assert 1 in stats, stats
        s = stats[1]
        # exact key parity with VectorEngine.lane_stats lanes
        assert set(s) == {
            "node_id",
            "leader_id",
            "term",
            "commit_gap",
            "last_index",
            "ticks_since_leader_change",
            "role",
            "payload_bytes",
        }
        assert s["last_index"] >= s["commit_gap"]
        assert s["role"] == 2  # this single node leads
        assert s["payload_bytes"] >= 0
        assert s["node_id"] == 1
        assert s["leader_id"] == 1
        assert s["term"] >= 1
        assert s["commit_gap"] >= 0
        # the election happened after tick 0, and ticks advanced since
        assert s["ticks_since_leader_change"] >= 0
        # gauges flow through the same _export_health_gauges seam
        nh._export_health_gauges()
        assert nh.metrics.gauge_value("engine_lane_leader_id", (1, 1)) == 1.0
        assert nh.metrics.gauge_value("engine_lane_term", (1, 1)) >= 1.0
    finally:
        nh.stop()


def test_census_and_counter_gauges_in_exposition(single_host):
    """ISSUE 18: the engine_hbm_* census gauges and engine_counter_*
    event gauges flow through _export_health_gauges into a conformant
    Prometheus exposition on a live vector host."""
    nh = single_host
    sess = nh.get_noop_session(1)
    for i in range(4):
        nh.sync_propose(sess, f"k{i}=v".encode(), timeout_s=10.0)
    nh._export_health_gauges()
    m = nh.metrics
    assert m.gauge_value("engine_hbm_bytes_total", (0, 0)) > 0
    assert m.gauge_value("engine_hbm_log_bytes", (0, 0)) > 0
    assert m.gauge_value("engine_hbm_log_fill_p50", (0, 0)) > 0.0
    assert m.gauge_value("engine_hbm_log_fill_p99", (0, 0)) > 0.0
    waste = m.gauge_value("engine_hbm_waste_ratio", (0, 0))
    assert 0.0 <= waste < 1.0
    assert m.gauge_value("engine_counter_elections_won", (0, 0)) >= 1.0
    assert m.gauge_value("engine_counter_commit_advances", (0, 0)) >= 4.0
    out = io.StringIO()
    nh.write_health_metrics(out)
    text = out.getvalue()
    assert "dragonboat_tpu_engine_hbm_bytes_total" in text
    assert "dragonboat_tpu_engine_counter_heartbeats_sent" in text
    types, samples = _parse_exposition(
        "\n".join(
            ln for ln in text.splitlines()
            if "_hbm_" in ln or "_counter_" in ln
        )
    )
    for name in (
        "dragonboat_tpu_engine_hbm_waste_ratio",
        "dragonboat_tpu_engine_counter_elections_started",
    ):
        assert types[name] == "gauge"


def test_history_gauges_in_exposition(single_host):
    """ISSUE 19: the engine_history_* sampler gauges are ALWAYS present
    (zero-filled with no sampler) and carry live counts once the host's
    HistorySampler runs, flowing through _export_health_gauges into a
    conformant Prometheus exposition."""
    nh = single_host
    # no sampler yet: gauges exist and read zero (stable dashboards)
    nh._export_health_gauges()
    m = nh.metrics
    assert m.gauge_value("engine_history_samples_total", (0, 0)) == 0.0
    assert m.gauge_value("engine_history_interval_seconds", (0, 0)) == 0.0
    nh.start_history(interval_s=0.02)
    try:
        deadline = time.time() + 10
        while time.time() < deadline:
            if nh._history.stats()["samples_total"] >= 2:
                break
            time.sleep(0.02)
        nh._export_health_gauges()
        assert m.gauge_value("engine_history_samples_total", (0, 0)) >= 2.0
        assert m.gauge_value("engine_history_errors_total", (0, 0)) == 0.0
        assert m.gauge_value("engine_history_interval_seconds", (0, 0)) > 0.0
    finally:
        nh.stop_history()
    out = io.StringIO()
    nh.write_health_metrics(out)
    text = out.getvalue()
    assert "dragonboat_tpu_engine_history_samples_total" in text
    types, _samples = _parse_exposition(
        "\n".join(ln for ln in text.splitlines() if "_history_" in ln)
    )
    assert types["dragonboat_tpu_engine_history_samples_total"] == "gauge"
    # the ring landed next to the host's WAL dir and reads back
    from dragonboat_tpu.profile import read_history

    ring = os.path.join(nh._dir, "history.ring")
    _meta, hist_samples = read_history(ring)
    assert hist_samples and hist_samples[-1]["host"] == "obs1:1"


def test_scalar_engine_counter_and_census_parity(tmp_path):
    """ISSUE 18: ExecEngine exposes the same counter_stats /
    lane_counters / device_census shapes as the vector engine (names =
    ops.state.CTR_NAMES; census always-present and all-zero — the
    scalar engine holds no device memory), so gauges and tools.top need
    not branch per engine."""
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.engine.execengine import _COUNTER_ATTRS
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.ops.state import CTR_NAMES
    from dragonboat_tpu.profile import CENSUS_KEYS
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
    from tests.test_nodehost import KVSM

    # the scalar twin's attribute list is pinned to the kernel's order
    assert _COUNTER_ATTRS == CTR_NAMES
    reg = _Registry()
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=1,
            rtt_millisecond=5,
            raft_address="sctr1:1",
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            enable_metrics=True,
            engine=EngineConfig(kind="scalar", max_groups=4, max_peers=4),
        )
    )
    try:
        nh.start_cluster(
            {1: "sctr1:1"},
            False,
            lambda c, n: KVSM(c, n),
            Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=2),
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            lid, ok = nh.get_leader_id(1)
            if ok and lid == 1:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("no leader")
        sess = nh.get_noop_session(1)
        for i in range(4):
            nh.sync_propose(sess, f"k{i}=v".encode(), timeout_s=10.0)
        counters = nh.engine.counter_stats()
        assert set(counters) == set(CTR_NAMES)
        assert counters["elections_won"] >= 1
        assert counters["commit_advances"] >= 4
        lanes = nh.engine.lane_counters()
        assert set(lanes) == {1}
        assert set(lanes[1]) == set(CTR_NAMES)
        census = nh.engine.device_census()
        assert set(CENSUS_KEYS) <= set(census)
        assert census["hbm_bytes_total"] == 0
        assert census["hbm_waste_ratio"] == 0.0
        # gauges flow through the same export seam as the vector engine
        nh._export_health_gauges()
        assert nh.metrics.gauge_value(
            "engine_counter_elections_won", (0, 0)
        ) >= 1.0
        assert nh.metrics.gauge_value(
            "engine_hbm_bytes_total", (0, 0)
        ) == 0.0
    finally:
        nh.stop()


def test_e2e_unsampled_requests_stay_traceless(tmp_path):
    """profile_sample_ratio=0 -> sparse default (1/32): a couple of
    proposals should mostly carry NO trace object (allocation-free hot
    path), while the sampler still exists."""
    from dragonboat_tpu.engine.execengine import ExecEngine
    from dragonboat_tpu.storage.logdb import ShardedLogDB

    db = ShardedLogDB()
    eng = ExecEngine(db)
    try:
        assert eng.request_sampler.ratio == 32
        assert [eng.request_sampler.sample() for _ in range(31)].count(True) == 0
        assert eng.request_sampler.sample() is True
    finally:
        eng.stop()
        db.close()
