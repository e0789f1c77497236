"""Perf attribution plane tests (dragonboat_tpu.profile).

Four subjects:

  * sampling discipline — unsampled profiler iterations must stay
    allocation- and event-free with the phase plane wired in (zero
    recorder events, zero Histogram observations on the off path);
  * the runtime device-sync audit — call-site attribution, blessed-seam
    classification, install/uninstall hygiene;
  * the compile watch — per-jitted-function retrace attribution;
  * the tier-1 acceptance assertion (`-m perf`): a live vector-engine
    scenario performs ZERO out-of-seam device syncs and ZERO
    steady-state XLA compiles, while the phase plane, the gauges and the
    Prometheus exposition all carry the attribution.
"""
from __future__ import annotations

import contextlib
import gzip
import io
import json
import os
import time

import pytest

from dragonboat_tpu.profile import (
    EXEC_PHASES,
    VECTOR_PHASES,
    PhasePlane,
    compile_watch,
    diff_compiles,
    diff_sync,
    phase_plane,
    sync_audit,
    write_exposition,
)
from dragonboat_tpu.trace import Profiler, flight_recorder


# ---------------------------------------------------------------------------
# sampling discipline (satellite: the off path stays event-free)
# ---------------------------------------------------------------------------


def _drive(prof):
    """One iteration's worth of every way a profiler is fed on the loop
    thread: a begin() chain, a start/end pair, a sub-span."""
    prof.new_iteration()
    prof.begin("wait")
    prof.begin("pack")
    prof.begin("dispatch")
    prof.start()
    prof.end("step")
    prof.add("deliver", 0.001)


def _unsampled_profiler():
    """(b), the profiler alone: iterations 1..3 of ratio 4 are never
    sampled and leave nothing anywhere; iteration 4 fills samples and
    histograms but, at SPARSE sampling, no span (spans would crowd the
    store at the always-on production default)."""
    plane = PhasePlane()
    prof = Profiler(sample_ratio=4)
    prof.attach_phase_plane(plane, "vector", idle_head=("wait", "pack"))
    rec = flight_recorder()
    rec.reset()
    for _ in range(3):
        _drive(prof)
        assert not prof.sampling
    exposition = io.StringIO()
    plane.write(exposition)
    assert exposition.getvalue() == "", "histogram observed off-path"
    assert prof.samples == {}, "sample created off-path"
    assert len(rec) == 0, "recorder event on the unsampled path"
    assert rec.open_spans == {}, "running span published off-path"
    _drive(prof)
    assert prof.sampling
    prof.new_iteration()  # unsampled again: closes the running span
    assert plane.histogram("vector", "pack").count == 1
    assert plane.histogram("vector", "dispatch").count == 1
    assert plane.histogram("vector", "step").count == 1
    assert plane.histogram("vector.sub", "deliver").count == 1
    assert len(prof.samples["pack.cpu"]) == 1, "no CPU companion"
    assert "deliver.cpu" not in prof.samples and "step.cpu" not in prof.samples
    assert len(rec) == 0, "phase_span recorded at sparse sampling"
    assert rec.open_spans == {}


def _unsampled_engine(tmp_path, monkeypatch):
    """(b), a live engine whose ratio never comes up: writes, a batch
    and reads go through, and no span, no sample, no request trace and
    no block_until_ready came of them."""
    import jax

    from dragonboat_tpu.engine import node as node_mod

    made, blocked = [], []
    real_trace = node_mod.LatencyTrace
    monkeypatch.setattr(
        node_mod, "LatencyTrace",
        lambda *a, **k: made.append(1) or real_trace(*a, **k),
    )
    real_block = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: blocked.append(1) or real_block(x),
    )
    with _single_host(tmp_path, profile_sample_ratio=1 << 30) as nh:
        flight_recorder().reset()
        sess = nh.get_noop_session(1)
        for i in range(4):
            nh.sync_propose(sess, f"k{i}=v".encode(), timeout_s=10.0)
        h = nh.propose_batch_async(sess, [b"a=1", b"b=2"], 5.0)
        assert h.wait(10.0) and h.completed == 2
        rs = nh.read_index(1, 5.0)
        assert rs.wait(10.0).completed
        assert rs.lat is None
        prof = nh.engine.core.profiler
        assert not prof.sampling
        assert prof.samples == {}, sorted(prof.samples)
        assert nh.engine.step_stats()["launches"] > 0
    rec = flight_recorder()
    assert rec.dump(event="phase_span") == []
    assert not [e for e in rec.dump() if "trace" in e], "chain event"
    assert not made, "an unsampled request allocated a LatencyTrace"
    assert not blocked, "block_until_ready on an unsampled iteration"


@pytest.mark.parametrize("case", ["profiler", "engine"])
def test_unsampled_iterations_stay_event_free(case, tmp_path, monkeypatch):
    if case == "profiler":
        _unsampled_profiler()
    else:
        _unsampled_engine(tmp_path, monkeypatch)


def test_full_sampling_emits_recorder_spans():
    """Spans reach the flight recorder only at ratio 1 (the traced run's
    and debugging's opt-in, EngineConfig.profile_sample_ratio=1): the
    loop's own, each with the instant it ended as `t` and the instant it
    began as `t0`;
    a sub-span leaves a histogram under `<kind>.sub` and no event."""
    plane = PhasePlane()
    prof = Profiler(sample_ratio=1)
    prof.attach_phase_plane(plane, "vector")
    rec = flight_recorder()
    rec.reset()
    t0 = time.monotonic()
    prof.new_iteration()
    prof.start()
    prof.end("pack")
    prof.add("deliver", 0.001)
    t1 = time.monotonic()
    (e,) = rec.dump(event="phase_span")
    assert (e["engine"], e["phase"]) == ("vector", "pack")
    assert t0 <= e["t0"] <= e["t"] <= t1 and e["dur"] == e["t"] - e["t0"]
    assert plane.histogram("vector.sub", "deliver").count == 1


def test_phase_vocabulary_covers_both_engines():
    # the canonical span names; decode phases 0-6 all named
    for p in ("wait", "prepare", "pack", "dispatch", "fetch", "place",
              "send_rep", "save", "send_resp", "apply", "reads", "maintain",
              "deliver", "put", "launch", "device_wait", "copy"):
        assert p in VECTOR_PHASES
    for p in ("step", "fast_apply", "send", "save", "apply", "exec"):
        assert p in EXEC_PHASES


def test_plane_exposition_is_conformant():
    from tests.test_observability import _parse_exposition

    plane = PhasePlane()
    plane.on_phase("vector", "pack", 0.002, True)
    plane.on_phase("vector", "save", 0.004, True)
    plane.on_phase("exec", "step", 0.001, True)
    out = io.StringIO()
    plane.write(out)
    types, samples = _parse_exposition(out.getvalue())
    assert types["dragonboat_tpu_engine_phase_seconds"] == "histogram"
    engines = {lb.get("engine") for _, lb, _, _ in samples}
    phases = {lb.get("phase") for _, lb, _, _ in samples}
    assert engines == {"vector", "exec"}
    assert {"pack", "save", "step"} <= phases
    for name, _, _, keys in samples:
        assert keys == sorted(keys), f"unsorted label keys in {name}"
    counts = [
        float(v) for n, lb, v, _ in samples
        if n.endswith("_count") and lb.get("phase") == "pack"
    ]
    assert counts == [1.0]


# ---------------------------------------------------------------------------
# runtime device-sync audit
# ---------------------------------------------------------------------------


def test_sync_audit_attributes_out_of_seam_sites():
    import jax.numpy as jnp

    sa = sync_audit()
    before = sa.snapshot()
    sa.install()
    try:
        import jax

        jax.device_get(jnp.zeros(2))  # out-of-seam: this very line
        jax.block_until_ready(jnp.zeros(2))
    finally:
        sa.uninstall()
    after = sa.snapshot()
    d = diff_sync(before, after)
    assert d["out_of_seam"] == 2
    assert any("test_profile.py" in s for s in d["sites"])
    # the test file is NOT package code: the tier-1 filter excludes it
    own = {
        s: n for s, n in sa.out_of_seam_in_package().items()
        if "test_profile.py" in s
    }
    assert not own
    # uninstall really restored the originals
    import jax

    assert not sa.installed
    jax.device_get(jnp.zeros(2))
    assert sa.snapshot()["out_of_seam"] == after["out_of_seam"]


def test_compile_watch_attributes_retraces_per_function():
    import jax
    import jax.numpy as jnp

    cw = compile_watch().install()
    fn = jax.jit(lambda x: x * 2)
    cw.register("test_fn", fn)
    cw.register("test_fn", fn)  # idempotent: no double counting
    mark = cw.snapshot()
    fn(jnp.ones(3))
    fn(jnp.ones(3))  # warm: no new trace
    d1 = diff_compiles(mark, cw.snapshot())
    assert d1["per_function"].get("test_fn") == 1
    assert d1["total"] >= 1
    fn(jnp.ones(5))  # RETRACE: new shape
    d2 = diff_compiles(mark, cw.snapshot())
    assert d2["per_function"].get("test_fn") == 2
    assert d2["total"] > d1["total"]
    # weakly held: dropping the function must release it (the watch
    # never pins a dead engine's compiled executables) and its entry
    # reads zero rather than a stale cache size
    del fn
    import gc

    gc.collect()
    assert cw.per_function().get("test_fn", 0) == 0


# ---------------------------------------------------------------------------
# live vector-engine scenario: the tier-1 acceptance assertions
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _single_host(tmp_path, **engine):
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
    from tests.test_nodehost import KVSM

    reg = _Registry()
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=1,
            rtt_millisecond=5,
            raft_address="perf1:1",
            nodehost_dir=str(tmp_path),
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            enable_metrics=True,
            engine=EngineConfig(
                kind="vector", max_groups=8, max_peers=4, log_window=64,
                **engine,
            ),
        )
    )
    try:
        nh.start_cluster(
            {1: "perf1:1"},
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


@pytest.fixture()
def vec_host(tmp_path):
    with _single_host(tmp_path, profile_sample_ratio=1) as nh:  # EVERY step
        yield nh


@pytest.mark.perf
def test_vector_scenario_runtime_audit_clean(vec_host):
    """Acceptance: during a live vector-engine scenario the ONLY
    device->host transfers are the blessed `_fetch_output` seam's, and
    steady state compiles nothing — the runtime twins of the `-m lint`
    device-sync/retrace gates, asserted on real behavior."""
    nh = vec_host
    sa = sync_audit().install()
    cw = compile_watch().install()
    try:
        sess = nh.get_noop_session(1)
        # warm: first proposals may still trigger legitimate lazy
        # compiles (activation scatters etc.)
        for i in range(4):
            nh.sync_propose(sess, f"w{i}=v".encode(), timeout_s=10.0)
        sync_mark = sa.snapshot()
        pkg_mark = dict(sa.out_of_seam_in_package())
        compile_mark = cw.snapshot()
        for i in range(8):
            nh.sync_propose(sess, f"k{i}=v".encode(), timeout_s=10.0)
        rs = nh.read_index(1, 5.0)
        assert rs.wait(10.0).completed
        sync_now = sa.snapshot()
        # the seam kept transferring (the engine stepped)...
        assert sync_now["in_seam"] > sync_mark["in_seam"]
        # ...and NOTHING ELSE in the package synced the device
        new_pkg = {
            s: n for s, n in sa.out_of_seam_in_package().items()
            if n > pkg_mark.get(s, 0)
        }
        assert not new_pkg, f"out-of-seam device syncs at {new_pkg}"
        # zero steady-state retraces, attributed per jitted function
        d = diff_compiles(compile_mark, cw.snapshot())
        assert d["total"] == 0, f"steady-state XLA compiles: {d}"
        assert not d["per_function"]
    finally:
        sa.uninstall()
    # the phase plane saw every vector step phase that ran
    plane = phase_plane()
    for phase in ("pack", "dispatch", "fetch", "place", "save", "apply"):
        h = plane.histogram("vector", phase)
        assert h is not None and h.count > 0, f"phase {phase} unattributed"
    # gauges + exposition carry the audit
    nh._export_health_gauges()
    m = nh.metrics
    assert m.gauge_value("engine_device_syncs_total", (0, 0)) > 0
    assert m.gauge_value("engine_device_syncs_out_of_seam", (0, 0)) is not None
    assert m.gauge_value("engine_compile_events_total", (0, 0)) is not None
    out = io.StringIO()
    nh.write_health_metrics(out)
    text = out.getvalue()
    assert "engine_phase_seconds_bucket" in text
    assert 'phase="fetch"' in text
    assert "engine_compile_cache_entries" in text
    # registered jitted functions are named in the exposition
    assert "step_batch[g8]" in text


def test_census_snapshot_survives_concurrent_lane_activation():
    """Exporter threads (NodeHost tick workers, the history sampler) call
    DeviceCensus.snapshot on the engine's LIVE mirrors while the loop
    thread activates lanes. Indexing with the live mask let numpy count
    few Trues, then copy many, past the end of its output buffer (found
    on the TPU host as `malloc(): invalid size`); the snapshot must work
    on private copies."""
    import sys
    import threading

    import numpy as np

    from dragonboat_tpu.profile import DeviceCensus

    G, W = 200_000, 64
    census = DeviceCensus()
    census.set_planes(
        {"state.log_term": G * W * 4}, log_planes=("state.log_term",),
        log_window=W,
    )
    active = np.zeros(G, bool)
    last = np.full(G, 10, np.int64)
    first = np.ones(G, np.int64)
    stop = threading.Event()

    def activate_and_clear():
        while not stop.is_set():
            active[:] = True
            active[:] = False

    t = threading.Thread(target=activate_and_clear, daemon=True)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t.start()
        deadline = time.monotonic() + 0.5
        while time.monotonic() < deadline:
            c = census.snapshot(last=last, devfirst=first, active=active)
            assert 0 <= c["lanes_active"] <= G
            assert 0.0 <= c["hbm_waste_ratio"] <= 1.0
    finally:
        stop.set()
        t.join(timeout=10)
        sys.setswitchinterval(old)
    assert not t.is_alive()


@pytest.mark.perf
def test_census_and_counters_add_zero_syncs(vec_host):
    """Acceptance (ISSUE 18): reading the HBM census and the counter
    plane on a LIVE vector scenario adds ZERO out-of-seam device syncs
    and zero steady-state retraces — census physical bytes come from
    init-time tensor metadata, logical fill and counters fold from the
    decode-maintained numpy mirrors."""
    nh = vec_host
    sa = sync_audit().install()
    cw = compile_watch().install()
    try:
        sess = nh.get_noop_session(1)
        for i in range(4):
            nh.sync_propose(sess, f"c{i}=v".encode(), timeout_s=10.0)
        pkg_mark = dict(sa.out_of_seam_in_package())
        compile_mark = cw.snapshot()
        census = counters = lanes = None
        for i in range(4):
            census = nh.engine.device_census()
            counters = nh.engine.counter_stats()
            lanes = nh.engine.lane_counters()
            nh.sync_propose(sess, f"z{i}=v".encode(), timeout_s=10.0)
        new_pkg = {
            s: n for s, n in sa.out_of_seam_in_package().items()
            if n > pkg_mark.get(s, 0)
        }
        assert not new_pkg, f"telemetry read synced the device at {new_pkg}"
        d = diff_compiles(compile_mark, cw.snapshot())
        assert d["total"] == 0, f"telemetry read retraced: {d}"
    finally:
        sa.uninstall()
    # the census reports this engine's real planes + this lane's fill
    assert census["hbm_bytes_total"] > 0
    assert 0 < census["hbm_log_bytes"] < census["hbm_bytes_total"]
    assert census["lanes_active"] == 1
    assert census["log_window"] == 64
    assert 0.0 < census["log_fill_p50"] <= 1.0
    assert 0.0 <= census["hbm_waste_ratio"] < 1.0
    assert "state.log_term" in census["planes"]
    # the counter plane moved: this lane elected itself and committed
    from dragonboat_tpu.ops.state import CTR_NAMES

    assert set(counters) == set(CTR_NAMES)
    assert counters["elections_won"] >= 1
    assert counters["commit_advances"] >= 8
    assert set(lanes) == {1}
    assert lanes[1]["commit_advances"] == counters["commit_advances"]


@pytest.mark.perf
def test_history_sampler_adds_zero_syncs_and_zero_retraces(vec_host, tmp_path):
    """Acceptance (ISSUE 19 tentpole): a LIVE HistorySampler ticking at
    a hot cadence over a vector host adds ZERO out-of-seam device syncs
    and zero steady-state retraces — every snapshotted source is a
    zero-sync stat export (decode-maintained numpy mirrors / plain
    ints) and the ring write is pure host-side json+mmap."""
    from dragonboat_tpu.profile import (
        HISTORY_STATS_KEYS,
        HistorySampler,
        read_history,
    )

    nh = vec_host
    sa = sync_audit().install()
    cw = compile_watch().install()
    ring = str(tmp_path / "hist" / "history.ring")
    os.makedirs(os.path.dirname(ring))
    sampler = None
    try:
        sess = nh.get_noop_session(1)
        for i in range(4):
            nh.sync_propose(sess, f"w{i}=v".encode(), timeout_s=10.0)
        pkg_mark = dict(sa.out_of_seam_in_package())
        compile_mark = cw.snapshot()
        sampler = HistorySampler(ring, {0: nh}, interval_s=0.02).start()
        try:
            for i in range(8):
                nh.sync_propose(sess, f"h{i}=v".encode(), timeout_s=10.0)
            time.sleep(0.1)  # several sampler ticks land mid-traffic
        finally:
            sampler.stop()
        new_pkg = {
            s: n for s, n in sa.out_of_seam_in_package().items()
            if n > pkg_mark.get(s, 0)
        }
        assert not new_pkg, f"history sampling synced the device at {new_pkg}"
        d = diff_compiles(compile_mark, cw.snapshot())
        assert d["total"] == 0, f"history sampling retraced: {d}"
    finally:
        sa.uninstall()
    st = sampler.stats()
    assert list(st) == list(HISTORY_STATS_KEYS)
    assert st["samples_total"] >= 2 and st["errors_total"] == 0
    _meta, samples = read_history(ring)
    assert len(samples) == st["samples_total"]
    last = samples[-1]
    assert last["event"] == "history_sample" and last["schema"] == 1
    assert last["host"] == "perf1:1"
    lane = last["lanes"]["1"]  # json object keys stringify
    assert lane["leader_id"] == 1 and lane["commit_gap"] >= 0
    assert lane["counters"]["commit_advances"] >= 8
    assert last["counters"]["elections_won"] >= 1
    assert last["census"]["hbm_bytes_total"] > 0
    assert last.get("errors", []) == []


@pytest.mark.perf
def test_write_exposition_standalone():
    out = io.StringIO()
    write_exposition(out)  # whatever the process accumulated so far
    # never raises; emits nothing or conformant families only
    for ln in out.getvalue().splitlines():
        assert ln.startswith("#") or "dragonboat_tpu_" in ln


# ---------------------------------------------------------------------------
# dump_flight artifact discipline (satellite: cap + gzip rotation) and
# the timeline CLI's transparent .gz / --spans rendering
# ---------------------------------------------------------------------------


def test_dump_flight_cap_and_gzip_rotation(vec_host, tmp_path):
    from dragonboat_tpu.tools import timeline

    rec = flight_recorder()
    for i in range(400):
        rec.record("noise", cluster=1, seq=i, pad="x" * 64)
    path = str(tmp_path / "dump.jsonl")
    vec_host.dump_flight(path, max_bytes=8192)
    assert os.path.getsize(path) <= 8192 + 512  # meta line slack
    with open(path) as f:
        meta = json.loads(f.readline())
    assert meta["event"] == "_meta"
    assert meta["dropped_events"] > 0
    # the kept tail is the NEWEST events
    evs = timeline.load_dump(path)
    noise = [e for e in evs if e["event"] == "noise"]
    assert noise and noise[-1]["seq"] == 399
    # second dump rotates the first to a gzip artifact
    vec_host.dump_flight(path, max_bytes=8192)
    rotated = path + ".1.gz"
    assert os.path.exists(rotated)
    with gzip.open(rotated, "rt") as f:
        assert json.loads(f.readline())["event"] == "_meta"
    # timeline reads the rotated .gz transparently (by magic, not name)
    evs_gz = timeline.load_dump(rotated)
    assert any(e["event"] == "noise" for e in evs_gz)
    # and a dump written STRAIGHT to .gz round-trips too
    gzpath = str(tmp_path / "direct.jsonl.gz")
    vec_host.dump_flight(gzpath)
    assert any(e["event"] == "noise" for e in timeline.load_dump(gzpath))


def test_timeline_spans_interleave_with_chain_stages(tmp_path, capsys):
    from dragonboat_tpu.tools import timeline

    dump = tmp_path / "spans.jsonl"
    lines = [
        {"event": "_meta", "mono_offset": 0.0, "source": "n1"},
        {"event": "propose_enqueue", "t": 10.0005, "cluster": 1,
         "node": 1, "trace": 7},
        # recorded at span END (t=10.002) with dur 0.004 -> starts 9.998,
        # BEFORE the propose despite the later record time
        {"event": "phase_span", "t": 10.002, "cluster": 0,
         "engine": "vector", "phase": "dispatch", "dur": 0.004},
        {"event": "quorum_commit", "t": 10.003, "cluster": 1,
         "node": 1, "trace": 7},
        {"event": "leader_changed", "t": 10.004, "cluster": 1, "node": 1,
         "leader": 1},
    ]
    dump.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
    rc = timeline.main([str(dump), "--spans"])
    assert rc == 0
    out = capsys.readouterr().out
    span_ln = [l for l in out.splitlines() if "|--" in l]
    assert len(span_ln) == 1 and "vector/dispatch" in span_ln[0]
    assert "4000.0us" in span_ln[0]
    # interleaving: the span line is re-anchored to its START, so it
    # prints before the propose; the default filter keeps chain stages
    # and drops unrelated events
    order = [l.split()[2] for l in out.splitlines() if l.startswith("+")]
    assert order[0].startswith("|--") or "propose_enqueue" in out.splitlines()[1]
    assert "leader_changed" not in out
    assert "propose_enqueue" in out and "quorum_commit" in out
