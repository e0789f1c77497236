"""The loop's span cover, the path of one sampled request through
launches, the span carrier and the ReadIndex drop counter (ISSUE 23).

A small co-hosted deployment (3 NodeHosts on one shared engine core, two
groups of three replicas) runs at profile_sample_ratio 1 in the three
modes the benchmark's cells use: one protocol step a launch with the
decode after its own launch, the same with the decode overlapped one
launch late (the accelerator default), and eight protocol steps a launch.
"""
from __future__ import annotations

import threading
import time

import pytest

from dragonboat_tpu import trace
from dragonboat_tpu.profile import (
    VECTOR_PHASES,
    VECTOR_SUBSPANS,
    phase_plane,
)
from dragonboat_tpu.trace import FlightRecorder, flight_recorder

GROUPS = (1, 2)
MODES = {
    # name: (steps_per_sync, overlap_decode, least launches from pack to
    # commit). With three co-hosted replicas and one step a launch a
    # commit needs the leader's append, the followers' append and ack and
    # the leader's commit; at eight steps a launch the kernel routes all
    # of that between lanes inside one launch.
    "k1": (1, False, 2),
    "k1-overlap": (1, True, 2),
    "k8": (8, None, 1),
}


class Cluster:
    def __init__(self, tmp, name: str, **engine) -> None:
        from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
        from dragonboat_tpu.nodehost import NodeHost
        from dragonboat_tpu.transport.loopback import (
            _Registry,
            loopback_factory,
        )
        from tests.test_nodehost import KVSM

        reg = _Registry()
        members = {n: f"rp{n}:1" for n in (1, 2, 3)}
        self.hosts = {}
        for n, addr in members.items():
            self.hosts[n] = NodeHost(NodeHostConfig(
                deployment_id=1, rtt_millisecond=5, raft_address=addr,
                nodehost_dir=str(tmp / f"nh{n}"),
                raft_rpc_factory=lambda a: loopback_factory(a, reg),
                enable_metrics=True,
                engine=EngineConfig(
                    kind="vector", max_groups=12, max_peers=4, log_window=64,
                    share_scope=f"request-path-{name}",
                    profile_sample_ratio=1, **engine,
                ),
            ))
        try:
            for n, nh in self.hosts.items():
                for g in GROUPS:
                    nh.start_cluster(
                        dict(members), False, lambda c, i: KVSM(c, i),
                        Config(cluster_id=g, node_id=n, election_rtt=20,
                               heartbeat_rtt=2),
                    )
            self.core = self.hosts[1].engine.core
            # trace every request, not 1 in vector.REQUEST_SAMPLE_FLOOR
            assert self.core.request_sampler.ratio == 8
            self.core.request_sampler.ratio = 1
            self.leaders = {g: self._leader(g) for g in GROUPS}
        except BaseException:
            self.stop()
            raise

    def _leader(self, g: int) -> int:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            lid, ok = self.hosts[1].get_leader_id(g)
            if ok and lid:
                return lid
            time.sleep(0.02)
        raise AssertionError(f"group {g} elected no leader")

    def traffic(self, rounds: int) -> None:
        """Per round and group: one batch of four writes and one read on
        the leader's host, and one read on a follower's (forwarded)."""
        for i in range(rounds):
            for g, lead in self.leaders.items():
                nh = self.hosts[lead]
                h = nh.propose_batch_async(
                    nh.get_noop_session(g),
                    [f"k{i}.{j}=v".encode() for j in range(4)], 5.0,
                )
                assert h.wait(10.0) and h.completed == 4
                assert h.completed_at >= time.monotonic() - 10.0
                assert nh.read_index(g, 5.0).wait(10.0).completed
                follower = self.hosts[1 + lead % 3]
                assert follower.read_index(g, 5.0).wait(10.0).completed

    def stop(self) -> None:
        for nh in self.hosts.values():
            nh.stop()


@pytest.fixture(scope="module", params=list(MODES))
def cluster(request, tmp_path_factory):
    k, overlap, _least = MODES[request.param]
    c = Cluster(
        tmp_path_factory.mktemp(request.param), request.param,
        steps_per_sync=k, overlap_decode=overlap,
    )
    c.mode = request.param
    assert c.core._multi == k and c.core._overlap == bool(overlap)
    yield c
    c.stop()


# ---------------------------------------------------------------- (a) cover
def test_loop_cover(cluster):
    """Over 50 launches and more, the loop thread's top-level spans are
    pairwise disjoint and add up to the loop's wall time; what is nested
    in them or comes from another thread does not carry their label."""
    rec = flight_recorder()
    rec.reset()
    first = cluster.core.launch_no
    cluster.traffic(6)
    deadline = time.monotonic() + 30
    while cluster.core.launch_no < first + 52 and time.monotonic() < deadline:
        time.sleep(0.05)  # ticks keep the loop launching
    spans = [e for e in rec.dump(event="phase_span") if not e.get("open")]
    top = [e for e in spans if e["engine"] == "vector"]
    top_names = set(VECTOR_PHASES) - set(VECTOR_SUBSPANS)
    assert {e["phase"] for e in top} <= top_names
    assert {"wait", "prepare", "pack", "dispatch", "fetch", "save",
            "maintain"} <= {e["phase"] for e in top}
    assert sum(e["phase"] == "dispatch" for e in top) >= 50
    for a, b in zip(top, top[1:]):
        assert b["t0"] >= a["t"], (a, b)  # in order and disjoint
    wall = top[-1]["t"] - top[0]["t0"]
    covered = sum(e["dur"] for e in top)
    assert abs(wall - covered) <= 0.02 * wall, (wall, covered)
    # sub-spans and the apply workers' spans leave no event: samples, and
    # histograms under labels of their own
    assert [e for e in spans if e["engine"] != "vector"] == []
    sums = {
        name: s.mean() * len(s)
        for name, s in cluster.core.profiler.samples.items()
    }
    plane = phase_plane()
    for sub in VECTOR_SUBSPANS:
        if sub == "deliver" and cluster.mode == "k8":
            continue  # routed on the device: the host delivers nothing
        assert sums[sub] > 0.0 and sub + ".cpu" not in sums
        assert plane.histogram("vector.sub", sub).count > 0
        assert plane.histogram("vector", sub) is None
    # the seam's halves lie inside the phases they split
    assert sums["put"] + sums["launch"] <= sums["dispatch"]
    assert sums["device_wait"] + sums["copy"] <= sums["fetch"]
    assert sums["rsm.handle"] > 0.0
    assert sums["rsm.handle.cpu"] <= sums["rsm.handle"] * 1.5
    assert plane.histogram("rsm", "rsm.handle").count > 0
    assert plane.histogram("vector", "rsm.handle") is None
    # a dump shows the span that is running, with the end it has so far
    now = [e for e in rec.dump(event="phase_span") if e.get("open")]
    assert len(now) <= 1
    assert all(e["engine"] == "vector" and e["dur"] >= 0.0 for e in now)
    assert rec.spans_dropped == 0


# --------------------------------------------------------- (c) request path
def test_request_path_stamps(cluster, monkeypatch):
    """Every sampled write and read is stamped in order, in time and in
    launches, and folds into the engine's profiler what the stamps say."""
    folded = {"w": [], "r": []}
    real = trace.LatencyTrace.fold

    def fold(self, prof, kind):
        folded[kind].append(self)
        real(self, prof, kind)

    monkeypatch.setattr(trace.LatencyTrace, "fold", fold)
    prof = cluster.core.profiler

    def count(name):
        s = prof.samples.get(name)
        return (len(s), s.mean() * len(s)) if s is not None else (0, 0.0)

    names = [
        f"req.{kind}.{part}"
        for kind, parts in (
            ("w", ("n", "launches", "queue", "replicate", "apply_wait",
                   "apply")),
            ("r", ("n", "launches", "queue", "confirm", "complete")),
        )
        for part in parts
    ]
    before = {name: count(name) for name in names}
    cluster.traffic(5)
    least = MODES[cluster.mode][2]
    writes, reads = folded["w"], folded["r"]
    assert len(writes) == 5 * len(GROUPS)  # one sampled entry a batch
    assert len(reads) == 2 * 5 * len(GROUPS)  # leader's host and follower's
    for lt in writes:
        assert lt.done and lt.trace_id
        assert (lt.t0 <= lt.t_pack <= lt.t_commit <= lt.t_apply0
                <= lt.t_done), lt
        assert lt.n0 <= lt.n_pack <= lt.n_commit <= lt.n_done, lt
        assert lt.n_commit - lt.n_pack + 1 >= least
    for lt in reads:
        assert lt.done and not lt.trace_id
        assert lt.t0 <= lt.t_pack <= lt.t_commit <= lt.t_done, lt
        assert lt.n0 <= lt.n_pack <= lt.n_commit <= lt.n_done, lt
        assert lt.n_commit - lt.n_pack + 1 >= least
    for kind, lts in folded.items():
        pre = f"req.{kind}."
        n = count(pre + "n")[0] - before[pre + "n"][0]
        assert n == len(lts)
        launches = count(pre + "launches")[1] - before[pre + "launches"][1]
        assert launches == pytest.approx(
            sum(lt.n_commit - lt.n_pack + 1 for lt in lts)
        )
        # the stretches add up to the whole: t_done - t0
        whole = sum(
            count(name)[1] - before[name][1] for name in names
            if name.startswith(pre) and name[len(pre):] not in (
                "n", "launches")
        )
        assert whole == pytest.approx(sum(lt.t_done - lt.t0 for lt in lts))
    # the chain events carry the launch they happened in
    chain = [e for e in flight_recorder().dump() if e.get("trace")]
    assert {"propose_enqueue", "quorum_commit", "proposal_applied"} <= {
        e["event"] for e in chain
    }
    assert all("launch" in e for e in chain), chain[:3]
    # one source: the histograms got what the stamps say
    m = cluster.hosts[cluster.leaders[1]].metrics
    h = m.histogram("proposal_apply_latency_seconds",
                    (1, cluster.leaders[1]))
    assert h is not None and h.count >= 5


# ------------------------------------------------------------- (d) carrier
def _carrier_evict():
    """50 000 chain events between two polls evict no phase span."""
    rec = FlightRecorder()
    for i in range(1000):
        rec.span("vector", "pack", float(i), i + 0.5)
    for i in range(50_000):
        rec.record("replicate_send", cluster=1, node=1, trace=i + 1)
    spans = rec.dump(event="phase_span")
    assert len(spans) == 1000 and rec.spans_dropped == 0
    assert len(rec.dump(event="replicate_send")) == 8192  # the ring's own


def _carrier_shed():
    """A full store sheds its oldest span, and counts it unless a dump
    had returned it: what a poller has had is retired, not lost."""
    rec = FlightRecorder(span_capacity=8)
    shed = [rec.span("vector", "save", float(i), i + 0.25) for i in range(11)]
    assert shed == [False] * 8 + [True] * 3 and rec.spans_dropped == 3
    assert [e["t0"] for e in rec.dump(event="phase_span")] == [
        float(i) for i in range(3, 11)
    ]
    shed = [rec.span("vector", "save", float(i), i + 0.25)
            for i in range(11, 21)]
    assert shed == [False] * 8 + [True] * 2  # spans 3..10 had been read
    assert rec.spans_dropped == 5
    rec.reset()
    assert len(rec) == 0 and rec.spans_dropped == 0
    assert not rec.span("vector", "save", 0.0, 1.0)


def _carrier_fields():
    """dump(event="phase_span") keeps t, dur, engine and phase, with the
    start beside them; other filters and the merged dump still work."""
    rec = FlightRecorder()
    rec.record("leader_changed", cluster=3, node=1, leader=1, term=2)
    rec.span("vector", "fetch", 10.0, 10.5)
    rec.open_spans[1] = ("vector", "save", time.monotonic() - 1.0)
    closed, running = rec.dump(event="phase_span")
    assert closed == {
        "t": 10.5, "dur": 0.5, "t0": 10.0, "engine": "vector",
        "phase": "fetch", "event": "phase_span", "cluster": 0,
    }
    assert running["open"] is True and running["phase"] == "save"
    assert 1.0 <= running["dur"] < 5.0
    assert running["t"] - running["dur"] == pytest.approx(running["t0"])
    assert [e["event"] for e in rec.dump()] == [
        "phase_span", "leader_changed", "phase_span",
    ]  # merged by t: the stored span is from long ago
    assert [e["event"] for e in rec.dump(cluster_id=3)] == ["leader_changed"]
    assert rec.dump(trace_id=7) == []
    assert len(rec) == 2


@pytest.mark.parametrize("case", ["evict", "shed", "fields"])
def test_span_carrier(case):
    {"evict": _carrier_evict, "shed": _carrier_shed,
     "fields": _carrier_fields}[case]()


# ---------------------------------------------------- (e) readindex_dropped
@pytest.mark.parametrize("overlap", [False, True], ids=["k1", "k1-overlap"])
def test_readindex_dropped_counts_the_kernels_plane(tmp_path, overlap):
    """With one ReadIndex slot a lane, contexts packed launch after launch
    overflow it: the engine's counter is the sum of the plane the kernel
    wrote, step by step, and so is the profiler's."""
    c = Cluster(tmp_path, f"ri-{overlap}", readindex_depth=1,
                overlap_decode=overlap)
    try:
        core = c.core
        seen = []
        place = core._decode_place

        def spy(o, packs):
            seen.append(int(o["dropped_readindex"].sum()))
            place(o, packs)

        core._decode_place = spy
        mark = core.step_stats()["readindex_dropped"]
        s = core.profiler.samples.get("n.readindex_dropped")
        mark_prof = s.mean() * len(s) if s is not None else 0.0
        stop = threading.Event()

        def reader(nh, g):
            while not stop.is_set():
                nh.read_index(g, 1.0).wait(1.5)

        threads = [
            threading.Thread(target=reader, args=(nh, g), daemon=True)
            for nh in c.hosts.values() for g in GROUPS
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 20
        while sum(seen) < 3 and time.monotonic() < deadline:
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        core.drain()
        core._decode_place = place
        assert sum(seen) >= 3, "one slot a lane never overflowed"
        assert core.step_stats()["readindex_dropped"] - mark == sum(seen)
        s = core.profiler.samples["n.readindex_dropped"]
        assert s.mean() * len(s) - mark_prof == pytest.approx(sum(seen))
    finally:
        c.stop()
