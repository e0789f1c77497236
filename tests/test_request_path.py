"""The loop's span cover, the path of one sampled request through
launches, the span carrier and the ReadIndex drop counter (ISSUE 23).

A small co-hosted deployment (3 NodeHosts on one shared engine core, two
groups of three replicas) runs at profile_sample_ratio 1 in the three
modes the benchmark's cells use: one protocol step a launch with the
whole decode after its own launch, the same with only a step's maintain
left behind the next launch (the accelerator default: the kernel runs
under it, and every hop still takes one launch), and eight protocol steps
a launch. ISSUE 25 added the cases that hold the overlapped loop to that.
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
from dragonboat_tpu.types import MessageType as MT

GROUPS = (1, 2)
MODES = {
    # name: (steps_per_sync, overlap_decode, least launches from pack to
    # commit). With three co-hosted replicas and one step a launch a
    # commit needs the leader's append, the followers' append and ack and
    # the leader's commit; at eight steps a launch the kernel routes all
    # of that between lanes inside one launch. The overlapped loop takes
    # no more launches than the plain one (test_overlap_costs_no_launch):
    # it decodes a step before the next pack and defers only maintain.
    "k1": (1, False, 2),
    "k1-overlap": (1, True, 2),
    "k8": (8, None, 1),
    # the steps left to the engine: all three replicas are routable, so
    # three steps a launch carry a commit, with the chip's overlap
    # option on and, at more than one step a launch, unused
    "auto": (None, True, 1),
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
    deadline = time.monotonic() + 60
    while c.core._multi != (k or 3):  # auto: at a launch boundary
        assert time.monotonic() < deadline
        time.sleep(0.02)
    assert c.core._overlap == (bool(overlap) and k == 1)
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
    # sub-spans and the apply workers' spans leave no event, but the save
    # wave's three stretches that are one piece of time each (ISSUE 37),
    # under the sub-spans' own kind: samples, and histograms under labels
    # of their own
    other = [e for e in spans if e["engine"] != "vector"]
    assert {e["engine"] for e in other} == {"vector.sub"}
    stretches = {"save.gather", "save.sync", "save.mirror"}
    assert {e["phase"] for e in other} == stretches
    sums = {
        name: s.mean() * len(s)
        for name, s in cluster.core.profiler.samples.items()
    }
    plane = phase_plane()
    for sub in VECTOR_SUBSPANS:
        if sub == "deliver" and cluster.mode in ("k8", "auto"):
            continue  # routed on the device: the host delivers nothing
        assert sums[sub] > 0.0
        # the barrier alone carries the thread's CPU seconds (it
        # sleeps, and two metrics take its sleep out of the loop's stall)
        assert (sub + ".cpu" in sums) == (sub == "save.sync")
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
                steps_per_sync=1, overlap_decode=overlap)
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


# ------------------------------------------- (f) one launch a hop (ISSUE 25)
def _request_sums(core) -> dict:
    """How many writes and reads were sampled so far, and their launches."""
    out = {}
    for kind in ("w", "r"):
        n = core.profiler.samples.get(f"req.{kind}.n")
        la = core.profiler.samples.get(f"req.{kind}.launches")
        out[kind] = (
            len(n) if n is not None else 0,
            la.mean() * len(la) if la is not None else 0.0,
        )
    return out


def test_overlap_costs_no_launch(tmp_path):
    """A commit and a ReadIndex take as many launches in the overlapped
    loop as in the plain one: what a step's decode hands to co-hosted
    lanes rides the very next launch, because the decode comes before the
    next pack and only maintain stays behind the launch. (Before ISSUE 25
    the overlapped loop took twice as many.)"""
    means = {}
    for mode in ("k1", "k1-overlap"):
        k, overlap, _least = MODES[mode]
        c = Cluster(tmp_path / mode, f"hops-{mode}", steps_per_sync=k,
                    overlap_decode=overlap)
        try:
            assert c.core._overlap == overlap
            # a write first: the kernel drops a ReadIndex that comes
            # before its leader's first commit of the term
            c.traffic(1)
            base = _request_sums(c.core)
            for i in range(50):  # writes and reads on the leaders' hosts
                writes, reads = [], []
                for g, lead in c.leaders.items():
                    nh = c.hosts[lead]
                    for j in range(2):
                        writes.append(nh.propose_batch_async(
                            nh.get_noop_session(g), [f"k{i}.{j}=v".encode()],
                            5.0))
                        reads.append(nh.read_index(g, 5.0))
                for h in writes:
                    assert h.wait(10.0) and h.completed == 1
                for rs in reads:
                    assert rs.wait(10.0).completed
            local = _request_sums(c.core)
            for i in range(25):  # reads a follower's host forwards
                for rs in [c.hosts[1 + lead % 3].read_index(g, 5.0)
                           for g, lead in c.leaders.items()]:
                    assert rs.wait(10.0).completed
            both = _request_sums(c.core)
        finally:
            c.stop()
        nw, lw = (a - b for a, b in zip(local["w"], base["w"]))
        nr, lr = (a - b for a, b in zip(local["r"], base["r"]))
        nf, lf = (a - b for a, b in zip(both["r"], base["r"]))
        assert nw >= 200 and nr >= 200 and nf - nr >= 50, (base, local, both)
        means[mode] = (lw / nw, lr / nr, (lf - lr) / (nf - nr))
    plain, overlapped = means["k1"], means["k1-overlap"]
    # the leader's append, the followers' append and ack, the leader's
    # commit: 3 launches, and as many for a ReadIndex's heartbeat round
    assert abs(overlapped[0] - plain[0]) <= 0.25, means
    assert abs(overlapped[1] - plain[1]) <= 0.25, means
    assert 3.0 <= overlapped[0] <= 3.25 and 3.0 <= overlapped[1] <= 3.25
    # a forwarded read adds the way to the leader and back: 5, or 4 where
    # one pack holds both lanes and takes the follower's first (the order
    # of a set), so the two loops may differ by a part of one launch
    assert 4.0 <= overlapped[2] <= 5.0 and 4.0 <= plain[2] <= 5.0, means


# what a replica may send only after the save wave of the step that built it
RESPONSES = {MT.REPLICATE_RESP, MT.REQUEST_VOTE_RESP, MT.HEARTBEAT_RESP,
             MT.REQUEST_PREVOTE_RESP, MT.READ_INDEX_RESP}


def _spy_decode(core, log) -> None:
    """Log, from the loop thread, the decode's calls in the order they
    run. The step's output dict itself is logged: identity names a step."""
    place, saves, sends = (
        core._decode_place, core._commit_saves, core._dispatch_sends)
    reads, maintain, rebase = (
        core._decode_reads, core._maintain, core._do_rebase)

    def spy_place(o, packs):
        log.append(("place", o))
        place(o, packs)

    def spy_saves(updates, lane_saves, mark=None):
        saves(updates, lane_saves, mark)
        log.append(("saved", len(updates)))

    def spy_sends(batch):
        log.append(("send", {m.type for _lane, m in batch}))
        sends(batch)

    def spy_reads(o, skip_routed=None):
        reads(o, skip_routed)
        log.append(("reads", o))

    def spy_maintain(o):
        log.append(("maintain", o))
        maintain(o)

    def spy_rebase():
        log.append(("rebase", None))
        rebase()

    core._decode_place, core._commit_saves = spy_place, spy_saves
    core._dispatch_sends, core._decode_reads = spy_sends, spy_reads
    core._maintain, core._do_rebase = spy_maintain, spy_rebase


@pytest.mark.parametrize("mode", ["k1", "k1-overlap"])
def test_decode_order_holds_in_both_loops(tmp_path, mode):
    """Whichever K=1 loop runs: no response built from a step's output
    leaves before that step's save wave has returned, a step's maintain
    comes after its reads and before the next step's place, and a rebase
    never falls between a step's place and its maintain."""
    k, overlap, _least = MODES[mode]
    c = Cluster(tmp_path, f"order-{mode}", steps_per_sync=k,
                overlap_decode=overlap)
    try:
        core = c.core
        log = []
        _spy_decode(core, log)
        c.traffic(3)
        # an election under the spies: vote requests and their responses
        g, old = GROUPS[0], c.leaders[GROUPS[0]]
        target = 1 + old % 3
        c.hosts[old].request_leader_transfer(g, target)
        deadline = time.monotonic() + 30
        while c._leader(g) != target and time.monotonic() < deadline:
            time.sleep(0.02)
        c.leaders[g] = c._leader(g)
        assert c.leaders[g] == target
        c.traffic(2)
        core._rebase_due = True  # what _maintain sets past 2**30 entries
        c.traffic(2)
        core.drain()
        events = list(log)
    finally:
        c.stop()
    first = next(i for i, (what, _) in enumerate(events) if what == "place")
    step = saved = read = maintained = None
    steps = seen_resp = 0
    for what, arg in events[first:]:
        if what == "place":
            assert step is None or maintained, "a step lost its maintain"
            step, saved, read, maintained = arg, False, False, False
            steps += 1
        elif what == "saved":
            saved = True
        elif what == "send":
            if arg & RESPONSES:
                seen_resp += 1
                assert saved, f"{arg} left before the step's save"
        elif what == "reads":
            assert arg is step
            read = True
        elif what == "maintain":
            assert arg is step and read and not maintained
            maintained = True
        elif what == "rebase":
            assert maintained, "rebase between a step's decode and maintain"
    kinds = [what for what, _ in events]
    assert steps >= 20 and seen_resp >= 10 and "rebase" in kinds
    sent = set().union(*(a for w, a in events if w == "send"))
    assert {MT.REPLICATE_RESP, MT.REQUEST_VOTE_RESP,
            MT.HEARTBEAT_RESP} <= sent


@pytest.mark.parametrize("flush", [False, True], ids=["crash", "stop"])
def test_stop_with_a_step_in_flight(tmp_path, flush):
    """The overlapped loop parks a launched step undecoded. A crash stop
    lets it die: nothing of it is saved or sent. A plain stop decodes it,
    save wave and maintain included."""
    c = Cluster(tmp_path, f"inflight-{flush}", steps_per_sync=1,
                overlap_decode=True)
    try:
        core = c.core
        launched, release = threading.Event(), threading.Event()
        step_fn = core._step_fn

        def held(state, inbox, ticks):
            res = step_fn(state, inbox, ticks)
            if not launched.is_set():
                launched.set()
                release.wait(30)
            return res

        core._step_fn = held  # ticks keep the loop launching
        assert launched.wait(30)
        log = []
        _spy_decode(core, log)
        stopper = threading.Thread(
            target=core.stop, kwargs={"flush": flush}, daemon=True)
        stopper.start()
        deadline = time.monotonic() + 10
        while not core._stopped.is_set() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert core._stopped.is_set()
        release.set()
        stopper.join(timeout=40)
        assert not stopper.is_alive()
        assert core._pending is None
        kinds = [what for what, _ in log]
        # the step before it was decoded before the launch: its maintain
        # alone may come after
        owed = kinds[:1] == ["maintain"]
        if flush:
            tail = kinds[owed:]
            assert tail[0] == "place" and tail[-1] == "maintain", kinds
            assert tail.count("saved") == 1 and tail.count("place") == 1
            assert log[-1][1] is log[owed][1]  # maintain of the same step
        else:
            assert kinds[owed:] == [], kinds
    finally:
        c.stop()


def test_decode_with_nothing_to_pack_still_maintains(cluster):
    """With the ticks held back, one read is all the work there is: the
    iteration that decodes its last launch has nothing to pack. It still
    runs that step's maintain, and the spans stay contiguous."""
    core = cluster.core
    rec = flight_recorder()
    g, lead = GROUPS[0], cluster.leaders[GROUPS[0]]
    core.global_tick = lambda host=0: None  # instance attribute: no ticks
    try:
        time.sleep(0.1)  # what was in flight drains; the loop goes idle
        rec.reset()
        first = core.launch_no
        assert cluster.hosts[lead].read_index(g, 5.0).wait(10.0).completed
        time.sleep(0.05)
        launches = core.launch_no - first
        spans = [e for e in rec.dump(event="phase_span")
                 if not e.get("open") and e["engine"] == "vector"]
    finally:
        del core.global_tick
    names = [e["phase"] for e in spans]
    assert launches >= 1
    assert names.count("dispatch") == launches
    assert names.count("fetch") == launches
    assert names.count("maintain") == launches
    assert core._pending is None
    last_fetch = len(names) - 1 - names[::-1].index("fetch")
    tail = names[last_fetch:]
    assert "maintain" in tail
    if cluster.mode == "k1-overlap":
        # decoded at the top of an iteration that launched nothing
        assert "dispatch" not in tail, tail
        assert tail.index("pack") < tail.index("maintain"), tail
    for a, b in zip(spans, spans[1:]):
        assert b["t0"] >= a["t"], (a, b)
    wall = spans[-1]["t"] - spans[0]["t0"]
    covered = sum(e["dur"] for e in spans)
    assert abs(wall - covered) <= 0.02 * wall, (wall, covered, names)
