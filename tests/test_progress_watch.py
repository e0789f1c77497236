"""The progress watch (ISSUE 37): a lane that owes progress and makes none.

Once a launch, at the head of `place`, the engine looks at three debts: a
leader's peer slot whose match is below its last index, a lane whose
commit index is below its last index, a lane whose state machine is below
its commit index. One that stands unpaid for `_STALL_LAUNCHES` sweeps
(launches at least an election timeout apart) is a stall: counted in `step_stats()`, warned about once, and left as a
`progress_stall` event, sampled or not. The first half drives the watch
launch by launch on made-up outputs; the second drives real clusters at
one step a launch, at the engine's own three and at eight.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
import types

import numpy as np
import pytest

from dragonboat_tpu.engine import vector as vec
from dragonboat_tpu.engine.vector import (
    _STALL_EVENTS_PER_LAUNCH, _STALL_LAUNCHES, VectorEngine,
)
from dragonboat_tpu.ops.state import ROLE, RSTATE
from dragonboat_tpu.trace import Profiler, flight_recorder

from benchmark.run import load_plugin
from tests.test_auto_steps import (
    CLUSTER, LogSM, _bring_up, _converged, _propose_n, _stop,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INTS = ("peer_stall_steps", "commit_stall_steps", "apply_stall_steps",
        "stalls_seen")
FOLDS = ("n.peer_stall_steps", "n.commit_stall_steps", "n.apply_stall_steps")
NEVER = 10 ** 9  # a sampling ratio no run reaches
LANE_FIELDS = {
    "kind", "cluster", "node", "lane", "launch", "age", "since", "role",
    "term", "leader", "base", "last", "commit", "applied", "steps",
}
PEER_FIELDS = LANE_FIELDS | {
    "peer_slot", "peer", "match", "next", "rstate", "route",
    # the co-hosted peer's own lane, from the mirrors
    "peer_lane", "peer_resid", "peer_active", "peer_recovering",
    "peer_role", "peer_term", "peer_last", "peer_commit",
}


# ------------------------------------------------- the watch, by the launch
class _SM:
    def __init__(self):
        self.applied = 0

    def last_applied_index(self):
        return self.applied

    applied_level = last_applied_index


class _Node:
    def __init__(self, cluster_id, node_id):
        self.cluster_id, self._nid, self.sm = cluster_id, node_id, _SM()

    def node_id(self):
        return self._nid


class _FakeLane:
    def __init__(self, g, cluster_id, node_id, peers):
        self.g, self.node = g, _Node(cluster_id, node_id)
        self.catchup, self.snap_inflight = {}, {}
        self.rev = dict(enumerate(range(1, peers + 1)))


class Watch:
    """The watch's state and code without an engine around them: one
    group of three replicas on lanes 0-2 (node ids 1-3 on slots 0-2),
    lane 0 leading at term 2, everyone at index 10; a fourth lane stays
    free. step() is one launch's sweep over `self.o`."""

    G, P = 4, 4
    _watch_progress = VectorEngine._watch_progress
    _sweep_progress = VectorEngine._sweep_progress
    _sweep_peers = VectorEngine._sweep_peers
    _level_applied = VectorEngine._level_applied
    _report_stalls = VectorEngine._report_stalls
    _set_owes = VectorEngine._set_owes

    def __init__(self, ratio=NEVER):
        G, P = self.G, self.P
        self._sstats = dict.fromkeys(INTS, 0)
        self.profiler = Profiler(sample_ratio=ratio)
        self._m_active = np.arange(G) < 3
        self._m_recovering = np.zeros(G, bool)
        self._m_base = np.zeros(G, np.int64)
        self._m_resid = np.zeros(G, np.int32)
        self._m_owes = np.zeros((G, P), bool)
        self._w_match = np.zeros((G, P), np.int32)
        self._w_commit = np.zeros(G, np.int32)
        self._w_peer_age = np.zeros((G, P), np.int32)
        self._w_commit_age = np.zeros(G, np.int32)
        self._w_led = np.zeros(G, bool)
        self._w_sweeps = 0
        # the engine's clock of ticks: a launch here is an election
        # timeout long (`ticks`), so every one is a sweep
        self.clock = types.SimpleNamespace(tick=0)
        self.ticks = self._w_period = 10
        self._w_tick = self._w_peer_n = self._w_commit_n = 0
        self._w_applied = np.zeros(G, np.int64)
        self._w_apply_owed = np.zeros(G, bool)
        self._w_apply_stalled = np.zeros(G, bool)
        self._w_apply_n = 0
        self._np_route = np.full((G, P), -1, np.int32)
        self._multi = 3
        self.launch_no = 0
        self._state = types.SimpleNamespace(next=np.full((G, P), 11, np.int32))
        self.lanes = [_FakeLane(g, 7, g + 1, 3) for g in range(3)]
        self._lane_by_g = self.lanes + [None] * (G - 3)
        self._route = {(7, ln.node.node_id()): ln for ln in self.lanes}
        for g in range(3):
            self._set_owes(g, [True, True, True, False], g)
            self.lanes[g].node.sm.applied = 10
        self.o = {
            "role": np.zeros(G, np.int32),
            "term": np.full(G, 2, np.int32),
            "leader": np.zeros(G, np.int32),
            "last_index": np.zeros(G, np.int32),
            "commit_index": np.zeros(G, np.int32),
            "match": np.zeros((G, P), np.int32),
            "rstate": np.full((G, P), RSTATE.REPLICATE, np.int32),
            "quiesced": np.zeros(G, bool),
        }
        self.o["role"][0] = ROLE.LEADER
        self.o["leader"][:3] = 1
        self.o["last_index"][:3] = 10
        self.o["commit_index"][:3] = 10
        self.o["match"][0, :3] = 10

    def step(self, n=1):
        for _ in range(n):
            self.launch_no += 1
            self.clock.tick += self.ticks
            self.profiler.new_iteration()
            self._watch_progress(self.o)

    def ints(self):
        return tuple(self._sstats[k] for k in INTS)

    def append(self, n=5, acked_by=(1,)):
        """The leader appends n entries; the followers on `acked_by`
        slots take and acknowledge them and a quorum commits."""
        o = self.o
        o["last_index"][0] += n
        for p in acked_by:
            o["last_index"][p] = o["match"][0, p] = o["last_index"][0]
        o["match"][0, 0] = o["last_index"][0]
        if acked_by:
            o["commit_index"][0] = o["last_index"][0]
            for p in acked_by:
                o["commit_index"][p] = o["last_index"][0]
            self.lanes[0].node.sm.applied = int(o["last_index"][0])
            for p in acked_by:
                self.lanes[p].node.sm.applied = int(o["last_index"][0])


def _stall_events():
    return flight_recorder().dump(event="progress_stall")


@pytest.fixture
def events():
    flight_recorder().reset()
    yield _stall_events


def _warnings(caplog):
    return [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("progress watch:")]


def test_a_peer_debt_is_a_stall_at_the_eighth_launch_and_not_before(
    events, caplog,
):
    caplog.set_level(logging.WARNING)
    w = Watch()
    w.step(3)
    w.append(acked_by=(1,))  # slot 2 is owed five entries from here on
    w.step(_STALL_LAUNCHES - 1)
    assert w.ints() == (0, 0, 0, 0) and events() == []
    w.step()
    assert w.ints() == (1, 0, 0, 1)
    (e,) = events()
    assert set(e) - {"t", "event"} == PEER_FIELDS
    assert (e["kind"], e["cluster"], e["node"], e["peer"], e["peer_slot"]) \
        == ("peer", 7, 1, 3, 2)
    assert (e["age"], e["launch"], e["since"]) == (8, w.launch_no,
                                                   w.launch_no - 8)
    assert (e["role"], e["term"], e["leader"], e["last"], e["commit"],
            e["applied"], e["steps"]) == (ROLE.LEADER, 2, 0, 15, 15, 15, 3)
    assert (e["match"], e["next"], e["rstate"], e["route"]) == (
        10, 11, RSTATE.REPLICATE, -1)
    assert (e["peer_lane"], e["peer_active"], e["peer_recovering"],
            e["peer_last"], e["peer_commit"], e["peer_resid"]) == (
        2, True, False, 10, 10, 0)
    (line,) = _warnings(caplog)
    assert "1 debt(s)" in line and "kind=peer" in line and "node=1" in line
    # it stands: counted every launch, reported once
    w.step(5)
    assert w.ints() == (6, 0, 0, 1)
    assert len(events()) == 1 and len(_warnings(caplog)) == 1
    # the peer answers: paid at once
    w.o["match"][0, 2] = 12
    w.step()
    assert w.ints() == (6, 0, 0, 1)
    # and stands again below the leader: a second stall, eight on
    w.step(_STALL_LAUNCHES - 1)
    assert w.ints() == (6, 0, 0, 1)
    w.step()
    assert w.ints() == (7, 0, 0, 2) and len(events()) == 2


def _cut_follower(w):
    w.step(3)
    w.append(acked_by=(1,))
    w.step()


@pytest.mark.parametrize("excuse", [
    "parked", "catchup", "snap_inflight", "restore", "quiesced", "stopped",
    "not_leading", "not_voting",
])
def test_who_owes_nothing(events, excuse):
    """A slot parked for a snapshot or served by a catch-up, a lane under
    restore, a quiesced lane, a lane that has left, a lane that no longer
    leads and a slot that does not vote owe no progress."""
    w = Watch()
    _cut_follower(w)
    if excuse == "parked":
        w.o["rstate"][0, 2] = RSTATE.SNAPSHOT
    elif excuse == "catchup":
        w.lanes[0].catchup[2] = object()
    elif excuse == "snap_inflight":
        w.lanes[0].snap_inflight[2] = (0, 0, 0, 0.0)
    elif excuse == "restore":
        w._m_recovering[0] = True
    elif excuse == "quiesced":
        w.o["quiesced"][0] = True
    elif excuse == "stopped":
        w._m_active[0] = False
    elif excuse == "not_leading":
        w.o["role"][0] = ROLE.FOLLOWER
    elif excuse == "not_voting":
        w._m_owes[0, 2] = False
    w.step(3 * _STALL_LAUNCHES)
    assert w.ints() == (0, 0, 0, 0) and events() == []
    if excuse not in ("catchup", "snap_inflight"):
        # (those two start again where they would cross) nothing is left
        # of the debt: a lane that leads no more has its row cleared
        assert not w._w_peer_age.any()


def test_a_commit_that_stands_below_the_last_index_is_a_commit_stall(
    events, caplog,
):
    caplog.set_level(logging.WARNING)
    w = Watch()
    w.step(2)
    w.append(acked_by=())  # nobody answers: three debts at the leader
    w.step(_STALL_LAUNCHES - 1)
    assert w.ints() == (0, 0, 0, 0)
    w.step()
    assert w.ints() == (2, 1, 0, 3)
    kinds = sorted(e["kind"] for e in events())
    assert kinds == ["commit", "peer", "peer"]
    (e,) = [e for e in events() if e["kind"] == "commit"]
    assert set(e) - {"t", "event"} == LANE_FIELDS
    assert (e["node"], e["last"], e["commit"], e["age"]) == (1, 15, 10, 8)
    (line,) = _warnings(caplog)  # one line a launch, whatever it saw
    assert "3 debt(s)" in line
    # a lane that knows no leader owes no commit: elections are counted
    # where they happen
    w.o["leader"][0] = 0
    w.step(2)
    assert w.ints() == (6, 1, 0, 3)


def test_a_state_machine_that_stands_below_its_commit_is_an_apply_stall(
    events,
):
    w = Watch()
    w.append(acked_by=(1, 2))
    w.lanes[2].node.sm.applied = 10  # committed to 15, applied none of it
    # the level is taken every eighth launch against the one before: the
    # first finds the debt, the second finds it standing
    w.step(2 * _STALL_LAUNCHES - 1)
    assert w.ints() == (0, 0, 0, 0)
    w.step()
    assert w.ints() == (0, 0, 1, 1)
    (e,) = events()
    assert set(e) - {"t", "event"} == LANE_FIELDS
    assert (e["kind"], e["node"], e["commit"], e["applied"]) == (
        "apply", 3, 15, 10)
    w.step(_STALL_LAUNCHES)  # still: counted every launch, reported once
    assert w.ints() == (0, 0, 1 + _STALL_LAUNCHES, 1) and len(events()) == 1
    w.lanes[2].node.sm.applied = 12  # it moves, still behind: no stall
    w.step(_STALL_LAUNCHES)  # (counted up to the level that sees it move)
    assert w._sstats["apply_stall_steps"] == 2 * _STALL_LAUNCHES
    assert w._w_apply_n == 0
    w.lanes[2].node.sm.applied = 15
    w.step(2 * _STALL_LAUNCHES)
    assert w._sstats["apply_stall_steps"] == 2 * _STALL_LAUNCHES
    assert w._sstats["stalls_seen"] == 1


def test_the_blocks_take_their_apply_level_in_turn(events, monkeypatch):
    """No sweep looks at more than an eighth of the state machines: block
    b's level falls where the sweep's ordinal plus b is a multiple of
    eight, and each lane is still looked at every eighth sweep."""
    monkeypatch.setattr(vec, "_SWEEP_ELEMENTS", 8)
    w = _wide()  # lanes 0-7 are block 0, lanes 8-11 block 1
    for g in (3, 9):  # committed to 15, applied none of it
        w.o["last_index"][g] = w.o["commit_index"][g] = 15
        w.o["match"][g, :2] = 15
    w.step(2 * _STALL_LAUNCHES - 2)
    assert w.ints() == (0, 0, 0, 0)
    w.step()  # the fifteenth sweep: block 1's second level
    assert w.ints() == (0, 0, 1, 1)
    w.step()  # the sixteenth: block 0's
    assert w.ints() == (0, 0, 3, 2)
    assert [e["lane"] for e in events()] == [9, 3]
    w.lanes[9].node.sm.applied = 15
    w.step(_STALL_LAUNCHES)
    assert w._w_apply_n == 1 and w._w_apply_stalled.tolist().count(True) == 1


def test_launches_shorter_than_an_election_timeout_are_not_sweeps(events):
    """Where a launch is a tick (bring-up, an idle loop) eight of them are
    no time at all: a debt is a stall after eight sweeps, an election
    timeout apart, and stands counted at every launch in between."""
    w = Watch()
    w.ticks = 1  # ten launches an election timeout
    w.step(30)
    w.append(acked_by=(1,))
    w.step(10 * _STALL_LAUNCHES - 1)
    assert w.ints() == (0, 0, 0, 0) and events() == []
    w.step()
    assert w.ints() == (1, 0, 0, 1) and len(events()) == 1
    w.step(25)  # counted a launch, swept or not
    assert w.ints() == (26, 0, 0, 1)
    w.o["match"][0, 2] = 15
    w.step(10)  # paid at the next sweep
    n = w._sstats["peer_stall_steps"]
    w.step(10)
    assert w._sstats["peer_stall_steps"] == n <= 26 + 10


def test_an_idle_fleet_counts_nothing_over_a_hundred_launches(events):
    w = Watch()
    w.step(100)
    w.append(acked_by=(1, 2))
    w.step(100)
    assert w.ints() == (0, 0, 0, 0) and events() == []


@pytest.mark.parametrize("block", [vec._SWEEP_ELEMENTS, 8],
                         ids=["one-block", "blocks-of-8"])
def test_no_more_than_eight_events_a_launch_and_every_stall_counted(
    events, caplog, monkeypatch, block,
):
    """Twelve lanes in one block as the engine sweeps them, and in two
    blocks of lanes and six chunks of leaders' rows: the same count."""
    monkeypatch.setattr(vec, "_SWEEP_ELEMENTS", block)
    caplog.set_level(logging.WARNING)
    w = _wide()
    w.step(2)
    w.o["last_index"][:] += 5  # a peer debt and a commit debt a lane
    w.step(_STALL_LAUNCHES)
    assert w._sstats["stalls_seen"] == 12 + 12
    assert len(events()) == _STALL_EVENTS_PER_LAUNCH
    (line,) = _warnings(caplog)
    assert "24 debt(s)" in line


def _wide():
    """Twelve leaders, each with one voting peer that never answers."""
    class Wide(Watch):
        G = 12

    w = Wide()
    G = w.G
    w._m_active = np.ones(G, bool)
    w.lanes = [_FakeLane(g, 100 + g, 1, 2) for g in range(G)]
    w._lane_by_g = list(w.lanes)
    w._route = {}
    w._m_owes[:] = False
    w._m_owes[:, 1] = True
    w.o["role"][:] = ROLE.LEADER
    w.o["leader"][:] = 1
    w.o["last_index"][:] = 10
    w.o["commit_index"][:] = 10
    w.o["match"][:] = 0
    w.o["match"][:, :2] = 10
    for ln in w.lanes:
        ln.node.sm.applied = 10
    return w


@pytest.mark.parametrize("ratio", [1, NEVER], ids=["sampled", "unsampled"])
def test_a_sweep_folds_on_sampled_iterations_only(ratio):
    w = Watch(ratio)
    w.step(3)
    w.append(acked_by=(1,))
    w.step(_STALL_LAUNCHES + 3)
    assert w.ints() == (4, 0, 0, 1)  # counted either way
    s = w.profiler.samples
    if ratio == 1:
        for name in FOLDS:  # once a sweep, 0 included: the anchor
            assert len(s[name]) == w.launch_no, name
        assert s["n.peer_stall_steps"].mean() * w.launch_no == 4
        assert s["n.commit_stall_steps"].mean() == 0
    else:
        assert not [n for n in s if n.startswith("n.")]


def test_the_watch_reads_no_clock(monkeypatch):
    w = Watch()
    w.step(2)
    fake = types.SimpleNamespace(
        monotonic=lambda: pytest.fail("monotonic"),
        thread_time=lambda: pytest.fail("thread_time"),
    )
    monkeypatch.setattr(vec, "time", fake)
    w.append(acked_by=(1,))
    w.step(4 * _STALL_LAUNCHES)  # sweeps and apply levels, stalls standing
    assert w._sstats["peer_stall_steps"] > 0


def test_the_constants_and_the_lint():
    from dragonboat_tpu.analysis.targets import DEFAULT_TARGETS, VECTOR

    assert (_STALL_LAUNCHES, _STALL_EVENTS_PER_LAUNCH) == (8, 8)
    assert _STALL_LAUNCHES == 2 * vec._ACK_LAUNCHES
    for fn in ("_maintain", "_watch_progress", "_sweep_progress",
               "_sweep_peers", "_level_applied"):
        key = (VECTOR, "VectorEngine." + fn)
        assert key in DEFAULT_TARGETS.hot_telemetry_functions, key
    from dragonboat_tpu.config import EngineConfig

    assert not [f for f in EngineConfig.__dataclass_fields__ if "stall" in f]


# ------------------------------------------------------------ real clusters
STEPS = {"k1": 1, "auto3": None, "k8": 8}


def _cluster(tmp_path, steps, tag, sm=LogSM, **engine):
    if STEPS[steps] is not None:
        engine["steps_per_sync"] = STEPS[steps]
    if steps == "k1":
        engine["overlap_decode"] = True  # the chip's K = 1 loop order
    import tests.test_auto_steps as tas

    old = tas.LogSM
    tas.LogSM = sm  # _bring_up starts every replica with tas.LogSM
    try:
        return _bring_up(tmp_path, f"pw-{tag}-{steps}", f"pw{tag}{steps}",
                         **engine)
    finally:
        tas.LogSM = old


def _wait(cond, bound_s=30, what=""):
    deadline = time.monotonic() + bound_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.01)


def _ints(core):
    st = core.step_stats()
    return {k: st[k] for k in INTS}


@pytest.mark.parametrize("steps", sorted(STEPS))
def test_a_cut_follower_is_a_peer_stall_and_clears_with_the_cut(
    tmp_path, steps, caplog,
):
    caplog.set_level(logging.WARNING)
    hosts, lead = _cluster(tmp_path, steps, "peer", profile_sample_ratio=1)
    try:
        core = hosts[1].engine.core
        sent = []
        _propose_n(hosts[lead], 5, b"a", sent)
        _converged(hosts, sent)
        assert _ints(core) == dict.fromkeys(INTS, 0)
        victim = next(n for n in hosts if n != lead)
        flight_recorder().reset()
        at = core.launch_no
        core.set_local_drop_hook(
            lambda m: m.to == victim or m.from_ == victim)
        _propose_n(hosts[lead], 5, b"b", sent)
        _wait(lambda: _ints(core)["stalls_seen"] >= 1, what=_ints(core))
        (e,) = flight_recorder().dump(event="progress_stall")
        assert set(e) - {"t", "event"} == PEER_FIELDS
        assert (e["kind"], e["cluster"], e["node"], e["peer"]) == (
            "peer", CLUSTER, lead, victim)
        assert e["launch"] - at >= _STALL_LAUNCHES and e["age"] == 8
        assert e["match"] < e["last"] and e["peer_last"] == e["match"]
        assert e["route"] == -1  # the hook takes every message to the host
        (line,) = _warnings(caplog)
        assert f"node={lead}" in line and f"peer={victim}" in line
        core.set_local_drop_hook(None)
        _converged(hosts, sent, bound_s=60)
        _wait(lambda: not core._w_peer_age.any(), what="debts paid")
        before = _ints(core)
        n = core.launch_no
        _wait(lambda: core.launch_no >= n + 3 * _STALL_LAUNCHES)
        assert _ints(core) == before
        assert before["apply_stall_steps"] == 0
        s = core.profiler.samples
        assert len(s["n.peer_stall_steps"]) == len(s["watch"]) > 0
        assert s["n.peer_stall_steps"].mean() > 0
    finally:
        core.set_local_drop_hook(None)
        _stop(hosts)


@pytest.mark.parametrize("steps", sorted(STEPS))
def test_a_leader_without_its_quorum_is_a_commit_stall(tmp_path, steps):
    hosts, lead = _cluster(tmp_path, steps, "commit")
    try:
        core = hosts[1].engine.core
        sent = []
        _propose_n(hosts[lead], 3, b"a", sent)
        _converged(hosts, sent)
        flight_recorder().reset()
        core.set_local_drop_hook(lambda m: True)  # every replica alone
        nh = hosts[lead]
        rs = nh.propose(nh.get_noop_session(CLUSTER), b"lost", 30)
        _wait(lambda: _ints(core)["commit_stall_steps"] >= 1,
              what=_ints(core))
        evs = flight_recorder().dump(event="progress_stall")
        (e,) = [e for e in evs if e["kind"] == "commit"]
        assert (e["node"], e["role"]) == (lead, ROLE.LEADER)
        assert e["last"] > e["commit"] == e["applied"] - e["base"]
        assert sorted(p["peer"] for p in evs if p["kind"] == "peer") == \
            sorted(n for n in hosts if n != lead)
        assert _ints(core)["apply_stall_steps"] == 0
        del rs
    finally:
        core.set_local_drop_hook(None)
        _stop(hosts)


class GateSM(LogSM):
    """LogSM whose update waits at a gate on the replicas in `held`."""

    held: set = set()
    gate = threading.Event()

    def __init__(self, cluster_id=0, node_id=0):
        super().__init__(cluster_id, node_id)
        self.node_id = node_id

    def update(self, data):
        if self.node_id in GateSM.held:
            GateSM.gate.wait(120)
        return super().update(data)


@pytest.mark.parametrize("steps", sorted(STEPS))
def test_an_update_that_blocks_is_an_apply_stall_on_that_lane_alone(
    tmp_path, steps,
):
    GateSM.held, GateSM.gate = set(), threading.Event()
    hosts, lead = _cluster(tmp_path, steps, "apply", sm=GateSM)
    try:
        core = hosts[1].engine.core
        sent = []
        _propose_n(hosts[lead], 3, b"a", sent)
        _converged(hosts, sent)
        # a follower whose apply worker serves no other replica of the
        # group: a worker that waits in one update serves nobody else
        part = core.task_ready.partition
        keys = {nid: hosts[nid]._get_node(CLUSTER)._vec_lane.key
                for nid in hosts}
        victim = next(
            n for n in hosts if n != lead and
            all(part(keys[n]) != part(keys[m]) for m in hosts if m != n)
        )
        flight_recorder().reset()
        GateSM.held = {victim}
        _propose_n(hosts[lead], 2, b"b", sent)  # the quorum applies them
        _wait(lambda: _ints(core)["apply_stall_steps"] >= 1,
              what=_ints(core))
        (e,) = flight_recorder().dump(event="progress_stall")
        assert (e["kind"], e["node"]) == ("apply", victim)
        assert e["applied"] < e["base"] + e["commit"]
        assert core._w_apply_n == 1
        ints = _ints(core)
        assert ints["peer_stall_steps"] == ints["commit_stall_steps"] == 0
        assert ints["stalls_seen"] == 1
        GateSM.gate.set()
        _converged(hosts, sent)
        _wait(lambda: core._w_apply_n == 0, what="the apply debt paid")
    finally:
        GateSM.gate.set()
        _stop(hosts)


def test_an_untraced_engine_counts_and_warns_and_folds_nothing(
    tmp_path, caplog,
):
    """The default sampling (one iteration in 32) with the sampled
    iterations' folds taken away: the ints, the event and the warning
    are there all the same."""
    caplog.set_level(logging.WARNING)
    hosts, lead = _cluster(tmp_path, "auto3", "off")
    try:
        core = hosts[1].engine.core
        core.profiler.ratio = NEVER
        sent = []
        _propose_n(hosts[lead], 3, b"a", sent)
        _converged(hosts, sent)
        _wait(lambda: not core.profiler.sampling)
        names = FOLDS + ("watch",)

        def folded():
            s = core.profiler.samples
            return [len(s[n]) if n in s else 0 for n in names]

        before = folded()
        victim = next(n for n in hosts if n != lead)
        flight_recorder().reset()
        core.set_local_drop_hook(
            lambda m: m.to == victim or m.from_ == victim)
        _propose_n(hosts[lead], 3, b"b", sent)
        _wait(lambda: _ints(core)["stalls_seen"] >= 1, what=_ints(core))
        assert len(flight_recorder().dump(event="progress_stall")) == 1
        assert len(_warnings(caplog)) == 1
        assert folded() == before
    finally:
        core.set_local_drop_hook(None)
        _stop(hosts)


# ------------------------------------------------------------- the readers
READERS = {
    "replication.stalled_peers_per_launch": ("n.peer_stall_steps", "replication"),
    "replication.commit_stalled_lanes_per_launch":
        ("n.commit_stall_steps", "replication"),
    "rsm.apply_stalled_lanes_per_launch": ("n.apply_stall_steps", "rsm"),
}


def _run(phases, ratio=1):
    # window["launches"] is protocol steps over the file's steps_per_sync:
    # three times the launches where the engine chose three steps
    return types.SimpleNamespace(client={}, window={
        "seconds": 15.0, "launches": 24.0, "phase_ratio": ratio,
        "phases": dict(phases),
    })


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_divides_by_the_programs_own_launches(name):
    read = load_plugin("layer_metrics", name).read
    counter = READERS[name][0]
    assert read(_run({"n.launches": 8.0, counter: 20.0})) == 2.5
    assert read(_run({"n.launches": 8.0, counter: 0.0})) == 0.0  # the anchor
    assert read(_run({"n.launches": 8.0})) is None  # a program without it
    assert read(_run({counter: 20.0})) is None
    assert read(_run({"n.launches": 8.0, counter: 20.0}, ratio=32)) is None


def test_the_three_are_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [m["name"] for m in spec["per_layer"][89:93]] == [
        "replication.stalled_peers_per_launch",
        "replication.commit_stalled_lanes_per_launch",
        "rsm.apply_stalled_lanes_per_launch",
        "engine.watch_ms_per_launch",  # what the watch itself costs
    ]
    layers = {m["layer"] for m in spec["per_layer"][:77]}
    for m in spec["per_layer"][89:92]:
        assert "workloads" not in m
        assert (m["source"], m["better"], m["moves"]) == (
            "program_counter", "lower", "committed_ops_per_s")
        assert m["layer"] == READERS[m["name"]][1] and m["layer"] in layers
    cost = spec["per_layer"][92]
    assert "workloads" not in cost
    assert (cost["source"], cost["better"], cost["moves"], cost["layer"]) == (
        "program_span", "lower", "committed_ops_per_s", "engine")


def test_the_watchs_own_cost_is_read_from_its_sub_span():
    read = load_plugin("layer_metrics", "engine.watch_ms_per_launch").read
    assert read(_run({"n.launches": 8.0, "watch": 0.004})) == 0.5
    assert read(_run({"n.launches": 8.0})) is None  # a program without it
    assert read(_run({"watch": 0.004})) is None
    assert read(_run({"n.launches": 8.0, "watch": 0.004}, ratio=32)) is None
