"""The engine chooses its own protocol steps a launch (ISSUE 35).

`EngineConfig.steps_per_sync` None: the whole commit in one launch
(three protocol steps) while every peer slot of every active lane is
routable on the device, the one-step loop otherwise, and a move between
the two at a launch boundary that loses and reorders nothing. What the
engine chose is read through `step_stats()['steps_per_launch']`.

Also here: what a torn save wave of such a launch leaves on disk, and
the benchmark's reader of the counters the loop folds.
"""
from __future__ import annotations

import json
import os
import threading
import time
import types

import numpy as np
import pytest

from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

from benchmark.run import load_plugin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLUSTER = 1
SHAPES = dict(
    max_groups=8, max_peers=4, log_window=256, inbox_depth=8,
    max_entries_per_msg=64,
)


class LogSM(IStateMachine):
    """Every command it was handed, in order."""

    def __init__(self, cluster_id=0, node_id=0):
        self.cmds = []

    def update(self, data):
        self.cmds.append(bytes(data))
        return Result(value=len(self.cmds))

    def lookup(self, q):
        return list(self.cmds)

    def save_snapshot(self, w, fc, done):
        w.write(json.dumps([c.decode() for c in self.cmds]).encode())

    def recover_from_snapshot(self, r, fc, done):
        self.cmds = [c.encode() for c in json.loads(r.read())]

    def close(self):
        pass


def _host(tmp_path, reg, nid, scope, name="as", **engine):
    kw = dict(SHAPES)
    kw.update(engine)
    return NodeHost(NodeHostConfig(
        raft_address=f"{name}{nid}:1",
        rtt_millisecond=10,
        nodehost_dir=str(tmp_path / f"nh-{name}-{nid}"),
        raft_rpc_factory=lambda a: loopback_factory(a, reg),
        engine=EngineConfig(kind="vector", share_scope=scope, **kw),
    ))


def _raft(nid, **kw):
    return Config(
        node_id=nid, cluster_id=CLUSTER, election_rtt=20, heartbeat_rtt=2,
        **kw,
    )


def _wait_leader(hosts, bound_s=120):
    deadline = time.monotonic() + bound_s
    while time.monotonic() < deadline:
        for nh in hosts.values():
            lid, ok = nh.get_leader_id(CLUSTER)
            if ok and lid in hosts:
                return lid
        time.sleep(0.02)
    raise AssertionError("no leader elected")


def _bring_up(tmp_path, scope, name="as", scopes=None, reg=None, **engine):
    """Three replicas of one group; `scopes` maps a node id to another
    share scope than `scope` (an engine core of its own)."""
    reg = reg or _Registry()
    members = {nid: f"{name}{nid}:1" for nid in (1, 2, 3)}
    hosts = {
        nid: _host(
            tmp_path, reg, nid, (scopes or {}).get(nid, scope), name,
            **engine,
        )
        for nid in members
    }
    for nid, nh in hosts.items():
        nh.start_cluster(dict(members), False, LogSM, _raft(nid))
    return hosts, _wait_leader(hosts)


def _stop(hosts):
    for nh in hosts.values():
        try:
            nh.stop()
        except Exception:
            pass


def _steps(core):
    return core.step_stats()["steps_per_launch"]


def _wait_steps(core, want, bound_s=20):
    deadline = time.monotonic() + bound_s
    while _steps(core) != want:
        assert time.monotonic() < deadline, (want, core.step_stats())
        time.sleep(0.01)


def _propose_n(nh, n, tag, sent):
    sess = nh.get_noop_session(CLUSTER)
    for i in range(n):
        cmd = b"%s-%03d" % (tag, i)
        r = nh.propose(sess, cmd, 10).wait(10)
        assert r is not None and r.completed, (tag, i)
        sent.append(cmd)


def _converged(hosts, sent, bound_s=20):
    for nid, nh in hosts.items():
        deadline = time.monotonic() + bound_s
        while nh.stale_read(CLUSTER, None) != sent:
            assert time.monotonic() < deadline, nid
            time.sleep(0.01)


# --------------------------------------------------------------- the rule
def _hook(core, hosts):
    core.set_local_drop_hook(lambda m: False)
    return lambda: core.set_local_drop_hook(None)


def _blocked_host(core, hosts):
    fol = next(n for n in hosts if n != _wait_leader(hosts))
    hosts[fol].engine.set_host_partitioned(True)
    return lambda: hosts[fol].engine.set_host_partitioned(False)


def _recovering_lane(core, hosts):
    fol = next(n for n in hosts if n != _wait_leader(hosts))
    node = hosts[fol]._get_node(CLUSTER)
    lane = node._vec_lane
    # what _handle_install_snapshot does to the lane before the restore
    lane.recovering = True
    core._m_recovering[lane.g] = True
    core._routes_dirty = True
    return lambda: core.recover_done(node)


@pytest.mark.parametrize(
    "disturb", [_hook, _blocked_host, _recovering_lane],
    ids=["hook", "blocked_host", "recovering_lane"],
)
def test_all_routable_runs_three_and_a_disturbance_one(tmp_path, disturb):
    """Three co-hosted replicas: the engine runs three steps a launch;
    each thing the host path special-cases takes it to one, and it comes
    back when the thing is gone. Every write is applied once on every
    replica through both switches."""
    hosts, lead = _bring_up(tmp_path, f"auto-{disturb.__name__}")
    try:
        core = hosts[1].engine.core
        assert core._steps_cfg is None and core._auto
        sent = []
        _wait_steps(core, 3)
        _propose_n(hosts[lead], 10, b"up", sent)
        st = core.step_stats()
        assert st["steps_per_launch"] == 3 and st["msgs_routed_device"] > 0
        undo = disturb(core, hosts)
        _wait_steps(core, 1)
        assert not core._m_resid.any()
        _propose_n(hosts[_wait_leader(hosts)], 10, b"down", sent)
        assert _steps(core) == 1
        undo()
        _wait_steps(core, 3)
        _propose_n(hosts[_wait_leader(hosts)], 10, b"back", sent)
        _converged(hosts, sent)
        assert core.step_stats()["loop_exceptions"] == 0
    finally:
        _stop(hosts)


def test_a_remote_peer_keeps_one_step(tmp_path):
    """Two replicas share a core, the third has a core of its own: a
    peer slot is not routable on either, so both run the one-step loop
    and never build the three-step program."""
    hosts, lead = _bring_up(
        tmp_path, "auto-remote-a", scopes={3: "auto-remote-b"}
    )
    try:
        a, b = hosts[1].engine.core, hosts[3].engine.core
        assert a is hosts[2].engine.core and a is not b
        sent = []
        _propose_n(hosts[lead], 10, b"r", sent)
        _converged(hosts, sent)
        for core in (a, b):
            st = core.step_stats()
            assert st["steps_per_launch"] == 1
            # (a launch may be out and not yet decoded)
            assert st["launches"] - st["steps"] in (0, 1)
        assert b._multi_fn is None  # nothing to route: never built
    finally:
        _stop(hosts)


def test_a_witness_slot_keeps_one_step(tmp_path):
    """A witness is served on the host path (its senders strip payloads),
    so a group with one is not all-routable."""
    reg = _Registry()
    members = {1: "aw1:1", 2: "aw2:1"}
    hosts = {
        nid: _host(tmp_path, reg, nid, "auto-witness", "aw")
        for nid in (1, 2, 3)
    }
    try:
        for nid in (1, 2):
            hosts[nid].start_cluster(dict(members), False, LogSM, _raft(nid))
        two = {n: hosts[n] for n in (1, 2)}
        lead = _wait_leader(two)
        core = hosts[1].engine.core
        _wait_steps(core, 3)
        hosts[lead].sync_request_add_witness(CLUSTER, 3, "aw3:1", timeout_s=15.0)
        hosts[3].start_cluster({}, True, LogSM, _raft(3, is_witness=True))
        _wait_steps(core, 1)
        sent = []
        _propose_n(hosts[_wait_leader(two)], 10, b"w", sent)
        _converged(two, sent)
        assert _steps(core) == 1
    finally:
        _stop(hosts)


def test_an_integer_is_exactly_that(tmp_path):
    """steps_per_sync 1 on a shared core stays the one-step loop with
    every peer routable: the option forces."""
    hosts, lead = _bring_up(tmp_path, "auto-forced-1", steps_per_sync=1)
    try:
        core = hosts[1].engine.core
        assert core._steps_cfg == 1 and not core._auto
        sent = []
        _propose_n(hosts[lead], 10, b"f", sent)
        _converged(hosts, sent)
        st = core.step_stats()
        assert st["steps_per_launch"] == 1
        assert st["launches"] - st["steps"] in (0, 1)
        assert st["msgs_routed_device"] == 0 and core._multi_fn is None
    finally:
        _stop(hosts)


def test_hosts_of_one_core_ask_for_the_same_steps(tmp_path):
    reg = _Registry()
    first = _host(tmp_path, reg, 1, "auto-mismatch")
    try:
        with pytest.raises(ValueError, match="steps_per_sync"):
            _host(tmp_path, reg, 2, "auto-mismatch", steps_per_sync=3)
    finally:
        first.stop()


# -------------------------------------------------------------- the switch
class _Launches:
    """The two step programs of a core, wrapped: what every launch found
    parked in the residual inbox, and whether its table routed."""

    def __init__(self, core):
        self.core = core
        self.one_step_over_parked = 0
        self.drains = []  # rows a launch took in under a table of -1
        self.by_steps = {1: 0, 3: 0}
        self._step, self._multi = core._step_fn, core._multi_fn
        core._step_fn = self.step
        core._multi_fn = self.multi

    def step(self, *a):
        self.by_steps[1] += 1
        if self.core._m_resid.any():
            self.one_step_over_parked += 1
        return self._step(*a)

    def multi(self, state, ints, bools, resid):
        # the route table rides the launch's slabs: the host plane is
        # the view of what this launch put
        self.by_steps[3] += 1
        parked = int(self.core._m_resid.sum())
        if parked and not (self.core._np_route >= 0).any():
            self.drains.append(parked)
        return self._multi(state, ints, bools, resid)


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "plain"])
def test_a_hook_set_and_cleared_under_load_loses_nothing(tmp_path, overlap):
    """Batches in flight while a chaos hook comes and goes: the engine
    moves down and up at launch boundaries, the one-step program never
    runs over parked residual rows, a launch that drains them takes them
    all in, every batch is acknowledged once and the replicas end equal.
    `overlap` runs the one-step stretches in the chip's loop order, with
    a step in flight at every switch up."""
    hosts, lead = _bring_up(
        tmp_path, f"auto-switch-{overlap}", overlap_decode=overlap,
    )
    try:
        core = hosts[1].engine.core
        _wait_steps(core, 3)
        watch = _Launches(core)
        sess = hosts[lead].get_noop_session(CLUSTER)
        sent, done, stop = [], [], threading.Event()

        def client():
            b = 0
            while not stop.is_set():
                cmds = [b"b%03d-%02d" % (b, i) for i in range(16)]
                h = hosts[lead].propose_batch_async(sess, cmds, 10)
                ok = h.wait(10)
                done.append((ok, h.completed, h.dropped))
                sent.extend(cmds)
                b += 1

        t = threading.Thread(target=client, daemon=True)
        t.start()
        seen = set()
        for _ in range(6):
            core.set_local_drop_hook(lambda m: False)
            _wait_steps(core, 1)
            assert core._overlap is overlap
            time.sleep(0.05)
            seen.add(_steps(core))
            core.set_local_drop_hook(None)
            _wait_steps(core, 3)
            assert core._overlap is False and core._pending is None
            time.sleep(0.05)
            seen.add(_steps(core))
        stop.set()
        t.join(20)
        assert not t.is_alive()
        assert seen == {1, 3}
        assert done and all(d == (True, 16, 0) for d in done), done
        _converged(hosts, sent)
        assert watch.by_steps[1] > 0 and watch.by_steps[3] > 0
        assert watch.one_step_over_parked == 0
        # a switch down met parked rows at least once in six, and the
        # launch that took them in left none behind
        assert watch.drains, watch.by_steps
        assert not core._m_resid.any() or _steps(core) == 3
        assert core.step_stats()["loop_exceptions"] == 0
    finally:
        _stop(hosts)


def test_a_single_host_engine_never_builds_the_three_step_program(tmp_path):
    reg = _Registry()
    nh = _host(tmp_path, reg, 1, None, "solo")
    try:
        nh.start_cluster({1: "solo1:1"}, False, LogSM, _raft(1))
        _wait_leader({1: nh})
        sent = []
        _propose_n(nh, 5, b"s", sent)
        core = nh.engine.core
        assert core._auto and core._multi_fn is None and core._resid is None
        assert _steps(core) == 1
    finally:
        nh.stop()


# ----------------------------------------------------------- a torn wave
class _Died(RuntimeError):
    pass


class _Tear:
    """From `arm()` on, the first save wave that carries one of the
    armed commands is torn: `keep`'s store takes its write and its
    barrier, every other host's write never reaches its store, and
    nothing is written by anyone afterwards: the process died inside
    the wave."""

    def __init__(self):
        self.keep = None
        self.cmds = None
        self.torn = threading.Event()

    def arm(self, keep, cmds):
        self.cmds, self.keep = set(cmds), keep

    def carries(self, updates):
        return self.cmds is not None and any(
            e.cmd in self.cmds for ud in updates for e in ud.entries_to_save
        )


class _TornSync:
    def __init__(self, kv, tear):
        self._kv, self._tear = kv, tear

    def __getattr__(self, name):
        return getattr(self._kv, name)

    def sync(self):
        self._kv.sync()
        self._tear.torn.set()
        raise _Died("after the first store's barrier")


class _TornLogDB:
    def __init__(self, inner, tear, nid):
        self._inner, self._tear, self._nid = inner, tear, nid

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def save_raft_state(self, updates):
        if self._tear.torn.is_set():
            raise _Died("dead")
        self._inner.save_raft_state(updates)

    def save_raft_state_deferred(self, updates):
        tear = self._tear
        if tear.torn.is_set():
            raise _Died("dead")
        if not tear.carries(updates):
            return self._inner.save_raft_state_deferred(updates)
        if self._nid != tear.keep:
            return []  # lost with the process
        kvs = self._inner.save_raft_state_deferred(updates)
        assert kvs
        return [_TornSync(kv, tear) for kv in kvs]


def test_a_torn_wave_leaves_no_commit_above_what_a_quorum_holds(tmp_path):
    """Co-hosted followers acknowledge on the device before the host has
    written, so within one wave a leader's hard state could carry a
    commit index over entries that only this wave makes durable on the
    followers' stores. Tear the wave after the leader's store: restarted
    from disk, no replica has a commit above what two of three hold, and
    the group goes on to agree."""
    reg = _Registry()
    name = "torn"
    hosts, lead = _bring_up(tmp_path, "auto-torn", name, reg=reg)
    sent = []
    try:
        core = hosts[1].engine.core
        _wait_steps(core, 3)
        _propose_n(hosts[lead], 8, b"pre", sent)
        _converged(hosts, sent)
        tear = _Tear()
        for nid, nh in hosts.items():
            node = nh._get_node(CLUSTER)
            node.logdb = _TornLogDB(node.logdb, tear, nid)
        batch = [b"torn-%02d" % i for i in range(32)]
        tear.arm(lead, batch)
        sess = hosts[lead].get_noop_session(CLUSTER)
        h = hosts[lead].propose_batch_async(sess, batch, 5)
        assert tear.torn.wait(10)
        time.sleep(0.2)
        assert h.completed == 0  # nothing of a torn wave was acknowledged
    finally:
        for nh in hosts.values():
            nh.crash()
    # what each store holds
    held = {}
    hosts = {nid: _host(tmp_path, reg, nid, "auto-torn-2", name)
             for nid in (1, 2, 3)}
    try:
        for nid, nh in hosts.items():
            rs = nh.logdb.read_raft_state(CLUSTER, nid, 0)
            held[nid] = (
                rs.state.commit, rs.first_index + rs.entry_count - 1
            )
        lasts = sorted((last for _c, last in held.values()), reverse=True)
        quorum_holds = lasts[1]
        assert held[lead][1] > quorum_holds, (
            "the wave was not torn after the leader's store", held)
        for nid, (commit, _last) in held.items():
            assert commit <= quorum_holds, (nid, held)
        # and the group recovers: one history on all three, the
        # acknowledged writes all in it
        members = {nid: f"{name}{nid}:1" for nid in hosts}
        for nid, nh in hosts.items():
            nh.start_cluster(dict(members), False, LogSM, _raft(nid))
        lead2 = _wait_leader(hosts)
        more = []
        _propose_n(hosts[lead2], 5, b"post", more)
        deadline = time.monotonic() + 20
        while True:
            logs = [nh.stale_read(CLUSTER, None) for nh in hosts.values()]
            if logs[0] == logs[1] == logs[2] and logs[0][-5:] == more:
                break
            assert time.monotonic() < deadline, logs
            time.sleep(0.02)
        assert logs[0][:len(sent)] == sent
    finally:
        _stop(hosts)


# ------------------------------------------- the benchmark's reader of it
def _window(ratio=1, **phases):
    return types.SimpleNamespace(window={
        "seconds": 2.0, "launches": 10.0, "phase_ratio": ratio,
        "phases": phases,
    })


def test_the_metric_is_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (m,) = [m for m in spec["per_layer"]
            if m["name"] == "engine.steps_per_launch"]
    assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
        "engine", "committed_ops_per_s", "program_counter", "steps")
    assert "workloads" not in m
    assert spec["per_layer"][76] is m  # appended, nothing moved


@pytest.mark.parametrize("launches, steps, want", [
    (40.0, 120.0, 3.0), (40.0, 40.0, 1.0), (7.0, 56.0, 8.0),
    (10.0, 12.0, 1.2),
])
def test_reader(launches, steps, want):
    read = load_plugin("layer_metrics", "engine.steps_per_launch").read
    run = _window(**{"n.launches": launches, "n.launch_steps": steps})
    assert read(run) == pytest.approx(want)


def test_reader_without_the_counters():
    read = load_plugin("layer_metrics", "engine.steps_per_launch").read
    # a program without the counters, as the parent: nothing, no raise
    assert read(_window(**{"pack": 0.6, "n.packs": 10.0})) is None
    # below full sampling a count is not whole
    assert read(_window(32, **{"n.launches": 4.0, "n.launch_steps": 12.0})) is None
    # a window without a launch
    assert read(_window(**{"n.launches": 0.0, "n.launch_steps": 0.0})) is None


@pytest.mark.parametrize("k, want", [(None, 3.0), (1, 1.0), (8, 8.0)])
def test_the_loop_folds_what_it_launched(tmp_path, k, want):
    """At full sampling every launch folds one `n.launches` and its
    protocol steps; the reader over the engine's own samples gives the
    launch's step count."""
    hosts, lead = _bring_up(
        tmp_path, f"auto-fold-{k}", f"fold{k}", steps_per_sync=k,
        profile_sample_ratio=1,
    )
    try:
        core = hosts[1].engine.core
        _wait_steps(core, int(want))
        samples = core.profiler.samples

        def snap():
            return {
                name: s.mean() * len(s)
                for name, s in list(samples.items())
                if name in ("n.launches", "n.launch_steps")
            }

        a = snap()
        sent = []
        _propose_n(hosts[lead], 10, b"c", sent)
        b = snap()
        run = _window(**{n: b[n] - a.get(n, 0.0) for n in b})
        read = load_plugin("layer_metrics", "engine.steps_per_launch").read
        assert read(run) == pytest.approx(want)
    finally:
        _stop(hosts)


def test_an_unsampled_launch_folds_nothing(tmp_path):
    hosts, lead = _bring_up(
        tmp_path, "auto-fold-off", "foldoff", profile_sample_ratio=1 << 30,
    )
    try:
        core = hosts[1].engine.core
        sent = []
        _propose_n(hosts[lead], 5, b"u", sent)
        n = core.profiler.samples.get("n.launches")
        assert n is None or len(n) <= 1  # the first iteration is sampled
        assert core.step_stats()["launches"] > 5
    finally:
        _stop(hosts)
