"""The save wave encodes a group's entries once, not once a replica
(ISSUE 38).

Co-hosted replicas of a group hand the same `Entry` objects to
`_Shard._save_entries`; the body of a batch record is made by the first
of them and taken by the others from the loop's `RecordBodies`, which
lives two waves. Held here: the files are byte for byte what they are
without the table, and what records built independently hold; how long a
body lives and what a hit needs; and how often the engine shares.
"""
from __future__ import annotations

import contextlib
import os
import sys
import time
import types

import pytest

from dragonboat_tpu import codec
from dragonboat_tpu.engine.vector import VectorEngine
from dragonboat_tpu.storage import keys
from dragonboat_tpu.storage.kv import (
    _OP_PUT, MemKV, WalKV, _decode_records, close_bodies, close_wave,
    open_bodies, open_wave, wave_bodies,
)
from dragonboat_tpu.storage.logdb import RecordBodies, ShardedLogDB, _Shard
from dragonboat_tpu.types import Entry, State, Update

from benchmark.lib import deploy
from benchmark.run import load_plugin

CID = 7
B = _Shard.BATCH


@contextlib.contextmanager
def wave(bodies):
    """One save wave of the engine's loop: the table turns, then stands
    on the thread while the logdbs write (None: a wave without one)."""
    if bodies is None:
        yield
        return
    bodies.turn()
    open_bodies(bodies)
    try:
        yield
    finally:
        close_bodies()


@contextlib.contextmanager
def counted():
    try:
        yield open_wave()
    finally:
        close_wave()


def ents(lo, hi, term, size):
    return [
        Entry(index=i, term=term, key=i * 3, client_id=i % 5,
              cmd=bytes([i % 251]) * size)
        for i in range(lo, hi + 1)
    ]


def update(nid, run, commit=0):
    return Update(
        cluster_id=CID, node_id=nid, entries_to_save=run,
        state=State(term=run[-1].term, vote=1, commit=commit),
    )


# ------------------------------------------------------------ byte identity
class Reference:
    """One replica's log as a plain dict, and the batch records its
    saves must have written, each value from codec.encode_entries over
    the record's entries."""

    def __init__(self, nid):
        self.nid = nid
        self.log = {}
        self.puts = []

    def save(self, run):
        first = run[0].index
        for i in [i for i in self.log if i >= first]:
            del self.log[i]  # a conflicting suffix goes
        for e in run:
            self.log[e.index] = e
        for bid in range(first // B, run[-1].index // B + 1):
            self._put(bid)

    def remove_to(self, index):
        cut = [i for i in self.log if i <= index]
        for i in cut:
            del self.log[i]
        if any(i // B == (index + 1) // B for i in cut):
            self._put((index + 1) // B)  # the boundary record, rewritten

    def _put(self, bid):
        rec = [self.log[i] for i in sorted(self.log) if i // B == bid]
        self.puts.append(
            (keys.batch_key(CID, self.nid, bid), codec.encode_entries(rec))
        )


def script(size):
    """(wave, {replica ordinal: run}) in order; ordinal -1 is the last
    replica, the follower that falls behind. `cut` and `reopen` are what
    happens between two waves."""
    a = ents(1, 20, 1, size)       # a cold store, from inside record 0
    b = ents(21, 30, 1, size)      # head merge: record 2 holds 16..20
    c = ents(27, 33, 2, size)      # rewrite from inside record 3, term 2
    d = ents(34, 45, 2, size)
    e = ents(46, 50, 2, size)
    f = ents(51, 60, 2, size)      # after the cut inside record 6
    g = ents(61, 70, 2, size)      # after the stores were reopened
    h = ents(71, 72, 2, size)      # runs of one and two, as YCSB's
    i = ents(73, 73, 2, size)
    return [
        ("save", {None: a}),
        ("save", {None: b}),
        ("save", {None: c}),
        ("save", {None: d, -1: None}),       # the last replica skips...
        ("save", {None: e, -1: d + e}),      # ...and saves a double run
        ("cut", 49),
        ("save", {None: f}),
        ("reopen", None),
        ("save", {None: g}),
        ("save", {None: h}),
        ("save", {None: i}),
    ]


def play(root, replicas, size, shared):
    """The script through `replicas` shards over WalKV, with the loop's
    table or without; returns (wal.log bytes per replica, the references,
    entries shared, entries read back after the last reopen)."""
    dirs = [os.path.join(root, f"r{r}") for r in range(replicas)]
    shards = [_Shard(WalKV(d, fsync=False)) for d in dirs]
    refs = [Reference(r + 1) for r in range(replicas)]
    bodies = RecordBodies() if shared else None
    taken = 0
    for what, arg in script(size):
        if what == "cut":
            for sh, ref in zip(shards, refs):
                sh.remove_entries_to(CID, ref.nid, arg)
                ref.remove_to(arg)
            continue
        if what == "reopen":
            for sh in shards:
                sh.kv.close()
            shards = [_Shard(WalKV(d, fsync=False)) for d in dirs]
            continue
        with wave(bodies), counted() as parts:
            for r, (sh, ref) in enumerate(zip(shards, refs)):
                last = replicas > 1 and r == replicas - 1
                run = arg.get(-1, arg[None]) if last else arg[None]
                if run is None:
                    continue
                kv = sh.save_raft_state_deferred([update(ref.nid, run)])
                assert kv is None  # fsync off: nothing owed
                ref.save(run)
        taken += parts["entries_shared"]
    for sh in shards:
        sh.kv.close()
    files = []
    for d in dirs:
        with open(os.path.join(d, "wal.log"), "rb") as f:
            files.append(f.read())
    back = []
    for d, ref in zip(dirs, refs):
        sh = _Shard(WalKV(d, fsync=False))
        back.append(sh.iterate_entries(CID, ref.nid, 50, 74, 1 << 30)[0])
        sh.kv.close()
    return files, refs, taken, back


@pytest.mark.parametrize("size", [16, 128])
@pytest.mark.parametrize("replicas", [1, 3, 5])
def test_the_files_are_the_same_bytes_with_the_table_and_without(
        tmp_path, replicas, size):
    on, refs, taken, back = play(str(tmp_path / "on"), replicas, size, True)
    off, _refs, none, _back = play(str(tmp_path / "off"), replicas, size, False)
    assert on == off
    assert none == 0
    # a replica that is not the first to save takes 69 of the 77 entries
    # it saves: not the 5 + 3 that go into a record whose retained prefix
    # it decoded itself (after the cut, after the reopening: its own
    # copies of those entries). The late one's double run 34..50 is cut
    # at 40 and 48 where the others' two runs were cut at 40, 46 and 48,
    # and it takes all of it: record 40..47 is what their head merge of
    # 46..47 onto 40..45 made
    assert taken == (replicas - 1) * 69
    batch = keys.batch_key(CID, 1, 0)[:1]
    for data, ref in zip(on, refs):
        wb, sealed = _decode_records(data)
        assert sealed == len(data)
        puts = [(k, v) for op, k, v in wb.ops
                if op == _OP_PUT and k[:1] == batch]
        assert puts == ref.puts
    want = [refs[0].log[i] for i in range(50, 74)]
    for got in back:
        assert [(e.index, e.term, e.cmd, e.key, e.client_id) for e in got] \
            == [(e.index, e.term, e.cmd, e.key, e.client_id) for e in want]


def test_a_run_with_a_hole_takes_the_walk_and_shares_nothing():
    run = ents(3, 9, 1, 16) + ents(20, 30, 1, 16)
    bodies = RecordBodies()
    stores = []
    for nid in (1, 2):
        sh = _Shard(MemKV())
        with wave(bodies), counted() as parts:
            sh.save_raft_state_deferred([update(nid, run)])
        assert (parts["entries"], parts["entries_shared"]) == (18, 0)
        stores.append(sh)
    assert len(bodies) == 0
    for nid, sh in zip((1, 2), stores):
        for bid, lo, hi in ((0, 3, 7), (1, 8, 9), (2, 20, 23), (3, 24, 30)):
            assert sh.kv.get_value(keys.batch_key(CID, nid, bid)) == \
                codec.encode_entries([e for e in run if lo <= e.index <= hi])


# ------------------------------------------------------------------ scope
def save(sh, nid, run, bodies):
    with counted() as parts:
        open_bodies(bodies)
        try:
            sh.save_raft_state_deferred([update(nid, run)])
        finally:
            close_bodies()
    return parts["entries_shared"]


def test_a_body_lives_two_waves_and_keeps_nothing_alive_after():
    run = ents(8, 31, 1, 16)  # three whole records
    probe = run[0]
    held = sys.getrefcount(probe)
    bodies = RecordBodies()
    shards = [_Shard(MemKV()) for _ in range(4)]
    bodies.turn()  # wave t
    assert save(shards[0], 1, run, bodies) == 0
    assert save(shards[1], 2, run, bodies) == 24
    assert len(bodies) == 3 and sys.getrefcount(probe) == held + 1
    bodies.turn()  # wave t + 1
    assert save(shards[2], 3, run, bodies) == 24
    assert len(bodies.now) == 0 and len(bodies.old) == 3
    bodies.turn()  # wave t + 2: made anew
    assert save(shards[3], 4, run, bodies) == 0
    assert len(bodies.now) == 3 and len(bodies.old) == 0
    for nid, sh in enumerate(shards, 1):
        for bid in (1, 2, 3):
            assert sh.kv.get_value(keys.batch_key(CID, nid, bid)) == \
                codec.encode_entries(run[(bid - 1) * 8:bid * 8])
    bodies.turn()
    bodies.turn()  # two empty waves
    assert len(bodies) == 0
    assert sys.getrefcount(probe) == held  # a shard's cache: record 3


@pytest.mark.parametrize("field", ["index", "term"])
def test_an_entry_that_changed_since_it_was_saved_is_encoded_anew(field):
    run = ents(8, 23, 1, 16)
    bodies = RecordBodies()
    bodies.turn()
    first, second = _Shard(MemKV()), _Shard(MemKV())
    assert save(first, 1, run, bodies) == 0
    for e in run:  # the same objects, placed again
        if field == "index":
            e.index += 16
        else:
            e.term = 2
    bodies.turn()
    assert save(second, 2, run, bodies) == 0
    lo = run[0].index // B
    for bid in (lo, lo + 1):
        raw = second.kv.get_value(keys.batch_key(CID, 2, bid))
        got = codec.decode_entries(raw)[0]
        want = run[(bid - lo) * 8:(bid - lo + 1) * 8]
        assert [(e.index, e.term) for e in got] == \
            [(e.index, e.term) for e in want]
    # and as they now are, they are shared again
    assert save(first, 3, run, bodies) == 16


def _engine(hosts, logdb):
    lane = types.SimpleNamespace(node=types.SimpleNamespace(logdb=logdb))
    engine = types.SimpleNamespace(
        _next_host=hosts, _logdb=logdb, _record_bodies=RecordBodies())
    return engine, lane


@pytest.mark.parametrize("lanes", [1, 2])
def test_a_wave_that_raises_leaves_no_table_on_the_thread(lanes):
    seen = []

    class Broken:
        def save_raft_state(self, updates):
            seen.append(wave_bodies())
            raise OSError("disk")

        save_raft_state_deferred = save_raft_state

    engine, lane = _engine(3, Broken())
    run = ents(1, 8, 1, 16)
    with pytest.raises(OSError):
        VectorEngine._save_updates(
            engine, [update(1, run)] * lanes, [(lane, run, None)] * lanes)
    assert seen == [engine._record_bodies]
    assert wave_bodies() is None


def test_the_loop_turns_the_table_on_empty_waves_and_one_host_has_none(
        tmp_path):
    db = ShardedLogDB(str(tmp_path / "db"), num_shards=2)
    try:
        seen = []
        inner = db.save_raft_state

        def spy(updates):
            seen.append(wave_bodies())
            inner(updates)

        db.save_raft_state = spy
        run = ents(8, 15, 1, 16)
        engine, lane = _engine(3, db)
        VectorEngine._save_updates(engine, [update(1, run)], [(lane, run, None)])
        assert seen == [engine._record_bodies] and wave_bodies() is None
        assert len(engine._record_bodies) == 1
        VectorEngine._save_updates(engine, [], [])
        assert len(engine._record_bodies) == 1
        VectorEngine._save_updates(engine, [], [])
        assert len(engine._record_bodies) == 0
        # one NodeHost on the core: the path as it was, no table
        alone, lane = _engine(1, db)
        run = ents(16, 23, 1, 16)
        VectorEngine._save_updates(alone, [update(1, run)], [(lane, run, None)])
        VectorEngine._save_updates(alone, [], [])
        assert seen[-1] is None and len(seen) == 2
        assert len(alone._record_bodies) == 0
        assert db.read_raft_state(CID, 1, 0).entry_count == 16
    finally:
        db.close()


def test_another_threads_save_finds_no_table():
    import threading

    bodies = RecordBodies()
    seen = []
    open_bodies(bodies)
    try:
        t = threading.Thread(target=lambda: seen.append(wave_bodies()))
        t.start()
        t.join(30)
        assert wave_bodies() is bodies
    finally:
        close_bodies()
    assert seen == [None]


# ------------------------------------------------------------- the engine
kv128 = load_plugin("statemachines", "kv128")


def _deployment(replicas, **engine):
    return {
        "deployment": {"groups": 4, "replicas": replicas},
        "statemachine": "kv128",
        "nodehost": {"rtt_millisecond": 5},
        "raft": {"election_rtt": 40, "heartbeat_rtt": 4,
                 "snapshot_entries": 0},
        "engine": {"max_peers": 8, "log_window": 256, "inbox_depth": 8,
                   "max_entries_per_msg": 64, **engine},
    }


def shares(core):
    """(entries shared, entries saved) over a core's sampled waves."""
    s = core.profiler.samples

    def total(name):
        return s[name].mean() * len(s[name]) if name in s else 0.0

    assert len(s["n.save_entries"]) == len(s["n.save_entries_shared"]) \
        == len(s["n.save_wal_records"])
    return total("n.save_entries_shared"), total("n.save_entries")


def _logs(cluster, groups, last):
    return [
        [
            [(e.index, e.term, e.cmd) for e in nh.logdb.iterate_entries(
                g + 1, nid, 1, last[g] + 1, 1 << 40)[0]]
            for nid, nh in cluster.hosts.items()
        ]
        for g in range(groups)
    ]


@pytest.mark.parametrize("steps", [1, 3])
@pytest.mark.parametrize("replicas", [1, 3, 5])
def test_the_engine_shares_all_replicas_but_one(tmp_path, replicas, steps):
    """A steady closed loop: batches of 24, one at a time a group. At one
    step a launch a follower saves a wave after its leader, and takes
    what the wave before made."""
    groups = 4
    over = {"profile_sample_ratio": 1}
    if steps == 1:
        over["steps_per_sync"] = 1
    cluster = deploy.Cluster(
        _deployment(replicas), groups, kv128.StateMachine, str(tmp_path),
        over)
    payloads = kv128.Payloads(38, groups)
    rows = 0
    try:
        cluster.start()
        leaders = cluster.wait_leaders(120.0)
        core = cluster.core
        if replicas > 1:
            deadline = time.monotonic() + 60
            while core.step_stats()["steps_per_launch"] != steps:
                assert time.monotonic() < deadline
                time.sleep(0.02)
        for _ in range(6):
            handles = [
                cluster.hosts[leaders[g]].propose_batch_async(
                    cluster.session(leaders[g], g),
                    payloads.cmds(g, rows, rows + 24), 10.0)
                for g in range(groups)
            ]
            for h in handles:
                assert h.wait(20) and h.completed == 24
            rows += 24
        want = [(rows, payloads.sum64(g, rows)) for g in range(groups)]
        deadline = time.monotonic() + 20
        while any(
            nh.stale_read(g + 1, None) != want[g]
            for nh in cluster.hosts.values() for g in range(groups)
        ):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert core.step_stats()["steps_per_launch"] == (
            steps if replicas > 1 else 1)
        last = [
            cluster.hosts[1]._get_node(g + 1).log_reader.get_range()[1]
            for g in range(groups)
        ]
        logs = _logs(cluster, groups, last)
    finally:
        cluster.stop()
    shared, saved = shares(core)
    assert len(core._record_bodies) == 0  # the core stopped
    assert core.step_stats()["loop_exceptions"] == 0
    for g in range(groups):
        assert len(logs[g][0]) == last[g] >= rows
        assert all(log == logs[g][0] for log in logs[g])
    assert saved >= replicas * groups * rows
    if replicas == 1:
        assert shared == 0
        return
    # all but the first of every group's replicas, less a record or two
    # of the bring-up's (the first leader's entries at one step a launch)
    want = (replicas - 1) / replicas
    assert want * (1 - 2 * B / rows) <= shared / saved <= want


# ------------------------------------------------------------- the reader
def _run(phases, ratio=1):
    return types.SimpleNamespace(client={}, window={
        "seconds": 15.0, "launches": 12.0, "phase_ratio": ratio,
        "phases": dict(phases),
    })


def test_the_reader_is_one_counter_over_the_other():
    import json

    name = "storage.encode_shared_share"
    read = load_plugin("layer_metrics", name).read
    both = {"n.save_entries": 900.0, "n.save_entries_shared": 600.0,
            "n.save_wal_records": 120.0, "n.launches": 4.0}
    assert read(_run(both)) == pytest.approx(2 / 3)
    assert read(_run({**both, "n.save_entries_shared": 0.0})) == 0.0
    assert read(_run(both, ratio=32)) is None  # below full sampling
    # the parent: a program with the wave's parts and without the counts
    assert read(_run({"n.save_wal_records": 120.0, "n.launches": 4.0})) is None
    assert read(_run({})) is None
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert next(m for m in spec["per_layer"] if m["name"] == name) == {
        "name": name, "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "storage",
        "moves": "committed_ops_per_s",
    }


def test_the_counting_site_is_under_the_hot_path_lint():
    from dragonboat_tpu.analysis.targets import DEFAULT_TARGETS, LOGDB

    assert (LOGDB, "_Shard._save_entries") in \
        DEFAULT_TARGETS.hot_telemetry_functions
