"""What `save` is made of, and how long the loop thread stands in it
(ISSUE 37).

On a sampled iteration the loop's save wave records six sub-spans
(`save.gather`, `.encode`, `.append`, `.table`, `.sync`, `.mirror`), the
thread's CPU over the write and the barrier, and what reached the WAL files.
An unsampled wave records none of it and reads no clock in `storage/`
that it did not read before. The benchmark's readers divide by the
program's own `n.launches`.
"""
from __future__ import annotations

import contextlib
import json
import os
import struct
import sys
import threading
import time
import types

import pytest

from dragonboat_tpu.engine import vector as vecmod
from dragonboat_tpu.storage import kv as kvmod
from dragonboat_tpu.storage import logdb as logdbmod
from dragonboat_tpu.storage.kv import (
    _REC, MemKV, WalKV, close_wave, open_wave, sync_all,
)
from dragonboat_tpu.storage.logdb import ShardedLogDB
from dragonboat_tpu.trace import Profiler, flight_recorder
from dragonboat_tpu.types import Entry, State, Update

from benchmark.lib import spans
from benchmark.run import load_cell, load_plugin
from tests.test_auto_steps import (
    CLUSTER, LogSM, _bring_up, _host, _raft, _stop, _wait_leader,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("save.gather", "save.encode", "save.append", "save.table",
         "save.sync", "save.mirror")
# the stretches that are one piece of time each and leave an event; the
# write between the first two has its CPU seconds alone. The thread's CPU
# seconds are kept where a metric reads them: the barrier's and the write's
STRETCHES = ("save.gather", "save.sync", "save.mirror")
CPU = ("save.sync.cpu", "save.write.cpu")
COUNTERS = ("n.save_wal_bytes", "n.save_wal_records")
NEVER = 10 ** 9  # a sampling ratio no run reaches
EMPTY = {
    "encode": 0.0, "commit": 0.0, "table": 0.0, "sync": 0.0,
    "sync_cpu": 0.0, "wal_bytes": 0, "wal_records": 0,
    "entries": 0, "entries_shared": 0,
}


@contextlib.contextmanager
def timed_wave():
    """The calling thread's save wave, timed, as the engine's loop opens
    and closes it around a sampled wave."""
    try:
        yield open_wave()
    finally:
        close_wave()


def total(samples, name):
    s = samples[name]
    return s.mean() * len(s)


def new_names(samples):
    return sorted(
        n for n in samples
        if n.startswith("save.") and n != "save.cpu" or n in COUNTERS
    )


# ---------------------------------------------------------------- clusters
def _one_host(tmp_path, **engine):
    """One NodeHost, one replica: the engine's undeferred save door."""
    from dragonboat_tpu.transport.loopback import _Registry

    nh = _host(tmp_path, _Registry(), 1, "sp-one", "sp", **engine)
    nh.start_cluster({1: "sp1:1"}, False, LogSM, _raft(1))
    hosts = {1: nh}
    return hosts, _wait_leader(hosts)


PATHS = {
    # three co-hosted NodeHosts: one deferred write a logdb, one barrier
    "k1-deferred": lambda tmp, **kw: _bring_up(
        tmp, "sp-k1", "spa", steps_per_sync=1, overlap_decode=True, **kw),
    # the same, three steps a launch: _decode_super's merged wave
    "k3-merged": lambda tmp, **kw: _bring_up(tmp, "sp-k3", "spb", **kw),
    "one-host-undeferred": _one_host,
}


def _propose(hosts, lid, n=12):
    nh = hosts[lid]
    session = nh.get_noop_session(CLUSTER)
    for i in range(n):
        nh.sync_propose(session, b"k%07d" % i + b"v" * 8, 10.0)
    return nh.engine.core


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_sampled_wave_records_its_six_parts(tmp_path, path):
    hosts, lid = PATHS[path](tmp_path, profile_sample_ratio=1)
    try:
        core = _propose(hosts, lid)
        want = {"k1-deferred": 1, "k3-merged": 3, "one-host-undeferred": 1}
        assert core.step_stats()["steps_per_launch"] == want[path]
        assert (core._next_host <= 1) == (path == "one-host-undeferred")
    finally:
        _stop(hosts)
    s = core.profiler.samples
    waves = len(s["save"])
    assert waves >= 12
    for name in PARTS + CPU + COUNTERS:
        assert len(s[name]) == waves, name
    for name in PARTS:  # no CPU clock read that no metric reads
        assert (name + ".cpu" in s) == (name == "save.sync")
    assert "save.write" not in s  # its CPU seconds alone
    whole = total(s, "save")
    covered = sum(total(s, name) for name in PARTS)
    assert covered <= whole
    assert whole - covered <= max(0.05 * whole, 0.002 * waves)
    for name in PARTS:
        assert total(s, name) > 0, name
    assert 0 <= total(s, "save.write.cpu") <= whole
    # the barrier sleeps; nothing else in the wave is meant to
    assert total(s, "save.sync.cpu") < total(s, "save.sync")
    assert total(s, "n.save_wal_bytes") > total(s, "n.save_bytes") > 0
    assert total(s, "n.save_wal_records") >= 2  # a record and its seal


def test_the_stretches_leave_events_and_the_loops_kind_stays_twelve(
    tmp_path,
):
    hosts, lid = PATHS["k3-merged"](tmp_path, profile_sample_ratio=1)
    try:
        _propose(hosts, lid, 4)
        events = flight_recorder().dump(event="phase_span")
    finally:
        _stop(hosts)
    top = {e["phase"] for e in events if e["engine"] == "vector"}
    assert top <= set(spans.TOP_LEVEL) and "save" in top
    sub = [e for e in events if e["engine"] == "vector.sub"]
    assert {e["phase"] for e in sub} == set(STRETCHES)
    # each lies inside a `save` span of the loop's own kind
    saves = [(e["t0"], e["t"]) for e in events
             if e["engine"] == "vector" and e["phase"] == "save"]
    inside = [
        e for e in sub
        if any(a - 1e-6 <= e["t0"] and e["t"] <= b + 1e-6 for a, b in saves)
    ]
    assert len(inside) >= len(sub) - len(STRETCHES)  # but a running wave's


# ---------------------------------------------------- the unsampled wave
class _CountingTime:
    """`time` for a storage module, counting the clock reads that the
    wave's timing adds: `_barrier`'s and `sync_all`'s own pairs of
    time.monotonic(), there before ISSUE 37, are let through."""

    OWN = {"_barrier": "monotonic", "sync_all": "monotonic"}

    def __init__(self):
        self.reads = []

    def _read(self, clock):
        caller = sys._getframe(2).f_code.co_name
        if self.OWN.get(caller) != clock or kvmod.wave_parts() is not None:
            self.reads.append((caller, clock, threading.get_ident()))
        return getattr(time, clock)()

    def by(self, thread):
        return {caller for caller, _clock, ident in self.reads
                if ident == thread.ident}

    def monotonic(self):
        return self._read("monotonic")

    def thread_time(self):
        return self._read("thread_time")


@pytest.mark.parametrize("path", ["k3-merged", "one-host-undeferred"])
@pytest.mark.parametrize("ratio", [1, NEVER], ids=["sampled", "unsampled"])
def test_only_a_sampled_wave_reads_a_clock_in_storage(
    tmp_path, monkeypatch, path, ratio,
):
    clock = _CountingTime()
    monkeypatch.setattr(kvmod, "time", clock)
    monkeypatch.setattr(logdbmod, "time", clock)
    began = []
    monkeypatch.setattr(
        vecmod, "_kv_open_wave",
        lambda: (began.append(1), open_wave())[1],
    )
    hosts, lid = PATHS[path](tmp_path, profile_sample_ratio=ratio)
    try:
        core = _propose(hosts, lid)
    finally:
        _stop(hosts)
    s = core.profiler.samples
    # what this engine's loop thread read (another test's engine, were
    # one still running in this process, is not this test's subject)
    read_in = clock.by(core._threads[0])
    if ratio == NEVER:
        assert not core.profiler.sampling
        assert new_names(s) == [] and "save" not in s
        assert read_in == set() and began == []  # nothing allocated
    else:
        assert set(PARTS + COUNTERS) <= set(new_names(s))
        assert {"save_raft_state_deferred", "commit_write_batch_deferred",
                "sync_all"} <= read_in
        assert len(began) == len(s["save"])
        assert kvmod.wave_parts() is None


# ------------------------------------------------- what reached the files
def _ents(lo, hi, term=1, size=16):
    return [Entry(index=i, term=term, cmd=b"c" * size) for i in range(lo, hi)]


def _update(cid, ents, commit, term=1):
    return Update(
        cluster_id=cid, node_id=1, entries_to_save=ents,
        state=State(term=term, vote=1, commit=commit),
    )


def _wal_sizes(dirname):
    return {
        os.path.join(d, f): os.path.getsize(os.path.join(d, f))
        for d, _sub, files in os.walk(dirname) for f in files
        if f == "wal.log"
    }


def _records(data: bytes) -> int:
    """Records a replay scans in `data`, commit seals included."""
    off = n = 0
    while off < len(data):
        total_len, _op, _klen, _vlen = _REC.unpack_from(data, off)
        (crc,) = struct.unpack_from("<I", data, off + total_len - 4)
        assert crc == kvmod.zlib.crc32(data[off:off + total_len - 4])
        off += total_len
        n += 1
    assert off == len(data)
    return n


def test_the_wal_counters_are_the_files_growth_and_the_records_replayed(
    tmp_path,
):
    db = ShardedLogDB(str(tmp_path / "db"), num_shards=4)
    try:
        db.save_raft_state([_update(c, _ents(1, 6), 0) for c in range(1, 9)])
        before = _wal_sizes(str(tmp_path))
        assert len(before) == 4
        with timed_wave() as parts:
            # appends into a batch record's retained prefix, a rewrite
            # from mid-batch at a new term, a hard state alone, and an
            # update that changes nothing and writes nothing
            db.save_raft_state(
                [_update(c, _ents(6, 40, size=16 + c), 5) for c in range(1, 9)]
            )
            db.save_raft_state([_update(3, _ents(20, 30, term=2), 19, term=2)])
            db.save_raft_state([_update(5, [], 39)])
            db.save_raft_state([_update(5, [], 39)])
        after = _wal_sizes(str(tmp_path))
        grown = 0
        replayed = 0
        for path, size in after.items():
            grown += size - before[path]
            with open(path, "rb") as f:
                f.seek(before[path])
                replayed += _records(f.read())
        assert parts["wal_bytes"] == grown > 0
        assert parts["wal_records"] == replayed > 0
        for name in ("encode", "commit", "table", "sync"):
            assert parts[name] > 0, name
        assert parts["table"] < parts["commit"]  # the append is the rest
        assert kvmod.wave_parts() is None
    finally:
        db.close()


def test_a_store_that_knows_nothing_has_its_commit_booked_as_append(tmp_path):
    """An IKVStore with the interface's own deferred commit (MemKV) and
    a wrapper around one that does tell (faults.FaultyKV): neither has a
    method or an argument for the wave."""
    from dragonboat_tpu.faults import FaultPlane, FaultyKV

    plain = ShardedLogDB(
        str(tmp_path / "plain"), num_shards=2, kv_factory=lambda d: MemKV(),
    )
    try:
        with timed_wave() as parts:
            plain.save_raft_state([_update(c, _ents(1, 9), 0) for c in (1, 2)])
        assert parts["encode"] > 0 and parts["commit"] > 0
        # all of its commit is the append: no table, no file, no barrier
        assert {k: parts[k] for k in ("table", "sync", "wal_bytes")} == {
            "table": 0.0, "sync": 0.0, "wal_bytes": 0}
        assert plain.read_raft_state(1, 1, 0).entry_count == 8
    finally:
        plain.close()
    plane = FaultPlane(seed=1)
    wrapped = ShardedLogDB(
        str(tmp_path / "wrapped"), num_shards=2,
        kv_factory=lambda d: FaultyKV(WalKV(d), plane, "fsync:t"),
    )
    try:
        with timed_wave() as parts:
            wrapped.save_raft_state([_update(c, _ents(1, 9), 0) for c in (1, 2)])
        assert 0 < parts["table"] < parts["commit"] and parts["sync"] > 0
        assert parts["wal_bytes"] > 0 and parts["wal_records"] > 0
    finally:
        wrapped.close()


def test_a_wave_whose_write_fails_leaves_no_wave_open():
    from dragonboat_tpu.engine.vector import VectorEngine

    def fail(updates, lane_saves):
        assert kvmod.wave_parts() == EMPTY
        raise OSError("disk")

    booked = []
    engine = types.SimpleNamespace(
        _save_updates=fail, _book_wave=lambda *a: booked.append(a))
    mark = time.monotonic()
    with pytest.raises(OSError):
        VectorEngine._commit_saves(engine, [object()], [], mark)
    assert kvmod.wave_parts() is None and booked == []
    # and an untimed wave opens none
    engine._save_updates = lambda u, ls: booked.append(kvmod.wave_parts())
    VectorEngine._commit_saves(engine, [object()], [])
    assert booked == [None]


@pytest.mark.parametrize("writer", ["snapshot-record", "compaction", "save"])
def test_another_threads_write_is_not_booked_into_the_wave(tmp_path, writer):
    """A snapshot worker's or a compaction's write to the same logdb
    while the loop's sampled wave is open adds nothing to its parts."""
    from dragonboat_tpu.types import Snapshot

    db = ShardedLogDB(str(tmp_path / "db"), num_shards=1)
    try:
        db.save_raft_state([_update(1, _ents(1, 200), 150)])
        writes = {
            "snapshot-record": lambda: db.save_snapshots([Update(
                cluster_id=1, node_id=1,
                snapshot=Snapshot(index=100, term=1, cluster_id=1),
            )]),
            "compaction": lambda: db.remove_entries_to(1, 1, 100),
            "save": lambda: db.save_raft_state([_update(1, _ents(200, 260), 150)]),
        }
        seen = []

        def other():
            seen.append(kvmod.wave_parts())
            writes[writer]()

        with timed_wave() as parts:
            t = threading.Thread(target=other)
            t.start()
            t.join(30)
            assert seen == [None] and not t.is_alive()
            assert parts == EMPTY
            db.save_raft_state([_update(1, _ents(260, 270), 150)])
            assert parts["wal_records"] > 0 and parts["encode"] > 0
    finally:
        db.close()


def test_sync_all_books_only_its_callers_wave(tmp_path):
    kv = WalKV(str(tmp_path / "kv"))
    try:
        sync_all([kv])  # no wave open: nothing to book, nothing raised
        with timed_wave() as parts:
            sync_all([])
            assert parts["sync"] == 0.0
            sync_all([kv])
        assert kvmod.wave_parts() is None
        assert parts["sync"] > 0 and parts["sync_cpu"] >= 0
        assert parts["sync_cpu"] <= parts["sync"] + 0.02
    finally:
        kv.close()


# ------------------------------------------------- a sub-span with an end
def test_add_with_an_end_leaves_a_sub_event_at_full_sampling_only():
    from dragonboat_tpu.profile import phase_plane

    for ratio, want in ((1, 1), (2, 0)):
        flight_recorder().reset()
        prof = Profiler(sample_ratio=ratio)
        prof.attach_phase_plane(phase_plane(), "probe")
        prof.sampling = True
        prof.add("piece", 0.5)  # a sum of pieces: no event
        prof.add("stretch", 0.25, 0.125, end=10.0)
        events = [
            e for e in flight_recorder().dump(event="phase_span")
            if e["engine"] == "probe.sub"
        ]
        assert len(events) == want
        if want:
            e = events[0]
            assert (e["phase"], e["t0"], e["t"]) == ("stretch", 9.75, 10.0)
        assert total(prof.samples, "stretch.cpu") == 0.125
        assert "piece.cpu" not in prof.samples


# ------------------------------------------------------------ the readers
LAUNCHES = 4.0
PHASES = {"n.launches": LAUNCHES, "n.launch_steps": 12.0}
for _i, _stage in enumerate(spans.TOP_LEVEL):
    PHASES[_stage] = 0.1 * (_i + 1)       # wait 0.1 ... maintain 1.2
    PHASES[_stage + ".cpu"] = 0.05 * (_i + 1)
PHASES.update({
    "save": 0.8, "save.cpu": 0.5,
    "save.gather": 0.06, "save.encode": 0.36, "save.append": 0.12,
    "save.table": 0.04, "save.sync": 0.14, "save.mirror": 0.05,
    "save.write.cpu": 0.4, "save.sync.cpu": 0.02,
    "n.save_wal_bytes": 8_000_000.0, "n.save_wal_records": 2_400.0,
    "device_wait": 0.2,
})
# Σ over the eleven spans but `wait`: wall 7.3 (save as 0.8, not 0.8's
# slot value), cpu 3.7
_BUSY_WALL = sum(PHASES[s] for s in spans.TOP_LEVEL if s != "wait")
_BUSY_CPU = sum(PHASES[s + ".cpu"] for s in spans.TOP_LEVEL if s != "wait")
WANT = {
    "storage.save_gather_ms_per_launch": 15.0,
    "storage.save_encode_ms_per_launch": 90.0,
    "storage.save_append_ms_per_launch": 30.0,
    "storage.save_table_ms_per_launch": 10.0,
    "storage.save_sync_ms_per_launch": 35.0,
    "storage.save_mirror_ms_per_launch": 12.5,
    "storage.save_write_cpu_ms_per_launch": 100.0,
    "storage.save_parts_uncovered_ms_per_launch": 7.5,
    "storage.save_stall_ms_per_launch": (0.3 - 0.12) / LAUNCHES * 1e3,
    "storage.wal_bytes_per_launch": 2_000_000.0,
    "storage.wal_records_per_launch": 600.0,
    "engine.stall_ms_per_launch":
        (_BUSY_WALL - _BUSY_CPU - 0.2 - 0.12) / LAUNCHES * 1e3,
}


def run_of(phases, ratio=1):
    # window["launches"] is protocol steps over the file's steps_per_sync:
    # three times the launches where the engine chose three steps
    return types.SimpleNamespace(client={}, window={
        "seconds": 15.0, "launches": 3 * LAUNCHES, "phase_ratio": ratio,
        "phases": dict(phases),
    })


def test_there_are_twelve_and_each_is_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert len(WANT) == 12
    got = {m["name"]: m for m in spec["per_layer"] if m["name"] in WANT}
    assert set(got) == set(WANT)
    assert [m["name"] for m in spec["per_layer"][77:89]] == list(WANT)
    for name, m in got.items():
        assert "workloads" not in m, name
        assert m["moves"] == "committed_ops_per_s"
        assert m["layer"] == name.split(".")[0]
        assert m["source"] in ("program_span", "program_counter")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_divides_by_the_programs_launches(name):
    read = load_plugin("layer_metrics", name).read
    assert read(run_of(PHASES)) == pytest.approx(WANT[name], rel=1e-9)
    assert read(run_of(PHASES, ratio=32)) is None  # below full sampling
    assert read(run_of({})) is None
    # the parent: every span and counter it has, none of this PR's
    parent = {
        k: v for k, v in PHASES.items()
        if not k.startswith("save.") or k == "save.cpu"
    }
    for k in COUNTERS:
        del parent[k]
    assert read(run_of(parent)) is None
    no_launch = dict(PHASES)
    del no_launch["n.launches"]
    assert read(run_of(no_launch)) is None


def test_the_new_sites_are_under_the_hot_path_lint():
    from dragonboat_tpu.analysis.targets import DEFAULT_TARGETS, KV, LOGDB, \
        VECTOR

    for key in (
        (VECTOR, "VectorEngine._commit_saves"),
        (VECTOR, "VectorEngine._book_wave"),
        (LOGDB, "_Shard.save_raft_state_deferred"),
        (KV, "WalKV.commit_write_batch_deferred"),
        (KV, "sync_all"),
    ):
        assert key in DEFAULT_TARGETS.hot_telemetry_functions, key
