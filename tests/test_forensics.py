"""Forensic observability tests (ISSUE 4): causal trace propagation, the
crash-persistent mmap flight ring, server-side recorder filtering, and the
post-crash recovery path.

The heavyweight acceptance scenarios live here too:

  * a 3-node shared-core vector cluster under a seeded FaultPlane
    partition schedule, whose merged per-node dumps reconstruct one
    sampled proposal's causal chain (propose -> replicate -> quorum ->
    apply) across >= 2 nodes keyed by a single trace id;
  * a subprocess NodeHost SIGKILL'd mid-chaos whose recovered mmap ring
    still holds the last leader-change and fault-injection events in
    order.
"""
import json
import os
import signal
import struct
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from dragonboat_tpu.tools import timeline
from dragonboat_tpu.trace import (
    FlightRecorder,
    MmapRing,
    flight_recorder,
    mint_trace_id,
    read_mmap_ring,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# trace ids
# ---------------------------------------------------------------------------


def test_mint_trace_id_unique_and_compact():
    ids = {mint_trace_id() for _ in range(1000)}
    assert len(ids) == 1000
    assert all(0 < i < 2**64 for i in ids)
    # one process's ids share the salt (merging keys on the full u64)
    assert len({i >> 32 for i in ids}) == 1


def test_entry_and_message_carry_trace_id_on_the_wire():
    from dragonboat_tpu.codec import (
        decode_entry,
        decode_message,
        encode_entry,
        encode_message,
    )
    from dragonboat_tpu.types import Entry, Message, MessageType

    tid = mint_trace_id()
    e = Entry(term=3, index=9, cmd=b"k=v", trace_id=tid)
    got, _ = decode_entry(encode_entry(e))
    assert got.trace_id == tid
    m = Message(
        type=MessageType.REPLICATE, cluster_id=2, to=2, from_=1,
        term=3, trace_id=tid, entries=[e],
    )
    gm, _ = decode_message(encode_message(m))
    assert gm.trace_id == tid
    assert gm.entries[0].trace_id == tid
    # unsampled default stays zero
    assert decode_entry(encode_entry(Entry(cmd=b"x")))[0].trace_id == 0


# ---------------------------------------------------------------------------
# recorder filtering + mandatory cluster field (server-side dump filters)
# ---------------------------------------------------------------------------


def test_recorder_events_carry_mandatory_cluster_field():
    rec = FlightRecorder(capacity=16)
    rec.record("host_level_thing", addr="a:1")
    rec.record("group_thing", cluster=7, node=1)
    d = rec.dump()
    assert all("cluster" in e for e in d)
    assert d[0]["cluster"] == 0  # host-level default
    assert d[1]["cluster"] == 7


def test_recorder_dump_filters():
    rec = FlightRecorder(capacity=64)
    t1, t2 = mint_trace_id(), mint_trace_id()
    rec.record("propose_enqueue", cluster=1, node=1, trace=t1)
    rec.record("propose_enqueue", cluster=2, node=1, trace=t2)
    rec.record("quorum_commit", cluster=2, node=1, trace=t2)
    rec.record("breaker_open", addr="x:1")
    assert len(rec.dump()) == 4
    assert [e["cluster"] for e in rec.dump(cluster_id=2)] == [2, 2]
    assert [e["event"] for e in rec.dump(trace_id=t2)] == [
        "propose_enqueue", "quorum_commit",
    ]
    assert len(rec.dump(event="breaker_open")) == 1
    assert rec.dump(cluster_id=2, event="quorum_commit")[0]["trace"] == t2


def test_dump_atomic_vs_concurrent_record():
    """Satellite: list(deque) during concurrent mutation can raise
    RuntimeError under free-threaded runs — dump() must snapshot
    atomically (retry loop). Two-thread hammer: one floods record(),
    the other dumps continuously; no exception may escape."""
    rec = FlightRecorder(capacity=128)
    stop = threading.Event()
    errs = []

    def writer():
        i = 0
        while not stop.is_set():
            rec.record("hammer", i=i)
            i += 1

    def reader():
        try:
            for _ in range(500):
                for e in rec.dump():
                    assert e["event"] == "hammer"
                rec.to_jsonl(meta={"source": "hammer"})
        except Exception as exc:  # pragma: no cover - the regression
            errs.append(exc)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        reader()
    finally:
        stop.set()
        t.join(timeout=5)
    assert not errs, errs


# ---------------------------------------------------------------------------
# mmap ring
# ---------------------------------------------------------------------------


def test_mmap_ring_roundtrip_and_wraparound(tmp_path):
    path = str(tmp_path / "r.ring")
    ring = MmapRing(path, capacity=8, slot_size=256)
    for i in range(11):  # wraps: only the last 8 survive
        ring.write(json.dumps({"t": i / 10, "event": "e", "i": i}).encode())
    ring.close()
    meta, events = read_mmap_ring(path)
    assert [e["i"] for e in events] == list(range(3, 11))
    assert "mono_offset" in meta


def test_mmap_ring_survives_torn_and_unsealed_slots(tmp_path):
    path = str(tmp_path / "torn.ring")
    ring = MmapRing(path, capacity=8, slot_size=128)
    for i in range(5):
        ring.write(json.dumps({"event": "e", "i": i}).encode())
    ring.close()
    hdr = 64
    with open(path, "r+b") as f:
        # slot 2: seal present but payload garbage (torn mid-write)
        f.seek(hdr + 2 * 128 + 12)
        f.write(b"\xff\xfegarbage")
        # slot 3: unsealed (the write a SIGKILL interrupted)
        f.seek(hdr + 3 * 128)
        f.write(struct.pack("<Q", 0))
    _meta, events = read_mmap_ring(path)
    assert [e["i"] for e in events] == [0, 1, 4]  # the rest stays valid


def test_recorder_tees_into_attached_ring(tmp_path):
    path = str(tmp_path / "tee.ring")
    rec = FlightRecorder(capacity=32)
    rec.attach_mmap(path, capacity=16, slot_size=256)
    try:
        rec.record("leader_changed", cluster=3, node=1, leader=2, term=5)
        rec.record("fault_injected", site="wire:x", kind="drop")
        # attach is idempotent for the same path (NodeHost + harness)
        r1 = rec.attach_mmap(path)
        assert r1 is rec._ring
    finally:
        rec.detach_mmap()
    _meta, events = read_mmap_ring(path)
    assert [e["event"] for e in events] == ["leader_changed", "fault_injected"]
    assert events[0]["cluster"] == 3 and events[1]["cluster"] == 0


def test_mmap_ring_oversized_event_degrades_to_marker(tmp_path):
    """An event bigger than a slot must survive recovery as a JSON-safe
    `_truncated` marker (when/what/which group), never as a dropped
    torn slot."""
    path = str(tmp_path / "big.ring")
    ring = MmapRing(path, capacity=8, slot_size=256)
    big = {"t": 1.5, "event": "_test_start", "cluster": 0,
           "nodeid": "x" * 50, "noise": "y" * 500}
    ring.write(json.dumps(big).encode())
    ring.write(json.dumps({"t": 2.0, "event": "small", "cluster": 0}).encode())
    ring.close()
    _meta, events = read_mmap_ring(path)
    assert [e["event"] for e in events] == ["_test_start", "small"]
    assert events[0]["_truncated"] is True
    assert events[0]["t"] == 1.5 and events[0]["nodeid"] == "x" * 50
    assert "noise" not in events[0]
    # a tiny slot sheds progressively but still keeps when/what
    tiny = str(tmp_path / "tiny.ring")
    ring = MmapRing(tiny, capacity=4, slot_size=80)
    ring.write(json.dumps(big).encode())
    ring.close()
    _meta, events = read_mmap_ring(tiny)
    assert len(events) == 1
    assert events[0]["event"] == "_test_start"
    assert events[0]["_truncated"] is True


def test_attach_rotates_previous_crash_ring(tmp_path):
    """Satellite/review fix: a restart's auto-attach (env var, session
    ring) must NOT truncate the previous — possibly SIGKILL'd — process's
    timeline; the old ring rotates to <path>.prev and stays readable."""
    path = str(tmp_path / "r.ring")
    crashed = FlightRecorder(capacity=8)
    crashed.attach_mmap(path, capacity=8, slot_size=256)
    crashed.record("leader_changed", cluster=1, node=1, leader=1, term=2)
    crashed.detach_mmap()  # stand-in for the process dying
    restarted = FlightRecorder(capacity=8)
    restarted.attach_mmap(path, capacity=8, slot_size=256)
    try:
        restarted.record("fresh_event")
    finally:
        restarted.detach_mmap()
    _m, prev = read_mmap_ring(path + ".prev")
    assert [e["event"] for e in prev] == ["leader_changed"]
    _m, cur = read_mmap_ring(path)
    assert [e["event"] for e in cur] == ["fresh_event"]


def test_session_ring_covers_timeout_kills():
    """Satellite: the conftest-attached session ring must already hold this
    test's `_test_start` marker — the mechanism that leaves a readable
    artifact when pytest-timeout / `timeout -k` SIGKILLs the run before
    any JSONL failure dump can be written."""
    rec = flight_recorder()
    ring = rec._ring
    if ring is None:
        pytest.skip("session ring not attached (FLIGHT_RING_PATH unset?)")
    rec.flush()
    _meta, events = read_mmap_ring(ring.path)
    markers = [
        e for e in events
        if e.get("event") == "_test_start"
        and "test_session_ring_covers_timeout_kills" in str(e.get("nodeid"))
    ]
    assert markers, "session ring is missing this test's _test_start marker"


# ---------------------------------------------------------------------------
# post-crash recovery: SIGKILL a NodeHost mid-chaos, recover the ring
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import IStateMachine, Result
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

    class SM(IStateMachine):
        def __init__(self):
            self.v = 0
        def update(self, data):
            self.v += 1
            return Result(value=self.v)
        def lookup(self, q):
            return self.v
        def save_snapshot(self, w, files, done):
            w.write(b"0")
        def recover_from_snapshot(self, r, files, done):
            pass

    reg = _Registry()
    nh = NodeHost(NodeHostConfig(
        deployment_id=1, rtt_millisecond=5, raft_address="kill1:1",
        raft_rpc_factory=lambda l: loopback_factory(l, reg),
        engine=EngineConfig(kind="scalar", max_groups=4, max_peers=4),
    ))
    nh.start_cluster(
        {{1: "kill1:1"}}, False, lambda c, n: SM(),
        Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=2),
    )
    deadline = time.time() + 60
    while time.time() < deadline:
        lid, ok = nh.get_leader_id(1)
        if ok:
            break
        time.sleep(0.02)
    else:
        print("NOLEADER", flush=True)
        sys.exit(2)
    # mid-chaos: a fired fault lands in the ring after the leader change
    from dragonboat_tpu.faults import FaultPlane, FaultSpec
    fp = FaultPlane(99, FaultSpec(drop=1.0))
    assert fp.decide("kill:wire", "drop", 1.0)
    print("READY", flush=True)
    time.sleep(120)  # parent SIGKILLs us here
    """
)


def test_sigkilled_nodehost_leaves_recoverable_ring(tmp_path):
    ring_path = str(tmp_path / "crash.ring")
    script = tmp_path / "child.py"
    script.write_text(_CHILD.format(repo=REPO))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["DRAGONBOAT_FLIGHT_RING"] = ring_path
    p = subprocess.Popen(
        [sys.executable, str(script)],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        env=env,
        text=True,
    )
    try:
        line = ""
        deadline = time.time() + 90
        while time.time() < deadline:
            line = p.stdout.readline()
            if "READY" in line or "NOLEADER" in line or not line:
                break
        assert "READY" in line, f"child never came up: {line!r}"
        os.kill(p.pid, signal.SIGKILL)
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait(timeout=10)
    # recover the dead process's timeline through the NodeHost path
    from dragonboat_tpu.nodehost import NodeHost

    events = NodeHost.recover_flight_ring(ring_path)
    kinds = [e["event"] for e in events]
    assert "leader_changed" in kinds, kinds
    assert "fault_injected" in kinds, kinds
    # the LAST leader change (node 1 won its own election) precedes the
    # fault injection in the recovered order
    last_lead = max(i for i, k in enumerate(kinds) if k == "leader_changed")
    first_fault = kinds.index("fault_injected")
    assert last_lead < first_fault
    lead = events[last_lead]
    assert lead["cluster"] == 1 and lead["leader"] == 1
    # and the timeline CLI renders the recovered ring as an ordered view
    merged = timeline.merge_dumps([ring_path])
    assert [e["event"] for e in merged] == kinds
    ts = [e["_tw"] for e in merged]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# end-to-end causal chain: 3 nodes, partition seed, merged dumps
# ---------------------------------------------------------------------------

CLUSTER = 2
HOSTS = (1, 2, 3)


def _mk_host(nid, reg, tmp, scope):
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import loopback_factory

    nh = NodeHost(
        NodeHostConfig(
            deployment_id=31,
            rtt_millisecond=5,
            nodehost_dir=f"{tmp}/h{nid}",
            raft_address=f"ca{nid}:1",
            raft_rpc_factory=lambda l, reg=reg: loopback_factory(l, reg),
            engine=EngineConfig(
                kind="vector",
                max_groups=16,
                max_peers=4,
                log_window=64,
                share_scope=scope,
                # the chain under test is the host path's: a Replicate
                # routed on the device leaves no replicate_send event
                steps_per_sync=1,
                profile_sample_ratio=1,  # sample EVERY step
            ),
        )
    )
    # and trace EVERY request: the engine's own request sampler stops at
    # 1 in vector.REQUEST_SAMPLE_FLOOR
    nh.engine.request_sampler.ratio = 1
    nh.start_cluster(
        {h: f"ca{h}:1" for h in HOSTS},
        False,
        lambda c, n: _kvsm(),
        Config(
            cluster_id=CLUSTER,
            node_id=nid,
            election_rtt=20,
            heartbeat_rtt=4,
            snapshot_entries=0,
        ),
    )
    return nh


def _kvsm():
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class KV(IStateMachine):
        def __init__(self):
            self.d = {}

        def update(self, data):
            k, v = data.decode().split("=", 1)
            self.d[k] = v
            return Result(value=1)

        def lookup(self, q):
            return self.d.get(q)

        def save_snapshot(self, w, files, done):
            w.write(json.dumps(self.d).encode())

        def recover_from_snapshot(self, r, files, done):
            self.d = json.loads(r.read().decode())

    return KV()


def _wait_leader(hosts, deadline_s=60.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        for nid, nh in hosts.items():
            lid, ok = nh.get_leader_id(CLUSTER)
            if ok and lid == nid:
                return nid
        time.sleep(0.02)
    return None


def test_e2e_causal_chain_across_nodes_under_partition(tmp_path):
    from dragonboat_tpu.faults import FaultPlane, FaultSpec
    from dragonboat_tpu.transport.loopback import _Registry

    seed = int(os.environ.get("CHAOS_SEED", "1789"), 0)
    print(f"CHAOS SEED={seed} (replay: CHAOS_SEED={seed})")
    fp = FaultPlane(seed, FaultSpec())
    reg = _Registry()
    rec = flight_recorder()
    hosts = {
        nid: _mk_host(nid, reg, str(tmp_path), f"causal-{seed}")
        for nid in HOSTS
    }
    try:
        assert _wait_leader(hosts) is not None, "no leader elected"
        # seeded partition windows (the chaos context the timeline must
        # survive), then heal and wait for a stable leader again
        for victim, window, idle in fp.partition_schedule(
            "causal", HOSTS, total_s=1.2, min_window_s=0.1, max_window_s=0.3
        ):
            hosts[victim].set_partitioned(True)
            time.sleep(window)
            hosts[victim].set_partitioned(False)
            time.sleep(idle)
        for nh in hosts.values():
            nh.set_partitioned(False)
        deadline = time.monotonic() + 45
        committed = False
        while not committed and time.monotonic() < deadline:
            leader = _wait_leader(hosts, 30.0)
            if leader is None:
                continue
            nh = hosts[leader]
            try:
                nh.sync_propose(
                    nh.get_noop_session(CLUSTER), b"causal=1", timeout_s=5.0
                )
                committed = True
            except Exception:
                time.sleep(0.1)
        assert committed, "no proposal committed after heal"
        time.sleep(0.3)  # let trailing ack/apply events land

        # per-node dumps, exactly as N separate hosts would produce them
        events = rec.dump(cluster_id=CLUSTER)
        paths = []
        for nid in HOSTS:
            p = str(tmp_path / f"node{nid}.jsonl")
            with open(p, "w") as f:
                f.write(
                    json.dumps(
                        {
                            "event": "_meta",
                            "mono_offset": rec.mono_offset,
                            "source": f"n{nid}",
                        }
                    )
                    + "\n"
                )
                for e in events:
                    if e.get("node") == nid:
                        f.write(json.dumps(e, sort_keys=True) + "\n")
            paths.append(p)

        merged = timeline.merge_dumps(paths)
        chains = timeline.causal_chains(merged)
        assert chains, "no trace-stamped events survived the run"
        need = (
            "propose_enqueue", "replicate_send", "quorum_commit",
            "proposal_applied",
        )
        good = None
        for tid, evs in chains.items():
            stages = [e["event"] for e in evs]
            nodes = {e.get("node") for e in evs}
            if not all(s in stages for s in need) or len(nodes) < 2:
                continue
            pos = [stages.index(s) for s in need]
            if pos == sorted(pos):
                good = tid
                break
        assert good is not None, (
            "no causal chain with >=4 ordered stages across >=2 nodes; "
            f"chains: { {hex(t): [e['event'] for e in c] for t, c in chains.items()} }"
        )
        # the CLI renders the chain
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = timeline.main(paths + ["--chains", "--trace", hex(good)])
        assert rc == 0
        out = buf.getvalue()
        assert f"trace {good:#x}" in out
        assert "propose_enqueue" in out and "quorum_commit" in out
    finally:
        for nh in hosts.values():
            nh.stop()
