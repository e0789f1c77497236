"""RSM layer tests: sessions, membership legality, managed SM apply path,
snapshot IO format (cf. internal/rsm/statemachine_test.go,
session_test.go, membership_test.go patterns)."""
import io
import struct
import threading
import time

import pytest

from dragonboat_tpu.client import Session as ClientSession
from dragonboat_tpu.config import Config
from dragonboat_tpu.core.peer import encode_config_change
from dragonboat_tpu.engine.node import Node
from dragonboat_tpu.requests import (
    BatchRequestState,
    LogicalClock,
    PendingProposal,
    PendingReadIndex,
    make_batch_key,
)
from dragonboat_tpu.rsm import (
    MembershipManager,
    SessionManager,
    SnapshotHeader,
    SnapshotReader,
    SnapshotWriter,
    StateMachineManager,
    StreamValidator,
    Task,
    wrap_state_machine,
)
from dragonboat_tpu.rsm.session import Session
from dragonboat_tpu.statemachine import (
    AbortSignal,
    IConcurrentStateMachine,
    IOnDiskStateMachine,
    IStateMachine,
    Result,
)
from dragonboat_tpu.trace import LatencyTrace, Profiler, flight_recorder
from dragonboat_tpu.types import (
    ConfigChange,
    ConfigChangeType,
    Entry,
    EntryType,
    Membership,
    NOOP_CLIENT_ID,
    SERIES_ID_FOR_REGISTER,
    SERIES_ID_FOR_UNREGISTER,
)


# ---------------------------------------------------------------- sessions
def test_session_response_cache():
    s = Session(100)
    s.add_response(1, Result(value=10))
    got, has = s.get_response(1)
    assert has and got.value == 10
    with pytest.raises(RuntimeError):
        s.add_response(1, Result(value=11))
    s.clear_to(1)
    assert s.has_responded(1)
    _, has = s.get_response(1)
    assert not has


def test_session_manager_lru_eviction():
    m = SessionManager(max_sessions=2)
    m.register_client_id(1)
    m.register_client_id(2)
    m.register_client_id(3)  # evicts 1
    assert m.get_registered_client(1) is None
    assert m.get_registered_client(2) is not None
    # 2 is now most recent; adding 4 evicts 3
    m.register_client_id(4)
    assert m.get_registered_client(3) is None
    assert m.get_registered_client(2) is not None


def test_session_manager_snapshot_roundtrip():
    m = SessionManager(max_sessions=8)
    for cid in (5, 6, 7):
        m.register_client_id(cid)
    s = m.get_registered_client(6)
    s.add_response(3, Result(value=33, data=b"abc"))
    s.responded_up_to = 2
    blob = m.save()
    m2 = SessionManager(max_sessions=8)
    m2.load(blob)
    s2 = m2.get_registered_client(6)
    got, has = s2.get_response(3)
    assert has and got.value == 33 and got.data == b"abc"
    assert m.hash() == m2.hash()


# -------------------------------------------------------------- membership
def mk_members():
    m = MembershipManager(1, 1, ordered=False)
    m.members.addresses = {1: "a:1", 2: "a:2", 3: "a:3"}
    return m


def test_membership_add_remove():
    m = mk_members()
    ok = m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=4, address="a:4"), 10
    )
    assert ok and m.members.addresses[4] == "a:4"
    assert m.members.config_change_id == 10
    ok = m.handle_config_change(
        ConfigChange(type=ConfigChangeType.REMOVE_NODE, node_id=4), 11
    )
    assert ok and 4 not in m.members.addresses and 4 in m.members.removed
    # re-adding a removed node is rejected
    ok = m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=4, address="a:9"), 12
    )
    assert not ok


def test_membership_rejects_dup_address():
    m = mk_members()
    ok = m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=9, address="a:2"), 10
    )
    assert not ok


def test_membership_observer_promotion():
    m = mk_members()
    assert m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_OBSERVER, node_id=5, address="a:5"), 10
    )
    # promote with same address ok
    assert m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=5, address="a:5"), 11
    )
    assert 5 in m.members.addresses and 5 not in m.members.observers


def test_membership_observer_promotion_wrong_address():
    m = mk_members()
    assert m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_OBSERVER, node_id=5, address="a:5"), 10
    )
    assert not m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=5, address="a:6"), 11
    )


def test_membership_cannot_delete_only_node():
    m = MembershipManager(1, 1)
    m.members.addresses = {1: "a:1"}
    assert not m.handle_config_change(
        ConfigChange(type=ConfigChangeType.REMOVE_NODE, node_id=1), 5
    )


def test_membership_ordered_ccid():
    m = MembershipManager(1, 1, ordered=True)
    m.members.addresses = {1: "a:1", 2: "a:2"}
    m.members.config_change_id = 7
    bad = ConfigChange(
        type=ConfigChangeType.ADD_NODE, node_id=3, address="a:3", config_change_id=6
    )
    assert not m.handle_config_change(bad, 10)
    good = ConfigChange(
        type=ConfigChangeType.ADD_NODE, node_id=3, address="a:3", config_change_id=7
    )
    assert m.handle_config_change(good, 10)


def test_membership_witness_rules():
    m = mk_members()
    assert m.handle_config_change(
        ConfigChange(type=ConfigChangeType.ADD_WITNESS, node_id=6, address="a:6"), 10
    )
    # adding an existing witness as full node must raise (illegal promotion)
    with pytest.raises(RuntimeError):
        m._apply(
            ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=6, address="a:6"), 11
        )


# ----------------------------------------------------------- snapshot io
def test_snapshot_io_roundtrip():
    buf = io.BytesIO()
    hdr = SnapshotHeader(
        index=100,
        term=7,
        smtype=1,
        membership=Membership(addresses={1: "a:1"}, config_change_id=3),
    )
    payload = bytes(range(256)) * 5000  # > 1MB, multiple blocks
    with SnapshotWriter(buf, hdr, session=b"sess-image") as w:
        w.write(payload)
    buf.seek(0)
    r = SnapshotReader(buf)
    assert r.header.index == 100 and r.header.term == 7
    assert r.header.membership.addresses == {1: "a:1"}
    assert r.session == b"sess-image"
    got = r.read()
    assert got == payload


def test_snapshot_io_detects_corruption():
    buf = io.BytesIO()
    hdr = SnapshotHeader(index=1, term=1)
    with SnapshotWriter(buf, hdr, session=b"") as w:
        w.write(b"x" * 100000)
    raw = bytearray(buf.getvalue())
    raw[len(raw) // 2] ^= 0xFF  # flip a payload bit
    v = StreamValidator()
    v.feed(bytes(raw))
    assert not v.valid()
    v2 = StreamValidator()
    v2.feed(buf.getvalue())
    assert v2.valid()


# ------------------------------------------------------- manager apply path
class KVSM(IStateMachine):
    def __init__(self):
        self.data = {}
        self.update_count = 0

    def update(self, cmd: bytes) -> Result:
        self.update_count += 1
        k, v = cmd.decode().split("=", 1)
        self.data[k] = v
        return Result(value=len(self.data))

    def lookup(self, q):
        return self.data.get(q)

    def save_snapshot(self, w, files, done):
        import json

        w.write(json.dumps(self.data, sort_keys=True).encode())

    def recover_from_snapshot(self, r, files, done):
        import json

        self.data = json.loads(r.read().decode())


class FakeNodeProxy:
    def __init__(self):
        self.updates = []
        self.ccs = []
        self.cc_results = []

    def node_ready(self):
        pass

    def apply_update(self, entry, result, rejected, ignored, notify_read):
        self.updates.append((entry.index, result, rejected, ignored))

    def apply_update_run(self, entries, results):
        # results is None where no entry of the run has a per-request key
        for e, r in zip(entries, results or [Result()] * len(entries)):
            self.apply_update(e, r, False, False, False)

    def apply_config_change(self, cc):
        self.ccs.append(cc)

    def config_change_processed(self, key, accepted):
        self.cc_results.append((key, accepted))

    def node_id(self):
        return 1

    def cluster_id(self):
        return 5

    def should_stop(self):
        return False


def mk_manager(sm=None):
    sm = sm or KVSM()
    managed = wrap_state_machine(sm, 5, 1)
    proxy = FakeNodeProxy()
    cfg = Config(node_id=1, cluster_id=5, election_rtt=10, heartbeat_rtt=2)
    mgr = StateMachineManager(None, managed, proxy, cfg)
    return mgr, sm, proxy


def entry(index, cmd=b"", client=NOOP_CLIENT_ID, series=0, responded=0, term=1):
    return Entry(
        index=index,
        term=term,
        cmd=cmd,
        client_id=client,
        series_id=series,
        responded_to=responded,
    )


def run_tasks(mgr, *tasks):
    for t in tasks:
        mgr.task_queue.add(t)
    batch, apply = [], []
    return mgr.handle(batch, apply)


def test_manager_applies_noop_session_entries():
    mgr, sm, proxy = mk_manager()
    run_tasks(mgr, Task(entries=[entry(1, b"a=1"), entry(2, b"b=2")]))
    assert sm.data == {"a": "1", "b": "2"}
    assert mgr.last_applied_index() == 2
    assert [u[0] for u in proxy.updates] == [1, 2]


def test_manager_session_dedup():
    mgr, sm, proxy = mk_manager()
    # register client 77
    reg = entry(1, client=77, series=SERIES_ID_FOR_REGISTER)
    run_tasks(mgr, Task(entries=[reg]))
    assert proxy.updates[-1][1].value == 77
    # first proposal
    e1 = entry(2, b"k=v", client=77, series=1)
    run_tasks(mgr, Task(entries=[e1]))
    assert sm.update_count == 1
    # duplicate of series 1 must NOT re-apply; cached result returned
    dup = entry(3, b"k=v2", client=77, series=1)
    run_tasks(mgr, Task(entries=[dup]))
    assert sm.update_count == 1
    assert sm.data == {"k": "v"}
    assert proxy.updates[-1][1] == proxy.updates[-2][1]
    # acknowledged responses are evicted; a replay below responded_to is
    # flagged ignored
    e2 = entry(4, b"k2=v", client=77, series=2, responded=1)
    run_tasks(mgr, Task(entries=[e2]))
    assert sm.update_count == 2
    old = entry(5, b"k=zzz", client=77, series=1, responded=1)
    run_tasks(mgr, Task(entries=[old]))
    assert sm.update_count == 2
    assert proxy.updates[-1][3]  # ignored
    # unregister
    unreg = entry(6, client=77, series=SERIES_ID_FOR_UNREGISTER)
    run_tasks(mgr, Task(entries=[unreg]))
    # proposals from unregistered client rejected
    e3 = entry(7, b"x=y", client=77, series=3)
    run_tasks(mgr, Task(entries=[e3]))
    assert proxy.updates[-1][2]  # rejected
    assert sm.update_count == 2


def test_rsm_retried_proposal_returns_cached_result_every_time():
    """ISSUE 14 satellite: a deadline-retried proposal (same client,
    same series) that already applied returns the CACHED result on
    EVERY retry until the client acknowledges — one apply, identical
    results, never the 'ignored' flag (the caller needs the payload)."""
    mgr, sm, proxy = mk_manager()
    run_tasks(
        mgr, Task(entries=[entry(1, client=77, series=SERIES_ID_FOR_REGISTER)])
    )
    run_tasks(mgr, Task(entries=[entry(2, b"a=1", client=77, series=1)]))
    first = proxy.updates[-1][1]
    for idx in (3, 4, 5):  # three deadline retries of the SAME series
        run_tasks(
            mgr, Task(entries=[entry(idx, b"a=1", client=77, series=1)])
        )
        assert proxy.updates[-1][1] == first
        assert not proxy.updates[-1][2]  # not rejected
        assert not proxy.updates[-1][3]  # cached result, not 'ignored'
    assert sm.update_count == 1
    # the response cache really holds the unacknowledged series
    s = mgr._sessions.get_registered_client(77)
    assert s.get_response(1)[1]


def test_rsm_eviction_honors_responded_to_advance():
    """ISSUE 14 satellite: advancing responded_to EVICTS the cached
    result (session.go:109-120 clearTo — the client promised never to
    re-ask), and a late replay below the watermark reports
    already-responded (ignored) rather than re-applying or answering
    from a cache that no longer exists."""
    mgr, sm, proxy = mk_manager()
    run_tasks(
        mgr, Task(entries=[entry(1, client=77, series=SERIES_ID_FOR_REGISTER)])
    )
    run_tasks(mgr, Task(entries=[entry(2, b"a=1", client=77, series=1)]))
    s = mgr._sessions.get_registered_client(77)
    assert s.get_response(1)[1]
    # the next proposal carries responded_to=1: series 1's cache frees
    run_tasks(
        mgr,
        Task(entries=[entry(3, b"b=2", client=77, series=2, responded=1)]),
    )
    assert sm.update_count == 2
    assert s.responded_up_to == 1
    assert not s.get_response(1)[1], "acknowledged result not evicted"
    assert s.get_response(2)[1]  # the new series is cached
    # a late replay of the acknowledged series: ignored, no third apply
    run_tasks(
        mgr,
        Task(entries=[entry(4, b"a=zzz", client=77, series=1, responded=1)]),
    )
    assert proxy.updates[-1][3]  # ignored
    assert sm.update_count == 2


def test_rsm_expired_session_rejects_retry():
    """ISSUE 14 satellite: a session evicted by the replicated LRU
    (capacity pressure = session EXPIRY) REJECTS a retried proposal —
    at-most-once cover is gone and the client must re-register, never
    silently double-apply."""
    mgr, sm, proxy = mk_manager()
    mgr._sessions = SessionManager(max_sessions=1)
    run_tasks(
        mgr, Task(entries=[entry(1, client=77, series=SERIES_ID_FOR_REGISTER)])
    )
    run_tasks(mgr, Task(entries=[entry(2, b"a=1", client=77, series=1)]))
    assert sm.update_count == 1
    # registering a second client evicts 77 from the 1-slot LRU
    run_tasks(
        mgr, Task(entries=[entry(3, client=88, series=SERIES_ID_FOR_REGISTER)])
    )
    run_tasks(mgr, Task(entries=[entry(4, b"a=1", client=77, series=1)]))
    assert proxy.updates[-1][2], "expired session's retry not rejected"
    assert sm.update_count == 1, "expired session's retry re-applied"


def test_manager_config_change():
    mgr, sm, proxy = mk_manager()
    cc = ConfigChange(
        type=ConfigChangeType.ADD_NODE, node_id=2, address="a:2", initialize=True
    )
    e = Entry(
        index=1, term=1, type=EntryType.CONFIG_CHANGE, cmd=encode_config_change(cc),
        key=42,
    )
    run_tasks(mgr, Task(entries=[e]))
    assert proxy.cc_results == [(42, True)]
    assert mgr.get_membership().addresses == {2: "a:2"}
    # duplicate add rejected
    e2 = Entry(
        index=2, term=1, type=EntryType.CONFIG_CHANGE, cmd=encode_config_change(cc),
        key=43,
    )
    run_tasks(mgr, Task(entries=[e2]))
    assert proxy.cc_results[-1] == (43, False)


def test_manager_snapshot_task_interrupts_batch():
    mgr, sm, proxy = mk_manager()
    t1 = Task(entries=[entry(1, b"a=1")])
    t2 = Task(snapshot_requested=True)
    t3 = Task(entries=[entry(2, b"b=2")])
    mgr.task_queue.add(t1)
    mgr.task_queue.add(t2)
    mgr.task_queue.add(t3)
    batch, apply = [], []
    got = mgr.handle(batch, apply)
    assert got is t2
    assert sm.data == {"a": "1"}  # t1 applied before returning snapshot task
    got2 = mgr.handle(batch, apply)
    assert got2 is None
    assert sm.data == {"a": "1", "b": "2"}


# ------------------------------------------------------- run-level apply path
# (ISSUE 29) maximal runs of plain no-op-session entries go to sm.update
# as one call and to the node as one apply_update_run; everything else
# keeps the per-entry path. The per-entry path is the reference here.
KINDS = ("regular", "concurrent", "ondisk")


class _Counting:
    """Counts and sums 8-byte commands: an entry applied twice, skipped
    or out of order changes (n, acc)."""

    def __init__(self, opened_at=0):
        self.n = 0
        self.acc = 0
        self.opened_at = opened_at
        self.gate = None  # (entered, proceed) events: park inside update

    def _one(self, cmd: bytes) -> Result:
        self.n += 1
        self.acc = (self.acc * 31 + struct.unpack("<Q", cmd)[0]) % (1 << 61)
        if self.gate is not None and self.n == self.gate[2]:
            self.gate[0].set()
            assert self.gate[1].wait(10)
        return Result(value=self.n)

    def _many(self, entries):
        for e in entries:
            e.result = self._one(e.cmd)
        return entries

    def get_hash(self):
        return hash((self.n, self.acc))

    def lookup(self, q):
        return self.n, self.acc

    def _image(self) -> bytes:
        return struct.pack("<QQ", self.n, self.acc)

    def _load(self, r) -> None:
        self.n, self.acc = struct.unpack("<QQ", r.read(16))


class CountRegular(_Counting, IStateMachine):
    def update(self, cmd):
        return self._one(cmd)

    def save_snapshot(self, w, files, done):
        w.write(self._image())

    def recover_from_snapshot(self, r, files, done):
        self._load(r)


class CountConcurrent(_Counting, IConcurrentStateMachine):
    update = _Counting._many

    def prepare_snapshot(self):
        return self._image()

    def save_snapshot(self, ctx, w, files, done):
        w.write(ctx)

    def recover_from_snapshot(self, r, files, done):
        self._load(r)


class CountOnDisk(_Counting, IOnDiskStateMachine):
    update = _Counting._many

    def open(self, stopc):
        return self.opened_at

    def sync(self):
        pass

    def prepare_snapshot(self):
        return self._image()

    def save_snapshot(self, ctx, w, done):
        w.write(ctx)

    def recover_from_snapshot(self, r, done):
        self._load(r)


COUNTING = {
    "regular": CountRegular, "concurrent": CountConcurrent,
    "ondisk": CountOnDisk,
}


class NodeHalf(FakeNodeProxy):
    """Node's completion half (apply_update, apply_update_run and what
    they call, unbound from Node) over real pending tables: what the
    proposing node's waiters see, without an engine."""

    apply_update = Node.apply_update
    apply_update_run = Node.apply_update_run
    _batch_applied = Node._batch_applied
    _observe_entry_latency = Node._observe_entry_latency

    def __init__(self):
        super().__init__()
        clock = LogicalClock()
        self.pending_proposals = PendingProposal(clock)
        self.pending_read_indexes = PendingReadIndex(clock)
        self._batch_mu = threading.Lock()
        self._batches = {}
        self._req_profiler = Profiler(1)
        self._apply_t0 = time.monotonic()
        self.cluster_id = 5
        self._node_id = 1

    def _launch_no(self):
        return 7

    def _metrics_registry(self):
        return None

    def batch(self, bid, n):
        h = self._batches[bid] = BatchRequestState(bid, n, 1 << 30)
        return h


class RunSpy(FakeNodeProxy):
    def __init__(self):
        super().__init__()
        self.runs = []

    def apply_update_run(self, entries, results):
        self.runs.append(([e.index for e in entries], results))


def mk_counting(kind, proxy=None, opened_at=0, snapshotter=None):
    sm = COUNTING[kind](opened_at)
    proxy = proxy or RunSpy()
    cfg = Config(node_id=1, cluster_id=5, election_rtt=10, heartbeat_rtt=2)
    mgr = StateMachineManager(
        snapshotter, wrap_state_machine(sm, 5, 1), proxy, cfg
    )
    if mgr.on_disk_state_machine():
        mgr.open()
    calls = []
    inner = mgr._sm.update

    def update(entries):
        calls.append([se.index for se in entries])
        return inner(entries)

    mgr._sm.update = update
    return mgr, sm, proxy, calls


def cmd8(i):
    return struct.pack("<Q", 1000 + i)


@pytest.mark.parametrize("kind", KINDS)
def test_a_task_of_plain_entries_is_one_update_and_one_run_notify(kind):
    mgr, sm, proxy, calls = mk_counting(kind)
    n = 17
    ents = [entry(i, cmd8(i), term=3) for i in range(1, n + 1)]
    for i, e in enumerate(ents):
        e.key = make_batch_key(9, i)
    run_tasks(mgr, Task(entries=ents[:5]), Task(entries=ents[5:]))
    assert calls == [list(range(1, n + 1))]
    assert proxy.runs == [(list(range(1, n + 1)), None)]
    assert proxy.updates == []
    assert sm.n == n and mgr.get_last_applied() == (n, 3)
    assert (mgr.applied_entries, mgr.applied_run_entries,
            mgr.applied_runs) == (n, n, 1)


def mixed_stream(node: NodeHalf, tid0: int):
    """no-op run, session register + session-managed update, config
    change, empty new-leader entry, no-op run; batch and per-request keys
    mixed. Returns (entries, waiters in log order, batch handles, the
    sampled traces, their flight-recorder ids from `tid0` up)."""
    noop = ClientSession.noop_session(5)
    waiters, traces = [], []
    a, b = node.batch(11, 4), node.batch(12, 4)
    stream = []

    def put(e, term=2):
        e.index, e.term = len(stream) + 1, term
        stream.append(e)
        return e

    def keyed(session, cmd):
        rs, e = node.pending_proposals.propose(session, cmd, 1 << 20)
        waiters.append(rs)
        return put(e)

    def batched(bid, seq, i):
        return put(Entry(key=make_batch_key(bid, seq), cmd=cmd8(i)))

    def sampled(e):
        lt = e.lat = LatencyTrace(node, time.monotonic(), trace_id=tid0 + e.index)
        lt.t_pack = lt.t_commit = time.monotonic()
        traces.append(lt)

    # run 1: batch 11 (3 of its 4), two per-request keys between them
    batched(11, 0, 1)
    keyed(noop, cmd8(2))
    batched(11, 1, 3)
    sampled(batched(11, 2, 4))
    keyed(noop, cmd8(5))
    # session-managed: register, then an update under the session
    s = ClientSession(cluster_id=5, client_id=77, series_id=SERIES_ID_FOR_REGISTER)
    keyed(s, b"")
    s = ClientSession(cluster_id=5, client_id=77, series_id=1)
    keyed(s, cmd8(7))
    cc = ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=2,
                      address="a:2", initialize=True)
    put(Entry(type=EntryType.CONFIG_CHANGE, cmd=encode_config_change(cc),
              key=42))
    put(Entry(), term=3)  # a new leader's empty entry
    # run 2: the rest of batch 11, batch 12 whole, one per-request key
    batched(12, 0, 10)
    batched(12, 1, 11)
    batched(11, 3, 12)
    sampled(keyed(noop, cmd8(13)))
    batched(12, 2, 14)
    batched(12, 3, 15)
    return stream, waiters, (a, b), traces


def outcome(mgr, sm, node, waiters, batches, traces, tid0):
    prof = node._req_profiler.samples
    return {
        "hash": (sm.n, sm.acc), "applied": mgr.get_last_applied(),
        "members": mgr.get_membership().addresses,
        "sessions": mgr.get_session_hash(),
        "results": [
            (rs.done(), rs.result.code, rs.result.result.value)
            for rs in waiters
        ],
        "batches": [(h.completed, h.dropped, h.finished) for h in batches],
        "tracked": sorted(node._batches),
        "cc": node.cc_results,
        "traces": [(lt.done, lt.n_done, lt.t_done >= lt.t_apply0 > 0)
                   for lt in traces],
        "folded": len(prof.get("req.w.n", ())),
        "events": sorted(
            (e["trace"] - tid0, e["launch"]) for e in flight_recorder().dump()
            if e.get("event") == "proposal_applied"
            and e.get("trace") in {lt.trace_id for lt in traces}
        ),
    }


@pytest.mark.parametrize("kind", KINDS)
def test_a_mixed_stream_applies_as_the_per_entry_path_applies_it(kind):
    got = []
    for run_path in (True, False):
        tid0 = (1 << 40) + (KINDS.index(kind) * 2 + run_path) * 100
        node = NodeHalf()
        mgr, sm, _, calls = mk_counting(kind, proxy=node)
        stream, waiters, batches, traces = mixed_stream(node, tid0)
        if run_path:
            run_tasks(mgr, Task(entries=stream[:3]), Task(entries=stream[3:]))
            assert calls == [[1, 2, 3, 4, 5], [7], [10, 11, 12, 13, 14, 15]]
            assert (mgr.applied_entries, mgr.applied_run_entries,
                    mgr.applied_runs) == (15, 11, 2)
        else:
            for e in stream:
                mgr._handle_entry(e, False)
            assert [len(c) for c in calls] == [1] * 12
        got.append(outcome(mgr, sm, node, waiters, batches, traces, tid0))
    assert got[0] == got[1]
    assert got[0]["applied"] == (15, 2) and got[0]["hash"][0] == 12
    assert all(done for done, _, _ in got[0]["results"])
    assert got[0]["batches"] == [(4, 0, True), (4, 0, True)]
    assert got[0]["traces"] == [(True, 7, True)] * 2
    assert got[0]["folded"] == 2 and got[0]["events"] == [(4, 7), (13, 7)]


@pytest.mark.parametrize("shape", ["skips_head", "skips_all", "sm_returns_fewer"])
def test_a_run_lines_results_up_with_its_keys(shape):
    """An on-disk machine that opened at index 4 skips entries 1-4 of a
    run of 8 and the keys of 5-8 still get their own results; a machine
    that hands back fewer entries than it was given shifts nothing."""
    node = NodeHalf()
    opened = {"skips_head": 4, "skips_all": 8, "sm_returns_fewer": 0}[shape]
    mgr, sm, _, calls = mk_counting("ondisk", proxy=node, opened_at=opened)
    mgr._index = 0  # replay from the log's start, below the SM's own state
    if shape == "sm_returns_fewer":
        inner = mgr._sm.update
        mgr._sm.update = lambda ents: [
            se for se in inner(ents) if se.index % 2 == 0
        ]
    noop = ClientSession.noop_session(5)
    waiters, ents = [], []
    for i in range(1, 9):
        rs, e = node.pending_proposals.propose(noop, cmd8(i), 1 << 20)
        e.index, e.term = i, 1
        waiters.append(rs)
        ents.append(e)
    run_tasks(mgr, Task(entries=ents))
    values = [rs.result.result.value for rs in waiters]
    assert all(rs.done() for rs in waiters)
    if shape == "sm_returns_fewer":
        assert calls[0] == list(range(1, 9))
        assert values == [0, 2, 0, 4, 0, 6, 0, 8]
    else:
        assert calls == ([[5, 6, 7, 8]] if opened == 4 else [])
        assert values == [0] * opened + list(range(1, 9 - opened))
    assert mgr.get_last_applied() == (8, 1)
    assert mgr._on_disk_index == 8 and mgr.applied_runs == 1


class OneSnapshot:
    """ISnapshotter's save half: the image and the label it was given."""

    def __init__(self):
        self.meta = None
        self.image = io.BytesIO()

    def save(self, save_fn, meta):
        self.meta = meta
        save_fn(self.image, None)
        return None, None


@pytest.mark.parametrize("kind", KINDS)
def test_a_snapshot_during_a_run_is_never_labelled_before_its_data(kind):
    """A snapshot taken from another thread while a run is inside
    sm.update: restored and replayed from its own index, it counts what
    the original counts. (With the label read between update and the
    index advance, the image holds the whole run under the index before
    it and the replay applies the run twice.)"""
    snap = OneSnapshot()
    mgr, sm, _, _ = mk_counting(kind, snapshotter=snap)
    mgr._members.members.addresses = {1: "a:1"}
    n = 64
    ents = [entry(i, cmd8(i)) for i in range(1, n + 1)]
    run_tasks(mgr, Task(entries=ents[:8]))
    entered, proceed = threading.Event(), threading.Event()
    sm.gate = (entered, proceed, 40)  # park inside the run, at entry 40
    worker = threading.Thread(
        target=run_tasks, args=(mgr, Task(entries=ents[8:])), daemon=True
    )
    saver = threading.Thread(target=mgr.save_snapshot, daemon=True)
    worker.start()
    assert entered.wait(10)
    saver.start()
    saver.join(0.3)  # it may finish (at index 8) or wait for the run
    proceed.set()
    worker.join(10)
    saver.join(10)
    assert not worker.is_alive() and not saver.is_alive()
    assert mgr.last_applied_index() == n and sm.n == n
    assert snap.meta.index in (8, n)
    twin, sm2, _, _ = mk_counting(kind)
    snap.image.seek(0)
    if kind == "ondisk":
        twin._sm.recover_from_snapshot(snap.image, None, AbortSignal())
    else:
        twin._sm.recover_from_snapshot(snap.image, [], AbortSignal())
    assert sm2.n == snap.meta.index, "the label is not the data's index"
    twin._index = snap.meta.index
    run_tasks(twin, Task(entries=[
        entry(e.index, e.cmd) for e in ents if e.index > snap.meta.index
    ]))
    assert (sm2.n, sm2.acc) == (sm.n, sm.acc)
