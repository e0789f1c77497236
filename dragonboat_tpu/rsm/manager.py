"""Replicated state machine manager.

Applies committed entries / sessions / membership changes to the managed
user SM and orchestrates snapshot save/recover — the equivalent of
internal/rsm/statemachine.go:163-1054. The execution engine's task workers
drain the TaskQueue through handle(); all session dedup (at-most-once
semantics) and membership legality enforcement happens here, inside the
replicated apply path, so every replica makes identical decisions.
"""
from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Protocol, Tuple

import enum

from ..config import Config
from ..requests import BATCH_KEY_BIT
from .encoded import decode_payload


class From(enum.IntEnum):
    """Components that hold a reference to a managed SM
    (cf. internal/rsm/offload.go:18-46)."""

    STEP_WORKER = 0
    COMMIT_WORKER = 1
    SNAPSHOT_WORKER = 2
    NODEHOST = 3


class OffloadedStatus:
    """Ref-counted destroy discipline (cf. offload.go:48-133): the SM dies
    exactly once, after the NodeHost requests teardown and every worker
    has released its reference."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._loaded: set = set()
        self._teardown = False
        self._destroyed = False

    def set_loaded(self, frm: From) -> bool:
        """False once teardown began: the caller lost the race with the
        NodeHost close and must NOT touch the SM (the reference panics on
        SetLoaded-after-destroyed; a skip is the non-fatal equivalent)."""
        with self._mu:
            if self._teardown or self._destroyed:
                return False
            self._loaded.add(frm)
            return True

    def set_offloaded(self, frm: From) -> bool:
        """Returns True exactly once, when the destroy must run."""
        with self._mu:
            self._loaded.discard(frm)
            if frm == From.NODEHOST:
                self._teardown = True
            if self._teardown and not self._loaded and not self._destroyed:
                self._destroyed = True
                return True
            return False
from ..statemachine import (
    SM_TYPE_ONDISK,
    AbortSignal,
    Result,
    SMEntry,
    SnapshotStopped,
)
from ..types import (
    ConfigChange,
    Entry,
    EntryType,
    Membership,
    Snapshot,
    NOOP_CLIENT_ID,
    SERIES_ID_FOR_REGISTER,
    SERIES_ID_FOR_UNREGISTER,
)
from ..core.peer import decode_config_change
from .managed import ManagedStateMachine
from .membership import MembershipManager
from .session import SessionManager


@dataclass(slots=True)
class Task:
    """A unit of apply/snapshot work queued to the task workers
    (cf. internal/rsm/statemachine.go:106-119 Task)."""

    cluster_id: int = 0
    node_id: int = 0
    index: int = 0
    entries: List[Entry] = field(default_factory=list)
    snapshot_available: bool = False  # recover from snapshot
    init_done: bool = False
    snapshot_requested: bool = False  # take a snapshot
    stream_snapshot: bool = False
    periodic_sync: bool = False
    new_node: bool = False
    ss_request: Optional["SSRequest"] = None

    def is_snapshot_task(self) -> bool:
        return (
            self.snapshot_available
            or self.snapshot_requested
            or self.stream_snapshot
        )


SS_REQ_PERIODIC = 0
SS_REQ_USER = 1
SS_REQ_EXPORTED = 2
SS_REQ_STREAM = 3


@dataclass(slots=True)
class SSRequest:
    """Why a snapshot is being taken (cf. rsm SSRequest)."""

    type: int = SS_REQ_PERIODIC
    key: int = 0
    path: str = ""
    override_compaction: bool = False
    compaction_overhead: int = 0

    def is_exported(self) -> bool:
        return self.type == SS_REQ_EXPORTED

    def is_streaming(self) -> bool:
        return self.type == SS_REQ_STREAM


@dataclass(slots=True)
class SSMeta:
    """Point-in-time metadata captured under the SM mutex before a snapshot
    is written (cf. rsm SSMeta / getSSMeta)."""

    from_index: int = 0
    index: int = 0
    term: int = 0
    on_disk_index: int = 0
    request: Optional[SSRequest] = None
    membership: Optional[Membership] = None
    session: bytes = b""
    ctx: object = None
    compression: int = 0


class TaskQueue:
    """MPSC queue of apply tasks (cf. internal/rsm/taskqueue.go:31-96)."""

    def __init__(self) -> None:
        self._q: deque = deque()
        self._mu = threading.Lock()

    def add(self, t: Task) -> None:
        with self._mu:
            self._q.append(t)

    def get_all(self) -> List[Task]:
        with self._mu:
            out = list(self._q)
            self._q.clear()
        return out

    def get(self) -> Optional[Task]:
        with self._mu:
            return self._q.popleft() if self._q else None

    def size(self) -> int:
        with self._mu:
            return len(self._q)


class INodeProxy(Protocol):
    """Callbacks from the RSM layer into the per-group node runtime
    (cf. internal/rsm/statemachine.go INodeProxy)."""

    def node_ready(self) -> None: ...

    def apply_update(
        self,
        entry: Entry,
        result: Result,
        rejected: bool,
        ignored: bool,
        notify_read: bool,
    ) -> None: ...

    def apply_update_run(self, entries, results) -> None: ...

    def apply_config_change(self, cc: ConfigChange) -> None: ...

    def config_change_processed(self, key: int, accepted: bool) -> None: ...

    def node_id(self) -> int: ...

    def cluster_id(self) -> int: ...

    def should_stop(self) -> bool: ...


class ISnapshotter(Protocol):
    """Host-side snapshot file lifecycle used by the manager
    (cf. internal/rsm/statemachine.go ISnapshotter)."""

    def save(self, save_fn, meta: SSMeta) -> Tuple[Snapshot, object]: ...

    def load(self, ss: Snapshot, load_fn) -> None: ...

    def stream(self, stream_fn, meta: SSMeta, sink) -> None: ...

    def get_most_recent_snapshot(self) -> Optional[Snapshot]: ...

    def is_no_snapshot_error(self, e: Exception) -> bool: ...


class StateMachineManager:
    """Drives one group's managed SM (cf. rsm.StateMachine
    statemachine.go:163-188)."""

    def __init__(
        self,
        snapshotter,
        managed: ManagedStateMachine,
        node: INodeProxy,
        cfg: Config,
    ) -> None:
        self._snapshotter = snapshotter
        self._sm = managed
        self._node = node
        self._cfg = cfg
        self._mu = threading.RLock()  # guards index/term/sessions/membership
        self._index = 0
        self._term = 0
        self._on_disk_init_index = 0  # applied index discovered at open()
        self._on_disk_index = 0  # latest persisted-by-SM index
        self._sessions = SessionManager()
        self._members = MembershipManager(
            cfg.cluster_id, cfg.node_id, cfg.ordered_config_change
        )
        self._snapshotting = False
        self._aborted = AbortSignal()
        self._offload = OffloadedStatus()
        self.task_queue = TaskQueue()
        self._batched_last_applied = 0
        self._sync_req_index = 0
        # what this replica's apply path did, for the worker that drives
        # it to fold into the profiler: entries applied, those of them a
        # run applied, runs, and config-change entries applied, accepted
        # or not (one worker handles a node, so plain ints)
        self.applied_entries = 0
        self.applied_run_entries = 0
        self.applied_runs = 0
        self.config_changes_applied = 0
        # Critical section for `sm.update + applied-index advance`, so a
        # snapshot can never capture an index older than the data it
        # saves (replay from it would apply the gap twice, a whole run
        # wide). For a non-concurrent SM it is the wrapper mutex, which
        # save_snapshot holds across its index label + data write. A
        # concurrent/on-disk SM's snapshot is point-in-time from
        # prepare_snapshot, but its label is read beside it in
        # _get_ss_meta: those two take a lock of their own, outside
        # `_mu`, so no user update ever runs under `_mu`.
        if managed.concurrent_snapshot() or managed.on_disk():
            self._apply_section = threading.Lock()
        else:
            self._apply_section = managed.exclusive()

    # ------------------------------------------------------------ properties
    def last_applied_index(self) -> int:
        with self._mu:
            return self._index

    def applied_level(self) -> int:
        """The applied index read without `_mu`: a moment old at worst
        (an int attribute's read is atomic). For the engine's progress
        watch, which compares levels taken seconds apart over thousands
        of lanes from the loop thread and must never wait there for a
        worker that was descheduled holding the lock."""
        return self._index

    def get_last_applied(self) -> Tuple[int, int]:
        with self._mu:
            return self._index, self._term

    def on_disk_state_machine(self) -> bool:
        return self._sm.on_disk()

    def concurrent_snapshot(self) -> bool:
        return self._sm.concurrent_snapshot()

    def sm_type(self) -> int:
        return self._sm.sm_type()

    def on_disk_init_index(self) -> int:
        with self._mu:
            return self._on_disk_init_index

    # ------------------------------------------------------------- lifecycle
    def open(self) -> int:
        """Open an on-disk SM (cf. OpenOnDiskStateMachine
        statemachine.go:374-389)."""
        idx = self._sm.open(self._aborted)
        with self._mu:
            self._on_disk_init_index = idx
            self._on_disk_index = idx
            self._index = idx
        return idx

    def loaded(self, frm: "From") -> bool:
        """A component takes a reference to the managed SM; False when
        teardown already began (cf. offload.go:48-133 SetLoaded)."""
        return self._offload.set_loaded(frm)

    def offloaded(self, frm: "From" = None) -> None:
        """Drop a component's reference; the user SM is destroyed exactly
        once, only after the NodeHost requested teardown AND every worker
        released it — destroying under a mid-flight apply/snapshot would
        hand the user a dead SM (cf. offload.go:48-133 SetOffloaded)."""
        if frm is None or frm == From.NODEHOST:
            frm = From.NODEHOST
            self._aborted.stop()
        if self._offload.set_offloaded(frm):
            self._sm.destroy()

    # ------------------------------------------------------------ membership
    def get_membership(self) -> Membership:
        with self._mu:
            return self._members.get_membership()

    def get_membership_hash(self) -> int:
        with self._mu:
            return self._members.hash()

    def get_session_hash(self) -> int:
        with self._mu:
            return self._sessions.hash()

    # ----------------------------------------------------------------- reads
    def lookup(self, query: object) -> object:
        return self._sm.lookup(query)

    def get_hash(self) -> int:
        """SM content digest for cross-replica checks; SMs may expose
        get_hash(); fall back to hashing a snapshot image."""
        sm = self._sm._sm
        if hasattr(sm, "get_hash"):
            return sm.get_hash()
        return 0

    # ------------------------------------------------------------ champions
    def recover_from_snapshot(self, t: Task) -> int:
        """Install the most recent snapshot file (init or follower-install
        path); returns the snapshot index, 0 if none
        (cf. statemachine.go:222-358)."""
        ss = self._snapshotter.get_most_recent_snapshot()
        if ss is None:
            return 0
        if ss.witness or ss.dummy:
            with self._mu:
                self._apply_snapshot_meta(ss)
            self._notify_membership_loaded(ss)
            return ss.index
        on_disk = self._sm.on_disk()
        with self._mu:
            if ss.index <= self._index and not t.init_done:
                # already ahead (restart replay); nothing to do
                return ss.index
        init = not t.init_done
        if on_disk and init and ss.index <= self._on_disk_init_index:
            # SM's own durable state is already newer than the snapshot image
            with self._mu:
                self._apply_snapshot_meta(ss)
            self._notify_membership_loaded(ss)
            return ss.index
        self._snapshotter.load(ss, self._make_load_fn(ss))
        with self._mu:
            self._apply_snapshot_meta(ss)
            if on_disk:
                self._on_disk_index = max(self._on_disk_index, ss.on_disk_index)
        self._notify_membership_loaded(ss)
        return ss.index

    def _apply_snapshot_meta(self, ss: Snapshot) -> None:
        self._index = max(self._index, ss.index)
        self._term = max(self._term, ss.term)
        if ss.membership is not None:
            self._members.set_membership(ss.membership)

    def _notify_membership_loaded(self, ss: Snapshot) -> None:
        """Outside _mu: a restored membership image names every member's
        ADDRESS — the node runtime registers them with the host transport
        (a join-started node's bootstrap is empty; the snapshot is its
        only source of peer routing). Optional on the proxy: minimal
        INodeProxy implementations (tests/tools) skip it."""
        if ss.membership is None:
            return
        cb = getattr(self._node, "membership_loaded", None)
        if cb is not None:
            cb(ss.membership)

    def _make_load_fn(self, ss: Snapshot):
        def load(reader, session_bytes: bytes, files) -> None:
            # on-disk SMs have no replicated session image in dummy
            # snapshots; everything else restores the session LRU first
            if session_bytes:
                with self._mu:
                    self._sessions.load(session_bytes)
            self._sm.recover_from_snapshot(reader, files, self._aborted)

        return load

    def load_sessions(self, data: bytes) -> None:
        with self._mu:
            self._sessions.load(data)

    # ---------------------------------------------------------------- saving
    def save_snapshot(self, req: Optional[SSRequest] = None) -> Tuple[Snapshot, object]:
        """Synchronously produce a snapshot (cf. statemachine.go:513-525,
        697-749). For concurrent SMs prepare runs under the apply mutex and
        the streaming write runs outside it. For NON-concurrent SMs the
        index label and the data write are one critical section under the
        wrapper mutex — a save racing the apply path could otherwise label
        post-capture data with a pre-capture index, and restart replay
        would re-apply the gap (observed as a double-applied counter)."""
        req = req or SSRequest()
        if self._sm.concurrent_snapshot() or self._sm.on_disk():
            meta = self._get_ss_meta(req)
            ss, env = self._snapshotter.save(self._make_save_fn(meta), meta)
            return ss, env
        with self._sm.exclusive():
            meta = self._get_ss_meta(req)
            ss, env = self._snapshotter.save(self._make_save_fn(meta), meta)
            return ss, env

    def stream_snapshot(self, sink) -> None:
        """Stream live state to a lagging peer (on-disk SMs,
        cf. statemachine.go:680-695)."""
        meta = self._get_ss_meta(SSRequest(type=SS_REQ_STREAM))
        self._snapshotter.stream(self._make_save_fn(meta), meta, sink)

    def _get_ss_meta(self, req: SSRequest) -> SSMeta:
        with self._apply_section, self._mu:
            if self._members.is_empty():
                raise RuntimeError("taking snapshot with empty membership")
            ctx = self._sm.prepare_snapshot() if self._sm.concurrent_snapshot() else None
            return SSMeta(
                from_index=0,
                index=self._index,
                term=self._term,
                on_disk_index=self._on_disk_index,
                request=req,
                membership=self._members.get_membership(),
                session=b"" if self._sm.on_disk() else self._sessions.save(),
                ctx=ctx,
                compression=int(self._cfg.snapshot_compression_type),
            )

    def _make_save_fn(self, meta: SSMeta):
        def save(writer, files) -> None:
            self._sm.save_snapshot(meta.ctx, writer, files, self._aborted)

        return save

    def sync(self) -> None:
        self._sm.sync()

    # --------------------------------------------------------------- applying
    def handle(self, batch: List[Task], apply: List[SMEntry]) -> Optional[Task]:
        """Drain the task queue, applying entry batches; returns the first
        snapshot task encountered (the engine routes it to a snapshot
        worker), cf. statemachine.go:560-608."""
        batch.clear()
        while True:
            t = self.task_queue.get()
            if t is None:
                break
            if t.is_snapshot_task():
                # apply what we have, then hand the snapshot task back
                self._handle_batch(batch, apply)
                return t
            if not t.entries:
                if t.periodic_sync:
                    self._periodic_sync()
                continue
            batch.append(t)
        self._handle_batch(batch, apply)
        return None

    def _periodic_sync(self) -> None:
        if self._sm.on_disk():
            self._sm.sync()

    def _handle_batch(self, batch: List[Task], apply: List[SMEntry]) -> None:
        if not batch:
            return
        use_batch = self._sm.concurrent_snapshot() or self._sm.on_disk()
        apply.clear()
        # fast path for EVERY SM type: maximal runs of plain no-op-session
        # application entries apply under ONE lock round-trip with ONE
        # run-level completion notify (per-entry locks + notifications
        # were the apply-side hot spot at high proposal rates). Session-
        # managed entries, config changes and empty new-leader entries go
        # one by one. Log order is preserved by flushing the other buffer
        # whenever the entry stream switches between the two.
        run: List[Entry] = []
        slow = 0
        floor = self._index  # the last index applied or taken up here
        for t in batch:
            for e in t.entries:
                if e.index <= floor:
                    # already applied: a snapshot recovery can leapfrog
                    # entry tasks that were queued before it (the reference
                    # tolerates the same overlap, statemachine.go onUpdate)
                    continue
                floor = e.index
                if (
                    e.client_id == NOOP_CLIENT_ID
                    and e.cmd
                    and e.type != EntryType.CONFIG_CHANGE
                ):
                    if apply:
                        self._apply_batch(apply)
                        apply.clear()
                    run.append(e)
                    continue
                self._flush_run(run)
                slow += 1
                if use_batch:
                    self._handle_entry_batched(e, apply)
                else:
                    self._handle_entry(e, False)
        self._flush_run(run)
        if apply:
            self._apply_batch(apply)
            apply.clear()
        self.applied_entries += slow
        batch.clear()

    def _flush_run(self, run: List[Entry]) -> None:
        """Apply a contiguous run of plain updates, then notify once."""
        if not run:
            return
        ents = run[:]
        run.clear()
        on_disk = self._sm.on_disk()
        skip_until = self._on_disk_init_index if on_disk else 0
        smes = [
            SMEntry(index=e.index, cmd=decode_payload(e))
            for e in ents if e.index > skip_until
        ]
        last = ents[-1]
        with self._apply_section:
            done = self._sm.update(smes) if smes else smes
            with self._mu:
                self._set_applied(last.index, last.term)
                if on_disk:
                    self._on_disk_index = max(self._on_disk_index, last.index)
        self.applied_entries += len(ents)
        self.applied_run_entries += len(ents)
        self.applied_runs += 1
        # per-proposal results are only retained for per-request keys;
        # batch-tracked proposals complete by count alone, so the common
        # bulk path skips the result realignment entirely. By index, not
        # by position: what the SM skipped (on-disk, already persisted)
        # or did not hand back answers with the empty Result, as
        # _do_update answers it.
        if any(e.key and not (e.key & BATCH_KEY_BIT) for e in ents):
            by_index = {se.index: se.result for se in done}
            empty = Result()
            results = [by_index.get(e.index, empty) for e in ents]
        else:
            results = None
        self._node.apply_update_run(ents, results)

    def _handle_entry_batched(self, e: Entry, apply: List[SMEntry]) -> None:
        """Batched path: plain updates accumulate; anything session- or
        config-related flushes the batch first (cf. handleBatch
        statemachine.go:895-937)."""
        if e.is_config_change() or not e.is_update() or e.is_empty():
            self._apply_batch(apply)
            apply.clear()
            self._handle_entry(e, False)
            return
        # session dedup check must happen at apply time in order
        self._apply_batch_boundary(e, apply)

    def _apply_batch_boundary(self, e: Entry, apply: List[SMEntry]) -> None:
        with self._mu:
            if e.is_session_managed():
                session = self._sessions.get_registered_client(e.client_id)
                if session is None:
                    self._flush_then_reject(e, apply)
                    return
                if session.has_responded(e.series_id):
                    self._flush_then_ignore(e, apply)
                    return
                cached, has = session.get_response(e.series_id)
                if has:
                    self._set_applied(e.index, e.term)
                    self._node.apply_update(e, cached, False, False, True)
                    return
        apply.append(SMEntry(index=e.index, cmd=decode_payload(e)))
        self._pending_session_entries = getattr(self, "_pending_session_entries", {})
        self._pending_session_entries[e.index] = e

    def _flush_then_reject(self, e: Entry, apply: List[SMEntry]) -> None:
        self._apply_batch(apply)
        apply.clear()
        self._set_applied(e.index, e.term)
        self._node.apply_update(e, Result(), True, False, True)

    def _flush_then_ignore(self, e: Entry, apply: List[SMEntry]) -> None:
        self._apply_batch(apply)
        apply.clear()
        self._set_applied(e.index, e.term)
        self._node.apply_update(e, Result(), False, True, True)

    def _apply_batch(self, apply: List[SMEntry]) -> None:
        # only reachable for concurrent/on-disk SMs (_handle_batch's
        # use_batch gate), and from _apply_batch_boundary under `_mu`,
        # inside which the _apply_section lock cannot be taken: a snapshot
        # between update and the index advance below can still label a
        # buffer of session-managed updates with the index before it
        if not apply:
            return
        skip_until = self._on_disk_init_index if self._sm.on_disk() else 0
        to_run = [se for se in apply if se.index > skip_until]
        results = self._sm.update(to_run) if to_run else []
        pend = getattr(self, "_pending_session_entries", {})
        with self._mu:
            for se in apply:
                ran = se.index > skip_until
                e = pend.pop(se.index, None)
                self._set_applied(se.index, e.term if e is not None else self._term)
                if self._sm.on_disk():
                    self._on_disk_index = max(self._on_disk_index, se.index)
                if e is None:
                    continue
                if e.is_session_managed() and ran:
                    session = self._sessions.get_registered_client(e.client_id)
                    if session is not None:
                        session.clear_to(e.responded_to)
                        if not session.has_responded(e.series_id):
                            session.add_response(e.series_id, se.result)
                self._node.apply_update(e, se.result, False, False, True)

    def _handle_entry(self, e: Entry, notify_read: bool) -> None:
        """Serial apply of one entry (cf. handleEntry
        statemachine.go:790-886, handleUpdate :989-1032)."""
        if e.is_config_change():
            accepted = self._handle_config_change(e)
            self._node.config_change_processed(e.key, accepted)
            return
        if not e.is_session_managed():
            if e.is_empty():
                # new-leader noop entry: only moves applied index
                with self._mu:
                    self._set_applied(e.index, e.term)
                self._node.apply_update(e, Result(), False, True, notify_read)
                return
            # noop-session proposal: apply without dedup
            self._do_update(e, notify_read)
            return
        if e.is_new_session_request():
            with self._mu:
                result = self._sessions.register_client_id(e.client_id)
                self._set_applied(e.index, e.term)
            self._node.apply_update(
                e, result, result.value == 0, False, notify_read
            )
            return
        if e.is_end_of_session_request():
            with self._mu:
                result = self._sessions.unregister_client_id(e.client_id)
                self._set_applied(e.index, e.term)
            self._node.apply_update(
                e, result, result.value == 0, False, notify_read
            )
            return
        # session-managed update with dedup
        with self._mu:
            session = self._sessions.get_registered_client(e.client_id)
            if session is None:
                self._set_applied(e.index, e.term)
                self._node.apply_update(e, Result(), True, False, notify_read)
                return
            session.clear_to(e.responded_to)
            if session.has_responded(e.series_id):
                self._set_applied(e.index, e.term)
                self._node.apply_update(e, Result(), False, True, notify_read)
                return
            cached, has = session.get_response(e.series_id)
            if has:
                self._set_applied(e.index, e.term)
                self._node.apply_update(e, cached, False, False, notify_read)
                return
        self._do_update(e, notify_read, session=e.client_id)

    def _do_update(self, e: Entry, notify_read: bool, session: int = 0) -> None:
        skip = self._sm.on_disk() and e.index <= self._on_disk_init_index
        with self._apply_section:
            if skip:
                results = [SMEntry(index=e.index, cmd=decode_payload(e))]
            else:
                results = self._sm.update(
                    [SMEntry(index=e.index, cmd=decode_payload(e))]
                )
            result = results[0].result if results else Result()
            with self._mu:
                if session:
                    s = self._sessions.get_registered_client(session)
                    if s is not None and not s.has_responded(e.series_id):
                        got, has = s.get_response(e.series_id)
                        if not has:
                            s.add_response(e.series_id, result)
                self._set_applied(e.index, e.term)
                if self._sm.on_disk():
                    self._on_disk_index = max(self._on_disk_index, e.index)
        self._node.apply_update(e, result, False, False, notify_read)

    def _handle_config_change(self, e: Entry) -> bool:
        cc = decode_config_change(e.cmd)
        with self._mu:
            accepted = self._members.handle_config_change(cc, e.index)
            self._set_applied(e.index, e.term)
        self.config_changes_applied += 1
        if accepted:
            self._node.apply_config_change(cc)
        return accepted

    def _set_applied(self, index: int, term: int) -> None:
        if index < self._index:
            raise RuntimeError(
                f"applied index moving backwards: {self._index} -> {index}"
            )
        self._index = index
        self._term = term


__all__ = [
    "Task",
    "TaskQueue",
    "SSRequest",
    "SSMeta",
    "SS_REQ_PERIODIC",
    "SS_REQ_USER",
    "SS_REQ_EXPORTED",
    "SS_REQ_STREAM",
    "INodeProxy",
    "ISnapshotter",
    "StateMachineManager",
]
