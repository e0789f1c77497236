"""Async request plumbing: pending proposals, reads, config changes,
snapshots, leader transfers.

cf. requests.go:48-1133 — every user request becomes a RequestState with a
completion event; timeouts are enforced by a logical clock advanced on the
NodeHost tick so no per-request timers exist. Proposals are keyed (the key
rides in the entry and comes back from the apply path), ReadIndex requests
batch many user reads under one 128-bit system context.
"""
from __future__ import annotations

import itertools
import os
import random
import struct
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from .client import Session
from .statemachine import Result
from .types import (
    Entry,
    EntryType,
    ConfigChange,
    Membership,
    Snapshot,
    SystemCtx,
)


class RequestError(Exception):
    code = "request error"


class ErrClusterNotFound(RequestError):
    code = "cluster not found"


class ErrClusterNotReady(RequestError):
    code = "cluster not ready"


class ErrClusterClosed(RequestError):
    code = "raft cluster already closed"


class ErrTimeout(RequestError):
    code = "timeout"


class ErrCanceled(RequestError):
    code = "request canceled"


class ErrRejected(RequestError):
    code = "request rejected"


class ErrSystemBusy(RequestError):
    """Overload shed: fail fast, safe to retry. `retry_after_s` is the
    machine-readable backoff hint (0.0 = none); the serving plane's
    typed subclasses (serving.admission.ErrOverloaded family) populate
    it, and serving.retry.call_with_retries honors it as a backoff
    floor — so every ErrSystemBusy anywhere in the stack reads uniformly
    at the client."""

    code = "system is too busy, try again later"
    retry_after_s = 0.0


class ErrSnapshotStreamAborted(ErrSystemBusy):
    """An inbound snapshot-install stream feeding this replica's catch-up
    aborted mid-transfer (receiver crash, sender failure, chunk gap).
    Client ops that gate on the install — linearizable reads waiting for
    the applied index, any op while the group has no reachable leader —
    fail FAST with this instead of burning their whole budget into a
    generic ErrTimeout. Subclasses ErrSystemBusy so
    serving.retry.call_with_retries retries it automatically, honoring
    `retry_after_s` (sized to the raft snapshot-status retry window: when
    the re-streamed install should have landed) as the backoff floor."""

    code = "snapshot install stream aborted, retry later"

    def __init__(self, retry_after_s: float = 0.0):
        super().__init__()
        self.retry_after_s = float(retry_after_s)


class ErrMigrationAborted(ErrSystemBusy):
    """A live group migration (serving/placement.py: leadership transfer
    + streamed-snapshot member swap) was aborted mid-flight — operator
    abort, catch-up timeout, or an admission shed of the migration's own
    bulk-class traffic. The group stays where it was and keeps serving;
    the move itself is what failed, and it is safe to retry once the
    pressure that killed it clears. Subclasses ErrSystemBusy so
    serving.retry.call_with_retries retries it automatically, honoring
    `retry_after_s` (sized by the aborting step: an admission shed
    forwards the shed's own hint, a catch-up timeout suggests one
    snapshot-status window) as the backoff floor."""

    code = "group migration aborted, retry later"

    def __init__(self, retry_after_s: float = 0.0, reason: str = ""):
        super().__init__(reason or self.code)
        self.retry_after_s = float(retry_after_s)
        self.reason = reason


class ErrLeaseExpired(ErrSystemBusy):
    """The lease-only read probe (NodeHost.lease_read) found no live
    leader lease on this replica — expired, revoked by step-down or
    leadership transfer, or suspended by a clock-anomaly report from the
    tick plane. This error is raised ONLY by the explicit lease-only
    probe API; the normal linearizable read path never surfaces it — an
    invalid lease there silently degrades to the ReadIndex quorum round
    (degradation, not danger). Subclasses ErrSystemBusy so
    serving.retry.call_with_retries retries it automatically, honoring
    `retry_after_s` (sized to roughly one heartbeat interval: the next
    quorum heartbeat round is what re-arms the lease) as the backoff
    floor."""

    code = "no live leader lease, read via ReadIndex instead"

    def __init__(self, retry_after_s: float = 0.0, reason: str = ""):
        super().__init__(reason or self.code)
        self.retry_after_s = float(retry_after_s)
        self.reason = reason


class ErrInvalidSession(RequestError):
    code = "invalid session"


class ErrTimeoutTooSmall(RequestError):
    code = "timeout is too small"


class ErrPayloadTooBig(RequestError):
    code = "payload is too big"


class ErrSystemStopped(RequestError):
    code = "system stopped"


# request completion codes (cf. requests.go RequestResultCode)
REQUEST_TIMEOUT = 0
REQUEST_COMPLETED = 1
REQUEST_TERMINATED = 2
REQUEST_REJECTED = 3
REQUEST_DROPPED = 4


@dataclass
class RequestResult:
    code: int = REQUEST_TIMEOUT
    result: Result = field(default_factory=Result)
    snapshot_index: int = 0

    @property
    def completed(self) -> bool:
        return self.code == REQUEST_COMPLETED

    @property
    def timeout(self) -> bool:
        return self.code == REQUEST_TIMEOUT

    @property
    def terminated(self) -> bool:
        return self.code == REQUEST_TERMINATED

    @property
    def rejected(self) -> bool:
        return self.code == REQUEST_REJECTED

    @property
    def dropped(self) -> bool:
        return self.code == REQUEST_DROPPED


# ---------------------------------------------------------------------------
# batch proposals: one completion record per submission
# ---------------------------------------------------------------------------

# Entry.key namespace bit marking batch-tracked proposals: the key encodes
# (batch_id, seq) instead of naming a per-request registry slot, so a
# thousand-proposal batch costs ONE registration and ONE completion event
# instead of a thousand (no referent in the reference — its clients are
# strictly one RequestState per proposal, requests.go:267-329).
BATCH_KEY_BIT = 1 << 62
_BATCH_SEQ_BITS = 24


def make_batch_id(node_id: int, counter: int) -> int:
    """Batch ids are registry keys AND travel in replicated entry keys, so
    they embed the submitting node's identity: a replica applying another
    node's batch entries must not credit a same-numbered batch of its own
    (the per-request path gets this protection from client_id/series_id
    checks; the batch path gets it from the id itself)."""
    return ((node_id & 0xFFFF) << 22) | (counter & 0x3FFFFF)


def make_batch_key(batch_id: int, seq: int) -> int:
    return BATCH_KEY_BIT | (batch_id << _BATCH_SEQ_BITS) | seq


def batch_id_of(key: int) -> int:
    return (key & ~BATCH_KEY_BIT) >> _BATCH_SEQ_BITS


class BatchRequestState:
    """Completion record for one propose_batch_async submission: counts
    applied/dropped proposals and fires a single event when the whole
    batch is accounted for. Thread-safe (engine loop + apply workers +
    the waiting client)."""

    __slots__ = ("batch_id", "n", "completed", "dropped", "deadline",
                 "completed_at", "_event", "_mu")

    def __init__(self, batch_id: int, n: int, deadline: int) -> None:
        self.batch_id = batch_id
        self.n = n
        self.completed = 0
        self.dropped = 0
        self.deadline = deadline
        # time.monotonic() at which the last proposal of the batch was
        # accounted, read on the thread that accounted it; 0.0 until then
        self.completed_at = 0.0
        self._event = threading.Event()
        self._mu = threading.Lock()

    def _finish_locked(self) -> None:
        if not self._event.is_set():
            self.completed_at = time.monotonic()
            self._event.set()

    def add_done(self, completed: int = 0, dropped: int = 0) -> None:
        with self._mu:
            self.completed += completed
            self.dropped += dropped
            if self.completed + self.dropped >= self.n:
                self._finish_locked()

    def expire(self) -> None:
        """Timeout: account every outstanding proposal as dropped."""
        with self._mu:
            rest = self.n - self.completed - self.dropped
            if rest > 0:
                self.dropped += rest
            self._finish_locked()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    @property
    def finished(self) -> bool:
        return self._event.is_set()


# guards the callback handoff in RequestState._fire_cb; module-level so
# the per-request fast path (no callback registered) stays lock-free
_cb_fire_mu = threading.Lock()

# sticky proposal-shard assignment per client thread (see
# PendingProposal.propose); module-level so every node's registry spreads
# the same way
_shard_tls = threading.local()
_shard_rr = itertools.count()


class RequestState:
    """One in-flight request (cf. requests.go:267-329). wait() blocks the
    calling thread; the engine thread completes it via notify()."""

    __slots__ = ("key", "client_id", "series_id", "deadline", "_event",
                 "_result", "_cb", "lat")

    def __init__(self) -> None:
        self.key = 0
        self.client_id = 0
        self.series_id = 0
        self.deadline = 0
        self._event = threading.Event()
        self._result: Optional[RequestResult] = None
        self._cb = None
        # sampled-latency carrier (see trace.LatencySampler): None on the
        # unsampled hot path; Node.read attaches a trace.LatencyTrace to
        # 1-in-N reads so the engine can stamp their path and completion
        # can observe readindex latency (proposals carry their trace on
        # the Entry instead — the same object travels propose -> arena ->
        # commit -> apply)
        self.lat = None

    def notify(self, result: RequestResult) -> None:
        self._result = result
        self._event.set()
        if self._cb is not None:
            self._fire_cb()

    def on_complete(self, cb) -> None:
        """Invoke cb(self) exactly once when the request completes — from
        the completing engine thread, so cb must be brief and non-blocking
        (used by the embedding ABI's event delivery; cf. the reference's
        Event.Set discipline, binding dragonboat.h:377-394). Fires
        immediately if already complete. Callbacks COMPOSE: a second
        registration chains after the first instead of replacing it (the
        latency sampler registers on 1-in-N reads before the caller gets
        the RequestState — a replacing slot would silently drop whichever
        callback came first)."""
        prev = self._cb
        if prev is not None:
            nxt = cb

            def cb(rs, _prev=prev, _nxt=nxt):
                _prev(rs)
                _nxt(rs)

        self._cb = cb
        if self._event.is_set():
            self._fire_cb()

    def _fire_cb(self) -> None:
        with _cb_fire_mu:  # exactly-once between notify and on_complete
            cb, self._cb = self._cb, None
        if cb is not None:
            cb(self)

    def wait(self, timeout: Optional[float] = None) -> RequestResult:
        if not self._event.wait(timeout):
            return RequestResult(code=REQUEST_TIMEOUT)
        return self._result

    def done(self) -> bool:
        return self._event.is_set()

    @property
    def result(self) -> Optional[RequestResult]:
        return self._result


# the random bases of proposal keys and ReadIndex contexts: seeded once
# from the system, then drawn without a system call (a bring-up makes
# eight a replica, and every system call on a busy process waits its turn
# for the GIL); a forked child draws its own
_RANDOM = random.Random(os.urandom(32))
os.register_at_fork(after_in_child=lambda: _RANDOM.seed(os.urandom(32)))


class LogicalClock:
    """Tick-driven clock for request GC (cf. requests.go:223-241)."""

    __slots__ = ("tick", "last_gc_time", "gc_tick")

    GC_TICK = 2

    def __init__(self) -> None:
        self.tick = 0
        self.last_gc_time = 0

    def increase_tick(self) -> None:
        self.tick += 1

    def should_gc(self) -> bool:
        if self.tick - self.last_gc_time >= self.GC_TICK:
            self.last_gc_time = self.tick
            return True
        return False


class _ProposalShard:
    """Keyed in-flight proposals, one lock's worth
    (cf. proposalShard requests.go:983-1133)."""

    def __init__(self, clock: LogicalClock, offset: int = 0,
                 stride: int = 1) -> None:
        self._mu = threading.Lock()
        self._pending: Dict[int, RequestState] = {}
        self._clock = clock
        # keys from this shard are ≡ offset (mod stride), so completions
        # route back by key alone; the random base has its low 16 bits
        # clear, keeping the congruence intact. Bits 61+ stay clear so a
        # per-request key can never collide with the BATCH_KEY_BIT
        # namespace (batch-tracked proposals route by batch id instead).
        self._key_seq = itertools.count(
            (_RANDOM.getrandbits(45) << 16) + offset, stride
        )
        self.stopped = False

    def _make_request(
        self, session: Session, cmd: bytes, deadline: int
    ) -> Tuple[RequestState, Entry]:
        """One registration record; single and batch submission MUST build
        identical requests (shared so they cannot drift)."""
        rs = RequestState()
        rs.key = next(self._key_seq)
        rs.client_id = session.client_id
        rs.series_id = session.series_id
        rs.deadline = deadline
        entry = Entry(
            key=rs.key,
            client_id=session.client_id,
            series_id=session.series_id,
            responded_to=session.responded_to,
            cmd=cmd,
        )
        return rs, entry

    def propose(
        self, session: Session, cmd: bytes, timeout_ticks: int
    ) -> Tuple[RequestState, Entry]:
        if timeout_ticks < 1:
            raise ErrTimeoutTooSmall()
        rs, entry = self._make_request(
            session, cmd, self._clock.tick + timeout_ticks
        )
        with self._mu:
            if self.stopped:
                raise ErrClusterClosed()
            self._pending[rs.key] = rs
        return rs, entry

    def propose_batch(
        self, session: Session, cmds, timeout_ticks: int
    ) -> Tuple[List[RequestState], List[Entry]]:
        """Register a whole batch under ONE lock acquisition — the
        per-proposal lock round-trip is the submission-path hot spot."""
        if timeout_ticks < 1:
            raise ErrTimeoutTooSmall()
        deadline = self._clock.tick + timeout_ticks
        pairs = [self._make_request(session, cmd, deadline) for cmd in cmds]
        with self._mu:
            if self.stopped:
                raise ErrClusterClosed()
            for rs, _ in pairs:
                self._pending[rs.key] = rs
        return [rs for rs, _ in pairs], [e for _, e in pairs]

    def applied(
        self, key: int, client_id: int, series_id: int, result: Result,
        rejected: bool,
    ) -> None:
        """Apply-path notification (cf. requests.go:1086-1103)."""
        with self._mu:
            rs = self._pending.get(key)
            if rs is None:
                return
            if rs.client_id != client_id or rs.series_id != series_id:
                return
            del self._pending[key]
        code = REQUEST_REJECTED if rejected else REQUEST_COMPLETED
        rs.notify(RequestResult(code=code, result=result))

    def dropped(self, key: int) -> None:
        with self._mu:
            rs = self._pending.pop(key, None)
        if rs is not None:
            rs.notify(RequestResult(code=REQUEST_DROPPED))

    def close(self) -> None:
        with self._mu:
            self.stopped = True
            pending = list(self._pending.values())
            self._pending.clear()
        for rs in pending:
            rs.notify(RequestResult(code=REQUEST_TERMINATED))

    def gc(self) -> None:
        """Sweep expired requests. Unconditional: the caller owns the
        cadence (one should_gc() check per clock window covers every
        Pending* sharing that clock — gating here let the first callee
        consume the window and starve the rest)."""
        now = self._clock.tick
        with self._mu:
            expired = [k for k, rs in self._pending.items() if rs.deadline < now]
            states = [self._pending.pop(k) for k in expired]
        for rs in states:
            rs.notify(RequestResult(code=REQUEST_TIMEOUT))

    def has_pending(self) -> bool:
        return bool(self._pending)

    def pending_count(self) -> int:
        """Lock-free in-flight count (backpressure probe; a torn read
        costs one stale sample, never a wrong decision stream)."""
        return len(self._pending)


class PendingProposal:
    """Sharded in-flight proposal registry (cf. pendingProposal
    requests.go:903-981: 16 shards keyed by random key to cut mutex
    contention). Even under the GIL the single proposal lock is contended
    — every client thread and the engine's apply path serialize on it —
    so proposals shard by submitting thread and completions route back by
    key congruence (shard i issues keys ≡ i mod SHARDS)."""

    SHARDS = 8

    def __init__(self, clock: LogicalClock) -> None:
        self._shards = [
            _ProposalShard(clock, offset=i, stride=self.SHARDS)
            for i in range(self.SHARDS)
        ]

    def _thread_shard(self) -> "_ProposalShard":
        # thread affinity: each client thread gets a sticky shard index
        # (round-robin at first use — thread idents are pointer-aligned,
        # so ident % SHARDS would collide), keeping concurrent submitters
        # on different locks with no per-propose shared routing state
        idx = getattr(_shard_tls, "idx", None)
        if idx is None:
            idx = _shard_tls.idx = next(_shard_rr)
        return self._shards[idx % self.SHARDS]

    def propose(
        self, session: Session, cmd: bytes, timeout_ticks: int
    ) -> Tuple[RequestState, Entry]:
        return self._thread_shard().propose(session, cmd, timeout_ticks)

    def propose_batch(
        self, session: Session, cmds, timeout_ticks: int
    ) -> Tuple[List[RequestState], List[Entry]]:
        return self._thread_shard().propose_batch(
            session, cmds, timeout_ticks
        )

    def applied(
        self, key: int, client_id: int, series_id: int, result: Result,
        rejected: bool,
    ) -> None:
        self._shards[key % self.SHARDS].applied(
            key, client_id, series_id, result, rejected
        )

    def dropped(self, key: int) -> None:
        self._shards[key % self.SHARDS].dropped(key)

    def close(self) -> None:
        for s in self._shards:
            s.close()

    def gc(self) -> None:
        for s in self._shards:
            s.gc()

    def has_pending(self) -> bool:
        return any(s.has_pending() for s in self._shards)

    def pending_count(self) -> int:
        """Total in-flight proposals across shards (backpressure probe)."""
        return sum(s.pending_count() for s in self._shards)


class PendingReadIndex:
    """ReadIndex batching: many user reads share one system context
    (cf. requests.go:654-886)."""

    def __init__(self, clock: LogicalClock) -> None:
        self._mu = threading.Lock()
        self._clock = clock
        # reads queued but not yet bound to a ctx
        self._queued: List[RequestState] = []
        # ctx -> (bound reads, ready index or None)
        self._batches: Dict[SystemCtx, List[RequestState]] = {}
        self._ready: List[Tuple[SystemCtx, int]] = []  # confirmed, awaiting apply
        self._ctx_seq = itertools.count(1)
        self.stopped = False

    def read(self, timeout_ticks: int) -> RequestState:
        if timeout_ticks < 1:
            raise ErrTimeoutTooSmall()
        rs = RequestState()
        rs.deadline = self._clock.tick + timeout_ticks
        with self._mu:
            if self.stopped:
                raise ErrClusterClosed()
            self._queued.append(rs)
        return rs

    def has_queued(self) -> bool:
        return bool(self._queued)

    def has_pending(self) -> bool:
        return bool(self._queued or self._batches)

    def pending_count(self) -> int:
        """Queued + bound-but-unreleased reads (backpressure probe;
        lock-free, torn reads cost one stale sample)."""
        return len(self._queued) + sum(
            len(b) for b in self._batches.values()
        )

    def has_ctx(self, ctx: SystemCtx) -> bool:
        """Whether a bound batch is still alive for ctx (engine-side
        routing entries are GC'd once their batch times out or completes)."""
        return ctx in self._batches

    def next_ctx(self) -> SystemCtx:
        return SystemCtx(
            low=next(self._ctx_seq),
            high=_RANDOM.getrandbits(64) | 1,
        )

    def bind_queued(self, ctx: SystemCtx) -> bool:
        """Engine: bind all queued reads to ctx before Peer.read_index(ctx)
        (cf. nextReadIndexCtx/peepNextCtx requests.go:732-778)."""
        with self._mu:
            if not self._queued:
                return False
            self._batches[ctx] = self._queued
            self._queued = []
        return True

    def bind_queued_states(self, states: List[RequestState], ctx: SystemCtx) -> bool:
        """Bind an explicit batch popped from the node's read queue; the
        states were registered in _queued by read() and move to the ctx."""
        if not states:
            return False
        with self._mu:
            qs = set(map(id, states))
            self._queued = [rs for rs in self._queued if id(rs) not in qs]
            live = [rs for rs in states if not rs.done()]
            if not live:
                return False
            self._batches[ctx] = live
        return True

    def add_ready_to_read(self, ready: List) -> None:
        """Update.ready_to_reads arrived (cf. addReadyToRead)."""
        if not ready:
            return
        with self._mu:
            for r in ready:
                if r.system_ctx in self._batches:
                    self._ready.append((r.system_ctx, r.index))

    def applied(self, applied_index: int) -> None:
        """SM applied up to applied_index: release confirmed reads whose
        read index is covered (cf. requests.go:798-858)."""
        done: List[Tuple[List[RequestState], int]] = []
        with self._mu:
            if not self._ready:
                return
            remaining = []
            for ctx, idx in self._ready:
                if idx <= applied_index:
                    states = self._batches.pop(ctx, [])
                    done.append((states, idx))
                else:
                    remaining.append((ctx, idx))
            self._ready = remaining
        for states, _ in done:
            for rs in states:
                rs.notify(RequestResult(code=REQUEST_COMPLETED))

    def dropped(self, ctx: SystemCtx) -> None:
        with self._mu:
            states = self._batches.pop(ctx, [])
        for rs in states:
            rs.notify(RequestResult(code=REQUEST_DROPPED))

    def close(self) -> None:
        with self._mu:
            self.stopped = True
            states = list(self._queued)
            self._queued = []
            for batch in self._batches.values():
                states.extend(batch)
            self._batches.clear()
            self._ready = []
        for rs in states:
            rs.notify(RequestResult(code=REQUEST_TERMINATED))

    def gc(self) -> None:
        """Sweep expired requests. Unconditional: the caller owns the
        cadence (one should_gc() check per clock window covers every
        Pending* sharing that clock — gating here let the first callee
        consume the window and starve the rest)."""
        now = self._clock.tick
        expired: List[RequestState] = []
        with self._mu:
            keep = []
            for rs in self._queued:
                (expired if rs.deadline < now else keep).append(rs)
            self._queued = keep
            for ctx in list(self._batches):
                batch = self._batches[ctx]
                live = [rs for rs in batch if rs.deadline >= now]
                expired.extend(rs for rs in batch if rs.deadline < now)
                if live:
                    self._batches[ctx] = live
                else:
                    del self._batches[ctx]
                    self._ready = [(c, i) for c, i in self._ready if c != ctx]
        for rs in expired:
            rs.notify(RequestResult(code=REQUEST_TIMEOUT))


class _SingleSlotPending:
    """Base for config-change / snapshot / transfer requests: at most one
    outstanding request per node (cf. pendingConfigChange requests.go:388-393)."""

    def __init__(self, clock: LogicalClock) -> None:
        self._mu = threading.Lock()
        self._clock = clock
        self._pending: Optional[RequestState] = None
        self._key_seq = itertools.count(1)
        self.stopped = False

    def _request(self, timeout_ticks: int) -> RequestState:
        if timeout_ticks < 1:
            raise ErrTimeoutTooSmall()
        rs = RequestState()
        rs.key = next(self._key_seq)
        rs.deadline = self._clock.tick + timeout_ticks
        with self._mu:
            if self.stopped:
                raise ErrClusterClosed()
            if self._pending is not None:
                raise ErrSystemBusy()
            self._pending = rs
        return rs

    def _take(self, key: Optional[int] = None) -> Optional[RequestState]:
        with self._mu:
            rs = self._pending
            if rs is None:
                return None
            if key is not None and rs.key != key:
                return None
            self._pending = None
        return rs

    def close(self) -> None:
        with self._mu:
            self.stopped = True
            rs = self._pending
            self._pending = None
        if rs is not None:
            rs.notify(RequestResult(code=REQUEST_TERMINATED))

    def gc(self) -> None:
        """Sweep expired requests. Unconditional: the caller owns the
        cadence (one should_gc() check per clock window covers every
        Pending* sharing that clock — gating here let the first callee
        consume the window and starve the rest)."""
        now = self._clock.tick
        with self._mu:
            rs = self._pending
            if rs is None or rs.deadline >= now:
                return
            self._pending = None
        rs.notify(RequestResult(code=REQUEST_TIMEOUT))

    def has_pending(self) -> bool:
        return self._pending is not None


class PendingConfigChange(_SingleSlotPending):
    def request(
        self, cc: ConfigChange, timeout_ticks: int
    ) -> Tuple[RequestState, ConfigChange, int]:
        rs = self._request(timeout_ticks)
        return rs, cc, rs.key

    def apply(self, key: int, rejected: bool) -> None:
        rs = self._take(key)
        if rs is not None:
            code = REQUEST_REJECTED if rejected else REQUEST_COMPLETED
            rs.notify(RequestResult(code=code))

    def dropped(self, key: int) -> None:
        rs = self._take(key)
        if rs is not None:
            rs.notify(RequestResult(code=REQUEST_DROPPED))


class PendingSnapshot(_SingleSlotPending):
    def request(self, req, timeout_ticks: int) -> Tuple[RequestState, object]:
        rs = self._request(timeout_ticks)
        return rs, req

    def apply(self, index: int, ignored: bool, failed: bool = False) -> None:
        rs = self._take()
        if rs is None:
            return
        if ignored or failed:
            rs.notify(RequestResult(code=REQUEST_REJECTED))
        else:
            rs.notify(
                RequestResult(code=REQUEST_COMPLETED, snapshot_index=index)
            )


class PendingLeaderTransfer:
    """cf. requests.go:402-431; completion is observed via leadership
    change events rather than an apply callback."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._target: Optional[int] = None

    def request(self, target: int) -> None:
        with self._mu:
            if self._target is not None:
                raise ErrSystemBusy()
            self._target = target

    def get(self) -> Optional[int]:
        with self._mu:
            t = self._target
            self._target = None
            return t

    def peek(self) -> bool:
        return self._target is not None


__all__ = [
    "RequestError",
    "ErrClusterNotFound",
    "ErrClusterNotReady",
    "ErrClusterClosed",
    "ErrTimeout",
    "ErrCanceled",
    "ErrRejected",
    "ErrSystemBusy",
    "ErrMigrationAborted",
    "ErrLeaseExpired",
    "ErrInvalidSession",
    "ErrTimeoutTooSmall",
    "ErrPayloadTooBig",
    "ErrSystemStopped",
    "REQUEST_TIMEOUT",
    "REQUEST_COMPLETED",
    "REQUEST_TERMINATED",
    "REQUEST_REJECTED",
    "REQUEST_DROPPED",
    "RequestResult",
    "RequestState",
    "BatchRequestState",
    "BATCH_KEY_BIT",
    "make_batch_key",
    "batch_id_of",
    "LogicalClock",
    "PendingProposal",
    "PendingReadIndex",
    "PendingConfigChange",
    "PendingSnapshot",
    "PendingLeaderTransfer",
]
