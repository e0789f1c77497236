"""Minimal ordered-KV contract + implementations backing the LogDB.

The reference's LogDB sits on a pluggable IKVStore (RocksDB/LevelDB/Pebble,
cf. internal/logdb/kv/kv.go:28-74). Here the contract is the same shape —
ordered iteration, atomic write batches, range deletes, compaction — with
two built-in stores:

  - MemKV: in-process ordered dict (tests, benchmarks, loopback slices)
  - WalKV: durable append-only WAL + in-memory table; write batches are
    appended and fsynced as one record group sealed by a commit record,
    compaction rewrites the live table to a fresh file with atomic rename
    (crash-safe: a torn or corrupt tail is detected by CRC/framing,
    replay rolls back to the last sealed group and the reopen truncates
    the discarded tail — batches apply atomically or not at all).
    FORMAT NOTE: the commit-seal framing is WAL format v2 (shared with
    native/walkv.cc); v1 files (per-record, no seals) are NOT readable —
    their records replay as one unsealed group and are discarded.

Keys are bytes and compare lexicographically; the key schema (keys.py) uses
big-endian ids so numeric order == byte order.
"""
from __future__ import annotations

import os
import struct
import threading
import time
import zlib
from typing import Callable, Dict, Iterator, List, Optional, Tuple

_REC = struct.Struct("<IBII")  # total_len, op, klen, vlen
_OP_PUT = 0
_OP_DEL = 1
_OP_RANGE_DEL = 2
# group-commit seal: a write batch's records only apply on replay once its
# trailing COMMIT record is intact — a torn tail can no longer surface a
# HALF-applied batch (atomicity of IWriteBatch survives the crash, not
# just individual records)
_OP_COMMIT = 3


class _Wave(threading.local):
    """`parts` is what the save wave that THIS thread is timing has cost
    so far, or None: seconds under "encode" (write batches built),
    "commit" (the stores' commits of them, of which "table" is the
    in-memory table's where the store can tell), "sync" and "sync_cpu"
    (the barrier), and "wal_bytes" and "wal_records" appended. The
    engine loop sets it around a sampled wave (open_wave, close_wave); the write
    path under it adds to it and reads a clock only where it finds one,
    so an unsampled wave pays a few `is None` tests a shard write and a
    barrier wave, and another thread's write to the same store (a
    snapshot worker's, a compaction's) finds None.

    `bodies` is the table of batch-record bodies that the wave on THIS
    thread shares among the logdbs it writes (logdb.RecordBodies), or
    None: the engine loop sets it around every wave that spans co-hosted
    NodeHosts (open_bodies, close_bodies), sampled or not, and reads no
    clock for it."""

    parts: Optional[dict] = None
    bodies: Optional[object] = None


_wave = _Wave()


def open_wave() -> dict:
    """Begin timing the calling thread's save wave: its parts, all zero,
    for the write path under it to add to until close_wave()."""
    parts = _wave.parts = {
        "encode": 0.0, "commit": 0.0, "table": 0.0, "sync": 0.0,
        "sync_cpu": 0.0, "wal_bytes": 0, "wal_records": 0,
        "entries": 0, "entries_shared": 0,
    }
    return parts


def close_wave() -> None:
    _wave.parts = None


def wave_parts() -> Optional[dict]:
    """The parts of the save wave that the calling thread is timing, or
    None: what the write path under open_wave() adds to."""
    return _wave.parts


def open_bodies(bodies) -> None:
    """Share `bodies` among the logdbs that the calling thread's save
    wave writes, until close_bodies()."""
    _wave.bodies = bodies


def close_bodies() -> None:
    _wave.bodies = None


def wave_bodies():
    """The record bodies that the calling thread's save wave shares, or
    None."""
    return _wave.bodies


class WriteBatch:
    """Ordered list of mutations applied atomically
    (cf. kv.go IWriteBatch)."""

    __slots__ = ("ops",)

    def __init__(self) -> None:
        self.ops: List[Tuple[int, bytes, bytes]] = []

    def put(self, key: bytes, value: bytes) -> None:
        self.ops.append((_OP_PUT, key, value))

    def delete(self, key: bytes) -> None:
        self.ops.append((_OP_DEL, key, b""))

    def delete_range(self, start: bytes, end: bytes) -> None:
        self.ops.append((_OP_RANGE_DEL, start, end))

    def clear(self) -> None:
        self.ops.clear()

    def count(self) -> int:
        return len(self.ops)


class IKVStore:
    """cf. internal/logdb/kv/kv.go:28-74."""

    def name(self) -> str:
        raise NotImplementedError

    def close(self) -> None:
        raise NotImplementedError

    def get_value(self, key: bytes) -> Optional[bytes]:
        raise NotImplementedError

    def put_value(self, key: bytes, value: bytes) -> None:
        wb = WriteBatch()
        wb.put(key, value)
        self.commit_write_batch(wb)

    def delete_value(self, key: bytes) -> None:
        wb = WriteBatch()
        wb.delete(key)
        self.commit_write_batch(wb)

    def iterate_value(
        self,
        fk: bytes,
        lk: bytes,
        inc_last: bool,
        op: Callable[[bytes, bytes], bool],
    ) -> None:
        """Visit keys in [fk, lk) or [fk, lk] in order; op returns False to
        stop."""
        raise NotImplementedError

    def commit_write_batch(self, wb: WriteBatch) -> None:
        raise NotImplementedError

    def commit_write_batch_deferred(self, wb: WriteBatch) -> bool:
        """Apply a write batch with its durability barrier DEFERRED to a
        later sync() call. Returns True when the caller owes a sync().

        The group-commit seam for the engine's per-step multi-lane save:
        every touched shard writes its batch first, then all barriers run
        in one parallel wave (sync_all), so a step pays max(fsync) instead
        of sum(fsync). Stores without a separate barrier (this default)
        just commit durably and owe nothing."""
        self.commit_write_batch(wb)
        return False

    def sync(self) -> None:
        """Durability barrier for writes committed via
        commit_write_batch_deferred. No-op unless overridden."""
        return None

    def bulk_remove_entries(self, fk: bytes, lk: bytes) -> None:
        """Range delete [fk, lk)."""
        raise NotImplementedError

    def compact_entries(self, fk: bytes, lk: bytes) -> None:
        """Reclaim space for a removed range; may be a no-op."""
        return None

    def full_compaction(self) -> None:
        return None

    def set_fsync_observer(self, cb: Optional[Callable[[float], None]]) -> None:
        """Install a durability-barrier latency observer: cb(seconds) runs
        after each fsync with its wall duration. Stores without a real
        barrier ignore it (this default)."""
        return None


class _BarrierStats:
    """Process-global durability-barrier pressure gauge: how many real
    fsync barriers are in flight right now (the WAL "fsync queue depth"
    — during a sync_all wave every touched shard counts) and an EWMA of
    barrier wall latency. This is a first-class backpressure SIGNAL (the
    serving front's SaturationMonitor folds it into admission), not just
    telemetry: when the barrier saturates, admission must tighten BEFORE
    the save wave starts stalling the engine step loop. Cost: one small
    lock + a few float ops per fsync — barriers are ms-scale."""

    __slots__ = ("_mu", "ewma_s", "last_s", "last_wave_s", "inflight",
                 "barriers")

    # EWMA smoothing: ~the last 5 barriers dominate, so a single slow
    # outlier neither saturates admission nor hides a real trend
    ALPHA = 0.2

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self.ewma_s = 0.0
        self.last_s = 0.0
        self.last_wave_s = 0.0  # last sync_all wave wall time
        self.inflight = 0
        self.barriers = 0

    def enter(self) -> None:
        with self._mu:
            self.inflight += 1

    def exit(self, seconds: float) -> None:
        with self._mu:
            self.inflight = max(self.inflight - 1, 0)
            self.last_s = seconds
            self.ewma_s = (
                seconds if self.barriers == 0
                else (1 - self.ALPHA) * self.ewma_s + self.ALPHA * seconds
            )
            self.barriers += 1

    def note_wave(self, seconds: float) -> None:
        with self._mu:
            self.last_wave_s = seconds

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "ewma_s": self.ewma_s,
                "last_s": self.last_s,
                "last_wave_s": self.last_wave_s,
                "inflight": self.inflight,
                "barriers": self.barriers,
            }

    def reset(self) -> None:
        with self._mu:
            self.ewma_s = self.last_s = self.last_wave_s = 0.0
            self.inflight = 0
            self.barriers = 0


_barrier_stats = _BarrierStats()


def barrier_stats() -> dict:
    """Snapshot of the process-global WAL-barrier pressure signal:
    {ewma_s, last_s, last_wave_s, inflight, barriers}."""
    return _barrier_stats.snapshot()


def reset_barrier_stats() -> None:
    """Test seam: zero the process-global barrier signal."""
    _barrier_stats.reset()


class MemKV(IKVStore):
    """Ordered in-memory store: dict + lazily sorted key list."""

    def __init__(self) -> None:
        self._d: Dict[bytes, bytes] = {}
        self._sorted: Optional[List[bytes]] = None
        self._mu = threading.RLock()

    def name(self) -> str:
        return "memkv"

    def close(self) -> None:
        pass

    def _keys(self) -> List[bytes]:
        if self._sorted is None:
            self._sorted = sorted(self._d)
        return self._sorted

    def get_value(self, key: bytes) -> Optional[bytes]:
        with self._mu:
            return self._d.get(key)

    def iterate_value(self, fk, lk, inc_last, op) -> None:
        import bisect

        with self._mu:
            keys = self._keys()
            i = bisect.bisect_left(keys, fk)
            while i < len(keys):
                k = keys[i]
                if (inc_last and k > lk) or (not inc_last and k >= lk):
                    break
                if not op(k, self._d[k]):
                    break
                i += 1

    def commit_write_batch(self, wb: WriteBatch) -> None:
        with self._mu:
            for op, k, v in wb.ops:
                if op == _OP_PUT:
                    if k not in self._d:
                        self._sorted = None
                    self._d[k] = v
                elif op == _OP_DEL:
                    if self._d.pop(k, None) is not None:
                        self._sorted = None
                else:
                    self._range_del(k, v)

    def _range_del(self, start: bytes, end: bytes) -> None:
        dead = [k for k in self._d if start <= k < end]
        for k in dead:
            del self._d[k]
        if dead:
            self._sorted = None

    def bulk_remove_entries(self, fk, lk) -> None:
        with self._mu:
            self._range_del(fk, lk)


def _scan_groups(data: bytes, on_group: Callable) -> int:
    """Walk a WAL byte stream group by group; call on_group(ops) at each
    intact _OP_COMMIT seal. Returns the byte offset just past the last
    applied seal.

    The record-group contract (the WAL decoder the fuzz harness drives,
    see fuzz.fuzz_wal_recovery): records accumulate into a pending group;
    only an intact _OP_COMMIT seal applies the group. Any torn, corrupt or
    absurd record (CRC mismatch, short tail, length fields past the
    buffer) ends replay at the last sealed group — recovery NEVER crashes,
    never half-applies a batch, and never accepts a record whose CRC does
    not match.

    The returned sealed offset matters to the writer: it must TRUNCATE
    its WAL there before appending again, or a torn tail would strand
    later writes behind a broken record — or worse, merge stale unsealed
    records into the next batch's group."""
    pending: List[Tuple[int, bytes, bytes]] = []
    off = 0
    sealed = 0
    n = len(data)
    while off + _REC.size <= n:
        total, op, klen, vlen = _REC.unpack_from(data, off)
        end = off + _REC.size + klen + vlen + 4
        if end > n or total != _REC.size + klen + vlen + 4:
            break  # torn tail / corrupt length fields
        (crc,) = struct.unpack_from("<I", data, end - 4)
        if zlib.crc32(data[off : end - 4]) != crc:
            break  # torn/corrupt tail: stop replay here
        if op == _OP_COMMIT:
            if pending:
                on_group(pending)
                pending = []
            sealed = end
        elif op in (_OP_PUT, _OP_DEL, _OP_RANGE_DEL):
            pending.append(
                (
                    op,
                    bytes(data[off + _REC.size : off + _REC.size + klen]),
                    bytes(data[off + _REC.size + klen : end - 4]),
                )
            )
        else:
            break  # unknown op: cannot trust anything past it
        off = end
    # a trailing unsealed group is a crash mid-batch: discarded
    return sealed


def _decode_records(data: bytes) -> Tuple[WriteBatch, int]:
    """Collect every committed op of a WAL stream into one WriteBatch
    (plus the sealed offset). Convenience wrapper over _scan_groups for
    tests/fuzz; the replay path applies groups incrementally instead so a
    large store never holds a second full copy of itself in op form."""
    wb = WriteBatch()
    sealed = _scan_groups(data, lambda ops: wb.ops.extend(ops))
    return wb, sealed


class WalKV(IKVStore):
    """Durable WAL-backed store. All reads served from the in-memory table;
    durability from the fsynced append-only log. Batches are framed as
    record GROUPS sealed by a commit record (_decode_records), so a torn
    tail rolls back to the last intact group on replay."""

    def __init__(self, dirname: str, fsync: bool = True) -> None:
        self._dir = dirname
        self._fsync = fsync
        self._mem = MemKV()
        self._mu = threading.RLock()
        os.makedirs(dirname, exist_ok=True)
        self._path = os.path.join(dirname, "wal.log")
        self._replay()
        self._f = open(self._path, "ab")
        self._since_compact = 0
        # fsync-latency observer (cb(seconds)); None = zero extra work
        self._fsync_observer: Optional[Callable[[float], None]] = None
        # per-record append fault seam (FaultPlane.maybe_append_fault):
        # called before each record write; raising aborts the batch and
        # MUST roll the file back past the half-written group
        self._append_fault: Optional[Callable[[], None]] = None
        # per-store barrier-pressure gauge: one NodeHost's saturation
        # must never shed another co-hosted NodeHost's traffic, so
        # ShardedLogDB.barrier_stats() aggregates THESE per host while
        # the process-global gauge keeps the whole-process picture
        self.bstats = _BarrierStats()

    def set_fsync_observer(self, cb: Optional[Callable[[float], None]]) -> None:
        self._fsync_observer = cb

    def set_append_fault(self, cb: Optional[Callable[[], None]]) -> None:
        self._append_fault = cb

    def _barrier(self) -> None:
        """The durability barrier: always timed into the process-global
        barrier-pressure signal (backpressure for admission control) and
        additionally reported to the histogram observer when installed."""
        obs = self._fsync_observer
        _barrier_stats.enter()
        self.bstats.enter()
        t0 = time.monotonic()
        try:
            os.fsync(self._f.fileno())
        finally:
            dt = time.monotonic() - t0
            _barrier_stats.exit(dt)
            self.bstats.exit(dt)
        if obs is not None:
            obs(dt)

    def name(self) -> str:
        return "walkv"

    # -- recovery ------------------------------------------------------------
    def _replay(self) -> None:
        compacted = os.path.join(self._dir, "table.log")
        for path in (compacted, self._path):
            if not os.path.exists(path):
                continue
            with open(path, "rb") as f:
                data = f.read()

            def apply_group(ops) -> None:
                gwb = WriteBatch()
                gwb.ops = ops
                self._mem.commit_write_batch(gwb)

            sealed = _scan_groups(data, apply_group)
            if path == self._path and sealed < len(data):
                # chop the discarded tail (torn group / corrupt record)
                # BEFORE the append fd opens: appending after a broken
                # record would strand the new writes behind it, and
                # appending after intact-but-unsealed records would merge
                # them into the next batch's sealed group (resurrecting a
                # rolled-back batch)
                with open(path, "r+b") as f:
                    f.truncate(sealed)

    # -- reads ---------------------------------------------------------------
    def get_value(self, key):
        return self._mem.get_value(key)

    def iterate_value(self, fk, lk, inc_last, op):
        self._mem.iterate_value(fk, lk, inc_last, op)

    # -- writes --------------------------------------------------------------
    def _append_rec(self, op: int, k: bytes, v: bytes) -> None:
        rec = _REC.pack(_REC.size + len(k) + len(v) + 4, op, len(k), len(v)) + k + v
        self._f.write(rec + struct.pack("<I", zlib.crc32(rec)))

    def _append_group(self, wb: WriteBatch) -> int:
        """Append wb's records + the commit seal as one group; on ANY
        append failure roll the file back to the pre-group offset before
        re-raising. Without the rollback the unsealed records would sit
        at the tail and the NEXT batch's seal would merge them into its
        group — resurrecting a batch the caller was told failed. Caller
        holds self._mu. Returns the offset the group begins at."""
        start = self._f.tell()
        try:
            fault = self._append_fault
            for op, k, v in wb.ops:
                if fault is not None:
                    fault()
                self._append_rec(op, k, v)
            self._append_rec(_OP_COMMIT, b"", b"")  # seal the group
            self._f.flush()
        except BaseException:
            try:
                self._f.flush()
                self._f.truncate(start)
            except Exception:
                # the unwind itself failed (e.g. the flush hit the same
                # disk error): reopen and truncate via a fresh descriptor
                # so no half-written group survives this fd's buffer
                try:
                    self._f.close()
                except Exception:
                    pass
                with open(self._path, "r+b") as f:
                    f.truncate(start)
                self._f = open(self._path, "ab")
            raise
        return start

    def commit_write_batch(self, wb: WriteBatch) -> None:
        with self._mu:
            self._append_group(wb)
            if self._fsync:
                self._barrier()
            self._mem.commit_write_batch(wb)
            self._since_compact += len(wb.ops)

    def commit_write_batch_deferred(self, wb: WriteBatch) -> bool:
        """Append + flush the batch but leave the fsync to sync(): the
        caller groups barriers across shards into one parallel wave. The
        batch is NOT durable until that sync() returns. Inside a save
        wave that its thread is timing (_Wave) it also says what the
        group put on the file and how long the table took."""
        parts = wave_parts()
        with self._mu:
            start = self._append_group(wb)
            if parts is not None:
                parts["wal_bytes"] += self._f.tell() - start
                parts["wal_records"] += len(wb.ops) + 1
                t0 = time.monotonic()
            self._mem.commit_write_batch(wb)
            self._since_compact += len(wb.ops)
            if parts is not None:
                parts["table"] += time.monotonic() - t0
        return self._fsync

    def sync(self) -> None:
        if not self._fsync:
            return
        with self._mu:
            if not self._f.closed:
                self._barrier()

    def bulk_remove_entries(self, fk, lk) -> None:
        wb = WriteBatch()
        wb.delete_range(fk, lk)
        self.commit_write_batch(wb)

    def compact_entries(self, fk, lk) -> None:
        with self._mu:
            if self._since_compact < 100000:
                return
            self.full_compaction()

    def full_compaction(self) -> None:
        """Rewrite the live table into table.log, truncate the WAL
        (crash-safe via tmp+rename: the WAL is only truncated after the
        compacted table is durable)."""
        with self._mu:
            tmp = os.path.join(self._dir, "table.log.tmp")
            final = os.path.join(self._dir, "table.log")
            with open(tmp, "wb") as f:
                items: List[Tuple[bytes, bytes]] = []
                self._mem.iterate_value(
                    b"", b"\xff" * 64, True, lambda k, v: (items.append((k, v)), True)[1]
                )
                seal = _REC.pack(_REC.size + 4, _OP_COMMIT, 0, 0)
                seal += struct.pack("<I", zlib.crc32(seal))
                # seal in chunks, not one table-sized group: replay
                # buffers a group before applying, so one giant group
                # would double peak memory at startup (the tmp+rename
                # already makes the whole file all-or-nothing)
                for i, (k, v) in enumerate(items):
                    rec = _REC.pack(_REC.size + len(k) + len(v) + 4, _OP_PUT, len(k), len(v)) + k + v
                    f.write(rec + struct.pack("<I", zlib.crc32(rec)))
                    if (i + 1) % 1024 == 0:
                        f.write(seal)
                f.write(seal)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, final)
            self._f.close()
            self._f = open(self._path, "wb")
            if self._fsync:
                os.fsync(self._f.fileno())
            self._since_compact = 0

    def close(self) -> None:
        with self._mu:
            if self._f.closed:
                return  # idempotent (stop paths can race teardown)
            try:
                self._f.flush()
                if self._fsync:
                    os.fsync(self._f.fileno())
            finally:
                self._f.close()

    def close_crashed(self) -> None:
        """Crash-teardown close (NodeHost.crash): release the fd WITHOUT
        the final durability barrier — a deferred-commit batch whose
        sync() never ran must be allowed to die exactly as a SIGKILL
        would kill it, or chaos restarts silently grant durability the
        real power cut never grants. FaultPlane.tear_wal_tails can then
        chop a torn mid-write tail off the closed file."""
        with self._mu:
            if not self._f.closed:
                self._f.close()


# shared barrier pool for sync_all: fsync releases the GIL, so syncing N
# shard WALs concurrently costs ~max(fsync) wall time instead of the sum.
# Lazily created; sized for IO concurrency, not core count.
_sync_pool = None
_sync_pool_mu = threading.Lock()


def _get_sync_pool():
    global _sync_pool
    if _sync_pool is None:
        with _sync_pool_mu:
            if _sync_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                # sized to cover a full default save wave in ONE round:
                # hard.logdb_pool_size shards per logdb, and a shared core
                # can sync several co-hosted logdbs in the same barrier.
                # fsync threads are IO-parked, not CPU contenders.
                from ..settings import hard

                _sync_pool = ThreadPoolExecutor(
                    max_workers=max(2 * hard.logdb_pool_size, 8),
                    thread_name_prefix="kv-sync",
                )
    return _sync_pool


def sync_all(kvs) -> None:
    """One durability barrier over many stores: fsync every store in
    parallel and return once ALL are durable (the group-commit half of
    commit_write_batch_deferred). Raises the first failure after every
    sync has settled — a failed barrier must not report durable. The
    wave's wall time lands in the barrier-pressure signal
    (barrier_stats) alongside the per-fsync depth/latency the member
    barriers record themselves."""
    unique = list(dict.fromkeys(kvs))
    if not unique:
        return
    parts = wave_parts()
    if parts is not None:  # the barrier of a save wave its thread is timing
        parts["sync_cpu"] -= time.thread_time()
    t0 = time.monotonic()
    try:
        if len(unique) == 1:
            unique[0].sync()
            return
        pool = _get_sync_pool()
        futures = [pool.submit(kv.sync) for kv in unique]
        first_exc = None
        for f in futures:
            try:
                f.result()
            except Exception as e:  # noqa: BLE001 - re-raised below
                if first_exc is None:
                    first_exc = e
        if first_exc is not None:
            raise first_exc
    finally:
        dt = time.monotonic() - t0
        if parts is not None:
            parts["sync"] += dt
            parts["sync_cpu"] += time.thread_time()
        _barrier_stats.note_wave(dt)
        for kv in unique:  # one wave = one host's save fan-out
            bs = getattr(kv, "bstats", None)
            if bs is not None:
                bs.note_wave(dt)


__all__ = [
    "IKVStore",
    "WriteBatch",
    "MemKV",
    "WalKV",
    "barrier_stats",
    "reset_barrier_stats",
    "close_bodies",
    "close_wave",
    "open_bodies",
    "open_wave",
    "sync_all",
    "wave_bodies",
    "wave_parts",
]
