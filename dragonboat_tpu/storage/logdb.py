"""Sharded LogDB: the raftio.ILogDB implementation.

Mirrors the reference's ShardedRDB/rdb pair (internal/logdb/sharded_rdb.go,
rdb.go): N independent KV shards partitioned by cluster_id, each update
batch written as ONE atomic write-batch commit (entries + state + maxIndex
together, cf. rdb.go:183-206), so the engine's whole-worker `SaveRaftState`
is a single fsync per step per shard.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

from .. import codec
from ..raftio import (
    ErrNoBootstrapInfo,
    ErrNoSavedLog,
    ILogDB,
    NodeInfo,
    RaftState,
)
from ..settings import hard
from ..types import Bootstrap, Entry, Snapshot, State, Update
from . import keys
from .kv import (
    IKVStore, MemKV, WalKV, WriteBatch, sync_all, wave_bodies, wave_parts,
)


class RecordBodies:
    """What the batch records that the engine loop's save waves have made
    hold, for the co-hosted replicas of a group to take instead of
    encoding the same Entry objects again: for every slice of a run (at
    most BATCH consecutive entries, cut at multiples of BATCH) the value
    of the record that holds just that slice, count and joined
    encodings, as made in the wave under way (`now`) or in the wave
    before it (`old`; in the one-step loop a follower saves one launch
    after its leader). It is the bytes object itself that is shared, so
    a replica that takes it allocates nothing and the stores' tables
    hold one copy a group, not one a replica. A value is found by the
    identity of its slice's first and last Entry. The row keeps both
    alive, so that neither id can be reused while it stands, and the key
    also holds the index and term of both ends as they were, so an entry
    that was placed again since does not match: Raft's log matching
    makes what lies between equal too. Nothing outlives the second wave;
    it belongs to one thread."""

    __slots__ = ("now", "old")

    def __init__(self) -> None:
        self.now: dict = {}
        self.old: dict = {}

    def __len__(self) -> int:
        return len(self.now) + len(self.old)

    def turn(self) -> None:
        """A wave opens: the older wave's bodies go."""
        if self.now or self.old:
            self.old, self.now = self.now, {}

    def clear(self) -> None:
        self.now, self.old = {}, {}


class _Shard:
    """One KV shard with the full key-schema CRUD
    (cf. internal/logdb/rdb.go:47-52). Entries use the BATCHED layout
    (cf. internal/logdb/batch.go:60-390): one key per fixed run of
    consecutive indexes, so the engine's per-step save writes
    O(entries/batch) kv records instead of O(entries), with a last-batch
    cache avoiding the read-modify-write on the append hot path
    (cf. rdbcache.go last-EntryBatch cache)."""

    BATCH = hard.logdb_entry_batch_size

    def __init__(self, kv: IKVStore) -> None:
        self.kv = kv
        # dedup caches for unchanged State/maxIndex writes
        # (cf. internal/logdb/rdbcache.go:24-116)
        self._state_cache = {}
        self._max_index_cache = {}
        # (cid, nid) -> (batch_id, entries of that batch as last written,
        # the record's value; None once a compaction has cut the record)
        self._batch_cache = {}
        self._mu = threading.Lock()
        # writer lock: the append path's boundary-batch read-modify-write
        # (+ its kv commit) and remove_entries_to's boundary rewrite
        # mutate the SAME tail batch record from different threads (step
        # worker vs snapshot worker). Without mutual exclusion the
        # compaction can read the record, lose the race to a tail append,
        # and write the pre-append content back — silently DELETING the
        # just-appended entries (observed as a log hole at restart:
        # replay stalls at the hole with commit far ahead).
        self._wmu = threading.Lock()

    # -- save path -----------------------------------------------------------
    def save_raft_state(self, updates: Sequence[Update]) -> None:
        with self._wmu:
            wb = WriteBatch()
            for ud in updates:
                self._record_update(wb, ud)
            if wb.count() > 0:
                self.kv.commit_write_batch(wb)

    def save_raft_state_deferred(self, updates: Sequence[Update]):
        """Write one batch for `updates` with the durability barrier
        deferred; returns the kv store owing a sync(), or None when
        nothing was written (or the store needs no separate barrier).
        Inside a save wave that its thread is timing (kv._Wave) the
        encode into the write batch is told apart from the store's
        commit of it."""
        with self._wmu:
            parts = wave_parts()
            bodies = wave_bodies()
            if parts is not None:
                t0 = time.monotonic()
            wb = WriteBatch()
            for ud in updates:
                self._record_update(wb, ud, bodies, parts)
            if parts is not None:
                t1 = time.monotonic()
                parts["encode"] += t1 - t0
            owes = wb.count() > 0 and self.kv.commit_write_batch_deferred(wb)
            if parts is not None:
                parts["commit"] += time.monotonic() - t1
            return self.kv if owes else None

    def _retained(self, cid: int, nid: int, bid: int, first: int):
        """What a run that starts mid-record at `first` keeps of record
        `bid`: the entries below `first` and their joined encodings (the
        merge rules of batch.go:60-126). Appending to the record that
        the cache holds whole takes them from the cached value; a
        rewrite from inside it, a cache that a compaction cut, and a
        cold cache read back from the store encode the kept entries one
        by one."""
        with self._mu:
            cached = self._batch_cache.get((cid, nid))
        if cached is not None and cached[0] == bid:
            existing, value = cached[1], cached[2]
        else:
            raw = self.kv.get_value(keys.batch_key(cid, nid, bid))
            existing = codec.decode_entries(raw)[0] if raw else []
            value = None
        keep = 0
        for e in existing:  # ascending; retained prefix is e.index < first
            if e.index >= first:
                break
            keep += 1
        if keep == len(existing) and value is not None:
            return existing, codec.encoded_entries_body(value)
        cur = existing[:keep]
        return cur, b"".join([codec.encode_entry(e) for e in cur])

    def _save_entries(
        self, wb: WriteBatch, cid: int, nid: int, ents, bodies=None, parts=None,
    ) -> None:
        """Pack a run of entries into batch records, merging the head
        record with any retained prefix. The run is walked by record
        slice (at most BATCH consecutive entries, cut at multiples of
        BATCH), and a record's value is taken from the wave's shared
        bodies where a co-hosted replica of the group has already made
        it from the same Entry objects: the value of a batch record is
        the same for every replica, only its key differs. The cache
        keeps the tail record's entries with its value, so appending to
        it re-encodes nothing. `bodies` and `parts` are the wave's own
        (kv._Wave), where the caller writes inside one."""
        B = self.BATCH
        first = ents[0].index
        n = len(ents)
        bid = first // B
        cur, body = self._retained(cid, nid, bid, first) if first % B else ([], b"")
        if ents[-1].index - first + 1 != n:
            self._save_entries_walk(wb, cid, nid, ents, bid, cur, body)
            if parts is not None:
                parts["entries"] += n
            return
        enc = codec.encode_entry
        frame = codec.frame_encoded_entries
        bkey = keys.batch_key
        put = wb.put
        if bodies is not None:
            now, old = bodies.now, bodies.old
        taken = 0
        lo, hi = 0, B - first % B
        while True:
            if hi > n:
                hi = n
            row = key = None
            count = len(cur) + hi - lo
            if bodies is not None:
                # the record's own ends: a retained prefix is the Entry
                # objects this replica saved before, its co-hosted
                # peers' too
                a, z = cur[0] if cur else ents[lo], ents[hi - 1]
                if z.index - a.index + 1 == count:
                    key = (id(a), id(z), a.index, a.term, z.index, z.term)
                    row = now.get(key) or old.get(key)
            if row is None:
                value = frame(count, body, *[enc(e) for e in ents[lo:hi]])
                if key is not None:
                    now[key] = (value, a, z)
            else:
                value = row[0]
                taken += hi - lo
            put(bkey(cid, nid, bid), value)
            if hi == n:
                break
            bid += 1
            cur, body = [], b""
            lo, hi = hi, hi + B
        with self._mu:
            self._batch_cache[(cid, nid)] = (bid, cur + ents[lo:hi], value)
        if parts is not None:
            parts["entries"] += n
            parts["entries_shared"] += taken

    def _save_entries_walk(self, wb, cid, nid, ents, bid, cur, body) -> None:
        """The per-entry walk, for a run whose indexes are not
        consecutive (no caller in the package hands one in): every entry
        goes to the record of its own index, and nothing is shared."""
        B = self.BATCH
        enc = codec.encode_entry
        cur = list(cur)
        pieces = [body]
        for e in ents:
            b = e.index // B
            if b != bid:
                wb.put(
                    keys.batch_key(cid, nid, bid),
                    codec.frame_encoded_entries(len(cur), *pieces),
                )
                bid, cur, pieces = b, [], []
            cur.append(e)
            pieces.append(enc(e))
        value = codec.frame_encoded_entries(len(cur), *pieces)
        wb.put(keys.batch_key(cid, nid, bid), value)
        with self._mu:
            self._batch_cache[(cid, nid)] = (bid, cur, value)

    def _record_update(
        self, wb: WriteBatch, ud: Update, bodies=None, parts=None
    ) -> None:
        cid, nid = ud.cluster_id, ud.node_id
        if ud.entries_to_save:
            self._save_entries(wb, cid, nid, ud.entries_to_save, bodies, parts)
            last = ud.entries_to_save[-1].index
            self._set_max_index(wb, cid, nid, last)
        if ud.snapshot is not None and not ud.snapshot.is_empty():
            wb.put(
                keys.snapshot_key(cid, nid, ud.snapshot.index),
                codec.encode_snapshot(ud.snapshot),
            )
        if not ud.state.is_empty():
            with self._mu:
                cached = self._state_cache.get((cid, nid))
                if cached != (ud.state.term, ud.state.vote, ud.state.commit):
                    self._state_cache[(cid, nid)] = (
                        ud.state.term,
                        ud.state.vote,
                        ud.state.commit,
                    )
                    wb.put(keys.state_key(cid, nid), codec.encode_state(ud.state))

    def _set_max_index(self, wb: WriteBatch, cid: int, nid: int, index: int) -> None:
        with self._mu:
            if self._max_index_cache.get((cid, nid)) == index:
                return
            self._max_index_cache[(cid, nid)] = index
        wb.put(keys.max_index_key(cid, nid), index.to_bytes(8, "big"))

    # -- read path -----------------------------------------------------------
    def read_state(self, cid: int, nid: int) -> Optional[State]:
        raw = self.kv.get_value(keys.state_key(cid, nid))
        if raw is None:
            return None
        st, _ = codec.decode_state(raw)
        return st

    def read_max_index(self, cid: int, nid: int) -> Optional[int]:
        raw = self.kv.get_value(keys.max_index_key(cid, nid))
        if raw is None:
            return None
        return int.from_bytes(raw, "big")

    def iterate_entries(
        self, cid: int, nid: int, low: int, high: int, max_size: int
    ) -> Tuple[List[Entry], int]:
        if high <= low:
            return [], 0
        B = self.BATCH
        fk, lk = keys.batch_range(cid, nid, low // B, (high - 1) // B + 1)
        out: List[Entry] = []
        size = 0
        expected = low

        def visit(k: bytes, v: bytes) -> bool:
            nonlocal size, expected
            batch, _ = codec.decode_entries(v)
            for e in batch:
                if e.index < expected or e.index >= high:
                    continue  # boundary batch: entries outside the window
                if e.index != expected:
                    return False  # hole: compacted below or beyond max
                out.append(e)
                expected += 1
                size += len(e.cmd) + 48
                if size > max_size:
                    return False
            return True

        self.kv.iterate_value(fk, lk, False, visit)
        return out, size

    def remove_entries_to(self, cid: int, nid: int, index: int) -> None:
        B = self.BATCH
        cut_bid = (index + 1) // B
        fk, lk = keys.batch_range(cid, nid, 0, cut_bid)
        self.kv.bulk_remove_entries(fk, lk)
        # the boundary batch straddles the cut: rewrite it with only the
        # surviving tail so removed indexes never resurface through a
        # direct iterate (the ILogDB contract; cf. batch.go:312-340).
        # The rewrite runs under the shard writer lock: it is a
        # read-modify-write of the record the append path may be extending
        # right now — an unserialized rewrite can write the pre-append
        # content back and DELETE freshly appended entries (the log-hole
        # bug guarded by tests/test_storage.py::
        # test_compaction_append_race_keeps_tail_entries)
        with self._wmu:
            bk = keys.batch_key(cid, nid, cut_bid)
            raw = self.kv.get_value(bk)
            if raw:
                batch, _ = codec.decode_entries(raw)
                keep = [e for e in batch if e.index > index]
                if len(keep) != len(batch):
                    if keep:
                        self.kv.put_value(bk, codec.encode_entries(keep))
                    else:
                        self.kv.delete_value(bk)
                    with self._mu:
                        cached = self._batch_cache.get((cid, nid))
                        if cached is not None and cached[0] == cut_bid:
                            self._batch_cache[(cid, nid)] = (
                                cut_bid, keep, None
                            )

    def compact_entries_to(self, cid: int, nid: int, index: int) -> None:
        fk, lk = keys.batch_range(cid, nid, 0, (index + 1) // self.BATCH)
        self.kv.compact_entries(fk, lk)

    def remove_node_data(self, cid: int, nid: int) -> None:
        wb = WriteBatch()
        fk, lk = keys.batch_range(cid, nid, 0, 2**62)
        wb.delete_range(fk, lk)
        sfk, slk = keys.snapshot_range(cid, nid, 0, 2**63)
        wb.delete_range(sfk, slk)
        wb.delete(keys.state_key(cid, nid))
        wb.delete(keys.max_index_key(cid, nid))
        wb.delete(keys.bootstrap_key(cid, nid))
        self.kv.commit_write_batch(wb)
        with self._mu:
            self._state_cache.pop((cid, nid), None)
            self._max_index_cache.pop((cid, nid), None)
            self._batch_cache.pop((cid, nid), None)


class ShardedLogDB(ILogDB):
    """cf. internal/logdb/sharded_rdb.go:38-114."""

    def __init__(
        self,
        dirname: str = "",
        num_shards: Optional[int] = None,
        fsync: bool = True,
        kv_factory: Optional[Callable[[str], IKVStore]] = None,
    ) -> None:
        self._num = num_shards or hard.logdb_pool_size
        self._shards: List[_Shard] = []
        self._dir = dirname
        for i in range(self._num):
            if kv_factory is not None:
                kv = kv_factory(os.path.join(dirname, f"shard-{i}") if dirname else "")
            elif dirname:
                kv = WalKV(os.path.join(dirname, f"shard-{i}"), fsync=fsync)
            else:
                kv = MemKV()
            self._shards.append(_Shard(kv))

    def _shard(self, cluster_id: int) -> _Shard:
        return self._shards[cluster_id % self._num]

    def name(self) -> str:
        return "sharded-" + self._shards[0].kv.name()

    def set_fsync_observer(self, cb) -> None:
        """Install a durability-barrier latency observer (cb(seconds)) on
        every shard store — NodeHost feeds it into its
        fsync_latency_seconds histogram."""
        for s in self._shards:
            set_obs = getattr(s.kv, "set_fsync_observer", None)
            if set_obs is not None:
                set_obs(cb)

    def barrier_stats(self) -> dict:
        """THIS logdb's durability-barrier pressure, aggregated across
        shard stores (serving.backpressure probes it so one host's fsync
        saturation never sheds a co-hosted NodeHost's traffic).
        Bottleneck semantics: latencies are the MAX across shards;
        in-flight barriers SUM (a sync_all wave fsyncs many shards at
        once — the depth IS the wave width). Memory-backed shards
        contribute nothing."""
        out = {
            "ewma_s": 0.0, "last_s": 0.0, "last_wave_s": 0.0,
            "inflight": 0, "barriers": 0,
        }
        for s in self._shards:
            bs = getattr(s.kv, "bstats", None)
            if bs is None:
                continue
            snap = bs.snapshot()
            out["ewma_s"] = max(out["ewma_s"], snap["ewma_s"])
            out["last_s"] = max(out["last_s"], snap["last_s"])
            out["last_wave_s"] = max(
                out["last_wave_s"], snap["last_wave_s"]
            )
            out["inflight"] += snap["inflight"]
            out["barriers"] += snap["barriers"]
        return out

    def close(self) -> None:
        for s in self._shards:
            s.kv.close()

    def close_crashed(self) -> None:
        """Crash-teardown close (NodeHost.crash): every shard store that
        can skip its final durability barrier does (WalKV.close_crashed);
        the rest close normally."""
        for s in self._shards:
            cc = getattr(s.kv, "close_crashed", None)
            (cc if cc is not None else s.kv.close)()

    def shard_dirs(self) -> List[str]:
        """On-disk shard directories (empty for in-memory stores) — the
        sweep surface for FaultPlane.tear_wal_tails after a crash."""
        if not self._dir:
            return []
        return [
            os.path.join(self._dir, f"shard-{i}") for i in range(self._num)
        ]

    # -- bootstrap -----------------------------------------------------------
    def save_bootstrap_info(self, cluster_id, node_id, bootstrap) -> None:
        self._shard(cluster_id).kv.put_value(
            keys.bootstrap_key(cluster_id, node_id),
            codec.encode_bootstrap(bootstrap),
        )

    def save_bootstrap_infos(self, items) -> None:
        """One atomic fsynced write-batch per shard — fleet bring-up pays
        one fsync per shard, not one per cluster (the per-cluster fsync
        was 2/3 of the measured 50k-group start cost)."""
        by_shard = {}
        for cid, nid, b in items:
            wb = by_shard.get(cid % self._num)
            if wb is None:
                wb = by_shard[cid % self._num] = WriteBatch()
            wb.put(keys.bootstrap_key(cid, nid), codec.encode_bootstrap(b))
        for sid, wb in by_shard.items():
            self._shards[sid].kv.commit_write_batch(wb)

    def get_bootstrap_info(self, cluster_id, node_id):
        raw = self._shard(cluster_id).kv.get_value(
            keys.bootstrap_key(cluster_id, node_id)
        )
        if raw is None:
            raise ErrNoBootstrapInfo()
        b, _ = codec.decode_bootstrap(raw)
        return b

    def list_node_info(self) -> List[NodeInfo]:
        out: List[NodeInfo] = []
        for s in self._shards:
            def visit(k: bytes, v: bytes) -> bool:
                cid, nid = keys.parse_node_key(k)
                out.append(NodeInfo(cluster_id=cid, node_id=nid))
                return True

            s.kv.iterate_value(b"b", b"c", False, visit)
        return out

    # -- raft state ------------------------------------------------------------
    def save_raft_state(self, updates: Sequence[Update], shard_id: int = 0) -> None:
        """Multi-lane save: ONE atomic write-batch per touched shard, then
        one parallel group-commit barrier over all of them (the engine
        hands every lane's per-step save through this single call)."""
        sync_all(self.save_raft_state_deferred(updates))

    def save_raft_state_deferred(self, updates: Sequence[Update]) -> list:
        """Write one batch per touched shard with the durability barrier
        deferred; returns the kv stores owing a sync (sync_all them). Lets
        the engine group-commit saves spanning SEVERAL logdbs (a shared
        core hosts lanes from many NodeHosts) in one barrier wave."""
        by_shard = {}
        for ud in updates:
            by_shard.setdefault(ud.cluster_id % self._num, []).append(ud)
        pending = []
        for sid, uds in by_shard.items():
            kv = self._shards[sid].save_raft_state_deferred(uds)
            if kv is not None:
                pending.append(kv)
        return pending

    def read_raft_state(self, cluster_id, node_id, last_index) -> RaftState:
        sh = self._shard(cluster_id)
        st = sh.read_state(cluster_id, node_id)
        if st is None:
            raise ErrNoSavedLog()
        max_index = sh.read_max_index(cluster_id, node_id)
        first, length = self._entry_range(sh, cluster_id, node_id, last_index, max_index)
        return RaftState(state=st, first_index=first, entry_count=length)

    def _entry_range(self, sh, cid, nid, snapshot_index, max_index):
        """(first_index, count) of contiguous entries after snapshot_index
        (cf. rdb.go getRange)."""
        if max_index is None:
            return snapshot_index, 0
        low = snapshot_index + 1
        first = None
        B = sh.BATCH

        def visit(k: bytes, v: bytes) -> bool:
            nonlocal first
            batch, _ = codec.decode_entries(v)
            for e in batch:
                if e.index >= low:
                    first = e.index
                    return False
            return True

        fk, lk = keys.batch_range(cid, nid, low // B, 2**62)
        sh.kv.iterate_value(fk, lk, False, visit)
        if first is None or max_index < first:
            return snapshot_index, 0
        return first, max_index - first + 1

    def iterate_entries(self, cluster_id, node_id, low, high, max_size):
        return self._shard(cluster_id).iterate_entries(
            cluster_id, node_id, low, high, max_size
        )

    def remove_entries_to(self, cluster_id, node_id, index) -> None:
        self._shard(cluster_id).remove_entries_to(cluster_id, node_id, index)

    def compact_entries_to(self, cluster_id, node_id, index) -> None:
        self._shard(cluster_id).compact_entries_to(cluster_id, node_id, index)

    # -- snapshots -------------------------------------------------------------
    def save_snapshots(self, updates: Sequence[Update]) -> None:
        for ud in updates:
            if ud.snapshot is None or ud.snapshot.is_empty():
                continue
            self._shard(ud.cluster_id).kv.put_value(
                keys.snapshot_key(ud.cluster_id, ud.node_id, ud.snapshot.index),
                codec.encode_snapshot(ud.snapshot),
            )

    def delete_snapshot(self, cluster_id, node_id, index) -> None:
        self._shard(cluster_id).kv.delete_value(
            keys.snapshot_key(cluster_id, node_id, index)
        )

    def list_snapshots(self, cluster_id, node_id, index) -> List[Snapshot]:
        out: List[Snapshot] = []

        def visit(k: bytes, v: bytes) -> bool:
            ss, _ = codec.decode_snapshot(v)
            out.append(ss)
            return True

        fk, lk = keys.snapshot_range(cluster_id, node_id, 0, index + 1)
        self._shard(cluster_id).kv.iterate_value(fk, lk, False, visit)
        return out

    def remove_node_data(self, cluster_id, node_id) -> None:
        self._shard(cluster_id).remove_node_data(cluster_id, node_id)

    def import_snapshot(self, ss: Snapshot, node_id: int) -> None:
        """Overwrite all state with the imported snapshot record
        (cf. rdb.go:208-233 importSnapshot)."""
        cid = ss.cluster_id
        sh = self._shard(cid)
        # delete old snapshots + entries, write new bootstrap (join mode,
        # like the reference's importSnapshot) + state + snapshot record
        wb = WriteBatch()
        fk, lk = keys.snapshot_range(cid, node_id, 0, 2**63)
        wb.delete_range(fk, lk)
        efk, elk = keys.batch_range(cid, node_id, 0, 2**62)
        wb.delete_range(efk, elk)
        bootstrap = Bootstrap(join=True, type=ss.type)
        wb.put(
            keys.bootstrap_key(cid, node_id), codec.encode_bootstrap(bootstrap)
        )
        st = State(term=ss.term, commit=ss.index)
        wb.put(keys.state_key(cid, node_id), codec.encode_state(st))
        wb.put(keys.max_index_key(cid, node_id), ss.index.to_bytes(8, "big"))
        wb.put(keys.snapshot_key(cid, node_id, ss.index), codec.encode_snapshot(ss))
        sh.kv.commit_write_batch(wb)
        with sh._mu:
            sh._state_cache.pop((cid, node_id), None)
            sh._max_index_cache[(cid, node_id)] = ss.index


__all__ = ["RecordBodies", "ShardedLogDB"]
