"""Inbound snapshot chunk reassembly with offset-resumable streams.

cf. internal/transport/chunks.go:67-347 — tracks in-flight snapshot
streams, writes chunks into a .receiving temp dir, validates the assembled
file, atomically finalizes it into the node's snapshot directory, and
converts the completed stream into an InstallSnapshot message delivered
through the normal receive path.

Resume protocol (no referent in the reference, which restarts aborted
streams from scratch): after every persisted chunk the tracker records a
progress file (`stream-progress.json`, atomic replace) next to the data.
When a RETRY of the same stream begins — the sender always restarts at
chunk 0; raft's snapshot-status feedback drives the retry — chunks the
progress record already covers are verified and SKIPPED without touching
disk, and writing resumes at the recorded offset (the in-progress file is
first truncated to the recorded durable size, so a torn tail from a
mid-write crash can never duplicate bytes). A receiver host crash
(NodeHost.crash) therefore costs at most one chunk of rewritten data, and
the `.receiving` dir survives process death because it lives under the
durable snapshot root.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Dict, Optional, Tuple

from ..rsm.snapshotio import validate_snapshot_file
from ..trace import flight_recorder
from ..types import Message, MessageBatch, MessageType, Snapshot, SnapshotChunk
from ..settings import soft

_PROGRESS_FILE = "stream-progress.json"


class _Track:
    __slots__ = (
        "first", "next_chunk", "f", "tmp_dir", "final_dir", "files", "tick",
        "skip_until",
    )

    def __init__(self, first: SnapshotChunk, tmp_dir: str, final_dir: str) -> None:
        self.first = first
        self.next_chunk = 1
        self.tmp_dir = tmp_dir
        self.final_dir = final_dir
        self.f = None
        self.files = []  # (file_info, local_path)
        self.tick = 0
        # resume fence: chunk ids below this are already durable from a
        # previous attempt of the SAME stream — verified and skipped
        self.skip_until = 0


class Chunks:
    """cf. Chunks internal/transport/chunks.go:67-98."""

    def __init__(self, nodehost) -> None:
        self._nh = nodehost
        self._mu = threading.Lock()
        self._tracked: Dict[Tuple[int, int, int], _Track] = {}
        self._tick = 0
        # stream-plane counters (read by tests/verdicts; ints under _mu)
        self._resumed_streams = 0
        self._skipped_chunks = 0
        self._aborted_streams = 0
        self._completed_streams = 0
        # install streams that began while their cluster was marked
        # mid live-migration on this host (NodeHost.mark_migrating, set
        # by serving/placement.py on both ends of a member swap): the
        # counter that lets the longhaul ledger tell migration
        # install traffic from ordinary crash-rejoin catch-up
        self._migration_streams = 0

    def _key(self, c: SnapshotChunk) -> Tuple[int, int, int]:
        return (c.cluster_id, c.node_id, c.from_)

    def stats(self) -> dict:
        with self._mu:
            return {
                "resumed_streams": self._resumed_streams,
                "skipped_chunks": self._skipped_chunks,
                "aborted_streams": self._aborted_streams,
                "completed_streams": self._completed_streams,
                "migration_streams": self._migration_streams,
            }

    # ------------------------------------------------------------------ entry
    def add_chunk(self, c: SnapshotChunk) -> bool:
        """Returns False to reject the stream (cf. addChunk
        chunks.go:227-282)."""
        with self._mu:
            key = self._key(c)
            t = self._tracked.get(key)
            if c.chunk_id == 0:
                if t is not None:
                    self._drop_locked(key, reason="restarted")
                t = self._begin_locked(c)
                if t is None:
                    return False
                # migration tagging: is_migrating takes NodeHost._nodes_mu
                # INSIDE Chunks._mu — hierarchy-legal (rank 36 -> 38) and
                # the probe is one set lookup
                is_mig = getattr(self._nh, "is_migrating", None)
                if is_mig is not None and is_mig(c.cluster_id):
                    self._migration_streams += 1
            elif t is None or c.chunk_id != t.next_chunk:
                if t is not None:
                    self._drop_locked(key, reason="out_of_order")
                return False
            else:
                t.next_chunk += 1
            if c.chunk_id < t.skip_until:
                # already durable from the previous attempt of this
                # stream: bookkeeping only, no disk write
                self._skipped_chunks += 1
                self._note_file_complete_locked(t, c)
            else:
                try:
                    self._save_chunk_locked(t, c)
                    self._write_progress_locked(t, c)
                except OSError:
                    self._drop_locked(key, reason="io_error")
                    return False
            if c.chunk_id == c.chunk_count - 1:
                ok = self._finalize_locked(key, t, c)
                return ok
            return True

    # ------------------------------------------------------------------ paths
    def _node_snapshot_dir(self, cluster_id: int, node_id: int) -> str:
        return os.path.join(
            self._nh.snapshot_dir_root(),
            f"snapshot-part-{cluster_id:020d}-{node_id:020d}",
        )

    def _begin_locked(self, c: SnapshotChunk) -> Optional[_Track]:
        base = self._node_snapshot_dir(c.cluster_id, c.node_id)
        final_dir = os.path.join(base, f"snapshot-{c.index:016X}")
        tmp_dir = final_dir + ".receiving"
        if os.path.exists(final_dir):
            # A finalized image already exists: its InstallSnapshot handoff
            # was lost (the receiver was partitioned or mid-restart at
            # finalize time). Rejecting the retry would poison EVERY
            # subsequent stream of this index — the observed chaos wedge
            # (hundreds of failed re-streams, zero recoveries). Re-deliver
            # from the on-disk image; external-file metadata was persisted
            # next to it at finalize time. The image is NEVER deleted here:
            # it may be the node's only durable copy of an installed
            # snapshot.
            self._redeliver_locked(c, final_dir)
            return None
        # reclaim older abandoned partials for this node: a stream at a
        # higher index makes them unreachable (the sender only ever
        # streams its newest image), and keeping them would leak disk —
        # the fixed-width hex name compares lexically == numerically
        try:
            this_part = f"snapshot-{c.index:016X}.receiving"
            for name in os.listdir(base):
                if name.endswith(".receiving") and name < this_part:
                    shutil.rmtree(os.path.join(base, name), ignore_errors=True)
        except OSError:
            pass
        t = self._try_resume_locked(c, tmp_dir, final_dir)
        if t is not None:
            return t
        if os.path.exists(tmp_dir):
            # stale/incompatible partial from a different stream shape
            shutil.rmtree(tmp_dir, ignore_errors=True)
        os.makedirs(tmp_dir, exist_ok=True)
        t = _Track(c, tmp_dir, final_dir)
        t.tick = self._tick
        self._tracked[self._key(c)] = t
        return t

    def _try_resume_locked(self, c: SnapshotChunk, tmp_dir, final_dir) -> Optional[_Track]:
        """Adopt a surviving `.receiving` dir of the SAME stream: verify
        the recorded progress, truncate the in-progress file to the
        durable size, and fence already-persisted chunks off the write
        path. Returns None when no compatible progress exists (the caller
        starts clean)."""
        prog = self._read_progress(tmp_dir)
        if (
            prog is None
            or prog.get("index") != c.index
            or prog.get("term") != c.term
            or prog.get("chunk_count") != c.chunk_count
        ):
            return None
        nxt = int(prog.get("next_chunk", 0))
        if nxt <= 0:
            return None
        fname = prog.get("file")
        if fname:
            fpath = os.path.join(tmp_dir, fname)
            size = int(prog.get("size", 0))
            try:
                have = os.path.getsize(fpath)
            except OSError:
                return None
            if have < size:
                return None  # progress outran data (should not happen)
            if have > size:
                # torn tail from a mid-write crash: roll the file back to
                # the last chunk the progress record covers
                with open(fpath, "ab") as f:
                    f.truncate(size)
        t = _Track(c, tmp_dir, final_dir)
        t.tick = self._tick
        t.skip_until = nxt
        self._tracked[self._key(c)] = t
        self._resumed_streams += 1
        flight_recorder().record(
            "snapshot_stream_resumed", cluster=c.cluster_id,
            node=c.node_id, index=c.index, offset_chunks=nxt,
            offset_bytes=int(prog.get("size", 0)),
        )
        return t

    def _progress_path(self, tmp_dir: str) -> str:
        return os.path.join(tmp_dir, _PROGRESS_FILE)

    def _read_progress(self, tmp_dir: str) -> Optional[dict]:
        try:
            with open(self._progress_path(tmp_dir)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _write_progress_locked(self, t: _Track, c: SnapshotChunk) -> None:
        """Record the durable resume point AFTER the chunk's bytes are on
        disk (write-then-record: the record can only ever lag the data, so
        resume never skips bytes that were lost)."""
        if c.has_file_info:
            name = f"external-file-{c.file_info.file_id}"
        else:
            name = f"snapshot-{c.index:016X}.gbsnap"
        path = os.path.join(t.tmp_dir, name)
        try:
            size = os.path.getsize(path)
        except OSError:
            size = 0
        rec = {
            "index": c.index,
            "term": c.term,
            "chunk_count": c.chunk_count,
            "next_chunk": c.chunk_id + 1,
            "file": name if not c.witness else "",
            "size": size if not c.witness else 0,
        }
        tmp = self._progress_path(t.tmp_dir) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, self._progress_path(t.tmp_dir))

    def _redeliver_locked(self, c: SnapshotChunk, final_dir: str) -> None:
        """Hand an already-received snapshot image to the node again (the
        stream that produced it finished, but the receiving raft never saw
        the InstallSnapshot). The stale-snapshot ACK path in the engine
        covers the 'already recovered' case."""
        fname = f"snapshot-{c.index:016X}.gbsnap"
        final_path = os.path.join(final_dir, fname)
        ss = Snapshot(
            filepath=final_path,
            file_size=(
                os.path.getsize(final_path)
                if not c.witness and os.path.exists(final_path)
                else 0
            ),
            index=c.index,
            term=c.term,
            membership=c.membership,
            files=self._load_stream_files(final_dir),
            cluster_id=c.cluster_id,
            on_disk_index=c.on_disk_index,
            witness=c.witness,
        )
        m = Message(
            type=MessageType.INSTALL_SNAPSHOT,
            cluster_id=c.cluster_id,
            to=c.node_id,
            from_=c.from_,
            snapshot=ss,
        )
        self._nh.handle_message_batch(MessageBatch(requests=[m]))
        self._nh.handle_snapshot(c.cluster_id, c.node_id, c.from_)

    def _note_file_complete_locked(self, t: _Track, c: SnapshotChunk) -> None:
        """External-file bookkeeping shared by the write and skip paths:
        the metadata rides the chunk stream, so a skipped (already
        durable) chunk must still contribute its file record."""
        if c.has_file_info and c.file_chunk_id == c.file_chunk_count - 1:
            name = f"external-file-{c.file_info.file_id}"
            t.files.append((c.file_info, os.path.join(t.final_dir, name)))

    def _save_chunk_locked(self, t: _Track, c: SnapshotChunk) -> None:
        if c.witness:
            return
        if c.has_file_info:
            name = f"external-file-{c.file_info.file_id}"
        else:
            name = f"snapshot-{c.index:016X}.gbsnap"
        path = os.path.join(t.tmp_dir, name)
        mode = "wb" if c.file_chunk_id == 0 else "ab"
        with open(path, mode) as f:
            f.write(c.data)
        self._note_file_complete_locked(t, c)

    def _finalize_locked(self, key, t: _Track, c: SnapshotChunk) -> bool:
        first = t.first
        fname = f"snapshot-{first.index:016X}.gbsnap"
        fpath = os.path.join(t.tmp_dir, fname)
        if not first.witness:
            if not validate_snapshot_file(fpath):
                # the assembled image is corrupt: the partial is
                # WORTHLESS — purge it, or the retry would resume past
                # every chunk (no rewrites), re-validate the same bytes
                # and wedge this snapshot index forever
                self._drop_locked(key, reason="validation", purge=True)
                return False
        del self._tracked[key]
        self._completed_streams += 1
        # the progress record must not travel into the finalized image dir
        try:
            os.remove(self._progress_path(t.tmp_dir))
        except OSError:
            pass
        if os.path.exists(t.final_dir):
            shutil.rmtree(t.tmp_dir, ignore_errors=True)
            return True
        # persist external-file metadata next to the image: a lost
        # InstallSnapshot handoff is re-delivered from disk later, and the
        # stream is the only carrier of this metadata
        if t.files:
            meta = [
                {
                    "name": os.path.basename(lp),
                    "file_id": fi.file_id,
                    "metadata": fi.metadata.hex() if fi.metadata else "",
                }
                for fi, lp in t.files
            ]
            with open(
                os.path.join(t.tmp_dir, "stream-files.json"), "w"
            ) as mf:
                json.dump(meta, mf)
        os.replace(t.tmp_dir, t.final_dir)
        final_path = os.path.join(t.final_dir, fname)
        from ..types import SnapshotFile as WireFile

        wire_files = [
            WireFile(
                filepath=lp,
                file_size=os.path.getsize(lp),
                file_id=fi.file_id,
                metadata=fi.metadata,
            )
            for fi, lp in t.files
        ]
        ss = Snapshot(
            filepath=final_path,
            file_size=os.path.getsize(final_path) if not first.witness else 0,
            index=first.index,
            term=first.term,
            membership=first.membership,
            files=wire_files,
            cluster_id=first.cluster_id,
            on_disk_index=first.on_disk_index,
            witness=first.witness,
        )
        m = Message(
            type=MessageType.INSTALL_SNAPSHOT,
            cluster_id=first.cluster_id,
            to=first.node_id,
            from_=first.from_,
            snapshot=ss,
        )
        self._nh.handle_message_batch(MessageBatch(requests=[m]))
        self._nh.handle_snapshot(first.cluster_id, first.node_id, first.from_)
        return True

    def _load_stream_files(self, final_dir: str):
        """External-file records persisted at finalize (for re-delivery)."""
        path = os.path.join(final_dir, "stream-files.json")
        if not os.path.exists(path):
            return []
        from ..types import SnapshotFile as WireFile

        try:
            with open(path) as f:
                meta = json.load(f)
            out = []
            for rec in meta:
                lp = os.path.join(final_dir, rec["name"])
                out.append(
                    WireFile(
                        filepath=lp,
                        file_size=(
                            os.path.getsize(lp) if os.path.exists(lp) else 0
                        ),
                        file_id=rec["file_id"],
                        metadata=bytes.fromhex(rec["metadata"]),
                    )
                )
            return out
        except Exception:
            return []

    def _drop_locked(self, key, reason: str = "", purge: bool = False) -> None:
        t = self._tracked.pop(key, None)
        if t is not None:
            # the partial data + progress record normally STAY on disk:
            # they are exactly what the next attempt of this stream
            # resumes from. Only the in-memory tracking is abandoned.
            # `purge` (validation failure) removes them — corrupt bytes
            # must be re-transferred, not resumed past.
            if purge:
                shutil.rmtree(t.tmp_dir, ignore_errors=True)
            if reason == "restarted":
                # not an abort: the sender's RETRY of this same stream
                # arrived (the normal resume path) — no counter bump and
                # no client fail-fast window
                return
            self._aborted_streams += 1
            flight_recorder().record(
                "snapshot_stream_aborted", cluster=t.first.cluster_id,
                node=t.first.node_id, index=t.first.index,
                reason=reason or "dropped",
            )
            notify = getattr(self._nh, "_on_snapshot_stream_aborted", None)
            if notify is not None:
                # lock-free downstream (plain attribute stamps on the
                # node): safe to invoke under _mu
                notify(
                    t.first.cluster_id, t.first.node_id, t.first.from_,
                    reason or "dropped",
                )

    # --------------------------------------------------------------------- gc
    # resumable partials whose stream is never retried (member removed,
    # sender permanently gone) expire after this wall-clock age — bounds
    # the disk a dead stream can hold to one image per (cluster, node)
    # for a bounded time
    RESUME_TTL_S = 1800.0

    def tick(self) -> None:
        """Periodic timeout sweep (cf. chunks.go:112-139)."""
        with self._mu:
            self._tick += 1
            dead = [
                k
                for k, t in self._tracked.items()
                if self._tick - t.tick > soft.snapshot_chunk_timeout_tick
            ]
            for k in dead:
                self._drop_locked(k, reason="timeout")
            sweep_due = self._tick % soft.snapshot_chunk_timeout_tick == 0
            tracked_dirs = (
                {t.tmp_dir for t in self._tracked.values()}
                if sweep_due
                else None
            )
        if sweep_due:
            # the walk/rmtree I/O runs OUTSIDE _mu: holding the tracker
            # lock across a directory sweep would stall inbound chunk
            # delivery — the cadence stall this plane exists to avoid.
            # Swept dirs are by definition untracked; a stream that
            # begins concurrently recreates its dir on the next chunk.
            self._sweep_stale_partials(tracked_dirs)

    def _sweep_stale_partials(self, tracked_dirs) -> None:
        """Age out resumable `.receiving` partials no live stream is
        feeding: process_orphans spares progress-carrying partials (they
        are resume state) and _begin's reclaim only fires when a NEWER
        stream targets the same node, so a stream that is simply never
        retried would otherwise hold a snapshot image of disk forever."""
        import time as _time

        try:
            root = self._nh.snapshot_dir_root()
            now = _time.time()
            for part in os.listdir(root):
                pdir = os.path.join(root, part)
                if not part.startswith("snapshot-part-"):
                    continue
                try:
                    names = os.listdir(pdir)
                except OSError:
                    continue
                for name in names:
                    if not name.endswith(".receiving"):
                        continue
                    path = os.path.join(pdir, name)
                    if path in tracked_dirs:
                        continue  # live stream: its own timeout governs
                    try:
                        age = now - os.path.getmtime(
                            self._progress_path(path)
                        )
                    except OSError:
                        continue  # no progress record: orphan sweep owns it
                    if age > self.RESUME_TTL_S:
                        shutil.rmtree(path, ignore_errors=True)
        except OSError:
            pass


__all__ = ["Chunks"]
