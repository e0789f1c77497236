"""Host-side snapshot file lifecycle.

cf. snapshotter.go:34-338 + internal/server/snapshotenv.go:117-280 — a
snapshot is written into a temp directory, finalized with an atomic rename,
and recorded in the LogDB; orphaned temp dirs from crashes are swept at
startup. Keeps the 3 most recent snapshots (snapshotter.go:34-36).
"""
from __future__ import annotations

import os
import shutil
import threading
from typing import List, Optional, Tuple

from ..rsm.manager import SSMeta, SSRequest
from ..rsm.snapshotio import (
    SnapshotHeader,
    SnapshotReader,
    SnapshotWriter,
    validate_snapshot_file,
)
from ..statemachine import ISnapshotFileCollection, SnapshotFile
from ..types import Membership, Snapshot, Update

SNAPSHOTS_TO_KEEP = 3
GENERATING_SUFFIX = ".generating"
# metadata record written into exported snapshot dirs (cf. the reference's
# server.SnapshotMetadataFilename "snapshot.metadata")
SNAPSHOT_METADATA_FILENAME = "snapshot.metadata"
RECEIVING_SUFFIX = ".receiving"


class FileCollection(ISnapshotFileCollection):
    """Collects external files the SM adds during save
    (cf. internal/rsm/files.go:26-89)."""

    def __init__(self, dirname: str) -> None:
        self._dir = dirname
        self.files: List[SnapshotFile] = []

    def add_file(self, file_id: int, path: str, metadata: bytes) -> None:
        self.files.append(
            SnapshotFile(file_id=file_id, filepath=path, metadata=metadata)
        )

    def finalize(self, record_dir: Optional[str] = None) -> List:
        """Hard-link/copy external files into the snapshot dir. The files
        land in self._dir (the crash-safe .generating temp dir), but the
        RECORDED paths must point at record_dir — the final directory the
        temp dir is renamed to on commit — or every later load would chase
        a path that no longer exists."""
        out = []
        from ..types import SnapshotFile as WireFile

        for i, f in enumerate(self.files):
            name = f"external-file-{f.file_id}"
            dst = os.path.join(self._dir, name)
            try:
                os.link(f.filepath, dst)
            except OSError:
                shutil.copy2(f.filepath, dst)
            out.append(
                WireFile(
                    filepath=os.path.join(record_dir or self._dir, name),
                    file_size=os.path.getsize(dst),
                    file_id=f.file_id,
                    metadata=f.metadata,
                )
            )
        return out


class Snapshotter:
    """Per-node snapshot manager (cf. snapshotter.go:55-78)."""

    def __init__(self, root_dir: str, cluster_id: int, node_id: int, logdb,
                 listed: Optional[set] = None) -> None:
        self.cluster_id = cluster_id
        self.node_id = node_id
        self._logdb = logdb
        name = f"snapshot-part-{cluster_id:020d}-{node_id:020d}"
        self._dir = os.path.join(root_dir, name)
        self._mu = threading.Lock()
        self._sm = None
        # lazy dir: a node that never snapshots never touches the fs — at
        # 50k groups the per-cluster mkdir+orphan scan was a measured third
        # of fleet bring-up. Orphan processing only matters if the dir
        # already exists (a previous incarnation wrote into it). `listed`
        # is root_dir's entries where the caller listed it once for many
        # nodes: one system call a bring-up, not one a replica.
        if (name in listed) if listed is not None else os.path.isdir(self._dir):
            self.process_orphans()

    def bind_sm(self, sm) -> None:
        self._sm = sm

    # ------------------------------------------------------------- locations
    def _final_dir(self, index: int) -> str:
        return os.path.join(self._dir, f"snapshot-{index:016X}")

    def _tmp_dir(self, index: int, suffix: str = GENERATING_SUFFIX) -> str:
        return self._final_dir(index) + suffix

    def _file_path(self, index: int) -> str:
        return os.path.join(self._final_dir(index), f"snapshot-{index:016X}.gbsnap")

    # ----------------------------------------------------------- save / load
    def save(self, save_fn, meta: SSMeta) -> Tuple[Snapshot, object]:
        """Write the snapshot image (cf. snapshotter.go:95-142 Save). The
        rsm manager supplies save_fn(writer, files)."""
        index = meta.index
        tmp = self._tmp_dir(index)
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        fname = f"snapshot-{index:016X}.gbsnap"
        fpath = os.path.join(tmp, fname)
        header = SnapshotHeader(
            index=meta.index,
            term=meta.term,
            on_disk_index=meta.on_disk_index,
            smtype=self._sm.sm_type() if self._sm is not None else 0,
            membership=meta.membership,
            compression=meta.compression,
        )
        files = FileCollection(tmp)
        with open(fpath, "wb") as f:
            w = SnapshotWriter(f, header, meta.session)
            save_fn(w, files)
            w.close()
            f.flush()
            os.fsync(f.fileno())
        wire_files = files.finalize(record_dir=self._final_dir(index))
        ss = Snapshot(
            filepath=os.path.join(self._final_dir(index), fname),
            file_size=os.path.getsize(fpath),
            index=meta.index,
            term=meta.term,
            membership=meta.membership,
            files=wire_files,
            cluster_id=self.cluster_id,
            type=header.smtype,
            on_disk_index=meta.on_disk_index,
        )
        return ss, tmp

    def commit(self, ss: Snapshot, req: Optional[SSRequest] = None) -> None:
        """Finalize: atomic rename + logdb record + retention
        (cf. snapshotter.go:173-194 Commit)."""
        tmp = self._tmp_dir(ss.index)
        final = self._final_dir(ss.index)
        if req is not None and req.is_exported():
            # exported snapshots move to the user path instead, with a
            # metadata record so tools.import_snapshot can rebuild the
            # Snapshot record (cf. server.SnapshotMetadataFilename). The
            # metadata is written INSIDE the temp dir so the rename below is
            # the single crash-atomic commit point; all recorded paths are
            # rebased onto the post-rename destination.
            import dataclasses

            from .. import codec

            dst = os.path.join(req.path, os.path.basename(final))
            meta_ss = dataclasses.replace(
                ss,
                filepath=os.path.join(dst, os.path.basename(ss.filepath)),
                files=[
                    dataclasses.replace(
                        f,
                        filepath=os.path.join(
                            dst, os.path.basename(f.filepath)
                        ),
                    )
                    for f in ss.files
                ],
            )
            mpath = os.path.join(tmp, SNAPSHOT_METADATA_FILENAME)
            with open(mpath, "wb") as f:
                f.write(codec.encode_snapshot(meta_ss))
                f.flush()
                os.fsync(f.fileno())
            os.rename(tmp, dst)
            return
        with self._mu:
            if os.path.exists(final):
                shutil.rmtree(tmp, ignore_errors=True)
            else:
                os.replace(tmp, final)
            self._logdb.save_snapshots(
                [
                    Update(
                        cluster_id=self.cluster_id,
                        node_id=self.node_id,
                        snapshot=ss,
                    )
                ]
            )
        self.compact(ss.index)

    def get_most_recent_snapshot(self) -> Optional[Snapshot]:
        snaps = self._logdb.list_snapshots(self.cluster_id, self.node_id, 2**62)
        while snaps:
            ss = snaps[-1]
            if ss.dummy or ss.witness or os.path.exists(ss.filepath):
                return ss
            snaps.pop()
        return None

    def load(self, ss: Snapshot, load_fn) -> None:
        """Open + validate + hand payload stream to the rsm layer
        (cf. snapshotter.go:144-171 Load)."""
        with open(ss.filepath, "rb") as f:
            r = SnapshotReader(f)
            files = [
                SnapshotFile(
                    file_id=sf.file_id, filepath=sf.filepath, metadata=sf.metadata
                )
                for sf in ss.files
            ]
            load_fn(r, r.session, files)

    def stream(self, save_fn, meta: SSMeta, sink) -> None:
        """Stream a snapshot through a chunk sink (on-disk SM live stream,
        cf. statemachine.go:680-695); sink implements write/close."""
        header = SnapshotHeader(
            index=meta.index,
            term=meta.term,
            on_disk_index=meta.on_disk_index,
            smtype=self._sm.sm_type() if self._sm is not None else 0,
            membership=meta.membership,
        )
        w = SnapshotWriter(sink, header, meta.session)
        try:
            save_fn(w, None)
            w.close()
            sink.finalize()
        except Exception:
            sink.abort()
            raise

    # ------------------------------------------------------------- retention
    def compact(self, latest_index: int) -> None:
        """Keep SNAPSHOTS_TO_KEEP records, remove older files + records
        (cf. snapshotter.go:255-277)."""
        snaps = self._logdb.list_snapshots(self.cluster_id, self.node_id, 2**62)
        if len(snaps) <= SNAPSHOTS_TO_KEEP:
            return
        for ss in snaps[:-SNAPSHOTS_TO_KEEP]:
            self._logdb.delete_snapshot(self.cluster_id, self.node_id, ss.index)
            shutil.rmtree(self._final_dir(ss.index), ignore_errors=True)

    def shrink(self, to_index: int) -> None:
        """Replace applied full snapshots of an on-disk SM with dummy
        metadata-only images (cf. snapshotter.go:229-253). The dummy keeps
        index/term/membership for restart replay but drops the payload."""
        snaps = self._logdb.list_snapshots(self.cluster_id, self.node_id, to_index)
        for ss in snaps:
            if ss.dummy or ss.witness:
                continue
            dummy = Snapshot(
                filepath=ss.filepath,
                index=ss.index,
                term=ss.term,
                membership=ss.membership,
                cluster_id=ss.cluster_id,
                on_disk_index=ss.on_disk_index,
                dummy=True,
            )
            self._logdb.save_snapshots(
                [
                    Update(
                        cluster_id=self.cluster_id,
                        node_id=self.node_id,
                        snapshot=dummy,
                    )
                ]
            )
            shutil.rmtree(self._final_dir(ss.index), ignore_errors=True)

    # --------------------------------------------------------------- recovery
    def process_orphans(self) -> None:
        """Sweep crashed temp dirs (cf. snapshotter.go:279-338).

        `.receiving` dirs carrying a stream-progress record are NOT
        orphans anymore: they are the resume state of an interrupted
        inbound snapshot stream (transport/chunks.py) — the restarted
        host's re-streamed install fast-forwards through the chunks they
        already hold instead of re-transferring them. Progress-less
        `.receiving` dirs (pre-resume-protocol leftovers, torn creates)
        still sweep; the chunk tracker reclaims stale resumable partials
        itself when a newer stream begins."""
        if not os.path.isdir(self._dir):
            return
        for name in os.listdir(self._dir):
            path = os.path.join(self._dir, name)
            if name.endswith(GENERATING_SUFFIX):
                shutil.rmtree(path, ignore_errors=True)
            elif name.endswith(RECEIVING_SUFFIX) and not os.path.exists(
                os.path.join(path, "stream-progress.json")
            ):
                shutil.rmtree(path, ignore_errors=True)

    def dir_path(self) -> str:
        return self._dir


__all__ = ["Snapshotter", "FileCollection", "SNAPSHOTS_TO_KEEP"]
