"""Compact binary codec for wire/state types.

The reference uses protobuf with a hand-written marshal fast path
(raftpb/raft_optimized.go). Here the codec is a little-endian
length-prefixed format built on struct packing — no varint dance, fixed
headers, memoryview slicing — fast enough in CPython and trivially portable
to the C++ transport/logdb runtime (the layout is the ABI).

All encode_* return bytes; all decode_* take (buf, offset) and return
(value, new_offset).
"""
from __future__ import annotations

import struct
from typing import List, Tuple

from .types import (
    Bootstrap,
    ConfigChange,
    ConfigChangeType,
    Entry,
    EntryType,
    Membership,
    Message,
    MessageBatch,
    MessageType,
    Snapshot,
    SnapshotChunk,
    SnapshotFile,
    State,
)

_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")
# type, term, index, key, client_id, series_id, responded_to, trace_id,
# cmd_len
_ENTRY = struct.Struct("<BQQQQQQQI")
# type, to, from, cluster_id, term, log_term, log_index, commit, reject,
# hint, hint_high, trace_id, n_entries, has_snapshot
_MSG = struct.Struct("<BQQQQQQQBQQQIB")
_STATE = struct.Struct("<QQQ")


class CodecError(ValueError):
    """The single controlled failure mode of every decode_* function:
    corrupt or truncated input raises this (found by dragonboat_tpu.fuzz;
    the reference gets the same guarantee from protobuf unmarshal errors,
    raftpb/fuzz.go:15-49)."""


def _need(buf, off: int, n: int) -> None:
    if n < 0 or off + n > len(buf):
        raise CodecError(f"truncated: need {n} bytes at {off}, have {len(buf)}")


def _checked(fn):
    """Public decoders convert every low-level unpack failure (truncated
    struct, bad enum value, invalid utf-8) into CodecError."""
    import functools

    @functools.wraps(fn)
    def wrap(buf, off: int = 0):
        try:
            return fn(buf, off)
        except CodecError:
            raise
        except (struct.error, ValueError, UnicodeDecodeError, IndexError,
                OverflowError) as e:
            raise CodecError(f"{fn.__name__}: {e}") from e

    return wrap


def _pack_bytes(b: bytes) -> bytes:
    return _U32.pack(len(b)) + b


def _unpack_bytes(buf, off: int) -> Tuple[bytes, int]:
    (n,) = _U32.unpack_from(buf, off)
    off += 4
    _need(buf, off, n)
    return bytes(buf[off : off + n]), off + n


def _pack_str(s: str) -> bytes:
    return _pack_bytes(s.encode())


def _unpack_str(buf, off: int) -> Tuple[str, int]:
    b, off = _unpack_bytes(buf, off)
    return b.decode(), off


def _unpack_count(buf, off: int, min_item_size: int) -> Tuple[int, int]:
    """Length-prefixed collection count, bounded by the bytes that could
    possibly remain — a corrupt count must not drive a multi-billion
    iteration loop."""
    (n,) = _U32.unpack_from(buf, off)
    off += 4
    if min_item_size > 0 and n > (len(buf) - off) // min_item_size:
        raise CodecError(f"corrupt collection count {n} at {off}")
    return n, off


# ---------------------------------------------------------------- Entry

def encode_entry(e: Entry) -> bytes:
    return (
        _ENTRY.pack(
            int(e.type),
            e.term,
            e.index,
            e.key,
            e.client_id,
            e.series_id,
            e.responded_to,
            e.trace_id,
            len(e.cmd),
        )
        + e.cmd
    )


@_checked
def decode_entry(buf, off: int = 0) -> Tuple[Entry, int]:
    t, term, index, key, cid, sid, resp, tid, clen = _ENTRY.unpack_from(
        buf, off
    )
    off += _ENTRY.size
    _need(buf, off, clen)
    cmd = bytes(buf[off : off + clen])
    return (
        Entry(
            type=EntryType(t),
            term=term,
            index=index,
            key=key,
            client_id=cid,
            series_id=sid,
            responded_to=resp,
            trace_id=tid,
            cmd=cmd,
        ),
        off + clen,
    )


def encode_entries(entries: List[Entry]) -> bytes:
    parts = [_U32.pack(len(entries))]
    parts.extend(encode_entry(e) for e in entries)
    return b"".join(parts)


def frame_encoded_entries(n: int, *bodies) -> bytes:
    """An entry-list record of `n` entries from their encode_entry()
    outputs, each alone or already joined, as one or more `bodies` (the
    logdb merges a record's retained prefix, which it keeps joined, with
    the entries a run appends), in one allocation."""
    return b"".join((_U32.pack(n), *bodies))


def encoded_entries_body(record: bytes) -> memoryview:
    """An entry-list record without its count (the entries' encodings,
    joined), as a view: nothing is copied."""
    return memoryview(record)[_U32.size:]


@_checked
def decode_entries(buf, off: int = 0) -> Tuple[List[Entry], int]:
    n, off = _unpack_count(buf, off, _ENTRY.size)
    out = []
    for _ in range(n):
        e, off = decode_entry(buf, off)
        out.append(e)
    return out, off


# ---------------------------------------------------------------- State

def encode_state(st: State) -> bytes:
    return _STATE.pack(st.term, st.vote, st.commit)


@_checked
def decode_state(buf, off: int = 0) -> Tuple[State, int]:
    term, vote, commit = _STATE.unpack_from(buf, off)
    return State(term=term, vote=vote, commit=commit), off + _STATE.size


# ------------------------------------------------------------ Membership

def _pack_addr_map(m: dict) -> bytes:
    parts = [_U32.pack(len(m))]
    for nid in sorted(m):
        parts.append(_U64.pack(nid))
        parts.append(_pack_str(m[nid]))
    return b"".join(parts)


def _unpack_addr_map(buf, off: int) -> Tuple[dict, int]:
    n, off = _unpack_count(buf, off, 12)  # u64 nid + u32 len prefix
    out = {}
    for _ in range(n):
        (nid,) = _U64.unpack_from(buf, off)
        off += 8
        addr, off = _unpack_str(buf, off)
        out[nid] = addr
    return out, off


def encode_membership(m: Membership) -> bytes:
    parts = [_U64.pack(m.config_change_id)]
    parts.append(_pack_addr_map(m.addresses))
    parts.append(_pack_addr_map(m.observers))
    parts.append(_pack_addr_map(m.witnesses))
    removed = sorted(m.removed)
    parts.append(_U32.pack(len(removed)))
    for nid in removed:
        parts.append(_U64.pack(nid))
    return b"".join(parts)


@_checked
def decode_membership(buf, off: int = 0) -> Tuple[Membership, int]:
    (ccid,) = _U64.unpack_from(buf, off)
    off += 8
    addresses, off = _unpack_addr_map(buf, off)
    observers, off = _unpack_addr_map(buf, off)
    witnesses, off = _unpack_addr_map(buf, off)
    n, off = _unpack_count(buf, off, 8)
    removed = {}
    for _ in range(n):
        (nid,) = _U64.unpack_from(buf, off)
        off += 8
        removed[nid] = True
    return (
        Membership(
            config_change_id=ccid,
            addresses=addresses,
            observers=observers,
            witnesses=witnesses,
            removed=removed,
        ),
        off,
    )


# -------------------------------------------------------------- Snapshot

_SS = struct.Struct("<QQQQBBBBQ")  # filesize,index,term,cluster,dummy,type,imported,witness,on_disk_index


def encode_snapshot(ss: Snapshot) -> bytes:
    parts = [
        _SS.pack(
            ss.file_size,
            ss.index,
            ss.term,
            ss.cluster_id,
            1 if ss.dummy else 0,
            ss.type,
            1 if ss.imported else 0,
            1 if ss.witness else 0,
            ss.on_disk_index,
        )
    ]
    parts.append(_pack_str(ss.filepath))
    parts.append(_pack_bytes(ss.checksum))
    if ss.membership is not None:
        parts.append(b"\x01")
        parts.append(encode_membership(ss.membership))
    else:
        parts.append(b"\x00")
    parts.append(_U32.pack(len(ss.files)))
    for f in ss.files:
        parts.append(_U64.pack(f.file_id))
        parts.append(_U64.pack(f.file_size))
        parts.append(_pack_str(f.filepath))
        parts.append(_pack_bytes(f.metadata))
    return b"".join(parts)


@_checked
def decode_snapshot(buf, off: int = 0) -> Tuple[Snapshot, int]:
    fs, idx, term, cid, dummy, typ, imported, witness, odi = _SS.unpack_from(buf, off)
    off += _SS.size
    filepath, off = _unpack_str(buf, off)
    checksum, off = _unpack_bytes(buf, off)
    has_m = buf[off]
    off += 1
    membership = None
    if has_m:
        membership, off = decode_membership(buf, off)
    nf, off = _unpack_count(buf, off, 24)  # 2x u64 + 2x u32 prefixes
    files = []
    for _ in range(nf):
        (fid,) = _U64.unpack_from(buf, off)
        off += 8
        (fsize,) = _U64.unpack_from(buf, off)
        off += 8
        fp, off = _unpack_str(buf, off)
        meta, off = _unpack_bytes(buf, off)
        files.append(
            SnapshotFile(filepath=fp, file_size=fsize, file_id=fid, metadata=meta)
        )
    return (
        Snapshot(
            filepath=filepath,
            file_size=fs,
            index=idx,
            term=term,
            membership=membership,
            files=files,
            checksum=checksum,
            dummy=bool(dummy),
            cluster_id=cid,
            type=typ,
            imported=bool(imported),
            on_disk_index=odi,
            witness=bool(witness),
        ),
        off,
    )


# --------------------------------------------------------------- Message

def encode_message(m: Message) -> bytes:
    parts = [
        _MSG.pack(
            int(m.type),
            m.to,
            m.from_,
            m.cluster_id,
            m.term,
            m.log_term,
            m.log_index,
            m.commit,
            1 if m.reject else 0,
            m.hint,
            m.hint_high,
            m.trace_id,
            len(m.entries),
            1 if m.snapshot is not None else 0,
        )
    ]
    parts.extend(encode_entry(e) for e in m.entries)
    if m.snapshot is not None:
        parts.append(encode_snapshot(m.snapshot))
    return b"".join(parts)


@_checked
def decode_message(buf, off: int = 0) -> Tuple[Message, int]:
    (
        t,
        to,
        frm,
        cid,
        term,
        lterm,
        lidx,
        commit,
        reject,
        hint,
        hint_high,
        tid,
        n_ent,
        has_ss,
    ) = _MSG.unpack_from(buf, off)
    off += _MSG.size
    entries = []
    for _ in range(n_ent):
        e, off = decode_entry(buf, off)
        entries.append(e)
    ss = None
    if has_ss:
        ss, off = decode_snapshot(buf, off)
    return (
        Message(
            type=MessageType(t),
            to=to,
            from_=frm,
            cluster_id=cid,
            term=term,
            log_term=lterm,
            log_index=lidx,
            commit=commit,
            reject=bool(reject),
            hint=hint,
            hint_high=hint_high,
            trace_id=tid,
            entries=entries,
            snapshot=ss,
        ),
        off,
    )


# ----------------------------------------------------------- MessageBatch

def encode_message_batch(b: MessageBatch) -> bytes:
    parts = [
        _U64.pack(b.deployment_id),
        _U32.pack(b.bin_ver),
        _pack_str(b.source_address),
        _U32.pack(len(b.requests)),
    ]
    parts.extend(encode_message(m) for m in b.requests)
    return b"".join(parts)


@_checked
def decode_message_batch(buf, off: int = 0) -> Tuple[MessageBatch, int]:
    (did,) = _U64.unpack_from(buf, off)
    off += 8
    (bv,) = _U32.unpack_from(buf, off)
    off += 4
    src, off = _unpack_str(buf, off)
    (n,) = _U32.unpack_from(buf, off)
    off += 4
    msgs = []
    for _ in range(n):
        m, off = decode_message(buf, off)
        msgs.append(m)
    return (
        MessageBatch(
            requests=msgs, deployment_id=did, source_address=src, bin_ver=bv
        ),
        off,
    )


# ---------------------------------------------------------- SnapshotChunk

_CHUNK = struct.Struct("<QQQQQQQQQQQQBBQB")


def encode_chunk(c: SnapshotChunk) -> bytes:
    parts = [
        _CHUNK.pack(
            c.cluster_id,
            c.node_id,
            c.from_,
            c.chunk_id,
            c.chunk_size,
            c.chunk_count,
            c.index,
            c.term,
            c.file_size,
            c.deployment_id,
            c.file_chunk_id,
            c.file_chunk_count,
            1 if c.has_file_info else 0,
            1 if c.witness else 0,
            c.on_disk_index,
            1 if c.membership is not None else 0,
        )
    ]
    parts.append(_pack_str(c.filepath))
    parts.append(_pack_bytes(c.data))
    if c.has_file_info and c.file_info is not None:
        parts.append(_U64.pack(c.file_info.file_id))
        parts.append(_U64.pack(c.file_info.file_size))
        parts.append(_pack_str(c.file_info.filepath))
        parts.append(_pack_bytes(c.file_info.metadata))
    if c.membership is not None:
        parts.append(encode_membership(c.membership))
    return b"".join(parts)


@_checked
def decode_chunk(buf, off: int = 0) -> Tuple[SnapshotChunk, int]:
    (
        cid,
        nid,
        frm,
        chunk_id,
        chunk_size,
        chunk_count,
        index,
        term,
        file_size,
        did,
        fcid,
        fcc,
        has_fi,
        witness,
        odi,
        has_m,
    ) = _CHUNK.unpack_from(buf, off)
    off += _CHUNK.size
    filepath, off = _unpack_str(buf, off)
    data, off = _unpack_bytes(buf, off)
    fi = None
    if has_fi:
        (fid,) = _U64.unpack_from(buf, off)
        off += 8
        (fsize,) = _U64.unpack_from(buf, off)
        off += 8
        fp, off = _unpack_str(buf, off)
        meta, off = _unpack_bytes(buf, off)
        fi = SnapshotFile(filepath=fp, file_size=fsize, file_id=fid, metadata=meta)
    membership = None
    if has_m:
        membership, off = decode_membership(buf, off)
    return (
        SnapshotChunk(
            cluster_id=cid,
            node_id=nid,
            from_=frm,
            chunk_id=chunk_id,
            chunk_size=chunk_size,
            chunk_count=chunk_count,
            data=data,
            index=index,
            term=term,
            filepath=filepath,
            file_size=file_size,
            deployment_id=did,
            file_chunk_id=fcid,
            file_chunk_count=fcc,
            has_file_info=bool(has_fi),
            file_info=fi,
            membership=membership,
            on_disk_index=odi,
            witness=bool(witness),
        ),
        off,
    )


# -------------------------------------------------------------- Bootstrap

def encode_bootstrap(b: Bootstrap) -> bytes:
    return (
        _pack_addr_map(b.addresses) + (b"\x01" if b.join else b"\x00") + _U32.pack(b.type)
    )


@_checked
def decode_bootstrap(buf, off: int = 0) -> Tuple[Bootstrap, int]:
    addresses, off = _unpack_addr_map(buf, off)
    join = buf[off] == 1
    off += 1
    (t,) = _U32.unpack_from(buf, off)
    off += 4
    return Bootstrap(addresses=addresses, join=join, type=t), off


__all__ = [
    "encode_entry",
    "decode_entry",
    "encode_entries",
    "decode_entries",
    "encode_state",
    "decode_state",
    "encode_membership",
    "decode_membership",
    "encode_snapshot",
    "decode_snapshot",
    "encode_message",
    "decode_message",
    "encode_message_batch",
    "decode_message_batch",
    "encode_chunk",
    "decode_chunk",
    "encode_bootstrap",
    "decode_bootstrap",
]
