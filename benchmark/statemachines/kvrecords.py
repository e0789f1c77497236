"""kvrecords: a YCSB record store over NodeHost, with its commands, its
plain reference and the seeded operation stream of YCSB's core workloads.

YCSB (Cooper et al., SoCC 2010) keeps one table of `recordcount` records,
each `fieldcount` fields of `fieldlength` bytes under a key "user<hash>".
Here the table is partitioned over Raft groups and every group runs one
StateMachine:

    insert(key, all fields)      a 1 000-byte value at the published sizes
    update(key, field, value)    one 100-byte field (writeallfields=false)
    lookup(key)    -> (record, applied), taken in one step under the lock
                      update() holds, so a lookup never tears
    lookup(None)   -> (applied, sum64): commands applied and the sum of the
                      little-endian u64 words of every command mod 2^64,
                      wrong if a payload byte is lost, duplicated or
                      replaced between the client and apply

update() answers each command with Result(value=n), the group's apply
sequence number, so a client can replay what it was acknowledged in the
order the system chose. Reference is that replay on a dict; it knows
nothing of the engine. Workload is the operation stream, a function of
the seed alone. tests/test_ycsb.py runs all of it on a small cluster.
"""
from __future__ import annotations

import functools
import struct
import threading

import numpy as np

from dragonboat_tpu.statemachine import IConcurrentStateMachine, Result

OP_INSERT, OP_UPDATE = 1, 2
KEY_BYTES = 24  # b"user" + 20 decimal digits
_HEAD = 8  # one u64 word: op | field << 8
_BODY = _HEAD + KEY_BYTES
_U64 = np.uint64
_MASK64 = (1 << 64) - 1


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over a uint64 array (wraps mod 2^64)."""
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


def _padded(n: int) -> int:
    return (n + 7) & ~7


def sum64(cmd: bytes) -> int:
    """Sum of a command's little-endian u64 words (commands are whole
    words long)."""
    return sum(struct.unpack(f"<{len(cmd) // 8}Q", cmd)) & _MASK64


def insert_cmd(key: bytes, fields) -> bytes:
    return OP_INSERT.to_bytes(_HEAD, "little") + key + b"".join(fields)


def update_cmd(key: bytes, field: int, value: bytes) -> bytes:
    cmd = (OP_UPDATE | field << 8).to_bytes(_HEAD, "little") + key + value
    return cmd + bytes(_padded(len(cmd)) - len(cmd))


class Table:
    """key -> tuple of fields, and how a command changes it: the one
    place that knows the command format, shared by the state machine and
    the reference (which differ in everything around it)."""

    def __init__(self, fieldcount: int = 10, fieldlength: int = 100) -> None:
        self.fieldcount = fieldcount
        self.fieldlength = fieldlength
        self.rows: dict = {}
        self._insert_len = _padded(_BODY + fieldcount * fieldlength)
        self._update_len = _padded(_BODY + fieldlength)

    def apply(self, cmd: bytes) -> None:
        op, key, L = cmd[0], cmd[_HEAD:_BODY], self.fieldlength
        if op == OP_UPDATE and len(cmd) == self._update_len:
            f = cmd[1]
            rec = self.rows.get(key)
            if rec is not None and f < self.fieldcount:
                # a new tuple: a record handed out by lookup never changes
                self.rows[key] = rec[:f] + (cmd[_BODY:_BODY + L],) + rec[f + 1:]
        elif op == OP_INSERT and len(cmd) == self._insert_len:
            self.rows[key] = tuple(
                cmd[_BODY + i * L:_BODY + (i + 1) * L]
                for i in range(self.fieldcount)
            )
        else:
            raise ValueError(f"kvrecords: no command {op} of {len(cmd)} bytes")


class StateMachine(IConcurrentStateMachine):
    def __init__(self, cluster_id, node_id):
        self.table = Table()
        self.state = (0, 0)  # (applied, sum64): one store, read lock-free
        self._mu = threading.Lock()

    def update(self, entries):
        table = self.table
        with self._mu:
            n, acc = self.state
            for e in entries:
                table.apply(e.cmd)
                n += 1
                acc += sum64(e.cmd)
                e.result = Result(value=n)
            self.state = (n, acc & _MASK64)
        return entries

    def lookup(self, query):
        if query is None:
            return self.state
        with self._mu:
            return self.table.rows.get(query), self.state[0]

    def prepare_snapshot(self):
        with self._mu:
            return self.state, dict(self.table.rows)

    def save_snapshot(self, ctx, w, fc, done):
        (n, acc), rows = ctx
        w.write(struct.pack("<3Q", n, acc, len(rows)))
        for key, rec in rows.items():
            w.write(key + b"".join(rec))

    def recover_from_snapshot(self, r, fc, done):
        n, acc, count = struct.unpack("<3Q", r.read(24))
        t = self.table
        L, width = t.fieldlength, KEY_BYTES + t.fieldcount * t.fieldlength
        body = r.read(width * count)
        rows = {}
        for at in range(0, len(body), width):
            rows[body[at:at + KEY_BYTES]] = tuple(
                body[i:i + L] for i in range(at + KEY_BYTES, at + width, L)
            )
        with self._mu:
            t.rows = rows
            self.state = (n, acc)

    def close(self):
        pass


class Reference:
    """The plain reference of one group: acknowledged commands replayed
    on a dict in the order of the n the system answered them with."""

    def __init__(self, fieldcount: int = 10, fieldlength: int = 100) -> None:
        self.table = Table(fieldcount, fieldlength)
        self.applied = 0

    def apply(self, cmd: bytes) -> None:
        self.table.apply(cmd)
        self.applied += 1

    def lookup(self, key: bytes):
        return self.table.rows.get(key)

    def replay(self, updates, reads) -> int:
        """Apply `updates`, [(n, command)], over what is already applied,
        and on the way compare each of `reads`, [(applied, key, record)],
        with the table as it stood after `applied` commands. Returns how
        many differ; a read from before this replay's first command, or
        from after a gap in the n, counts as one."""
        wrong = at = 0
        reads = sorted(reads, key=lambda r: r[0])
        for n, cmd in sorted(updates, key=lambda u: u[0]):
            while at < len(reads) and reads[at][0] < n:
                wrong += self._differs(*reads[at])
                at += 1
            if n != self.applied + 1:
                wrong += 1  # acknowledged twice, or an n nobody was told
            self.apply(cmd)
        return wrong + sum(self._differs(*r) for r in reads[at:])

    def _differs(self, applied: int, key: bytes, record) -> bool:
        return applied != self.applied or record != self.lookup(key)


# site.ycsb.generator.ScrambledZipfianGenerator: the ranks of a Zipfian
# over ITEM_COUNT items, whatever the table holds, each hashed onto the
# table's key numbers; ZETAN is zeta(ITEM_COUNT, 0.99) as YCSB states it.
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
_HEAD_RANKS = 1 << 22  # ranks hashed one by one; the rest are spread evenly


def fnvhash64(val: np.ndarray) -> np.ndarray:
    """site.ycsb.Utils.fnvhash64 over a uint64 array: FNV-1 over the
    eight octets, low one first, and Java's Math.abs of the signed sum."""
    h = np.full(val.shape, 0xCBF29CE484222325, _U64)
    for _ in range(8):
        h = (h ^ (val & _U64(0xFF))) * _U64(1099511628211)
        val = val >> _U64(8)
    return np.abs(h.view(np.int64)).view(_U64)


def zeta(lo: int, hi: int, theta: float) -> float:
    """Sum of r ** -theta over the ranks (lo, hi], by Euler-Maclaurin's
    midpoint integral: within 1e-9 of the sum from lo = 2 ** 22 on."""
    return ((hi + 0.5) ** (1 - theta) - (lo + 0.5) ** (1 - theta)) / (1 - theta)


@functools.lru_cache(maxsize=4)
def scrambled_zipfian(recordcount: int, theta: float) -> np.ndarray:
    """p[key number] under YCSB's scrambled Zipfian: rank r of ITEM_COUNT
    has weight (r + 1) ** -theta and goes to key fnvhash64(r) %
    recordcount, collisions included. The first 2 ** 22 ranks (64 % of
    the weight at 0.99) are hashed one by one. The others, under 3e-7 of
    the hottest each and ITEM_COUNT / recordcount of them a key, are
    spread evenly, as a hash spreads them. The hottest key takes 1 /
    ZETAN = 3.8 % of the operations whatever the table's size."""
    if not 0.0 < theta < 1.0:
        raise ValueError("the Zipfian constant lies between 0 and 1")
    head = min(_HEAD_RANKS, ITEM_COUNT)
    weight = np.arange(1, head + 1, dtype=np.float64) ** -theta
    key = fnvhash64(np.arange(head, dtype=_U64)) % _U64(recordcount)
    p = np.bincount(key.astype(np.int64), weight, recordcount)
    p += zeta(head, ITEM_COUNT, theta) / recordcount
    p /= p.sum()
    p.setflags(write=False)
    return p


_ROWS = 256  # update rows and read rows are made a chunk at a time
_OPS = 1 << 16  # and the operation stream a block at a time


class Workload:
    """YCSB's core workload over `groups` partitions, from the seed.

    Popularity: requestdistribution=zipfian is YCSB's scrambled Zipfian
    (scrambled_zipfian above) over the key numbers [0, recordcount), the
    same in every run as in YCSB. Key number i is the record
    "user<hash of i>" and lives in group i % groups, so every group holds
    recordcount / groups records: the hash of the key onto the groups.

    The stream: operation i is a read with probability `readproportion`
    and goes to a group drawn by the groups' shares of the popularity.
    What it does there is that group's own next row: update row k of
    group g (after the group's inserts, rows [0, records a group)) and
    read row j of group g each draw a key from g's conditional
    distribution, so the rows of a group are fixed by the seed however
    the groups' operations interleave. That makes this object the
    `payloads` of benchmark.lib.loadgen.Ledger: cmds(g, lo, hi) and
    sum64(g, rows).
    """

    def __init__(self, seed: int, groups: int, recordcount: int,
                 zipfian_constant: float = 0.99, readproportion: float = 0.5,
                 fieldcount: int = 10, fieldlength: int = 100) -> None:
        if recordcount % groups:
            raise ValueError("recordcount must be a multiple of the groups")
        self.seed = seed
        self.groups = groups
        self.recordcount = recordcount
        self.per_group = per = recordcount // groups
        self.readproportion = readproportion
        self.fieldcount = fieldcount
        self.fieldlength = fieldlength
        self._vwords = _padded(fieldlength) // 8
        self.p_item = p_item = scrambled_zipfian(recordcount, zipfian_constant)
        self.by_popularity = np.argsort(-p_item, kind="stable")
        by_group = p_item.reshape(per, groups).T  # [g, slot]: item slot*G+g
        self.group_share = by_group.sum(axis=1)
        self._group_cdf = np.cumsum(self.group_share)
        self._key_cdf = np.cumsum(
            by_group / self.group_share[:, None], axis=1
        )
        # b"user" + the 20 decimal digits of a 64-bit hash of the item
        h = _mix64(np.arange(recordcount, dtype=_U64) + _U64(seed & _MASK64))
        pow10 = _U64(10) ** np.arange(19, -1, -1, dtype=_U64)
        keys = np.empty((recordcount, KEY_BYTES), np.uint8)
        keys[:, :4] = np.frombuffer(b"user", np.uint8)
        keys[:, 4:] = (h[:, None] // pow10 % _U64(10)).astype(np.uint8) + 48
        self._keys = keys
        self._ops: list = []  # blocks of (is_read, group)
        self._updates = [[] for _ in range(groups)]  # chunks: (blob, wsum)
        self._reads = [[] for _ in range(groups)]  # chunks of slots
        self._insert_sums: dict = {}

    # -------------------------------------------------------------- keys
    def item(self, g: int, slot: int) -> int:
        return slot * self.groups + g

    def key(self, g: int, slot: int) -> bytes:
        return self._keys[slot * self.groups + g].tobytes()

    def _salt(self, g: int, what: int) -> np.uint64:
        x = (self.seed * 0x9E3779B97F4A7C15 + g * 4 + what + 1) & _MASK64
        return _mix64(np.array([x], _U64))[0]

    def _uniform(self, g: int, what: int, lo: int, n: int) -> np.ndarray:
        """n uniforms in [0, 1) for rows [lo, lo + n) of stream `what` of
        group g: 53 bits of a hash of the row number."""
        k = np.arange(lo, lo + n, dtype=_U64)
        return (_mix64(k + self._salt(g, what)) >> _U64(11)) * 2.0 ** -53

    def _slots(self, g: int, u: np.ndarray) -> np.ndarray:
        slots = np.searchsorted(self._key_cdf[g], u, side="right")
        return np.minimum(slots, self.per_group - 1)

    # ---------------------------------------------------- operation stream
    def op(self, i: int):
        """(is a read, group) of operation i."""
        b = i // _OPS
        while len(self._ops) <= b:
            rng = np.random.default_rng([self.seed, 12, len(self._ops)])
            group = np.searchsorted(
                self._group_cdf, rng.random(_OPS), side="right"
            )
            self._ops.append((
                (rng.random(_OPS) < self.readproportion).tolist(),
                np.minimum(group, self.groups - 1).tolist(),
            ))
        is_read, group = self._ops[b]
        return is_read[i % _OPS], group[i % _OPS]

    def read_slot(self, g: int, j: int) -> int:
        """The record that read row j of group g asks for."""
        chunks = self._reads[g]
        while len(chunks) <= j // _ROWS:
            u = self._uniform(g, 1, len(chunks) * _ROWS, _ROWS)
            chunks.append(self._slots(g, u).tolist())
        return chunks[j // _ROWS][j % _ROWS]

    # ------------------------------------------------------------ commands
    def _values(self, g: int, what: int, lo: int, n: int, words: int):
        """[n, words * 8] seeded bytes for rows [lo, lo + n)."""
        k = np.arange(lo * words, (lo + n) * words, dtype=_U64)
        return _mix64(k + self._salt(g, what)).view(np.uint8).reshape(n, -1)

    def _inserts(self, g: int) -> np.ndarray:
        """The group's load phase, rows [0, per_group): row k inserts the
        record in slot k. Not kept: at the published sizes it is 1 KB a
        record."""
        per, L = self.per_group, self.fieldlength
        width = _padded(_BODY + self.fieldcount * L)
        rows = np.zeros((per, width), np.uint8)
        rows[:, 0] = OP_INSERT
        rows[:, _HEAD:_BODY] = self._keys[g::self.groups]
        body = self._values(g, 2, 0, per, _padded(self.fieldcount * L) // 8)
        rows[:, _BODY:_BODY + self.fieldcount * L] = body[:, :self.fieldcount * L]
        if g not in self._insert_sums:
            self._insert_sums[g] = rows.view("<u8").sum(axis=1, dtype=_U64)
        return rows

    def _update_chunk(self, g: int, c: int):
        chunks = self._updates[g]
        while len(chunks) <= c:
            lo, L = len(chunks) * _ROWS, self.fieldlength
            slots = self._slots(g, self._uniform(g, 3, lo, _ROWS))
            field = (self._uniform(g, 4, lo, _ROWS) * self.fieldcount).astype(
                np.uint8
            )
            rows = np.zeros((_ROWS, _padded(_BODY + L)), np.uint8)
            rows[:, 0] = OP_UPDATE
            rows[:, 1] = field
            rows[:, _HEAD:_BODY] = self._keys[slots * self.groups + g]
            rows[:, _BODY:_BODY + L] = self._values(
                g, 5, lo, _ROWS, self._vwords
            )[:, :L]
            chunks.append((
                rows.tobytes(), rows.view("<u8").sum(axis=1, dtype=_U64),
                rows.shape[1],
            ))
        return chunks[c]

    def cmds(self, g: int, lo: int, hi: int) -> list:
        """Rows [lo, hi) of group g as commands."""
        out = []
        per = self.per_group
        if lo < per:
            rows = self._inserts(g)
            out = [rows[k].tobytes() for k in range(lo, min(hi, per))]
        for k in range(max(lo, per) - per, hi - per):
            blob, _sums, width = self._update_chunk(g, k // _ROWS)
            at = (k % _ROWS) * width
            out.append(blob[at:at + width])
        return out

    def sum64(self, g: int, rows: int) -> int:
        """Sum of the words of rows [0, rows) of group g, mod 2^64."""
        per = self.per_group
        if g not in self._insert_sums:
            self._inserts(g)
        total = int(self._insert_sums[g][:min(rows, per)].sum(dtype=_U64))
        left = rows - per
        for c in range((left + _ROWS - 1) // _ROWS if left > 0 else 0):
            sums = self._update_chunk(g, c)[1][:min(_ROWS, left - c * _ROWS)]
            total += int(sums.sum(dtype=_U64))
        return total & _MASK64
