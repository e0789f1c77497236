"""kv128: kv16's key-value state machine for 128-byte commands, with the
plain reference and the seeded rows of `fleet-1024x5-drops`.

A 128-byte command is put(key = cmd[:8], value = cmd[8:16]) followed by
112 seeded bytes. Beside the table the state machine keeps (entries
applied, sum of all sixteen little-endian u64 words of every command mod
2^64): wrong if any byte of any command is lost, doubled or replaced on
the way through the arena, a Replicate that was sent twice, the WAL and
apply.

lookup(None) -> (applied, sum64); lookup(key) -> value bytes or None.

`Payloads` is what the cell's generator hands run.py's Ledger
(`ledger.payloads`): cmds(g, lo, hi) and sum64(g, rows), every row a
function of the seed alone and made without the program.
"""
from __future__ import annotations

import numpy as np

from benchmark.lib.loadgen import _mix64
from benchmark.statemachines import kv16
from dragonboat_tpu.statemachine import Result

_MASK64 = (1 << 64) - 1
_WORDS = 16
CMD_BYTES = 8 * _WORDS
_CHUNK = 1024
_U64 = np.uint64


class StateMachine(kv16.StateMachine):
    """kv16's table, snapshot image and lookups; only the sum differs."""

    def update(self, entries):
        n, acc = self.state
        table = self.table
        cmds = []
        for e in entries:
            cmd = e.cmd
            table[cmd[:8]] = cmd[8:16]
            n += 1
            cmds.append(cmd)
            e.result = Result(value=n)
        # every word of every command of the run in one sum (it wraps mod
        # 2^64, as the reference's does)
        words = np.frombuffer(b"".join(cmds), "<u8")
        acc += int(words.sum(dtype=_U64))
        self.state = (n, acc & _MASK64)  # one store: lookups never tear
        return entries


class Payloads:
    """The 128-byte commands of every group, made from the seed on demand.

    Row k of group g is sixteen u64 words, little-endian: word 0 is k
    (keys are unique inside a group), word j > 0 is mix(salt(seed, g) +
    16 k + j). Rows are built a chunk at a time, so a closed loop may
    take as many as the system commits."""

    def __init__(self, seed: int, groups: int) -> None:
        self._seed = seed
        self._chunks = [[] for _ in range(groups)]

    def _chunk(self, g: int, c: int):
        chunks = self._chunks[g]
        while len(chunks) <= c:
            k = np.arange(
                len(chunks) * _CHUNK, (len(chunks) + 1) * _CHUNK, dtype=_U64
            )
            salt = _mix64(np.array(
                [(self._seed * 0x9E3779B97F4A7C15 + g + 1) & _MASK64], _U64
            ))[0]
            words = _mix64(
                salt + k[:, None] * _U64(_WORDS)
                + np.arange(_WORDS, dtype=_U64)[None, :]
            )
            words[:, 0] = k
            rows = words.astype("<u8")
            # per-row sums wrap mod 2^64, as the state machine's do
            chunks.append((rows.tobytes(), rows.sum(axis=1, dtype=_U64)))
        return chunks[c]

    def cmds(self, g: int, lo: int, hi: int) -> list:
        out = []
        for k in range(lo, hi):
            blob = self._chunk(g, k // _CHUNK)[0]
            off = (k % _CHUNK) * CMD_BYTES
            out.append(blob[off:off + CMD_BYTES])
        return out

    def sum64(self, g: int, rows: int) -> int:
        """Sum of all sixteen words of rows [0, rows) of group g, mod 2^64."""
        total = 0
        for c in range((rows + _CHUNK - 1) // _CHUNK):
            sums = self._chunk(g, c)[1][:min(_CHUNK, rows - c * _CHUNK)]
            total += int(sums.sum(dtype=_U64))
        return total & _MASK64
