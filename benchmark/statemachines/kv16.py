"""kv16: the in-memory key-value state machine every configuration runs.

A 16-byte command is put(key = cmd[:8], value = cmd[8:16]). Beside the
table it keeps (entries applied, sum of both little-endian u64 words of
every command mod 2^64): cheap, and wrong if any payload byte is lost,
duplicated or replaced on the way through the WAL, the arena and apply.

lookup(None) -> (applied, sum64); lookup(key) -> value bytes or None.
"""
from __future__ import annotations

from dragonboat_tpu.statemachine import IConcurrentStateMachine, Result

_MASK64 = (1 << 64) - 1


class StateMachine(IConcurrentStateMachine):
    def __init__(self, cluster_id, node_id):
        self.table = {}
        self.state = (0, 0)

    def update(self, entries):
        n, acc = self.state
        table = self.table
        for e in entries:
            cmd = e.cmd
            table[cmd[:8]] = cmd[8:16]
            n += 1
            acc += int.from_bytes(cmd[:8], "little")
            acc += int.from_bytes(cmd[8:16], "little")
            e.result = Result(value=n)
        self.state = (n, acc & _MASK64)  # one store: lookups never tear
        return entries

    def lookup(self, query):
        if query is None:
            return self.state
        return self.table.get(query)

    def prepare_snapshot(self):
        return self.state, dict(self.table)

    def save_snapshot(self, ctx, w, fc, done):
        (n, acc), table = ctx
        w.write(n.to_bytes(8, "little") + acc.to_bytes(8, "little"))
        w.write(len(table).to_bytes(8, "little"))
        for k, v in table.items():
            w.write(k + v)

    def recover_from_snapshot(self, r, fc, done):
        head = r.read(24)
        self.state = (
            int.from_bytes(head[:8], "little"),
            int.from_bytes(head[8:16], "little"),
        )
        rows = int.from_bytes(head[16:], "little")
        body = r.read(16 * rows)
        self.table = {
            body[i:i + 8]: body[i + 8:i + 16] for i in range(0, len(body), 16)
        }

    def close(self):
        pass
