"""closed_loop_mixed: clients with one operation each, nine reads to a write.

Parameters (the traffic file): `clients`, `reads_per_write`,
`payload_bytes` (16: kv16's rows), `timeout_s`, `poll_ms`, `warm_batch`,
`warm_ops`, `run_bound_s`.

The bound comes first: `run_bound_s` after the generator is made (run.py
makes it before the backend starts), the process dumps every thread's
stack and exits non-zero, whatever phase it is in. It replaces run.py's
own watchdog, and run.py cancels it before it prints the result, so a
bring-up that would outlast the run's limit ends by its own exit.
0 leaves run.py's watchdog alone (an in-process test).

The stream is drawn from the seed in blocks of `BLOCK` operations, each
an exact share of reads (loadgen.exact_share): every operation's group
is uniform over the fleet, and every block of 1 000 holds 900 reads and
100 writes at nine reads to a write. `clients` is the deployment's and
scales with the share of its groups that a rehearsal runs.

warm(): one propose_batch_async of `warm_batch` rows a group on its
leader's host, then one linearizable read a group; then the program's
bring-up account (VectorEngine.bringup_stats, where the program has
one) goes into the client's numbers under the names of its per-layer
metrics. measure(): every client submits one operation and, once the
one thread that plays them all has accounted for it, the next. A read is
read_index on the leader's host and, in the completion callback,
read_local_node of one row: the newest row of its group acknowledged
when the read was issued, or with probability one half a uniformly
chosen older one. A write is one 16-byte row through propose_batch_async
of one command on the leader's host, settled in run.py's Ledger. The
window opens once every client has finished `warm_ops` operations.

What decides `reads_wrong`, exact: every acknowledged read's value
against loadgen.Payloads' row (a row acknowledged before the read was
issued must be there; one whose write was cut short may be there or
not, and if it is, it is the row's value). run.py's check.read_back
follows, as for every cell.

`committed_ops_per_s` is counted as ycsb_closed counts it: every
acknowledged operation is one unit of work spread evenly from its
submission to its acknowledgement, and the rate is the work that falls
inside [t_open, t_close) over the window's length. `failed` counts the
operations submitted in the window that were not acknowledged, and
`writes_acked` the writes submitted in it that were (the denominator of
`storage.fsyncs_per_kop`, as every other generator gives it).
"""
from __future__ import annotations

import faulthandler
import time
from collections import deque
from functools import partial

import numpy as np

from benchmark.lib import check, loadgen
from dragonboat_tpu.requests import RequestError

clock = loadgen.clock

BLOCK = 1000  # operations a draw; an exact share of reads in each
# the program's bring-up account, as bringup_stats() names it -> the
# name the client's numbers carry it under (the per-layer metric's)
BRINGUP = {
    "start_clusters_s": "setup.start_clusters_s",
    "activate_s": "setup.activate_s",
    "elect_launches": "setup.elect_launches",
}
ACCOUNT_WAIT_S = 30.0  # for the first launch after which all lanes lead


class Generator:
    def __init__(self, params: dict, groups: int, ledger, seed: int,
                 seconds: float, scale: float) -> None:
        self.t_made = clock()
        self.run_bound_s = float(params["run_bound_s"])
        if self.run_bound_s > 0:
            faulthandler.dump_traceback_later(self.run_bound_s, exit=True)
        if int(params["payload_bytes"]) != 16:
            raise ValueError("closed_loop_mixed writes kv16's 16-byte rows")
        rpw = int(params["reads_per_write"])
        if BLOCK % (rpw + 1):
            raise ValueError(f"a block of {BLOCK} holds no exact share")
        self.read_share = rpw / (rpw + 1.0)
        self.groups = groups
        self.ledger = ledger
        self.seconds = float(seconds)
        self.clients = max(1, int(round(int(params["clients"]) * scale)))
        self.timeout_s = float(params["timeout_s"])
        self.poll_s = float(params["poll_ms"]) / 1000.0
        self.warm_batch = int(params["warm_batch"])
        self.warm_ops = int(params["warm_ops"])
        self._rng = np.random.default_rng([seed, 40])
        # the stream, drawn a block at a time: group, is a read, asks
        # for the newest row, where an older row falls
        self.s_group: list = []
        self.s_read: list = []
        self.s_newest: list = []
        self.s_frac: list = []
        # one entry an operation, in the order of issue
        self.o_client: list = []
        self.o_row: list = []  # write: its row; read: -1 - the row asked
        self.o_sure: list = []  # read: the row was acknowledged at issue
        self.o_issue: list = []
        self.o_done: list = []  # acknowledgement time, 0 = none
        self.o_ok: list = []
        self._done: deque = deque()  # (operation, time, ok, value)
        self._writes: dict = {}  # operation -> BatchRequestState
        self.reads_wrong = 0
        self.t_open = self.t_close = 0.0
        self.bringup: dict = {}

    def _draw(self, i: int) -> None:
        """Extend the stream to hold operation i."""
        while len(self.s_group) <= i:
            rng = self._rng
            self.s_group += rng.integers(0, self.groups, BLOCK).tolist()
            self.s_read += loadgen.exact_share(
                rng, BLOCK, self.read_share
            ).tolist()
            self.s_newest += (rng.random(BLOCK) < 0.5).tolist()
            self.s_frac += rng.random(BLOCK).tolist()

    # -------------------------------------------------------------- warm-up
    def warm(self, cluster) -> None:
        leaders = cluster.leaders()
        hs = []
        for g in range(self.groups):
            lo, hi, cmds = self.ledger.take(g, self.warm_batch)
            nid = leaders[g]
            hs.append((g, lo, hi, cluster.hosts[nid].propose_batch_async(
                cluster.session(nid, g), cmds, self.timeout_s)))
        for g, lo, hi, h in hs:
            h.wait(self.timeout_s + 1.0)
            self.ledger.settle(g, lo, hi, h.completed, h.n - h.completed)
        check.read_all(
            [(cluster.hosts[leaders[g]], g + 1) for g in range(self.groups)]
        )
        self.bringup = self._bringup_account(cluster.core)

    @staticmethod
    def _bringup_account(core) -> dict:
        """The program's bring-up account under the metrics' names; {} on
        a program that keeps none. The launch after which every lane knows
        a leader may come a little after the harness's own look (which
        asks one host's replicas): waited for, boundedly."""
        stats = getattr(core, "bringup_stats", None)
        if stats is None:
            return {}
        deadline = clock() + ACCOUNT_WAIT_S
        b = stats()
        while b.get("elect_launches") is None and clock() < deadline:
            time.sleep(0.05)
            b = stats()
        out = {name: b[key] for key, name in BRINGUP.items()
               if b.get(key) is not None}
        out["bringup"] = b
        return out

    # -------------------------------------------------------------- measure
    def measure(self, cluster, on_open, on_close) -> None:
        C = self.clients
        finished = [0] * C
        warming = C
        leaders = cluster.leaders()
        next_refresh = clock() + 0.5
        for c in range(C):
            self._issue(cluster, leaders, c, clock())
        opened = False
        while True:
            now = clock()
            if opened and now >= self.t_close:
                break
            for c in self._accounted(now):
                finished[c] += 1
                warming -= finished[c] == self.warm_ops
                self._issue(cluster, leaders, c, clock())
            if not opened and not warming:
                self.t_open = clock()
                self.t_close = self.t_open + self.seconds
                on_open(self.t_open)
                opened = True
            if now >= next_refresh:
                next_refresh = now + 0.5
                fresh = cluster.leaders()
                leaders = [f or old for f, old in zip(fresh, leaders)]
            time.sleep(self.poll_s)
        on_close(self.t_close)
        outstanding = sum(1 for t in self.o_done if not t)
        deadline = clock() + self.timeout_s + 1.0
        while outstanding and clock() < deadline:
            outstanding -= len(self._accounted(clock()))
            time.sleep(self.poll_s)
        for i, h in self._writes.items():  # never told: fate unknown
            g, row = self.s_group[i], self.o_row[i]
            self.ledger.settle(g, row, row + 1, h.completed, 1 - h.completed)

    def _issue(self, cluster, leaders, c: int, now: float) -> None:
        """Client c submits the stream's next operation."""
        i = len(self.o_client)
        self._draw(i)
        g = self.s_group[i]
        nid = leaders[g]
        nh = cluster.hosts[nid]
        self.o_client.append(c)
        self.o_issue.append(now)
        self.o_done.append(0.0)
        self.o_ok.append(False)
        if self.s_read[i]:
            readable = self.ledger.readable[g]
            row = (readable - 1 if self.s_newest[i]
                   else int(self.s_frac[i] * readable))
            row = max(row, 0)
            self.o_row.append(-1 - row)
            self.o_sure.append(row < readable)
            try:
                nh.read_index(g + 1, self.timeout_s).on_complete(partial(
                    self._read_done, i, nh, g + 1, loadgen.Payloads.key(row)
                ))
            except RequestError:
                self._done.append((i, now, False, None))
            return
        row, _hi, cmds = self.ledger.take(g, 1)
        self.o_row.append(row)
        self.o_sure.append(True)
        try:
            self._writes[i] = cluster.hosts[nid].propose_batch_async(
                cluster.session(nid, g), cmds, self.timeout_s
            )
        except RequestError:
            # refused at the door: nothing was queued, the row is spent
            self.ledger.settle(g, row, row + 1, 0, 1)
            self._done.append((i, now, False, None))

    # on the completing engine thread: brief, never blocks
    def _read_done(self, i: int, nh, cid: int, key: bytes, rs) -> None:
        if rs.result.completed:
            self._done.append((i, clock(), True, nh.read_local_node(cid, key)))
        else:
            self._done.append((i, clock(), False, None))

    def _accounted(self, now: float) -> list:
        """Account every operation that finished since the last look;
        their clients, in that order."""
        out = []
        for _ in range(len(self._done)):
            i, t, ok, value = self._done.popleft()
            self.o_done[i] = t
            self.o_ok[i] = ok
            if ok:
                g, row = self.s_group[i], -1 - self.o_row[i]
                want = self.ledger.payloads.value(g, row)
                self.reads_wrong += not (
                    value == want or (value is None and not self.o_sure[i])
                )
            out.append(self.o_client[i])
        done = [i for i, h in self._writes.items() if h.finished]
        for i in done:
            h = self._writes.pop(i)
            ok = h.completed == 1
            g, row = self.s_group[i], self.o_row[i]
            self.ledger.settle(g, row, row + 1, int(ok), int(not ok))
            self.o_done[i] = h.completed_at or now
            self.o_ok[i] = ok
            out.append(self.o_client[i])
        return out

    # -------------------------------------------------------------- results
    def results(self) -> dict:
        t0, t1 = self.t_open, self.t_close
        attempted = failed = reads = writes_acked = 0
        work = 0.0  # acknowledged operations, by their share inside
        completed = 0
        lat = {True: [], False: []}  # by "is a read", ms
        for i in range(len(self.o_client)):
            issued, done, ok = self.o_issue[i], self.o_done[i], self.o_ok[i]
            if ok:
                inside = min(done, t1) - max(issued, t0)
                if inside > 0.0:
                    work += inside / max(done - issued, 1e-9)
                completed += t0 <= done < t1
            if not t0 <= issued < t1:
                continue
            is_read = self.o_row[i] < 0
            attempted += 1
            failed += not ok
            reads += is_read
            writes_acked += ok and not is_read
            if ok:
                lat[is_read].append((done - issued) * 1000.0)
        out = {
            "attempted": attempted,
            "failed": failed,
            "reads_wrong": self.reads_wrong,
            "committed_ops_per_s": work / (t1 - t0),
            "completed_in_window_per_s": completed / (t1 - t0),
            "clients": self.clients,
            "reads": reads,
            "writes": attempted - reads,
            "writes_acked": writes_acked,
            "read_share_issued": reads / max(1, attempted),
            **self.bringup,
        }
        for is_read, name in ((True, "read"), (False, "write")):
            if lat[is_read]:
                out[f"{name}_p50_ms"] = loadgen.percentile(lat[is_read], 0.5)
                out[f"{name}_p99_ms"] = loadgen.percentile(lat[is_read], 0.99)
        return out
