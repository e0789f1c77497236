"""open_loop_rate: operations on a fixed schedule, whatever the system does.

Parameters (the traffic file): `rate_ops_per_s`, `reads_per_write` (0 =
writes only), `slot_ms`, `timeout_s`, `run_in_s`, `warm_batches`,
`warm_batch`.

The schedule is drawn from the seed before anything starts: Poisson
arrivals at the fixed rate (conditioned on their number, so every seed
offers the same amount of work), each for a uniformly chosen group. The
writes of one group that fall due in one slot go down in one
propose_batch_async on the leader's host when the slot ends; a read goes
down alone through read_index on the leader's host and asks for a key
whose write had been acknowledged when the read was issued (half of them
for the newest such key, where a stale answer would show). Nothing is
issued twice: a dropped write may yet commit, and no cell has faults that
would drop a read. Every
operation is timed from the instant it was DUE, not from when it was
issued, so a stall costs latency and never lowers the load. How late
operations were issued is reported beside the latencies.

One thread does all of it: it issues what is due, and between slots it
looks at the oldest unfinished batch of each group and stamps the ones
that finished (a BatchRequestState has an event and no completion time).
The gap between two such looks is the stamping resolution and is
reported. Reads are stamped exactly, in their on_complete callback.
"""
from __future__ import annotations

import time
from collections import deque
from functools import partial

import numpy as np

from benchmark.lib import check, loadgen
from dragonboat_tpu.requests import RequestError

clock = loadgen.clock


class Generator:
    def __init__(self, params: dict, groups: int, ledger, seed: int,
                 seconds: float, scale: float) -> None:
        self.p = params
        self.groups = groups
        self.ledger = ledger
        self.seconds = float(seconds)
        self.rate = float(params["rate_ops_per_s"]) * scale
        self.timeout_s = float(params["timeout_s"])
        self.run_in_s = float(params["run_in_s"])
        rpw = int(params["reads_per_write"])
        rng = np.random.default_rng([seed, 2])
        n_in = int(round(self.rate * self.run_in_s))
        n_win = int(round(self.rate * self.seconds))
        t1 = self.run_in_s + self.seconds
        due = np.concatenate([
            loadgen.conditioned_poisson(rng, n_in, 0.0, self.run_in_s),
            loadgen.conditioned_poisson(rng, n_win, self.run_in_s, t1),
        ])
        self.n = n = len(due)
        self.first_in_window = n_in
        slot = float(params["slot_ms"]) / 1000.0
        self.due = due.tolist()
        self.issue_at = ((np.floor(due / slot) + 1.0) * slot).tolist()
        self.group = rng.integers(0, groups, n).tolist()
        share = rpw / (rpw + 1.0)
        self.is_read = np.concatenate([
            loadgen.exact_share(rng, n_in, share),
            loadgen.exact_share(rng, n_win, share),
        ]).tolist()
        self.newest = (rng.random(n) < 0.5).tolist()
        self.frac = rng.random(n).tolist()
        # outcomes, by operation
        self.issued = [0.0] * n
        self.done = [0.0] * n  # acknowledgement time, 0 = none
        self.failed = [False] * n
        self.read_row = {}  # operation -> row asked for
        # (operation, time, completed, value), appended by callbacks
        self.read_out = []
        self.look_gaps = []
        self._pending = [deque() for _ in range(groups)]
        self._active = set()
        self.start = 0.0

    # ------------------------------------------------------------ warm-up
    def warm(self, cluster) -> None:
        """`warm_batches` batches of `warm_batch` writes per group, then a
        read per group: the engine's step, the device-window compaction
        (first run once a window is half full) and the ReadIndex path all
        run once before anything is timed."""
        leaders = cluster.leaders()
        for _ in range(int(self.p["warm_batches"])):
            hs = []
            for g in range(self.groups):
                lo, hi, cmds = self.ledger.take(g, int(self.p["warm_batch"]))
                nid = leaders[g]
                hs.append((g, lo, hi, cluster.hosts[nid].propose_batch_async(
                    cluster.session(nid, g), cmds, self.timeout_s)))
            for g, lo, hi, h in hs:
                h.wait(self.timeout_s + 1.0)
                self.ledger.settle(g, lo, hi, h.completed, h.n - h.completed)
        check.read_all(
            [(cluster.hosts[leaders[g]], g + 1) for g in range(self.groups)]
        )

    # ------------------------------------------------------------ measure
    def measure(self, cluster, on_open, on_close) -> None:
        n, issue_at = self.n, self.issue_at
        self.start = start = clock() + 0.05
        t_open = start + self.run_in_s
        t_close = t_open + self.seconds
        leaders = cluster.leaders()
        next_refresh = start + 0.5
        opened = closed = False
        last_look = 0.0
        i = 0
        while i < n or not closed:
            now = clock()
            if not opened and now >= t_open:
                on_open(t_open)
                opened = True
                last_look = 0.0
            if not closed and now >= t_close:
                on_close(t_close)
                closed = True
            self._look(now)
            if opened and not closed:
                if last_look:
                    self.look_gaps.append(now - last_look)
                last_look = now
            rel = now - start
            j = i
            while j < n and issue_at[j] <= rel:
                j += 1
            if j > i:
                self._issue(cluster, leaders, i, j)
                i = j
            if now >= next_refresh:
                next_refresh = now + 0.5
                fresh = cluster.leaders()
                leaders = [f or old for f, old in zip(fresh, leaders)]
            nxt = issue_at[i] - rel if i < n else t_close - now
            time.sleep(max(0.0, min(nxt, 0.001)))
        # everything is issued and the window is closed: wait for the rest
        deadline = clock() + self.timeout_s + 1.0
        while self._active and clock() < deadline:
            self._look(clock())
            time.sleep(0.001)
        n_reads = len(self.read_row)
        while len(self.read_out) < n_reads and clock() < deadline:
            time.sleep(0.001)

    def _issue(self, cluster, leaders, i: int, j: int) -> None:
        writes: dict = {}
        for k in range(i, j):
            g = self.group[k]
            if self.is_read[k]:
                self._issue_read(cluster, leaders[g], g, k)
            else:
                writes.setdefault(g, []).append(k)
        for g, ks in writes.items():
            lo, hi, cmds = self.ledger.take(g, len(ks))
            nid = leaders[g]
            try:
                h = cluster.hosts[nid].propose_batch_async(
                    cluster.session(nid, g), cmds, self.timeout_s
                )
            except RequestError:
                # refused at the door: nothing was queued, so nothing of
                # it can commit later, but the rows are spent
                self.ledger.settle(g, lo, hi, 0, hi - lo)
                for k in ks:
                    self.failed[k] = True
                continue
            t = clock()
            for k in ks:
                self.issued[k] = t
            self._pending[g].append((h, ks, lo, hi))
            self._active.add(g)

    def _issue_read(self, cluster, nid: int, g: int, k: int) -> None:
        readable = self.ledger.readable[g]
        row = readable - 1 if self.newest[k] else int(self.frac[k] * readable)
        nh = cluster.hosts[nid]
        self.read_row[k] = row
        try:
            rs = nh.read_index(g + 1, self.timeout_s)
        except RequestError:
            self.read_out.append((k, clock(), False, None))
            return
        self.issued[k] = clock()
        rs.on_complete(
            partial(self._read_done, k, nh, g + 1, loadgen.Payloads.key(row))
        )

    def _read_done(self, k: int, nh, cid: int, key: bytes, rs) -> None:
        # on the completing engine thread: brief, never blocks
        ok = rs.result.completed
        value = nh.read_local_node(cid, key) if ok else None
        self.read_out.append((k, clock(), ok, value))

    def _look(self, now: float) -> None:
        """Stamp every batch that has finished; a group's batches commit
        in order, so only its oldest needs looking at."""
        idle = []
        for g in self._active:
            dq = self._pending[g]
            while dq and dq[0][0].finished:
                h, ks, lo, hi = dq.popleft()
                dropped = h.n - h.completed
                self.ledger.settle(g, lo, hi, h.completed, dropped)
                for pos, k in enumerate(ks):
                    if pos < h.completed:
                        self.done[k] = now
                    else:
                        self.failed[k] = True
            if not dq:
                idle.append(g)
        self._active.difference_update(idle)

    # ------------------------------------------------------------ results
    def results(self) -> dict:
        """Latencies over the operations DUE in the window. A failed
        operation counts at its timeout."""
        wrong = 0
        for k, t, ok, value in self.read_out:
            if not ok:
                self.failed[k] = True
                continue
            self.done[k] = t
            want = self.ledger.payloads.value(self.group[k], self.read_row[k])
            wrong += value != want
        lo, n = self.first_in_window, self.n
        timeout_ms = self.timeout_s * 1000.0
        w_lat, r_lat, late = [], [], []
        failed = w_acked = 0
        for k in range(lo, n):
            ok = bool(self.done[k]) and not self.failed[k]
            if ok:
                ms = (self.done[k] - self.start - self.due[k]) * 1000.0
            else:
                ms = timeout_ms
                failed += 1
            if self.is_read[k]:
                r_lat.append(ms)
            else:
                w_lat.append(ms)
                w_acked += ok
            if self.issued[k]:
                late.append(
                    (self.issued[k] - self.start - self.issue_at[k]) * 1000.0
                )
        half = len(w_lat) // 2
        out = {
            "attempted": n - lo,
            "failed": failed,
            "reads_wrong": wrong,
            "committed_ops_per_s": (n - lo - failed) / self.seconds,
            "writes": len(w_lat),
            "writes_acked": w_acked,
            "reads": len(r_lat),
            "client.late_p99_ms": loadgen.percentile(late, 0.99),
            "client.late_p50_ms": loadgen.percentile(late, 0.50),
            "client.stamp_resolution_ms":
                loadgen.percentile(self.look_gaps, 0.99) * 1000.0,
            "commit_latency_p50_ms": loadgen.percentile(w_lat, 0.50),
            "client.commit_latency_p99_ms": loadgen.percentile(w_lat, 0.99),
            # writes are in due order: a queue that grows shows as a second
            # half slower than the first (benchmark/sweep.py stops on it)
            "commit_latency_p50_ms_first_half":
                loadgen.percentile(w_lat[:half], 0.50),
            "commit_latency_p50_ms_second_half":
                loadgen.percentile(w_lat[half:], 0.50),
        }
        if r_lat:
            out["read_latency_p50_ms"] = loadgen.percentile(r_lat, 0.50)
            out["client.read_latency_p99_ms"] = loadgen.percentile(r_lat, 0.99)
        return out
