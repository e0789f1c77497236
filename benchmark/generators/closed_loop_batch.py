"""closed_loop_batch: every group keeps one batch of writes outstanding.

Parameters (the traffic file): `batch`, `timeout_s`, `warm_rounds`,
`poll_ms`.

Each group has one propose_batch_async of `batch` seeded writes in
flight on its leader's host and submits the next the moment the one
before is accounted for: callers that wait for a reply. The load follows
the system, so this mix is judged on what it completes, and the time a
batch takes is the loop's own queue (Little's law) and a per-layer
metric only.

The window opens once every group has finished `warm_rounds` batches:
the loop is then in its steady state, and the device-window compaction
(first run once a window is half full) has run. One thread submits and
looks; `poll_ms` apart.

Completed operations per second is the sum over the groups of what a
group had acknowledged in its whole cycles (submit to accounted-for)
inside the window, over the time those cycles took. A group is never
idle, so that is its rate; and it does not depend on where the window's
edges fall among the bursts. Groups move in step with the engine, so
acknowledgements arrive in bursts of up to a whole fleet's batches (65 536
operations, a quarter of a 15-second window at the first chip run), and a
plain count over the window would be quantised by them.
"""
from __future__ import annotations

import time

from benchmark.lib import loadgen
from dragonboat_tpu.requests import RequestError

clock = loadgen.clock


class Generator:
    def __init__(self, params: dict, groups: int, ledger, seed: int,
                 seconds: float, scale: float) -> None:
        self.groups = groups
        self.ledger = ledger
        self.seconds = float(seconds)
        self.batch = int(params["batch"])
        self.timeout_s = float(params["timeout_s"])
        self.warm_rounds = int(params["warm_rounds"])
        self.poll_s = float(params["poll_ms"]) / 1000.0
        self.t_open = self.t_close = 0.0
        # finished batches: (group, submitted, looked, completed, dropped)
        self.batches = []

    def warm(self, cluster) -> None:
        """The loop warms itself: measure() opens the window only after
        `warm_rounds` rounds."""

    def measure(self, cluster, on_open, on_close) -> None:
        G = self.groups
        inflight = [None] * G  # (handle, submitted, lo, hi)
        rounds = [0] * G
        leaders = cluster.leaders()
        next_refresh = clock() + 0.5
        opened = False
        while True:
            now = clock()
            if opened and now >= self.t_close:
                break
            for g in range(G):
                rec = inflight[g]
                if rec is not None:
                    if not rec[0].finished:
                        continue
                    self._finish(g, rec, now)
                    rounds[g] += 1
                inflight[g] = self._submit(cluster, leaders[g], g, now)
            if not opened and min(rounds) >= self.warm_rounds:
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
        deadline = clock() + self.timeout_s + 1.0
        while any(r is not None for r in inflight) and clock() < deadline:
            now = clock()
            for g in range(G):
                rec = inflight[g]
                if rec is not None and rec[0].finished:
                    self._finish(g, rec, now)
                    inflight[g] = None
            time.sleep(self.poll_s)
        for g in range(G):
            rec = inflight[g]
            if rec is not None:  # never accounted for: fate unknown
                h, t_sub, lo, hi = rec
                self.ledger.settle(g, lo, hi, h.completed, h.n - h.completed)
                self.batches.append(
                    (g, t_sub, 0.0, h.completed, h.n - h.completed)
                )

    def _submit(self, cluster, nid: int, g: int, now: float):
        lo, hi, cmds = self.ledger.take(g, self.batch)
        try:
            h = cluster.hosts[nid].propose_batch_async(
                cluster.session(nid, g), cmds, self.timeout_s
            )
        except RequestError:
            self.ledger.settle(g, lo, hi, 0, hi - lo)
            self.batches.append((g, now, now, 0, hi - lo))
            return None
        return h, now, lo, hi

    def _finish(self, g: int, rec, now: float) -> None:
        h, t_sub, lo, hi = rec
        dropped = h.n - h.completed
        self.ledger.settle(g, lo, hi, h.completed, dropped)
        self.batches.append((g, t_sub, now, h.completed, dropped))

    def results(self) -> dict:
        t0, t1 = self.t_open, self.t_close
        mine = [b for b in self.batches if t0 <= b[1] < t1]
        attempted = sum(c + d for _g, _s, _l, c, d in mine)
        failed = sum(d for _g, _s, _l, _c, d in mine)
        ops = [0] * self.groups
        busy = [0.0] * self.groups
        lat = []
        for g, sub, looked, c, _d in mine:
            if sub < looked < t1:  # a whole cycle inside the window
                ops[g] += c
                busy[g] += looked - sub
                lat.append((looked - sub) * 1000.0)
        if not all(busy):
            raise RuntimeError(
                "a group finished no batch inside the window: the window "
                "is too short for this configuration"
            )
        return {
            "attempted": attempted,
            "failed": failed,
            "reads_wrong": 0,
            "committed_ops_per_s": sum(n / t for n, t in zip(ops, busy)),
            "cycles": len(lat),
            "writes": attempted,
            "writes_acked": attempted - failed,
            "reads": 0,
            "client.commit_latency_p50_ms": loadgen.percentile(lat, 0.50),
            "client.commit_latency_p99_ms": loadgen.percentile(lat, 0.99),
        }
