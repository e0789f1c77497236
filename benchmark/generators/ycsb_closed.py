"""ycsb_closed: YCSB's client threads, each with one operation outstanding.

Parameters (the traffic file): `clients`, `readproportion`,
`updateproportion`, `requestdistribution`, `zipfian_constant`,
`recordcount`, `fieldcount`, `fieldlength`, `readallfields`,
`writeallfields`, `timeout_s`, `poll_ms`, `load_batch`, `warm_ops`,
`readback_keys`, `readback_hot`.

The stream is statemachines/kvrecords.Workload, made from the seed before
the backend starts: YCSB's scrambled Zipfian keys over the groups, reads
and updates by their proportions. `clients` and `recordcount` are the
deployment's and scale with the share of its groups that a rehearsal
runs; the record's shape does not.

warm() is YCSB's load phase: every record of a group inserted through
Raft in batches of `load_batch`, then one linearizable read a group.
measure() is its run phase. A client submits ONE operation, alone: an
update through NodeHost.propose on the leader's host, a read through
read_index and then read_local_node(key) in the completion callback. One
thread plays all the clients: every `poll_ms` it accounts for what
completed since its last look and submits the successor of each. The
window opens once every client has finished `warm_ops` operations.

Completed operations per second is all the work over all the window:
every acknowledged operation counts as one unit of work spread evenly
from its submission to its acknowledgement, and the rate is the work
that falls inside [t_open, t_close) over the window's length. Nothing
is left out: an operation submitted before the window or acknowledged
after it counts by the share of its life inside, one that waits long
counts little a second, and one that fails counts nothing, so a lane
that stalls shows in full. Counting whole operations by the instant of
their acknowledgement (`completed_in_window_per_s`, beside it in the
client's numbers) has the same expectation but moves by a launch's
worth, 5 % of a 15 s window, with where the window's end falls among
the bursts in which the engine acknowledges. Latency is the closed
loop's own queue and a per-layer metric only.

What decides `reads_wrong`, all exact: (1) every read's (record,
applied) against the plain reference (kvrecords.Reference) replayed, in
the order of the n the system answered its updates with, to `applied`;
(2) `applied` no lower than the highest n acknowledged in the group
before the read was issued; (3) after the drain, `readback_keys` seeded
keys, the `readback_hot` most popular among them, read linearizably on
the leader's host and locally on every replica, field by field. A group
with an update whose fate the client was not told is left out of (1)
and (3), as loadgen.Ledger leaves it out of the count.
"""
from __future__ import annotations

import importlib.util
import os
import time
from collections import deque
from functools import partial

import numpy as np

from benchmark.lib import check, loadgen
from dragonboat_tpu.requests import RequestError

clock = loadgen.clock


def _kvrecords():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "statemachines", "kvrecords.py",
    )
    spec = importlib.util.spec_from_file_location(
        "benchmark_statemachines_kvrecords", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Generator:
    def __init__(self, params: dict, groups: int, ledger, seed: int,
                 seconds: float, scale: float) -> None:
        if params["requestdistribution"] != "zipfian":
            raise ValueError("ycsb_closed draws keys from a scrambled Zipfian")
        if not params["readallfields"] or params["writeallfields"]:
            raise ValueError(
                "ycsb_closed reads whole records and updates one field"
            )
        read_share = float(params["readproportion"])
        if abs(read_share + float(params["updateproportion"]) - 1.0) > 1e-9:
            raise ValueError("ycsb_closed runs reads and updates only")
        self.kv = _kvrecords()
        self.groups = groups
        self.ledger = ledger
        self.seed = seed
        self.seconds = float(seconds)
        self.clients = max(1, int(round(int(params["clients"]) * scale)))
        self.timeout_s = float(params["timeout_s"])
        self.poll_s = float(params["poll_ms"]) / 1000.0
        self.load_batch = int(params["load_batch"])
        self.warm_ops = int(params["warm_ops"])
        self.readback_keys = int(params["readback_keys"])
        self.readback_hot = int(params["readback_hot"])
        self.fields = (int(params["fieldcount"]), int(params["fieldlength"]))
        self.workload = self.kv.Workload(
            seed, groups, int(round(int(params["recordcount"]) * scale)),
            float(params["zipfian_constant"]), read_share, *self.fields,
        )
        ledger.payloads = self.workload  # cmds(g, lo, hi) and sum64(g, rows)
        self.t_open = self.t_close = 0.0
        # one entry an operation, in the order of issue
        self.o_client: list = []
        self.o_group: list = []
        self.o_row: list = []  # update: its row; read: -1 - the slot asked
        self.o_issue: list = []
        self.o_floor: list = []  # read: highest n acknowledged at issue
        self.o_done: list = []  # stamped on the completing thread
        self.o_ok: list = []
        self.o_n: list = []  # update: its n; read: applied
        self.o_record: list = []
        self.acked_n = [0] * groups  # highest n acknowledged, per group
        self._done: deque = deque()  # (operation, time, ok, n, record)
        self._reads_issued = [0] * groups
        self.check: dict = {}

    # ---------------------------------------------------------- load phase
    def warm(self, cluster) -> None:
        leaders = cluster.leaders()
        per = self.workload.per_group
        for lo in range(0, per, self.load_batch):
            hs = []
            for g in range(self.groups):
                lo_, hi, cmds = self.ledger.take(g, min(self.load_batch, per - lo))
                nid = leaders[g]
                hs.append((g, lo_, hi, cluster.hosts[nid].propose_batch_async(
                    cluster.session(nid, g), cmds, self.timeout_s)))
            for g, lo_, hi, h in hs:
                h.wait(self.timeout_s + 1.0)
                self.ledger.settle(g, lo_, hi, h.completed, h.n - h.completed)
        check.read_all(
            [(cluster.hosts[leaders[g]], g + 1) for g in range(self.groups)]
        )

    # ----------------------------------------------------------- run phase
    def measure(self, cluster, on_open, on_close) -> None:
        C = self.clients
        finished = [0] * C
        warming = C
        leaders = cluster.leaders()
        next_refresh = clock() + 0.5
        for c in range(C):
            self._issue(cluster, leaders, c, clock())
        outstanding = C
        opened = False
        while True:
            now = clock()
            if opened and now >= self.t_close:
                break
            for _ in range(len(self._done)):
                c = self._account(self._done.popleft())
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
        deadline = clock() + self.timeout_s + 1.0
        while outstanding and clock() < deadline:
            for _ in range(len(self._done)):
                self._account(self._done.popleft())
                outstanding -= 1
            time.sleep(self.poll_s)
        for i, t in enumerate(self.o_done):
            if not t and self.o_row[i] >= 0:  # never told: fate unknown
                g, row = self.o_group[i], self.o_row[i]
                self.ledger.settle(g, row, row + 1, 0, 1)
        self.check = self._read_back(cluster)

    def _issue(self, cluster, leaders, c: int, now: float) -> None:
        """Client c submits the stream's next operation, alone."""
        i = len(self.o_client)
        is_read, g = self.workload.op(i)
        nh = cluster.hosts[leaders[g]]
        self.o_client.append(c)
        self.o_group.append(g)
        self.o_issue.append(now)
        self.o_done.append(0.0)
        self.o_ok.append(False)
        self.o_n.append(0)
        self.o_record.append(None)
        try:
            if is_read:
                j = self._reads_issued[g]
                self._reads_issued[g] = j + 1
                slot = self.workload.read_slot(g, j)
                self.o_row.append(-1 - slot)
                self.o_floor.append(self.acked_n[g])
                nh.read_index(g + 1, self.timeout_s).on_complete(partial(
                    self._read_done, i, nh, g + 1, self.workload.key(g, slot)
                ))
            else:
                row, _hi, cmds = self.ledger.take(g, 1)
                self.o_row.append(row)
                self.o_floor.append(0)
                nh.propose(
                    cluster.session(leaders[g], g), cmds[0], self.timeout_s
                ).on_complete(partial(self._update_done, i, g))
        except RequestError:
            # refused at the door; its client draws again at the next look
            self._done.append((i, now, False, 0, None))

    # on the completing engine thread: brief, never blocks
    def _update_done(self, i: int, g: int, rs) -> None:
        r = rs.result
        n = r.result.value if r.completed else 0
        if n > self.acked_n[g]:
            self.acked_n[g] = n
        self._done.append((i, clock(), r.completed, n, None))

    def _read_done(self, i: int, nh, cid: int, key: bytes, rs) -> None:
        if rs.result.completed:
            record, applied = nh.read_local_node(cid, key)
            self._done.append((i, clock(), True, applied, record))
        else:
            self._done.append((i, clock(), False, 0, None))

    def _account(self, done) -> int:
        i, t, ok, n, record = done
        self.o_done[i] = t
        self.o_ok[i] = ok
        self.o_n[i] = n
        self.o_record[i] = record
        row = self.o_row[i]
        if row >= 0:
            self.ledger.settle(self.o_group[i], row, row + 1, ok, not ok)
        return self.o_client[i]

    # ------------------------------------------------- after the run phase
    def _read_back(self, cluster) -> dict:
        """The three comparisons of the module's docstring, over every
        group whose updates were all acknowledged."""
        wl, ledger, hosts = self.workload, self.ledger, cluster.hosts
        rng = np.random.default_rng([self.seed, 13])
        hot = wl.by_popularity[:min(self.readback_hot, wl.recordcount)]
        rest = np.setdiff1d(np.arange(wl.recordcount), hot)
        more = max(0, min(self.readback_keys, wl.recordcount) - len(hot))
        items = np.concatenate([hot, rng.choice(rest, more, replace=False)])
        sample: dict = {}  # group -> slots
        for item in items.tolist():
            sample.setdefault(item % self.groups, []).append(item // self.groups)
        exact = [g for g in sample if not ledger.indeterminate[g]]
        leaders = cluster.wait_leaders(check.READ_S)
        # every ReadIndex goes down first and is waited for after
        states = [
            hosts[leaders[g]].read_index(g + 1, check.READ_TRY_S) for g in exact
        ]
        got: dict = {}  # (group, slot) -> [(record, applied)] by reader
        for g, rs in zip(exact, states):
            nh = hosts[leaders[g]]
            if not rs.wait(check.READ_TRY_S + 1.0).completed:
                check.sync_read(nh, g + 1, None)
            for slot in sample[g]:
                got[g, slot] = [nh.read_local_node(g + 1, wl.key(g, slot))]
        deadline = clock() + check.CONVERGE_S
        for g in exact:  # every replica has applied what was acknowledged
            for nh in hosts.values():
                while nh.stale_read(g + 1, None)[0] < ledger.used[g]:
                    if clock() >= deadline:
                        raise loadgen.CheckFailure(
                            f"group {g + 1}: a replica stayed behind "
                            f"{ledger.used[g]} for {check.CONVERGE_S:.0f}s"
                        )
                    time.sleep(0.02)
                for slot in sample[g]:
                    got[g, slot].append(nh.stale_read(g + 1, wl.key(g, slot)))

        updates = [[] for _ in range(self.groups)]
        reads = [[] for _ in range(self.groups)]
        stale = 0
        for i, ok in enumerate(self.o_ok):
            if not ok:
                continue
            g, row, n = self.o_group[i], self.o_row[i], self.o_n[i]
            if row >= 0:
                updates[g].append((n, wl.cmds(g, row, row + 1)[0]))
            else:
                reads[g].append((n, wl.key(g, -1 - row), self.o_record[i]))
                stale += n < self.o_floor[i]
        reads_differ = records_differ = reads_checked = 0
        for g in range(self.groups):
            if ledger.indeterminate[g]:
                continue
            ref = self.kv.Reference(*self.fields)
            for cmd in wl.cmds(g, 0, wl.per_group):
                ref.apply(cmd)
            reads_differ += ref.replay(updates[g], reads[g])
            reads_checked += len(reads[g])
            for slot in sample.get(g, ()):
                want = (ref.lookup(wl.key(g, slot)), ledger.used[g])
                records_differ += sum(r != want for r in got[g, slot])
        return {
            "reads_checked": reads_checked,
            "reads_differ": reads_differ,
            "reads_stale": stale,
            "records_read_back": sum(len(v) for v in got.values()),
            "records_differ": records_differ,
            "groups_exact": self.groups - sum(map(bool, ledger.indeterminate)),
        }

    # -------------------------------------------------------------- results
    def results(self) -> dict:
        t0, t1 = self.t_open, self.t_close
        n = len(self.o_client)
        last = [None] * self.clients  # client -> its operation before
        per_group = [0] * self.groups
        attempted = failed = reads = writes = acked = completed = 0
        work = 0.0  # acknowledged operations, by their share inside the window
        lat = {True: [], False: []}  # by "is a read", ms
        gaps = []
        thirds = [[], [], []]  # updates, by when in the window they ended
        for i in range(n):
            c, issued, done = self.o_client[i], self.o_issue[i], self.o_done[i]
            if self.o_ok[i]:
                inside = min(done, t1) - max(issued, t0)
                if inside > 0.0:
                    work += inside / (done - issued)
                if t0 <= done < t1:
                    completed += 1
                    if self.o_row[i] >= 0:
                        thirds[int((done - t0) / (t1 - t0) * 3)].append(
                            (done - issued) * 1000.0
                        )
            prev = last[c]
            last[c] = i
            if not t0 <= issued < t1:
                continue
            if prev is not None and self.o_done[prev]:
                gaps.append((issued - self.o_done[prev]) * 1000.0)
            is_read, ok = self.o_row[i] < 0, self.o_ok[i]
            attempted += 1
            failed += not ok
            reads += is_read
            writes += not is_read
            acked += ok and not is_read
            per_group[self.o_group[i]] += 1
            if ok:
                lat[is_read].append((self.o_done[i] - issued) * 1000.0)
        c = self.check
        out = {
            "attempted": attempted,
            "failed": failed,
            "reads_wrong":
                c["reads_differ"] + c["reads_stale"] + c["records_differ"],
            "committed_ops_per_s": work / (t1 - t0),
            "completed_in_window_per_s": completed / (t1 - t0),
            "clients": self.clients,
            "writes": writes,
            "writes_acked": acked,
            "reads": reads,
            "client.ycsb_hot_group_share": max(per_group) / max(1, attempted),
            "offered_hot_group_share": float(self.workload.group_share.max()),
            **c,
        }
        if lat[False]:
            out["client.ycsb_update_p50_ms"] = loadgen.percentile(lat[False], 0.5)
            out["client.ycsb_update_p99_ms"] = loadgen.percentile(lat[False], 0.99)
        if lat[True]:
            out["client.ycsb_read_p50_ms"] = loadgen.percentile(lat[True], 0.5)
            out["client.ycsb_read_p99_ms"] = loadgen.percentile(lat[True], 0.99)
        if all(thirds):  # a queue that grows through the window shows here
            out["update_p99_ms_by_third"] = [
                loadgen.percentile(t, 0.99) for t in thirds
            ]
        if gaps:
            out["client.ycsb_issue_ms_p99"] = loadgen.percentile(gaps, 0.99)
        return out
