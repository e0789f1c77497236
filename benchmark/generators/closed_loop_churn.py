"""closed_loop_churn: closed_loop_batch's load while replicas are replaced.

Parameters (the traffic file): `batch`, `timeout_s`, `poll_ms`,
`warm_rounds`, `warm_extra_rounds`, `replace_every_ms`,
`replace_max_inflight`, `config_change_timeout_s`, `config_change_tries`,
`run_in_s`, `drain_s`, `bring_up_bound_s`, `warm_bound_s`, `run_bound_s`,
`lost_batches_bound_share`, and `rehearsal` (values that replace these in
a rehearsal).

Foreground: every group keeps one propose_batch_async of `batch` seeded
writes in flight on its leader's host and submits the next the moment
the one before is accounted for, as closed_loop_batch does. warm() runs
`warm_rounds` batches a group plus a seeded 0..`warm_extra_rounds` more;
a group that has done its rounds waits for the rest, so the groups'
snapshot instants (Config.snapshot_entries applied entries apart) are
spread over a snapshot period.

Background, the rebalancer: from the start of measure() to the window's
close one replacement is started every `replace_every_ms`, seeded: the
next group of a seeded order with none in flight, one of its followers.
A replacement is Drummer's node repair (the reference's docs/test.md):

    request_delete_node(victim)         on the leader's host, polled
    stop_cluster                        on the victim's host, pool thread
    request_add_node(victim + replicas, the same address)
    start_cluster({}, join=True)        nothing on disk, pool thread
    done when the new replica's applied count is within one batch of
    the leader's

A fresh id is the replica's id plus the number of replicas, so it names
its host (ids 6..10 after one replacement of each slot, then 11..15) and
never returns. A config change whose request was not acknowledged is
asked again, `config_change_tries` times in all; an add that is refused
after one whose fate was not told took effect then. Nothing here blocks
the generator's thread: requests are polled, and the two blocking calls
run on a small pool. At most `replace_max_inflight` are in flight; a
start that would exceed that is skipped and counted.

The window opens `run_in_s` after the first replacement starts. After it
closes nothing new is started; batches in flight get `timeout_s` + 1 and
replacements `drain_s`. Then every host's get_cluster_membership of every
group is compared with the plain reference: a dict a group, replayed from
the acknowledged config changes in order. A difference, unequal
config_change_ids across the hosts, or a replacement still unfinished
counts into `reads_wrong`, which decides `correct` with the read-back.

Completed operations per second is all the acknowledged work over all
the window, as ycsb_closed counts it: every batch's acknowledged writes
are work spread evenly from its submission to the look that accounted
for it, and the rate is the work that falls inside [t_open, t_close)
over the window's length. A batch that straddles an edge counts by the
share of its life inside, so the number does not depend on where the
window falls among the bursts in which the engine acknowledges.
closed_loop_batch's form (whole cycles inside the window over the time
they took) needs two whole cycles of every group in the window; here a
cycle is three launches of 2.5-3 s, and a window of 15 s holds one or
none: `client.stalled_groups` counts the groups without one, and
`whole_cycle_ops_per_s` in the client's numbers is that form over the
groups that have one. `failed` counts every write submitted in the
window that was not acknowledged.

Every phase has a bound; one that overruns it raises, and the process
exits non-zero. From warm() on a watchdog ends the process `run_bound_s`
after the generator was made, whatever hangs. The service has a bound
too: from the first replacement on, once batches with writes that were
not acknowledged number more than `lost_batches_bound_share` of the
groups, the fleet is not serving its closed loop, a window over it would
measure the timeout, and the run ends at that look with a non-zero exit
(the tree before PR 31 loses a batch of every group each `timeout_s`; a
fleet that serves has lost 0 to 5 in a run).
"""
from __future__ import annotations

import faulthandler
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.lib import loadgen
from dragonboat_tpu.config import Config
from dragonboat_tpu.requests import RequestError

clock = loadgen.clock

# a replacement's states, in order
DELETE, STOP, ADD, START, CATCH_UP, DONE, FAILED = range(7)
STATE_NAMES = ("delete", "stop", "add", "start", "catch_up", "done", "failed")
REPLACE_POLL_S = 0.05
CONVERGE_S = 30.0
POOL_THREADS = 8
STOP_S = 45.0  # for run.py to stop the cluster after the service's bound


class PhaseOverrun(RuntimeError):
    pass


class MembershipReference:
    """The plain membership reference: a dict a group, replayed from the
    acknowledged config changes in order. Independent of the program."""

    def __init__(self, groups: int, members: dict) -> None:
        self.addresses = [dict(members) for _ in range(groups)]
        self.removed = [set() for _ in range(groups)]

    def delete(self, g: int, nid: int) -> None:
        del self.addresses[g][nid]
        self.removed[g].add(nid)

    def add(self, g: int, nid: int, address: str) -> None:
        if nid in self.removed[g] or nid in self.addresses[g]:
            raise AssertionError(f"group {g + 1}: id {nid} is not fresh")
        self.addresses[g][nid] = address

    def wrong(self, g: int, memberships: list) -> list:
        """What differs between the reference and the memberships the
        hosts report for group g (one reason a differing host, and one if
        their config_change_ids differ)."""
        out = []
        for where, m in memberships:
            if dict(m.addresses) != self.addresses[g]:
                out.append(f"group {g + 1} on {where}: addresses "
                           f"{dict(m.addresses)}, acknowledged "
                           f"{self.addresses[g]}")
            elif set(m.removed) != self.removed[g]:
                out.append(f"group {g + 1} on {where}: removed "
                           f"{sorted(m.removed)}, acknowledged "
                           f"{sorted(self.removed[g])}")
        ids = {m.config_change_id for _w, m in memberships}
        if len(ids) > 1:
            out.append(f"group {g + 1}: config_change_id {sorted(ids)}")
        return out


class Replacement:
    __slots__ = ("g", "victim", "fresh", "state", "t_start", "t_done",
                 "rs", "fut", "tries", "unknown", "retry_at")

    def __init__(self, g: int, victim: int, fresh: int, now: float) -> None:
        self.g = g
        self.victim = victim
        self.fresh = fresh
        self.state = DELETE
        self.t_start = now
        self.t_done = 0.0
        self.rs = None  # the config change in flight
        self.fut = None  # the pool call in flight
        self.tries = 0
        self.unknown = False  # a try of this change ended untold
        self.retry_at = 0.0


class _Hosts(dict):
    """The cluster's hosts by node id; a fresh id names the host of the
    replica it replaced."""

    def __init__(self, hosts: dict, replicas: int) -> None:
        super().__init__(hosts)
        self._replicas = replicas

    def __missing__(self, nid: int):
        return self[(nid - 1) % self._replicas + 1]


def leaders_of(cluster) -> list:
    """Leader's node id per group, 0 where no replica knows one: the
    claim at the highest term over every host's replica (one host's view
    is blind while its own replica of a group is being replaced)."""
    best = [(0, 0)] * cluster.groups
    for (_host, cid), (lid, term) in cluster.core.leader_snapshot().items():
        if lid and term >= best[cid - 1][1]:
            best[cid - 1] = (lid, term)
    return [lid for lid, _term in best]


def account(batches, groups: int, t0: float, t1: float) -> dict:
    """The closed loop's numbers from finished batches (group, submitted,
    looked, completed, dropped), over the window [t0, t1)."""
    mine = [b for b in batches if t0 <= b[1] < t1]
    attempted = sum(c + d for _g, _s, _l, c, d in mine)
    failed = sum(d for _g, _s, _l, _c, d in mine)
    ops = [0] * groups
    busy = [0.0] * groups
    lat = []
    for g, sub, looked, c, _d in mine:
        if sub < looked < t1:  # a whole cycle inside the window
            ops[g] += c
            busy[g] += looked - sub
            lat.append((looked - sub) * 1000.0)
    # every acknowledged batch is work spread evenly over its life, from
    # submission to the look that accounted for it; the rate is the work
    # that falls inside the window over the window's length
    work = 0.0
    for _g, sub, looked, c, _d in batches:
        lo, hi = max(sub, t0), min(looked, t1)
        if hi > lo:
            work += c * (hi - lo) / (looked - sub)
    return {
        "attempted": attempted,
        "failed": failed,
        "committed_ops_per_s": work / (t1 - t0),
        "whole_cycle_ops_per_s": sum(
            n / t for n, t in zip(ops, busy) if t
        ),
        "cycles": len(lat),
        "stalled_groups": sum(1 for t in busy if not t),
        "latencies_ms": lat,
    }


def thirds(samples, t0: float, t1: float) -> list:
    """Time-average of a step function, given as (instant, value) in
    order, over each third of [t0, t1)."""
    out = []
    edges = [t0 + (t1 - t0) * i / 3 for i in range(4)]
    for lo, hi in zip(edges, edges[1:]):
        area, value, at = 0.0, 0, lo
        for t, v in samples:
            if t <= lo:
                value = v
                continue
            if t >= hi:
                break
            area += value * (t - at)
            value, at = v, t
        area += value * (hi - at)
        out.append(area / (hi - lo) if hi > lo else 0.0)
    return out


class Generator:
    def __init__(self, params: dict, groups: int, ledger, seed: int,
                 seconds: float, scale: float) -> None:
        self.t_made = clock()
        if scale < 1.0:
            params = {**params, **params.get("rehearsal", {})}
        self.groups = groups
        self.ledger = ledger
        self.seconds = float(seconds)
        self.batch = int(params["batch"])
        self.timeout_s = float(params["timeout_s"])
        self.poll_s = float(params["poll_ms"]) / 1000.0
        self.every_s = float(params["replace_every_ms"]) / 1000.0
        self.cap = int(params["replace_max_inflight"])
        self.cc_timeout_s = float(params["config_change_timeout_s"])
        self.cc_tries = int(params["config_change_tries"])
        self.run_in_s = float(params["run_in_s"])
        self.drain_s = float(params["drain_s"])
        self.bring_up_bound_s = float(params["bring_up_bound_s"])
        self.warm_bound_s = float(params["warm_bound_s"])
        self.run_bound_s = float(params["run_bound_s"])
        self.lost_bound = float(params["lost_batches_bound_share"]) * groups
        self.lost = 0  # batches since the first replacement with a lost write
        rng = np.random.default_rng([seed, 31])
        self.rounds_due = (
            int(params["warm_rounds"])
            + rng.integers(0, int(params["warm_extra_rounds"]) + 1, groups)
        ).tolist()
        self._order = rng.permutation(groups).tolist()
        self._picks = rng.random(4096).tolist()
        self._next = 0
        self.t_first = self.t_open = self.t_close = 0.0
        # finished batches: (group, submitted, looked, completed, dropped)
        self.batches = []
        self.replacements = []
        self.skipped = 0
        self.cc_retries = 0
        self._busy = set()  # groups with a replacement in flight
        self._live = []  # replacements in flight
        self._inflight_log = []  # (instant, replacements in flight)
        self._leaders_open = self._leaders_close = ()
        self.wrong = []
        self.ref = None
        self._pool = None

    # ------------------------------------------------------------ foreground
    def _submit(self, cluster, nid: int, g: int, now: float):
        lo, hi, cmds = self.ledger.take(g, self.batch)
        try:
            h = cluster.hosts[nid].propose_batch_async(
                cluster.session(nid, g), cmds, self.timeout_s
            )
        except RequestError:
            self.ledger.settle(g, lo, hi, 0, hi - lo)
            self.batches.append((g, now, now, 0, hi - lo))
            self.lost += 1
            return None
        return h, now, lo, hi

    def _finish(self, g: int, rec, now: float) -> None:
        h, t_sub, lo, hi = rec
        dropped = h.n - h.completed
        self.ledger.settle(g, lo, hi, h.completed, dropped)
        self.batches.append((g, t_sub, now, h.completed, dropped))
        self.lost += bool(dropped)

    def _hold_service(self, now: float) -> None:
        if self.lost > self.lost_bound:
            if self.run_bound_s:
                # stopping a fleet in this state may take a launch a host
                faulthandler.dump_traceback_later(STOP_S, exit=True)
            raise PhaseOverrun(
                f"{self.lost} batches lost writes in the "
                f"{now - self.t_first:.0f}s since the first replacement, "
                f"over the bound of {self.lost_bound:.0f}: the fleet is "
                "not serving its closed loop"
            )

    def _settle_unfinished(self, inflight) -> None:
        for g, rec in enumerate(inflight):
            if rec is not None:  # never accounted for: fate unknown
                h, t_sub, lo, hi = rec
                self.ledger.settle(g, lo, hi, h.completed, h.n - h.completed)
                self.batches.append(
                    (g, t_sub, 0.0, h.completed, h.n - h.completed)
                )

    def warm(self, cluster) -> None:
        """`rounds_due[g]` batches a group; a group that is done waits."""
        spent = clock() - self.t_made
        if spent > self.bring_up_bound_s:
            raise PhaseOverrun(
                f"bring-up took {spent:.0f}s, over its bound of "
                f"{self.bring_up_bound_s:.0f}s"
            )
        if self.run_bound_s:
            # whatever hangs from here on, the process ends (run.py
            # cancels the watchdog before it prints the result)
            faulthandler.dump_traceback_later(
                max(1.0, self.run_bound_s - spent), exit=True
            )
        cluster.hosts = _Hosts(cluster.hosts, cluster.replicas)
        self.ref = MembershipReference(self.groups, cluster._members)
        G = self.groups
        inflight = [None] * G
        rounds = [0] * G
        leaders = leaders_of(cluster)
        deadline = clock() + self.warm_bound_s
        left = G
        while left:
            now = clock()
            if now >= deadline:
                raise PhaseOverrun(
                    f"{left} of {G} groups had not finished their warm "
                    f"rounds after {self.warm_bound_s:.0f}s"
                )
            for g in range(G):
                rec = inflight[g]
                if rec is not None:
                    if not rec[0].finished:
                        continue
                    self._finish(g, rec, now)
                    inflight[g] = None
                    rounds[g] += 1
                    if rounds[g] == self.rounds_due[g]:
                        left -= 1
                if rounds[g] < self.rounds_due[g]:
                    inflight[g] = self._submit(cluster, leaders[g], g, now)
            time.sleep(self.poll_s)

    # ---------------------------------------------------------------- window
    def measure(self, cluster, on_open, on_close) -> None:
        G = self.groups
        self._pool = ThreadPoolExecutor(POOL_THREADS, "churn")
        inflight = [None] * G  # (handle, submitted, lo, hi)
        leaders = leaders_of(cluster)
        self.lost = 0  # warm()'s are not this bound's
        self.t_first = t_first = clock()
        self.t_open = t_first + self.run_in_s
        self.t_close = self.t_open + self.seconds
        next_refresh = t_first + 0.5
        next_start = t_first
        next_advance = t_first
        opened = False
        try:
            while True:
                now = clock()
                if now >= self.t_close:
                    break
                if not opened and now >= self.t_open:
                    self.t_open = now
                    self.t_close = now + self.seconds
                    self._leaders_open = leaders_of(cluster)
                    on_open(now)
                    opened = True
                for g in range(G):
                    rec = inflight[g]
                    if rec is not None:
                        if not rec[0].finished:
                            continue
                        self._finish(g, rec, now)
                    inflight[g] = self._submit(cluster, leaders[g], g, now)
                self._hold_service(now)
                if now >= next_start:
                    next_start += self.every_s
                    self._start_one(cluster, leaders, now)
                if now >= next_advance:
                    next_advance = now + REPLACE_POLL_S
                    self._advance(cluster, leaders, now)
                if now >= next_refresh:
                    next_refresh = now + 0.5
                    fresh = leaders_of(cluster)
                    leaders = [f or old for f, old in zip(fresh, leaders)]
                time.sleep(self.poll_s)
            self._leaders_close = leaders_of(cluster)
            on_close(self.t_close)
            batches_by = clock() + self.timeout_s + 1.0
            drained_by = self.t_close + self.drain_s
            while True:
                now = clock()
                for g in range(G):
                    rec = inflight[g]
                    if rec is not None and rec[0].finished:
                        self._finish(g, rec, now)
                        inflight[g] = None
                self._hold_service(now)
                waiting = now < batches_by and any(
                    r is not None for r in inflight
                )
                if not waiting and (not self._live or now >= drained_by):
                    break
                if now >= next_refresh:
                    next_refresh = now + 0.5
                    fresh = leaders_of(cluster)
                    leaders = [f or old for f, old in zip(fresh, leaders)]
                if now >= next_advance:
                    next_advance = now + REPLACE_POLL_S
                    self._advance(cluster, leaders, now)
                time.sleep(self.poll_s)
            self._settle_unfinished(inflight)
            self._close_replacements(cluster)
        finally:
            self._pool.shutdown(wait=True, cancel_futures=True)
        self._check_memberships(cluster)

    # ----------------------------------------------------------- rebalancer
    def _start_one(self, cluster, leaders, now: float) -> None:
        if len(self._live) >= self.cap:
            self.skipped += 1
            return
        for _ in range(self.groups):
            g = self._order[self._next % self.groups]
            self._next += 1
            if g not in self._busy and leaders[g]:
                break
        else:
            self.skipped += 1
            return
        followers = sorted(n for n in self.ref.addresses[g] if n != leaders[g])
        pick = self._picks[len(self.replacements) % len(self._picks)]
        victim = followers[int(pick * len(followers))]
        r = Replacement(g, victim, victim + cluster.replicas, now)
        self.replacements.append(r)
        self._live.append(r)
        self._busy.add(g)
        self._inflight_log.append((now, len(self._live)))

    def _request(self, cluster, leaders, r: Replacement, now: float) -> None:
        """Ask for the config change of r's state on the leader's host."""
        nh = cluster.hosts[leaders[r.g]]
        cid = r.g + 1
        r.tries += 1
        try:
            if r.state == DELETE:
                r.rs = nh.request_delete_node(
                    cid, r.victim, timeout_s=self.cc_timeout_s
                )
            else:
                r.rs = nh.request_add_node(
                    cid, r.fresh, cluster.hosts[r.fresh].raft_address(),
                    timeout_s=self.cc_timeout_s,
                )
        except RequestError:
            r.rs = None  # busy, or the host has no such node just now
            r.retry_at = now + 0.2

    def _answer(self, r: Replacement):
        """True: acknowledged. False: ask again. None: not answered yet,
        or given up (r.state is then FAILED)."""
        if r.rs is None:
            acknowledged = False
        elif not r.rs.done():
            return None
        else:
            res = r.rs.result
            acknowledged = res.completed or (
                # refused because it is a member already: the try whose
                # fate was not told took effect
                r.state == ADD and res.rejected and r.unknown
            )
            if not acknowledged and not res.rejected:
                r.unknown = True
        if acknowledged:
            r.rs, r.tries, r.unknown = None, 0, False
            return True
        if r.tries >= self.cc_tries:
            r.state = FAILED
            return None
        self.cc_retries += 1
        return False

    def _advance(self, cluster, leaders, now: float) -> None:
        done = []
        for r in self._live:
            g, cid = r.g, r.g + 1
            if r.state in (DELETE, ADD):
                if r.rs is None and r.tries == 0:
                    self._request(cluster, leaders, r, now)
                    continue
                if r.rs is None and now < r.retry_at:
                    continue
                ok = self._answer(r)
                if ok is False:
                    self._request(cluster, leaders, r, now)
                elif ok and r.state == DELETE:
                    self.ref.delete(g, r.victim)
                    r.fut = self._pool.submit(
                        cluster.hosts[r.victim].stop_cluster, cid
                    )
                    r.state = STOP
                elif ok:
                    self.ref.add(
                        g, r.fresh, cluster.hosts[r.fresh].raft_address()
                    )
                    r.fut = self._pool.submit(self._start, cluster, r)
                    r.state = START
            elif r.state in (STOP, START):
                if not r.fut.done():
                    continue
                if r.fut.exception() is not None:
                    print(f"[churn] group {cid} {STATE_NAMES[r.state]}: "
                          f"{r.fut.exception()!r}", flush=True)
                    r.state = FAILED
                else:
                    r.state = ADD if r.state == STOP else CATCH_UP
                r.fut = None
            elif r.state == CATCH_UP:
                try:
                    mine = cluster.hosts[r.fresh].stale_read(cid, None)[0]
                    lead = cluster.hosts[leaders[g]].stale_read(cid, None)[0]
                except RequestError:
                    continue
                if lead - mine <= self.batch:
                    r.state = DONE
                    r.t_done = now
            if r.state in (DONE, FAILED):
                done.append(r)
        for r in done:
            self._live.remove(r)
            if r.state == DONE:
                self._busy.discard(r.g)  # a failed group is left alone
        if done:
            self._inflight_log.append((now, len(self._live)))

    def _start(self, cluster, r: Replacement) -> None:
        cluster.hosts[r.fresh].start_cluster(
            {}, True, cluster._sm_factory,
            Config(node_id=r.fresh, cluster_id=r.g + 1, **cluster._raft),
        )

    def _close_replacements(self, cluster) -> None:
        """What is unfinished stays unfinished and counts as wrong. A host
        left without a node of the group gets the new replica started all
        the same, so that the read-back can ask every host."""
        for r in self.replacements:
            if r.state == DONE:
                continue
            self.wrong.append(
                f"group {r.g + 1}: replacing {r.victim} by {r.fresh} "
                f"stood at '{STATE_NAMES[r.state]}' "
                f"{clock() - r.t_start:.1f}s after it started"
            )
            if r.fut is not None:
                try:
                    r.fut.result(timeout=30.0)
                except Exception as e:  # noqa: BLE001 - reported, counted
                    print(f"[churn] group {r.g + 1}: {e!r}", flush=True)
            if not cluster.hosts[r.fresh].has_node(r.g + 1):
                self._start(cluster, r)

    def _check_memberships(self, cluster) -> None:
        """Every host's membership of every group against the reference.
        A replica counted as caught up may still be a batch behind, with
        a config change in it: a group gets CONVERGE_S to agree, as the
        read-back gives the replicas' states."""
        deadline = clock() + CONVERGE_S
        lagging = list(range(self.groups))
        while True:
            found = {}
            for g in lagging:
                wrong = self.ref.wrong(g, [
                    (f"host {nid}", nh.get_cluster_membership(g + 1))
                    for nid, nh in cluster.hosts.items()
                ])
                if wrong:
                    found[g] = wrong
            lagging = list(found)
            if not lagging or clock() >= deadline:
                break
            time.sleep(0.05)
        for wrong in found.values():
            self.wrong.extend(wrong)
        for line in self.wrong[:20]:
            print(f"[churn] WRONG: {line}", flush=True)

    # --------------------------------------------------------------- results
    def results(self) -> dict:
        t0, t1 = self.t_open, self.t_close
        a = account(self.batches, self.groups, t0, t1)
        lat = a.pop("latencies_ms")
        done = [r for r in self.replacements if r.state == DONE]
        in_window = [r for r in done if t0 <= r.t_done < t1]
        # the median is over those that finished inside the window; a
        # window in which none did falls back on all that finished
        took = [(r.t_done - r.t_start) * 1000.0 for r in in_window or done]
        moves = [
            (g + 1, a_, b_) for g, (a_, b_) in enumerate(
                zip(self._leaders_open, self._leaders_close)
            ) if a_ != b_
        ]
        states = {}
        for r in self.replacements:
            name = STATE_NAMES[r.state]
            states[name] = states.get(name, 0) + 1
        out = {
            "attempted": a["attempted"],
            "failed": a["failed"],
            "reads_wrong": len(self.wrong),
            "committed_ops_per_s": a["committed_ops_per_s"],
            "whole_cycle_ops_per_s": a["whole_cycle_ops_per_s"],
            "cycles": a["cycles"],
            "writes": a["attempted"],
            "writes_acked": a["attempted"] - a["failed"],
            "reads": 0,
            "client.stalled_groups": a["stalled_groups"],
            "client.replacements_done_in_window": len(in_window),
            "client.leader_moves_in_window": len(moves),
            "leader_moves": moves[:16],  # (cluster id, at open, at close)
            "replacements_started": len(self.replacements),
            "replacements_skipped": self.skipped,
            "replacements_by_state": states,
            "replacements_started_in_window": sum(
                1 for r in self.replacements if t0 <= r.t_start < t1
            ),
            "replacements_inflight_by_thirds": thirds(
                self._inflight_log, t0, t1
            ),
            "config_change_retries": self.cc_retries,
            "batches_lost": self.lost,  # since the first replacement
            # (cluster id, seconds from submission to accounting, writes
            # not acknowledged): one that took `timeout_s` expired, a
            # shorter one was dropped
            "failed_batches": [
                (g + 1, round(looked - sub, 1), d)
                for g, sub, looked, _c, d in self.batches
                if d and t0 <= sub < t1
            ][:16],
            "replace_all_ms": sorted(
                round((r.t_done - r.t_start) * 1000.0) for r in done
            ),
        }
        if lat:
            out["client.commit_latency_p50_ms"] = loadgen.percentile(lat, 0.50)
            out["client.commit_latency_p99_ms"] = loadgen.percentile(lat, 0.99)
        if took:
            out["client.replace_p50_ms"] = loadgen.percentile(took, 0.50)
        return out
