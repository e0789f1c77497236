"""closed_loop_drops: closed_loop_batch's load while followers lose messages.

Parameters (the traffic file): `batch`, `payload_bytes` (128: kv128's
rows), `timeout_s`, `poll_ms`, `warm_rounds`, `loss_rounds`,
`drop_probability`, `drop_to_leader` (false), `slow_batch_s`,
`heal_bound_s`, `bring_up_bound_s`, `warm_bound_s`, `run_bound_s`, and
`rehearsal` (values that replace these in a rehearsal).

Foreground: every group keeps one propose_batch_async of `batch` seeded
128-byte commands in flight on its leader's host and submits the next the
moment the one before is accounted for, exactly as closed_loop_batch does
(this class is a subclass of that one and submits, finishes and counts
through it). The rows come from benchmark/statemachines/kv128.py's
`Payloads`, handed to run.py's Ledger as its payload source.

Loss: warm() runs `warm_rounds` batches a group with every message
delivered (a group that has done its rounds waits for the rest, so no
batch is in flight at the phase's edge). measure() then installs the
chaos hook over co-hosted delivery (`core.set_local_drop_hook`): a
`DropHook`. Every message whose receiver is not its group's leader, by
the table this loop keeps (`cluster.leaders()`, refreshed every 0.5 s),
is dropped with probability `drop_probability`, whatever its type; a
message to a leader never is. The decision is a hash of (seed, group,
receiving replica, that link's message ordinal): the seed and the order
of a link's own messages decide a run's losses, and nothing is shared
between links.

The window opens once every group has finished `loss_rounds` further
rounds under loss. At its close nothing new is submitted; batches in
flight get `timeout_s` + 1; then the hook is cleared and the time until
every replica of every group holds its group's last acknowledged row is
`client.heal_to_converged_ms`. run.py's check.read_back follows, as for
every cell.

`attempted` and `failed` are closed_loop_batch's: every write submitted
in the window, and every one of them that was not acknowledged.
`committed_ops_per_s` is all the acknowledged work over all the window,
as closed_loop_churn and ycsb_closed count it: every batch's
acknowledged writes are work spread evenly from its submission to the
look that accounted for it, and the rate is the work that falls inside
[t_open, t_close) over the window's length. closed_loop_batch's form
(whole cycles inside the window over the time they took) needs two whole
cycles of every group in the window; here a cycle is three launches of
2.5 s and more where a Replicate was lost, and a window of 15 s holds
one whole cycle or none (202, 565 and 879 of 1 024 groups had none in
this PR's first three chip runs, and that form read 6 377, 3 754 and
1 023 ops/s of one program). It stays in the client's numbers as
`whole_cycle_ops_per_s`, over the groups that have a whole cycle, with
`stalled_groups` beside it.

For the diagnosis of a failed batch the client's numbers carry
`failed_batches` (group, `expired` or `dropped`, the group's term and
leader at submission and at the look that accounted for it, seconds in
flight, launches, inside the window or before it) and `slow_batches`
(every batch that completed after more than `slow_batch_s`).

Every phase has a bound; one that overruns it raises and the process
exits non-zero. From warm() on a watchdog ends the process `run_bound_s`
after the generator was made, whatever hangs.
"""
from __future__ import annotations

import faulthandler
import time

from benchmark.generators.closed_loop_batch import Generator as ClosedLoop
from benchmark.lib import loadgen
from benchmark.statemachines import kv128

clock = loadgen.clock

_MASK64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15
REFRESH_S = 0.5
HEAL_POLL_S = 0.05
LISTED = 32  # failed, slow and lagging records kept in the client's numbers


class PhaseOverrun(RuntimeError):
    pass


def _mix(x: int) -> int:
    """splitmix64's finaliser on a Python int."""
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


class DropHook:
    """hook(message) -> True drops it. `leaders` is the closed loop's
    table (node id a group, 0 unknown), swapped whole by the generator's
    thread; the hook runs on the engine's loop thread."""

    def __init__(self, seed: int, groups: int, replicas: int,
                 probability: float) -> None:
        self.replicas = replicas
        self.leaders = [0] * groups
        self.threshold = int(probability * (1 << 64))
        self._base = [
            _mix((seed * _GOLDEN + (g * replicas + r + 1) * 0xD1B54A32D192ED03)
                 & _MASK64)
            for g in range(groups) for r in range(replicas)
        ]
        self._ordinal = [0] * (groups * replicas)
        self.seen = 0  # messages to a replica that is not the leader
        self.dropped = 0
        self.to_leader = 0
        self.dropped_by_type: dict = {}

    def decide(self, link: int, ordinal: int) -> bool:
        """Is the `ordinal`-th message of `link` (group * replicas +
        receiving replica - 1) lost: a function of the seed alone."""
        return _mix(
            (self._base[link] + ordinal * _GOLDEN) & _MASK64
        ) < self.threshold

    def __call__(self, m) -> bool:
        g = m.cluster_id - 1
        to = m.to
        if to == self.leaders[g]:
            self.to_leader += 1
            return False
        link = g * self.replicas + (to - 1) % self.replicas
        n = self._ordinal[link]
        self._ordinal[link] = n + 1
        self.seen += 1
        if self.decide(link, n):
            self.dropped += 1
            name = m.type.name
            self.dropped_by_type[name] = self.dropped_by_type.get(name, 0) + 1
            return True
        return False

    def counts(self) -> tuple:
        return self.seen, self.dropped, self.to_leader


def look(cluster) -> tuple:
    """One pass over the engine's host mirrors: (what cluster.leaders()
    answers: the leader's node id a group as host 1's replica knows it, 0
    unknown; (term, leader's node id) a group: the claim at the highest
    term over every host's replica)."""
    first = cluster.hosts[1].engine.host
    leaders = [0] * cluster.groups
    best = [(0, 0)] * cluster.groups
    for (host, cid), (lid, term) in cluster.core.leader_snapshot().items():
        if host == first:
            leaders[cid - 1] = lid
        if term > best[cid - 1][0] or (term == best[cid - 1][0] and lid):
            best[cid - 1] = (term, lid)
    return leaders, best


def terms_of(cluster) -> list:
    return look(cluster)[1]


def program_counts(core) -> dict:
    """The program's cumulative protocol counters the cell reads itself:
    the kernel's (counter_stats) and the engine's plain ints (step_stats;
    a program older than one of them lacks its key)."""
    ctr = core.counter_stats()
    st = core.step_stats()
    out = {
        name: ctr[name] for name in
        ("replicate_rejects", "elections_started", "elections_won")
    }
    for name in ("catchups_started", "catchup_entries", "replicate_resends",
                 "snapshot_fallbacks", "launches"):
        if name in st:
            out[name] = st[name]
    return out


class Generator(ClosedLoop):
    def __init__(self, params: dict, groups: int, ledger, seed: int,
                 seconds: float, scale: float) -> None:
        if scale < 1.0:
            params = {**params, **params.get("rehearsal", {})}
        super().__init__(params, groups, ledger, seed, seconds, scale)
        self.t_made = clock()
        self.seed = seed
        if int(params["payload_bytes"]) != kv128.CMD_BYTES:
            raise ValueError("the rows of kv128 are 128 bytes")
        if params["drop_to_leader"]:
            raise ValueError("a message to a leader is never dropped")
        self.loss_rounds = int(params["loss_rounds"])
        self.probability = float(params["drop_probability"])
        self.slow_s = float(params["slow_batch_s"])
        self.heal_bound_s = float(params["heal_bound_s"])
        self.bring_up_bound_s = float(params["bring_up_bound_s"])
        self.warm_bound_s = float(params["warm_bound_s"])
        self.run_bound_s = float(params["run_bound_s"])
        # rows made from the seed, independent of the program
        ledger.payloads = kv128.Payloads(seed, groups)
        self.hook = None
        self._meta = [None] * groups  # (term, leader, launch) at submission
        self._table = [(0, 0)] * groups  # terms_of, at the last refresh
        self.failed_batches = []
        self.slow_batches = []
        self.t_loss = 0.0
        self._at = {}  # "open"/"close" -> hook and program counts
        self.lag = {}
        self.term_changes = []
        self.heal_ms = None

    # ------------------------------------------------------------ foreground
    def _submit_tracked(self, cluster, leaders, g: int, now: float):
        term, lead = self._table[g]
        self._meta[g] = (term, lead or leaders[g], cluster.core.launch_no)
        return self._submit(cluster, leaders[g], g, now)

    def _finish_tracked(self, cluster, g: int, rec, now: float) -> None:
        self._finish(g, rec, now)
        h, t_sub = rec[0], rec[1]
        dropped = h.n - h.completed
        flight = (h.completed_at or now) - t_sub
        if not dropped and flight <= self.slow_s:
            return
        term0, lead0, launch0 = self._meta[g]
        term1, lead1 = terms_of(cluster)[g]
        record = {
            "group": g + 1,
            "seconds": round(flight, 2),
            "launches": cluster.core.launch_no - launch0,
            "term": [term0, term1],
            "leader": [lead0, lead1],
            "term_changed": term0 != term1,
            "in_window": bool(
                self.t_open and self.t_open <= t_sub < self.t_close
            ),
        }
        if dropped:
            record["fate"] = (
                "expired" if flight >= self.timeout_s - 0.5 else "dropped"
            )
            record["writes_lost"] = dropped
            self.failed_batches.append(record)
        else:
            self.slow_batches.append(record)

    def _refresh(self, cluster, leaders):
        fresh, self._table = look(cluster)
        leaders = [f or old for f, old in zip(fresh, leaders)]
        if self.hook is not None:
            self.hook.leaders = leaders
        return leaders

    # ---------------------------------------------------------------- phases
    def warm(self, cluster) -> None:
        """`warm_rounds` batches a group with every message delivered; a
        group that is done waits for the rest."""
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
        G = self.groups
        inflight = [None] * G
        rounds = [0] * G
        leaders = self._refresh(cluster, [0] * self.groups)
        next_refresh = clock() + REFRESH_S
        deadline = clock() + self.warm_bound_s
        left = G if self.warm_rounds else 0
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
                    self._finish_tracked(cluster, g, rec, now)
                    inflight[g] = None
                    rounds[g] += 1
                    if rounds[g] == self.warm_rounds:
                        left -= 1
                if rounds[g] < self.warm_rounds:
                    inflight[g] = self._submit_tracked(cluster, leaders, g, now)
            if now >= next_refresh:
                next_refresh = now + REFRESH_S
                leaders = self._refresh(cluster, leaders)
            time.sleep(self.poll_s)

    def measure(self, cluster, on_open, on_close) -> None:
        G = self.groups
        core = cluster.core
        self.hook = DropHook(
            self.seed, G, cluster.replicas, self.probability
        )
        leaders = self._refresh(cluster, [0] * self.groups)
        core.set_local_drop_hook(self.hook)
        self.t_loss = clock()
        self._at["loss"] = program_counts(core)
        terms_at_loss = self._table
        inflight = [None] * G  # (handle, submitted, lo, hi)
        rounds = [0] * G
        next_refresh = clock() + REFRESH_S
        opened = False
        try:
            while True:
                now = clock()
                if opened and now >= self.t_close:
                    break
                for g in range(G):
                    rec = inflight[g]
                    if rec is not None:
                        if not rec[0].finished:
                            continue
                        self._finish_tracked(cluster, g, rec, now)
                        rounds[g] += 1
                    inflight[g] = self._submit_tracked(cluster, leaders, g, now)
                if not opened and min(rounds) >= self.loss_rounds:
                    self.t_open = clock()
                    self.t_close = self.t_open + self.seconds
                    self._at["open"] = (self.hook.counts(), program_counts(core))
                    on_open(self.t_open)
                    opened = True
                if now >= next_refresh:
                    next_refresh = now + REFRESH_S
                    leaders = self._refresh(cluster, leaders)
                time.sleep(self.poll_s)
            on_close(self.t_close)
            self._at["close"] = (self.hook.counts(), program_counts(core))
            self.lag = self._lag(cluster, leaders)
            deadline = clock() + self.timeout_s + 1.0
            while any(r is not None for r in inflight) and clock() < deadline:
                now = clock()
                for g in range(G):
                    rec = inflight[g]
                    if rec is not None and rec[0].finished:
                        self._finish_tracked(cluster, g, rec, now)
                        inflight[g] = None
                if now >= next_refresh:
                    next_refresh = now + REFRESH_S
                    leaders = self._refresh(cluster, leaders)
                time.sleep(self.poll_s)
            for g in range(G):
                rec = inflight[g]
                if rec is not None:  # never accounted for: fate unknown
                    h, t_sub, lo, hi = rec
                    lost = h.n - h.completed
                    self.ledger.settle(g, lo, hi, h.completed, lost)
                    self.batches.append((g, t_sub, 0.0, h.completed, lost))
                    self.failed_batches.append({
                        "group": g + 1, "fate": "unaccounted",
                        "writes_lost": lost,
                        "seconds": round(clock() - t_sub, 2),
                        "in_window": self.t_open <= t_sub < self.t_close,
                    })
        finally:
            core.set_local_drop_hook(None)
        self._at["end"] = program_counts(core)
        # (group, term and leader when the loss began, and when it ended)
        self.term_changes = [
            (g + 1, a, b) for g, (a, b) in enumerate(
                zip(terms_at_loss, terms_of(cluster))
            ) if a != b
        ]
        self.heal_ms = self._heal(cluster)

    # ------------------------------------------------- followers and healing
    def _lag(self, cluster, leaders) -> dict:
        """Entries every replica is behind its leader's applied count."""
        lags = []
        behind = []
        for g in range(self.groups):
            counts = {
                nid: nh.stale_read(g + 1, None)[0]
                for nid, nh in cluster.hosts.items()
            }
            lead = counts.get(leaders[g], max(counts.values()))
            for nid, n in counts.items():
                lag = max(lead - n, 0)
                lags.append(lag)
                if lag > self.batch:
                    behind.append((g + 1, nid, lag))
        return {
            "share": len(behind) / len(lags),
            "p99": loadgen.percentile(lags, 0.99),
            "max": max(lags),
            "behind": behind[:LISTED],
        }

    def _heal(self, cluster):
        """ms from the hook's removal until every replica of every group
        holds its group's last acknowledged row; None past the bound (the
        read-back then says what did not converge)."""
        t0 = clock()
        deadline = t0 + self.heal_bound_s
        ledger = self.ledger
        hosts = list(cluster.hosts.values())
        lagging = list(range(self.groups))
        while True:
            still = []
            for g in lagging:
                got = {nh.stale_read(g + 1, None)[0] for nh in hosts}
                want = ledger.used[g] - ledger.indeterminate[g]
                if len(got) != 1 or min(got) < want:
                    still.append(g)
            lagging = still
            if not lagging:
                return (clock() - t0) * 1000.0
            if clock() >= deadline:
                print(f"[drops] {len(lagging)} groups had not converged "
                      f"{self.heal_bound_s:.0f}s after the loss stopped, "
                      f"e.g. group {lagging[0] + 1}", flush=True)
                return None
            time.sleep(HEAL_POLL_S)

    # --------------------------------------------------------------- results
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
        # every acknowledged batch is work spread evenly over its life;
        # the rate is the work inside the window over the window's length
        work = 0.0
        for _g, sub, looked, c, _d in self.batches:
            lo, hi = max(sub, t0), min(looked, t1)
            if hi > lo:
                work += c * (hi - lo) / (looked - sub)
        (seen0, drop0, lead0), prog0 = self._at["open"]
        (seen1, drop1, lead1), prog1 = self._at["close"]
        end = self._at["end"]
        seen = seen1 - seen0
        out = {
            "attempted": attempted,
            "failed": failed,
            "reads_wrong": 0,
            "committed_ops_per_s": work / (t1 - t0),
            "whole_cycle_ops_per_s": sum(
                n / t for n, t in zip(ops, busy) if t
            ),
            "cycles": len(lat),
            "stalled_groups": sum(1 for t in busy if not t),
            "writes": attempted,
            "writes_acked": attempted - failed,
            "reads": 0,
            "failed_batches": self.failed_batches[:LISTED],
            "failed_batches_all": len(self.failed_batches),
            "slow_batches": self.slow_batches[:LISTED],
            "slow_batches_all": len(self.slow_batches),
            "loss_to_open_s": t0 - self.t_loss,
            "messages_to_followers_in_window": seen,
            "messages_dropped_in_window": drop1 - drop0,
            "messages_to_leaders_in_window": lead1 - lead0,
            "dropped_by_type": dict(self.hook.dropped_by_type),
            "program_in_window": {
                k: prog1[k] - prog0[k] for k in prog1 if k in prog0
            },
            # hook installed -> hook cleared: the whole time under loss
            "program_under_loss": {
                k: end[k] - self._at["loss"][k]
                for k in end if k in self._at["loss"]
            },
            "term_changes_under_loss": self.term_changes[:LISTED],
            "lagging_at_close": self.lag.get("behind", []),
            "follower_lag_max_entries": self.lag.get("max"),
            "client.lagging_followers_share": self.lag.get("share"),
            "client.follower_lag_p99_entries": self.lag.get("p99"),
        }
        if seen:
            out["client.dropped_share"] = (drop1 - drop0) / seen
        if self.heal_ms is not None:
            out["client.heal_to_converged_ms"] = self.heal_ms
        if lat:
            out["client.commit_latency_p50_ms"] = loadgen.percentile(lat, 0.50)
            out["client.commit_latency_p99_ms"] = loadgen.percentile(lat, 0.99)
        return out
