"""Raft event aggregation + Prometheus-style health metrics.

cf. reference event.go:30-141: a raftEventListener sits between the raft
core's event callbacks and (a) per-node gauges/counters exported in
Prometheus text exposition format (WriteHealthMetrics event.go:30-32) and
(b) the user's IRaftEventListener (LeaderUpdated via a dedicated queue —
nodehost.go:1686-1701; here the user callback runs on a single dispatcher
thread so a slow listener can't stall step workers).

The registry also carries the observability plane's latency histograms
(log-bucketed, Prometheus `_bucket`/`_sum`/`_count` exposition): the
proposal lifecycle (propose-enqueue -> quorum commit -> apply/notify),
linearizable reads, and the WAL fsync barrier.
"""
from __future__ import annotations

import bisect
import threading
from typing import Dict, List, Optional, Tuple

from .raftio import IRaftEventListener, LeaderInfo
from .trace import flight_recorder

_LabelKey = Tuple[int, int]  # (cluster_id, node_id)


# log-bucketed latency bounds in seconds: powers of two from ~15us to
# ~131s (24 buckets + overflow). Log spacing keeps p50/p99 estimation
# error bounded at a constant relative factor across six decades — the
# proposal path spans sub-ms co-hosted commits to multi-second chaos
# stalls on one scale.
DEFAULT_LATENCY_BUCKETS: Tuple[float, ...] = tuple(
    2.0**e for e in range(-16, 8)
)


class Histogram:
    """Log-bucketed histogram with Prometheus semantics.

    observe() is bucket-increment + two adds under one small lock — no
    allocation, so sampled hot-path observation stays cheap. Bucket counts
    are NON-cumulative internally; exposition writes the cumulative
    `_bucket{le=...}` / `_sum` / `_count` triplet."""

    __slots__ = ("bounds", "counts", "sum", "count", "_mu")

    def __init__(
        self, bounds: Tuple[float, ...] = DEFAULT_LATENCY_BUCKETS
    ) -> None:
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # +1 overflow (+Inf)
        self.sum = 0.0
        self.count = 0
        self._mu = threading.Lock()

    def observe(self, v: float) -> None:
        i = bisect.bisect_left(self.bounds, v)
        with self._mu:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def merge(self, other: "Histogram") -> None:
        """Fold another histogram with identical bounds into this one."""
        if other.bounds != self.bounds:
            raise ValueError("histogram bounds mismatch")
        with other._mu:
            counts = list(other.counts)
            s, c = other.sum, other.count
        with self._mu:
            for i, n in enumerate(counts):
                self.counts[i] += n
            self.sum += s
            self.count += c

    def quantile(self, q: float) -> float:
        """Bucket-interpolated quantile estimate (0 <= q <= 1)."""
        with self._mu:
            total = self.count
            counts = list(self.counts)
        if total == 0:
            return 0.0
        target = q * total
        cum = 0
        lo = 0.0
        for i, c in enumerate(counts[:-1]):
            if c and cum + c >= target:
                frac = (target - cum) / c
                hi = self.bounds[i]
                return lo + (hi - lo) * frac
            cum += c
            lo = self.bounds[i]
        return self.bounds[-1]  # landed in the +Inf overflow bucket

    def snapshot(self) -> Tuple[List[int], float, int]:
        with self._mu:
            return list(self.counts), self.sum, self.count

    def since(self, prev: Optional[Tuple[List[int], float, int]]) -> "Histogram":
        """A NEW histogram holding only the observations made after
        `prev` (a snapshot() of this histogram; None means everything).
        Delta semantics for verdicts over cumulative per-host series —
        e.g. one overload storm's urgent p99 on a host that has already
        run other storms."""
        h = Histogram(self.bounds)
        counts, s, c = self.snapshot()
        if prev is None:
            h.counts = counts
            h.sum, h.count = s, c
            return h
        pc, ps, pn = prev
        h.counts = [max(a - b, 0) for a, b in zip(counts, pc)]
        h.sum = max(s - ps, 0.0)
        h.count = max(c - pn, 0)
        return h


def _labels(pairs) -> str:
    """Prometheus label block with SORTED label keys."""
    return "{" + ",".join(f'{k}="{v}"' for k, v in sorted(pairs)) + "}"


def write_histogram_series(w, full: str, label_pairs, h: "Histogram") -> None:
    """One labelled histogram series in Prometheus text format: cumulative
    `_bucket{le=...}` lines, a `+Inf` bucket equal to `_count`, then
    `_sum`/`_count`. Shared by MetricsRegistry.write and the perf
    attribution plane (profile.PhasePlane), so both expositions obey the
    same conformance contract (tests/test_observability.py parser)."""
    counts, total_sum, count = h.snapshot()
    base = tuple(label_pairs)
    cum = 0
    for bound, c in zip(h.bounds, counts):
        cum += c
        w.write(
            f"{full}_bucket{_labels(base + (('le', f'{bound:g}'),))} {cum}\n"
        )
    w.write(f"{full}_bucket{_labels(base + (('le', '+Inf'),))} {count}\n")
    w.write(f"{full}_sum{_labels(base)} {total_sum:g}\n")
    w.write(f"{full}_count{_labels(base)} {count}\n")


class MetricsRegistry:
    """Counter/gauge/histogram registry with Prometheus text exposition."""

    def __init__(self, prefix: str = "dragonboat_tpu") -> None:
        self._prefix = prefix
        self._mu = threading.Lock()
        self._counters: Dict[str, Dict[_LabelKey, float]] = {}
        self._gauges: Dict[str, Dict[_LabelKey, float]] = {}
        self._hists: Dict[str, Dict[_LabelKey, Histogram]] = {}
        # per-metric label NAMES for the 2-tuple keys; families not
        # declared here expose the historical ("clusterid", "nodeid")
        self._label_names: Dict[str, tuple] = {}

    def declare_label_names(self, name: str, names) -> None:
        """Install the label names a metric family's 2-tuple keys mean
        (e.g. the serving plane's ("tenant", "klass")). Idempotent;
        undeclared families keep ("clusterid", "nodeid")."""
        with self._mu:
            self._label_names[name] = tuple(names)

    def inc(self, name: str, key: _LabelKey, delta: float = 1.0) -> None:
        with self._mu:
            self._counters.setdefault(name, {})
            self._counters[name][key] = self._counters[name].get(key, 0.0) + delta

    def set_gauge(self, name: str, key: _LabelKey, value: float) -> None:
        with self._mu:
            self._gauges.setdefault(name, {})[key] = value

    def set_gauges(self, name: str, values: Dict[_LabelKey, float]) -> None:
        """Many series of one gauge family under one lock round trip."""
        with self._mu:
            self._gauges.setdefault(name, {}).update(values)

    def counter_value(self, name: str, key: _LabelKey) -> float:
        with self._mu:
            return self._counters.get(name, {}).get(key, 0.0)

    def gauge_value(self, name: str, key: _LabelKey) -> Optional[float]:
        with self._mu:
            return self._gauges.get(name, {}).get(key)

    # -- histograms --------------------------------------------------------
    def observe(self, name: str, key: _LabelKey, value: float) -> None:
        """Record one observation into the (name, key) histogram. The
        common case (histogram exists) costs one dict probe under the
        registry lock plus the bucket increment."""
        with self._mu:
            table = self._hists.get(name)
            if table is None:
                table = self._hists[name] = {}
            h = table.get(key)
            if h is None:
                h = table[key] = Histogram()
        h.observe(value)

    def histogram(self, name: str, key: _LabelKey) -> Optional[Histogram]:
        with self._mu:
            return self._hists.get(name, {}).get(key)

    def histogram_items(self, name: str) -> List[Tuple[_LabelKey, Histogram]]:
        """(key, histogram) pairs for `name` — key-aware reads (the
        placement controller takes the bulk class by the klass label)."""
        with self._mu:
            return list(self._hists.get(name, {}).items())

    def write(self, w) -> None:
        """Prometheus text exposition (cf. WriteHealthMetrics event.go:30).
        One `# TYPE` line per metric family; cumulative histogram buckets
        with a `+Inf` bucket equal to `_count`; label keys sorted."""
        with self._mu:
            for kind, table in (("counter", self._counters), ("gauge", self._gauges)):
                for name in sorted(table):
                    full = f"{self._prefix}_{name}"
                    lnames = self._label_names.get(
                        name, ("clusterid", "nodeid")
                    )
                    w.write(f"# TYPE {full} {kind}\n")
                    for key, v in sorted(table[name].items()):
                        w.write(
                            f"{full}{_labels(tuple(zip(lnames, key)))} {v:g}\n"
                        )
            for name in sorted(self._hists):
                full = f"{self._prefix}_{name}"
                lnames = self._label_names.get(name, ("clusterid", "nodeid"))
                w.write(f"# TYPE {full} histogram\n")
                for key, h in sorted(self._hists[name].items()):
                    write_histogram_series(
                        w, full, tuple(zip(lnames, key)), h
                    )


class RaftEventAggregator:
    """Receives the raft core's event callbacks (via the node's adapter),
    updates metrics, and forwards LeaderUpdated to the user listener
    (cf. event.go:34-141 raftEventListener)."""

    def __init__(
        self,
        metrics: MetricsRegistry,
        user_listener: Optional[IRaftEventListener] = None,
        enable_metrics: bool = True,
    ) -> None:
        self.metrics = metrics
        self._user = user_listener
        self._enabled = enable_metrics
        # Coalescing mailbox: only the LATEST LeaderInfo per (cluster, node)
        # is kept, so a slow listener can never block a step worker or miss
        # the final "leader is now X" update — intermediate churn collapses.
        self._cv = threading.Condition()
        self._pending: Dict[_LabelKey, LeaderInfo] = {}
        # last leader recorded per (cluster, node): the flight recorder
        # logs LEADER transitions (including ->0, the gap-opening edge)
        # but not term-only churn — bring-up election storms bump terms
        # every step and would flood the ring exactly when the host is
        # CPU-bound (plain dict: torn reads only cost a dup/missed event)
        self._last_leader: Dict[_LabelKey, int] = {}
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        if user_listener is not None:
            self._thread = threading.Thread(
                target=self._dispatch_main, name="raft-event-dispatch", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            with self._cv:
                self._stop = True
                self._cv.notify()
            self._thread.join(timeout=2)
            self._thread = None

    def _dispatch_main(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._stop:
                    self._cv.wait()
                if self._stop and not self._pending:
                    return
                batch = list(self._pending.values())
                self._pending.clear()
            for info in batch:
                try:
                    self._user.leader_updated(info)
                except Exception:
                    pass  # user listener errors must not kill the dispatcher

    # -- callbacks from the raft core (all on step-worker threads) ----------
    def leader_updated(self, cluster_id, node_id, leader_id, term) -> None:
        # flight-recorder breadcrumb regardless of the metrics flag (a
        # postmortem timeline without leader changes is useless). LEADER
        # transitions only — including ->0, the availability gap's
        # opening edge — while term-only churn is suppressed (bring-up
        # election storms bump terms every step and would flood the ring
        # exactly when the host is CPU-bound)
        key = (cluster_id, node_id)
        if self._last_leader.get(key) != leader_id:
            self._last_leader[key] = leader_id
            flight_recorder().record(
                "leader_changed",
                cluster=cluster_id,
                node=node_id,
                leader=leader_id,
                term=term,
            )
        if self._enabled:
            key = (cluster_id, node_id)
            self.metrics.set_gauge("raftnode_has_leader", key, 1.0 if leader_id else 0.0)
            self.metrics.set_gauge("raftnode_leader_id", key, float(leader_id))
            self.metrics.set_gauge("raftnode_term", key, float(term))
        if self._user is not None:
            info = LeaderInfo(
                cluster_id=cluster_id, node_id=node_id,
                leader_id=leader_id, term=term,
            )
            with self._cv:
                self._pending[(cluster_id, node_id)] = info
                self._cv.notify()

    def campaign_launched(self, cluster_id, node_id, term) -> None:
        if self._enabled:
            self.metrics.inc("raftnode_campaign_launched_total", (cluster_id, node_id))

    def campaign_skipped(self, cluster_id, node_id, term) -> None:
        if self._enabled:
            self.metrics.inc("raftnode_campaign_skipped_total", (cluster_id, node_id))

    def snapshot_rejected(
        self, cluster_id, node_id, index, term, from_node
    ) -> None:
        if self._enabled:
            self.metrics.inc("raftnode_snapshot_rejected_total", (cluster_id, node_id))

    def replication_rejected(
        self, cluster_id, node_id, log_index, log_term, from_node
    ) -> None:
        if self._enabled:
            self.metrics.inc(
                "raftnode_replication_rejected_total", (cluster_id, node_id)
            )

    def proposal_dropped(self, cluster_id, node_id, entries) -> None:
        if self._enabled:
            n = len(entries) if entries else 1
            self.metrics.inc(
                "raftnode_proposal_dropped_total", (cluster_id, node_id), n
            )

    def read_index_dropped(self, cluster_id, node_id) -> None:
        if self._enabled:
            self.metrics.inc(
                "raftnode_read_index_dropped_total", (cluster_id, node_id)
            )

    # Optional event-callback vocabulary the raft core MAY grow into (cf.
    # internal/server/event.go:75-83 raftEventListener's full surface):
    # these resolve to a shared noop until a real handler exists. Anything
    # else raises AttributeError — the old unconditional noop fallback
    # masked typo'd callback names and made hasattr() probing useless
    # (every probe answered True).
    _OPTIONAL_CALLBACKS = frozenset(
        {
            "connection_established",
            "connection_failed",
            "membership_changed",
            "send_snapshot_started",
            "send_snapshot_completed",
            "send_snapshot_aborted",
            "snapshot_received",
            "snapshot_recovered",
            "snapshot_created",
            "snapshot_compacted",
            "log_compacted",
            "logdb_compacted",
        }
    )

    @staticmethod
    def _noop(*a, **k):
        return None

    def __getattr__(self, name):
        if name in RaftEventAggregator._OPTIONAL_CALLBACKS:
            return RaftEventAggregator._noop
        raise AttributeError(
            f"RaftEventAggregator has no event callback {name!r} "
            f"(declared optional callbacks: sorted list in "
            f"_OPTIONAL_CALLBACKS)"
        )


__all__ = [
    "DEFAULT_LATENCY_BUCKETS",
    "Histogram",
    "MetricsRegistry",
    "RaftEventAggregator",
    "write_histogram_series",
]
