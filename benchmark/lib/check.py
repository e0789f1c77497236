"""The comparison that decides `correct`, outside the timed window.

Per group the plain reference is the ledger: (writes acknowledged, sum64
of their payloads). The system is held to the guarantees a configuration
states: an acknowledged write is read back through a linearizable read
on the leader's host and on a follower's host, every replica's local
state reaches the same answer, the log is the on-disk WAL and the engine
loop swallowed no exception.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.lib.loadgen import CheckFailure
from dragonboat_tpu.requests import ErrClusterNotReady, ErrTimeout

READ_S = 60.0
READ_TRY_S = 10.0
CONVERGE_S = 30.0
SAMPLE_GROUPS = 128


def sync_read(nh, cid: int, query):
    """A linearizable read, retried like a client: a ReadIndex may be
    dropped by protocol (leader moved, no entry committed in its term)."""
    deadline = time.monotonic() + READ_S
    while True:
        try:
            return nh.sync_read(cid, query, READ_TRY_S)
        except (ErrTimeout, ErrClusterNotReady):
            if time.monotonic() >= deadline:
                raise


def read_all(pairs):
    """Linearizable (applied, sum64) for each (host, cluster id): every
    ReadIndex goes down first and is waited for after, so the reads of
    all groups share engine steps."""
    states = [nh.read_index(cid, READ_TRY_S) for nh, cid in pairs]
    out = []
    for (nh, cid), rs in zip(pairs, states):
        if rs.wait(READ_TRY_S + 1.0).completed:
            out.append(nh.read_local_node(cid, None))
        else:
            out.append(sync_read(nh, cid, None))
    return out


def read_back(cluster, ledger, seed: int) -> dict:
    """Raises CheckFailure on the first broken guarantee; returns counts
    and timings of what was checked."""
    hosts = cluster.hosts
    groups = cluster.groups
    leaders = cluster.wait_leaders(READ_S)
    for nid, nh in hosts.items():
        if nh.logdb.name() != "sharded-walkv" or not nh.logdb.shard_dirs():
            raise CheckFailure(
                f"host {nid} logs to {nh.logdb.name()}, not the on-disk WAL"
            )
    swallowed = cluster.core.step_stats()["loop_exceptions"]
    if swallowed:
        raise CheckFailure(f"the engine loop swallowed {swallowed} exceptions")

    # (b) linearizable reads on leader and follower hosts
    if groups > SAMPLE_GROUPS:
        sample = np.random.default_rng([seed, 3]).choice(
            groups, SAMPLE_GROUPS, replace=False
        ).tolist()
    else:
        sample = list(range(groups))
    t0 = time.monotonic()
    on_leader = read_all([(hosts[leaders[g]], g + 1) for g in sample])
    follower = {
        g: next(n for n in hosts if n != leaders[g]) for g in sample
    }
    on_follower = read_all([(hosts[follower[g]], g + 1) for g in sample])
    for g, a, b in zip(sample, on_leader, on_follower):
        ledger.check(g, "leader-host linearizable read", a)
        ledger.check(g, "follower-host linearizable read", b)
        if b[0] < a[0]:  # a later linearizable read never reads less
            raise CheckFailure(f"group {g + 1}: follower read {b} after {a}")
    t_reads = time.monotonic() - t0

    # (c) every replica of every group reaches one answer
    t0 = time.monotonic()
    deadline = t0 + CONVERGE_S
    want = [ledger.expected(g) for g in range(groups)]
    lagging = list(range(groups))
    while True:
        still = []
        for g in lagging:
            got = {nh.stale_read(g + 1, None) for nh in hosts.values()}
            if len(got) != 1 or (want[g] is not None and got != {want[g]}):
                still.append(g)
        lagging = still
        if not lagging:
            break
        if time.monotonic() >= deadline:
            g = lagging[0]
            raise CheckFailure(
                f"{len(lagging)} groups did not converge within "
                f"{CONVERGE_S:.0f}s, e.g. group {g + 1}: "
                f"{[nh.stale_read(g + 1, None) for nh in hosts.values()]} "
                f"against {want[g]}"
            )
        time.sleep(0.05)
    for g in range(groups):
        ledger.check(g, "local state", hosts[1].stale_read(g + 1, None))
    return {
        "groups_read": len(sample),
        "groups_exact": sum(w is not None for w in want),
        "read_back_s": t_reads,
        "converge_s": time.monotonic() - t0,
    }
