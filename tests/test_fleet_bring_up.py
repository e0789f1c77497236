"""Fleet bring-up through NodeHost.start_clusters at the 5-replica shapes:
the bulk path leaves what the per-node path (start_cluster a replica)
leaves, every bootstrap record is synced before any raft state of its
node is saved, a restart with a snapshot still takes the per-node restore
path, and the engine's bring-up account is kept once a host."""
import os
import time

import numpy as np
import pytest

from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

R = 5
ELECT_S = 180.0


class _SM(IStateMachine):
    def __init__(self, *a):
        self.n = 0

    def update(self, data):
        self.n += 1
        return Result(value=self.n)

    def lookup(self, q):
        return self.n

    def save_snapshot(self, w, fc, done):
        w.write(self.n.to_bytes(8, "little"))

    def recover_from_snapshot(self, r, fc, done):
        self.n = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


def _fleet(tmp_path, scope: str, groups: int, **raft):
    reg = _Registry()
    members = {n: f"host:{n}" for n in range(1, R + 1)}
    hosts = {
        n: NodeHost(NodeHostConfig(
            raft_address=addr, rtt_millisecond=10,
            nodehost_dir=str(tmp_path / scope / f"nh{n}"),
            raft_rpc_factory=lambda a: loopback_factory(a, reg),
            engine=EngineConfig(
                kind="vector", max_groups=R * groups, max_peers=8,
                log_window=64, inbox_depth=8, max_entries_per_msg=16,
                share_scope=f"fleet-bring-up-{scope}",
            ),
        ))
        for n, addr in members.items()
    }

    def spec(n, g):
        return (dict(members), False, lambda cid, nid: _SM(),
                Config(node_id=n, cluster_id=g, election_rtt=20,
                       heartbeat_rtt=2, **raft))
    return hosts, spec


def _elected(core, groups: int) -> dict:
    """group -> the one leader every replica names, once all do."""
    deadline = time.monotonic() + ELECT_S
    while True:
        snap = core.leader_snapshot()
        by_group: dict = {}
        for (_host, cid), (lid, _term) in snap.items():
            by_group.setdefault(cid, set()).add(lid)
        if (len(snap) == R * groups
                and all(len(v) == 1 and 0 not in v for v in by_group.values())):
            return {cid: v.pop() for cid, v in by_group.items()}
        assert time.monotonic() < deadline, "the fleet did not elect"
        time.sleep(0.05)


def _lanes(core) -> dict:
    """What activation left of every lane, by its key."""
    out = {}
    for key, lane in core._lanes.items():
        g = lane.g
        out[key] = (
            g, int(core._m_host[g]), int(core._m_base[g]),
            int(core._m_devfirst[g]), dict(lane.slots), lane.mem_sig,
            int(core._m_tick_cap[g]), bool(core._m_active[g]),
        )
    return out


def test_bulk_and_per_node_bring_up_leave_the_same_fleet(tmp_path):
    """64 groups x 5 through start_clusters and through start_cluster a
    replica: the same bootstrap records on disk, the same lanes and
    mirrors of activation, and a fleet that elects one leader a group
    that all five replicas name."""
    G = 64
    seen = {}
    for path in ("bulk", "per-node"):
        hosts, spec = _fleet(tmp_path, path, G)
        try:
            for n, nh in hosts.items():
                if path == "bulk":
                    nh.start_clusters([spec(n, g) for g in range(1, G + 1)])
                else:
                    for g in range(1, G + 1):
                        nh.start_cluster(*spec(n, g))
            core = hosts[1].engine.core
            leaders = _elected(core, G)
            boots = {
                (n, g): nh.logdb.get_bootstrap_info(g, n)
                for n, nh in hosts.items() for g in range(1, G + 1)
            }
            seen[path] = (
                {k: (b.addresses, b.join, b.type) for k, b in boots.items()},
                _lanes(core), sorted(leaders),
                set(hosts[1].engine.leader_snapshot()),
            )
        finally:
            for nh in hosts.values():
                nh.stop()
    bulk, per_node = seen["bulk"], seen["per-node"]
    assert bulk[0] == per_node[0]
    assert len(bulk[0]) == R * G
    assert bulk[1] == per_node[1]
    assert bulk[2] == per_node[2] == list(range(1, G + 1))
    # a host's own look names its own lanes only
    assert bulk[3] == per_node[3] == set(range(1, G + 1))


def test_every_bootstrap_record_is_synced_before_raft_state(tmp_path):
    """A logdb wrapped on every host: a raft-state save that names a node
    whose bootstrap record has not returned from its fsynced batch fails
    the bring-up."""
    G = 16
    hosts, spec = _fleet(tmp_path, "order", G)
    synced: set = set()
    early = []
    saved = []
    try:
        for nh in hosts.values():
            db = nh.logdb
            boot, save, deferred = (
                db.save_bootstrap_infos, db.save_raft_state,
                db.save_raft_state_deferred,
            )

            def save_boots(items, _boot=boot):
                _boot(items)
                synced.update((cid, nid) for cid, nid, _b in items)

            def check(updates):
                for u in updates:
                    saved.append((u.cluster_id, u.node_id))
                    if (u.cluster_id, u.node_id) not in synced:
                        early.append((u.cluster_id, u.node_id))

            def save_state(updates, *a, _save=save, **k):
                check(updates)
                return _save(updates, *a, **k)

            def save_deferred(updates, _deferred=deferred):
                check(updates)
                return _deferred(updates)

            db.save_bootstrap_infos = save_boots
            db.save_raft_state = save_state
            db.save_raft_state_deferred = save_deferred
        for n, nh in hosts.items():
            nh.start_clusters([spec(n, g) for g in range(1, G + 1)])
        _elected(hosts[1].engine.core, G)
    finally:
        for nh in hosts.values():
            nh.stop()
    assert len(synced) == R * G
    assert saved, "no raft state was saved"
    assert early == []


def test_a_restart_with_a_snapshot_takes_the_per_node_restore_path(tmp_path):
    """A replica stopped after its group snapshotted comes back through
    restart_cluster -> start_cluster, one node: its lane starts at the
    snapshot's index and its state machine holds what was acknowledged."""
    G = 4
    hosts, spec = _fleet(tmp_path, "restart", G, snapshot_entries=8,
                         compaction_overhead=2)
    try:
        for n, nh in hosts.items():
            nh.start_clusters([spec(n, g) for g in range(1, G + 1)])
        core = hosts[1].engine.core
        leaders = _elected(core, G)
        cid = 1
        lead = hosts[leaders[cid]]
        for _ in range(24):
            lead.sync_propose(lead.get_noop_session(cid), b"x", 15.0)
        victim = next(n for n in hosts if n != leaders[cid])
        nh = hosts[victim]
        deadline = time.monotonic() + 60
        while nh._get_node(cid).snapshotter.get_most_recent_snapshot() is None:
            assert time.monotonic() < deadline, "no snapshot was taken"
            time.sleep(0.05)
        nh.stop_cluster(cid)
        bulk_calls = []
        orig = nh.start_clusters
        nh.start_clusters = lambda specs: bulk_calls.append(specs) or orig(specs)
        nh.restart_cluster(cid)
        assert bulk_calls == []
        node = nh._get_node(cid)
        ss = node.snapshotter.get_most_recent_snapshot()
        assert ss is not None and ss.index > 0
        lane = node._vec_lane
        deadline = time.monotonic() + 60
        while not (lane is not None and lane.active):
            assert time.monotonic() < deadline, "the lane never activated"
            time.sleep(0.02)
            lane = node._vec_lane
        assert int(core._m_base[lane.g]) > 0  # the lane starts at its image
        while nh.stale_read(cid, None) < 24:
            assert time.monotonic() < deadline, "the replica fell behind"
            time.sleep(0.05)
        assert nh.stale_read(cid, None) == 24
    finally:
        for h in hosts.values():
            h.stop()


def test_the_bring_up_account(tmp_path):
    """Each host's start_clusters by its parts, the lanes activated with
    the seconds it took, and the launches to a fleet whose every lane
    knows a leader, counted once."""
    G = 8
    hosts, spec = _fleet(tmp_path, "account", G)
    try:
        for n, nh in hosts.items():
            nh.start_clusters([spec(n, g) for g in range(1, G + 1)])
        core = hosts[1].engine.core
        _elected(core, G)
        deadline = time.monotonic() + 30
        while core.bringup_stats()["elect_launches"] is None:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        b = hosts[3].engine.bringup_stats()  # one account, through any host
        assert sorted(b["hosts"]) == sorted(nh.engine.host for nh in hosts.values())
        for parts in b["hosts"].values():
            assert parts["nodes"] == G
            assert parts["total_s"] == pytest.approx(
                parts["prepare_s"] + parts["bootstrap_s"] + parts["launch_s"]
            )
        assert b["start_clusters_s"] == pytest.approx(
            sum(p["total_s"] for p in b["hosts"].values())
        )
        assert b["activated"] == R * G and b["activate_s"] > 0
        first = b["elect_launches"]
        assert isinstance(first, int) and first >= 1
        assert b["first_activation_launch"] is not None
        # once a bring-up: later launches leave the count as it was
        launches = core.launch_no
        lead = hosts[_elected(core, G)[1]]
        lead.sync_propose(lead.get_noop_session(1), b"y", 15.0)
        assert core.launch_no > launches
        assert core.bringup_stats()["elect_launches"] == first
        # a host's own surfaces hold its own lanes
        for nh in hosts.values():
            assert set(nh.engine.lane_stats()) == set(range(1, G + 1))
        assert len(core.lane_stats()) == R * G
        gauges = hosts[2]
        gauges._export_health_gauges()
        for g in range(1, G + 1):
            assert gauges.metrics.gauge_value("engine_lane_term", (g, 2)) >= 1
        assert np.all(core._m_active[:R * G])
    finally:
        for nh in hosts.values():
            nh.stop()
