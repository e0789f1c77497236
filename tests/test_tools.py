"""Operator tools tests: import_snapshot quorum repair + checkdisk."""
import json
import os
import time

import pytest

from dragonboat_tpu.config import Config, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.tools import (
    ErrIncompleteSnapshot,
    ErrInvalidMembers,
    ErrPathNotExist,
    check_disk,
    import_snapshot,
)
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

CLUSTER = 1


class KV(IStateMachine):
    def __init__(self):
        self.d = {}

    def update(self, data):
        k, v = data.decode().split("=", 1)
        self.d[k] = v
        return Result(value=1)

    def lookup(self, q):
        return self.d.get(q)

    def save_snapshot(self, w, files, done):
        w.write(json.dumps(self.d).encode())

    def recover_from_snapshot(self, r, files, done):
        self.d = json.loads(r.read().decode())


def _nh_config(nid, tmp, reg):
    return NodeHostConfig(
        deployment_id=11, rtt_millisecond=5,
        nodehost_dir=f"{tmp}/h{nid}",
        raft_address=f"t{nid}:1",
        raft_rpc_factory=lambda l, reg=reg: loopback_factory(l, reg),
    )


def _wait_leader(hosts, deadline_s=60):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        for nid, nh in hosts.items():
            lid, ok = nh.get_leader_id(CLUSTER)
            if ok and lid == nid:
                return nid
        time.sleep(0.02)
    raise AssertionError("no leader")


def test_check_disk(tmp_path):
    out = check_disk(str(tmp_path), count=20, payload_size=512)
    assert out["count"] == 20
    assert out["fsync_p50_us"] > 0
    assert out["synced_writes_per_sec"] > 0
    assert os.listdir(str(tmp_path)) == []  # probe file removed


def test_import_snapshot_quorum_repair(tmp_path):
    """The full repair story: 3-node cluster loses 2 nodes permanently; an
    exported snapshot is imported on the survivor with a single-member
    membership; the survivor restarts alone with all data."""
    reg = _Registry()
    hosts = {}
    members = {n: f"t{n}:1" for n in (1, 2, 3)}
    for nid in (1, 2, 3):
        nh = NodeHost(_nh_config(nid, str(tmp_path), reg))
        nh.start_cluster(
            members, False, lambda c, n: KV(),
            Config(cluster_id=CLUSTER, node_id=nid,
                   election_rtt=20, heartbeat_rtt=4),
        )
        hosts[nid] = nh
    leader = _wait_leader(hosts)
    s = hosts[leader].get_noop_session(CLUSTER)
    for i in range(10):
        hosts[leader].sync_propose(s, f"k{i}=v{i}".encode(), timeout_s=45.0)

    export_root = str(tmp_path / "export")
    os.makedirs(export_root)
    hosts[leader].sync_request_snapshot(
        CLUSTER, export_path=export_root, timeout_s=30.0
    )
    exported = [
        os.path.join(export_root, d) for d in os.listdir(export_root)
    ]
    assert len(exported) == 1, exported
    src = exported[0]
    assert os.path.exists(os.path.join(src, "snapshot.metadata"))

    # catastrophe: all hosts stop; 2 and 3 are gone forever
    for nh in hosts.values():
        nh.stop()

    # operator repairs node 1 with a single-member cluster
    cfg1 = _nh_config(1, str(tmp_path), reg)
    ss = import_snapshot(cfg1, src, {1: "t1:1"}, 1)
    assert ss.imported and ss.membership.addresses == {1: "t1:1"}
    assert ss.membership.removed.keys() >= {2, 3}

    # survivor restarts alone and owns all the data
    nh1 = NodeHost(_nh_config(1, str(tmp_path), reg))
    nh1.start_cluster(
        {}, False, lambda c, n: KV(),
        Config(cluster_id=CLUSTER, node_id=1,
               election_rtt=20, heartbeat_rtt=4),
    )
    deadline = time.time() + 60
    while time.time() < deadline:
        lid, ok = nh1.get_leader_id(CLUSTER)
        if ok and lid == 1:
            break
        time.sleep(0.02)
    else:
        raise AssertionError("survivor never became single-node leader")
    assert nh1.sync_read(CLUSTER, "k9", timeout_s=30.0) == "v9"
    m = nh1.get_cluster_membership(CLUSTER)
    assert set(m.addresses) == {1}
    # and it can still make progress
    s = nh1.get_noop_session(CLUSTER)
    nh1.sync_propose(s, b"post=repair", timeout_s=30.0)
    assert nh1.sync_read(CLUSTER, "post", timeout_s=30.0) == "repair"
    nh1.stop()


def test_import_snapshot_validation(tmp_path):
    cfg = NodeHostConfig(
        deployment_id=1, rtt_millisecond=5,
        nodehost_dir=str(tmp_path / "nh"), raft_address="v1:1",
    )
    with pytest.raises(ErrInvalidMembers):
        import_snapshot(cfg, str(tmp_path), {2: "v2:1"}, 1)  # 1 not a member
    with pytest.raises(ErrPathNotExist):
        import_snapshot(cfg, str(tmp_path / "nope"), {1: "v1:1"}, 1)
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(ErrIncompleteSnapshot):
        import_snapshot(cfg, str(empty), {1: "v1:1"}, 1)


def test_export_does_not_compact_own_history(tmp_path):
    """Regression: an exported snapshot must leave the node's own log and
    snapshot records alone — with compaction_overhead set, a restart after
    export must still replay (the export writes no logdb record, so
    compacting against it would strand the node)."""
    reg = _Registry()
    nh = NodeHost(_nh_config(1, str(tmp_path), reg))
    nh.start_cluster(
        {1: "t1:1"}, False, lambda c, n: KV(),
        Config(cluster_id=CLUSTER, node_id=1, election_rtt=20,
               heartbeat_rtt=2, compaction_overhead=3),
    )
    _wait_leader({1: nh})
    s = nh.get_noop_session(CLUSTER)
    for i in range(20):
        nh.sync_propose(s, f"e{i}=x{i}".encode(), timeout_s=5.0)
    exp = tmp_path / "exp"
    exp.mkdir()
    nh.sync_request_snapshot(CLUSTER, export_path=str(exp), timeout_s=30.0)
    nh.stop()

    nh2 = NodeHost(_nh_config(1, str(tmp_path), reg))
    nh2.start_cluster(
        {}, False, lambda c, n: KV(),
        Config(cluster_id=CLUSTER, node_id=1, election_rtt=20,
               heartbeat_rtt=2, compaction_overhead=3),
    )
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if nh2.stale_read(CLUSTER, "e19") == "x19":
                break
        except Exception:
            pass
        time.sleep(0.02)
    else:
        raise AssertionError("node failed to recover after export")
    nh2.stop()


def test_request_snapshot_bad_export_path(tmp_path):
    from dragonboat_tpu.nodehost import ErrDirNotExist

    reg = _Registry()
    nh = NodeHost(_nh_config(1, str(tmp_path), reg))
    nh.start_cluster(
        {1: "t1:1"}, False, lambda c, n: KV(),
        Config(cluster_id=CLUSTER, node_id=1, election_rtt=20,
               heartbeat_rtt=2),
    )
    try:
        with pytest.raises(ErrDirNotExist):
            nh.request_snapshot(CLUSTER, export_path=str(tmp_path / "missing"))
    finally:
        nh.stop()


def test_raft_top_renders_checked_in_snapshot_via_cli():
    """ISSUE 18 acceptance: `python -m dragonboat_tpu.tools.top` renders
    the checked-in snapshot fixture — header census/counter panel, lanes
    ranked hottest-first (the churning lane with 6 elections and a
    40-entry commit gap outranks everything), --json and --sort modes."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixture = os.path.join(repo, "tests", "data", "top_snapshot.json")

    def cli(*args):
        return subprocess.run(
            [sys.executable, "-m", "dragonboat_tpu.tools.top", *args],
            cwd=repo, capture_output=True, text=True, timeout=60,
        )

    p = cli(fixture)
    assert p.returncode == 0, p.stdout + p.stderr
    out = p.stdout.splitlines()
    assert out[0].startswith("raft-top  lanes=4")
    assert "hbm=52.0MiB" in out[0]
    assert "waste=0.69" in out[0]
    assert "elections 6/5" in out[1]
    assert "backlog 3" in out[1]
    # the table is ranked: the churning lane 101 leads
    first_row = out[3].split()
    assert first_row[1] == "101"
    # --sort ingest re-ranks (all rates are 0 on a frozen view: stable)
    assert cli(fixture, "--sort", "ingest").returncode == 0
    # --limit truncates rows but keeps the header
    p = cli(fixture, "--limit", "1")
    assert len(p.stdout.splitlines()) == 4
    # --json emits the ranked snapshot for downstream tooling
    p = cli(fixture, "--json")
    snap = json.loads(p.stdout)
    assert snap["lanes"][0]["cluster_id"] == 101
    assert snap["lanes"][0]["heat"] > snap["lanes"][-1]["heat"]
    assert snap["census"]["hbm_waste_ratio"] == 0.69
    # a non-snapshot file refuses cleanly
    p = cli(os.path.join(repo, "tests", "data", "timeline_node1.jsonl"))
    assert p.returncode == 2
    assert "error" in p.stderr


def test_raft_top_collects_and_ranks_from_live_host(tmp_path):
    """collect_snapshot folds a live host's lane_stats/lane_counters/
    census/pressure into the snapshot schema the CLI renders, and the
    two-snapshot delta path derives ingest rates."""
    from dragonboat_tpu.config import EngineConfig
    from dragonboat_tpu.tools.top import collect_snapshot, rank_lanes, render
    from tests.test_nodehost import KVSM
    import io as _io

    reg = _Registry()
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=1,
            rtt_millisecond=5,
            raft_address="top1:1",
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            engine=EngineConfig(kind="scalar", max_groups=4, max_peers=4),
        )
    )
    try:
        nh.start_cluster(
            {1: "top1:1"}, False, lambda c, n: KVSM(c, n),
            Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=2),
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            lid, ok = nh.get_leader_id(1)
            if ok and lid == 1:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("no leader")
        sess = nh.get_noop_session(1)
        first = collect_snapshot({1: nh})
        for i in range(4):
            nh.sync_propose(sess, f"k{i}=v".encode(), timeout_s=10.0)
        snap = collect_snapshot({1: nh})
        assert snap["schema"] == 1
        rows = snap["lanes"]
        assert len(rows) == 1 and rows[0]["cluster_id"] == 1
        assert rows[0]["counters"]["commit_advances"] >= 4
        assert snap["census"]["hbm_bytes_total"] == 0  # scalar engine
        assert snap["counters"]["elections_won"] >= 1
        # delta ranking derives a positive ingest rate from two snapshots
        snap["ts"] = first["ts"] + 2.0  # pin dt: no wall-clock flake
        ranked = rank_lanes(snap, prev=first)
        assert ranked[0]["ingest_rate"] > 0
        buf = _io.StringIO()
        render(snap, prev=first, out=buf)
        assert "raft-top  lanes=1" in buf.getvalue()
    finally:
        nh.stop()


def test_logdb_checker_accepts_replicas_and_detects_divergence():
    """The logdb consistency checker passes identical replica logs and
    flags a committed-range divergence / commit-beyond-log violation
    (Log Matching, raft paper 5.3)."""
    from dragonboat_tpu.storage.kv import MemKV
    from dragonboat_tpu.storage.logdb import ShardedLogDB
    from dragonboat_tpu.tools.logdbcheck import check_logdb_consistency
    from dragonboat_tpu.types import Entry, State, Update

    def mk_db(node_id, cmds, commit, divergent_at=None):
        db = ShardedLogDB(kv_factory=lambda shard: MemKV())
        ents = []
        for i, cmd in enumerate(cmds, start=1):
            term = 2 if (divergent_at is not None and i >= divergent_at) else 1
            ents.append(Entry(index=i, term=term, cmd=cmd))
        db.save_raft_state([
            Update(
                cluster_id=CLUSTER, node_id=node_id,
                state=State(term=2, vote=1, commit=commit),
                entries_to_save=ents,
            )
        ])
        return db

    cmds = [f"c{i}".encode() for i in range(1, 8)]
    dbs = {nid: mk_db(nid, cmds, commit=7) for nid in (1, 2)}
    report = check_logdb_consistency(dbs, CLUSTER)
    assert report.ok, report.violations
    assert len(report.replicas) == 2

    # replica 3 diverges at index 5 while both claim commit=7: violation
    dbs[3] = mk_db(3, cmds, commit=7, divergent_at=5)
    report = check_logdb_consistency(dbs, CLUSTER)
    assert not report.ok
    assert any("divergence" in v for v in report.violations)

    # commit beyond the persisted log is a per-replica violation
    dbs2 = {1: mk_db(1, cmds, commit=99)}
    report = check_logdb_consistency(dbs2, CLUSTER)
    assert any("beyond last persisted" in v for v in report.violations)
