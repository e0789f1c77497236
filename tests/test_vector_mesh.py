"""Multi-device VectorEngine: the engine's (G, ...) state sharded over a
jax.sharding.Mesh along the group axis (conftest pins an 8-device CPU
platform). Proves propose->quorum->commit with the protocol state spread
across devices — the multi-chip scaling story of SURVEY §2.9.1."""
import time

import jax
import numpy as np
import pytest

from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import loopback_factory, _Registry


class KV(IStateMachine):
    def __init__(self, cluster_id, node_id):
        self.d = {}

    def update(self, data):
        k, v = data.decode().split("=", 1)
        self.d[k] = v
        return Result(value=len(self.d))

    def lookup(self, q):
        return self.d.get(q)

    def save_snapshot(self, w, fc, done):
        import json

        w.write(json.dumps(self.d).encode())

    def recover_from_snapshot(self, r, fc, done):
        import json

        self.d = json.loads(r.read().decode())

    def close(self):
        pass


def wait(pred, timeout=30):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.05)
    return False


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a multi-device mesh")
def test_sharded_engine_three_replicas_commit():
    n_dev = jax.device_count()
    groups = 2 * n_dev  # at least two lanes per device
    reg = _Registry()
    members = {1: "m:1", 2: "m:2", 3: "m:3"}
    hosts = {}
    for nid, addr in members.items():
        hosts[nid] = NodeHost(NodeHostConfig(
            deployment_id=11, rtt_millisecond=20, raft_address=addr,
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            engine=EngineConfig(
                kind="vector", max_groups=groups, max_peers=4,
                log_window=64, shard_over_mesh=True,
            ),
        ))
    try:
        # the engine state must actually live on the mesh
        for nh in hosts.values():
            sh = nh.engine._state.term.sharding
            assert len(sh.device_set) == n_dev, sh
        for c in range(1, groups + 1):
            for nid in members:
                hosts[nid].start_cluster(
                    dict(members), False, KV,
                    Config(cluster_id=c, node_id=nid, election_rtt=20,
                           heartbeat_rtt=4),
                )
        pending = set(range(1, groups + 1))
        deadline = time.monotonic() + 150
        while pending and time.monotonic() < deadline:
            pending -= {
                c for c in pending if hosts[1].get_leader_id(c)[1]
            }
            if pending:
                time.sleep(0.1)
        assert not pending, f"{len(pending)} groups leaderless"
        # one write per group through its leader, quorum-committed across
        # lanes living on different devices; leadership can churn under
        # full-suite CPU load between the probe and the propose — retry
        # against the refreshed leader like a real client
        from dragonboat_tpu.requests import RequestError

        for c in range(1, groups + 1):
            for attempt in range(6):
                lid, ok = hosts[1].get_leader_id(c)
                try:
                    if not ok or lid not in hosts:
                        raise RequestError("leaderless between waves")
                    s = hosts[lid].get_noop_session(c)
                    hosts[lid].sync_propose(s, f"g{c}=v{c}".encode(), 30.0)
                    break
                except RequestError:
                    if attempt == 5:
                        raise
                    time.sleep(1.0)
        # linearizable read-back on a follower host for a few groups
        for c in (1, groups // 2, groups):
            lid = hosts[1].get_leader_id(c)[0]
            fid = next(n for n in members if n != lid)
            assert wait(
                lambda c=c, fid=fid: hosts[fid].sync_read(
                    c, f"g{c}", timeout_s=10.0
                ) == f"v{c}",
                timeout=60,
            )
    finally:
        for nh in hosts.values():
            nh.stop()


@pytest.mark.perf
@pytest.mark.skipif(jax.device_count() < 2, reason="needs a multi-device mesh")
def test_sharded_multistep_engine_padding_and_device_routing(tmp_path):
    """shard_over_mesh composes with steps_per_sync>1 on a shared core:
    the lane round-up is stamped (not silent), ghost lanes are never
    allocated or reported, co-hosted cross-shard traffic rides the
    on-device router (zero host Message objects), and a live lane
    add/remove mid-run stays inside the blessed sync seam with zero
    steady-state retraces."""
    from dragonboat_tpu.profile import (
        compile_watch, diff_compiles, diff_sync, sync_audit,
    )
    from dragonboat_tpu.requests import RequestError

    n_dev = jax.device_count()
    reg = _Registry()
    members = {1: "mk4:1", 2: "mk4:2", 3: "mk4:3"}
    groups = 3       # clusters live at bring-up
    max_groups = 12  # 3 hosts x (3 clusters + 1 live-add slot)
    hosts = {}
    for nid, addr in members.items():
        hosts[nid] = NodeHost(NodeHostConfig(
            deployment_id=11, rtt_millisecond=10, raft_address=addr,
            nodehost_dir=str(tmp_path / f"nh{nid}"),
            raft_rpc_factory=lambda l: loopback_factory(l, reg),
            engine=EngineConfig(
                kind="vector", max_groups=max_groups, max_peers=4,
                log_window=64, shard_over_mesh=True, steps_per_sync=4,
                share_scope="mesh-k4",
            ),
        ))
    try:
        core = hosts[1].engine.core
        assert core._multi == 4  # K>1 really composed with the mesh
        # the requested lane count rounds UP to a mesh multiple: the
        # round-up is stamped in stats and the ghost lanes are never
        # handed to the allocator
        padded = -(-max_groups // n_dev) * n_dev
        assert core.kcfg.groups == padded
        assert core._groups_requested == max_groups
        assert len(core._free) == max_groups
        ss = core.step_stats()
        assert ss["mesh_devices"] == n_dev
        assert ss["padded_groups"] == padded - max_groups
        assert len(core._state.term.sharding.device_set) == n_dev
        for c in range(1, groups + 1):
            for nid in members:
                hosts[nid].start_cluster(
                    dict(members), False, KV,
                    Config(cluster_id=c, node_id=nid, election_rtt=20,
                           heartbeat_rtt=4),
                )
        pending = set(range(1, groups + 1))
        deadline = time.monotonic() + 150
        while pending and time.monotonic() < deadline:
            pending -= {c for c in pending if hosts[1].get_leader_id(c)[1]}
            if pending:
                time.sleep(0.1)
        assert not pending, f"{len(pending)} groups leaderless"

        def _propose(c, payload):
            for attempt in range(6):
                lid, ok = hosts[1].get_leader_id(c)
                try:
                    if not ok or lid not in hosts:
                        raise RequestError("leaderless between waves")
                    s = hosts[lid].get_noop_session(c)
                    hosts[lid].sync_propose(s, payload, 30.0)
                    return
                except RequestError:
                    if attempt == 5:
                        raise
                    time.sleep(1.0)

        # warm the steady state — including one full lane add/remove
        # cycle so the batch-size-parameterized activation helpers are
        # compiled — then mark the audit window
        for c in range(1, groups + 1):
            _propose(c, f"warm{c}=w".encode())
        for nid in members:
            hosts[nid].start_cluster(
                dict(members), False, KV,
                Config(cluster_id=groups + 1, node_id=nid,
                       election_rtt=20, heartbeat_rtt=4),
            )
        assert wait(lambda: hosts[1].get_leader_id(groups + 1)[1],
                    timeout=120)
        for nid in members:
            hosts[nid].stop_cluster(groups + 1)
        sync_mark = sync_audit().snapshot()
        compile_mark = compile_watch().snapshot()
        stats_mark = core.step_stats()

        for i in range(10):
            _propose(1, f"x{i}=v".encode())
        # forwarded linearizable read from a follower host: the routed
        # READ_INDEX / READ_INDEX_RESP round trip crosses shards too
        lid = hosts[1].get_leader_id(1)[0]
        fol = next(n for n in members if n != lid)
        assert wait(
            lambda: hosts[fol].sync_read(1, "x0", timeout_s=10.0) == "v",
            timeout=60,
        )

        # steady state: ZERO host Message objects for co-hosted traffic
        # — everything rode the on-device cross-shard router
        stats_mid = core.step_stats()
        for key in ("msgs_replicate", "msgs_broadcast", "msgs_resp"):
            assert stats_mid[key] == stats_mark[key], (key, stats_mid)
        assert (
            stats_mid["msgs_routed_device"]
            > stats_mark["msgs_routed_device"]
        )

        # live lane add: a new cluster joins all three hosts mid-run...
        c_new = groups + 2
        for nid in members:
            hosts[nid].start_cluster(
                dict(members), False, KV,
                Config(cluster_id=c_new, node_id=nid, election_rtt=20,
                       heartbeat_rtt=4),
            )
        assert wait(lambda: hosts[1].get_leader_id(c_new)[1], timeout=120)
        _propose(c_new, b"live=add")
        # ...and leaves again; the mesh keeps serving the old lanes
        for nid in members:
            hosts[nid].stop_cluster(c_new)
        _propose(1, b"after=remove")

        # across the add/remove the device router kept carrying traffic;
        # a handful of host messages are EXPECTED mid-add (a lane whose
        # peers' lanes don't exist yet rides the host fallback by
        # construction), so only the device counter is asserted here
        stats = core.step_stats()
        assert stats["msgs_routed_device"] > stats_mid["msgs_routed_device"]
        d = diff_sync(sync_mark, sync_audit().snapshot())
        assert d["in_seam"] > 0
        bad = sync_audit().out_of_seam_in_package()
        assert not bad, bad
        # steady state compiles nothing: the sharded scanned kernel is
        # warm and lane add/remove reuses it
        dc = diff_compiles(compile_mark, compile_watch().snapshot())
        assert not dc["per_function"], dc
        # lane_stats reports only REAL lanes: padding never leaks ghosts
        # and cluster c_new's lanes were freed on stop
        assert len(core.lane_stats()) <= 3 * groups
    finally:
        for nh in hosts.values():
            nh.stop()


def test_shard_over_mesh_on_one_device_raises(monkeypatch):
    from dragonboat_tpu.engine import vector

    one = jax.devices()[:1]
    monkeypatch.setattr(vector.jax, "devices", lambda: one)
    cfg = NodeHostConfig(
        engine=EngineConfig(kind="vector", max_groups=8, shard_over_mesh=True)
    )
    with pytest.raises(ValueError, match="more than one visible jax device"):
        vector.VectorEngine(None, nh_config=cfg)
