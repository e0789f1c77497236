"""Fleet bring-up (VERDICT r3 item 5): batched StartCluster + vectorized
leadership readout. The 50k-group regime from BASELINE.json comes up in
~42s on one CPU core (.verify/dbg_bringup.py measured run: start_clusters
25.6s + elections 15.7s); this test guards the mechanism at CI-friendly
scale with CI-generous bounds."""
from __future__ import annotations

import os
import time

import jax

from dragonboat_tpu import _jaxenv
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import loopback_factory, _Registry


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


def test_bulk_fleet_bring_up(tmp_path):
    """2048 single-replica groups: bulk start (one bootstrap fsync per
    shard) + self-election + one vectorized leadership snapshot."""
    G = 2048
    reg = _Registry()
    nh = NodeHost(NodeHostConfig(
        raft_address="bu:1", rtt_millisecond=10,
        nodehost_dir=str(tmp_path / "nh"),
        raft_rpc_factory=lambda a: loopback_factory(a, reg),
        engine=EngineConfig(kind="vector", max_groups=G, max_peers=4,
                            log_window=64, inbox_depth=4,
                            max_entries_per_msg=16)))
    try:
        t0 = time.monotonic()
        nh.start_clusters([
            ({1: "bu:1"}, False, lambda cid, n: _SM(),
             Config(node_id=1, cluster_id=c, election_rtt=20,
                    heartbeat_rtt=2))
            for c in range(1, G + 1)
        ])
        leaders = {}
        while len(leaders) < G and time.monotonic() - t0 < 120:
            snap = nh.engine.leader_snapshot()
            leaders = {c: l for c, (l, _t) in snap.items() if l}
            time.sleep(0.05)
        took = time.monotonic() - t0
        assert len(leaders) == G, f"{len(leaders)}/{G} elected in {took:.1f}s"
        # every group is led by its only replica
        assert set(leaders.values()) == {1}
        # the fleet is live: a proposal commits on an arbitrary group
        r = nh.sync_propose(nh.get_noop_session(G // 2), b"x", 15.0)
        assert r.value == 1
    finally:
        nh.stop()


def test_bulk_start_matches_incremental(tmp_path):
    """start_clusters and start_cluster produce identical on-disk
    bootstraps: a fleet-started node restarts through the normal path."""
    reg = _Registry()

    def mk():
        return NodeHost(NodeHostConfig(
            raft_address="bu2:1", rtt_millisecond=10,
            nodehost_dir=str(tmp_path / "nh"),
            raft_rpc_factory=lambda a: loopback_factory(a, reg),
            engine=EngineConfig(kind="vector", max_groups=8, max_peers=4,
                                log_window=64)))

    nh = mk()
    nh.start_clusters([
        ({1: "bu2:1"}, False, lambda cid, n: _SM(),
         Config(node_id=1, cluster_id=c, election_rtt=20, heartbeat_rtt=2))
        for c in (1, 2)
    ])
    t0 = time.monotonic()
    while time.monotonic() - t0 < 60:
        if all(nh.get_leader_id(c)[1] for c in (1, 2)):
            break
        time.sleep(0.02)
    for c in (1, 2):
        nh.sync_propose(nh.get_noop_session(c), b"p", 15.0)
    nh.stop()
    # restart through the INCREMENTAL path: bootstrap records must validate
    nh = mk()
    try:
        for c in (1, 2):
            nh.start_cluster({1: "bu2:1"}, False, lambda cid, n: _SM(),
                             Config(node_id=1, cluster_id=c,
                                    election_rtt=20, heartbeat_rtt=2))
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            if all(nh.stale_read(c, None) >= 1 for c in (1, 2)):
                break
            time.sleep(0.05)
        for c in (1, 2):
            assert nh.stale_read(c, None) >= 1
    finally:
        nh.stop()


def test_compile_cache_placement(monkeypatch):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: JAX honours the variable, code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert _jaxenv.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        # otherwise: the one fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert _jaxenv.enable_compile_cache() == _jaxenv.COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
