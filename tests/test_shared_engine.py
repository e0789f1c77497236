"""Shared-core engine: several NodeHosts in one process advancing all their
replicas in ONE device state (EngineConfig.share_scope), with co-hosted
message exchange short-circuiting the transport.

This is the TPU-native deployment shape from SURVEY §7 ("co-hosted replica
exchange"): one engine per accelerator host, many NodeHost replicas on it.
The reference has no equivalent — its execengine is per-process
(execengine.go:474-560) and all replica traffic rides the NIC.
"""
from __future__ import annotations

import os
import time

import pytest

from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import loopback_factory, _Registry

GROUPS = 4
MEMBERS = {1: "shared:1", 2: "shared:2", 3: "shared:3"}


class _CounterSM(IStateMachine):
    def __init__(self, cluster_id, node_id):
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


@pytest.fixture
def hosts(tmp_path):
    reg = _Registry()
    hs = {}
    for nid, addr in MEMBERS.items():
        cfg = NodeHostConfig(
            raft_address=addr,
            rtt_millisecond=10,
            nodehost_dir=str(tmp_path / f"nh{nid}"),
            raft_rpc_factory=lambda a: loopback_factory(a, reg),
            engine=EngineConfig(
                kind="vector",
                max_groups=3 * GROUPS,
                max_peers=4,
                log_window=64,
                inbox_depth=4,
                max_entries_per_msg=16,
                share_scope="test-shared",
            ),
        )
        hs[nid] = NodeHost(cfg)
    yield hs
    for nh in hs.values():
        nh.stop()


def _bring_up(hosts):
    for c in range(1, GROUPS + 1):
        for nid in MEMBERS:
            hosts[nid].start_cluster(
                dict(MEMBERS),
                False,
                lambda cid, nid_: _CounterSM(cid, nid_),
                Config(
                    node_id=nid, cluster_id=c, election_rtt=20, heartbeat_rtt=2
                ),
            )
    t0 = time.monotonic()
    leaders = {}
    while len(leaders) < GROUPS and time.monotonic() - t0 < 90:
        snap = hosts[1].engine.leader_snapshot()
        leaders = {c: l for c, (l, _t) in snap.items() if l}
        time.sleep(0.02)
    assert len(leaders) == GROUPS, f"elected {len(leaders)}/{GROUPS}"
    return leaders


def test_shared_core_identity(hosts):
    core = hosts[1].engine.core
    assert hosts[2].engine.core is core
    assert hosts[3].engine.core is core
    # distinct host ids per handle
    assert len({hosts[n].engine.host for n in MEMBERS}) == 3


def test_shared_commit_and_read(hosts):
    leaders = _bring_up(hosts)
    total = 0
    for c in range(1, GROUPS + 1):
        nh = hosts[leaders[c]]
        sess = nh.get_noop_session(c)
        rss = nh.propose_batch(sess, [b"x" * 16] * 32, 10)
        rss[-1].wait(10)
        total += sum(1 for rs in rss if rs.result and rs.result.completed)
    assert total == GROUPS * 32
    # all protocol traffic between the three hosts short-circuited the wire
    for nh in hosts.values():
        assert nh.transport.metrics()["sent"] == 0
    # linearizable read through the shared core
    v = hosts[leaders[1]].sync_read(1, None)
    assert v == 32
    # every replica applied (stale reads on the followers converge)
    deadline = time.monotonic() + 10
    for nid in MEMBERS:
        while (
            hosts[nid].stale_read(1, None) != 32
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert hosts[nid].stale_read(1, None) == 32


def test_shared_release_keeps_core_alive(hosts):
    _bring_up(hosts)
    core = hosts[1].engine.core
    # stopping one host must not stop the shared core
    hosts.pop(1).stop()
    assert not core._stopped.is_set()
    # remaining hosts' lanes are still registered
    assert any(k[0] == hosts[2].engine.host for k in core._lanes)


def test_overlapped_decode_pipeline(tmp_path):
    """Forced overlap_decode (the accelerator default): dispatch step t,
    maintain t-1 while the device computes, decode t before the next
    pack. Commits and reads must flow unchanged through the pipelined
    loop."""
    reg = _Registry()
    hs = {}
    for nid, addr in MEMBERS.items():
        hs[nid] = NodeHost(NodeHostConfig(
            raft_address=addr.replace("shared", "ovl"),
            rtt_millisecond=10,
            nodehost_dir=str(tmp_path / f"ovl{nid}"),
            raft_rpc_factory=lambda a: loopback_factory(a, reg),
            engine=EngineConfig(
                kind="vector", max_groups=3 * GROUPS, max_peers=4,
                log_window=64, inbox_depth=4, max_entries_per_msg=16,
                share_scope="test-overlap", overlap_decode=True,
            ),
        ))
    try:
        assert hs[1].engine.core._overlap is True
        for c in range(1, GROUPS + 1):
            for nid in MEMBERS:
                hs[nid].start_cluster(
                    {n: a.replace("shared", "ovl") for n, a in MEMBERS.items()},
                    False,
                    lambda cid, nid_: _CounterSM(cid, nid_),
                    Config(node_id=nid, cluster_id=c, election_rtt=20,
                           heartbeat_rtt=2),
                )
        t0 = time.monotonic()
        leaders = {}
        while len(leaders) < GROUPS and time.monotonic() - t0 < 90:
            snap = hs[1].engine.leader_snapshot()
            leaders = {c: l for c, (l, _t) in snap.items() if l}
            time.sleep(0.02)
        assert len(leaders) == GROUPS
        for c in range(1, GROUPS + 1):
            nh = hs[leaders[c]]
            h = nh.propose_batch_async(
                nh.get_noop_session(c), [b"x"] * 96, 20
            )
            assert h.wait(20) and h.completed == 96, (c, h.completed, h.dropped)
        assert hs[leaders[1]].sync_read(1, None) == 96
    finally:
        for nh in hs.values():
            nh.stop()
