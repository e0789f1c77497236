"""Witness/observer LANE VARIANTS at vector scale (thesis 4.2.1 /
11.7.2 — the scalar conformance lives in test_witness_conformance /
test_observer_conformance; this file proves the vector engine's per-lane
role tensors + payload-stripped replication end to end):

  * a witness joined through the membership-change API votes/acks and
    counts toward the commit quorum while storing ZERO payload bytes
    (lane_stats probe) and never mutating its SM;
  * an observer replicates the full log (SM converges) but never
    campaigns or votes, and promotes to a full member via add_node;
  * both lane flavors survive removal and re-join (the membership-change
    scenario family at vector scale).
"""
import json
import threading
import time
import zlib

import pytest

from conftest import VECTOR_KINDS, engine_kw, host_of_kind
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.ops.state import ROLE
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

CLUSTER = 7


class KV(IStateMachine):
    def __init__(self):
        self.d = {}

    def update(self, data):
        k, v = data.decode().split("=", 1)
        self.d[k] = v
        return Result(value=1)

    def lookup(self, q):
        return self.d.get(q)

    def get_hash(self):
        return zlib.crc32(json.dumps(sorted(self.d.items())).encode())

    def save_snapshot(self, w, files, done):
        w.write(json.dumps(self.d).encode())

    def recover_from_snapshot(self, r, files, done):
        self.d = json.loads(r.read().decode())


def _mk_host(nid, reg, engine_kind):
    nh = NodeHost(
        NodeHostConfig(
            deployment_id=9,
            rtt_millisecond=5,
            raft_address=f"wl{nid}:1",
            raft_rpc_factory=lambda l, reg=reg: loopback_factory(l, reg),
            engine=EngineConfig(
                **engine_kw(engine_kind), max_groups=32, max_peers=4,
                log_window=64,
            ),
        )
    )
    return host_of_kind(nh, engine_kind)


def _cfg(nid, **kw):
    base = dict(
        cluster_id=CLUSTER, node_id=nid, election_rtt=20, heartbeat_rtt=4
    )
    base.update(kw)
    return Config(**base)


def _wait_leader(hosts, deadline_s=30.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        for nid, nh in hosts.items():
            try:
                lid, ok = nh.get_leader_id(CLUSTER)
            except Exception:
                continue
            if ok and lid == nid:
                return nid
        time.sleep(0.02)
    raise AssertionError("no leader")


def _propose_n(nh, n, tag, timeout_s=5.0):
    s = nh.get_noop_session(CLUSTER)
    for i in range(n):
        nh.sync_propose(s, f"k{i % 4}={tag}{i}".encode(), timeout_s=timeout_s)


@pytest.fixture(params=VECTOR_KINDS)
def two_plus_witness(request):
    """Hosts 1,2 full members; host 3 joins as a WITNESS through the
    membership-change API (request_add_witness + join start)."""
    reg = _Registry()
    hosts = {nid: _mk_host(nid, reg, request.param) for nid in (1, 2, 3)}
    members = {1: "wl1:1", 2: "wl2:1"}
    for nid in (1, 2):
        hosts[nid].start_cluster(
            members, False, lambda c, n: KV(), _cfg(nid)
        )
    leader = _wait_leader({n: hosts[n] for n in (1, 2)})
    hosts[leader].sync_request_add_witness(
        CLUSTER, 3, "wl3:1", timeout_s=10.0
    )
    hosts[3].start_cluster(
        {}, True, lambda c, n: KV(), _cfg(3, is_witness=True)
    )
    try:
        yield hosts, leader
    finally:
        for nh in hosts.values():
            try:
                nh.stop()
            except Exception:
                pass


def test_witness_lane_zero_payload_and_role(two_plus_witness):
    """Across a seeded traffic run the witness lane reports the WITNESS
    role and ZERO resident payload bytes, and its SM never applies a
    client update (the empty-SM hash)."""
    hosts, leader = two_plus_witness
    _propose_n(hosts[leader], 60, "w")
    # let replication toward the witness settle
    deadline = time.monotonic() + 20
    stats = None
    while time.monotonic() < deadline:
        stats = hosts[3].engine.lane_stats().get(CLUSTER)
        if stats is not None and stats["term"] > 0:
            break
        time.sleep(0.05)
    assert stats is not None, "witness lane never activated"
    assert stats["role"] == ROLE.WITNESS, stats
    assert stats["payload_bytes"] == 0, (
        f"witness lane stored payload bytes: {stats}"
    )
    # the witness SM never saw a client update
    empty_hash = KV().get_hash()
    assert hosts[3].get_sm_hash(CLUSTER) == empty_hash
    # the full members DID apply the payloads
    assert hosts[leader].get_sm_hash(CLUSTER) != empty_hash


def test_witness_counts_toward_commit_quorum(two_plus_witness):
    """2 full members + 1 witness = 3 voters, quorum 2. With one full
    member down, commit requires the WITNESS ack — proposals that still
    commit prove the witness is a live quorum participant."""
    hosts, leader = two_plus_witness
    _propose_n(hosts[leader], 10, "pre")
    # wait until the witness is an acking member (its lane is active and
    # past the join): commit with follower down requires it
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        st = hosts[3].engine.lane_stats().get(CLUSTER)
        if st is not None and st["leader_id"] == leader:
            break
        time.sleep(0.05)
    follower = 2 if leader == 1 else 1
    hosts[follower].stop_cluster(CLUSTER)
    try:
        # leader + witness form the quorum now
        _propose_n(hosts[leader], 5, "q", timeout_s=10.0)
    finally:
        hosts[follower].restart_cluster(CLUSTER)
    st = hosts[3].engine.lane_stats().get(CLUSTER)
    assert st is not None and st["payload_bytes"] == 0


@pytest.mark.parametrize("engine_kind", VECTOR_KINDS)
def test_observer_replicates_without_voting_then_promotes(engine_kind):
    """An observer lane replicates + applies the full log (SM hash
    converges) but never votes or campaigns; add_node promotes it to a
    full member in place."""
    reg = _Registry()
    hosts = {nid: _mk_host(nid, reg, engine_kind) for nid in (1, 2, 3)}
    members = {1: "wl1:1", 2: "wl2:1"}
    try:
        for nid in (1, 2):
            hosts[nid].start_cluster(
                members, False, lambda c, n: KV(), _cfg(nid)
            )
        leader = _wait_leader({n: hosts[n] for n in (1, 2)})
        hosts[leader].sync_request_add_observer(
            CLUSTER, 3, "wl3:1", timeout_s=10.0
        )
        hosts[3].start_cluster(
            {}, True, lambda c, n: KV(), _cfg(3, is_observer=True)
        )
        _propose_n(hosts[leader], 40, "o")
        # the observer applies the full payload log: hash convergence
        want = hosts[leader].get_sm_hash(CLUSTER)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                if hosts[3].get_sm_hash(CLUSTER) == want:
                    break
            except Exception:
                pass
            time.sleep(0.05)
        assert hosts[3].get_sm_hash(CLUSTER) == want, "observer diverged"
        st = hosts[3].engine.lane_stats().get(CLUSTER)
        assert st is not None and st["role"] == ROLE.OBSERVER
        # observers never campaign: leadership stayed where it was
        lid, ok = hosts[leader].get_leader_id(CLUSTER)
        assert ok and lid == leader
        # promote to full member, in place
        hosts[leader].sync_request_add_node(
            CLUSTER, 3, "wl3:1", timeout_s=10.0
        )
        _propose_n(hosts[leader], 5, "p")
        deadline = time.monotonic() + 20
        role = None
        while time.monotonic() < deadline:
            st = hosts[3].engine.lane_stats().get(CLUSTER)
            role = st["role"] if st else None
            if role == ROLE.FOLLOWER:
                break
            time.sleep(0.05)
        assert role == ROLE.FOLLOWER, f"observer not promoted: role={role}"
    finally:
        for nh in hosts.values():
            try:
                nh.stop()
            except Exception:
                pass


@pytest.mark.parametrize("engine_kind", VECTOR_KINDS)
def test_witness_removal_and_rejoin(engine_kind):
    """The churn half: remove the witness, re-add a FRESH witness id, and
    the group keeps committing throughout (membership change over lane
    variants at vector scale)."""
    reg = _Registry()
    hosts = {nid: _mk_host(nid, reg, engine_kind) for nid in (1, 2, 3)}
    members = {1: "wl1:1", 2: "wl2:1"}
    try:
        for nid in (1, 2):
            hosts[nid].start_cluster(
                members, False, lambda c, n: KV(), _cfg(nid)
            )
        leader = _wait_leader({n: hosts[n] for n in (1, 2)})
        hosts[leader].sync_request_add_witness(
            CLUSTER, 3, "wl3:1", timeout_s=10.0
        )
        hosts[3].start_cluster(
            {}, True, lambda c, n: KV(), _cfg(3, is_witness=True)
        )
        _propose_n(hosts[leader], 10, "a")
        hosts[leader].sync_request_delete_node(CLUSTER, 3, timeout_s=10.0)
        hosts[3].stop_cluster(CLUSTER)
        _propose_n(hosts[leader], 10, "b")
        # fresh witness id on the same host (removed ids never rejoin)
        hosts[leader].sync_request_add_witness(
            CLUSTER, 4, "wl3:1", timeout_s=10.0
        )
        hosts[3].start_cluster(
            {}, True, lambda c, n: KV(),
            _cfg(4, is_witness=True),
        )
        _propose_n(hosts[leader], 10, "c")
        m = hosts[leader].get_cluster_membership(CLUSTER)
        assert 4 in m.witnesses and 3 not in m.witnesses
        st = hosts[3].engine.lane_stats().get(CLUSTER)
        assert st is not None and st["payload_bytes"] == 0
    finally:
        for nh in hosts.values():
            try:
                nh.stop()
            except Exception:
                pass


def test_witness_zero_payload_on_cohosted_multistep(tmp_path):
    """The device-routing bypass regression: on a SHARED-core engine at
    steps_per_sync>1, co-hosted replication is routed on device — but
    witness-bound traffic must stay on the (payload-stripping) host
    path, or full client payloads land in the witness arena. Route
    tables exclude wit_slots; this asserts the zero-payload contract in
    exactly that configuration."""
    reg = _Registry()
    scope = "wl-multistep"
    members = {1: "wms1:1", 2: "wms2:1"}

    def mk(nid):
        return NodeHost(
            NodeHostConfig(
                deployment_id=9,
                rtt_millisecond=10,
                nodehost_dir=str(tmp_path / f"wms{nid}"),
                raft_address=f"wms{nid}:1",
                raft_rpc_factory=lambda l, reg=reg: loopback_factory(l, reg),
                engine=EngineConfig(
                    kind="vector", max_groups=8, max_peers=4, log_window=64,
                    inbox_depth=8, max_entries_per_msg=8, share_scope=scope,
                    steps_per_sync=4,
                ),
            )
        )

    hosts = {nid: mk(nid) for nid in (1, 2, 3)}
    try:
        for nid in (1, 2):
            hosts[nid].start_cluster(
                members, False, lambda c, n: KV(), _cfg(nid)
            )
        leader = _wait_leader({n: hosts[n] for n in (1, 2)}, deadline_s=120)
        hosts[leader].sync_request_add_witness(
            CLUSTER, 3, "wms3:1", timeout_s=15.0
        )
        hosts[3].start_cluster(
            {}, True, lambda c, n: KV(), _cfg(3, is_witness=True)
        )
        _propose_n(hosts[leader], 40, "co", timeout_s=10.0)
        deadline = time.monotonic() + 30
        st = None
        while time.monotonic() < deadline:
            st = hosts[3].engine.lane_stats().get(CLUSTER)
            if st is not None and st["term"] > 0 and st["leader_id"] == leader:
                break
            time.sleep(0.05)
        assert st is not None and st["role"] == ROLE.WITNESS, st
        assert st["payload_bytes"] == 0, (
            f"co-hosted device routing leaked payload into the witness "
            f"lane: {st}"
        )
    finally:
        for nh in hosts.values():
            try:
                nh.stop()
            except Exception:
                pass
