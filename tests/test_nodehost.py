"""End-to-end NodeHost tests: the minimum slice from SURVEY.md §7 step 3 —
propose → step → commit → apply → notify on single- and multi-replica
deployments over the loopback transport (cf. nodehost_test.go patterns).

Every test runs twice: once with the scalar per-group engine and once with
the vector engine (the device kernel advancing all groups per step)."""
import threading
import time

import pytest

from conftest import ENGINE_KINDS, engine_kw, host_of_kind
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.requests import ErrRejected, ErrTimeout
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory


@pytest.fixture(params=ENGINE_KINDS)
def engine_kind(request):
    return request.param


class KVSM(IStateMachine):
    """In-memory KV test SM (cf. internal/tests/kvtest.go, sans chaos)."""

    instances = []

    def __init__(self, cluster_id, node_id):
        self.cluster_id = cluster_id
        self.node_id = node_id
        self.data = {}
        self.update_count = 0
        KVSM.instances.append(self)

    def update(self, cmd: bytes) -> Result:
        k, v = cmd.decode().split("=", 1)
        self.data[k] = v
        self.update_count += 1
        return Result(value=self.update_count)

    def lookup(self, q):
        return self.data.get(q)

    def save_snapshot(self, w, files, done):
        import json

        w.write(json.dumps([self.data, self.update_count]).encode())

    def recover_from_snapshot(self, r, files, done):
        import json

        self.data, self.update_count = json.loads(r.read().decode())


def mk_nodehost(addr, registry, rtt_ms=5, nodehost_dir="", engine_kind="scalar"):
    cfg = NodeHostConfig(
        deployment_id=1,
        rtt_millisecond=rtt_ms,
        raft_address=addr,
        nodehost_dir=nodehost_dir,
        raft_rpc_factory=lambda listen: loopback_factory(listen, registry),
        # one canonical shape for every vector-engine test so the whole
        # suite shares a single compiled kernel (make_step_fn lru cache)
        engine=EngineConfig(
            **engine_kw(engine_kind), max_groups=32, max_peers=4,
            log_window=64,
        ),
    )
    return host_of_kind(NodeHost(cfg), engine_kind)


def group_config(cluster_id, node_id, **kw):
    return Config(
        cluster_id=cluster_id,
        node_id=node_id,
        election_rtt=10,
        heartbeat_rtt=2,
        **kw,
    )


def wait_for(pred, timeout=30.0):
    # default must comfortably cover the vector engine's cold kernel
    # compile (~10s on a busy 1-cpu box): elections cannot complete until
    # the first step_fn compilation returns
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


@pytest.fixture(autouse=True)
def clear_instances():
    KVSM.instances = []
    yield
    KVSM.instances = []


def test_single_node_propose_and_read(engine_kind):
    reg = _Registry()
    nh = mk_nodehost("a:1", reg, engine_kind=engine_kind)
    try:
        nh.start_cluster({1: "a:1"}, False, KVSM, group_config(100, 1))
        assert wait_for(lambda: nh.get_leader_id(100)[1])
        s = nh.get_noop_session(100)
        r = nh.sync_propose(s, b"k1=v1", timeout_s=20.0)
        assert r.value == 1
        assert nh.sync_read(100, "k1", timeout_s=20.0) == "v1"
        # a second propose
        r2 = nh.sync_propose(s, b"k2=v2")
        assert r2.value == 2
        assert nh.sync_read(100, "k2") == "v2"
    finally:
        nh.stop()


def test_three_replicas_replicate(engine_kind):
    reg = _Registry()
    members = {1: "a:1", 2: "b:2", 3: "c:3"}
    nhs = [mk_nodehost(addr, reg, engine_kind=engine_kind) for addr in members.values()]
    try:
        for nid, nh in zip(members, nhs):
            nh.start_cluster(members, False, KVSM, group_config(5, nid))
        assert wait_for(
            lambda: any(nh.get_leader_id(5)[1] for nh in nhs), timeout=45
        )
        # find leader host
        def leader_nh():
            for nh in nhs:
                lid, ok = nh.get_leader_id(5)
                if ok:
                    nid = {v: k for k, v in members.items()}[nh.raft_address()]
                    if lid == nid:
                        return nh
            return None

        assert wait_for(lambda: leader_nh() is not None, timeout=45)
        lnh = leader_nh()
        s = lnh.get_noop_session(5)
        res = lnh.sync_propose(s, b"x=42", timeout_s=20.0)
        assert res.value == 1
        # all three replicas converge
        assert wait_for(
            lambda: sum(1 for sm in KVSM.instances if sm.data.get("x") == "42") == 3
        )
        # linearizable read from the leader host
        assert lnh.sync_read(5, "x") == "42"
    finally:
        for nh in nhs:
            nh.stop()


def test_many_groups_one_nodehost(engine_kind):
    reg = _Registry()
    nh = mk_nodehost("a:1", reg, engine_kind=engine_kind)
    n_groups = 16
    try:
        for g in range(1, n_groups + 1):
            nh.start_cluster({1: "a:1"}, False, KVSM, group_config(g, 1))
        assert wait_for(
            lambda: all(nh.get_leader_id(g)[1] for g in range(1, n_groups + 1)),
            timeout=20,
        )
        for g in range(1, n_groups + 1):
            s = nh.get_noop_session(g)
            nh.sync_propose(s, b"g=%d" % g)
        for g in range(1, n_groups + 1):
            assert nh.sync_read(g, "g") == str(g)
    finally:
        nh.stop()


def test_session_dedup_e2e(engine_kind):
    reg = _Registry()
    nh = mk_nodehost("a:1", reg, engine_kind=engine_kind)
    try:
        nh.start_cluster({1: "a:1"}, False, KVSM, group_config(7, 1))
        assert wait_for(lambda: nh.get_leader_id(7)[1])
        s = nh.sync_get_session(7)
        r1 = nh.sync_propose(s, b"a=1")
        # NOT calling proposal_completed: retry of same series must dedup
        rs = nh.propose(s, b"a=SHOULD-NOT-APPLY", 4.0)
        r2 = rs.wait(5.0)
        assert r2.completed
        assert r2.result == r1
        sm = KVSM.instances[-1]  # instances[0] is the start-time type probe
        assert sm.data["a"] == "1"
        s.proposal_completed()
        r3 = nh.sync_propose(s, b"b=2")
        assert sm.data["b"] == "2"
        s.proposal_completed()
        nh.sync_close_session(s)
        # proposing on closed session rejected
        s.series_id = 99
        with pytest.raises(ErrRejected):
            nh.sync_propose(s, b"c=3")
    finally:
        nh.stop()


def test_membership_change_e2e(engine_kind):
    reg = _Registry()
    members = {1: "a:1", 2: "b:2", 3: "c:3"}
    nhs = {nid: mk_nodehost(addr, reg, engine_kind=engine_kind) for nid, addr in members.items()}
    try:
        for nid in (1, 2):
            nhs[nid].start_cluster(
                {1: "a:1", 2: "b:2"}, False, KVSM, group_config(9, nid)
            )
        assert wait_for(
            lambda: any(nhs[n].get_leader_id(9)[1] for n in (1, 2)), timeout=45
        )
        lid = next(
            nhs[n].get_leader_id(9)[0] for n in (1, 2) if nhs[n].get_leader_id(9)[1]
        )
        lnh = nhs[lid]
        lnh.sync_request_add_node(9, 3, "c:3", timeout_s=25.0)
        m = lnh.get_cluster_membership(9)
        assert m.addresses.get(3) == "c:3"
        # node 3 joins
        nhs[3].start_cluster({}, True, KVSM, group_config(9, 3))
        s = lnh.get_noop_session(9)
        lnh.sync_propose(s, b"after=join")
        assert wait_for(
            lambda: sum(
                1 for sm in KVSM.instances if sm.data.get("after") == "join"
            )
            == 3,
            timeout=45,
        )
        # remove node 3 again
        lnh.sync_request_delete_node(9, 3, timeout_s=25.0)
        m2 = lnh.get_cluster_membership(9)
        assert 3 not in m2.addresses
    finally:
        for nh in nhs.values():
            nh.stop()


def test_restart_replay(tmp_path, engine_kind):
    reg = _Registry()
    d = str(tmp_path)
    nh = mk_nodehost("a:1", reg, nodehost_dir=d, engine_kind=engine_kind)
    try:
        nh.start_cluster({1: "a:1"}, False, KVSM, group_config(3, 1))
        assert wait_for(lambda: nh.get_leader_id(3)[1])
        s = nh.get_noop_session(3)
        for i in range(5):
            nh.sync_propose(s, b"k%d=%d" % (i, i))
    finally:
        nh.stop()
    # restart: log replay restores the SM
    reg2 = _Registry()
    nh2 = mk_nodehost("a:1", reg2, nodehost_dir=d, engine_kind=engine_kind)
    try:
        nh2.start_cluster({1: "a:1"}, False, KVSM, group_config(3, 1))
        assert wait_for(lambda: nh2.get_leader_id(3)[1], timeout=45)
        assert wait_for(
            lambda: nh2.stale_read(3, "k4") == "4", timeout=30
        )
    finally:
        nh2.stop()


def test_leader_transfer(engine_kind):
    reg = _Registry()
    members = {1: "a:1", 2: "b:2", 3: "c:3"}
    nhs = {nid: mk_nodehost(addr, reg, engine_kind=engine_kind) for nid, addr in members.items()}
    try:
        for nid, nh in nhs.items():
            nh.start_cluster(members, False, KVSM, group_config(11, nid))
        def current_leader():
            for nid, nh in nhs.items():
                lid, ok = nh.get_leader_id(11)
                if ok and lid == nid:
                    return nid
            return None

        assert wait_for(lambda: current_leader() is not None, timeout=45)
        old = current_leader()
        target = next(n for n in (1, 2, 3) if n != old)
        nhs[old].request_leader_transfer(11, target)
        assert wait_for(lambda: current_leader() == target, timeout=45)
    finally:
        for nh in nhs.values():
            nh.stop()


def test_ping_pong_rtt_and_nodehost_info(tmp_path):
    """RTT probing (cf. nodehost.go:2069-2088) + aggregate introspection
    (cf. nodehost.go:1289-1302 GetNodeHostInfo with log info)."""
    import time as _t
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

    reg = _Registry()
    members = {1: "rtt:1", 2: "rtt:2", 3: "rtt:3"}
    hosts = {}
    for nid, addr in members.items():
        hosts[nid] = NodeHost(NodeHostConfig(
            deployment_id=77, rtt_millisecond=5, raft_address=addr,
            nodehost_dir=str(tmp_path / f"nh{nid}"),
            raft_rpc_factory=lambda l, r=reg: loopback_factory(l, r),
            engine=EngineConfig(kind="vector", max_groups=4, max_peers=4,
                                log_window=64),
        ))
    try:
        for nid in members:
            hosts[nid].start_cluster(
                dict(members), False, lambda c, n: KVSM(c, n),
                Config(cluster_id=1, node_id=nid, election_rtt=20,
                       heartbeat_rtt=2))
        deadline = _t.time() + 60
        while _t.time() < deadline:
            if any(hosts[n].get_leader_id(1)[1] for n in members):
                break
            _t.sleep(0.02)
        sent = hosts[1].ping_peers()
        assert sent == 2
        deadline = _t.time() + 10
        while _t.time() < deadline and len(hosts[1].get_rtt_samples()) < 2:
            _t.sleep(0.05)
        samples = hosts[1].get_rtt_samples()
        assert set(samples) == {(1, 2), (1, 3)}, samples
        for vals in samples.values():
            assert len(vals) >= 1
            assert 0 <= vals[0] < 10_000_000  # microseconds, sane bound
        # aggregate info: cluster list + logdb inventory, iterable for
        # backwards compatibility
        info = hosts[1].get_nodehost_info()
        assert info.raft_address == "rtt:1"
        cis = list(info)
        assert len(cis) == 1 and cis[0].cluster_id == 1
        assert any(
            ni.cluster_id == 1 and ni.node_id == 1 for ni in info.log_info
        )
        lean = hosts[1].get_nodehost_info(skip_log_info=True)
        assert lean.log_info == []
    finally:
        for nh in hosts.values():
            nh.stop()
