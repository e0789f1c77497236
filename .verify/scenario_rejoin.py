"""PR 10 verify drive: the rejoin-without-disruption plane through the
REAL NodeHost surface — pre-vote leader stability across a partition
heal, a witness joined via the membership API holding zero payload while
counting toward quorum, and a crash/rejoin through the (resumable)
snapshot-install path."""
import os, sys, time, tempfile

sys.path.insert(0, "/root/repo")
from dragonboat_tpu._jaxenv import pin_cpu

pin_cpu()  # these scenarios are cpu drives by design

from dragonboat_tpu.config import Config, NodeHostConfig, EngineConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.ops.state import ROLE
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

CID = 1


class SM(IStateMachine):
    def __init__(s, c, n):
        s.d = {}

    def update(s, data):
        k, v = data.decode().split("=", 1)
        s.d[k] = v
        return Result(value=len(s.d))

    def lookup(s, q):
        return s.d.get(q)

    def save_snapshot(s, w, fc, done):
        import json

        w.write(json.dumps(s.d).encode())

    def recover_from_snapshot(s, r, fc, done):
        import json

        s.d = json.loads(r.read().decode())


def mk(nid, reg, run_dir):
    return NodeHost(
        NodeHostConfig(
            deployment_id=8,
            rtt_millisecond=5,
            nodehost_dir=os.path.join(run_dir, f"h{nid}"),
            raft_address=f"v{nid}:1",
            raft_rpc_factory=lambda l, reg=reg: loopback_factory(l, reg),
            engine=EngineConfig(
                kind="vector", max_groups=32, max_peers=4, log_window=64
            ),
        )
    )


def cfg(nid, **kw):
    base = dict(
        cluster_id=CID, node_id=nid, election_rtt=20, heartbeat_rtt=4,
        snapshot_entries=25, compaction_overhead=5, pre_vote=True,
        check_quorum=True,
    )
    base.update(kw)
    return Config(**base)


def leader_of(hosts):
    for n, nh in hosts.items():
        try:
            lid, ok = nh.get_leader_id(CID)
        except Exception:
            continue
        if ok and lid == n and not nh.is_partitioned():
            return n
    return None


def wait(pred, timeout, what):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        v = pred()
        if v:
            return v
        time.sleep(0.05)
    raise SystemExit(f"timeout waiting for {what}")


def retry_propose(nh, s, cmd, tries=8):
    for _ in range(tries):
        try:
            nh.sync_propose(s, cmd, timeout_s=4.0)
            return
        except Exception:
            time.sleep(0.2)
    raise SystemExit("propose kept failing")


tmp = tempfile.mkdtemp(prefix="verify-rejoin-")
reg = _Registry()
members = {n: f"v{n}:1" for n in (1, 2, 3)}
hosts = {n: mk(n, reg, tmp) for n in (1, 2, 3)}
for n in (1, 2, 3):
    hosts[n].start_cluster(members, False, lambda c, n_: SM(c, n_), cfg(n))
leader = wait(lambda: leader_of(hosts), 60, "leader")
term0 = hosts[leader].engine.lane_stats()[CID]["term"]
s = hosts[leader].get_noop_session(CID)

# ---- (1) pre-vote: partition/heal a follower, leader + term stable ----
victim = 2 if leader != 2 else 3
hosts[victim].set_partitioned(True)
for i in range(10):
    retry_propose(hosts[leader], s, f"p{i}=x".encode())
time.sleep(1.0)  # several election timeouts for the isolated victim
hosts[victim].set_partitioned(False)
time.sleep(0.6)
assert leader_of(hosts) == leader, "leader disturbed by partition heal"
assert hosts[leader].engine.lane_stats()[CID]["term"] == term0, "term bumped"
print("prevote heal: OK (leader", leader, "term", term0, ")")

# ---- (2) witness join via membership API: zero payload, in quorum ----
reg4 = hosts  # same registry
wnh = mk(4, reg, tmp)
hosts_w = dict(hosts)
hosts_w[4] = wnh
hosts[leader].sync_request_add_witness(CID, 4, "v4:1", timeout_s=10.0)
wnh.start_cluster({}, True, lambda c, n_: SM(c, n_),
                  cfg(4, is_witness=True, snapshot_entries=0,
                      compaction_overhead=0))
for i in range(20):
    retry_propose(hosts[leader], s, f"w{i}=payload-{i}".encode())
st = wait(
    lambda: (lambda x: x if x and x["term"] > 0 else None)(
        wnh.engine.lane_stats().get(CID)
    ),
    30, "witness lane",
)
assert st["role"] == ROLE.WITNESS, st
assert st["payload_bytes"] == 0, st
print("witness lane: OK (role WITNESS, payload_bytes 0)")
hosts[leader].sync_request_delete_node(CID, 4, timeout_s=10.0)
wnh.stop()

# ---- (3) crash + snapshot-install rejoin ----
victim = 3 if leader != 3 else 2
hosts[victim].crash_cluster(CID)
for i in range(40):
    retry_propose(hosts[leader], s, f"c{i}=y{i}".encode())
hosts[leader].sync_request_snapshot(CID, timeout_s=10.0)
hosts[victim].restart_cluster(CID)
want = hosts[leader].get_sm_hash(CID)
wait(
    lambda: hosts[victim].get_sm_hash(CID) == want
    if hosts[victim].has_node(CID)
    else False,
    60, "rejoiner convergence",
)
print("crash + install rejoin: OK (hash converged)")

for nh in hosts.values():
    nh.stop()
print("VERIFY REJOIN PLANE: ALL OK")
