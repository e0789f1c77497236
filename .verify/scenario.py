import os, sys, time, tempfile, shutil, socket
sys.path.insert(0, "/root/repo")
from dragonboat_tpu._jaxenv import pin_cpu

pin_cpu()  # these scenarios are cpu drives by design

from dragonboat_tpu.config import Config, NodeHostConfig, EngineConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

class SM(IStateMachine):
    def __init__(s, c, n): s.n = 0
    def update(s, data): s.n += 1; return Result(value=s.n)
    def lookup(s, q): return s.n
    def save_snapshot(s, w, fc, done): w.write(s.n.to_bytes(8,'little'))
    def recover_from_snapshot(s, r, fc, done): s.n = int.from_bytes(r.read(8),'little')
    def close(s): pass

def wait_leader(hosts, cid, timeout=60):
    t0 = time.monotonic()
    while time.monotonic()-t0 < timeout:
        for nid, nh in hosts.items():
            lid, ok = nh.get_leader_id(cid)
            if ok: return lid
        time.sleep(0.05)
    raise SystemExit("no leader elected")

# ---- (1) 3-host loopback ----
reg = _Registry()
members = {1:"h:1", 2:"h:2", 3:"h:3"}
hosts = {n: NodeHost(NodeHostConfig(deployment_id=5, rtt_millisecond=5,
        raft_address=a, raft_rpc_factory=lambda l, r=reg: loopback_factory(l, r)))
        for n, a in members.items()}
for n in members:
    hosts[n].start_cluster(dict(members), False, lambda c,i: SM(c,i),
        Config(cluster_id=1, node_id=n, election_rtt=10, heartbeat_rtt=2))
lid = wait_leader(hosts, 1)
def propose_retry(hs, cid, cmd, tries=4):
    # a proposal can be legitimately lost to election churn (appended at a
    # term that lost); real clients retry on timeout
    global lid
    from dragonboat_tpu.requests import ErrTimeout, ErrClusterNotReady
    for _ in range(tries):
        try:
            return hs[lid].sync_propose(hs[lid].get_noop_session(cid), cmd, 10)
        except (ErrTimeout, ErrClusterNotReady):
            time.sleep(0.3)
            lid = wait_leader(hs, cid)
    raise SystemExit("propose kept timing out")
r = propose_retry(hosts, 1, b"cmd")
assert r.value >= 1, r.value
assert hosts[lid].sync_read(1, None) >= 1
fol = next(n for n in members if n != lid)
assert hosts[fol].sync_read(1, None, timeout_s=10) >= 1
# leader transfer
hosts[lid].request_leader_transfer(1, fol)
t0 = time.monotonic()
while time.monotonic()-t0 < 30:
    l2, ok = hosts[fol].get_leader_id(1)
    if ok and l2 == fol: break
    time.sleep(0.05)
assert hosts[fol].get_leader_id(1)[0] == fol, "transfer failed"
print("loopback 3-host: OK (leader", lid, "-> transfer", fol, ")")
for nh in hosts.values(): nh.stop()

# ---- (2) 2-host TCP ----
def free_port():
    s = socket.socket(); s.bind(("127.0.0.1", 0)); p = s.getsockname()[1]; s.close(); return p
a1 = f"127.0.0.1:{free_port()}"; a2 = f"127.0.0.1:{free_port()}"
tm = {1: a1, 2: a2}
th = {n: NodeHost(NodeHostConfig(deployment_id=7, rtt_millisecond=5, raft_address=a))
      for n, a in tm.items()}
for n in tm:
    th[n].start_cluster(dict(tm), False, lambda c,i: SM(c,i),
        Config(cluster_id=9, node_id=n, election_rtt=10, heartbeat_rtt=2))
lid = wait_leader(th, 9)
from dragonboat_tpu.requests import ErrTimeout, ErrClusterNotReady
r = None
for _ in range(4):
    try:
        r = th[lid].sync_propose(th[lid].get_noop_session(9), b"x", 10)
        break
    except (ErrTimeout, ErrClusterNotReady):
        time.sleep(0.3)
        lid = wait_leader(th, 9)
if r is None:
    raise SystemExit("tcp propose kept failing (timeout/not-ready)")
assert r.value >= 1
print("tcp 2-host: OK")
for nh in th.values(): nh.stop()

# ---- (3) durable restart ----
wd = tempfile.mkdtemp(prefix="dbtpu-verify-")
reg2 = _Registry()
def mk(reg2):
    return NodeHost(NodeHostConfig(rtt_millisecond=5, raft_address="d:1",
        nodehost_dir=wd, raft_rpc_factory=lambda l: loopback_factory(l, reg2)))
nh = mk(reg2)
nh.start_cluster({1:"d:1"}, False, lambda c,i: SM(c,i),
    Config(cluster_id=2, node_id=1, election_rtt=10, heartbeat_rtt=2))
wait_leader({1: nh}, 2)
sess = nh.get_noop_session(2)
for i in range(10):
    nh.sync_propose(sess, b"p%d" % i, 30)
nh.stop()
reg3 = _Registry()
nh = mk(reg3)
nh.start_cluster({1:"d:1"}, False, lambda c,i: SM(c,i),
    Config(cluster_id=2, node_id=1, election_rtt=10, heartbeat_rtt=2))
t0 = time.monotonic()
while nh.stale_read(2, None) < 10 and time.monotonic()-t0 < 30:
    time.sleep(0.05)
assert nh.stale_read(2, None) >= 10, nh.stale_read(2, None)
print("durable restart: OK")
nh.stop()
shutil.rmtree(wd, ignore_errors=True)
print("VERIFY SCENARIO: ALL OK")
