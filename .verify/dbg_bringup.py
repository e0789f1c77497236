import os, sys, time
sys.path.insert(0, "/root/repo")
import tempfile, shutil
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import loopback_factory, _Registry
reg = _Registry()

G = int(sys.argv[1]) if len(sys.argv) > 1 else 4096


class _SM(IStateMachine):
    """Counts the entries it has applied."""

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


wd = tempfile.mkdtemp(prefix="dbtpu-bu-")
t0 = time.monotonic()
nh = NodeHost(NodeHostConfig(
    raft_address="bu:1", rtt_millisecond=10,
    nodehost_dir=wd,
    raft_rpc_factory=lambda a: loopback_factory(a, reg),
    engine=EngineConfig(kind="vector", max_groups=G, max_peers=4,
        log_window=64, inbox_depth=4, max_entries_per_msg=16)))
t1 = time.monotonic()
nh.start_clusters([
    ({1: "bu:1"}, False, lambda cid, n: _SM(),
     Config(node_id=1, cluster_id=c, election_rtt=20, heartbeat_rtt=2))
    for c in range(1, G+1)
])
t2 = time.monotonic()
leaders = {}
while len(leaders) < G and time.monotonic()-t2 < 300:
    snap = nh.engine.leader_snapshot()
    leaders = {c: l for c, (l, _t) in snap.items() if l}
    time.sleep(0.05)
t3 = time.monotonic()
print(f"G={G}: nodehost_init={t1-t0:.2f}s start_clusters={t2-t1:.2f}s elections={t3-t2:.2f}s total={t3-t0:.2f}s")
nh.stop()
shutil.rmtree(wd, ignore_errors=True)
