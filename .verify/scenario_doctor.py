"""PR 19 known-good scenario: telemetry history ring + raft-doctor e2e.

Drives the REAL surface: a 3-host vector-engine loopback cluster with a
live HistorySampler per host (NodeHost.start_history), healthy traffic
diagnosed as healthy_idle, a full partition diagnosed as
no_quorum_partition, then the crash-persistent rings read back and fed
through the doctor CLI and tools.top --history as an operator would.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, "/root/repo")
from dragonboat_tpu._jaxenv import pin_cpu

pin_cpu()  # these scenarios are cpu drives by design

from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.profile import read_history
from dragonboat_tpu.requests import ErrClusterNotReady, ErrTimeout
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.tools.doctor import diagnose
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory


class SM(IStateMachine):
    def __init__(s, c, n): s.n = 0
    def update(s, data): s.n += 1; return Result(value=s.n)
    def lookup(s, q): return s.n
    def save_snapshot(s, w, fc, done): w.write(s.n.to_bytes(8, 'little'))
    def recover_from_snapshot(s, r, fc, done):
        s.n = int.from_bytes(r.read(8), 'little')
    def close(s): pass


def wait_leader(hosts, cid, timeout=60):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        for nid, nh in hosts.items():
            lid, ok = nh.get_leader_id(cid)
            if ok:
                return lid
        time.sleep(0.05)
    raise SystemExit("no leader elected")


tmp = tempfile.mkdtemp(prefix="verify-doctor-")
reg = _Registry()
members = {1: "v:1", 2: "v:2", 3: "v:3"}
hosts = {
    n: NodeHost(NodeHostConfig(
        deployment_id=5, rtt_millisecond=5, raft_address=a,
        nodehost_dir=os.path.join(tmp, f"h{n}"),
        raft_rpc_factory=lambda l, r=reg: loopback_factory(l, r),
        engine=EngineConfig(kind="vector", max_groups=8, max_peers=4,
                            log_window=64),
    ))
    for n, a in members.items()
}
for n in members:
    hosts[n].start_cluster(dict(members), False, lambda c, i: SM(c, i),
        Config(cluster_id=1, node_id=n, election_rtt=10, heartbeat_rtt=2))
for nh in hosts.values():
    nh.start_history(interval_s=0.1)
lid = wait_leader(hosts, 1)


def propose_retry(cmd, tries=4):
    global lid
    for _ in range(tries):
        try:
            return hosts[lid].sync_propose(
                hosts[lid].get_noop_session(1), cmd, 10)
        except (ErrTimeout, ErrClusterNotReady):
            time.sleep(0.3)
            lid = wait_leader(hosts, 1)
    raise SystemExit("propose kept timing out")


for i in range(8):
    propose_retry(b"cmd%d" % i)

# ---- healthy fleet diagnoses idle ----
vs = diagnose(hosts, window_s=0.5, interval_s=0.1, flight=[])
kinds = [v.kind for v in vs]
assert kinds == ["healthy_idle"], kinds
print("live diagnose healthy: OK", kinds)

# ---- full partition diagnoses no_quorum ----
for nh in hosts.values():
    nh.set_partitioned(True)
time.sleep(0.8)
vs = diagnose(hosts, window_s=1.2, interval_s=0.3, flight=[])
kinds = [v.kind for v in vs]
assert "no_quorum_partition" in kinds, kinds
assert "healthy_idle" not in kinds, kinds
print("live diagnose partition: OK", kinds)
for nh in hosts.values():
    nh.set_partitioned(False)
wait_leader(hosts, 1)

# ---- seal the rings, read them back, drive the CLIs ----
rings = {}
for n, nh in hosts.items():
    ring = os.path.join(nh._dir, "history.ring")
    nh.stop_history()
    meta, samples = read_history(ring)
    assert samples and all(
        s["event"] == "history_sample" for s in samples), ring
    assert samples[-1]["host"] == members[n]
    rings[n] = ring
print("history rings: OK",
      {n: len(read_history(r)[1]) for n, r in rings.items()})

env = dict(os.environ, PYTHONPATH="/root/repo", JAX_PLATFORMS="cpu")
proc = subprocess.run(
    [sys.executable, "-m", "dragonboat_tpu.tools.doctor", rings[1],
     "--json"],
    capture_output=True, text=True, env=env, cwd="/root/repo", timeout=120)
assert proc.returncode == 0, proc.stderr
rep = json.loads(proc.stdout)
assert rep["schema"] == 1 and rep["verdicts"], rep
# the whole run is in the ring: the partition window dominates
assert any(v["kind"] == "no_quorum_partition" for v in rep["verdicts"])
print("doctor CLI on ring: OK",
      [v["kind"] for v in rep["verdicts"]])

proc = subprocess.run(
    [sys.executable, "-m", "dragonboat_tpu.tools.top", "--history",
     rings[1]],
    capture_output=True, text=True, env=env, cwd="/root/repo", timeout=120)
assert proc.returncode == 0, proc.stderr
assert "doctor:" in proc.stdout and "raft-top" in proc.stdout
print("top --history: OK",
      [l for l in proc.stdout.splitlines() if l.startswith("doctor:")][0])

for nh in hosts.values():
    nh.stop()
print("VERIFY DOCTOR SCENARIO: ALL OK")
