"""helloworld: a 3-replica ordered-KV Raft group end to end.

Counterpart of the reference's canonical helloworld example (the
dragonboat-example repo's ondisk/helloworld walkthrough): start three
NodeHosts, let them elect a leader, make linearizable proposals and reads,
move leadership, kill a replica and watch the survivors keep serving, then
restart it and watch it catch up from its durable state.

Run (no TPU needed — uses the CPU backend):

    JAX_PLATFORMS=cpu PYTHONPATH=. python examples/helloworld.py

Three NodeHosts live in this one process and talk over real TCP on
localhost ports 26101-26103; each persists under ./helloworld-data/.
"""
import os
import shutil
import sys
import time

from dragonboat_tpu.config import Config, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result

CLUSTER_ID = 128
ADDRS = {1: "127.0.0.1:26101", 2: "127.0.0.1:26102", 3: "127.0.0.1:26103"}
DATA = "helloworld-data"


class KVStore(IStateMachine):
    """The replicated state machine: an ordered map of str -> str.

    Commands are "key=value" bytes; lookups are the key. Snapshots write
    the whole table; recover rebuilds it. The framework guarantees update
    is applied in log order on every replica."""

    def __init__(self, cluster_id: int, node_id: int):
        self.table = {}

    def update(self, data: bytes) -> Result:
        key, value = data.decode().split("=", 1)
        self.table[key] = value
        return Result(value=len(self.table))

    def lookup(self, query):
        q = query.decode() if isinstance(query, bytes) else query
        v = self.table.get(q)
        return v.encode() if v is not None else None

    def save_snapshot(self, w, files, done) -> None:
        import json

        w.write(json.dumps(self.table).encode())

    def recover_from_snapshot(self, r, files, done) -> None:
        import json

        self.table = json.loads(r.read().decode())

    def close(self) -> None:
        pass


def make_host(node_id: int, restart: bool = False) -> NodeHost:
    nh = NodeHost(NodeHostConfig(
        deployment_id=2026,
        rtt_millisecond=10,
        raft_address=ADDRS[node_id],
        nodehost_dir=os.path.join(DATA, f"node{node_id}"),
    ))
    nh.start_cluster(
        {} if restart else dict(ADDRS),  # {} = restart from durable state
        False,
        KVStore,
        Config(cluster_id=CLUSTER_ID, node_id=node_id,
               election_rtt=20, heartbeat_rtt=2,
               snapshot_entries=100, compaction_overhead=20),
    )
    return nh


def propose_retry(hosts, leader, cmd: bytes, attempts=5):
    """Propose with leader re-resolution: real Raft clients retry dropped
    or timed-out proposals against the current leader — a proposal handed
    to a just-deposed leader is rejected, not silently re-routed."""
    from dragonboat_tpu.requests import RequestError

    last = None
    for _ in range(attempts):
        try:
            s = hosts[leader].get_noop_session(CLUSTER_ID)
            return hosts[leader].sync_propose(s, cmd, timeout_s=10.0), leader
        except RequestError as e:
            last = e
            time.sleep(0.2)
            leader = wait_leader(hosts)
    raise last


def wait_leader(hosts, timeout_s=60.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        for nid, nh in hosts.items():
            if nh is None:
                continue
            leader, ok = nh.get_leader_id(CLUSTER_ID)
            if ok and hosts.get(leader) is not None:
                return leader
        time.sleep(0.05)
    raise SystemExit("no leader elected")


def main() -> None:
    shutil.rmtree(DATA, ignore_errors=True)
    hosts = {nid: make_host(nid) for nid in ADDRS}
    try:
        leader = wait_leader(hosts)
        print(f"leader elected: node {leader}")

        # --- linearizable writes (retrying across leadership churn, as
        # any real Raft client does)
        for i in range(10):
            r, leader = propose_retry(
                hosts, leader, f"greeting{i}=hello world {i}".encode())
            print(f"proposed greeting{i}; table size on apply: {r.value}")

        # --- linearizable read from a FOLLOWER host (ReadIndex)
        follower = next(n for n in hosts if n != leader)
        v = hosts[follower].sync_read(CLUSTER_ID, b"greeting7",
                                      timeout_s=10.0)
        print(f"linearizable read via follower node {follower}: {v}")

        # --- move leadership
        hosts[leader].request_leader_transfer(CLUSTER_ID, follower)
        deadline = time.time() + 30
        while time.time() < deadline:
            lid, ok = hosts[follower].get_leader_id(CLUSTER_ID)
            if ok and lid == follower:
                break
            time.sleep(0.05)
        else:
            raise SystemExit("leader transfer did not complete")
        print(f"leadership transferred to node {follower}")

        # --- kill one replica: quorum of 2 keeps the group available
        victim = next(n for n in hosts if n != follower)
        print(f"stopping node {victim} ...")
        hosts[victim].stop()
        hosts[victim] = None
        leader = wait_leader(hosts)
        _, leader = propose_retry(hosts, leader,
                                  b"during_outage=still here")
        print("proposed during the outage: ok")

        # --- restart it from durable state; it replays and catches up
        print(f"restarting node {victim} ...")
        hosts[victim] = make_host(victim, restart=True)
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if hosts[victim].stale_read(
                        CLUSTER_ID, b"during_outage") == b"still here":
                    break
            except Exception:
                pass
            time.sleep(0.1)
        else:
            raise SystemExit(f"node {victim} never caught up after restart")
        print(f"node {victim} caught up after restart")
        print("HELLOWORLD PASS")
    finally:
        for nh in hosts.values():
            if nh is not None:
                nh.stop()
        shutil.rmtree(DATA, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
