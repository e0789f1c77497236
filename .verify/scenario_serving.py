import sys, time
sys.path.insert(0, "/root/repo")
from dragonboat_tpu._jaxenv import pin_cpu

pin_cpu()  # these scenarios are cpu drives by design

from dragonboat_tpu.config import Config, NodeHostConfig, EngineConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
from dragonboat_tpu.serving import (
    AdmissionConfig, TenantSpec, ErrOverloaded, call_with_retries,
    run_overload_storm,
)

class SM(IStateMachine):
    def __init__(s, c, n): s.d = {}
    def update(s, data):
        k, v = data.decode().split("=", 1); s.d[k] = v
        return Result(value=len(s.d))
    def lookup(s, q): return s.d.get(q)
    def save_snapshot(s, w, fc, done):
        import json; w.write(json.dumps(s.d).encode())
    def recover_from_snapshot(s, r, fc, done):
        import json; s.d = json.loads(r.read().decode())

def wait(pred, timeout=60):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred(): return True
        time.sleep(0.05)
    return False

reg = _Registry()
members = {1: "h1:1", 2: "h2:1", 3: "h3:1"}
hosts = {}
for nid, addr in members.items():
    hosts[nid] = NodeHost(NodeHostConfig(
        deployment_id=9, rtt_millisecond=5, raft_address=addr,
        raft_rpc_factory=lambda l, r=reg: loopback_factory(l, r),
        engine=EngineConfig(kind="scalar"),
    ))
try:
    for nid, nh in hosts.items():
        nh.start_cluster(members, False, SM, Config(
            cluster_id=1, node_id=nid, election_rtt=10, heartbeat_rtt=2,
            quiesce=True,
        ))
    assert wait(lambda: any(nh.get_leader_id(1)[1] for nh in hosts.values()))
    leader = next(n for n, nh in hosts.items()
                  if nh.get_leader_id(1) == (n, True))
    nh = hosts[leader]

    # multi-tenant front on the leader host, tight bulk caps
    front = nh.serving_front(AdmissionConfig(
        default=TenantSpec(rate=200.0, burst=20.0, weight=1.0),
        tenants={2: TenantSpec(rate=400.0, burst=40.0, weight=2.0)},
    ))
    # 1) admitted bulk for two tenants completes through the real 3-node
    #    replication path; urgent reads interleave, never queued
    done = sheds = 0
    hints = []
    tickets = []
    for i in range(120):
        tid = 1 + (i % 2)
        try:
            tickets.append(
                front.propose(tid, 1, f"t{tid}k{i}=v{i}".encode(), 10.0)
            )
        except ErrOverloaded as e:
            sheds += 1; hints.append(e.retry_after_s)
    done = sum(1 for t in tickets if t.wait().completed)
    assert done > 0, "no bulk completed"
    assert sheds > 0, "tight caps never shed"
    assert all(h > 0 for h in hints), "shed without a retry hint"
    rs = front.read(1, 1, 5.0)
    assert rs.wait(5.0).completed, "urgent read failed"
    print(f"front multi-tenant: OK (done={done} sheds={sheds})")

    # 2) client retry helper rides the hints to completion under deadline
    val = call_with_retries(
        lambda remaining: front.sync_propose(1, 1, b"retry=me", remaining),
        deadline_s=10.0,
    )
    assert val is not None
    print("retry helper under deadline: OK")

    # 3) quiesce wake-on-admit: a single-replica group on the leader
    #    host idles into quiesce; the FIRST admit wakes it and the op
    #    commits (multi-replica scalar groups keep exchanging heartbeats
    #    and do not quiesce -- pre-existing seed behavior)
    nh.start_cluster({leader: members[leader]}, False, SM, Config(
        cluster_id=2, node_id=leader, election_rtt=10, heartbeat_rtt=2,
        quiesce=True,
    ))
    assert wait(lambda: nh.get_leader_id(2)[1])
    qnode = nh._get_node(2)
    assert wait(lambda: qnode.quiesce_mgr.quiesced(), timeout=40), \
        "idle group never quiesced"
    t = front.propose(2, 2, b"wake=up", 15.0)
    assert t.wait().completed, "post-quiesce proposal failed"
    assert front.admission.counters()[2]["wakes"] >= 1
    assert wait(lambda: qnode.quiesce_mgr.quiesced(), timeout=40), \
        "group never re-quiesced after the burst"
    print("quiesce wake-on-admit + re-quiesce: OK")

    # 4) follower-host read of replicated data (linearizable via leader's
    #    applied state reaching followers)
    fnh = hosts[1 if leader != 1 else 2]
    assert wait(lambda: fnh.stale_read(1, "t1k0") == "v0", timeout=20)
    print("replicated to follower: OK")

    # 5) overload storm verdict on the live leader
    rep = run_overload_storm(nh, 1, seed=0xCAFE, storm_s=0.6,
                             baseline_ops=200, capacity_rate=600.0)
    assert rep.ok, rep.verdicts
    print(f"overload storm verdict: OK {rep.verdicts}")

    # 6) exposition carries the per-tenant ledger
    import io
    nh._export_health_gauges()
    w = io.StringIO(); nh.write_health_metrics(w)
    text = w.getvalue()
    assert 'serving_admitted_total{klass="bulk",tenant="1"}' in text
    assert "serving_saturation" in text
    print("exposition: OK")
finally:
    for nh in hosts.values():
        try: nh.stop()
        except Exception: pass
print("VERIFY SERVING: ALL OK")
