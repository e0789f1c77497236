"""Headline benchmark: END-TO-END framework proposal throughput.

Regime from BASELINE.md: the reference's peak is 9M proposals/s on 3x22-core
servers with 48 Raft groups, 3 replicas per group, fsync honored
(reference README.md:46). This bench measures the same THING the reference
measures — proposals committed through the full framework stack:

    propose -> leader engine packs -> device step kernel -> Replicate over
    the transport (codec-encoded loopback) -> follower engines ack ->
    quorum commit -> ONE batched fsynced logdb write -> SM apply ->
    completion notify

with 3 NodeHosts in one process, G groups x 3 replicas, 16B payloads and
disk-backed WAL persistence. The bare-kernel number (what the device alone
sustains, single-replica lanes; the round-1/2 headline) is reported as a
secondary metric in the same JSON line.

Prints ONE JSON line. Run: python bench.py
(CPU works but is slow — pass --groups/--duration to shrink for smoke tests.)
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import tempfile
import time

from dragonboat_tpu._jaxenv import enable_compile_cache, pin_cpu

BASELINE_PROPOSALS_PER_SEC = 9_000_000  # reference README.md:46 (3-node peak)


def _host_stamp() -> dict:
    """Bench-honesty box fingerprint: hostname/cpu-count identity plus a
    timed fixed numpy spin (a human-readable load indicator for the
    trajectory). tools.perfdiff refuses to diff records whose ids differ
    — re-benching one commit on a second box of this repo's own
    trajectory showed a 1.65x throughput gap at identical code/shape."""
    import platform as _platform
    import numpy as _np

    t0 = time.perf_counter()
    a = _np.random.default_rng(0).random((256, 256))
    for _ in range(20):
        a = (a @ a) % 1.0
    calib = time.perf_counter() - t0
    return {
        "id": f"{_platform.node() or 'unknown'}/{os.cpu_count()}cpu",
        "calib_s": round(calib, 4),
    }


# results accumulate here as each ladder config finishes, so the watchdog
# can emit everything measured so far instead of an empty error record
RECORD: dict = {
    "metric": "e2e_proposals_per_sec",
    "value": 0.0,
    "unit": "proposals/s",
    "vs_baseline": 0.0,
}


def _arm_watchdog(seconds: float):
    """A run can wedge on a deadlock. ALWAYS armed: the wedged process
    prints one parseable JSON line carrying whatever partial ladder
    results landed before the hang, then exits 3."""
    import threading

    def fire() -> None:  # pragma: no cover - only on wedged runs
        err = f"watchdog: no result within {seconds:.0f}s"
        try:
            snap = json.loads(json.dumps(RECORD, default=str))  # best-effort
        except Exception:
            # RECORD mutated mid-dump: still emit SOMETHING parseable
            snap = {"metric": RECORD["metric"], "value": 0.0}
        snap["error"] = err
        try:
            print(json.dumps(snap), flush=True)
        finally:
            os._exit(3)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


import jax
import jax.numpy as jnp

from dragonboat_tpu.ops.kernel import step_batch, _term_at
from dragonboat_tpu.ops.state import (
    MSG,
    KernelConfig,
    RaftTensors,
    configure_groups_uniform,
    init_state,
    make_empty_inbox,
)


# ---------------------------------------------------------------------------
# end-to-end framework benchmark
# ---------------------------------------------------------------------------


def _bench_sm_class():
    from dragonboat_tpu.statemachine import (
        IConcurrentStateMachine,
        Result,
    )

    class _BenchSM(IConcurrentStateMachine):
        """Minimal in-memory counter SM (the reference benches an in-mem
        KV, internal/tests/kvtest.go). Concurrent flavour: update() takes
        the whole committed batch in ONE call — the apply-side shape a
        throughput-focused SM should use on this framework."""

        def __init__(self, cluster_id, node_id):
            self.n = 0

        def update(self, entries):
            n = self.n
            for e in entries:
                n += 1
                e.result = Result(value=n)
            self.n = n
            return entries

        def lookup(self, q):
            return self.n

        def prepare_snapshot(self):
            return self.n

        def save_snapshot(self, ctx, w, fc, done):
            w.write(int(ctx).to_bytes(8, "little"))

        def recover_from_snapshot(self, r, fc, done):
            self.n = int.from_bytes(r.read(8), "little")

        def close(self):
            pass

    return _BenchSM


def bench_e2e(
    groups: int,
    duration_s: float,
    payload: int,
    workdir: str,
    shared: bool = True,
    wave: int = 128,
    inbox_depth: int = 4,
    entries_per_msg: int = 64,
    log_window: int = 256,
    replicas: int = 3,
    read_ratio: int = 0,
    read_mode: str = "readindex",
    drop_rate: float = 0.0,
    churn: bool = False,
    steps_per_sync: int = 1,
    through_front: bool = False,
    tenants: int = 0,
    shard_over_mesh: bool = False,
):
    """N NodeHosts, G groups x N replicas, quorum + fsync + apply.

    shared=True co-hosts all NodeHosts on ONE engine core (the TPU-native
    deployment shape: the whole replica fleet advances in one kernel step;
    messages between replicas ride the shared inbox, not the wire).
    shared=False keeps independent engines talking over the codec-encoded
    loopback transport.

    read_ratio=R submits R linearizable ReadIndex requests per write
    (BASELINE config 3's 9:1 mix). read_mode='lease' turns on
    Config.lease_read for every group: the SAME read API, but a leader
    holding a live quorum lease serves the read locally and an expired/
    suspect lease degrades to the ReadIndex quorum round (config 8's
    read_heavy A/B; the stamp makes tools.perfdiff refuse cross-mode
    diffs). drop_rate randomly drops that fraction
    of replication traffic (config 4's log-matching divergence stress).
    churn interleaves snapshot requests and membership changes during the
    measurement (config 5). steps_per_sync=K runs the device-resident
    multi-step engine: K protocol steps per kernel launch with co-hosted
    traffic routed on device (config 6 is config 2 at K=8).
    through_front drives the measurement THROUGH SessionManager/
    ServingFront (config 7): the headline becomes ADMITTED throughput —
    per-tenant admission + weighted-fair fan-in + at-most-once session
    traffic — with per-tenant latency percentiles and the dedup/
    migration counters in the JSON."""
    import random as _random

    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import Result  # noqa: F401 (SM dep)
    from dragonboat_tpu.transport.loopback import loopback_factory, _Registry
    from dragonboat_tpu.types import MessageType

    sm_cls = _bench_sm_class()
    reg = _Registry()
    members = {n: f"bench:{n}" for n in range(1, replicas + 1)}
    hosts = {}
    try:
        return _bench_e2e_body(
            hosts, members, reg, sm_cls, groups, duration_s, payload,
            workdir, shared, wave, inbox_depth, entries_per_msg, log_window,
            replicas, read_ratio, read_mode, drop_rate, churn,
            steps_per_sync, through_front, tenants, shard_over_mesh,
        )
    finally:
        # an exception must not leak NodeHosts: the share_scope='bench'
        # core would survive (refcount never reaching zero) and poison
        # every later ladder config with an engine-shape mismatch
        for nh in hosts.values():
            try:
                nh.stop()
            except Exception:
                pass


def _bench_e2e_body(
    hosts, members, reg, sm_cls, groups, duration_s, payload, workdir,
    shared, wave, inbox_depth, entries_per_msg, log_window, replicas,
    read_ratio, read_mode, drop_rate, churn, steps_per_sync=1,
    through_front=False, tenants=0, shard_over_mesh=False,
):
    import random as _random

    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.types import MessageType
    from dragonboat_tpu.transport.loopback import loopback_factory
    # timers: the election timeout must comfortably exceed the in-process
    # message RTT AND the worst-case GIL starvation of an engine loop
    # while the submitter thread bursts a wave, or heartbeat gaps trigger
    # spurious elections mid-bench — the same config rule the reference
    # documents for its RTT-derived timeouts (config.go:60-126).
    # 10ms ticks x 300 election RTT = 3-6s timeouts, 300ms heartbeats —
    # the submitter's initial burst (G x WAVE entry creations) can hold
    # the GIL for over a second at G=1024, and a heartbeat gap that long
    # must not depose live leaders.
    for nid, addr in members.items():
        cfg = NodeHostConfig(
            raft_address=addr,
            rtt_millisecond=10,
            nodehost_dir=os.path.join(workdir, f"nh{nid}"),
            raft_rpc_factory=lambda a: loopback_factory(a, reg),
            engine=EngineConfig(
                kind="vector",
                max_groups=replicas * groups if shared else groups,
                max_peers=max(replicas, 4),
                log_window=log_window,
                inbox_depth=inbox_depth,
                max_entries_per_msg=entries_per_msg,
                steps_per_sync=steps_per_sync,
                shard_over_mesh=shard_over_mesh,
                share_scope=(
                    f"bench-k{steps_per_sync}" if shared else None
                ),
                # full stage sampling: the BENCH JSON carries per-stage
                # host timings so the perf trajectory tracks where the
                # host half of each step goes
                profile_sample_ratio=1,
            ),
        )
        hosts[nid] = NodeHost(cfg)
    for nid in members:
        hosts[nid].start_clusters([
            (
                dict(members),
                False,
                lambda cid, nid_: sm_cls(cid, nid_),
                Config(
                    node_id=nid, cluster_id=c, election_rtt=300,
                    heartbeat_rtt=30, lease_read=(read_mode == "lease"),
                ),
            )
            for c in range(1, groups + 1)
        ])
    # wait for every group to elect a leader — ONE vectorized leadership
    # readout per poll instead of per-group get_leader_id calls
    t0 = time.monotonic()
    leaders = {}
    pending = set(range(1, groups + 1))
    snap_fn = getattr(hosts[1].engine, "leader_snapshot", None)
    # the bring-up budget scales with fleet size: a 250k-lane nominal
    # config legitimately needs minutes of elections on one host, and a
    # fixed 180s would fail it before the ladder's watchdog even matters
    election_wait = max(180.0, 0.004 * groups * replicas)
    if shard_over_mesh:
        # the sharded engine's bring-up is paced in LAUNCHES: the tick
        # plane clamps each launch's burst at the heartbeat RTT, so a
        # timeout expires after ~election_rtt/heartbeat_rtt launches no
        # matter the wall clock, and the split-vote tail across 10k+
        # independent clusters adds several re-election rounds on top.
        # Each launch pays the replicated cross-shard router: ~25-30s
        # at 50k lanes on 2 virtual CPU devices, linear in lanes.
        election_wait = max(1800.0, 0.04 * groups * replicas)
    while pending and time.monotonic() - t0 < election_wait:
        if snap_fn is not None:
            snap = snap_fn()
            for c in list(pending):
                lid, _term = snap.get(c, (0, 0))
                if lid:
                    leaders[c] = lid
                    pending.discard(c)
        else:
            done = set()
            for c in pending:
                lid, ok = hosts[1].get_leader_id(c)
                if ok:
                    leaders[c] = lid
                    done.add(c)
            pending -= done
        if pending:
            time.sleep(0.05)
    bring_up_s = time.monotonic() - t0
    if pending:
        err = {
            "error": f"{len(pending)} groups never elected",
            "value": 0.0,
            "steps_per_sync": steps_per_sync,
        }
        err.update(_mesh_report(hosts, shard_over_mesh))
        err.update(_attribution_report(hosts, None, None))
        err.update(_read_report(hosts, 0, 0.0, read_mode))
        err.update(_census_report(hosts))
        err.update(_history_report(None))
        return err
    if drop_rate > 0 and shared:
        # randomized replication drops over the co-hosted path (the wire
        # analogue is the transport pre-send hook); rejects/backoff and
        # re-replication must recover the divergence. Installed AFTER
        # bring-up: the stress targets replication during the measured
        # window, and a hook forces the multi-step engine off on-device
        # routing (every message must pass the host-side predicate) —
        # pre-install would put the election traffic on the slow path
        # for no measurement gain.
        rnd = _random.Random(1234)
        rep_types = (
            MessageType.REPLICATE,
            MessageType.REPLICATE_RESP,
        )

        def _drop(m, _rnd=rnd, _t=rep_types):
            return m.type in _t and _rnd.random() < drop_rate

        hosts[1].engine.core.set_local_drop_hook(_drop)
    # warmup: the first kernel compile stalls every engine and piles ticks;
    # the resulting election churn settles within ~2s. Measuring through it
    # records churn losses, not steady-state throughput.
    time.sleep(2.0)
    # runtime sync/retrace audit marks: the folds below report the
    # MEASUREMENT WINDOW's deltas (bring-up legitimately compiles; a
    # steady-state compile or stray sync is the regression signal)
    from dragonboat_tpu.profile import compile_watch, sync_audit

    sync_mark = sync_audit().snapshot()
    compile_mark = compile_watch().install().snapshot()
    # the history sampler runs through the measured window: its cost is
    # part of the reported number, the attribution fold proves it stays
    # sync- and retrace-free
    hist = _start_history(workdir, hosts)
    if snap_fn is not None:
        for c, (lid, _t) in snap_fn().items():
            if lid and c in leaders:
                leaders[c] = lid
    cmd = b"x" * payload
    if through_front:
        out = _front_measure(
            hosts, leaders, snap_fn, groups, duration_s, cmd, wave,
            max(tenants, 1), bring_up_s, steps_per_sync,
        )
        if hist is not None:
            try:
                hist.stop()
            except Exception:
                pass
        out.update(_mesh_report(hosts, shard_over_mesh))
        out.update(_host_stage_report(hosts))
        out.update(_attribution_report(hosts, sync_mark, compile_mark))
        out.update(_latency_report(hosts))
        out.update(_lane_report(hosts))
        out.update(_serving_report(hosts))
        out.update(_read_report(hosts, 0, out["seconds"], read_mode))
        out.update(_census_report(hosts))
        out.update(_history_report(hist))
        return out
    sessions = {
        c: hosts[leaders[c]].get_noop_session(c) for c in range(1, groups + 1)
    }
    # per-group pipelined batches: each group keeps ONE async batch of WAVE
    # proposals in flight (propose_batch_async: one handle + one event per
    # batch); a group resubmits the moment its batch completes. There is no
    # global barrier, so a group wedged by leadership churn costs only its
    # own lane while every other group keeps streaming — the shape of the
    # reference's pipelined benchmark clients.
    WAVE = wave
    total = 0
    dropped = 0
    reads_done = 0
    reads_submitted = 0
    inflight: dict = {}
    read_inflight: dict = {c: [] for c in sessions} if read_ratio else {}
    wave_cmds = [cmd] * WAVE
    churn_state = {"snapshots": 0, "membership": 0, "next": 0.0, "rr": 0}
    t0 = time.perf_counter()
    deadline = t0 + duration_s
    next_leader_refresh = t0 + 0.5
    while time.perf_counter() < deadline:
        progressed = False
        for c, sess in sessions.items():
            h = inflight.get(c)
            if h is not None:
                if not h.finished:
                    continue
                total += h.completed
                dropped += h.dropped
                if read_ratio:
                    rss = read_inflight[c]
                    reads_done += sum(
                        1
                        for rs in rss
                        if rs.result is not None and rs.result.completed
                    )
                    read_inflight[c] = []
            nh = hosts[leaders[c]]
            inflight[c] = nh.propose_batch_async(sess, wave_cmds, 15)
            if read_ratio:
                # R linearizable reads per write, riding the same cycle;
                # PendingReadIndex batches them under shared system ctxs
                n_reads = read_ratio * WAVE
                rss = read_inflight[c]
                for _ in range(n_reads):
                    rss.append(nh.read_index(c, 15))
                reads_submitted += n_reads
            progressed = True
        now = time.perf_counter()
        if churn and now >= churn_state["next"]:
            # BASELINE config 5: membership change + snapshot/compaction
            # interleaved with the write load
            churn_state["next"] = now + 0.5
            rr = churn_state["rr"] = churn_state["rr"] % groups + 1
            try:
                hosts[leaders[rr]].request_snapshot(rr, timeout_s=30.0)
                churn_state["snapshots"] += 1
            except Exception:
                pass
            try:
                # add-then-remove a (never-started) observer: the change
                # itself commits through the log; replication to the absent
                # node exercises the unreachable/breaker paths under load
                cyc = churn_state["membership"] % 2
                nh = hosts[leaders[rr]]
                if cyc == 0:
                    nh.request_add_observer(
                        rr, replicas + 1, "bench:absent", timeout_s=5.0
                    )
                else:
                    nh.request_delete_node(rr, replicas + 1, timeout_s=5.0)
                churn_state["membership"] += 1
            except Exception:
                pass
        if now >= next_leader_refresh:
            next_leader_refresh = now + 0.5
            if snap_fn is not None:
                for c, (lid, _t) in snap_fn().items():
                    if lid and c in sessions:
                        leaders[c] = lid
            else:
                for c in sessions:
                    lid, ok = hosts[1].get_leader_id(c)
                    if ok:
                        leaders[c] = lid
        if not progressed:
            time.sleep(0.002)
    # settle the last in-flight batch per group (bounded)
    settle_deadline = time.perf_counter() + 10
    for c, h in inflight.items():
        h.wait(max(0.0, settle_deadline - time.perf_counter()))
        total += h.completed
        dropped += h.dropped
    for c, rss in read_inflight.items():
        for rs in rss:
            if rs.result is not None and rs.result.completed:
                reads_done += 1
    dt = time.perf_counter() - t0
    if hist is not None:
        try:
            hist.stop()
        except Exception:
            pass
    host_stages = _host_stage_report(hosts)
    out = {
        "value": (total + reads_done) / dt,
        "groups": groups,
        "replicas": replicas,
        "payload_bytes": payload,
        "committed": total,
        "client_dropped": dropped,
        "seconds": round(dt, 2),
        "bring_up_s": round(bring_up_s, 2),
        "fsync": True,
        "shared_engine": shared,
        "wave": wave,
        # bench honesty: K is stamped on every config so tools.perfdiff
        # refuses to diff runs of different engines (K=1 vs K=8 measure
        # different machines, like scaled-down vs nominal does)
        "steps_per_sync": steps_per_sync,
    }
    out.update(_mesh_report(hosts, shard_over_mesh))
    if read_ratio:
        out["reads_completed"] = reads_done
        out["reads_submitted"] = reads_submitted
        out["read_ratio"] = read_ratio
    if drop_rate:
        out["drop_rate"] = drop_rate
    if churn:
        out["snapshots_requested"] = churn_state["snapshots"]
        out["membership_changes"] = churn_state["membership"]
    if host_stages:
        out.update(host_stages)
    out.update(_attribution_report(hosts, sync_mark, compile_mark))
    out.update(_latency_report(hosts))
    out.update(_lane_report(hosts))
    out.update(_serving_report(hosts))
    out.update(_read_report(hosts, reads_done, dt, read_mode))
    out.update(_census_report(hosts))
    out.update(_history_report(hist))
    return out


def _read_report(hosts, reads_done: int, dt: float, read_mode: str) -> dict:
    """Read-path honesty fold, ALWAYS present in every config JSON so the
    schema is stable and tools.perfdiff can apply its read_mode refusal:
    which read path the run measured ('readindex' quorum confirmation vs
    'lease' local serves with automatic ReadIndex fallback), the read
    throughput, and the engines' lease serve/fallback ledger (distinct
    engines only — a shared core hands every host the same counters)."""
    seen = {}
    for nh in hosts.values():
        eng = getattr(nh, "engine", None)
        fn = getattr(eng, "lease_stats", None)
        if fn is not None:
            seen[id(getattr(eng, "core", eng))] = fn
    local = fallback = 0
    for fn in seen.values():
        try:
            d = fn()
        except Exception:
            continue
        local += d["local"]
        fallback += d["fallback"]
    return {
        "read_mode": read_mode,
        "reads_per_sec": round(reads_done / dt, 1) if dt > 0 else 0.0,
        "lease_reads_local": local,
        "lease_reads_fallback": fallback,
    }


def _census_report(hosts) -> dict:
    """HBM census + protocol-event counter fold, ALWAYS present in every
    config JSON — zero-filled when no engine reports (including the
    bring-up-failed path) so the schema stays stable for tools.perfdiff
    and the paged-arena ROADMAP item reads its sizing baseline straight
    off any bench artifact. Distinct engines only (same dedupe as
    _read_report); bytes sum across engines, fill/waste take the worst
    engine (percentiles don't sum)."""
    from dragonboat_tpu.ops.state import CTR_NAMES
    from dragonboat_tpu.profile import CENSUS_KEYS, DeviceCensus

    seen = {}
    for nh in hosts.values():
        eng = getattr(nh, "engine", None)
        if getattr(eng, "device_census", None) is not None:
            seen[id(getattr(eng, "core", eng))] = eng
    out = {k: DeviceCensus.empty()[k] for k in CENSUS_KEYS}
    counters = {name: 0 for name in CTR_NAMES}
    for eng in seen.values():
        try:
            c = eng.device_census()
        except Exception:
            continue
        out["hbm_bytes_total"] += int(c["hbm_bytes_total"])
        out["hbm_log_bytes"] += int(c["hbm_log_bytes"])
        for k in ("log_fill_p50", "log_fill_p99", "hbm_waste_ratio"):
            out[k] = max(out[k], float(c[k]))
        fn = getattr(eng, "counter_stats", None)
        if fn is not None:
            for name, v in fn().items():
                if name in counters:
                    counters[name] += int(v)
    out["counters"] = counters
    return out


def _history_report(sampler) -> dict:
    """Telemetry-history sampler fold, ALWAYS present in every config
    JSON (zero-filled when the sampler never started) so the schema
    stays stable for tools.perfdiff — which shows the sampler's cost
    informationally, never as a gate. The sampler runs LIVE through the
    measured window: its per-sample cost is part of the number the bench
    reports, and the runtime sync/retrace attribution below it proves
    the sampling added zero device syncs and zero recompiles."""
    from dragonboat_tpu.profile import HistorySampler

    stats = (
        sampler.stats() if sampler is not None
        else HistorySampler.empty_stats()
    )
    return {f"history_{k}": v for k, v in stats.items()}


def _start_history(workdir: str, hosts) -> object:
    from dragonboat_tpu.profile import HistorySampler

    try:
        return HistorySampler(
            os.path.join(workdir, "history.ring"), lambda: hosts
        ).start()
    except Exception:
        return None  # telemetry must never block the bench


def _front_measure(
    hosts, leaders, snap_fn, groups, duration_s, cmd, wave, tenants,
    bring_up_s, steps_per_sync,
):
    """The through_front measurement (BASELINE config 7): T tenants drive
    bulk waves through each leader host's ServingFront (admission +
    weighted-fair pump) and an at-most-once SESSION lane rides every few
    waves, so the headline is ADMITTED throughput with per-tenant
    latency percentiles and dedup/migration counters — the ladder's
    millions-of-users shape instead of raw propose_batch. A placement
    plane (no targets on one box, default thresholds) runs its pacer
    through the window so `placement_enabled` is an honest stamp."""
    import threading

    from dragonboat_tpu.serving import (
        AdmissionConfig,
        SessionManager,
        TenantSpec,
    )

    # bulk buckets sized far above capacity: the bench measures what the
    # stack ADMITS under healthy load, not an artificial bucket ceiling
    admission = AdmissionConfig(
        default=TenantSpec(rate=2_000_000.0, burst=200_000.0)
    )
    fronts = {nid: nh.serving_front(admission=admission)
              for nid, nh in hosts.items()}
    mgrs = {nid: SessionManager(front) for nid, front in fronts.items()}
    planes = [
        nh.placement_plane(targets=[]) for nh in hosts.values()
    ]
    for p in planes:
        p.start()
    # tenant t owns clusters {c : c % tenants == t}; register ONE session
    # per tenant on its first cluster's leader host (the dedup lane)
    sess_cluster = {}
    for t in range(tenants):
        own = [c for c in range(1, groups + 1) if c % tenants == t % tenants]
        if not own:
            continue
        c = own[0]
        if mgrs[leaders[c]].register(t, c, count=1, timeout_s=30.0):
            sess_cluster[t] = c
    stats = {
        "admitted": 0, "shed": 0, "session_ops": 0, "session_errors": 0,
    }
    stats_mu = threading.Lock()
    stop = threading.Event()

    def tenant_main(t: int) -> None:
        own = [c for c in range(1, groups + 1) if c % tenants == t % tenants]
        admitted = shed = s_ops = s_err = 0
        rounds = 0
        while not stop.is_set():
            for c in own:
                if stop.is_set():
                    break
                front = fronts[leaders[c]]
                tickets = []
                for _ in range(wave):
                    try:
                        tickets.append(front.propose(t, c, cmd, 15.0))
                    except Exception:
                        shed += 1
                for tk in tickets:
                    # Ticket.wait RE-RAISES pump-side sheds (engine
                    # busy / inbox overflow): count them, never let one
                    # kill the tenant worker mid-window
                    try:
                        r = tk.wait()
                    except Exception:
                        shed += 1
                        continue
                    if r is not None and r.completed:
                        admitted += 1
                    else:
                        shed += 1
            rounds += 1
            if t in sess_cluster and rounds % 4 == 0:
                # the at-most-once lane: one session proposal through the
                # same pump, deadline-retried under the SAME series
                c = sess_cluster[t]
                try:
                    mgrs[leaders[c]].propose(t, c, cmd, 10.0)
                    s_ops += 1
                except Exception:
                    s_err += 1
        with stats_mu:
            stats["admitted"] += admitted
            stats["shed"] += shed
            stats["session_ops"] += s_ops
            stats["session_errors"] += s_err

    workers = [
        threading.Thread(target=tenant_main, args=(t,), daemon=True)
        for t in range(tenants)
    ]
    t0 = time.perf_counter()
    for w in workers:
        w.start()
    deadline = t0 + duration_s
    while time.perf_counter() < deadline:
        time.sleep(0.25)
        if snap_fn is not None:
            for c, (lid, _t) in snap_fn().items():
                if lid and c in leaders:
                    leaders[c] = lid
    stop.set()
    for w in workers:
        w.join(timeout=20)
    dt = time.perf_counter() - t0
    session_stats = {"registered": 0, "retired": 0, "proposals": 0,
                     "safe_retries": 0, "register_failed": 0, "pooled": 0}
    for m in mgrs.values():
        for k, v in m.stats().items():
            session_stats[k] = session_stats.get(k, 0) + v
    total = stats["admitted"] + stats["session_ops"]
    return {
        "value": total / dt,
        "groups": groups,
        "replicas": len(hosts),
        "payload_bytes": len(cmd),
        "committed": total,
        "client_dropped": stats["shed"],
        "seconds": round(dt, 2),
        "bring_up_s": round(bring_up_s, 2),
        "fsync": True,
        "shared_engine": True,
        "wave": wave,
        "steps_per_sync": steps_per_sync,
        # ---- bench honesty: a front run measures a different machine
        # than raw propose_batch — perfdiff refuses cross-workload diffs
        "workload": "through_front",
        "session_mode": "sessions",
        "placement_enabled": True,
        "tenants": tenants,
        # ---- the session/dedup lane's ledger
        "session_registered_total": session_stats["registered"],
        "session_proposals_total": session_stats["proposals"],
        "session_safe_retries_total": session_stats["safe_retries"],
        "session_errors_total": stats["session_errors"],
    }


def _engine_profilers(hosts) -> dict:
    """Every DISTINCT engine profiler across the hosts (a shared core
    hands every host the same object — counted once; shared=False runs
    sum the per-host engines)."""
    profs = {}
    for nh in hosts.values():
        prof = getattr(getattr(nh, "engine", None), "profiler", None)
        if prof is not None:
            profs[id(prof)] = prof
    return profs


def _attribution_report(hosts, sync_mark, compile_mark) -> dict:
    """The perf attribution fold (tools.perfdiff's input): an ALWAYS-
    present `phase_breakdown` with every canonical phase key (zero when
    the phase never ran, so the JSON schema is stable across configs and
    the gate can diff any two runs), plus the measurement-window
    `device_syncs` / `compile_events` deltas from the runtime audit.
    `sync_mark`/`compile_mark` of None (the bring-up-failed path) report
    zero-delta audits so the schema still holds."""
    from dragonboat_tpu.profile import (
        VECTOR_PHASES,
        compile_watch,
        diff_compiles,
        diff_sync,
        sync_audit,
    )

    phases = {p: 0.0 for p in VECTOR_PHASES}
    for prof in _engine_profilers(hosts).values():
        for name, s in prof.summary().items():
            phases[name] = round(phases.get(name, 0.0) + s["total_s"], 4)
    out = {"phase_breakdown": phases}
    if sync_mark is None:
        out["device_syncs"] = {"in_seam": 0, "out_of_seam": 0, "sites": {}}
    else:
        out["device_syncs"] = diff_sync(sync_mark, sync_audit().snapshot())
    if compile_mark is None:
        out["compile_events"] = {
            "total": 0, "total_s": 0.0, "cache_hits": 0,
            "per_function": {},
        }
    else:
        out["compile_events"] = diff_compiles(
            compile_mark, compile_watch().snapshot()
        )
    return out


def _lane_report(hosts) -> dict:
    """Per-lane introspection fold (VectorEngine.lane_stats: derived from
    the numpy mirrors the decode phase maintains — zero device syncs).
    Keys are ALWAYS present so the BENCH JSON schema stays stable: lane
    count, leader coverage, and the worst/typical commit gap (how far any
    lane's accepted log runs ahead of its quorum commit at bench end)."""
    lanes_total = lanes_with_leader = 0
    gap_max = 0
    gaps = []
    for nh in hosts.values():
        lane_stats = getattr(getattr(nh, "engine", None), "lane_stats", None)
        if lane_stats is None:
            continue
        for _cid, s in lane_stats().items():
            lanes_total += 1
            if s["leader_id"]:
                lanes_with_leader += 1
            gaps.append(s["commit_gap"])
            gap_max = max(gap_max, s["commit_gap"])
    gaps.sort()
    return {
        "lanes_total": lanes_total,
        "lanes_with_leader": lanes_with_leader,
        "lane_commit_gap_max": gap_max,
        "lane_commit_gap_p50": gaps[len(gaps) // 2] if gaps else 0,
    }


def _serving_report(hosts) -> dict:
    """Serving-front overload fold (ISSUE 8): total admit/shed/wake
    counts across every tenant of every host that created a front, and
    the urgent/bulk serving latency percentiles merged across hosts from
    the (tenant, klass)-keyed histogram plane. Keys are ALWAYS present —
    zero when no front exists (the default harness drives propose_batch
    directly) — so the BENCH JSON schema is stable across configs."""
    from dragonboat_tpu.events import Histogram
    from dragonboat_tpu.serving import KLASS_BULK, KLASS_URGENT

    admitted = shed = wakes = 0
    lat = {KLASS_URGENT: Histogram(), KLASS_BULK: Histogram()}
    per_tenant = {}
    for nh in hosts.values():
        front = getattr(nh, "_serving", None)
        if front is not None:
            for c in front.admission.counters().values():
                admitted += sum(c["admitted"].values())
                shed += sum(c["shed"].values())
                wakes += c["wakes"]
        m = getattr(nh, "metrics", None)
        if m is None:
            continue
        for (tid, klass), h in m.histogram_items("serving_latency_seconds"):
            if klass in lat:
                lat[klass].merge(h)
            if klass == KLASS_BULK and h.count:
                agg = per_tenant.setdefault(str(tid), Histogram())
                agg.merge(h)
    # live-migration ledger (serving/placement.py planes + the chunk
    # tracker's migration-tagged install streams); zero when no plane ran
    mig = {"started": 0, "completed": 0, "aborted": 0}
    mig_streams = 0
    for nh in hosts.values():
        plane = getattr(nh, "_placement", None)
        if plane is not None:
            c = plane.counters()
            for k in mig:
                mig[k] += c[f"migrations_{k}"]
        chunks = getattr(nh, "_chunks", None)
        if chunks is not None:
            mig_streams += chunks.stats().get("migration_streams", 0)
    return {
        "serving_admitted_total": admitted,
        "serving_shed_total": shed,
        "serving_wakes_total": wakes,
        "serving_urgent_p99_s": round(lat[KLASS_URGENT].quantile(0.99), 6),
        "serving_bulk_p50_s": round(lat[KLASS_BULK].quantile(0.5), 6),
        "serving_bulk_p99_s": round(lat[KLASS_BULK].quantile(0.99), 6),
        # per-tenant commit percentiles through the front (empty for raw
        # runs; config 7's headline detail) — keys are ALWAYS present
        "serving_tenant_latency": {
            tid: {
                "p50_s": round(h.quantile(0.5), 6),
                "p99_s": round(h.quantile(0.99), 6),
            }
            for tid, h in sorted(per_tenant.items())
        },
        "migrations_started": mig["started"],
        "migrations_completed": mig["completed"],
        "migrations_aborted": mig["aborted"],
        "migration_streams": mig_streams,
    }


def _latency_report(hosts) -> dict:
    """Proposal-lifecycle latency percentiles from the hosts' sampled
    histograms (EngineConfig.profile_sample_ratio=1 in the bench config:
    one sampled proposal per submitted wave), merged across hosts into one
    distribution per metric. The commit-latency keys are ALWAYS present —
    0.0 when no sample landed — so the BENCH JSON schema is stable for
    every ladder config."""
    from dragonboat_tpu.events import Histogram

    def merged(name: str) -> Histogram:
        agg = Histogram()
        for nh in hosts.values():
            m = getattr(nh, "metrics", None)
            if m is None:
                continue
            for h in m.histograms(name):
                agg.merge(h)
        return agg

    commit = merged("proposal_commit_latency_seconds")
    apply_ = merged("proposal_apply_latency_seconds")
    fsync = merged("fsync_latency_seconds")
    out = {
        "commit_latency_p50_s": round(commit.quantile(0.5), 6),
        "commit_latency_p99_s": round(commit.quantile(0.99), 6),
        "commit_latency_samples": commit.count,
        "apply_latency_p99_s": round(apply_.quantile(0.99), 6),
        "fsync_latency_p99_s": round(fsync.quantile(0.99), 6),
    }
    # read-latency keys are ALWAYS present (0.0 with no read traffic):
    # config 8's lease-vs-readindex A/B diffs them, and a stable schema
    # is what lets perfdiff fold any two same-mode records. The histogram
    # is serve-path agnostic — Node.read() samples at submit and records
    # at completion whether the lease path or the quorum path served it.
    reads = merged("readindex_latency_seconds")
    out["read_latency_p50_s"] = round(reads.quantile(0.5), 6)
    out["read_latency_p99_s"] = round(reads.quantile(0.99), 6)
    out["read_latency_samples"] = reads.count
    if reads.count:
        out["readindex_latency_p99_s"] = round(reads.quantile(0.99), 6)
    return out


# vector-engine profiler stages making up the host fan-out half of a step
# (everything between the device fetch and the next pack; "deliver" is a
# sub-span nested inside the send/apply/reads phases, so it is excluded
# here to avoid double counting)
_FANOUT_STAGES = ("place", "send_rep", "send_resp", "apply", "reads")


def _mesh_report(hosts, shard_over_mesh: bool) -> dict:
    """Mesh honesty stamps for every config JSON: how many devices the
    engine actually sharded over (1 = unsharded), the mesh shape, and the
    ghost-lane count from the device-multiple round-up. tools.perfdiff
    refuses to diff configs whose mesh shapes differ, exactly like the
    scaled-down / K / workload refusals."""
    n_dev, padded = 0, 0
    try:
        ss = hosts[1].engine.step_stats()
        n_dev = int(ss.get("mesh_devices", 0) or 0)
        padded = int(ss.get("padded_groups", 0) or 0)
    except Exception:
        pass
    n_dev = n_dev or 1
    return {
        "shard_over_mesh": bool(shard_over_mesh),
        "n_devices": n_dev,
        "mesh_shape": [n_dev],
        "padded_groups": padded,
    }


def _host_stage_report(hosts) -> dict:
    """Per-stage host timings from the engine's stage profiler: total
    seconds per stage (pack / device dispatch+step / fan-out / save) plus
    the fan-out+pack share of step wall time — the number the columnar
    host dataflow is accountable to."""
    profs = _engine_profilers(hosts)
    totals_raw: dict = {}
    for prof in profs.values():
        for name, s in prof.summary().items():
            totals_raw[name] = totals_raw.get(name, 0.0) + s["total_s"]
    if not totals_raw:
        return {}
    totals = {name: round(v, 4) for name, v in totals_raw.items()}
    # sub-spans lie inside a phase: keep them out of the wall sum or
    # their seconds would count twice
    from dragonboat_tpu.profile import VECTOR_SUBSPANS

    wall = sum(v for n, v in totals_raw.items() if n not in VECTOR_SUBSPANS)
    fanout = sum(totals_raw.get(n, 0.0) for n in _FANOUT_STAGES)
    pack = totals_raw.get("pack", 0.0)
    out = {"host_stage_total_s": totals}
    if wall > 0:
        out["fanout_pack_share"] = round((fanout + pack) / wall, 4)
    return out


# ---------------------------------------------------------------------------
# bare-kernel benchmark (secondary metric; the round-1/2 headline)
# ---------------------------------------------------------------------------


def kernel_step(state: RaftTensors, inbox, ticks, cfg: KernelConfig):
    state, out = step_batch(state, inbox, ticks, cfg)
    # engine-side compaction: applied entries leave the device window
    state = state._replace(
        marker_term=_term_at(state, state.applied),
        first_index=state.applied + 1,
    )
    return state, out.commit_index


def bench_kernel(groups: int, steps: int, warmup: int, log_window: int):
    cfg = KernelConfig(
        groups=groups, peers=8, log_window=log_window,
        inbox_depth=8, max_entries_per_msg=8, readindex_depth=4,
    )
    G, K, E = cfg.groups, cfg.inbox_depth, cfg.max_entries_per_msg
    state = init_state(cfg)
    # one voting replica per group: commit is immediate; this measures the
    # device ceiling (quorum/transport/fsync excluded BY DESIGN — the e2e
    # metric above is the honest framework number)
    state = configure_groups_uniform(state, self_slot=0, voting_slots=(0,))
    fn = jax.jit(functools.partial(kernel_step, cfg=cfg), donate_argnums=(0,))
    elect = make_empty_inbox(cfg)
    elect = elect._replace(mtype=elect.mtype.at[:, 0].set(MSG.ELECTION))
    ticks = jnp.zeros((G,), jnp.int32)
    state, _ = fn(state, elect, ticks)
    inbox = make_empty_inbox(cfg)
    inbox = inbox._replace(
        mtype=jnp.full_like(inbox.mtype, MSG.PROPOSE),
        n_entries=jnp.full_like(inbox.n_entries, E),
    )
    for _ in range(warmup):
        state, commit = fn(state, inbox, ticks)
    jax.block_until_ready(commit)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, commit = fn(state, inbox, ticks)
    jax.block_until_ready(commit)
    dt = time.perf_counter() - t0
    expected = (warmup + steps) * K * E + 1  # +1 leader noop
    final_commit = int(jnp.min(commit))
    assert final_commit == expected, (final_commit, expected)
    return steps * G * K * E / dt


# The BASELINE.json five-config ladder. `nominal` is the regime the
# baseline names; `scaled` is what an e2e run at that regime costs on one
# in-process box — group counts shrink so every config completes inside
# the watchdog budget (the 50k-group regime is covered at full scale by
# the kernel metric and the bring-up benchmark in tests/test_bring_up.py).
LADDER = {
    1: dict(
        label="3-node, 1 group, 16B (benchmark_test.go baseline)",
        nominal_groups=1, groups=1, replicas=3, payload=16, wave=512,
        duration=6.0,
    ),
    2: dict(
        label="3-node, 1024 groups, 16B, batched step",
        nominal_groups=1024, groups=1024, replicas=3, payload=16,
        wave=128, duration=10.0,
    ),
    3: dict(
        label="5-node, 10k groups, 9:1 ReadIndex:write, elections on",
        nominal_groups=10_000, groups=256, replicas=5, payload=16,
        wave=8, duration=8.0, read_ratio=9,
    ),
    4: dict(
        label="5-node, 50k groups, 128B, randomized follower drops",
        nominal_groups=50_000, groups=256, replicas=5, payload=128,
        wave=64, duration=8.0, drop_rate=0.01,
    ),
    5: dict(
        label="5-node, 50k groups, membership + snapshot interleave",
        nominal_groups=50_000, groups=128, replicas=5, payload=16,
        wave=64, duration=8.0, churn=True,
    ),
    # config 2's workload on the device-resident multi-step engine: K=8
    # protocol steps per kernel launch, co-hosted replica traffic routed
    # on device. Kept as its OWN config id so the perfdiff trajectory
    # never diffs it against a K=1 run of config 2 (the K honesty rule).
    6: dict(
        label="3-node, 1024 groups, 16B, K=8 device-resident super-steps",
        nominal_groups=1024, groups=1024, replicas=3, payload=16,
        wave=128, duration=10.0, steps_per_sync=8,
    ),
    # the millions-of-users shape: traffic THROUGH SessionManager/
    # ServingFront (admission control, weighted-fair fan-in, at-most-once
    # session lane, placement plane live) — the headline is ADMITTED
    # throughput with per-tenant p50/p99 and dedup/migration counters.
    # Its own config id: perfdiff refuses front-vs-raw comparisons.
    7: dict(
        label="3-node, 64 groups, 16B, through_front: sessions + "
              "admission + placement",
        nominal_groups=64, groups=64, replicas=3, payload=16,
        wave=32, duration=8.0, through_front=True, tenants=4,
    ),
    # read_heavy: config 2's fleet shape under a 9:1 read:write mix, run
    # TWICE — once with reads on the ReadIndex quorum path, once with
    # leader leases serving reads locally (automatic ReadIndex fallback
    # on expiry/suspect). The record is the LEASE run (stamped
    # read_mode='lease' so perfdiff refuses cross-mode diffs) carrying
    # the ReadIndex run's read numbers under `readindex_mode` plus the
    # reads/s speedup ratio — the lease read path's headline.
    8: dict(
        label="3-node, 1024 groups, 16B, read_heavy 9:1, "
              "lease vs ReadIndex reads",
        nominal_groups=1024, groups=1024, replicas=3, payload=16,
        wave=8, duration=10.0, read_ratio=9, both_read_modes=True,
    ),
}


def _run_ladder_config(n: int, spec: dict) -> dict:
    groups = spec["groups"]
    duration = spec["duration"]

    def _run(read_mode: str) -> dict:
        workdir = tempfile.mkdtemp(prefix=f"dbtpu-bench-c{n}-")
        try:
            return bench_e2e(
                groups, duration, spec["payload"], workdir,
                wave=spec["wave"],
                entries_per_msg=spec.get("entries_per_msg", 64),
                replicas=spec["replicas"],
                read_ratio=spec.get("read_ratio", 0),
                read_mode=read_mode,
                drop_rate=spec.get("drop_rate", 0.0),
                churn=spec.get("churn", False),
                steps_per_sync=spec.get("steps_per_sync", 1),
                through_front=spec.get("through_front", False),
                tenants=spec.get("tenants", 0),
                shard_over_mesh=spec.get("shard_over_mesh", False),
            )
        finally:
            shutil.rmtree(workdir, ignore_errors=True)

    if spec.get("both_read_modes"):
        # the read_heavy A/B: ReadIndex-mode first (the baseline), then
        # the lease-mode run that IS the config record. Both halves ran
        # on the same box minutes apart, so the speedup ratio inside one
        # record is the honest same-host comparison perfdiff's
        # read_mode refusal would otherwise forbid across records.
        base = _run("readindex")
        r = _run("lease")
        r["readindex_mode"] = {
            k: base[k]
            for k in (
                "value", "reads_per_sec", "read_latency_p50_s",
                "read_latency_p99_s", "read_latency_samples", "committed",
                "seconds", "bring_up_s",
            )
            if k in base
        }
        rps, base_rps = r.get("reads_per_sec", 0), base.get("reads_per_sec")
        if base_rps:
            r["lease_vs_readindex_reads"] = round(rps / base_rps, 3)
    else:
        r = _run(spec.get("read_mode", "readindex"))
    r["label"] = spec["label"]
    # bench honesty: the JSON names BOTH the regime the ladder config
    # claims (nominal_groups) and what this run actually exercised
    # (actual_groups); a run standing in for a larger regime is stamped
    # scaled_down so tools.perfdiff refuses to compare it against a
    # nominal run of the same config
    r["nominal_groups"] = spec["nominal_groups"]
    r["actual_groups"] = groups
    r["scaled_down"] = groups != spec["nominal_groups"]
    r["entries_per_msg"] = spec.get("entries_per_msg", 64)
    return r


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=0,
                    choices=[0, 1, 2, 3, 4, 5, 6, 7, 8],
                    help="run ONE BASELINE.json ladder config (1-8) at its "
                         "declared scale instead of the full reduced sweep")
    ap.add_argument("--groups", type=int, default=0,
                    help="override group count (with --config)")
    ap.add_argument("--steps-per-sync", type=int, default=0,
                    help="override EngineConfig.steps_per_sync (with "
                         "--config): K protocol steps per kernel launch")
    ap.add_argument("--shard-over-mesh", action="store_true",
                    help="shard the engine's lane axis over every visible "
                         "device (EngineConfig.shard_over_mesh); composes "
                         "with --steps-per-sync")
    ap.add_argument("--devices", type=int, default=0,
                    help="pin N virtual CPU devices before backend init "
                         "(XLA host-platform device count; CPU only — on "
                         "an accelerator the real topology is used)")
    ap.add_argument("--entries-per-msg", type=int, default=0,
                    help="override the e2e engine's max_entries_per_msg "
                         "(with --config). The cross-shard router ships "
                         "2*E entry rows per candidate message, so E "
                         "dominates the routed-slab width; sharded CPU "
                         "runs use E=8 to keep the per-launch cost sane. "
                         "Stamped into the config record.")
    ap.add_argument("--duration", type=float, default=0.0)
    ap.add_argument("--kernel-groups", type=int, default=50_000)
    ap.add_argument("--kernel-steps", type=int, default=50)
    ap.add_argument("--kernel-warmup", type=int, default=5)
    ap.add_argument("--kernel-log-window", type=int, default=512)
    ap.add_argument("--skip-kernel", action="store_true")
    ap.add_argument("--skip-e2e", action="store_true")
    ap.add_argument("--watchdog-s", type=float, default=560.0)
    args = ap.parse_args()

    if args.devices > 0:
        # must land before anything touches the backend: XLA reads the
        # host-platform device count at first initialization only
        pin_cpu(n_devices=args.devices)
    watchdog = _arm_watchdog(args.watchdog_s)
    # warm XLA compiles across bench runs (each ladder config's engine
    # shape costs seconds of compile; the cache makes reruns start warm)
    enable_compile_cache()
    # the bench runs on whatever backend jax gives this process — the one
    # process that touches jax — and stamps it; it never substitutes
    # another backend or shrinks a workload on its own
    devs = jax.devices()
    RECORD["platform"] = devs[0].platform
    RECORD["device_kind"] = devs[0].device_kind
    RECORD["device_count"] = len(devs)
    # runtime perf attribution: count XLA compile events and wrap
    # jax.device_get/block_until_ready so any transfer outside the
    # blessed _fetch_output seam lands in the device_syncs fold with its
    # call site (dragonboat_tpu.profile; the runtime twin of `-m lint`'s
    # device-sync/retrace families)
    from dragonboat_tpu.profile import compile_watch, sync_audit

    compile_watch().install()
    sync_audit().install()

    RECORD["host"] = _host_stamp()
    failed = []
    if not args.skip_e2e:
        configs = {}
        RECORD["configs"] = configs
        to_run = [args.config] if args.config else list(LADDER)
        for n in to_run:
            spec = dict(LADDER[n])
            if args.config:
                if args.groups:
                    spec["groups"] = args.groups
                else:
                    spec["groups"] = spec["nominal_groups"]
                if args.duration:
                    spec["duration"] = args.duration
                if args.steps_per_sync:
                    spec["steps_per_sync"] = args.steps_per_sync
                if args.shard_over_mesh:
                    spec["shard_over_mesh"] = True
                if args.entries_per_msg:
                    spec["entries_per_msg"] = args.entries_per_msg
            try:
                configs[str(n)] = _run_ladder_config(n, spec)
            except Exception as e:  # record, keep laddering, exit non-zero
                import traceback

                traceback.print_exc()
                configs[str(n)] = {"label": spec["label"], "error": repr(e)}
            if "error" in configs[str(n)]:
                failed.append(n)
        headline = configs.get(str(args.config or 2), {})
        RECORD["value"] = round(headline.get("value", 0.0), 1)
        RECORD["vs_baseline"] = round(
            RECORD["value"] / BASELINE_PROPOSALS_PER_SEC, 6
        )
    if not args.skip_kernel:
        kv = bench_kernel(
            args.kernel_groups, args.kernel_steps, args.kernel_warmup,
            args.kernel_log_window,
        )
        RECORD["kernel_proposals_per_sec"] = round(kv, 1)
        RECORD["kernel_vs_baseline"] = round(
            kv / BASELINE_PROPOSALS_PER_SEC, 3
        )
        if args.skip_e2e:
            RECORD["metric"] = "kernel_proposals_per_sec"
            RECORD["value"] = round(kv, 1)
            RECORD["vs_baseline"] = round(kv / BASELINE_PROPOSALS_PER_SEC, 3)

    watchdog.cancel()
    print(json.dumps(RECORD))
    if failed:
        sys.exit(f"bench: config(s) {failed} failed; see the record's "
                 "per-config error fields")


if __name__ == "__main__":
    main()
