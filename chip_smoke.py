"""Chip smoke: the NodeHost -> VectorEngine -> step-kernel path on the TPU.

The quickest proof that the system still starts, compiles and answers on
the chip. ONE process, the only one that touches JAX. It drives the public
surface (NodeHost, start_clusters, propose_batch_async, sync_read,
stale_read) at BASELINE.json config 2 / bench.LADDER[2] nominal size:

    3 NodeHosts co-hosted on one shared engine core, 1024 groups x 3
    replicas = 3072 lanes, 16-byte proposals, WAL on disk with fsync
    honoured, engine shape P=4, log_window=256, inbox_depth=4,
    max_entries_per_msg=64.

Two passes share the process and its compile cache: K=1 with
overlap_decode left at None (so the accelerator default, overlap on, is
what runs) and steps_per_sync=8 (the on-device router). Each pass: every
group elects; three waves of 128 seeded proposals per group (393216 in
all) are acknowledged; then, per group, a linearizable sync_read on the
leader's host and one on a follower's host must return exactly what a
plain reference computes from the acknowledged payloads, and all three
replicas' local state must reach it within CONVERGE_S seconds. Then the
engine rebases its device indexes (the one kernel-side path no traffic of
this size reaches by itself), and a tail wave is read back the same way.

Exit code 0 and a last stdout line {"ok": true, "device": {...}} only when
the platform is tpu, state lives on the TPU, every phase finished inside
its bound, the engine loop swallowed no exception and no acknowledged
write is missing. There is no CPU fallback: without a TPU the plain
invocation exits non-zero and prints no result.

    python chip_smoke.py                 one chip (what the driver runs)
    python chip_smoke.py --mesh          shard_over_mesh over every device
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal [--mesh]
                                         tiny CPU rehearsal, never a pass

The phase times it prints are a smoke's timings, not benchmark results.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax  # importing starts no backend; main() pins or checks it first
import numpy as np

from dragonboat_tpu._jaxenv import enable_compile_cache, pin_cpu
from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.profile import compile_watch, diff_compiles
from dragonboat_tpu.requests import ErrClusterNotReady, ErrTimeout
from dragonboat_tpu.statemachine import IConcurrentStateMachine, Result
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

GROUPS = 1024
REPLICAS = 3
LOG_WINDOW = 256
WAVE = 128
WAVES = 3  # > LOG_WINDOW entries per group, so the rebase has work to do
TAIL_WAVE = 8
PAYLOAD_BYTES = 16
REHEARSAL_GROUPS = 8
REHEARSAL_MESH_DEVICES = 4
PASSES = (("k1", 1), ("k8", 8))

# phase bounds, seconds (compilation included where it happens)
ELECT_S = 420.0
TRAFFIC_S = 240.0
READ_S = 90.0
READ_TRY_S = 20.0
READ_THREADS = 256  # reads in flight; each waits a few engine steps
CONVERGE_S = 30.0
# the whole script gives up (thread dump, exit 1) before the driver's 1200s
HARD_LIMIT_S = 1150

_WORKDIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".chip_smoke_work"
)
_MASK64 = (1 << 64) - 1


class SmokeFailure(AssertionError):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _SmokeSM(IConcurrentStateMachine):
    """Replicated state = (entries applied, sum of every payload's two
    little-endian u64 words mod 2^64): cheap, and wrong if any payload
    byte is lost, duplicated or replaced on the way through the WAL, the
    arena and apply."""

    def __init__(self, cluster_id, node_id):
        self.state = (0, 0)

    def update(self, entries):
        n, acc = self.state
        for e in entries:
            n += 1
            acc += int.from_bytes(e.cmd[:8], "little")
            acc += int.from_bytes(e.cmd[8:16], "little")
            e.result = Result(value=n)
        self.state = (n, acc & _MASK64)  # one store: lookups never tear
        return entries

    def lookup(self, q):
        return self.state

    def prepare_snapshot(self):
        return self.state

    def save_snapshot(self, ctx, w, fc, done):
        w.write(ctx[0].to_bytes(8, "little") + ctx[1].to_bytes(8, "little"))

    def recover_from_snapshot(self, r, fc, done):
        b = r.read(16)
        self.state = (
            int.from_bytes(b[:8], "little"), int.from_bytes(b[8:], "little")
        )

    def close(self):
        pass


def _say(label: str, key: str, value) -> None:
    print(f"[{label}] {key}: {value}", flush=True)


def _compiles(d: dict) -> str:
    """One diff_compiles() window as the smoke prints it."""
    return (
        f"{d['total']} requests, {d['total_s']:.2f}s, {d['cache_hits']} "
        f"persistent-cache hits, {d['total'] - d['cache_hits']} backend "
        "compiles"
    )


def _wait_leaders(hosts, groups: int, bound_s: float) -> dict:
    leaders: dict = {}
    pending = set(range(1, groups + 1))
    deadline = time.monotonic() + bound_s
    while pending:
        for c in list(pending):
            lid, ok = hosts[1].get_leader_id(c)
            if ok:
                leaders[c] = lid
                pending.discard(c)
        if not pending:
            break
        _require(
            time.monotonic() < deadline,
            f"{len(pending)} of {groups} groups elected no leader within "
            f"{bound_s:.0f}s",
        )
        time.sleep(0.1)
    return leaders


class _Ledger:
    """What the client was told, per group: proposals acknowledged,
    proposals whose fate it was not told (a batch cut short by a leader
    change or a full queue: they may still commit), and how many of the
    group's seeded payload rows have been submitted. It is also the plain
    reference: a group with nothing indeterminate holds exactly
    (rows submitted, sum of those rows' u64 words mod 2^64)."""

    def __init__(self, groups: int, rows: int, seed) -> None:
        self.payloads = np.random.default_rng(seed).integers(
            0, 256, (groups, rows, PAYLOAD_BYTES), dtype=np.uint8
        )
        self.acked = {c: 0 for c in range(1, groups + 1)}
        self.indeterminate = dict.fromkeys(self.acked, 0)
        self.used = dict.fromkeys(self.acked, 0)
        self.read_retries = 0

    def take(self, c: int, n: int) -> list:
        lo = self.used[c]
        _require(
            lo + n <= self.payloads.shape[1],
            f"group {c} ran out of spare payloads after repeated drops",
        )
        self.used[c] = lo + n
        return [r.tobytes() for r in self.payloads[c - 1, lo:lo + n]]

    def expected(self, c: int):
        """(count, sum) if exact, else None."""
        if self.indeterminate[c]:
            return None
        words = self.payloads[c - 1, :self.used[c]].view("<u8")
        return self.used[c], int(words.sum(dtype=np.uint64))

    def check(self, c: int, where: str, got) -> None:
        want = self.expected(c)
        if want is not None:
            _require(
                got == want,
                f"group {c} {where}: read {got}, acknowledged {want}",
            )
            return
        lo, hi = self.acked[c], self.acked[c] + self.indeterminate[c]
        _require(
            lo <= got[0] <= hi,
            f"group {c} {where}: read count {got[0]} outside [{lo}, {hi}]",
        )


def _drive_wave(hosts, leaders, ledger: _Ledger, wave: int, bound_s: float):
    """`wave` more proposals per group, acknowledged. A batch cut short
    has its remainder resubmitted from the group's spare payloads."""
    want = {c: ledger.acked[c] + wave for c in leaders}
    deadline = time.monotonic() + bound_s
    todo = set(leaders)
    while todo:
        inflight = {}
        for c in todo:
            nh = hosts[leaders[c]]
            inflight[c] = nh.propose_batch_async(
                nh.get_noop_session(c),
                ledger.take(c, want[c] - ledger.acked[c]),
                bound_s,
            )
        for c, h in inflight.items():
            _require(
                h.wait(max(0.0, deadline - time.monotonic())),
                f"group {c}: wave not accounted for within {bound_s:.0f}s "
                f"({h.completed}/{h.n} acknowledged)",
            )
            ledger.acked[c] += h.completed
            ledger.indeterminate[c] += h.dropped
        todo = {c for c in todo if ledger.acked[c] < want[c]}
        if todo:
            _require(
                time.monotonic() < deadline,
                f"{len(todo)} groups short of {wave} acknowledged proposals "
                f"after {bound_s:.0f}s",
            )
            for c in todo:
                lid, ok = hosts[1].get_leader_id(c)
                if ok:
                    leaders[c] = lid
            time.sleep(0.2)


def _read_back(hosts, leaders, ledger: _Ledger):
    """Per group: a linearizable read on the leader's host, then one on a
    follower's host, both held to the ledger; then every replica's local
    state must reach it within CONVERGE_S. Returns (seconds reading,
    seconds converging, groups held to the exact reference)."""

    def read(nh, c: int):
        # a ReadIndex may be dropped by protocol (leader moved, no entry
        # committed in its term yet): retry like a client, inside READ_S
        deadline = time.monotonic() + READ_S
        while True:
            try:
                return nh.sync_read(c, None, READ_TRY_S)
            except (ErrTimeout, ErrClusterNotReady):
                ledger.read_retries += 1
                if time.monotonic() >= deadline:
                    raise

    def read_pair(c: int):
        lid = leaders[c]
        fid = next(n for n in hosts if n != lid)
        return read(hosts[lid], c), read(hosts[fid], c)

    t0 = time.perf_counter()
    final = {}
    with ThreadPoolExecutor(max_workers=READ_THREADS) as pool:
        futs = {c: pool.submit(read_pair, c) for c in leaders}
        for c, f in futs.items():
            on_leader, on_follower = f.result()
            ledger.check(c, "leader-host sync_read", on_leader)
            ledger.check(c, "follower-host sync_read", on_follower)
            # a later linearizable read never reads less
            _require(
                on_follower[0] >= on_leader[0],
                f"group {c}: follower read {on_follower} after {on_leader}",
            )
            final[c] = on_follower[0]
    t_reads = time.perf_counter() - t0

    t0 = time.perf_counter()
    deadline = time.monotonic() + CONVERGE_S
    lagging = [(c, n) for c in leaders for n in hosts]
    while lagging:
        lagging = [
            (c, n) for c, n in lagging
            if hosts[n].stale_read(c, None)[0] < final[c]
        ]
        if not lagging:
            break
        _require(
            time.monotonic() < deadline,
            f"{len(lagging)} replicas did not converge within "
            f"{CONVERGE_S:.0f}s, e.g. (group, host) {lagging[:4]}",
        )
        time.sleep(0.05)
    n_exact = 0
    for c in leaders:
        if ledger.expected(c) is not None:
            n_exact += 1
            for n in hosts:
                ledger.check(
                    c, f"host {n} local state", hosts[n].stale_read(c, None)
                )
    return t_reads, time.perf_counter() - t0, n_exact


def _state_bytes_per_device(state) -> dict:
    """Bytes of engine state each device holds, from the arrays' own
    shardings (metadata only: the loop thread donates the buffers)."""
    per_dev: dict = {}
    for leaf in jax.tree.leaves(state):
        shard = leaf.sharding.shard_shape(leaf.shape)
        nbytes = int(np.prod(shard, dtype=np.int64)) * leaf.dtype.itemsize
        for d in leaf.sharding.device_set:
            per_dev[d.id] = per_dev.get(d.id, 0) + nbytes
    return dict(sorted(per_dev.items()))


def run_pass(
    label: str, steps_per_sync: int, groups: int, seed: int, mesh: bool,
    workdir: str,
) -> dict:
    """One pass of the smoke; prints its measurements and raises on any
    failed check. Returns the counts a caller can assert on."""
    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cw = compile_watch().install()
    mark_start = cw.snapshot()
    members = {n: f"smoke-{label}:{n}" for n in range(1, REPLICAS + 1)}
    reg = _Registry()
    hosts: dict = {}
    try:
        t0 = time.perf_counter()
        for nid, addr in members.items():
            hosts[nid] = NodeHost(NodeHostConfig(
                raft_address=addr,
                rtt_millisecond=10,
                nodehost_dir=os.path.join(workdir, label, f"nh{nid}"),
                raft_rpc_factory=lambda a: loopback_factory(a, reg),
                engine=EngineConfig(
                    kind="vector",
                    max_groups=REPLICAS * groups,
                    max_peers=4,
                    log_window=LOG_WINDOW,
                    inbox_depth=4,
                    max_entries_per_msg=64,
                    steps_per_sync=steps_per_sync,
                    shard_over_mesh=mesh,
                    share_scope=f"chip-smoke-{label}",
                ),
            ))
        core = hosts[1].engine.core
        _require(
            all(nh.engine.core is core for nh in hosts.values()),
            "the three NodeHosts do not share one engine core",
        )
        term = core._state.term
        state_platforms = sorted({d.platform for d in term.devices()})
        _say(label, "engine build", f"{time.perf_counter() - t0:.2f}s")
        _say(label, "state.term on", f"{state_platforms} x{len(term.devices())}"
             f" ({term.sharding})")
        _say(label, "overlap_decode (auto)", core._overlap)
        _require(
            state_platforms == [platform],
            f"engine state lives on {state_platforms}, not on {platform}",
        )
        if steps_per_sync == 1:
            _require(
                core._overlap == (platform != "cpu"),
                f"overlap_decode auto chose {core._overlap} on {platform}",
            )
        if mesh:
            _require(
                len(term.sharding.device_set) == n_dev,
                f"state spread over {len(term.sharding.device_set)} of "
                f"{n_dev} devices",
            )

        # elections (the first step compiles the kernel inside this phase)
        t0 = time.perf_counter()
        for nid in members:
            hosts[nid].start_clusters([
                (
                    dict(members), False, _SmokeSM,
                    Config(node_id=nid, cluster_id=c, election_rtt=300,
                           heartbeat_rtt=30),
                )
                for c in range(1, groups + 1)
            ])
        leaders = _wait_leaders(hosts, groups, ELECT_S)
        _say(label, "elections", f"{time.perf_counter() - t0:.2f}s "
             f"({groups} groups x {REPLICAS} replicas)")
        mark_warm = cw.snapshot()
        _say(label, "compile up to elected",
             _compiles(diff_compiles(mark_start, mark_warm)))

        # traffic: WAVES waves of WAVE seeded proposals per group, each
        # wave acknowledged before the next is sent
        ledger = _Ledger(
            groups, 2 * (WAVES * WAVE + TAIL_WAVE), [seed, steps_per_sync]
        )
        t0 = time.perf_counter()
        for _ in range(WAVES):
            _drive_wave(hosts, leaders, ledger, WAVE, TRAFFIC_S)
        acked = sum(ledger.acked.values())
        _say(label, "traffic", f"{time.perf_counter() - t0:.2f}s, {WAVES} "
             f"waves of {WAVE} per group, {acked} acknowledged, "
             f"{sum(ledger.indeterminate.values())} indeterminate")
        _require(
            acked == groups * WAVES * WAVE,
            f"acknowledged {acked} of {groups * WAVES * WAVE}",
        )

        t_reads, t_conv, n_exact = _read_back(hosts, leaders, ledger)
        _say(label, "read-back", f"{t_reads:.2f}s for {2 * groups} "
             f"linearizable reads (leader + follower host), "
             f"{n_exact}/{groups} groups held to the exact reference")
        _say(label, "replica convergence", f"{t_conv:.2f}s "
             f"(bound {CONVERGE_S:.0f}s), {REPLICAS * groups} replicas")

        # rebase at this shape: nothing short of 2**30 entries per group
        # triggers it from outside, so the flag the decode path would set
        # is set here; the loop thread then shifts every lane whose window
        # has moved past one log_window. A tail wave and a second
        # read-back prove device state and host mirrors still agree.
        t0 = time.perf_counter()
        core._rebase_due = True
        core._ready.set()
        core.drain()
        _require(not core._rebase_due, "the engine loop never ran the rebase")
        rebased = int((core._m_base > 0).sum())
        _require(rebased > 0, "rebase shifted no lane")
        _drive_wave(hosts, leaders, ledger, TAIL_WAVE, TRAFFIC_S)
        _read_back(hosts, leaders, ledger)
        _say(label, "rebase", f"{rebased} of {REPLICAS * groups} lanes shifted "
             f"by one window; tail wave of {TAIL_WAVE} per group and second "
             f"read-back in {time.perf_counter() - t0:.2f}s")
        _say(label, "reads retried after a timeout or drop",
             ledger.read_retries)

        mark_end = cw.snapshot()
        steady = diff_compiles(mark_warm, mark_end)
        ss = core.step_stats()
        _say(label, "engine steps", ss["steps"])
        _say(label, "msgs routed on device", ss["msgs_routed_device"])
        _say(label, "compiles after warm-up", f"{steady['total']} requests, "
             f"watched functions retraced: {steady['per_function'] or 'none'}")
        _say(label, "compile whole pass",
             _compiles(diff_compiles(mark_start, mark_end)))
        _say(label, "swallowed step exceptions", ss["loop_exceptions"])

        census = core.device_census()
        per_dev = _state_bytes_per_device(core._state)
        _say(label, "device_census",
             f"hbm_bytes_total={census['hbm_bytes_total']} "
             f"hbm_bytes_per_device={census['hbm_bytes_per_device']} "
             f"lanes_active={census['lanes_active']}")
        _say(label, "state bytes held per device", per_dev)
        if mesh:
            whole = sum(
                int(leaf.nbytes) for leaf in jax.tree.leaves(core._state)
            )
            _require(
                len(per_dev) == n_dev and max(per_dev.values()) < whole,
                f"a device holds the whole state: {per_dev} of {whole}",
            )
        stats = [d.memory_stats() for d in jax.devices()]
        peaks = [s.get("peak_bytes_in_use") if s else None for s in stats]
        _say(label, "peak_bytes_in_use",
             peaks if any(p is not None for p in peaks) else "not reported")
        _require(
            ss["loop_exceptions"] == 0,
            f"the engine loop swallowed {ss['loop_exceptions']} exceptions "
            "(tracebacks above)",
        )
    finally:
        for nh in hosts.values():
            nh.stop()
    return {
        "steps_per_sync": steps_per_sync,
        "acknowledged": sum(ledger.acked.values()),
        "engine_steps": ss["steps"],
        "msgs_routed_device": ss["msgs_routed_device"],
        "loop_exceptions": ss["loop_exceptions"],
    }


def run_smoke(groups: int, seed: int, mesh: bool, workdir: str) -> list:
    """Both passes in this process, sharing the compile cache."""
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        return [
            run_pass(label, k, groups, seed, mesh, workdir)
            for label, k in PASSES
        ]
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the proposal payload bytes")
    ap.add_argument("--mesh", action="store_true",
                    help="EngineConfig.shard_over_mesh=True over every "
                         "visible device (the four-chip run)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU rehearsal of the same scenario; prints "
                         "REHEARSAL and is never a pass")
    args = ap.parse_args(argv)

    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True)
    t0 = time.perf_counter()
    if args.rehearsal:
        pin_cpu(n_devices=REHEARSAL_MESH_DEVICES if args.mesh else None)
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    backend_init_s = time.perf_counter() - t0
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if not args.rehearsal and device["platform"] != "tpu":
        sys.exit(
            f"chip_smoke: platform is {device['platform']!r}, not 'tpu' — "
            "this smoke has no CPU fallback (use --rehearsal for a tiny "
            "CPU rehearsal, which is never a pass)"
        )
    tag = "REHEARSAL" if args.rehearsal else "smoke"
    _say(tag, "jax", jax.__version__)
    _say(tag, "platform", device["platform"])
    _say(tag, "device_kind", device["kind"])
    _say(tag, "device count", device["count"])
    _say(tag, "compile cache", cache_dir)
    _say(tag, "backend init", f"{backend_init_s:.2f}s")
    _say(tag, "seed", args.seed)

    groups = REHEARSAL_GROUPS if args.rehearsal else GROUPS
    t0 = time.perf_counter()
    run_smoke(groups, args.seed, args.mesh, _WORKDIR)
    _say(tag, "both passes", f"{time.perf_counter() - t0:.2f}s")
    faulthandler.cancel_dump_traceback_later()
    if args.rehearsal:
        print("REHEARSAL on cpu at a tiny size: not a chip result")
        print(json.dumps({"rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
