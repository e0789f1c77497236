"""Declarative analysis targets: WHICH code each rule family watches.

This file is the contract between the codebase's performance/concurrency
architecture and the rule engine:

  * the engine step loop's hot functions (pack -> dispatch -> fetch ->
    decode/fan-out -> save) where per-message Python, device syncs and
    unguarded telemetry are regressions (PR 1's columnar fan-out closed a
    340x kernel-vs-e2e gap; these lists keep it closed);
  * the jit-traced kernel code where Python control flow on traced values
    and per-call trace-signature variance silently recompile;
  * the declared LOCK HIERARCHY of the host runtime and the shared state
    each lock guards (the two PR 3 data races — snapshot index/data skew
    and the logdb compaction-vs-append lost update — were both
    "documented-shared-state written outside its lock" bugs).

Paths are package-relative ("engine/vector.py"); functions are qualnames
("VectorEngine._decode", nested defs as "make_step_fn.apply").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

VECTOR = "engine/vector.py"
NODE = "engine/node.py"
EXEC = "engine/execengine.py"
NODEHOST = "nodehost.py"
TRANSPORT = "transport/transport.py"
LOGDB = "storage/logdb.py"
KV = "storage/kv.py"
TRACE = "trace.py"
PROFILE = "profile.py"
MANAGED = "rsm/managed.py"
KERNEL = "ops/kernel.py"
STATE = "ops/state.py"
SERVING_ADMISSION = "serving/admission.py"
SERVING_BACKPRESSURE = "serving/backpressure.py"
SERVING_FRONT = "serving/front.py"
SERVING_SESSIONS = "serving/sessions.py"
SERVING_PLACEMENT = "serving/placement.py"
CHUNKS = "transport/chunks.py"
FAULTS = "faults.py"

FnKey = Tuple[str, str]  # (relpath, qualname)


@dataclass
class LockSpec:
    """One declared lock: its rank in the acquisition hierarchy (SMALLER =
    must be taken FIRST / outermost) and a one-line role description."""

    cls: str  # owning class name
    attr: str  # attribute name on instances of cls
    rank: int
    doc: str = ""


@dataclass
class Targets:
    """The full target configuration handed to every rule."""

    # ---- hot-path families (PR 1 columnar fan-out) -----------------------
    hot_functions: Set[FnKey] = field(default_factory=set)
    hot_lock_functions: Set[FnKey] = field(default_factory=set)
    hot_telemetry_functions: Set[FnKey] = field(default_factory=set)
    hot_trace_functions: Set[FnKey] = field(default_factory=set)

    # ---- device-sync family ---------------------------------------------
    # the ONE blessed device->host transfer seam on the step path
    blessed_device_get: Set[FnKey] = field(default_factory=set)
    # dotted prefixes that name device-resident values in hot functions
    device_roots: Set[str] = field(default_factory=set)

    # ---- recompilation-hazard family ------------------------------------
    # modules whose top-level functions all run under jit (minus exempt)
    traced_modules: Set[str] = field(default_factory=set)
    traced_exempt: Set[str] = field(default_factory=set)  # root qualnames
    traced_functions: Set[FnKey] = field(default_factory=set)  # extras
    # parameter names that are static under jit everywhere they appear
    static_param_names: Set[str] = field(default_factory=set)

    # ---- lock-discipline family -----------------------------------------
    locks: List[LockSpec] = field(default_factory=list)
    # variable-name -> class hints for non-self lock expressions (sh._mu)
    lock_var_hints: Dict[str, str] = field(default_factory=dict)
    # relpath -> {class -> {field -> guarding lock attr}}
    guarded_state: Dict[str, Dict[str, Dict[str, str]]] = field(
        default_factory=dict
    )
    # method-name suffix asserting the caller already holds the lock
    locked_suffix: str = "_locked"

    # ---- interprocedural families (ISSUE 20) ----------------------------
    # (cls, attr) of locks on the engine step / per-node protocol path: a
    # blocking call (fsync, .result(), sleep, queue wait) TRANSITIVELY
    # reachable while one is held stalls the step loop for every lane
    # (locks/blocking-under-hot-lock)
    hot_locks: Set[Tuple[str, str]] = field(default_factory=set)
    # rule ids / families whose allow() pragmas are exempt from
    # pragma/unused — rules gated off by configuration (empty
    # device_roots, a family not enabled in this deployment) legitimately
    # suppress zero findings
    unused_pragma_allowlist: Set[str] = field(default_factory=set)

    # -- queries -----------------------------------------------------------
    def is_hot(self, key: FnKey) -> bool:
        return key in self.hot_functions

    def is_hot_lock(self, key: FnKey) -> bool:
        return key in self.hot_lock_functions or key in self.hot_functions

    def is_traced(self, key: FnKey) -> bool:
        relpath, qualname = key
        if key in self.traced_functions:
            return True
        return (
            relpath in self.traced_modules
            and qualname.split(".")[0] not in self.traced_exempt
        )

    def is_hot_lock_spec(self, spec: Optional["LockSpec"]) -> bool:
        return spec is not None and (spec.cls, spec.attr) in self.hot_locks

    def lock_rank(self, cls: Optional[str], attr: str, module=None):
        """Resolve (class, attr) -> LockSpec; subclass names resolve
        through the module's base map when one is provided."""
        for spec in self.locks:
            if spec.attr != attr:
                continue
            if cls is None or spec.cls == cls:
                return spec
            if module is not None and module.is_subclass_of(cls, spec.cls):
                return spec
        return None

    def all_function_targets(self):
        """(relpath, qualname, why) for config-drift detection."""
        for name in (
            "hot_functions",
            "hot_lock_functions",
            "hot_telemetry_functions",
            "hot_trace_functions",
            "blessed_device_get",
            "traced_functions",
        ):
            for relpath, qualname in sorted(getattr(self, name)):
                yield relpath, qualname, name


def _default_targets() -> Targets:
    # the step hot path: every function here runs once per engine step on
    # the loop thread (pack -> dispatch -> fetch -> decode/fan-out -> save)
    hot = {
        (VECTOR, "VectorEngine._run_once"),
        (VECTOR, "VectorEngine._pack"),
        (VECTOR, "VectorEngine._pack_wire"),
        (VECTOR, "VectorEngine._stage_row"),
        (VECTOR, "VectorEngine._flush_staged_rows"),
        (VECTOR, "VectorEngine._fetch_output"),
        (VECTOR, "VectorEngine._fetch_super"),
        (VECTOR, "VectorEngine._decode"),
        # the decode phase bodies (split out of _decode so the K-step
        # super-step path orchestrates the same code) and the multi-step
        # super-step machinery — all run once per engine step / inner step
        (VECTOR, "VectorEngine._decode_super"),
        (VECTOR, "VectorEngine._decode_place"),
        (VECTOR, "VectorEngine._refresh_mirrors"),
        (VECTOR, "VectorEngine._decode_send_rep"),
        (VECTOR, "VectorEngine._commit_saves"),
        (VECTOR, "VectorEngine._decode_send_post"),
        (VECTOR, "VectorEngine._decode_apply"),
        (VECTOR, "VectorEngine._decode_reads"),
        (VECTOR, "VectorEngine._routed_rep_plan"),
        (VECTOR, "VectorEngine._place_routed_reps"),
        (VECTOR, "VectorEngine._mask_routed"),
        (VECTOR, "VectorEngine._dispatch_sends"),
        (VECTOR, "VectorEngine._save_updates"),
        (VECTOR, "VectorEngine.try_local_deliver_many"),
        (VECTOR, "gather_replicate_sends"),
        (VECTOR, "gather_post_sends"),
        (VECTOR, "gather_resp_sends"),
        (VECTOR, "build_save_updates"),
    }
    # the transport send hot path: one lock/breaker-check per TARGET
    # BATCH, never per message
    hot_lock = {
        (TRANSPORT, "Transport.send_many"),
        (TRANSPORT, "_SendQueue.put_many"),
    }
    hot_telemetry = set(hot) | set(hot_lock) | {
        (TRANSPORT, "_SendQueue._admit_locked"),
        # the step-phase profiler's stamping seams (PR 6 attribution
        # plane): Sample.record + the phase-plane fan-out run once per
        # stage per step — they must stay inside the `if self.sampling`
        # gate or every step pays histogram/recorder work
        (TRACE, "Profiler.end"),
        (TRACE, "Profiler.begin"),
        (TRACE, "Profiler.add"),
        (PROFILE, "PhasePlane.on_phase"),
        # what `save` is made of (ISSUE 37, part 2): the timed wave and the
        # storage doors under it, once a launch or a shard write, on
        # sampled iterations only
        (VECTOR, "VectorEngine._commit_saves"),
        (VECTOR, "VectorEngine._book_wave"),
        (LOGDB, "_Shard.save_raft_state_deferred"),
        # what the wave's replicas share (ISSUE 38): two counts a run
        # of entries, on sampled iterations only
        (LOGDB, "_Shard._save_entries"),
        (KV, "WalKV.commit_write_batch_deferred"),
        (KV, "sync_all"),
        # the progress watch (ISSUE 37), once a launch at the head of
        # `place` (and _maintain's catch-up sweep beside it): whole-G
        # numpy, and three folds on sampled iterations. What a
        # stall's crossing leaves (_report_stalls: events and one
        # warning, sampled or not) is anomaly-only and stays outside.
        (VECTOR, "VectorEngine._maintain"),
        (VECTOR, "VectorEngine._watch_progress"),
        (VECTOR, "VectorEngine._sweep_progress"),
        (VECTOR, "VectorEngine._sweep_peers"),
        (VECTOR, "VectorEngine._level_applied"),
    }
    # request entry points that mint trace ids + the decode/send phases
    # that propagate them: unsampled requests stay allocation/event-free
    hot_trace = {
        (NODE, "Node.propose"),
        (NODE, "Node.propose_batch"),
        (NODE, "Node.propose_batch_async"),
        (NODE, "Node.apply_raft_update"),
        (VECTOR, "gather_replicate_sends"),
        (VECTOR, "gather_resp_sends"),
        (VECTOR, "VectorEngine._pack_wire"),
        (VECTOR, "VectorEngine._decode"),
        # the quorum_commit stamp moved into the split-out apply phase
        (VECTOR, "VectorEngine._decode_apply"),
        (TRANSPORT, "Transport.send_many"),
    }
    # the declared lock hierarchy, outermost first. Acquisition must go
    # DOWN this table; taking an equal-or-outer lock while holding an
    # inner one is an ordering violation.
    locks = [
        LockSpec(
            "ManagedStateMachine", "_mu", 10,
            "SM serialization (exclusive()): update+applied-advance and "
            "snapshot index+data each form one critical section (PR 3 "
            "snapshot skew fix)",
        ),
        LockSpec(
            "_Shard", "_wmu", 20,
            "logdb shard writer lock: append vs compaction boundary-batch "
            "rewrite (PR 3 lost-update fix)",
        ),
        LockSpec(
            "_Shard", "_mu", 30,
            "logdb shard cache lock (state/max-index/last-batch caches)",
        ),
        LockSpec(
            "Chunks", "_mu", 36,
            "inbound snapshot-stream tracker (resume fences, per-stream "
            "progress, stream counters); held across finalize's "
            "InstallSnapshot handoff and the abort notify, both of which "
            "take NodeHost._nodes_mu inside it",
        ),
        LockSpec(
            "PlacementPlane", "_mu", 35,
            "placement plan/active-migration table + migration ledger; "
            "outer of NodeHost._nodes_mu (the load fold and every "
            "migration step call into the host's request API, which "
            "takes _nodes_mu inside)",
        ),
        LockSpec(
            "SessionManager", "_mu", 37,
            "session pool + lifecycle counters; outer of "
            "NodeHost._nodes_mu for the same reason (checkout never "
            "holds it across a propose, but the rank keeps any future "
            "nesting legal in one direction only)",
        ),
        LockSpec(
            "NodeHost", "_nodes_mu", 38,
            "node registry + launch-spec table (the restart plane: "
            "stop/crash/restart_cluster all transition through it); held "
            "briefly on every inbound batch and API lookup, released "
            "before any engine or node lock is taken",
        ),
        LockSpec(
            "Transport", "_mu", 40,
            "transport registry lock (queue/breaker maps)",
        ),
        LockSpec(
            "Node", "_mu", 41,
            "per-node protocol lock (step vs API surface); API paths take "
            "it before marking the engine dirty",
        ),
        LockSpec(
            "VectorEngine", "_lanes_mu", 42, "engine lane registry",
        ),
        LockSpec(
            "VectorEngine", "_dirty_mu", 44,
            "engine dirty-set / pending-tick state",
        ),
        LockSpec(
            "VectorEngine", "_snap_status_mu", 44,
            "engine snapshot-completion set",
        ),
        LockSpec(
            "ServingFront", "_mu", 45,
            "serving-front tenant queue table (admitted-but-unsubmitted "
            "bulk ops); released before propose_batch is called, never "
            "held across engine or node locks",
        ),
        LockSpec(
            "AdmissionController", "_mu", 46,
            "admission tenant registry + admit/shed ledger",
        ),
        LockSpec(
            "SaturationMonitor", "_mu", 47,
            "cached saturation score + last signal sample",
        ),
        LockSpec(
            "_SendQueue", "_cv", 50,
            "send-queue condition (urgent/bulk deques + admission counters)",
        ),
        LockSpec(
            "_Breaker", "_mu", 50, "circuit-breaker state",
        ),
        LockSpec(
            "TokenBucket", "_mu", 55,
            "token-bucket balance/refill-time pair (leaf: one take() is "
            "one atomic refill+spend)",
        ),
        LockSpec(
            "_BarrierStats", "_mu", 60,
            "WAL barrier-pressure gauge (leaf: taken inside the fsync "
            "seam with shard locks already held)",
        ),
        LockSpec(
            "MmapRing", "_mu", 60,
            "flight-ring slot seal (leaf: taken with no other lock held)",
        ),
        LockSpec(
            "PhasePlane", "_mu", 60,
            "phase-histogram table (leaf: dict probe only; the Histogram "
            "observation itself happens outside it)",
        ),
        LockSpec(
            "SyncAudit", "_mu", 60,
            "device-sync site-attribution table (leaf)",
        ),
        LockSpec(
            "ClockPlane", "_mu", 60,
            "per-host clock-fault table (ISSUE 17: skew/drift/jump "
            "anchors); leaf — clock_fn closures read it from every tick "
            "worker with no other lock held, mutations come from the "
            "chaos scheduler thread",
        ),
        LockSpec(
            "CompileWatch", "_mu", 60,
            "compile-event counters + registered-function table (leaf)",
        ),
        LockSpec(
            "DeviceCensus", "_mu", 60,
            "HBM census plane table (leaf: written once at engine init, "
            "read by the 1/s export paths)",
        ),
        LockSpec(
            "HistorySampler", "_mu", 60,
            "telemetry-history ring handle (leaf: the sampler thread "
            "copies the ref out under it and writes the ring outside; "
            "the sample itself only reads zero-sync stat exports, never "
            "another lock)",
        ),
    ]
    guarded_state = {
        TRANSPORT: {
            "_SendQueue": {
                "_urgent": "_cv",
                "_bulk": "_cv",
                "_closed": "_cv",
                "evicted_bulk": "_cv",
                "dropped_bulk": "_cv",
                "dropped_urgent": "_cv",
            },
            "_Breaker": {
                "_state": "_mu",
                "_fails": "_mu",
                "_nominal": "_mu",
                "_cooldown": "_mu",
                "_opened_at": "_mu",
                "_probe_inflight": "_mu",
                "opens": "_mu",
                "probes": "_mu",
                "probe_failures": "_mu",
            },
        },
        LOGDB: {
            "_Shard": {
                "_state_cache": "_mu",
                "_max_index_cache": "_mu",
                "_batch_cache": "_mu",
            },
        },
        TRACE: {
            "MmapRing": {"_seq": "_mu", "_mm": "_mu"},
        },
        PROFILE: {
            "PhasePlane": {"_hists": "_mu"},
            "SyncAudit": {"_out": "_mu"},
            "CompileWatch": {"_fns": "_mu"},
            "DeviceCensus": {"_planes": "_mu"},
            # the history sampler's ring handle swaps on stop(); the
            # plain-int sample/error counters are sampler-thread-only
            "HistorySampler": {"_ring": "_mu"},
        },
        MANAGED: {
            "ManagedStateMachine": {"_destroyed": "_mu"},
        },
        VECTOR: {
            "VectorEngine": {
                "_dirty": "_dirty_mu",
                "_gc_set": "_dirty_mu",
                "_pending_ticks": "_dirty_mu",
                "_snap_status": "_snap_status_mu",
                "_lanes": "_lanes_mu",
                # the restart plane's lane recycling (ISSUE 7): the free
                # list, g->lane table and message route are read by the
                # loop/delivery hot paths and mutated by add/remove/
                # _deactivate — a write outside _lanes_mu is exactly the
                # double-free / stale-route class of restart bug
                "_free": "_lanes_mu",
                "_lane_by_g": "_lanes_mu",
                "_route": "_lanes_mu",
                # the clock-fault plane (ISSUE 17): per-host suspect
                # deadlines are written by tick-worker threads reporting
                # anomalies and drained by the loop thread — a write
                # outside _dirty_mu is a lost-revocation (stale lease
                # read) class of bug. The lease mirrors themselves
                # (_m_lease_ok, _lease_local, _lease_fb) are loop-thread
                # only, like every other _m_* mirror.
                "_clock_suspect": "_dirty_mu",
            },
        },
        # the clock-fault plane (ISSUE 17): each host's [anchor_real,
        # anchor_fault, rate] triple is read by that host's tick worker
        # on every tick and rewritten by the chaos scheduler — a write
        # outside _mu tears the re-anchor continuity rule and turns a
        # drift change into a spurious step jump
        FAULTS: {
            "ClockPlane": {"_hosts": "_mu"},
        },
        NODEHOST: {
            "NodeHost": {
                "_nodes": "_nodes_mu",
                "_launch_specs": "_nodes_mu",
                # live-migration tag set (serving/placement.py): read by
                # the inbound chunk tracker on every stream begin
                "_migrating": "_nodes_mu",
            },
        },
        # the serving overload plane (ISSUE 8): admit/shed decisions and
        # the saturation cache are read on every client request from many
        # threads — a write outside the declared lock is exactly the
        # lost-increment / torn-decision class of admission bug
        KV: {
            "_BarrierStats": {
                "ewma_s": "_mu",
                "last_s": "_mu",
                "last_wave_s": "_mu",
                "inflight": "_mu",
                "barriers": "_mu",
            },
        },
        SERVING_ADMISSION: {
            "AdmissionController": {"_tenants": "_mu"},
            "TokenBucket": {"tokens": "_mu", "_t": "_mu"},
        },
        SERVING_BACKPRESSURE: {
            "SaturationMonitor": {
                "_cached": "_mu",
                "_cached_at": "_mu",
                "_last_signals": "_mu",
            },
        },
        SERVING_FRONT: {
            "ServingFront": {"_queues": "_mu"},
        },
        # the millions-of-users plane (ISSUE 14): the session pools and
        # the migration ledger are mutated from client threads, the
        # placement pacer and teardown — a write outside the declared
        # lock is a lost-session / double-migration class of bug
        SERVING_SESSIONS: {
            "SessionManager": {
                "_pools": "_mu",
                "_counters": "_mu",
                "_dead": "_mu",
            },
        },
        SERVING_PLACEMENT: {
            "PlacementPlane": {
                "_active": "_mu",
                "_counters": "_mu",
                "_last_lanes": "_mu",
                "_abort": "_mu",
            },
        },
        # the streamed-install plane (ISSUE 13): the stream tracker and
        # its resume/abort counters are mutated from transport delivery
        # threads and the tick sweeper — a write outside _mu is exactly
        # the torn-progress / double-count class of resume bug
        CHUNKS: {
            "Chunks": {
                "_tracked": "_mu",
                "_tick": "_mu",
                "_resumed_streams": "_mu",
                "_skipped_chunks": "_mu",
                "_aborted_streams": "_mu",
                "_completed_streams": "_mu",
                "_migration_streams": "_mu",
            },
        },
    }
    return Targets(
        hot_functions=hot,
        hot_lock_functions=hot_lock,
        hot_telemetry_functions=hot_telemetry,
        hot_trace_functions=hot_trace,
        blessed_device_get={
            (VECTOR, "VectorEngine._fetch_output"),
            # the multi-step engine's once-per-K-steps consolidated
            # transfer (mirrors profile.SyncAudit.BLESSED)
            (VECTOR, "VectorEngine._fetch_super"),
        },
        device_roots={"self._state"},
        traced_modules={KERNEL},
        traced_exempt={
            "make_step_fn",
            "make_multi_step_fn",
            # the sharded twin: shard_map + jit factory (same contract)
            "make_sharded_multi_step_fn",
        },
        traced_functions={(VECTOR, "_make_activate_fn.apply")},
        # `steps` is the super-step scan length: a compile-time constant
        # baked into the executable by make_multi_step_fn (a traced K
        # would rebuild the scan per value — the retrace family's
        # recompile-hazard meta-test covers exactly this). The sharded
        # factory additionally bakes the mesh and the cross-shard axis
        # (axis_name/n_shards): all compile-time topology, never traced.
        static_param_names={
            "cfg", "donate", "steps", "mesh", "axis_name", "n_shards",
        },
        locks=locks,
        lock_var_hints={
            "node": "Node",
            "sh": "_Shard",
            "sq": "_SendQueue",
            "breaker": "_Breaker",
        },
        guarded_state=guarded_state,
        # blocking work must never be reachable under these: the engine
        # lane/dirty/snap registries gate the step loop itself, and
        # Node._mu gates every protocol step and API call on that node.
        # (_SendQueue._cv is deliberately NOT here: waiting on the send
        # condition IS its job, and the sender thread owns that latency.)
        hot_locks={
            ("VectorEngine", "_lanes_mu"),
            ("VectorEngine", "_dirty_mu"),
            ("VectorEngine", "_snap_status_mu"),
            ("Node", "_mu"),
        },
    )


DEFAULT_TARGETS = _default_targets()

__all__ = [
    "DEFAULT_TARGETS",
    "FnKey",
    "LockSpec",
    "Targets",
    "CHUNKS",
    "FAULTS",
    "KERNEL",
    "KV",
    "LOGDB",
    "MANAGED",
    "NODE",
    "NODEHOST",
    "PROFILE",
    "SERVING_ADMISSION",
    "SERVING_BACKPRESSURE",
    "SERVING_FRONT",
    "SERVING_PLACEMENT",
    "SERVING_SESSIONS",
    "STATE",
    "TRACE",
    "TRANSPORT",
    "VECTOR",
]
