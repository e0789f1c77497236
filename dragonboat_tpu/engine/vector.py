"""VectorEngine: the device-kernel-backed execution engine.

The scalar ExecEngine advances each group with a per-group Peer inside
worker threads (cf. reference execengine.go:474-560). This engine is the
TPU-first replacement: ALL groups hosted by a NodeHost live as lanes of one
(G, P) tensor state (ops/state.RaftTensors) and advance together in one
compiled kernel step (ops/kernel.step_batch). The host side of the engine

  1. packs per-group events (wire messages, proposals, reads, config
     changes, transfers) into the device Inbox,
  2. runs the jitted step,
  3. fans the StepOutput out with the reference's ordering invariants
     (cf. execengine.go:474-560): Replicate messages leave BEFORE the
     fsync; hard state + new entries are persisted in ONE batched
     save_raft_state call for every lane; responses (vote grants,
     ReplicateResp) leave only after persistence; committed entries are
     handed to the RSM task workers after persistence.

The host half is vectorized to match the device half: work is driven by a
dirty set (only lanes with pending host events are touched in Python),
ticks are a single engine-global counter folded into one device tick
array (replacing per-lane LocalTick messages, cf. node.go:1152-1159),
per-lane protocol mirrors live in whole-G numpy arrays refreshed from one
`jax.device_get` per step, and lane activation is batched into one
scatter per state field instead of per-lane device dispatches. Idle lanes
cost zero host work per step.

Columnar host dataflow (the step loop's host half stays O(active lanes),
never O(messages) Python):

  pack    - inbox rows are STAGED as column lists (_stage_row) and land in
            the numpy planes as one fancy-indexed scatter per plane
            (_flush_staged_rows), not ten scalar stores per message;
            per-lane mirror reads are gathered once per step as columns.
  fetch   - ONE consolidated device->host transfer of the StepOutput per
            step (_fetch_output, shared by the overlap/non-overlap paths).
            The planes ship together because on every backend the batched
            transfer beats per-plane masked fetches: the arrays are small
            (G- and GxP-sized) and per-dispatch overhead dominates.
  fan-out - each decode phase derives its (g, p)/(g, k) work list from one
            np.nonzero and gathers every needed field as whole columns
            (`arr[gs, ps].tolist()`), so the per-message Python is just
            tuple unpacking + Message construction at the transport
            boundary; batches leave through Node._send_messages ->
            NodeHost._send_messages -> VectorEngine.try_local_deliver_many
            (one queue lock + one wake per destination lane) or
            Transport.send_many (grouped per target address).
  save    - every lane's per-step save is ONE multi-group write wave:
            a single write-batch per touched logdb shard with the
            durability barrier deferred, then one parallel sync over all
            touched WALs (storage/logdb.save_raft_state_deferred +
            storage/kv.sync_all), so a step pays max(fsync) not sum.

The kernel advances all groups in one compiled step, and the host fans
its output out in whole-plane numpy instead of per-(group, peer) Python.

Payload bytes never touch the device: the kernel works on (index, term,
is_cc) metadata while the engine keeps an arena of Entry objects keyed by
(lane, real index). The kernel reports where each proposal/replicate landed
(StepOutput.prop_base / rep_base) so the host places payloads at the
device-assigned indexes without guessing.

Node identity on device is the peer slot (0..P-1). The canonical mapping is
rank-in-sorted-order of the member node ids, recomputed whenever membership
changes — a pure function of the (replicated) membership image, so every
replica derives the same mapping at the same applied index. The wire always
carries real node ids and real (un-rebased) indexes.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config, NodeHostConfig
from ..core.peer import PeerAddress, encode_config_change
from ..core.raft import _make_metadata_entries, _make_witness_snapshot
from ..core.rate import ENTRY_OVERHEAD_BYTES
from ..logger import get_logger
from ..ops.kernel import (
    launch_in_slabs,
    launch_out_slabs,
    make_packed_multi_step_fn,
    make_sharded_multi_step_fn,
    make_step_fn,
)
from ..ops.state import (
    CTR,
    CTR_NAMES,
    MSG,
    NEED_SNAPSHOT,
    ROLE,
    RSTATE,
    SEND_HEARTBEAT,
    SEND_REPLICATE,
    SEND_TIMEOUT_NOW,
    SEND_VOTE_REQ,
    Inbox,
    KernelConfig,
    RaftTensors,
    RoutePlan,
    StepOutput,
    init_state,
    lane_seed,
    make_empty_inbox,
    rebase,
)
from ..profile import (
    HOT_LANE_COUNTERS,
    DeviceCensus,
    compile_watch,
    note_engine_steps,
    note_seam_sync,
    phase_plane,
)
from ..requests import LogicalClock
from ..settings import soft
from ..storage.kv import sync_all as _kv_sync_all
from ..storage.kv import close_bodies as _kv_close_bodies
from ..storage.kv import close_wave as _kv_close_wave
from ..storage.kv import open_bodies as _kv_open_bodies
from ..storage.kv import open_wave as _kv_open_wave
from ..storage.logdb import RecordBodies
from ..trace import LatencySampler, Profiler, flight_recorder
from ..types import (
    ConfigChange,
    ConfigChangeType,
    Entry,
    EntryType,
    Message,
    MessageType,
    ReadyToRead,
    Snapshot,
    State,
    SystemCtx,
    Update,
)
from ..rsm.manager import From as OffloadFrom
from .execengine import WorkReady
from .fairness import FairnessWatchdog
from .node import Node

_plog = get_logger("vectorengine")

# One sharded collective program in flight per process: the K>1 mesh
# kernel contains a cross-shard all-gather, and concurrent launches from
# co-hosted engines interleave their rendezvous on the shared per-device
# executors — the CPU backend stalls its participant threads outright.
# Production runs one engine per host, so serializing launches costs
# nothing there; multi-NodeHost-in-process tests pay a fair round-robin.
# K=1 sharded and every unsharded path have no collectives and never
# take this lock.
_MESH_LAUNCH_MU = threading.Lock()


# the request sampler is never denser than 1 in this many, whatever the
# stage profiler's ratio (see VectorEngine.request_sampler)
REQUEST_SAMPLE_FLOOR = 8


MT = MessageType

# device index value guard: rebase once any lane's last index crosses this
_REBASE_THRESHOLD = 1 << 30

# ctx encoding over TWO int32 device planes: the low plane carries
# (origin_slot + 1) << 24 | ctx.low[0:24], the high plane ctx.low[24:55].
# 55 bits of the node's sequential read counter plus the origin slot are
# collision-free for any realistic pending window (the reference carries a
# 128-bit random SystemCtx in the message envelope, requests.go:365-381;
# the origin slot rides inside the hint so a leader can route confirmed
# forwarded reads back to the requesting replica, raft.go:1871-1898)
_CTX_LOW_MASK = 0xFFFFFF


def _enc_ctx(origin_slot: int, low: int) -> tuple:
    return (
        ((origin_slot + 1) << 24) | (low & _CTX_LOW_MASK),
        (low >> 24) & 0x7FFFFFFF,
    )


def _ctx_origin(enc_lo: int) -> int:
    return (enc_lo >> 24) - 1


import functools


@functools.lru_cache(maxsize=None)
def _make_activate_fn(cfg: KernelConfig, n: int):
    """Jitted bulk lane activation: scatter n lanes' bring-up values into
    the device state in ONE compiled call. Batches are padded to a few
    fixed bucket sizes (powers of 4) so each (cfg, n) compiles once —
    eagerly dispatched `.at[g].set` chains compile a fresh scatter per
    batch shape, which at fleet bring-up dominated wall clock."""
    P, W, R = cfg.peers, cfg.log_window, cfg.readindex_depth

    def apply(s: RaftTensors, gi, v):
        zi = jnp.zeros((n,), jnp.int32)
        zb = jnp.zeros((n,), bool)
        zip_ = jnp.zeros((n, P), jnp.int32)
        zbp = jnp.zeros((n, P), bool)
        zir = jnp.zeros((n, R), jnp.int32)
        return s._replace(
            active=s.active.at[gi].set(True),
            self_slot=s.self_slot.at[gi].set(v["self_slot"]),
            member=s.member.at[gi].set(v["member"]),
            voting=s.voting.at[gi].set(v["voting"]),
            observer=s.observer.at[gi].set(v["observer"]),
            witness=s.witness.at[gi].set(v["witness"]),
            term=s.term.at[gi].set(v["term"]),
            vote=s.vote.at[gi].set(v["vote"]),
            role=s.role.at[gi].set(v["role"]),
            leader=s.leader.at[gi].set(zi),
            tick_count=s.tick_count.at[gi].set(zi),
            election_tick=s.election_tick.at[gi].set(zi),
            heartbeat_tick=s.heartbeat_tick.at[gi].set(zi),
            election_timeout=s.election_timeout.at[gi].set(
                v["election_timeout"]
            ),
            heartbeat_timeout=s.heartbeat_timeout.at[gi].set(
                v["heartbeat_timeout"]
            ),
            rand_timeout=s.rand_timeout.at[gi].set(v["rand_timeout"]),
            check_quorum=s.check_quorum.at[gi].set(v["check_quorum"]),
            prevote_on=s.prevote_on.at[gi].set(v["prevote_on"]),
            lease_on=s.lease_on.at[gi].set(v["lease_on"]),
            lease_margin=s.lease_margin.at[gi].set(v["lease_margin"]),
            # a reused lane must not inherit its predecessor's lease
            lease_until=s.lease_until.at[gi].set(zi),
            hb_round_tick=s.hb_round_tick.at[gi].set(zi),
            hb_ack_bits=s.hb_ack_bits.at[gi].set(zi),
            first_index=s.first_index.at[gi].set(v["first_index"]),
            marker_term=s.marker_term.at[gi].set(v["marker_term"]),
            last_index=s.last_index.at[gi].set(v["last_index"]),
            committed=s.committed.at[gi].set(v["committed"]),
            processed=s.processed.at[gi].set(v["processed"]),
            applied=s.applied.at[gi].set(v["applied"]),
            unsaved_from=s.unsaved_from.at[gi].set(v["unsaved_from"]),
            log_term=s.log_term.at[gi].set(v["log_term"]),
            log_is_cc=s.log_is_cc.at[gi].set(v["log_is_cc"]),
            match=s.match.at[gi].set(zip_),
            next=s.next.at[gi].set(
                jnp.broadcast_to(v["next"][:, None], (n, P))
            ),
            rstate=s.rstate.at[gi].set(
                jnp.full((n, P), RSTATE.RETRY, jnp.int32)
            ),
            ract=s.ract.at[gi].set(zbp),
            snap_sent=s.snap_sent.at[gi].set(zip_),
            vresp=s.vresp.at[gi].set(zbp),
            vgrant=s.vgrant.at[gi].set(zbp),
            transfer_to=s.transfer_to.at[gi].set(zi),
            transfer_flag=s.transfer_flag.at[gi].set(zb),
            pending_cc=s.pending_cc.at[gi].set(zb),
            quiesce_on=s.quiesce_on.at[gi].set(v["quiesce_on"]),
            quiesce_threshold=s.quiesce_threshold.at[gi].set(
                v["quiesce_threshold"]
            ),
            quiesced=s.quiesced.at[gi].set(zb),
            idle_ticks=s.idle_ticks.at[gi].set(zi),
            ri_ctx=s.ri_ctx.at[gi].set(zir),
            ri_index=s.ri_index.at[gi].set(zir),
            ri_acks=s.ri_acks.at[gi].set(zir),
            ri_count=s.ri_count.at[gi].set(zi),
        )

    return compile_watch().register(
        f"activate[n{n}]", jax.jit(apply, donate_argnums=(0,))
    )


@functools.lru_cache(maxsize=None)
def _make_patch_fn(cfg: KernelConfig):
    """Jitted reconcile of the lanes a config change, a snapshot restore
    or a stop touched, ALL of them in one fixed-shape call an iteration
    of the loop: whole-G masked updates, compiled once. The per-peer
    planes are re-ranked ON DEVICE from `src` (the old slot now standing
    at each slot, -1 for a new peer), so the loop reads nothing back. A
    lane reconciled by `.at[g].set` chains cost ten blocking reads and
    twenty eager dispatches a config change a replica; at a few replica
    moves a second over a fleet that was the whole loop."""
    P = cfg.peers

    def apply(s: RaftTensors, v):
        m, rs, src = v["remap"], v["restore"], v["src"]
        has = src >= 0
        idx = jnp.maximum(src, 0)
        mp, rsp = m[:, None], rs[:, None]
        cols = jnp.arange(1, P + 1, dtype=jnp.int32)[None, :]

        def perm(x, default):
            moved = jnp.where(has, jnp.take_along_axis(x, idx, axis=1), default)
            return jnp.where(mp, moved, x)

        def ref(x):
            # slot+1 encoded references (leader/vote/transfer)
            new = jnp.max(
                jnp.where(has & (src == (x - 1)[:, None]), cols, 0), axis=1
            )
            return jnp.where(m, jnp.where(x > 0, new, 0), x).astype(x.dtype)

        def put(x, value, mask=rs):
            mask = mask if x.ndim == 1 else mask[:, None]
            return jnp.where(mask, value, x).astype(x.dtype)

        nxt = jnp.maximum(perm(s.next, (s.last_index + 1)[:, None]), 1)
        return s._replace(
            active=s.active & ~v["deact"],
            pending_cc=s.pending_cc & ~v["cc_clear"],
            member=put(s.member, v["member"], m),
            voting=put(s.voting, v["voting"], m),
            observer=put(s.observer, v["observer"], m),
            witness=put(s.witness, v["witness"], m),
            self_slot=put(s.self_slot, v["self_slot"], m),
            role=put(s.role, ROLE.FOLLOWER, v["to_follower"]),
            leader=ref(s.leader),
            vote=ref(s.vote),
            transfer_to=ref(s.transfer_to),
            match=put(perm(s.match, 0), 0),
            next=put(jnp.where(mp, nxt, s.next), 1),
            rstate=put(perm(s.rstate, RSTATE.RETRY), RSTATE.RETRY),
            ract=perm(s.ract, False),
            snap_sent=put(perm(s.snap_sent, 0), 0),
            vresp=perm(s.vresp, False),
            vgrant=perm(s.vgrant, False),
            # ack bitmasks are slot-indexed: clear and let heartbeats
            # re-confirm (membership changes are rare a lane)
            ri_acks=put(s.ri_acks, 0, m),
            # a lane rebuilt at a snapshot point (raft.go:439-517 restore)
            term=put(s.term, jnp.maximum(s.term, v["term"])),
            first_index=put(s.first_index, 1),
            marker_term=put(s.marker_term, v["marker_term"]),
            last_index=put(s.last_index, 0),
            committed=put(s.committed, 0),
            processed=put(s.processed, 0),
            applied=put(s.applied, 0),
            unsaved_from=put(s.unsaved_from, 1),
            log_term=put(s.log_term, 0),
            log_is_cc=put(s.log_is_cc, False),
            ri_ctx=put(s.ri_ctx, 0),
            ri_index=put(s.ri_index, 0),
            ri_count=put(s.ri_count, 0),
            # an InstallSnapshot is word from the leader (raft.go
            # handleFollowerInstallSnapshot: leaderIsAvailable resets the
            # election tick). The kernel never saw that message, so the
            # timer is reset here: a joiner that carried an expired timer
            # into its first membership campaigned at once and deposed
            # the leader that was bringing it up
            election_tick=put(s.election_tick, 0),
        )

    return compile_watch().register(
        "reconcile_lanes", jax.jit(apply, donate_argnums=(0,))
    )


class _SharedClock(LogicalClock):
    """One logical clock shared by every lane of a VectorEngine. The engine
    loop gates the pending-queue gc pass with ONE should_gc() check per
    window (see _run_gc) — Pending*.gc() itself sweeps unconditionally."""


class VectorNode(Node):
    """A Node whose protocol core is a lane of the shared device state.

    The public request surface (propose/read/config-change/snapshot/
    transfer), the RSM manager, the snapshotter drivers and the pending
    notification machinery are all inherited; only the protocol stepping is
    different — there is no Peer, the VectorEngine advances every lane in
    one kernel call. Protocol status (leader/term/role/commit) is read from
    the engine's numpy mirror arrays, refreshed once per kernel step."""

    def _make_clock(self, engine):
        # all lanes share the engine's logical clock so request deadlines
        # are comparable across lanes and gc is one pass, not G passes
        return engine.clock

    def _launch_core(self, cfg, log_reader, peer_addresses, initial, new_node, rng):
        self._vec_initial = initial
        self._vec_new_node = new_node
        self._vec_addresses = list(peer_addresses)
        self._vec_lane = None  # bound by VectorEngine.add_node
        self._vec_wake_counted = False  # see notify_admission
        # snapshot record awaiting persistence on the snapshot worker
        # (handed off by _handle_install_snapshot; at most one in flight —
        # lane.recovering gates re-entry)
        self._vec_install_record = None
        return None  # no scalar Peer

    @property
    def _rate_limited(self) -> bool:
        """Per-lane Config.max_in_mem_log_size enforcement: the arena is
        this replica's in-memory log tier, and its tracked byte size gates
        new proposals (the scalar core additionally aggregates follower
        reports via RATE_LIMIT messages, cf. rate.go; lanes enforce the
        bound locally — device lanes carry no payload bytes to report)."""
        mx = self.config.max_in_mem_log_size
        if not mx:
            return False
        lane = self._vec_lane
        return lane is not None and lane.arena.unapplied_bytes > mx

    @_rate_limited.setter
    def _rate_limited(self, value) -> None:
        # derived live from the lane arena; the base class's cached-flag
        # writes (Node.__init__ / step_node) are meaningless here
        pass

    # ------------------------------------------------------------ status
    def get_leader_id(self) -> int:
        lane = self._vec_lane
        if lane is None or not lane.active:
            return 0
        eng = self.engine
        return lane.rev.get(int(eng._m_leader[lane.g]) - 1, 0)

    def local_status(self):
        lane = self._vec_lane
        if lane is None:
            return {
                "leader_id": 0,
                "term": 0,
                "state": ROLE.FOLLOWER,
                "commit": 0,
                "cluster_id": self.cluster_id,
                "node_id": self._node_id,
                "applied": self.sm.last_applied_index(),
            }
        eng = self.engine
        g = lane.g
        return {
            "leader_id": lane.rev.get(int(eng._m_leader[g]) - 1, 0),
            "term": int(eng._m_term[g]),
            "state": int(eng._m_role[g]),
            "commit": int(eng._m_base[g] + eng._m_commit[g]),
            "cluster_id": self.cluster_id,
            "node_id": self._node_id,
            "applied": self.sm.last_applied_index(),
        }

    def notify_admission(self) -> bool:
        """Serving-front first-admit wake (see Node.notify_admission).
        Vector quiesce lives in the kernel plane; the decode-maintained
        _m_quiesced mirror says whether this lane was quiesced as of its
        last step (zero device syncs). The admitted op's arrival stages
        the wake NOOP itself (_pack wakes quiesced lanes with fresh host
        work); marking the lane ready here just lets the loop turn
        immediately instead of waiting out the pump interval."""
        lane = self._vec_lane
        if lane is None or not lane.active:
            return False
        if not bool(self.engine._m_quiesced[lane.g]):
            self._vec_wake_counted = False
            return False
        # the mirror stays stale until the next decode clears it: a burst
        # of admits against one quiesced lane is ONE quiesced->active
        # transition, so only the first admit reports (and counts) a wake
        # — matching the scalar QuiesceManager.wake_on_admit semantics.
        # Later admits still nudge the loop (cheap, idempotent).
        self.engine.set_node_ready(self.cluster_id)
        if self._vec_wake_counted:
            return False
        self._vec_wake_counted = True
        return True

    def _leader_event(self, leader_id: int, term: int) -> None:
        """Engine loop: the lane's (leader, term) changed this step."""
        if self.events is not None:
            self.events.leader_updated(
                self.cluster_id, self._node_id, leader_id, term
            )

    # ------------------------------------------------- INodeProxy overrides
    def apply_config_change(self, cc) -> None:
        """A config change committed and passed the membership legality
        checks: reconcile the device lane (slot remap) on the engine loop.
        The new member's address registers host-wide first (base-class
        seam): the replicated entry is every replica's routing source.

        A bootstrap entry that adds a member the lane was activated with
        changes nothing on the device: the lane starts with every member
        of its bootstrap, as the reference's peer does, and the applied
        image only grows back to it, one entry at a time. Remapping at
        each would renumber the lane's slots on the way, and replicas
        that apply at different times would number differently meanwhile
        (no device route between them, and a lane that counts one voter)."""
        self._register_cc_address(cc)
        lane = self._vec_lane
        if (
            cc.initialize
            and cc.type == ConfigChangeType.ADD_NODE
            and lane is not None
            and lane.mem_sig is not None
            and cc.node_id in lane.mem_sig[0]
        ):
            return
        self.engine.membership_changed(self)

    def config_change_processed(self, key: int, accepted: bool) -> None:
        self.pending_config_change.apply(key, rejected=not accepted)
        # the device's single-pending-config-change latch opens once the
        # change is applied or rejected (cf. raft.go:1242-1295; the scalar
        # core clears it through apply_config_change/reject_config_change)
        self.engine.cc_processed(self)

    # --------------------------------------------------- snapshot overrides
    def _recover_initial_snapshot_locked(self) -> None:
        from ..rsm import Task

        t = Task(
            cluster_id=self.cluster_id,
            node_id=self._node_id,
            snapshot_available=True,
        )
        self.sm.recover_from_snapshot(t)

    def _do_recover_snapshot(self, task) -> None:
        """InstallSnapshot arrived and the SM recovered from it on a
        snapshot worker; reconcile the device lane and ack the leader
        (cf. node.go:950-965 + raft.go handleInstallSnapshotMessage)."""
        try:
            # persist the snapshot record FIRST (restart safety: the
            # recovery below reads the image through this record) — on
            # THIS worker thread, not the engine loop: the record write is
            # an fsync, and a monolithic install must not stall the whole
            # fleet's super-step cadence (see _handle_install_snapshot)
            ss_rec = self._vec_install_record
            self._vec_install_record = None
            if ss_rec is not None:
                self.logdb.save_raft_state(
                    [
                        Update(
                            cluster_id=self.cluster_id,
                            node_id=self._node_id,
                            snapshot=ss_rec,
                        )
                    ]
                )
            idx = self.sm.recover_from_snapshot(task)
            if idx > 0:
                self.clear_install_aborted()
                self.snapshots_installed += 1
                ss = self.snapshotter.get_most_recent_snapshot()
                if ss is not None and not ss.is_empty():
                    with self._mu:
                        self.log_reader.apply_snapshot(ss)
                    self.engine.snapshot_restored(self, ss)
                    return
            self.engine.recover_done(self)
        finally:
            self.ss.clear_recovering_from_snapshot()

    def _notify_snapshot_status(self) -> None:
        # the engine loop owns this lane's protocol state (incl. the log
        # reader the finalization mutates): route completions there
        self.engine.snapshot_status_ready(self)


class _Arena:
    """Entry arena over the device window: a RING of W slots indexed by
    real index % W, so placement/lookup are list indexing (a dict per
    index was a measured hot spot across place/send/save/apply) and
    compaction is free — overwriting a slot IS the eviction, exactly when
    the device window has moved past it.

    Byte counters back per-lane Config.max_in_mem_log_size enforcement
    (cf. internal/server/rate.go + inmemory.go size accounting; the arena
    is the vector engine's in-memory log tier): mem_bytes is everything
    resident; unapplied_bytes covers only entries above the applied
    watermark — the real backpressure signal, because applied entries stay
    resident merely as the window's payload cache (the scalar inmem drops
    them instead, inmemory.go appliedLogTo)."""

    __slots__ = (
        "w", "buf", "mem_bytes", "unapplied_bytes", "payload_bytes", "applied"
    )

    def __init__(self, window: int) -> None:
        self.w = window
        self.buf: List[Optional[Entry]] = [None] * window
        self.mem_bytes = 0
        self.unapplied_bytes = 0
        # resident CLIENT-payload bytes only (no per-entry overhead, and
        # config-change entries excluded — their encoded membership cmd
        # is protocol metadata that legitimately reaches witnesses
        # intact, cf. raft.go:742-756): the witness-lane probe — a
        # witness replica must hold ZERO of these, asserted by
        # lane_stats/tests and the observer_witness_churn verdict
        self.payload_bytes = 0
        self.applied = 0

    def __setitem__(self, key: int, entry: Entry) -> None:
        slot = key % self.w
        old = self.buf[slot]
        sz = ENTRY_OVERHEAD_BYTES + len(entry.cmd)
        if old is not None:
            osz = ENTRY_OVERHEAD_BYTES + len(old.cmd)
            self.mem_bytes -= osz
            if old.type != EntryType.CONFIG_CHANGE:
                self.payload_bytes -= len(old.cmd)
            if old.index > self.applied:
                self.unapplied_bytes -= osz
        self.mem_bytes += sz
        if entry.type != EntryType.CONFIG_CHANGE:
            self.payload_bytes += len(entry.cmd)
        if key > self.applied:
            self.unapplied_bytes += sz
        self.buf[slot] = entry

    def get(self, key: int) -> Optional[Entry]:
        e = self.buf[key % self.w]
        return e if e is not None and e.index == key else None

    def __getitem__(self, key: int) -> Entry:
        e = self.buf[key % self.w]
        if e is None or e.index != key:
            raise KeyError(key)
        return e

    def get_run(self, lo: int, hi: int):
        """Entries [lo, hi] inclusive, or (None, missing_index) on a hole."""
        w, buf = self.w, self.buf
        out = []
        for i in range(lo, hi + 1):
            e = buf[i % w]
            if e is None or e.index != i:
                return None, i
            out.append(e)
        return out, -1

    def mark_applied(self, index: int) -> None:
        """Advance the applied watermark; entries in (applied, index] no
        longer count toward unapplied_bytes."""
        w, buf = self.w, self.buf
        dec = 0
        for i in range(self.applied + 1, index + 1):
            e = buf[i % w]
            if e is not None and e.index == i:
                dec += ENTRY_OVERHEAD_BYTES + len(e.cmd)
        self.unapplied_bytes -= dec
        if index > self.applied:
            self.applied = index


class _Lane:
    """Per-group host bookkeeping owned by the engine loop thread. Protocol
    mirrors (term/role/leader/commit/last/first/base) live in the engine's
    whole-G numpy arrays, not here."""

    __slots__ = (
        "g",
        "key",
        "node",
        "cfg",
        "slots",
        "rev",
        "arena",
        "staged_props",
        "staged_reads",
        "staged_ccs",
        "msg_backlog",
        "pack_info",
        "packed_pending",
        "ri_pending",
        "ri_lat",
        "recovering",
        "adopted_term",
        "catchup",
        "snap_inflight",
        "active",
        "cc_inflight",
        "mem_sig",
        "wit_slots",
        "commit_seen",
    )

    def __init__(self, g: int, node: VectorNode, key=None) -> None:
        self.g = g
        self.key = key if key is not None else node.cluster_id
        self.node = node
        self.cfg: Config = node.config
        self.slots: Dict[int, int] = {}  # node_id -> slot
        self.rev: Dict[int, int] = {}  # slot -> node_id
        # ring over the device window; real index -> Entry, size-tracked
        self.arena: _Arena = _Arena(node.engine.kcfg.log_window)
        self.staged_props: deque = deque()  # Entry
        self.staged_reads: deque = deque()  # RequestState
        self.staged_ccs: deque = deque()  # (Entry, key)
        self.msg_backlog: deque = deque()  # wire Messages awaiting a slot
        self.pack_info: Dict[int, tuple] = {}
        self.packed_pending = 0  # entries packed into not-yet-decoded steps
        self.ri_pending: Dict[Tuple[int, int], SystemCtx] = {}  # (lo,hi)->ctx
        # (lo,hi) -> the LatencyTraces of the sampled reads bound to that
        # ctx; empty unless a read of the ctx was sampled
        self.ri_lat: Dict[Tuple[int, int], list] = {}
        self.recovering = False
        # term adopted from an InstallSnapshot sender; the restore ack must
        # carry it or the leader drops the ack as stale. Kept on the lane
        # because the engine's _m_term mirror is rebound from device state
        # every step (the device never saw the snapshot message).
        self.adopted_term = 0
        self.catchup: Dict[int, _Catchup] = {}  # slot -> host-log replay
        # snapshot-status feedback (cf. feedback.go:38-128): slot ->
        # (sent_tick, snapshot_index, sent_launch, sent_at: the host clock
        # of a sampled send, else 0.0); a peer that does not ack the
        # snapshot within the retry window gets a synthetic
        # SNAPSHOT_STATUS reject so the kernel un-parks it and the
        # leader retries — a lost InstallSnapshot must not wedge the
        # remote in SNAPSHOT state forever
        self.snap_inflight: Dict[int, tuple] = {}
        self.active = False
        self.cc_inflight = False
        # (members, observers, witnesses) snapshot of the last membership
        # image reconciled onto the device — config changes that restate
        # the same image (e.g. bootstrap CCs) skip the device remap
        self.mem_sig: Optional[tuple] = None
        # peer slots holding WITNESS members: replication toward these is
        # payload-stripped (metadata entries / witness-shaped snapshots,
        # cf. raft.go:742-756) at every host sender site. Maintained by
        # the same three reconcile paths that maintain mem_sig.
        self.wit_slots: frozenset = frozenset()
        # the real commit index a sampled pack last saw while this lane
        # led (n.hot_commit_entries); None until one has
        self.commit_seen: Optional[int] = None

    # ------------------------------------------------------- slot mapping
    def set_slots(self, member_ids) -> Dict[int, int]:
        """Canonical mapping: rank in sorted member-id order. Returns the
        old->new slot permutation for device remap."""
        new = {nid: i for i, nid in enumerate(sorted(member_ids))}
        perm = {}
        for nid, old_slot in self.slots.items():
            if nid in new:
                perm[old_slot] = new[nid]
        self.slots = new
        self.rev = {s: nid for nid, s in new.items()}
        return perm

    def slot_of(self, node_id: int, provisional: bool = False) -> int:
        s = self.slots.get(node_id)
        if s is not None:
            return s
        if not provisional:
            return -1
        # a sender we have not learned through membership yet (join path):
        # park it on a free slot; the canonical remap fixes it at apply time
        P = self.node.engine.kcfg.peers
        used = set(self.slots.values())
        for s in range(P):
            if s not in used:
                self.slots[node_id] = s
                self.rev[s] = node_id
                return s
        return -1

    def self_slot(self) -> int:
        return self.slots.get(self.node.node_id(), -1)

    def has_staged(self) -> bool:
        return bool(
            self.msg_backlog
            or self.staged_props
            or self.staged_reads
            or self.staged_ccs
        )


# wire type for each device response-plane type (phase-3 fan-out)
_RESP_WIRE = {
    int(MSG.REPLICATE_RESP): MT.REPLICATE_RESP,
    int(MSG.REQUEST_VOTE_RESP): MT.REQUEST_VOTE_RESP,
    int(MSG.REQUEST_PREVOTE_RESP): MT.REQUEST_PREVOTE_RESP,
    int(MSG.HEARTBEAT_RESP): MT.HEARTBEAT_RESP,
    int(MSG.NOOP): MT.NOOP,
}


# ---------------------------------------------------------------------------
# Columnar fan-out: StepOutput planes -> wire Messages.
#
# Each builder derives its work list from ONE np.nonzero over the relevant
# mask, gathers every field it needs as whole columns (`arr[gs, ps]`), and
# only then iterates plain python values — Message objects materialize at
# the transport boundary and nowhere earlier. These are module-level pure
# readers (they mutate no engine state) so the differential test can drive
# them directly against a per-element reference (tests/test_fanout_columnar).
# ---------------------------------------------------------------------------


def _send_target(lane_by_g, g: int, p: int):
    """The fan-out builders' shared skip rules: (lane, to_nid), or None
    when the lane is unoccupied or the peer slot has no known node id.
    One place to extend when a new skip rule applies to every send kind."""
    lane = lane_by_g[g]
    if lane is None:
        return None
    to_nid = lane.rev.get(p)
    if to_nid is None:
        return None
    return lane, to_nid


def gather_replicate_sends(
    o: dict, base, lane_by_g, fetch_from_log=None, launch: int = 0
) -> List[Tuple[_Lane, Message]]:
    """Phase-1 Replicate materialization (these leave BEFORE the fsync).
    `launch` is the engine's launch ordinal, for the chain events."""
    sends: List[Tuple[_Lane, Message]] = []
    gs, ps = np.nonzero(o["send_flags"] & SEND_REPLICATE)
    if not gs.size:
        return sends
    cols = zip(
        gs.tolist(),
        ps.tolist(),
        base[gs].tolist(),
        o["term"][gs].tolist(),
        o["send_prev_index"][gs, ps].tolist(),
        o["send_prev_term"][gs, ps].tolist(),
        o["send_n_entries"][gs, ps].tolist(),
        o["send_commit"][gs, ps].tolist(),
    )
    for g, p, b, term, prev, prev_term, n, commit in cols:
        tgt = _send_target(lane_by_g, g, p)
        if tgt is None:
            continue
        lane, to_nid = tgt
        ents, _missing = lane.arena.get_run(b + prev + 1, b + prev + n)
        if ents is None:
            ents = (
                fetch_from_log(lane, b + prev + 1, b + prev + n)
                if fetch_from_log is not None
                else None
            )
            if ents is None:
                _plog.errorf(
                    "%s missing entries for replicate [%d..%d]",
                    lane.node.describe(), b + prev + 1, b + prev + n,
                )
                continue
        if p in lane.wit_slots:
            # witness peers replicate metadata only — payload bytes never
            # leave this host toward a witness
            ents = _make_metadata_entries(ents)
        # causal trace: a sampled entry's trace id rides the Message (and
        # the Entry codec) so the follower stamps the same key. Scanning
        # is bounded by max_entries_per_msg; only the 1-in-N sampled case
        # records anything.
        trace_id = 0
        for e in ents:
            if e.trace_id:
                trace_id = e.trace_id
        if trace_id:
            flight_recorder().record(
                "replicate_send", cluster=lane.node.cluster_id,
                node=lane.node.node_id(), to=to_nid, trace=trace_id,
                launch=launch,
            )
        sends.append(
            (
                lane,
                Message(
                    type=MT.REPLICATE,
                    cluster_id=lane.node.cluster_id,
                    to=to_nid,
                    from_=lane.node.node_id(),
                    term=term,
                    log_index=b + prev,
                    log_term=prev_term,
                    commit=b + commit,
                    trace_id=trace_id,
                    entries=ents,
                ),
            )
        )
    return sends


def gather_post_sends(o: dict, base, lane_by_g) -> List[Tuple[_Lane, Message]]:
    """Phase-3 broadcast-plane sends (vote requests, heartbeats,
    TimeoutNow), in the same per-kind order the scalar fan-out used."""
    sends: List[Tuple[_Lane, Message]] = []
    send_flags = o["send_flags"]
    term_plane = o["term"]
    role_plane = o["role"]
    gs, ps = np.nonzero(send_flags & SEND_VOTE_REQ)
    if gs.size:
        for g, p, b, term, role, vli, vlt, hint in zip(
            gs.tolist(),
            ps.tolist(),
            base[gs].tolist(),
            term_plane[gs].tolist(),
            role_plane[gs].tolist(),
            o["vote_last_index"][gs].tolist(),
            o["vote_last_term"][gs].tolist(),
            o["send_hint"][gs, ps].tolist(),
        ):
            tgt = _send_target(lane_by_g, g, p)
            if tgt is None:
                continue
            lane, to_nid = tgt
            # the shared vote plane serves both election phases: a
            # PRE_CANDIDATE lane polls with REQUEST_PREVOTE at the
            # PROSPECTIVE term (its own term stays untouched)
            pre = role == ROLE.PRE_CANDIDATE
            sends.append(
                (
                    lane,
                    Message(
                        type=MT.REQUEST_PREVOTE if pre else MT.REQUEST_VOTE,
                        cluster_id=lane.node.cluster_id,
                        to=to_nid,
                        from_=lane.node.node_id(),
                        term=term + 1 if pre else term,
                        log_index=b + vli,
                        log_term=vlt,
                        hint=hint,
                    ),
                )
            )
    gs, ps = np.nonzero(send_flags & SEND_HEARTBEAT)
    if gs.size:
        for g, p, b, term, hb_commit, hint, hint2, lease_round in zip(
            gs.tolist(),
            ps.tolist(),
            base[gs].tolist(),
            term_plane[gs].tolist(),
            o["send_hb_commit"][gs, ps].tolist(),
            o["send_hint"][gs, ps].tolist(),
            o["send_hint2"][gs, ps].tolist(),
            o["lease_round"][gs].tolist(),
        ):
            tgt = _send_target(lane_by_g, g, p)
            if tgt is None:
                continue
            lane, to_nid = tgt
            sends.append(
                (
                    lane,
                    Message(
                        type=MT.HEARTBEAT,
                        cluster_id=lane.node.cluster_id,
                        to=to_nid,
                        from_=lane.node.node_id(),
                        term=term,
                        # lease round tag: an opaque tick stamp the follower
                        # echoes back, NOT an index — no +b translation
                        log_index=lease_round,
                        commit=b + hb_commit,
                        hint=hint,
                        hint_high=hint2,
                    ),
                )
            )
    gs, ps = np.nonzero(send_flags & SEND_TIMEOUT_NOW)
    if gs.size:
        for g, p, term in zip(
            gs.tolist(), ps.tolist(), term_plane[gs].tolist()
        ):
            tgt = _send_target(lane_by_g, g, p)
            if tgt is None:
                continue
            lane, to_nid = tgt
            sends.append(
                (
                    lane,
                    Message(
                        type=MT.TIMEOUT_NOW,
                        cluster_id=lane.node.cluster_id,
                        to=to_nid,
                        from_=lane.node.node_id(),
                        term=term,
                    ),
                )
            )
    return sends


def gather_resp_sends(
    o: dict, base, lane_by_g, launch: int = 0
) -> List[Tuple[_Lane, Message]]:
    """Phase-3 response-plane sends: one reply per consumed inbox slot.
    `launch` is the engine's launch ordinal, for the chain events."""
    sends: List[Tuple[_Lane, Message]] = []
    resp_type = o["resp_type"]
    gs, ks = np.nonzero(resp_type != MSG.NONE)
    if not gs.size:
        return sends
    cols = zip(
        gs.tolist(),
        base[gs].tolist(),
        resp_type[gs, ks].tolist(),
        o["resp_to"][gs, ks].tolist(),
        o["resp_term"][gs, ks].tolist(),
        o["resp_log_index"][gs, ks].tolist(),
        o["resp_reject"][gs, ks].tolist(),
        o["resp_hint"][gs, ks].tolist(),
        o["resp_hint2"][gs, ks].tolist(),
    )
    for g, b, t, to_slot, term, log_index, reject, hint, hint2 in cols:
        tgt = _send_target(lane_by_g, g, to_slot)
        if tgt is None:
            continue
        lane, to_nid = tgt
        if to_nid == lane.node.node_id():
            continue  # self-addressed (e.g. local election artifacts)
        wire = _RESP_WIRE.get(t)
        if wire is None:
            continue
        trace_id = 0
        if wire == MT.REPLICATE_RESP:
            log_index += b
            hint += b
            # ack hop of the causal chain: if the ACCEPTED index is a
            # sampled entry this follower placed, carry its trace id back
            # (one arena ring probe; records only on the 1-in-N case).
            # Best-effort by design: a sampled entry that is not the last
            # of its acked run goes unprobed, and rejected acks never
            # probe — a reject's hint index can land on a stale
            # conflicting arena entry and would misattribute an unrelated
            # proposal's chain.
            if not reject:
                te = lane.arena.get(log_index)
                if te is not None:
                    trace_id = te.trace_id
            if trace_id:
                flight_recorder().record(
                    "replicate_ack", cluster=lane.node.cluster_id,
                    node=lane.node.node_id(), to=to_nid, trace=trace_id,
                    index=log_index, launch=launch,
                )
        sends.append(
            (
                lane,
                Message(
                    type=wire,
                    cluster_id=lane.node.cluster_id,
                    to=to_nid,
                    from_=lane.node.node_id(),
                    term=term,
                    log_index=log_index,
                    reject=bool(reject),
                    hint=hint,
                    hint_high=hint2,
                    trace_id=trace_id,
                ),
            )
        )
    return sends


def build_save_updates(o: dict, base, lane_by_g, commit_cap=None, owed=None):
    """Phase-2 hard-state/entry persistence as (updates, lane_saves): the
    whole step's saves gathered columnar, written downstream as ONE
    multi-group write wave. `commit_cap` (device units a lane) bounds
    the commit index a hard state carries; lanes under `owed` get their
    hard state written whether or not this step changed it (see
    VectorEngine._decode_super for both)."""
    updates: List[Update] = []
    lane_saves: List[Tuple[_Lane, List[Entry], State]] = []
    changed = o["hard_changed"]
    if owed is not None:
        changed = changed | owed
    gs = np.nonzero((o["save_from"] > 0) | changed)[0]
    if not gs.size:
        return updates, lane_saves
    commits = o["commit_index"][gs]
    if commit_cap is not None:
        commits = np.minimum(commits, commit_cap[gs])
    cols = zip(
        gs.tolist(),
        base[gs].tolist(),
        o["save_from"][gs].tolist(),
        o["save_to"][gs].tolist(),
        o["vote"][gs].tolist(),
        o["term"][gs].tolist(),
        commits.tolist(),
        changed[gs].tolist(),
    )
    for g, b, sf, st_, vote_slot, term, commit, hard_changed in cols:
        lane = lane_by_g[g]
        if lane is None or not lane.active:
            continue
        ents: List[Entry] = []
        if sf > 0:
            ents, missing_at = lane.arena.get_run(b + sf, b + st_)
            if ents is None:
                _plog.errorf(
                    "%s missing arena entry %d for save",
                    lane.node.describe(), missing_at,
                )
                ents = []
        state = State(
            term=term,
            vote=lane.rev.get(vote_slot - 1, 0) if vote_slot > 0 else 0,
            commit=b + commit,
        )
        if ents or hard_changed:
            updates.append(
                Update(
                    cluster_id=lane.node.cluster_id,
                    node_id=lane.node.node_id(),
                    state=state,
                    entries_to_save=ents,
                )
            )
            lane_saves.append((lane, ents, state))
    return updates, lane_saves


# The per-peer recovery timers (catch-up retry, snapshot feedback retry)
# are counted in ticks, as the reference's (feedback.go:38-128), AND in
# launches: a peer's acknowledgement cannot be back in fewer launches than
# its round trip takes (replicate out, follower step, response in, leader
# step), nor a restore's in fewer than the hand-offs it passes (pack, task
# worker, snapshot worker, reconcile, acknowledgement, leader step). On a
# loop whose launch outlasts the tick bound (a loaded fleet: 1-3 s a
# launch, 1-2 s of ticks) the ticks alone declared every catching-up peer
# silent before its first acknowledgement could return. Where launches
# are short the ticks decide.
_ACK_LAUNCHES = 4
_RESTORE_LAUNCHES = 8

# The progress watch (VectorEngine._watch_progress): a debt of progress
# that a stepped lane has owed for this many of the watch's sweeps running
# (launches at least an election timeout apart) is a stall, twice the
# four launches that the repair of one lost Replicate takes (heartbeat,
# response, probe, reject and resend: PERF.md section 6, PR 34); and how
# many of a sweep's new stalls leave a `progress_stall` event in full
# (all of them are counted). Not knobs.
_STALL_LAUNCHES = 8
_STALL_EVENTS_PER_LAUNCH = 8
# The watch sweeps in blocks of at most this many elements a numpy call,
# through slices and through fancy indexes into ONE dimension. numpy gives
# up the GIL around every inner loop of more than 500 elements and around
# every fancy index into two dimensions whatever its size, and while
# apply or snapshot workers are busy the loop thread gets it back only
# after their slices: swept over whole planes the watch stood 300 ms a
# launch in the fleet behind `apply` and 12-25 ms in the two 5-replica
# cells in `place`, and with `match[rows]` in it 25-56 ms in the churn
# cell, on 0.3 to 2 ms of work (PERF.md section 6, PR 37). Should numpy
# move its threshold the watch stays right and gets slow, and
# `engine.watch_ms_per_launch` says so.
_SWEEP_ELEMENTS = 480

# What the engine runs a launch when it chooses for itself
# (EngineConfig.steps_per_sync None) and every peer is routable on the
# device: the protocol steps a commit takes, leader append, follower
# append and acknowledge, leader commit (PERF.md section 5). A step past
# the third carries heartbeats at most and costs a router pass. Not a
# knob.
_AUTO_STEPS = 3


def _steps_option(ecfg) -> Optional[int]:
    """EngineConfig.steps_per_sync as the engine reads it: None = the
    engine chooses, else a launch's protocol steps (at least one)."""
    k = getattr(ecfg, "steps_per_sync", None) if ecfg is not None else None
    return None if k is None else max(1, int(k))


class _Catchup:
    """One peer served from its leader's host log (VectorEngine.
    _run_catchups): `nxt` the next index to send, `goal` where device
    replication takes over, `match` the peer's match when it last moved
    and `tick`/`launch` when that was (or when the last retry went out),
    `sent_hi` the highest index sent so far, `rewound` the launch of the
    last rewind on a reject, `probing` once the peer has gone silent."""

    __slots__ = ("nxt", "goal", "match", "tick", "launch", "sent_hi",
                 "rewound", "probing")

    def __init__(self, nxt: int, goal: int, match: int, tick: int,
                 launch: int) -> None:
        self.nxt = nxt
        self.goal = goal
        self.match = match
        self.tick = tick
        self.launch = launch
        self.sent_hi = nxt - 1
        self.rewound = 0
        self.probing = False


def _is_ack(m: Message) -> bool:
    t = m.type
    return t == MT.HEARTBEAT_RESP or (t == MT.REPLICATE_RESP and not m.reject)


def _coalesce_acks(backlog: deque) -> None:
    """Order a leader's waiting wire messages for an inbox that cannot
    take them all. Every acknowledgement is folded into the newest of
    its sender and term, at the oldest's place in its kind's queue (so
    the sender whose answer has waited longest is served first, and none
    starves): an accepted ReplicateResp only ever raises match and next
    to its index, and a HeartbeatResp confirms its ReadIndex context and
    with it every earlier one (kernel: readindex_pop), so the newest of
    each says all the older ones said. The two kinds then take turns, a
    ReplicateResp first: one follower's answer of each kind is a quorum
    at three replicas, so a step that has two slots for them advances
    both the commit index and the reads. Rejections and every other
    type go first, as they came."""
    newest = {}
    for m in backlog:
        if _is_ack(m):
            newest[m.type, m.from_, m.term] = m
    kept = []
    acks = {MT.REPLICATE_RESP: [], MT.HEARTBEAT_RESP: []}
    for m in backlog:
        if _is_ack(m):
            m = newest.pop((m.type, m.from_, m.term), None)
            if m is not None:
                acks[m.type].append(m)
        else:
            kept.append(m)
    for pair in itertools.zip_longest(*acks.values()):
        kept.extend(m for m in pair if m is not None)
    backlog.clear()
    backlog.extend(kept)


class VectorEngine:
    """Engine-compatible facade (add/remove/set_*_ready/stop) around the
    single-stepper loop that advances all lanes per kernel call."""

    def __init__(
        self,
        logdb,
        nh_config: Optional[NodeHostConfig] = None,
        num_task_workers: Optional[int] = None,
        num_snapshot_workers: int = 2,
    ) -> None:
        self._logdb = logdb
        ecfg = nh_config.engine if nh_config is not None else None
        self.kcfg = KernelConfig(
            groups=ecfg.max_groups if ecfg else 64,
            peers=ecfg.max_peers if ecfg else 8,
            log_window=ecfg.log_window if ecfg else 128,
            inbox_depth=ecfg.inbox_depth if ecfg else 8,
            max_entries_per_msg=(
                getattr(ecfg, "max_entries_per_msg", 8) if ecfg else 8
            ),
            readindex_depth=ecfg.readindex_depth if ecfg else 4,
        )
        E, W = self.kcfg.max_entries_per_msg, self.kcfg.log_window
        if E >= W:
            # the kernel's ring-slot scatter maps each written index to a
            # unique slot only while a message's span fits the window, and
            # a follower's pack takes a Replicate only into the room its
            # window has, W - 1 at most (a slot is kept for a new leader's
            # noop): a row of W entries would wait there for ever
            raise ValueError(
                f"max_entries_per_msg ({E}) must be below log_window ({W})"
            )
        # a device window compacts once it is half full, or once it has
        # no room for a whole row of E entries where that comes first
        # (E of half a window or more): a follower's pack holds a
        # Replicate that does not fit until compaction makes room, so
        # waiting for half full alone left a follower served from the
        # host log behind for good
        self._compact_mark = min(W // 2, W - 1 - E)
        # multi-device: shard the group axis over every visible device
        # (SURVEY §2.9.1 — groups are independent Raft instances, so the
        # kernel partitions along G with zero collectives on the hot path)
        self._sharding = None
        self._inbox_shardings = None  # cached pytree; shapes never change
        self._multi_shardings = None  # K>1 twin: (inbox, ticks, route, rdelta)
        self._mesh = None
        self._mesh_devices = 0  # 0 = unsharded single-device engine
        groups_requested = self.kcfg.groups
        if ecfg is not None and getattr(ecfg, "shard_over_mesh", False):
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            devs = jax.devices()
            n = len(devs)
            if n < 2:
                # quietly running unsharded would let a one-device run
                # pass for a mesh run
                raise ValueError(
                    "EngineConfig.shard_over_mesh=True needs more than one "
                    f"visible jax device; found {n} "
                    f"({devs[0].platform}:{devs[0].device_kind})"
                )
            if self.kcfg.groups % n:
                # round UP to a device multiple so every shard holds the
                # same block. NOT silent: the shortfall is stamped in
                # step_stats (padded_groups/mesh_devices -> engine_step_*
                # gauges) and the ghost lanes are never handed out by
                # the allocator, so lane_stats never reports them
                self.kcfg = self.kcfg._replace(
                    groups=((self.kcfg.groups + n - 1) // n) * n
                )
            mesh = Mesh(np.array(devs), ("groups",))
            self._mesh = mesh
            self._mesh_devices = n

            def _shard_for(x, _mesh=mesh, _NS=NamedSharding, _P=PartitionSpec):
                # canonical spec: trailing dims replicate implicitly. An
                # explicit trailing None is the SAME placement but a
                # DIFFERENT jit cache key than the normalized spec jit
                # outputs carry, so a fresh device_put state would re-trace
                # every activation bucket once — and whether that second
                # trace lands before or after a compile-audit mark depends
                # on how lane-add batches happen to coalesce
                return _NS(_mesh, _P("groups"))

            self._sharding = _shard_for
        self._groups_requested = groups_requested
        self._padded_groups = self.kcfg.groups - groups_requested
        self.clock = _SharedClock()
        # protocol steps of the next launch (EngineConfig.steps_per_sync):
        # an integer is that for the engine's life; None lets the engine
        # move between 1 and _AUTO_STEPS at launch boundaries, by what
        # _rebuild_routes observes. K=1 runs the one-step loop, K>1 the
        # scanned super-step path. A mesh without an integer stays at 1.
        self._steps_cfg = _steps_option(ecfg)
        self._auto = self._steps_cfg is None and self._mesh is None
        self._multi = self._steps_cfg or 1
        ov = getattr(ecfg, "overlap_decode", None) if ecfg else None
        if ov is None:
            ov = jax.default_backend() != "cpu"  # auto: see EngineConfig
        # what the K=1 program runs; a launch of more steps is its own
        # pipelining (dispatch and fetch amortize over K steps), and the
        # pack behind it needs ITS fetch's residual-inbox occupancy: a
        # step in flight would make that two steps stale and clobber
        # device-routed residual rows
        self._overlap_k1 = bool(ov)
        self._overlap = self._overlap_k1 and self._multi == 1
        self._pending = None  # in-flight (work, packs, StepOutput future)
        self._rebase_due = False
        # stage profiler for the hot loop (cf. reference execengine.go
        # :197-211 + trace.go:98-162). Sparse sampling by default (1/32):
        # per-step full sampling is pure hot-loop overhead in production;
        # the benchmark's traced run and debugging opt into every-step
        # recording through EngineConfig.profile_sample_ratio=1.
        ratio = (getattr(ecfg, "profile_sample_ratio", 0) or 0) if ecfg else 0
        self.profiler = Profiler(sample_ratio=ratio if ratio > 0 else 32)
        # sampled stage durations also land in the process-global phase
        # plane (engine_phase_seconds{engine="vector",phase=...}) and, at
        # ratio 1, in the flight recorder's span store; unsampled steps
        # never reach either. An iteration that neither decodes a step
        # nor launches one has wait, prepare and pack and nothing else.
        self.profiler.attach_phase_plane(
            phase_plane(), "vector", idle_head=("wait", "prepare", "pack")
        )
        # kernel launches dispatched so far: the ordinal that the request
        # path's stamps carry (trace.LatencyTrace). Written by the loop
        # thread only; other threads read it as a plain int.
        self.launch_no = 0
        # request-lifecycle latency sampling shares the profiler's ratio
        # knob down to a floor of its own: 1-in-N proposals/reads carry a
        # LatencyTrace into the proposal_commit/apply and readindex
        # latency histograms and the profiler's req.* samples; the other
        # N-1 stay allocation-free (see trace.LatencySampler). Tracing
        # EVERY request (a trace, some nine chain events and a fold
        # each) cost the upstream write cell four times what the stage
        # profiler at ratio 1 did (PERF.md, PR 23), which made the traced
        # run another regime than the one it explains; a test that wants
        # every request traced sets request_sampler.ratio = 1.
        self.request_sampler = LatencySampler(
            max(ratio if ratio > 0 else 32, REQUEST_SAMPLE_FLOOR)
        )
        # per-step counters accumulated inline by the decode phases on
        # objects they already materialize (no extra device syncs, no
        # extra numpy reductions); exported via step_stats() and folded
        # into NodeHost._export_health_gauges as engine_step_* gauges
        self._sstats = {
            "steps": 0,
            "msgs_replicate": 0,  # phase-1 Replicate messages out
            "msgs_broadcast": 0,  # phase-3 votes/heartbeats/TimeoutNow out
            "msgs_resp": 0,  # phase-3 response-plane messages out
            "lanes_commit_advanced": 0,  # lanes handing commits to the RSM
            "leader_changes": 0,  # (leader, term) transitions observed
            "elections_started": 0,  # lanes that went leaderless
            "entries_applied": 0,  # entries handed to the RSM
            # host-log catch-up of peers below the device window
            "catchups_started": 0,
            "catchup_entries": 0,  # entries sent from the host log
            "replicate_resends": 0,  # Replicates below an index sent before
            "snapshot_fallbacks": 0,  # peers handed to the snapshot path
            # the progress watch (_watch_progress): over the launches,
            # the debts of progress standing at or past _STALL_LAUNCHES
            # (a leader's peer slots, lanes whose commit stands below
            # their last index, lanes whose state machine stands below
            # their commit), and how often a debt crossed that line
            "peer_stall_steps": 0,
            "commit_stall_steps": 0,
            "apply_stall_steps": 0,
            "stalls_seen": 0,
            # multi-step engine: co-hosted messages routed ON DEVICE
            # between inner steps (zero host Message objects each)
            "msgs_routed_device": 0,
            # sharded mesh: ghost lanes added by the device-multiple
            # round-up (never allocated) and the mesh width — static
            # stamps, not counters, so the gauges can tell a padded
            # sharded run from an exact one
            "padded_groups": self._padded_groups,
            "mesh_devices": self._mesh_devices,
            # exceptions the loop caught from _run_once and survived: a
            # kernel the compiler refuses would otherwise show up only as
            # proposal time-outs (benchmark/lib/check.py holds it at zero)
            "loop_exceptions": 0,
            # ReadIndex contexts the kernel dropped for want of a slot
            # (StepOutput.dropped_readindex, summed as fetched). Counted,
            # not repaired: the reads behind them still time out at the
            # client.
            "readindex_dropped": 0,
        }
        # ---- tick-fairness watchdog (ROADMAP seed flake) -----------------
        # Inter-iteration latency vs the host's tick period, a starvation
        # gauge, and an enforced yield when a long kernel step starved a
        # co-scheduled peer loop (see engine/fairness.py).
        tick_s = (
            (nh_config.rtt_millisecond or 50) / 1000.0
            if nh_config is not None
            else 0.05
        )
        yield_ms = getattr(ecfg, "fairness_yield_ms", None) if ecfg else None
        self.watchdog = FairnessWatchdog(
            "vec-step",
            tick_s,
            # 0 disables enforcement (measurement stays on); None = auto
            yield_threshold_s=(
                float("inf") if yield_ms == 0
                else (yield_ms / 1000.0 if yield_ms else None)
            ),
        )
        # per-step replay clamp for coalesced tick backlogs: replaying a
        # stall's whole backlog at election-RTT granularity expires every
        # follower's randomized timer in the same step (synchronized
        # split-vote storms after any multi-second stall — the seed
        # flake); 0 = auto: clamp at each lane's heartbeat RTT
        self._catchup_tick_cap = (
            getattr(ecfg, "max_catchup_ticks", 0) or 0 if ecfg else 0
        )
        self._last_tick_burst = 0
        self._step_fn = make_step_fn(self.kcfg, donate=True)
        # runtime retrace attribution: the step kernel's trace cache is
        # watched per function; a steady-state compile shows up in
        # engine_compile_events_total and fails the perf tier-1 assertion
        compile_watch().install().register(
            f"step_batch[g{self.kcfg.groups}]", self._step_fn
        )
        # ---- multi-step (K>1) state --------------------------------------
        # the device route table (lane index of the co-hosted replica
        # behind each peer slot, -1 = host path) + window-base deltas,
        # rebuilt on the loop thread whenever lane topology changes; the
        # device-resident residual inbox (the last inner step's routed
        # messages, consumed by the next super-step's inner step 0) and
        # its fetched per-lane occupancy; and the routed-Replicate
        # payload placements awaiting their acceptance report.
        G = self.kcfg.groups
        self._m_resid = np.zeros(G, np.int32)
        self._pending_rep_copies: list = []
        # what _drop_parked staged for _flush_patch: lanes whose residual
        # rows go, and (lane, gone) whose payload copies go
        self._resid_drop: List[int] = []
        self._copies_drop: list = []
        self._routes_dirty = True
        # auto: does the route table route every peer slot of every
        # active lane (and at least one)? Set by _rebuild_routes.
        self._all_routable = False
        # lanes whose persisted commit the last merged save wave held
        # below the device's (see _decode_super): the next wave owes
        # them a hard state
        self._m_commit_owed = np.zeros(G, bool)
        self._multi_fn = None
        self._out_slabs = None  # what a packed K-step launch fetches
        self._resid = None
        self.census = None  # made below, once the planes exist
        if self._multi > 1:
            self._build_multi(self._multi)
        self._state: RaftTensors = init_state(self.kcfg)
        if self._sharding is not None:
            self._state = jax.tree.map(
                lambda x: jax.device_put(x, self._sharding(x)), self._state
            )
        # lanes keyed by (host, cluster_id): a SHARED core hosts replicas
        # from several NodeHosts (hosts = handle ids), so cluster_id alone
        # does not identify a lane
        self._lanes: Dict[tuple, _Lane] = {}
        # the same lanes by host: a host's own look at its lanes
        # (leader_snapshot, lane_stats through its handle) costs its lanes,
        # not every lane of the core
        self._host_lanes: Dict[int, Dict[tuple, _Lane]] = {}
        # bumped wherever a lane joins, leaves, activates or deactivates:
        # the active lanes of a host, as _active_lanes last listed them,
        # stand until it moves
        self._lanes_gen = 0
        self._active_cache: Dict[Optional[int], tuple] = {}
        # (cluster_id, node_id) -> lane, for in-core message short-circuit
        self._route: Dict[tuple, _Lane] = {}
        # ghost lanes from the sharded round-up are NOT capacity: the
        # allocator only hands out the lanes the caller configured, so
        # padded lanes never reach _lanes / lane_stats / gauges
        self._free = list(range(self._groups_requested - 1, -1, -1))
        self._lanes_mu = threading.RLock()
        self._reconq: deque = deque()  # host->device ops, loop-applied
        self._patch: Optional[dict] = None  # see _staged_patch
        # the bring-up account (bringup_stats): each host's start_clusters
        # and its parts, the wall seconds of lane activation, and the
        # launches from the first lane activated to the first launch after
        # which every active lane knows a leader. Recorded once a host and
        # once a bring-up: nothing per launch once the fleet has led.
        self._bring_hosts: Dict[int, dict] = {}
        self._bring_activate_s = 0.0
        self._bring_activated = 0
        self._bring_launch0: Optional[int] = None
        self._bring_elect: Optional[int] = None
        self._stopped = threading.Event()
        self._ready = threading.Event()
        # crash teardown flag (stop(flush=False)): the loop discards its
        # un-decoded in-flight step instead of landing it
        self._discard_pending = False
        # the batch-record bodies that the loop's save waves share among
        # co-hosted NodeHosts' logdbs (_save_updates): two waves' worth
        self._record_bodies = RecordBodies()
        # ---- host sharing (handles) --------------------------------------
        self._hosts_mu = threading.Lock()
        self._host_refs: Set[int] = set()
        self._next_host = 0
        self._blocked_hosts: Set[int] = set()  # partitioned NodeHosts
        # per-host clock-suspect deadlines (monotonic seconds): a host
        # whose tick worker reported a clock anomaly loses lease rights
        # (clock_ok=False) on all its lanes until the hold expires.
        # Written by tick workers under _dirty_mu, reconciled onto the
        # device clock_ok plane by the loop thread on transitions only.
        self._clock_suspect: Dict[int, float] = {}
        # cumulative lease read counters (loop-thread writes, lock-free
        # int reads via lease_stats)
        self._lease_local = 0
        self._lease_fb = 0
        # chaos hook over co-hosted delivery (the analogue of the
        # transport's pre-send hook for traffic that never touches the
        # wire): return True to drop the message
        self._local_drop_hook = None
        # ---- host-event staging (producers: API/transport threads) -------
        self._dirty_mu = threading.Lock()
        self._dirty: Set[tuple] = set()  # lane keys with host events
        self._gc_set: Set[tuple] = set()  # lane keys with pending requests
        self._pending_ticks: Dict[int, int] = {}  # host -> coalesced ticks
        # ---- serving-plane backpressure mirrors --------------------------
        # refreshed once per _pack from data the pack pass already touches
        # (zero device syncs); read lock-free by pressure_stats — a torn
        # read costs one stale sample, never a wrong decision stream
        self._p_inbox_rows = 0
        self._p_inbox_lanes = 0
        self._p_staged_backlog = 0
        # ---- loop-thread-only work sets ----------------------------------
        self._carry: Set[_Lane] = set()  # lanes with leftover staged work
        self._catchups: Set[_Lane] = set()  # lanes replaying host log
        self._snapfb: Set[_Lane] = set()  # lanes with in-flight snapshots
        # nodes with completed snapshot work awaiting finalization on this
        # loop (cf. node.go processSaveStatus; scalar nodes do this in
        # step_node)
        self._snap_status: Set[VectorNode] = set()
        self._snap_status_mu = threading.Lock()
        self._alloc_buffers()
        self._alloc_mirrors()
        # HBM census (profile.DeviceCensus): plane bytes are STATIC
        # tensor metadata (shapes never change over the engine's life;
        # the residual inbox joins them when auto first builds it),
        # reported from `.nbytes` — device_census() later folds
        # the logical log fill from the decode-maintained mirrors, so
        # reading the census costs zero device syncs at any point
        self.census = DeviceCensus()
        self._report_planes()
        # worker pools for apply + snapshot work (same split as ExecEngine)
        self._n_task = num_task_workers or min(
            soft.step_engine_task_worker_count, 4
        )
        self._n_snap = num_snapshot_workers
        self.task_ready = WorkReady(self._n_task)
        self.snapshot_ready = WorkReady(self._n_snap)
        self._threads: List[threading.Thread] = []
        t = threading.Thread(target=self._loop, name="vec-step", daemon=True)
        t.start()
        self._threads.append(t)
        for i in range(self._n_task):
            t = threading.Thread(
                target=self._task_worker_main, args=(i,), name=f"vtask-{i}",
                daemon=True,
            )
            t.start()
            self._threads.append(t)
        for i in range(self._n_snap):
            t = threading.Thread(
                target=self._snapshot_worker_main, args=(i,), name=f"vsnap-{i}",
                daemon=True,
            )
            t.start()
            self._threads.append(t)

    def _build_multi(self, steps: int) -> None:
        """The K-step program and the residual inbox it carries from one
        launch to the next. An integer steps_per_sync builds them with
        the engine; auto at the first route rebuild that finds a peer to
        route, so an engine whose peers are all elsewhere never does, and
        a co-hosted deployment traces and compiles the program at its
        first all-routable launch, during bring-up."""
        G = self.kcfg.groups
        if self._mesh is not None:
            # K-step kernel over the mesh: cross-shard lane traffic
            # moves device-to-device inside the launch (all-gather);
            # the host path stays the fallback for lanes the route
            # table marks -1
            self._multi_fn = make_sharded_multi_step_fn(
                self.kcfg, steps, self._mesh
            )
            name = f"multi_step[g{G}.k{steps}.d{self._mesh_devices}]"
        else:
            # one chip: the launch puts two slabs and fetches two
            self._multi_fn = make_packed_multi_step_fn(self.kcfg, steps)
            self._out_slabs = launch_out_slabs(self.kcfg)
            name = f"multi_step[g{G}.k{steps}]"
        # no comma in the name: it becomes a Prometheus label value
        compile_watch().register(name, self._multi_fn)
        resid = make_empty_inbox(self.kcfg)
        if self._sharding is not None:
            # the residual inbox must live on the mesh like the rest
            # of the lane state, or every launch would reshard it
            self._resid = jax.device_put(
                resid, jax.tree_util.tree_map(self._sharding, resid)
            )
        else:
            self._resid = jax.device_put(resid)
        if self.census is not None:
            self._report_planes()  # built after the engine: auto

    def _report_planes(self) -> None:
        """The device planes this engine holds, to its HBM census."""
        planes = {
            f"state.{name}": int(arr.nbytes)
            for name, arr in self._state._asdict().items()
        }
        if self._resid is not None:
            for name, arr in self._resid._asdict().items():
                planes[f"resid.{name}"] = int(arr.nbytes)
        staging = sum(
            int(plane.nbytes)
            for plane in list(self._buf.values()) + [self._ticks]
        )
        self.census.set_planes(
            planes,
            log_planes=("state.log_term", "state.log_is_cc"),
            devices=max(1, self._mesh_devices),
            log_window=self.kcfg.log_window,
            host_staging_bytes=staging,
        )

    def _alloc_buffers(self) -> None:
        # numpy staging buffers for a launch's inputs: the inbox, the tick
        # plane and the K>1 route/delta planes. ONE set in every loop: a
        # step's output is fetched before the next _pack rewrites them, so
        # the device has consumed them by then. Every plane is a view
        # into one of two slabs (ops/slab.py), which a one-chip K-step
        # launch puts whole; the other launches put the planes.
        slabs = launch_in_slabs(self.kcfg)
        self._in_slabs = (
            np.zeros(slabs.int_shape, np.int32),
            np.zeros(slabs.bool_shape, bool),
        )
        self._host_inbox, self._ticks, self._np_route, self._np_rdelta = (
            slabs.unpack(*self._in_slabs)
        )
        self._host_inbox.mtype.fill(MSG.NONE)
        self._np_route.fill(-1)
        self._buf = self._host_inbox._asdict()
        # columnar row staging for _pack: rows accumulate as python column
        # lists and land in the numpy planes as ONE fancy-indexed scatter
        # per plane (_flush_staged_rows) — list appends are ~4x cheaper
        # than per-row scalar numpy stores across ten planes
        self._rows = {
            "g": [], "k": [], "mtype": [], "from_slot": [], "term": [],
            "log_index": [], "log_term": [], "commit": [], "reject": [],
            "hint": [], "hint_high": [], "n_entries": [], "ents": [],
        }
        if self._sharding is not None:
            self._inbox_shardings = (
                jax.tree_util.tree_map(self._sharding, self._host_inbox),
                self._sharding(self._ticks),
            )
            if self._multi > 1:
                # the K>1 transfer also ships the route/delta planes
                self._multi_shardings = self._inbox_shardings + (
                    self._sharding(self._np_route),
                    self._sharding(self._np_rdelta),
                )

    def _alloc_mirrors(self) -> None:
        """Whole-G numpy mirrors of per-lane protocol state, refreshed from
        the StepOutput once per step (device units where applicable)."""
        G = self.kcfg.groups
        self._lane_by_g: List[Optional[_Lane]] = [None] * G
        self._m_base = np.zeros(G, np.int64)  # real = device + base
        self._m_devfirst = np.ones(G, np.int64)  # device-units first index
        self._m_term = np.zeros(G, np.int32)
        self._m_role = np.full(G, ROLE.FOLLOWER, np.int32)
        self._m_leader = np.zeros(G, np.int32)  # slot+1, 0=none
        self._m_commit = np.zeros(G, np.int64)  # device units
        self._m_last = np.zeros(G, np.int64)  # device units
        self._m_tick_cap = np.ones(G, np.int32)  # election_rtt per lane
        self._m_active = np.zeros(G, bool)
        self._m_snap_every = np.zeros(G, np.int64)  # cfg.snapshot_entries
        self._m_applied_since = np.zeros(G, np.int64)
        self._m_snap_pending = np.zeros(G, bool)
        self._m_quiesced = np.zeros(G, bool)
        self._m_host = np.zeros(G, np.int32)  # owning handle id per lane
        self._m_clock_ok = np.ones(G, bool)  # mirror of device clock_ok
        # lease validity after the last decoded step (StepOutput.lease_ok):
        # read by the lease-only probe (NodeHost.lease_read) with zero
        # device syncs; a stale read is inherent to probing and safe — the
        # serve itself is decided by the kernel, not this mirror
        self._m_lease_ok = np.zeros(G, bool)
        # engine-clock tick of the lane's last LEADER transition: feeds the
        # per-lane ticks_since_leader_change gauge (lane_stats) with zero
        # device syncs — updated only for lanes the decode phase already
        # iterates as changed
        self._m_leader_change_tick = np.zeros(G, np.int64)
        # cumulative per-lane protocol-event counters: the kernel's
        # per-step u32 deltas (StepOutput.counters, one CTR.* column per
        # event) summed here by the decode fold — loop-thread writes,
        # lock-free reads via counter_stats/lane_counters (a torn read
        # costs one stale sample on an export path, never a decision)
        # lanes whose state machine is being restored from a snapshot:
        # they are given no ticks (see the tick plane in _run_once)
        self._m_recovering = np.zeros((G,), bool)
        # ---- the progress watch (_watch_progress) ------------------------
        # the peer slots a lane would owe progress to if it led: voting
        # members other than itself, kept where the device's `voting`
        # plane is staged (_compute_activation, _stage_remap)
        P = self.kcfg.peers
        self._m_owes = np.zeros((G, P), bool)
        # what the last sweep saw (device units, as the StepOutput's) and
        # for how many sweeps running each debt has stood unpaid
        self._w_match = np.zeros((G, P), np.int32)
        self._w_commit = np.zeros((G,), np.int32)
        self._w_peer_age = np.zeros((G, P), np.int32)
        self._w_commit_age = np.zeros((G,), np.int32)
        # the lanes that led at the last sweep (a peer debt is a leading
        # lane's: the row of one that leads no more is cleared)
        self._w_led = np.zeros((G,), bool)
        # a sweep is a launch at least `_w_period` ticks of the engine's
        # clock after the sweep before it: the longest election timeout
        # among the lanes activated so far (see _watch_progress); between
        # two sweeps the debts stand as counted
        self._w_tick = 0
        self._w_period = 1
        self._w_peer_n = 0
        self._w_commit_n = 0
        # the apply debt is a level taken every _STALL_LAUNCHES-th sweep,
        # block of lanes by block in turn: the applied index each lane
        # had then (real units), whether it stood below its commit, and
        # which lanes stand stalled since
        self._w_sweeps = 0
        self._w_applied = np.zeros((G,), np.int64)
        self._w_apply_owed = np.zeros((G,), bool)
        self._w_apply_stalled = np.zeros((G,), bool)
        self._w_apply_n = 0
        self._ctr = np.zeros((G, CTR.COUNT), np.uint64)
        # what the lanes that have left had counted: counter_stats() stays
        # cumulative when a lane is freed or reused
        self._ctr_left = np.zeros((CTR.COUNT,), np.uint64)

    # ------------------------------------------------------- mirror helpers
    def _committed_real(self, g: int) -> int:
        return int(self._m_base[g] + self._m_commit[g])

    def _last_real(self, g: int) -> int:
        return int(self._m_base[g] + self._m_last[g])

    # --------------------------------------------------------- registration
    def add_node(self, node: VectorNode, host: int = 0) -> None:
        self.add_nodes([node], host)

    def add_nodes(self, nodes, host: int = 0) -> None:
        """Give each node a lane of `host`; the loop activates them in ONE
        batch (one scatter, one compile bucket, one launch that finds them
        all), however many a NodeHost's start_clusters brings."""
        lanes: List[_Lane] = []
        try:
            for node in nodes:
                lanes.append(self._take_lane(node, host))
        finally:
            if lanes:
                self._reconq.append(("activate", lanes))
                # dirty, not armed for request GC: a new node has no
                # requests yet, and its first one arms it
                # (set_node_ready)
                with self._dirty_mu:
                    self._dirty.update(lane.key for lane in lanes)
                self._kick()

    def _take_lane(self, node: VectorNode, host: int) -> _Lane:
        key = (host, node.cluster_id)
        for attempt in range(2):
            with self._lanes_mu:
                if self._free:
                    g = self._free.pop()
                    lane = _Lane(g, node, key=key)
                    self._lanes[key] = lane
                    self._host_lanes.setdefault(host, {})[key] = lane
                    self._lane_by_g[g] = lane
                    self._route[(node.cluster_id, node.node_id())] = lane
                    self._m_host[g] = host
                    self._lanes_gen += 1
                    node._vec_lane = lane
                    return lane
            if attempt == 0:
                # the free list can be momentarily empty while freed lanes
                # sit in the reconcile queue (stop_cluster immediately
                # followed by restart_cluster): drain the loop once so a
                # restart is never failed by its own predecessor's
                # not-yet-reaped lane
                self.drain(10.0)
        raise RuntimeError(
            f"vector engine lane capacity ({self.kcfg.groups}) exhausted"
        )

    def remove_node(self, key) -> None:
        with self._lanes_mu:
            lane = self._lanes.pop(key, None)
            if lane is not None:
                self._host_lanes[key[0]].pop(key, None)
                self._lanes_gen += 1
                rk = (lane.node.cluster_id, lane.node.node_id())
                if self._route.get(rk) is lane:
                    del self._route[rk]
        if lane is not None:
            self._reconq.append(("deactivate", lane))
            self._kick()

    def get_node(self, key):
        lane = self._lanes.get(key)  # one dict read: no lock to queue on
        return lane.node if lane is not None else None

    def lease_valid(self, key) -> bool:
        """Did this lane hold a live leader lease after the last decoded
        step? Mirror read (no device sync) for the lease-only probe;
        the authoritative serve/fallback decision stays in the kernel."""
        with self._lanes_mu:
            lane = self._lanes.get(key)
        return lane is not None and bool(self._m_lease_ok[lane.g])

    # -------------------------------------------------------------- wakeups
    def _kick(self) -> None:
        """Wake the loop for work queued just before. An event that is set
        needs no second set: the loop clears it before it drains, so what
        was queued before this look is drained by the iteration that
        follows. The look is a plain read; a set takes the event's lock,
        which every submitting thread and apply worker would otherwise
        queue on while the loop is busy."""
        if not self._ready.is_set():
            self._ready.set()

    def set_node_ready(self, key) -> None:
        with self._dirty_mu:
            self._dirty.add(key)
            self._gc_set.add(key)
        self._kick()

    def _wake(self, key) -> None:
        """Like set_node_ready but without arming request GC — the hot path
        for message delivery (messages alone never need a timeout sweep)."""
        with self._dirty_mu:
            self._dirty.add(key)
        self._kick()

    def global_tick(self, host: int = 0) -> None:
        """One logical tick for every lane of `host` (replaces per-lane
        LocalTick messages; the loop folds counts into the device tick
        array, per owning host)."""
        with self._dirty_mu:
            self._pending_ticks[host] = self._pending_ticks.get(host, 0) + 1
        self._kick()

    def set_task_ready(self, key) -> None:
        self.task_ready.notify(key)

    def set_snapshot_ready(self, key) -> None:
        self.snapshot_ready.notify(key)

    # ------------------------------------------------------ local delivery
    def try_local_deliver(self, m: Message) -> bool:
        """Deliver a wire message directly to a co-hosted lane of this core
        (same engine => same process), skipping the transport and codec
        entirely. This is the host half of SURVEY §7's 'co-hosted replica
        exchange': replicas that advance in one kernel step exchange their
        protocol traffic through the shared inbox, not the network.
        InstallSnapshot is excluded — snapshot images move through the
        streaming path so the receiver owns its on-disk copy."""
        if m.type == MT.INSTALL_SNAPSHOT:
            return False
        lane = self._route.get((m.cluster_id, m.to))
        if lane is None:
            return False
        if lane.key[0] in self._blocked_hosts:
            # the receiving NodeHost simulates a partition: co-hosted
            # traffic must drop exactly like the wire path does
            # (nodehost.handle_message_batch returns early when
            # partitioned)
            return True
        hook = self._local_drop_hook
        if hook is not None and hook(m):
            return True  # dropped by chaos hook
        node = lane.node
        if node.stopped or not node.mq.add(m):
            return False
        self._wake(lane.key)
        return True

    def try_local_deliver_many(self, msgs: List[Message]) -> List[Message]:
        """Bulk co-hosted delivery: group the batch by destination lane,
        enqueue each lane's messages under ONE queue lock, mark every
        receiver dirty under ONE engine lock and wake the loop once.
        Returns the messages that must ride the wire instead (no co-hosted
        lane, stopped node, or a full receive queue — the same per-message
        fallthrough try_local_deliver reports with False)."""
        rest: List[Message] = []
        by_lane: Dict[_Lane, List[Message]] = {}
        route = self._route
        blocked = self._blocked_hosts
        hook = self._local_drop_hook
        for m in msgs:
            if m.type == MT.INSTALL_SNAPSHOT:
                rest.append(m)
                continue
            lane = route.get((m.cluster_id, m.to))
            if lane is None:
                rest.append(m)
                continue
            if lane.key[0] in blocked:
                continue  # partitioned receiver: drop like the wire path
            if hook is not None and hook(m):
                continue  # dropped by chaos hook
            lst = by_lane.get(lane)
            if lst is None:
                lst = by_lane[lane] = []
            lst.append(m)
        if not by_lane:
            return rest
        woke = []
        for lane, ms in by_lane.items():
            node = lane.node
            if node.stopped:
                rest.extend(ms)
                continue
            taken = node.mq.add_many(ms)
            if taken < len(ms):
                rest.extend(ms[taken:])
            if taken:
                woke.append(lane.key)
        if woke:
            with self._dirty_mu:
                self._dirty.update(woke)
            self._kick()
        return rest

    def set_host_partitioned(self, host: int, partitioned: bool) -> None:
        if partitioned:
            self._blocked_hosts.add(host)
        else:
            self._blocked_hosts.discard(host)
        # multi-step: a partitioned host's lanes must drop out of the
        # on-device routing table (its traffic falls back to the host
        # path, where the partition drop applies)
        self._routes_dirty = True

    def set_clock_suspect(self, host: int, hold_s: float) -> None:
        """Clock-anomaly report from a host's tick worker (backward
        reading / backlog past the catch-up cap): every lane owned by
        `host` loses lease rights (clock_ok=False) until the hold
        expires — lease reads degrade to the ReadIndex quorum path,
        never to staleness. Cheap to call; the loop thread touches the
        device only on suspect-set transitions."""
        deadline = time.monotonic() + max(float(hold_s), 0.0)
        with self._dirty_mu:
            cur = self._clock_suspect.get(host, 0.0)
            self._clock_suspect[host] = max(cur, deadline)
        self._ready.set()

    def _apply_clock_suspect(self) -> None:
        """Loop-thread reconcile of the per-host suspect deadlines onto
        the per-lane clock_ok plane. No-op (one dict probe) while no host
        is suspect; while one is, a G-bool compare per iteration and a
        device write only when the lane set actually changes — including
        the final restore when the last hold expires."""
        if not self._clock_suspect and self._m_clock_ok.all():
            return
        now = time.monotonic()
        with self._dirty_mu:
            for h in [
                h for h, d in self._clock_suspect.items() if d <= now
            ]:
                del self._clock_suspect[h]
            bad = list(self._clock_suspect)
        if bad:
            want = ~np.isin(self._m_host, np.asarray(bad, np.int32))
        else:
            want = np.ones(self.kcfg.groups, bool)
        if not np.array_equal(want, self._m_clock_ok):
            self._m_clock_ok = want
            arr = jnp.asarray(want)
            if self._sharding is not None:
                arr = jax.device_put(arr, self._sharding(arr))
            self._state = self._state._replace(clock_ok=arr)

    def set_local_drop_hook(self, hook) -> None:
        """Install a chaos drop predicate over co-hosted delivery
        (hook(message) -> True drops it). None clears. While a hook is
        installed the multi-step engine disables on-device routing
        entirely: every co-hosted message must pass the hook, which only
        the host path can evaluate."""
        self._local_drop_hook = hook
        self._routes_dirty = True

    # ------------------------------------------------- host->device bridges
    def membership_changed(self, node: VectorNode) -> None:
        """Called on a task worker when a config change applies; the loop
        recomputes the canonical slot mapping from the SM membership."""
        self._reconq.append(("membership", node))
        self._kick()

    def snapshot_restored(self, node: VectorNode, ss: Snapshot) -> None:
        self._reconq.append(("restore", node, ss))
        self._kick()

    def cc_processed(self, node: VectorNode) -> None:
        self._reconq.append(("cc_done", node))
        self._kick()

    def recover_done(self, node: VectorNode) -> None:
        self._reconq.append(("recover_done", node))
        self._kick()

    # ---------------------------------------------------------------- loop
    def _loop(self) -> None:
        period = 0.002
        wd = self.watchdog
        prof = self.profiler
        while not self._stopped.is_set():
            prof.new_iteration()
            prof.begin("wait")
            self._ready.wait(period)
            self._ready.clear()
            if self._stopped.is_set():
                break
            t0 = wd.iter_begin()
            self._last_tick_burst = 0
            try:
                self._run_once()
            except Exception:
                import traceback

                self._sstats["loop_exceptions"] += 1
                traceback.print_exc()
            wd.iter_end(t0, ticks=self._last_tick_burst, steps=self._multi)
        try:
            if self._discard_pending:
                # crash teardown (stop(flush=False)): the un-decoded
                # in-flight step dies undecoded — a SIGKILL'd process
                # would never have fanned it out or saved it, and chaos
                # restarts must not silently grant that durability
                self._pending = None
            else:
                self._flush_pending()  # the last step's saves must land
        except Exception:
            import traceback

            traceback.print_exc()
        self._record_bodies.clear()
        prof.close()

    def snapshot_status_ready(self, node) -> None:
        with self._snap_status_mu:
            self._snap_status.add(node)
        self._ready.set()

    def _run_once(self) -> None:
        prof = self.profiler
        prof.begin("prepare")
        # overlapped K=1: the step the last iteration launched is fetched
        # and decoded FIRST, before the dirty set is swapped, so what it
        # hands to co-hosted lanes (set_node_ready marks them dirty) and
        # the mirrors it refreshes are in this iteration's pack: one
        # launch a Raft hop, as in the unoverlapped loop. Only its
        # maintain is owed until this iteration's launch is out, and
        # hides the kernel: it acknowledges nothing, and all it sends is
        # the host-side catch-up of a peer that fell out of the device
        # window. It is owed inside this call only: every return below
        # pays it. (An exception in between loses it; every trigger in
        # _maintain is a level, so the next step's maintain makes it up.)
        owed = self._decode_pending()
        if owed is not None:
            prof.begin("prepare")
        # reconciles, snapshot finalization and rebase rewrite per-group
        # mirrors (_m_base/_m_last/_lane_by_g) that _maintain reads beside
        # the step's output, so these rare paths take a step whose
        # maintain has run, and nothing in flight
        if self._reconq or self._snap_status or self._rebase_due:
            if owed is not None:
                self._decode_maintain(owed)
                owed = None
                prof.begin("prepare")
            if self._rebase_due:
                self._rebase_due = False
                self._do_rebase()
        self._apply_reconciles()
        self._apply_clock_suspect()
        with self._snap_status_mu:
            snap_done, self._snap_status = self._snap_status, set()
        for node in snap_done:
            # lint: allow(locks/lock-in-hot-loop) snapshot completions:
            # empty ~every step, bounded by in-flight snapshot workers
            with node._mu:
                node._process_snapshot_status()
        if self._routes_dirty and (self._auto or self._multi > 1):
            self._rebuild_routes()
        if self._auto:
            # the engine's own choice, at the launch boundary: the whole
            # commit in one launch while the device can carry every
            # message of it, and once more after that to take in what
            # the last such launch left parked there (the table routes
            # nothing by then, so that launch parks nothing new)
            steps = (
                _AUTO_STEPS
                if self._all_routable or self._m_resid.any()
                else 1
            )
            if steps != self._multi:
                if owed is not None:
                    # up: nothing in flight and the mirrors current
                    # before a pack that reads residual occupancy
                    self._decode_maintain(owed)
                    owed = None
                    prof.begin("prepare")
                self._multi = steps
                self._overlap = self._overlap_k1 and steps == 1
        with self._dirty_mu:
            dirty = self._dirty
            self._dirty = set()
            tick_counts = self._pending_ticks
            self._pending_ticks = {}
            ticks = max(tick_counts.values()) if tick_counts else 0
            gc_cids = list(self._gc_set) if ticks else ()
        if ticks:
            for _ in range(ticks):
                self.clock.increase_tick()
            self._run_gc(gc_cids)
        work = self._carry
        self._carry = set()
        if dirty:
            with self._lanes_mu:
                for cid in dirty:
                    lane = self._lanes.get(cid)
                    if lane is not None and lane.active:
                        work.add(lane)
        work |= self._catchups
        prof.begin("pack")
        had, packs = self._pack(work)
        if not had:
            skip = False
            if ticks == 0:
                skip = True
            else:
                # no active lanes: ticks have nobody to advance
                act = self._m_active
                if not act.any():
                    skip = True
                # a fully-quiesced fleet needs no kernel step for ticks:
                # every timer is frozen, so the step would be a no-op (this
                # is what makes 10k+ idle lanes cost zero host AND device
                # work)
                elif bool(np.all(~act | self._m_quiesced)):
                    skip = True
            if skip and self._m_resid.any():
                # device-routed messages from the previous super-step's
                # last inner step are parked in the residual inbox: they
                # must be consumed even with no fresh host work
                skip = False
            if skip:
                if owed is not None:
                    self._decode_maintain(owed)  # no launch to hide behind
                return
        prof.begin("dispatch")
        if ticks:
            # per-lane tick counts come from the OWNING host's counter (a
            # shared core serves several NodeHosts, each with its own tick
            # thread); clamped per lane at its catch-up burst cap, and the
            # EXCESS backlog is shed — not deferred — so a stall charges
            # at most one small burst to each timer and the randomized
            # election spread survives (see _catchup_tick_cap)
            if self._next_host <= 1:
                per_lane = ticks
            else:
                hv = np.zeros(self._next_host + 1, np.int32)
                for h, c in tick_counts.items():
                    hv[h] = c
                per_lane = hv[self._m_host]
            np.minimum(self._m_tick_cap, per_lane, out=self._ticks)
            # a lane being restored from a snapshot is not stepped in the
            # reference (node.go: a recovering node's step is skipped):
            # its messages are held host-side, so its election timer must
            # stand too, or a restore that outlasts the timeout makes the
            # replica campaign against the leader that is bringing it up
            self._ticks *= self._m_active & ~self._m_recovering
            self._last_tick_burst = ticks
            if ticks > 1 and bool(
                np.any((per_lane > self._m_tick_cap) & self._m_active)
            ):
                # some ACTIVE lane's own host backlog exceeded its cap
                # (per_lane broadcasts: scalar for a single host, the
                # owning host's column otherwise)
                self.watchdog.tick_burst_clamped()
        else:
            self._ticks.fill(0)
        # ONE device_put over the (inbox, ticks) pytree: 12 small host
        # arrays ship in a single batched transfer instead of 12 dispatch
        # round-trips (per-call overhead dominates at these sizes); the
        # Inbox views and sharding pytree were built once at allocation.
        # On sampled iterations the put and the jitted call (which
        # returns futures) are timed apart, as sub-spans of dispatch.
        self.launch_no += 1
        sampling = prof.sampling
        if sampling:
            prof.fold("n.launches", 1)
            prof.fold("n.launch_steps", self._multi)
            prof.fold("n.seam_buffers", self._seam_arrays())
        t0 = time.monotonic() if sampling else 0.0
        if self._multi > 1 and self._mesh is None:
            # K protocol steps on one chip: the staging planes go as
            # their two slabs, and the outputs come back as two, so the
            # seam's cost per buffer is paid four times a launch
            ints, bools = jax.device_put(self._in_slabs)
            t1 = time.monotonic() if sampling else 0.0
            self._state, out_ints, out_bools, self._resid = self._multi_fn(
                self._state, ints, bools, self._resid
            )
            if sampling:
                self._add_seam("put", "launch", t0, t1)
            o, pl, rc = self._fetch_super((out_ints, out_bools))
            self._m_resid = rc
            self._decode_super(work, packs, o, pl)
            return
        if self._multi > 1:
            # K protocol steps per launch over the mesh: the route/delta
            # planes ride the same batched transfer (small G x P arrays;
            # rebuilt host-side only when lane topology changes)
            payload = (
                self._host_inbox, self._ticks,
                self._np_route, self._np_rdelta,
            )
            with _MESH_LAUNCH_MU:
                inbox, tarr, route, rdelta = jax.device_put(
                    payload, self._multi_shardings
                )
                t1 = time.monotonic() if sampling else 0.0
                self._state, outs, plans, self._resid, resid_count = (
                    self._multi_fn(
                        self._state, inbox, tarr, self._resid, route, rdelta
                    )
                )
                if sampling:
                    self._add_seam("put", "launch", t0, t1)
                o, pl, rc = self._fetch_super((outs, plans, resid_count))
            self._m_resid = rc
            self._decode_super(work, packs, o, pl)
            return
        if self._sharding is not None:
            inbox, tarr = jax.device_put(
                (self._host_inbox, self._ticks), self._inbox_shardings
            )
        else:
            inbox, tarr = jax.device_put((self._host_inbox, self._ticks))
        t1 = time.monotonic() if sampling else 0.0
        self._state, out = self._step_fn(self._state, inbox, tarr)
        if sampling:
            self._add_seam("put", "launch", t0, t1)
        if self._overlap:
            # pipeline: the device computes step t (jax dispatch is async,
            # `out` is a future) under step t-1's maintain, the loop's
            # wait and the next prepare; the next iteration fetches and
            # decodes it before it packs. The eager compaction in
            # _maintain lands on the state step t returns, and
            # _m_devfirst with it: _pack sees both one maintain behind.
            # Park the launched step FIRST so a maintain that raises
            # cannot lose it.
            self._pending = (work, packs, out)
            if owed is not None:
                self._decode_maintain(owed)
        else:
            o = self._fetch_output(out)
            self._decode(work, packs, o)
            self._decode_maintain(o)

    def _add_seam(self, first: str, second: str, t0: float, t1: float) -> None:
        """Two consecutive sub-spans of the host<->device seam, begun at
        t0 and t1 and ending now (sampled iterations only)."""
        t2 = time.monotonic()
        self.profiler.add(first, t1 - t0)
        self.profiler.add(second, t2 - t1)

    def _seam_arrays(self) -> int:
        """The arrays the coming launch moves across the seam, put plus
        fetched: two slabs each way at K steps on one chip; a plane each
        in the one-step loop (inbox, ticks; StepOutput) and over the mesh
        (those, route and rdelta; RoutePlan, residual occupancy)."""
        if self._multi == 1:
            return len(Inbox._fields) + 1 + len(StepOutput._fields)
        if self._out_slabs is not None:
            return 2 + 2
        return (
            len(Inbox._fields) + 3 + len(StepOutput._fields)
            + len(RoutePlan._fields) + 1
        )

    def _fetch_output(self, out) -> dict:
        """ONE consolidated device->host transfer for the whole StepOutput,
        shared by the overlap and non-overlap paths. The planes ship as a
        single batched fetch rather than per-plane masked gets: every plane
        is G- or GxP-sized, so per-dispatch overhead dominates transfer
        cost, and each decode phase masks its own work list host-side from
        send_flags/dirty lanes. On sampled iterations the wait for the
        kernel and the copy down are timed apart (device_wait, copy): the
        extra block_until_ready, on one plane of the output, waits for
        what the device_get would have waited for, inside this blessed
        seam."""
        prof = self.profiler
        prof.begin("fetch")
        if prof.sampling:
            t0 = time.monotonic()
            jax.block_until_ready(out[0])  # one program: ready together
            t1 = time.monotonic()
            o = jax.device_get(out)._asdict()
            self._add_seam("device_wait", "copy", t0, t1)
        else:
            o = jax.device_get(out)._asdict()
        note_seam_sync()  # runtime sync audit: the ONE blessed transfer
        return o

    def _fetch_super(self, got):
        """The multi-step twin of _fetch_output: ONE consolidated
        device->host transfer for the whole K-step super-step (the
        stacked per-step StepOutput planes, the per-step route plans and
        the residual-inbox occupancy ship together): on one chip the
        launch's two slabs, cut here into views of those planes, over
        the mesh the planes themselves. This is the other blessed sync
        seam — it fires once per K protocol steps."""
        prof = self.profiler
        prof.begin("fetch")
        if prof.sampling:
            t0 = time.monotonic()
            jax.block_until_ready(got[-1])  # ready with the rest
            t1 = time.monotonic()
            got = jax.device_get(got)
            self._add_seam("device_wait", "copy", t0, t1)
        else:
            got = jax.device_get(got)
        note_seam_sync()  # runtime sync audit: one transfer per K steps
        if self._out_slabs is not None:
            o, pl, occ = self._out_slabs.unpack(*got)
            got = o, pl, occ[-1]
        o, pl, rc = got
        return o._asdict(), pl._asdict(), np.array(rc, np.int32)

    def _decode_pending(self) -> Optional[dict]:
        """Fetch the in-flight step, if there is one, and decode all of it
        but its maintain; returns its output, to which _decode_maintain is
        still owed. The step leaves _pending first: a decode that raises
        is not decoded twice."""
        pending, self._pending = self._pending, None
        if pending is None:
            return None
        work, packs, out = pending
        o = self._fetch_output(out)
        self._decode(work, packs, o)
        return o

    def _flush_pending(self) -> None:
        o = self._decode_pending()
        if o is not None:
            self._decode_maintain(o)

    def _run_gc(self, gc_cids) -> None:
        """Request-timeout pass over lanes with outstanding requests only
        (the reference runs four gc calls per node per tick; idle lanes
        here cost nothing)."""
        if not self.clock.should_gc():
            return
        drop = []
        for cid in gc_cids:
            with self._lanes_mu:
                lane = self._lanes.get(cid)
            if lane is None:
                drop.append(cid)
                continue
            node = lane.node
            node.pending_proposals.gc()
            node.pending_read_indexes.gc()
            node.pending_config_change.gc()
            node.pending_snapshot.gc()
            node.gc_batches()
            if lane.ri_pending:
                # engine-side ctx routing entries die with their batches
                # (timed-out forwarded reads would otherwise leak here)
                pri = node.pending_read_indexes
                dead = [
                    enc
                    for enc, ctx in lane.ri_pending.items()
                    if not pri.has_ctx(ctx)
                ]
                for enc in dead:
                    del lane.ri_pending[enc]
                    lane.ri_lat.pop(enc, None)
            if not (
                node.pending_proposals.has_pending()
                or node.pending_read_indexes.has_pending()
                or node.pending_config_change.has_pending()
                or node.pending_snapshot.has_pending()
                or node._batches
            ):
                drop.append(cid)
        if drop:
            with self._dirty_mu:
                # a request registered concurrently re-adds its cid to
                # _dirty AND _gc_set (set_node_ready); keep those — else
                # the new request's timeout gc would never run
                self._gc_set.difference_update(set(drop) - self._dirty)

    # ---------------------------------------------------------------- pack
    def _pack(self, lanes: Set[_Lane]):
        K = self.kcfg.inbox_depth
        E = self.kcfg.max_entries_per_msg
        W = self.kcfg.log_window
        buf = self._buf
        buf["mtype"].fill(MSG.NONE)
        buf["n_entries"].fill(0)
        buf["entry_cc"].fill(False)
        # self-healing like the old direct writes: rows staged by an
        # iteration that died mid-pack (loop catches and continues) must
        # not replay into this step's planes as phantom kernel messages
        for col in self._rows.values():
            col.clear()
        had = bool(self._catchups)
        packs: Dict[_Lane, Dict[int, tuple]] = {}
        # what this launch carries, lane by lane, for the sampled
        # iterations' n.* counters (plain local ints; folded at the end)
        counting = self.profiler.sampling
        n_lanes = n_ents = n_hot = n_cut = n_reads = n_ctxs = 0
        # the fullest leader lane: (last - commit, commit moved since its
        # last sampled pack), the most of the first, then of the second
        hot_wait = hot_moved = 0
        # per-lane mirror reads gathered ONCE as columns (per-element
        # int(arr[g]) reads were a measured hot spot at fleet widths)
        work = list(lanes)
        if work:
            w_gs = [lane.g for lane in work]
            cols = zip(
                work,
                self._m_quiesced[w_gs].tolist(),
                self._m_role[w_gs].tolist(),
                self._m_leader[w_gs].tolist(),
                self._m_last[w_gs].tolist(),
                self._m_commit[w_gs].tolist(),
                self._m_devfirst[w_gs].tolist(),
                self._m_base[w_gs].tolist(),
                # multi-step: device-routed residual messages occupy the
                # low inbox slots of the NEXT super-step; host rows pack
                # after them (all-zero at K=1)
                self._m_resid[w_gs].tolist(),
            )
        else:
            cols = ()
        for (
            lane, g_quiesced, g_role, g_leader, g_last, g_commit, g_devfirst,
            b, g_resid,
        ) in cols:
            node = lane.node
            g = lane.g
            lane.pack_info = {}
            # queue drains gated on lock-free emptiness probes: producers
            # mark the lane dirty AFTER enqueueing, so a racy miss is
            # re-delivered next iteration; most dirty lanes carry only ONE
            # kind of event and skip the other queues' lock round-trips
            if node.mq.has_pending():
                msgs, _ = node.mq.get()
                lane.msg_backlog.extend(msgs)
            if lane.recovering:
                # an InstallSnapshot recover is in flight: hold everything
                # until the device lane is reconciled (cf. node.go:1199)
                if lane.has_staged():
                    self._carry.add(lane)
                continue
            # drain API queues into the staging deques
            if node.incoming_proposals.has_pending():
                lane.staged_props.extend(node.incoming_proposals.get())
            if node.incoming_reads.has_pending():
                lane.staged_reads.extend(node.incoming_reads.get())
            if node._cc_queue:
                # lint: allow(locks/lock-in-hot-loop) config changes: the
                # lock-free emptiness probe above keeps steady-state lanes
                # off this lock; only lanes with a queued cc pay it
                with node._mu:
                    ccs, node._cc_queue = node._cc_queue, []
                for cc, key in ccs:
                    ce = Entry(
                        type=EntryType.CONFIG_CHANGE,
                        cmd=encode_config_change(cc),
                        key=key,
                    )
                    lane.staged_ccs.append((ce, key))
            k = g_resid
            # a quiesced lane with fresh host work gets a wake NOOP (the
            # kernel exits quiesce on any non-heartbeat inbox message; the
            # reference wakes through exitQuiesce on activity, quiesce.go)
            if (
                g_quiesced
                and k < K
                and (lane.has_staged() or node.pending_leader_transfer.peek())
            ):
                self._stage_row(
                    g, k, MSG.NOOP, from_slot=max(lane.self_slot(), 0)
                )
                had = True
                k += 1
            # 1. wire/protocol messages first. A leader whose followers'
            # acknowledgements would fill the inbox keeps one slot for a
            # row of its own proposals (if its window has room) and one
            # for its ReadIndex context: at K = 4 the two Replicate and
            # two heartbeat responses of a step otherwise starve both for
            # as long as anything is in flight, and a saturated lane
            # commits a window's worth every eight launches (PERF.md,
            # PR 26). What waits among the wire messages is made
            # cumulative first, so it never piles up.
            is_leader = g_role == ROLE.LEADER
            if counting and is_leader:
                seen = b + g_commit
                moved = 0
                if lane.commit_seen is not None:
                    moved = max(seen - lane.commit_seen, 0)
                lane.commit_seen = seen
                wait = g_last - g_commit
                if wait > hot_wait or (wait == hot_wait and moved > hot_moved):
                    hot_wait, hot_moved = wait, moved
            wire_end = K
            if is_leader and len(lane.msg_backlog) + k > K - 2:
                own = bool(lane.staged_reads) + bool(
                    lane.staged_props
                    and W - 1 - (g_last - g_devfirst + 1) > lane.packed_pending
                )
                if own and len(lane.msg_backlog) + k > K - own:
                    _coalesce_acks(lane.msg_backlog)
                    wire_end = max(K - own, k + 1)
            # A follower takes no more Replicate entries in a step than
            # its device window has room for. The leader's own window
            # bounds what device replication sends, but not a backlog of
            # host-log catch-up Replicates released at once (held while a
            # snapshot restored, or sent one a launch while the launches
            # were slow): five rows of 64 into a window of 256 wrapped
            # the ring, and the replica lost entries it had acknowledged.
            # What does not fit waits, in order, for the next step.
            room = W - 1 - (g_last - g_devfirst + 1)
            tail = g_last
            held = None
            while lane.msg_backlog and k < wire_end:
                m = lane.msg_backlog.popleft()
                if m.type == MT.REPLICATE and m.entries and not is_leader:
                    if held is not None:
                        held.append(m)  # behind one that waits: in order
                        continue
                    # (one that leaves a gap is the kernel's to reject:
                    # the leader learns where this replica stands from it)
                    if m.log_index - b <= tail:
                        grow = (
                            m.log_index - b + min(len(m.entries), E) - tail
                        )
                        if grow > room:
                            held = [m]
                            continue
                        if grow > 0:
                            room -= grow
                            tail += grow
                k_used = self._pack_wire(lane, m, k, b)
                if k_used:
                    had = True
                    k += 1
            if held:
                lane.msg_backlog.extendleft(reversed(held))
            leader_nid = lane.rev.get(g_leader - 1)
            # 2. one config change per step (lone message; host invariant)
            if k < K and lane.staged_ccs and not lane.cc_inflight:
                if is_leader:
                    ce, key = lane.staged_ccs.popleft()
                    self._stage_row(
                        g, k, MSG.PROPOSE, from_slot=lane.self_slot(),
                        n_entries=1,
                    )
                    self._rows["ents"].append((g, k, None, (True,)))
                    lane.pack_info[k] = ("cc", ce, key)
                    lane.cc_inflight = True
                    lane.packed_pending += 1
                    had = True
                    k += 1
                elif leader_nid is not None and leader_nid != node.node_id():
                    while lane.staged_ccs:
                        ce, key = lane.staged_ccs.popleft()
                        node._send_message(
                            Message(
                                type=MT.PROPOSE,
                                cluster_id=node.cluster_id,
                                to=leader_nid,
                                from_=node.node_id(),
                                entries=[ce],
                            )
                        )
            # 3. proposals — throttled to the device window's free space so
            # the kernel never has to drop for lack of room (minus 1 slot
            # of slack for a concurrent new-leader noop append); what
            # doesn't fit stays staged and re-packs after compaction
            k_wire = k
            if lane.staged_props:
                if is_leader:
                    free = (
                        W - 1 - (g_last - g_devfirst + 1)
                        - lane.packed_pending
                    )
                    lane_ents = 0
                    # one context confirms every read staged: it is never
                    # the proposals that take its slot
                    k_end = K - 1 if lane.staged_reads else K
                    while lane.staged_props and k < k_end and free > 0:
                        ents = []
                        cap = min(E, free)
                        while lane.staged_props and len(ents) < cap:
                            e = lane.staged_props.popleft()
                            if e.lat is not None:
                                # sampled: it leaves the queue for the
                                # launch this pack is building
                                e.lat.t_pack = time.monotonic()
                                e.lat.n_pack = self.launch_no + 1
                            ents.append(e)
                        free -= len(ents)
                        lane_ents += len(ents)
                        lane.packed_pending += len(ents)
                        self._stage_row(
                            g, k, MSG.PROPOSE, from_slot=lane.self_slot(),
                            n_entries=len(ents),
                        )
                        lane.pack_info[k] = ("prop", ents)
                        had = True
                        k += 1
                    if counting:
                        n_ents += lane_ents
                        if lane_ents > n_hot:
                            n_hot = lane_ents
                        if lane.staged_props:  # cut by `free` or by K
                            n_cut += 1
                elif leader_nid is not None and leader_nid != node.node_id():
                    ents = list(lane.staged_props)
                    lane.staged_props.clear()
                    for i in range(0, len(ents), 64):
                        node._send_message(
                            Message(
                                type=MT.PROPOSE,
                                cluster_id=node.cluster_id,
                                to=leader_nid,
                                from_=node.node_id(),
                                entries=ents[i : i + 64],
                            )
                        )
            # 4. reads
            if lane.staged_reads:
                if is_leader and lane.self_slot() >= 0:
                    if k < K:
                        states = list(lane.staged_reads)
                        lane.staged_reads.clear()
                        ctx = node.pending_read_indexes.next_ctx()
                        if node.pending_read_indexes.bind_queued_states(
                            states, ctx
                        ):
                            enc = _enc_ctx(lane.self_slot(), ctx.low)
                            lane.ri_pending[enc] = ctx
                            self._stamp_reads_packed(lane, enc, states)
                            if counting:
                                n_reads += len(states)
                                n_ctxs += 1
                            self._stage_row(
                                g, k, MSG.READ_INDEX,
                                from_slot=lane.self_slot(), hint=enc[0],
                                hint_high=enc[1],
                            )
                            had = True
                            k += 1
                elif leader_nid is not None and leader_nid != node.node_id():
                    states = list(lane.staged_reads)
                    lane.staged_reads.clear()
                    ctx = node.pending_read_indexes.next_ctx()
                    if node.pending_read_indexes.bind_queued_states(states, ctx):
                        enc = _enc_ctx(lane.self_slot(), ctx.low)
                        lane.ri_pending[enc] = ctx
                        self._stamp_reads_packed(lane, enc, states)
                        if counting:
                            n_reads += len(states)
                            n_ctxs += 1
                        node._send_message(
                            Message(
                                type=MT.READ_INDEX,
                                cluster_id=node.cluster_id,
                                to=leader_nid,
                                from_=node.node_id(),
                                hint=enc[0],
                                hint_high=enc[1],
                            )
                        )
            if counting and k > k_wire:  # its clients' rows, not its peers'
                n_lanes += 1
            # 5. leadership transfer
            target = node.pending_leader_transfer.get()
            if target is not None and k < K:
                tslot = lane.slots.get(target, -1)
                if tslot >= 0:
                    self._stage_row(
                        g, k, MSG.LEADER_TRANSFER,
                        from_slot=lane.self_slot(), hint=tslot + 1,
                    )
                    had = True
                    k += 1
            # lanes with leftover staged work re-pack next iteration
            # (K exhausted, or a leaderless lane waiting for an election)
            if lane.has_staged():
                self._carry.add(lane)
            if lane.pack_info:
                packs[lane] = lane.pack_info
        # serving backpressure mirrors: rows packed vs this step's lane
        # capacity, and the staged backlog the carry set drags into the
        # next step (leftover staged work means the inbox could not drain
        # the offered load — the engine-side saturation signal). Row
        # count captured BEFORE the flush clears the staging columns.
        self._p_inbox_rows = len(self._rows["g"])
        self._p_inbox_lanes = len(work)
        backlog = 0
        for lane in self._carry:
            backlog += (
                len(lane.staged_props)
                + len(lane.staged_reads)
                + len(lane.staged_ccs)
            )
        self._p_staged_backlog = backlog
        if counting and had:  # a launch follows: one record a launch
            fold = self.profiler.fold
            fold("n.packs", 1)
            fold("n.lanes_packed", n_lanes)
            fold("n.entries_packed", n_ents)
            fold("n.hot_lane_entries", n_hot)
            fold("n.lanes_window_cut", n_cut)
            fold("n.staged_left", backlog)
            fold("n.hot_commit_entries", hot_moved)
            fold("n.hot_uncommitted_rows", -(-hot_wait // E))
            fold("n.reads_bound", n_reads)
            fold("n.read_contexts", n_ctxs)
        self._flush_staged_rows()
        return had, packs

    def _stamp_reads_packed(self, lane: _Lane, enc, states) -> None:
        """The sampled reads among `states` leave the node's queue under
        the context `enc`: on the leader into the launch this pack is
        building, on a follower toward the leader. Their traces wait in
        lane.ri_lat for the reads phase that confirms the context."""
        lts = None
        for rs in states:
            lt = rs.lat
            if lt is not None:
                if lts is None:
                    lts = []
                    now = time.monotonic()
                lt.t_pack = now
                lt.n_pack = self.launch_no + 1
                lts.append(lt)
        if lts is not None:
            lane.ri_lat[enc] = lts

    def _stage_row(
        self, g: int, k: int, mtype: int, from_slot: int = 0, term: int = 0,
        log_index: int = 0, log_term: int = 0, commit: int = 0,
        reject: bool = False, hint: int = 0, hint_high: int = 0,
        n_entries: int = 0,
    ) -> None:
        """Stage one inbox row as column appends; _flush_staged_rows lands
        the whole step's rows with one scatter per plane."""
        r = self._rows
        r["g"].append(g)
        r["k"].append(k)
        r["mtype"].append(mtype)
        r["from_slot"].append(max(from_slot, 0))
        r["term"].append(term)
        r["log_index"].append(log_index)
        r["log_term"].append(log_term)
        r["commit"].append(commit)
        r["reject"].append(reject)
        r["hint"].append(hint)
        r["hint_high"].append(hint_high)
        r["n_entries"].append(n_entries)

    def _flush_staged_rows(self) -> None:
        rows = self._rows
        gs = rows["g"]
        if gs:
            buf = self._buf
            ks = rows["k"]
            buf["mtype"][gs, ks] = rows["mtype"]
            buf["from_slot"][gs, ks] = rows["from_slot"]
            buf["term"][gs, ks] = rows["term"]
            buf["log_index"][gs, ks] = rows["log_index"]
            buf["log_term"][gs, ks] = rows["log_term"]
            buf["commit"][gs, ks] = rows["commit"]
            buf["reject"][gs, ks] = rows["reject"]
            buf["hint"][gs, ks] = rows["hint"]
            buf["hint_high"][gs, ks] = rows["hint_high"]
            buf["n_entries"][gs, ks] = rows["n_entries"]
            ents = rows["ents"]
            if ents:
                terms_buf = buf["entry_terms"]
                cc_buf = buf["entry_cc"]
                for g, k, terms, ccs in ents:
                    if terms is not None:
                        terms_buf[g, k, : len(terms)] = terms
                    cc_buf[g, k, : len(ccs)] = ccs
        for col in rows.values():
            col.clear()

    def _pack_wire(self, lane: _Lane, m: Message, k: int, b: int) -> bool:
        """Convert one wire message into a staged inbox row (b = the lane's
        device window base, gathered once per step by _pack). Returns False
        when the message was consumed host-side (snapshot, propose
        staging)."""
        g = lane.g
        t = m.type
        if t == MT.INSTALL_SNAPSHOT:
            self._handle_install_snapshot(lane, m)
            return False
        if t == MT.PROPOSE:
            for e in m.entries:
                if e.type == EntryType.CONFIG_CHANGE:
                    lane.staged_ccs.append((e, e.key))
                else:
                    lane.staged_props.append(e)
            return False
        if t == MT.QUIESCE:
            return False
        from_slot = lane.slot_of(m.from_, provisional=t == MT.REPLICATE or t == MT.HEARTBEAT or t == MT.REQUEST_VOTE or t == MT.REQUEST_PREVOTE or t == MT.TIMEOUT_NOW or t == MT.READ_INDEX_RESP)
        if from_slot < 0 and m.from_ != 0:
            return False  # unknown sender and no room to learn it
        if t == MT.REPLICATE:
            n = len(m.entries)
            E = self.kcfg.max_entries_per_msg
            if n > E:
                # split: re-queue the tail as a chained Replicate
                head, tail = m.entries[:E], m.entries[E:]
                rest = Message(
                    type=MT.REPLICATE, cluster_id=m.cluster_id, to=m.to,
                    from_=m.from_, term=m.term, commit=m.commit,
                    log_index=head[-1].index, log_term=head[-1].term,
                    entries=tail,
                )
                lane.msg_backlog.appendleft(rest)
                m.entries = head
                n = E
            # causal trace: the receive hop of a sampled entry's chain
            # (after the split so a trace in the requeued tail records
            # when ITS chunk packs)
            trace_id = 0
            for e in m.entries:
                if e.trace_id:
                    trace_id = e.trace_id
            if trace_id:
                flight_recorder().record(
                    "replicate_recv", cluster=lane.node.cluster_id,
                    node=lane.node.node_id(), from_node=m.from_,
                    trace=trace_id, launch=self.launch_no + 1,
                )
            self._stage_row(
                g, k, MSG.REPLICATE, from_slot=from_slot, term=m.term,
                log_index=m.log_index - b, log_term=m.log_term,
                commit=max(m.commit - b, 0), n_entries=n,
            )
            self._rows["ents"].append(
                (
                    g, k,
                    [e.term for e in m.entries],
                    [e.is_config_change() for e in m.entries],
                )
            )
            lane.pack_info[k] = ("rep", list(m.entries))
            return True
        if t == MT.HEARTBEAT:
            self._stage_row(
                g, k, MSG.HEARTBEAT, from_slot=from_slot, term=m.term,
                # log_index is the lease round tag (opaque tick stamp,
                # 0 when leases off) — staged raw, no -b translation
                log_index=m.log_index,
                commit=max(m.commit - b, 0), hint=m.hint,
                hint_high=m.hint_high,
            )
            return True
        if t == MT.REQUEST_VOTE:
            self._stage_row(
                g, k, MSG.REQUEST_VOTE, from_slot=from_slot, term=m.term,
                log_index=m.log_index - b, log_term=m.log_term, hint=m.hint,
            )
            return True
        if t == MT.REQUEST_VOTE_RESP:
            self._stage_row(
                g, k, MSG.REQUEST_VOTE_RESP, from_slot=from_slot, term=m.term,
                reject=m.reject,
            )
            return True
        if t == MT.REQUEST_PREVOTE:
            self._stage_row(
                g, k, MSG.REQUEST_PREVOTE, from_slot=from_slot, term=m.term,
                log_index=m.log_index - b, log_term=m.log_term, hint=m.hint,
            )
            return True
        if t == MT.REQUEST_PREVOTE_RESP:
            self._stage_row(
                g, k, MSG.REQUEST_PREVOTE_RESP, from_slot=from_slot,
                term=m.term, reject=m.reject,
            )
            return True
        if t == MT.REPLICATE_RESP:
            if m.reject and from_slot in lane.catchup:
                # the peer is served from the host log and the device has
                # it parked (the kernel leaves a parked remote's next
                # alone): its reject says where its log ends
                self._rewind_catchup(lane, from_slot, m.hint)
                return False
            if m.reject and m.hint < b and from_slot >= 0:
                # the follower's log ends BELOW our device window: the
                # kernel cannot back off past its own first_index, so a
                # clamped hint would loop rejects forever. Serve the gap
                # host-side (log replay or snapshot) and park the device
                # remote until the follower crosses the window base.
                self._below_window_reject(lane, from_slot, m)
                return False
            self._stage_row(
                g, k, MSG.REPLICATE_RESP, from_slot=from_slot, term=m.term,
                log_index=m.log_index - b, reject=m.reject,
                hint=max(m.hint - b, 0),
            )
            return True
        if t == MT.HEARTBEAT_RESP:
            self._stage_row(
                g, k, MSG.HEARTBEAT_RESP, from_slot=from_slot, term=m.term,
                # echoed lease round tag, raw (see MT.HEARTBEAT above)
                log_index=m.log_index,
                hint=m.hint, hint_high=m.hint_high,
            )
            return True
        if t == MT.READ_INDEX:
            self._stage_row(
                g, k, MSG.READ_INDEX, from_slot=from_slot, term=m.term,
                hint=m.hint, hint_high=m.hint_high,
            )
            return True
        if t == MT.READ_INDEX_RESP:
            self._stage_row(
                g, k, MSG.READ_INDEX_RESP, from_slot=from_slot, term=m.term,
                log_index=m.log_index - b, hint=m.hint,
                hint_high=m.hint_high,
            )
            return True
        if t == MT.TIMEOUT_NOW:
            self._stage_row(
                g, k, MSG.TIMEOUT_NOW, from_slot=from_slot, term=m.term
            )
            return True
        if t == MT.UNREACHABLE:
            self._stage_row(g, k, MSG.UNREACHABLE, from_slot=from_slot)
            return True
        if t == MT.SNAPSHOT_STATUS:
            self._stage_row(
                g, k, MSG.SNAPSHOT_STATUS, from_slot=from_slot, reject=m.reject
            )
            return True
        if t == MT.NOOP:
            self._stage_row(g, k, MSG.NOOP, from_slot=from_slot, term=m.term)
            return True
        return False

    def _handle_install_snapshot(self, lane: _Lane, m: Message) -> None:
        ss = m.snapshot
        node = lane.node
        if ss is None or ss.is_empty():
            return
        applied = node.sm.last_applied_index()
        if ss.index <= applied:
            # stale snapshot: ACK it (etcd TestRestoreIgnores semantics —
            # the scalar core does the same). A silent drop wedges the
            # sender: its remote stays parked in SNAPSHOT state waiting for
            # match >= snapshot index, it resends the same snapshot on the
            # feedback retry, and we'd drop that too, forever.
            node._send_message(
                Message(
                    type=MT.REPLICATE_RESP,
                    cluster_id=node.cluster_id,
                    to=m.from_,
                    from_=node.node_id(),
                    term=max(m.term, int(self._m_term[lane.g]),
                             lane.adopted_term),
                    log_index=applied,
                )
            )
            return
        if lane.recovering:
            return  # a restore is already in flight; the retry re-delivers
        lane.recovering = True
        self._m_recovering[lane.g] = True
        # multi-step: a recovering lane leaves the on-device routing
        # table — routed traffic would advance kernel state the restore
        # is about to overwrite; the host path holds its messages instead
        self._routes_dirty = True
        # the restore ack must carry a term the sender will not drop as
        # stale; the kernel never sees this message (it is consumed host-
        # side), so remember the sender's term for the ack path
        # (cf. raft.go:1415-1449 term preamble)
        lane.adopted_term = max(lane.adopted_term, m.term)
        # the snapshot record is persisted (fsync) on the snapshot worker
        # right before recovery, NOT here: this is the engine loop thread,
        # and a monolithic install must not stall every other lane's
        # super-step cadence (the streamed-install watchdog bound)
        node._vec_install_record = ss
        lane.node._push_install_snapshot(ss)

    # --------------------------------------------------------------- decode
    def _decode(self, worked: Set[_Lane], packs, o: dict) -> None:
        """One engine step's host fan-out (the K=1 path): the decode
        phases that send, save and acknowledge run in the reference
        ordering over a single StepOutput; the caller owes the step its
        _decode_maintain. The phase bodies live in the _decode_*
        subfunctions so the multi-step super-step (_decode_super) can
        orchestrate the same code with its masked, per-inner-step
        inputs."""
        self.last_output = o  # numpy snapshot for diagnostics/tools
        note_engine_steps(1)
        prof = self.profiler
        prof.begin("place")
        self._watch_progress(o)  # first: see there
        self._decode_place(o, packs)
        self._refresh_mirrors(o)
        # ---- phase 1: Replicate messages leave BEFORE the fsync ----------
        prof.begin("send_rep")
        self._decode_send_rep(o)
        # ---- phase 2: one batched fsynced write for every lane -----------
        prof.begin("save")
        mark = self._wave_mark()
        owed = self._m_commit_owed
        if owed.any():
            # the first one-step launch after a switch down: the last
            # merged wave's held-back commit indexes (_decode_super)
            self._m_commit_owed = np.zeros_like(owed)
        else:
            owed = None
        updates, lane_saves = build_save_updates(
            o, self._m_base, self._lane_by_g, owed=owed
        )
        self._commit_saves(updates, lane_saves, mark)
        # ---- phase 3: post-fsync sends (votes, responses, heartbeats) ----
        prof.begin("send_resp")
        self._decode_send_post(o)
        # ---- phase 4: hand committed entries to the RSM ------------------
        prof.begin("apply")
        self._decode_apply(o)
        # ---- phase 5: confirmed reads ------------------------------------
        prof.begin("reads")
        self._decode_reads(o)

    def _decode_maintain(self, o: dict) -> None:
        """Phase 6 of a K=1 step, maintenance: no request waits for it,
        so the overlapped loop runs it behind the next launch."""
        self.profiler.begin("maintain")
        self._maintain(o)

    def _decode_super(self, worked: Set[_Lane], packs, o: dict, pl: dict) -> None:
        """Decode one K-step super-step (the multi-step path): the
        host-only residue of every inner step, with device-routed
        traffic masked out of the send/response planes and its
        Replicate payload bytes replayed into the destination arenas.

        Phase ordering across the window:
          * place + phase-1 Replicates run per inner step IN ORDER (a
            cross-host Replicate of step t materializes its payload
            BEFORE step t+1's placements can conflict-truncate it);
          * the WAL save is ONE merged wave: every inner step's updates
            land in step order inside a single batched write + barrier,
            so responses of EVERY inner step leave only after the
            window's final — maximal — hard state is durable (the
            persist-before-ack invariant holds against a state at least
            as new as what each response reflects). One thing the wave
            must not write: co-hosted followers acknowledge on the
            device before the host has written their entries, so a
            leader's commit index of THIS launch may cover entries that
            only this same wave makes durable, on other hosts' stores.
            A crash that tears the wave (one store written, another
            not) would leave a replica with a persisted commit above
            what a quorum holds, and its restart would apply entries a
            new leader may overwrite. So a hard state of this wave
            carries a commit no higher than the lane's at the end of
            the last launch, whose wave has returned; the lanes held
            back are owed their hard state by the next wave
            (_m_commit_owed), which by then is safe to write in full.
            Nothing is applied or acknowledged before the wave returns,
            so only what a restart replays by itself is a launch late;
          * post-fsync sends, RSM apply and confirmed reads then run per
            inner step in order.
        """
        K = len(o["term"])  # the launch's own step count
        prof = self.profiler
        prof.begin("place")
        steps = []
        for t in range(K):
            ot = {k: v[t] for k, v in o.items()}
            plt = {k: v[t] for k, v in pl.items()}
            steps.append((ot, plt))
        self._watch_progress(steps[-1][0])  # first: see there
        self.last_output = steps[-1][0]
        note_engine_steps(K)
        st = self._sstats
        base = self._m_base
        lane_by_g = self._lane_by_g
        safe_commit = self._m_commit  # the last launch's, before the refresh
        # ---- place + phase 1, per inner step in order --------------------
        for t, (ot, plt) in enumerate(steps):
            prof.begin("place")
            # routed Replicates consumed by THIS inner step: acceptance
            # (rep_base) is in ot; the candidate plan was staged by the
            # previous inner step (or the previous super-step's last one)
            self._place_routed_reps(ot)
            self._decode_place(ot, packs if t == 0 else None)
            self._pending_rep_copies = self._routed_rep_plan(ot, plt)
            for kind in ("rep", "vote", "hb", "tn", "resp", "rir"):
                st["msgs_routed_device"] += int(plt[kind].sum())
            self._mask_routed(ot, plt)
            prof.begin("send_rep")
            self._decode_send_rep(ot)
        prof.begin("place")  # as at K=1, the mirror refresh is place's
        self._refresh_mirrors(steps[-1][0])
        # ---- phase 2: ONE merged save wave for the whole window ----------
        prof.begin("save")
        mark = self._wave_mark()
        updates: List[Update] = []
        lane_saves: List[Tuple[_Lane, List[Entry], State]] = []
        owed = self._m_commit_owed if self._m_commit_owed.any() else None
        for ot, _plt in steps:
            u, ls = build_save_updates(
                ot, base, lane_by_g, commit_cap=safe_commit,
                owed=owed if ot is self.last_output else None,
            )
            updates.extend(u)
            lane_saves.extend(ls)
        self._m_commit_owed = (
            self.last_output["commit_index"] > safe_commit
        ) & self._m_active
        self._commit_saves(updates, lane_saves, mark)
        # ---- phases 3-5 per inner step in order --------------------------
        prof.begin("send_resp")
        for ot, _plt in steps:
            self._decode_send_post(ot)
        prof.begin("apply")
        for ot, _plt in steps:
            self._decode_apply(ot)
        prof.begin("reads")
        for ot, plt in steps:
            self._decode_reads(ot, skip_routed=plt["rir"])
        # ---- phase 6: maintenance on the window's final state ------------
        prof.begin("maintain")
        self._maintain(steps[-1][0])

    # ------------------------------------------------ multi-step routing
    def _rebuild_routes(self) -> None:
        """Recompute the on-device routing table: for every active lane
        and peer slot, the co-hosted destination lane index and the
        window-base delta the kernel adds to index-valued fields.
        Conservative by construction — any condition the host delivery
        path special-cases (chaos drop hook, partitioned host, stopped
        node, in-flight snapshot restore, witness, unknown peer, a peer
        that numbers the slots differently) routes -1, so that traffic
        falls back to the host path and its exact semantics.

        Where the engine chooses its own steps a launch (auto) this is
        also what it chooses from: _all_routable says that the table
        routes every peer slot of every lane it looked at, and at least
        one. Short of that the table is left routing nothing, so a K-step
        launch that only drains the residual inbox parks nothing new."""
        self._routes_dirty = False
        self._all_routable = False
        route = self._np_route
        rdelta = self._np_rdelta
        route.fill(-1)
        rdelta.fill(0)
        if self._local_drop_hook is not None:
            return  # every co-hosted message must pass the chaos hook
        P = self.kcfg.peers
        base = self._m_base
        blocked = self._blocked_hosts
        with self._lanes_mu:
            lanes = list(self._lanes.values())
            rt = dict(self._route)
        routed = unrouted = 0
        for lane in lanes:
            if not lane.active or lane.node.stopped:
                continue
            g = lane.g
            self_slot = lane.self_slot()
            # partitioned host: neither sends nor receives
            cut = lane.key[0] in blocked
            for p, nid in lane.rev.items():
                if p == self_slot or p < 0 or p >= P:
                    continue
                # witness peers stay on the host path: its senders strip
                # payloads to METADATA (the zero-payload witness
                # contract); the device route would copy full entries
                # into the witness arena
                dst = (
                    None if cut or p in lane.wit_slots
                    else rt.get((lane.node.cluster_id, nid))
                )
                if (
                    dst is None
                    or not dst.active
                    or dst.recovering
                    or dst.node.stopped
                    or dst.key[0] in blocked
                    # a routed message names its sender by the SENDER's
                    # slot numbering (rank among the members it has
                    # applied); between one replica's apply of a config
                    # change and the other's the two number differently,
                    # and only the host path translates through node ids
                    or dst.slots != lane.slots
                ):
                    unrouted += 1
                    continue
                routed += 1
                route[g, p] = dst.g
                rdelta[g, p] = int(base[g] - base[dst.g])
        if not self._auto:
            return
        if routed and self._multi_fn is None:
            self._build_multi(_AUTO_STEPS)
        self._all_routable = routed > 0 and unrouted == 0
        if not self._all_routable:
            route.fill(-1)
            rdelta.fill(0)

    def _routed_rep_plan(self, o: dict, plan: dict) -> list:
        """Replay the kernel's deterministic inbox-slot assignment for
        this step's device-routed Replicates: [(dst_g, slot, src_lane,
        dst_lane, lo_real, hi_real)]. Replicate candidates come FIRST in
        the kernel's kind-major candidate order, so their per-destination
        slots are simply their rank among routed Replicates to the same
        destination in row-major (g, p) order — exactly what np.nonzero
        yields. The payload copy waits for the CONSUMING step's
        acceptance report (_place_routed_reps)."""
        rep = plan["rep"]
        gs, ps = np.nonzero(rep)
        if not gs.size:
            return []
        route = self._np_route
        base = self._m_base
        lane_by_g = self._lane_by_g
        out = []
        counts: Dict[int, int] = {}
        cols = zip(
            gs.tolist(),
            route[gs, ps].tolist(),
            base[gs].tolist(),
            o["send_prev_index"][gs, ps].tolist(),
            o["send_n_entries"][gs, ps].tolist(),
        )
        for g, d, b, prev, n in cols:
            slot = counts.get(d, 0)
            counts[d] = slot + 1
            if n <= 0:
                continue  # empty commit-refresh Replicate: no payload
            src = lane_by_g[g]
            dst = lane_by_g[d]
            if src is None or dst is None:
                continue
            lo = b + prev + 1
            out.append((d, slot, src, dst, lo, lo + n - 1))
        return out

    def _place_routed_reps(self, o: dict) -> None:
        """Payload placement for device-routed Replicates consumed by
        this inner step: the destination ACCEPTED the entries iff its
        rep_base for the (lane, slot) the kernel routed them into is
        nonzero — the same acceptance gate the host wire path applies
        before placing a Replicate's entries into the arena."""
        pend, self._pending_rep_copies = self._pending_rep_copies, []
        if not pend:
            return
        lane_by_g = self._lane_by_g
        rep_base = o["rep_base"]
        for d, slot, src, dst, lo, hi in pend:
            if lane_by_g[d] is not dst or not dst.active:
                continue  # lane recycled between super-steps
            if rep_base[d, slot] <= 0:
                continue  # rejected (or consumed by a stale-term drop)
            arena = dst.arena
            sa = src.arena
            for i in range(lo, hi + 1):
                e = sa.get(i)
                if e is not None:
                    arena[e.index] = e

    def _mask_routed(self, o: dict, plan: dict) -> None:
        """Clear device-routed candidates out of the send/response
        planes so the host fan-out only materializes Messages for
        traffic the kernel could NOT route (cross-host, overflowed,
        below-window). Builds new arrays — the fetched planes can be
        read-only views."""
        clr = (
            np.where(plan["rep"], SEND_REPLICATE, 0)
            | np.where(plan["vote"], SEND_VOTE_REQ, 0)
            | np.where(plan["hb"], SEND_HEARTBEAT, 0)
            | np.where(plan["tn"], SEND_TIMEOUT_NOW, 0)
        )
        o["send_flags"] = o["send_flags"] & ~clr
        o["resp_type"] = np.where(
            plan["resp"], np.int32(MSG.NONE), o["resp_type"]
        )

    # ----------------------------------------------------- decode phases
    def _decode_place(self, o: dict, packs) -> None:
        """Phase 0: payloads at device-assigned indexes (host-packed
        rows when ``packs`` is given, plus new-leader noop entries) and
        the per-step stats base count."""
        lane_by_g = self._lane_by_g
        base = self._m_base
        # lease read counters: per-step deltas from the kernel, folded
        # into the engine totals (numpy sums over planes the decode
        # already fetched — zero extra device syncs)
        self._lease_local += int(o["lease_served"].sum())
        self._lease_fb += int(o["lease_fallback"].sum())
        ri_dropped = int(o["dropped_readindex"].sum())
        self._sstats["readindex_dropped"] += ri_dropped
        if self.profiler.sampling:
            self.profiler.fold("n.readindex_dropped", ri_dropped)
        # on-device event-counter plane: one (G, CTR.COUNT) u32 delta
        # block per protocol step, accumulated where the events happened
        # (inside step_batch / the K-step scan) and folded here into the
        # cumulative per-lane totals — the K>1 / device-routed regime
        # counts exactly like K=1 because the kernel counted it, not the
        # host decode
        self._ctr += o["counters"]
        self._m_lease_ok = np.asarray(o["lease_ok"])
        # ---- phase 0: place payloads at device-assigned indexes ----------
        # columnar: ONE gather per StepOutput plane over every packed row,
        # then plain-python iteration (no per-element device_get reads)
        if packs:
            pk_lanes: List[_Lane] = []
            pk_ks: List[int] = []
            pk_infos: List[tuple] = []
            for lane, pack_info in packs.items():
                for k, info in pack_info.items():
                    pk_lanes.append(lane)
                    pk_ks.append(k)
                    pk_infos.append(info)
            pk_gs = [lane.g for lane in pk_lanes]
            place_cols = zip(
                pk_lanes,
                pk_infos,
                base[pk_gs].tolist(),
                o["prop_base"][pk_gs, pk_ks].tolist(),
                o["rep_base"][pk_gs, pk_ks].tolist(),
                o["resp_term"][pk_gs, pk_ks].tolist(),
                o["dropped_cc"][pk_gs].tolist(),
            )
            for lane, info, b, pbase, rbase, rterm, dcc in place_cols:
                kind = info[0]
                if kind == "prop":
                    ents = info[1]
                    if pbase > 0:
                        arena = lane.arena
                        for i, e in enumerate(ents):
                            e.index = b + pbase + i
                            e.term = rterm
                            arena[e.index] = e
                    else:
                        node = lane.node
                        for e in ents:
                            node.proposal_dropped(e)
                    lane.packed_pending = max(
                        0, lane.packed_pending - len(ents)
                    )
                elif kind == "cc":
                    ce, key = info[1], info[2]
                    if pbase > 0 and not dcc:
                        ce.index = b + pbase
                        ce.term = rterm
                        lane.arena[ce.index] = ce
                    else:
                        if pbase > 0:
                            # the kernel appended the entry with its cc bit
                            # stripped (single-pending invariant): it lives
                            # on as an empty noop entry (raft.go:1587-1606)
                            lane.arena[b + pbase] = Entry(
                                type=EntryType.APPLICATION,
                                index=b + pbase,
                                term=rterm,
                            )
                        lane.cc_inflight = False
                        lane.node.pending_config_change.apply(
                            key, rejected=True
                        )
                    lane.packed_pending = max(0, lane.packed_pending - 1)
                elif kind == "rep":
                    if rbase > 0:
                        arena = lane.arena
                        for e in info[1]:
                            arena[e.index] = e
        # new-leader noop entries can appear on ANY lane (tick elections)
        noop_gs = np.nonzero(o["noop_appended"])[0]
        if noop_gs.size:
            for g, noop_at, noop_term, b in zip(
                noop_gs.tolist(),
                o["noop_appended"][noop_gs].tolist(),
                o["noop_term"][noop_gs].tolist(),
                base[noop_gs].tolist(),
            ):
                lane = lane_by_g[g]
                if lane is None:
                    continue
                lane.arena[b + noop_at] = Entry(
                    type=EntryType.APPLICATION,
                    term=noop_term,
                    index=b + noop_at,
                )
        # ---- per-step stats: steps counter (the rest accumulates inline
        # on objects each phase already materializes — len() of the send
        # batches, counts inside loops that already run — so the stats
        # plane adds ZERO numpy reductions to the step)
        st = self._sstats
        st["steps"] += 1

    def _refresh_mirrors(self, o: dict) -> None:
        """Rebind the whole-G numpy protocol mirrors from a StepOutput
        and emit leader-change events for lanes whose (leader, term)
        moved. The multi-step path calls this ONCE per super-step with
        the window's final state: intermediate transitions inside the
        window collapse into one observed change (the mirrors are a
        per-sync snapshot plane, not a per-protocol-step event log)."""
        lane_by_g = self._lane_by_g
        st = self._sstats
        # ---- mirror refresh + leader-change events -----------------------
        new_leader = o["leader"]
        new_term = o["term"]
        changed = np.nonzero(
            ((new_leader != self._m_leader) | (new_term != self._m_term))
            & self._m_active
        )[0]
        # old leader column for the changed lanes, captured before the
        # rebind: distinguishes true LEADER transitions (which arm the
        # ticks_since_leader_change gauge) from term-only churn
        old_leader_changed = self._m_leader[changed]
        # device_get arrays can be read-only views: mirrors are mutated by
        # the activation/reconcile paths, so copy on rebind
        self._m_leader = np.array(new_leader)
        self._m_term = np.array(new_term)
        self._m_role = np.array(o["role"])
        self._m_quiesced = np.array(o["quiesced"])
        self._m_commit = o["commit_index"].astype(np.int64)
        self._m_last = o["last_index"].astype(np.int64)
        if changed.size:
            lead_n = elect_n = 0
            chg_tick = self.clock.tick
            for g, lslot, old_lslot, term in zip(
                changed.tolist(),
                new_leader[changed].tolist(),
                old_leader_changed.tolist(),
                new_term[changed].tolist(),
            ):
                lane = lane_by_g[g]
                if lane is None or not lane.active:
                    continue
                lead_n += 1
                if lslot != old_lslot:
                    # real leader transition (not term-only churn)
                    self._m_leader_change_tick[g] = chg_tick
                if lslot == 0:
                    # lane went leaderless: an election is underway
                    elect_n += 1
                lane.node._leader_event(lane.rev.get(lslot - 1, 0), term)
            st["leader_changes"] += lead_n
            st["elections_started"] += elect_n
        if self._bring_elect is None and self._bring_launch0 is not None:
            # the bring-up account's election count, until it is known
            act = self._m_active
            if not (act & (self._m_leader == 0)).any():
                self._bring_elect = self.launch_no - self._bring_launch0

    def _decode_send_rep(self, o: dict) -> None:
        """Phase 1: Replicate messages leave BEFORE the fsync."""
        st = self._sstats
        base = self._m_base
        lane_by_g = self._lane_by_g
        rep_sends = gather_replicate_sends(
            o, base, lane_by_g, self._fetch_from_log, self.launch_no
        )
        st["msgs_replicate"] += len(rep_sends)
        self._dispatch_sends(rep_sends)

    def _wave_mark(self) -> Optional[float]:
        """Where a save wave begins on the wall clock, before its
        gather: sampled iterations only."""
        if self.profiler.sampling:
            return time.monotonic()
        return None

    def _commit_saves(self, updates, lane_saves, mark=None) -> None:
        """Phase 2: one batched fsynced write wave + log-reader mirror
        append, in update order (a multi-step window passes every inner
        step's updates through ONE call, so conflict-truncation rewrites
        apply sequentially inside a single barrier). A wave with a
        `mark` (_wave_mark) is timed part by part: the storage layer
        under it finds the wave's parts in its thread-local
        (storage.kv._Wave) and adds to them."""
        parts = None
        if mark is not None:
            t1, c1 = time.monotonic(), time.thread_time()
            parts = _kv_open_wave()
        try:
            self._save_updates(updates, lane_saves)
        finally:
            if parts is not None:
                _kv_close_wave()
        if mark is not None:
            t2, c2 = time.monotonic(), time.thread_time()
        for lane, ents, state in lane_saves:
            if ents:
                lane.node.log_reader.append(ents)
            lane.node.log_reader.set_state(state)
        if mark is not None:
            self._book_wave(updates, parts, (mark, t1, t2), c2 - c1)

    def _book_wave(self, updates, parts, at, write_cpu) -> None:
        """What a sampled save wave was made of, as sub-spans of `save`.
        Three stretches follow one another with the write between the
        first two, and leave an event at full sampling: save.gather
        (build_save_updates, since the wave's mark), save.sync (the
        barrier: storage.kv.sync_all books it, with the thread's CPU
        seconds, on the deferred path and inside a logdb's own
        save_raft_state alike) and save.mirror. The write (_save_updates
        up to its barrier) has its CPU seconds (save.write.cpu: the
        thread's between gather and mirror, `write_cpu`, less the
        barrier's) and its three pieces, which the storage layer tells
        apart shard by shard: the encode into write batches, the stores'
        commits less their in-memory tables (the WAL append; a store
        that cannot tell has its whole commit here) and the tables.
        Everything is recorded here, in one go behind the work, so that
        the window's edge that falls between this wave's parts and the
        close of its `save` span is microseconds wide."""
        prof = self.profiler
        t0, t1, t2 = at
        t3 = time.monotonic()
        n_bytes = sum(len(e.cmd) for u in updates for e in u.entries_to_save)
        sync, sync_cpu = parts["sync"], parts["sync_cpu"]
        prof.add("save.gather", t1 - t0, end=t1)
        prof.fold("save.write.cpu", write_cpu - sync_cpu)
        prof.add("save.encode", parts["encode"])
        prof.add("save.append", parts["commit"] - parts["table"])
        prof.add("save.table", parts["table"])
        prof.add("save.sync", sync, sync_cpu, end=t2)
        prof.add("save.mirror", t3 - t2, end=t3)
        if updates:
            prof.fold("n.save_bytes", n_bytes)
        prof.fold("n.save_wal_bytes", parts["wal_bytes"])
        prof.fold("n.save_wal_records", parts["wal_records"])
        prof.fold("n.save_entries", parts["entries"])
        prof.fold("n.save_entries_shared", parts["entries_shared"])

    def _decode_send_post(self, o: dict) -> None:
        """Phase 3: post-fsync sends (votes, responses, heartbeats) plus
        the snapshot path for peers that fell behind the device window."""
        st = self._sstats
        base = self._m_base
        lane_by_g = self._lane_by_g
        post = gather_post_sends(o, base, lane_by_g)
        st["msgs_broadcast"] += len(post)
        resp_sends = gather_resp_sends(o, base, lane_by_g, self.launch_no)
        st["msgs_resp"] += len(resp_sends)
        post.extend(resp_sends)
        self._dispatch_sends(post)
        # snapshot path for peers that fell behind the device window
        snap_gs, snap_ps = np.nonzero(o["send_flags"] & NEED_SNAPSHOT)
        if snap_gs.size:
            for g, p in zip(snap_gs.tolist(), snap_ps.tolist()):
                lane = lane_by_g[g]
                if lane is not None:
                    self._start_catchup(lane, p, o)

    def _decode_apply(self, o: dict) -> None:
        """Phase 4: hand committed entries to the RSM task workers."""
        st = self._sstats
        base = self._m_base
        lane_by_g = self._lane_by_g
        from ..rsm import Task

        apply_gs = np.nonzero(o["apply_from"])[0]
        if apply_gs.size:
            applied_n = lanes_n = 0
            t_commit = time.monotonic()  # one clock read for the step
            for g, b, af, at in zip(
                apply_gs.tolist(),
                base[apply_gs].tolist(),
                o["apply_from"][apply_gs].tolist(),
                o["apply_to"][apply_gs].tolist(),
            ):
                lane = lane_by_g[g]
                if lane is None or not lane.active:
                    continue
                ents, missing_at = lane.arena.get_run(b + af, b + at)
                if ents is None:
                    # the ring only spans the device window; a restart
                    # replays the WHOLE committed log through the SM, whose
                    # early entries live in the host log alone
                    ents = self._fetch_from_log(lane, b + af, b + at)
                    if ents is None:
                        _plog.errorf(
                            "%s missing entry %d for apply (arena+log)",
                            lane.node.describe(), missing_at,
                        )
                        continue
                if not ents:
                    continue
                lane.node.sm.task_queue.add(
                    Task(
                        cluster_id=lane.node.cluster_id,
                        node_id=lane.node.node_id(),
                        entries=ents,
                    )
                )
                self._m_applied_since[g] += len(ents)
                applied_n += len(ents)
                lanes_n += 1  # this lane really handed work to the RSM
                # committed + dispatched to the RSM: no longer mem pressure
                lane.arena.mark_applied(b + at)
                has_cc = False
                for e in ents:
                    if e.type == EntryType.CONFIG_CHANGE:
                        has_cc = True
                    lt = e.lat
                    if lt is not None and lt.t_commit == 0.0:
                        # sampled proposal reached quorum commit this step
                        lt.t_commit = t_commit
                        lt.n_commit = self.launch_no
                        if lt.trace_id:
                            flight_recorder().record(
                                "quorum_commit",
                                cluster=lane.node.cluster_id,
                                node=lane.node.node_id(),
                                trace=lt.trace_id, index=e.index,
                                launch=self.launch_no,
                            )
                if has_cc:
                    lane.cc_inflight = False
                self.set_task_ready(lane.key)
            st["entries_applied"] += applied_n
            st["lanes_commit_advanced"] += lanes_n

    def _decode_reads(self, o: dict, skip_routed=None) -> None:
        """Phase 5: confirmed reads. ``skip_routed`` (multi-step) marks
        ready-queue slots whose READ_INDEX_RESP the kernel already
        routed to the forwarding origin's co-hosted lane — the host
        must not send a duplicate."""
        base = self._m_base
        lane_by_g = self._lane_by_g
        rc = o["ready_count"]
        ready_gs = np.nonzero(rc)[0]
        if ready_gs.size:
            # flatten the (lane, slot<count) pairs, then gather columns
            ridx = np.arange(o["ready_ctx"].shape[1])
            rrow, ris = np.nonzero(ridx[None, :] < rc[ready_gs, None])
            sel = ready_gs[rrow]
            read_sends: List[Tuple[_Lane, Message]] = []
            applied_lanes: Dict[_Lane, None] = {}
            for g, _slot, b, enc_lo, enc_hi, dev_idx, term in zip(
                sel.tolist(),
                ris.tolist(),
                base[sel].tolist(),
                o["ready_ctx"][sel, ris].tolist(),
                o["ready_ctx2"][sel, ris].tolist(),
                o["ready_index"][sel, ris].tolist(),
                # the confirming lane's own end-of-step term (== the
                # refreshed _m_term mirror on the K=1 path)
                o["term"][sel].tolist(),
            ):
                lane = lane_by_g[g]
                if lane is None or not lane.active:
                    continue
                node = lane.node
                applied_lanes[lane] = None
                if skip_routed is not None and skip_routed[g, _slot]:
                    continue  # kernel already routed this response
                enc = (enc_lo, enc_hi)
                idx = b + dev_idx
                origin = _ctx_origin(enc_lo)
                if origin == lane.self_slot():
                    ctx = lane.ri_pending.pop(enc, None)
                    if lane.ri_lat:
                        # sampled reads of this context: confirmed now
                        now = time.monotonic()
                        for lt in lane.ri_lat.pop(enc, ()):
                            lt.t_commit = now
                            lt.n_commit = self.launch_no
                    if ctx is not None:
                        node.pending_read_indexes.add_ready_to_read(
                            [ReadyToRead(index=idx, system_ctx=ctx)]
                        )
                else:
                    to_nid = lane.rev.get(origin)
                    if to_nid is not None:
                        read_sends.append(
                            (
                                lane,
                                Message(
                                    type=MT.READ_INDEX_RESP,
                                    cluster_id=node.cluster_id,
                                    to=to_nid,
                                    from_=node.node_id(),
                                    term=term,
                                    log_index=idx,
                                    hint=enc_lo,
                                    hint_high=enc_hi,
                                ),
                            )
                        )
            self._dispatch_sends(read_sends)
            for lane in applied_lanes:
                lane.node.pending_read_indexes.applied(
                    lane.node.sm.last_applied_index()
                )

    def _dispatch_sends(self, sends: List[Tuple["_Lane", Message]]) -> None:
        """Hand a decode phase's (lane, Message) batch to each owning
        node's bulk send path: one co-hosted delivery pass plus one grouped
        wire send per node, instead of a queue hop per message. Relative
        order within the batch is preserved per destination."""
        if not sends:
            return
        # "deliver" sub-span: the bulk send/deliver seam's share of the
        # enclosing send/apply/reads phase (sampled iterations only — the
        # off path pays no clock reads)
        prof = self.profiler
        t0 = time.monotonic() if prof.sampling else 0.0
        by_node: Dict[object, List[Message]] = {}
        for lane, m in sends:
            node = lane.node
            lst = by_node.get(node)
            if lst is None:
                lst = by_node[node] = []
            lst.append(m)
        for node, msgs in by_node.items():
            many = node._send_messages
            if many is not None:
                many(msgs)
            else:
                send = node._send_message
                for m in msgs:
                    send(m)
        if prof.sampling:
            prof.add("deliver", time.monotonic() - t0)

    def _save_updates(self, updates: List[Update], lane_saves) -> None:
        """One multi-group write wave per step: a single write-batch per
        touched logdb shard with the durability barrier deferred, then one
        parallel sync over every touched WAL — group commit across shards
        AND across co-hosted NodeHosts' logdbs (a shared core hosts lanes
        from several hosts, each with its own WAL). The replicas of a
        group that the core co-hosts save the same Entry objects, in this
        wave or (one step a launch) in the next: the logdbs under the
        wave find the loop's record bodies on the thread and encode a
        group's entries once, not once a replica."""
        if self._next_host <= 1:
            if updates:
                self._logdb.save_raft_state(updates)
            return
        bodies = self._record_bodies
        bodies.turn()
        if not updates:
            return
        _kv_open_bodies(bodies)
        try:
            if len(lane_saves) == 1:
                lane_saves[0][0].node.logdb.save_raft_state(updates)
                return
            by_db: Dict[int, tuple] = {}
            for (lane, _e, _s), ud in zip(lane_saves, updates):
                db = lane.node.logdb
                ent = by_db.get(id(db))
                if ent is None:
                    ent = by_db[id(db)] = (db, [])
                ent[1].append(ud)
            pending = []
            for db, uds in by_db.values():
                deferred = getattr(db, "save_raft_state_deferred", None)
                if deferred is not None:
                    pending.extend(deferred(uds))
                else:
                    db.save_raft_state(uds)
        finally:
            _kv_close_bodies()
        _kv_sync_all(pending)

    def _fetch_from_log(self, lane: _Lane, lo: int, hi: int):
        """Contiguous [lo, hi] from the host log (the arena ring's backing
        tier); None if the log cannot serve the whole range."""
        try:
            ents = lane.node.log_reader.entries(lo, hi + 1, 1 << 30)
        except Exception:
            return None
        if (
            len(ents) != hi - lo + 1
            or (ents and (ents[0].index != lo or ents[-1].index != hi))
        ):
            return None
        return ents
    # ------------------------------------------------------ catchup path
    def _below_window_reject(self, lane: _Lane, p: int, m: Message) -> None:
        """A reject whose hint is below the device window base: replicate
        the gap from the host log (or ship a snapshot), with the device
        remote parked so it stops probing indexes the follower cannot
        match. The park watermark is base+1: the first ack at or above the
        window base un-parks it and device replication takes over."""
        g = lane.g
        if p in lane.catchup or p in lane.snap_inflight:
            return  # recovery already running for this peer
        if int(self._m_role[g]) != ROLE.LEADER:
            return
        b = int(self._m_base[g])
        s = self._state
        self._state = s._replace(
            rstate=s.rstate.at[g, p].set(RSTATE.SNAPSHOT),
            snap_sent=s.snap_sent.at[g, p].set(1),
        )
        start = m.hint + 1
        goal = self._last_real(g)
        first, last = lane.node.log_reader.get_range()
        if start >= first and start <= last + 1:
            self._open_catchup(lane, p, start, goal, m.hint)
        else:
            self._send_snapshot(lane, p)

    def _start_catchup(self, lane: _Lane, p: int, o) -> None:
        """A peer's next index fell behind the device window. If the host
        log still has the entries, replicate them host-side (the device has
        parked the peer in SNAPSHOT state; ReplicateResps move match and the
        kernel un-parks it once caught). Otherwise stream a real snapshot
        (cf. raft.go:774-785)."""
        if p in lane.catchup:
            return
        g = lane.g
        b = int(self._m_base[g])
        goal = b + int(o["last_index"][g])
        match = b + int(o["match"][g, p])
        sent = lane.snap_inflight.get(p)
        if sent is not None and match < sent[1]:
            # a snapshot is on its way and its restore not acknowledged
            # yet. The transport's "delivered" status un-parked the peer
            # (becomeWait, remote.go:119-127) and the kernel parked it
            # again at the leader's last index: put the watermark back on
            # the snapshot that was sent, so its acknowledgement un-parks
            # the peer, and send nothing. (Deciding by `match` here, which
            # a restore in progress has not moved, shipped a second whole
            # image behind every first.) A lost snapshot is the feedback
            # timer's to retry.
            s = self._state
            self._state = s._replace(
                snap_sent=s.snap_sent.at[g, p].set(max(sent[1] - b, 0))
            )
            return
        start = match + 1
        first, last = lane.node.log_reader.get_range()
        if start >= first and start <= last + 1:
            self._open_catchup(lane, p, start, goal, match)
        else:
            # the follower needs entries the host log no longer has
            # (compacted behind a snapshot): only a snapshot can help
            self._send_snapshot(lane, p)

    def _count(self, name: str, n: int = 1) -> None:
        """A replication counter: a plain int in step_stats() always, the
        profiler's `n.<name>` on sampled iterations."""
        self._sstats[name] += n
        if self.profiler.sampling:
            self.profiler.fold("n." + name, n)

    def _open_catchup(
        self, lane: _Lane, p: int, start: int, goal: int, match: int
    ) -> None:
        """Begin serving peer p from the host log, at index `start`."""
        lane.catchup[p] = _Catchup(
            start, goal, match, self.clock.tick, self.launch_no
        )
        self._catchups.add(lane)
        self._count("catchups_started")

    def _rewind_catchup(self, lane: _Lane, p: int, hint: int) -> None:
        """A peer in catch-up refused a Replicate: one before it was lost,
        and `hint` is where the peer's log ends. Go back there (never
        below what it has acknowledged) and let the next sweep send again.
        Everything sent behind the lost one is refused too, one reject a
        message: those that arrive inside the round trip of the resend
        rewind nothing."""
        cu = lane.catchup[p]
        back = max(cu.match + 1, hint + 1)
        if back < cu.nxt and self.launch_no - cu.rewound >= 2:
            cu.nxt = back
            cu.rewound = self.launch_no
            cu.probing = False  # it answers

    def _send_snapshot(self, lane: _Lane, p: int) -> None:
        to_nid = lane.rev.get(p)
        if to_nid is None:
            return
        self._count("snapshot_fallbacks")
        ss = lane.node.snapshotter.get_most_recent_snapshot()
        if ss is None or ss.is_empty():
            ss = lane.node.log_reader.snapshot()
        if ss is None or ss.is_empty():
            _plog.warningf(
                "%s peer %d needs a snapshot but none exists",
                lane.node.describe(), to_nid,
            )
            # still arm the feedback timer: the synthetic reject will
            # un-park the peer so host-log replication retries instead of
            # wedging it in SNAPSHOT state
            lane.snap_inflight[p] = (self.clock.tick, 0, self.launch_no, 0.0)
            self._snapfb.add(lane)
            return
        if p in lane.wit_slots:
            # witnesses get a real (non-dummy) snapshot record with the
            # data payload stripped (cf. raft.go:699-707)
            ss = _make_witness_snapshot(ss)
        lane.node._send_message(
            Message(
                type=MT.INSTALL_SNAPSHOT,
                cluster_id=lane.node.cluster_id,
                to=to_nid,
                from_=lane.node.node_id(),
                term=int(self._m_term[lane.g]),
                snapshot=ss,
            )
        )
        # reconcile the device's parked-peer watermark to the snapshot
        # ACTUALLY sent (the kernel parked it at the leader's last index):
        # the remote un-parks once match >= snap_sent (remote.go:62-69,
        # 145-153), so the watermark must be reachable by restoring this
        # snapshot or the peer wedges in SNAPSHOT state forever
        g = lane.g
        dev_idx = max(int(ss.index - self._m_base[g]), 0)
        s = self._state
        self._state = s._replace(
            snap_sent=s.snap_sent.at[g, p].set(dev_idx)
        )
        sent_at = 0.0
        if self.profiler.sampling:
            self.profiler.fold("n.snapshots_sent", 1)
            sent_at = time.monotonic()
        lane.snap_inflight[p] = (
            self.clock.tick, ss.index, self.launch_no, sent_at
        )
        self._snapfb.add(lane)

    def _run_catchups(self, lane: _Lane, o) -> int:
        """One sweep over the peers this leader serves from its host log;
        returns the entries it sent."""
        if not lane.catchup:
            self._catchups.discard(lane)
            return 0
        g = lane.g
        b = int(self._m_base[g])
        # a peer whose match has not moved for an election timeout, and
        # for the launches an acknowledgement's round trip takes, lost
        # what was in flight (or its answer): go back to match + 1 and
        # send again, as the reference's remote does from its retry state
        # on a rejected or unanswered Replicate (remote.go:155-171), one
        # message a retry until it answers. Only a host log that no longer
        # has the entries hands the peer to the snapshot path; silence
        # alone never does, so a deployment without snapshots recovers
        # every loss from the log.
        retry_ticks = max(lane.cfg.election_rtt, 4)
        now, launch = self.clock.tick, self.launch_no
        W, E = self.kcfg.log_window, self.kcfg.max_entries_per_msg
        sent = 0
        done = []
        for p, cu in lane.catchup.items():
            match = b + int(o["match"][g, p])
            if match >= cu.goal or int(self._m_role[g]) != ROLE.LEADER:
                done.append(p)
                continue
            if match > cu.match:
                cu.match, cu.tick, cu.launch = match, now, launch
                cu.probing = False
            elif (
                now - cu.tick > retry_ticks
                and launch - cu.launch >= _ACK_LAUNCHES
            ):
                cu.nxt = match + 1
                cu.tick, cu.launch = now, launch
                cu.probing = True
            elif cu.probing:
                continue  # the probe is out: wait for its answer
            nxt = max(cu.nxt, match + 1)
            to_nid = lane.rev.get(p)
            if to_nid is None:
                done.append(p)
                continue
            # half a window of entries a launch, in Replicates of
            # max_entries_per_msg, and never more than a window past what
            # the peer has acknowledged: the follower's pack takes what
            # its own window has room for and keeps the rest in order. One
            # message a launch left a joiner four batches behind five
            # launches from its leader, on a loop whose launch is seconds
            # long.
            budget = E if cu.probing else max(W // 2, 1)
            while budget > 0 and nxt <= match + W:
                first, last = lane.node.log_reader.get_range()
                if nxt < first:
                    # compacted behind a snapshot: only that can help
                    done.append(p)
                    self._send_snapshot(lane, p)
                    break
                if nxt > last or nxt > cu.goal:
                    break  # wait for the follower to ack what's in flight
                hi = min(nxt + E - 1, last, cu.goal)
                try:
                    ents = lane.node.log_reader.entries(nxt, hi + 1, 1 << 20)
                    prev = nxt - 1
                    prev_term = (
                        lane.node.log_reader.term(prev) if prev > 0 else 0
                    )
                except Exception:
                    done.append(p)
                    self._send_snapshot(lane, p)
                    break
                if not ents:
                    done.append(p)
                    break
                last_sent = ents[-1].index
                if nxt <= cu.sent_hi:
                    self._count("replicate_resends")
                cu.sent_hi = max(cu.sent_hi, last_sent)
                if p in lane.wit_slots:
                    # host catchup honors the witness shape too
                    ents = _make_metadata_entries(ents)
                lane.node._send_message(
                    Message(
                        type=MT.REPLICATE,
                        cluster_id=lane.node.cluster_id,
                        to=to_nid,
                        from_=lane.node.node_id(),
                        term=int(self._m_term[g]),
                        log_index=prev,
                        log_term=prev_term,
                        commit=min(self._committed_real(g), last_sent),
                        entries=ents,
                    )
                )
                budget -= len(ents)
                sent += len(ents)
                nxt = last_sent + 1
            cu.nxt = nxt
        for p in done:
            lane.catchup.pop(p, None)
        if not lane.catchup:
            self._catchups.discard(lane)
        return sent

    def _run_snapshot_feedback(self, lane: _Lane, o) -> None:
        """Delayed snapshot-status retry (cf. feedback.go:38-128): an
        InstallSnapshot that is not acked within the retry window gets a
        synthetic SNAPSHOT_STATUS reject queued to the local lane. The
        kernel then moves the remote SNAPSHOT->WAIT (next=match+1); the
        following HeartbeatResp probes it, and replication — or another
        snapshot — retries. Without this, a snapshot lost to a partition
        wedges the remote in SNAPSHOT state forever."""
        if not lane.snap_inflight:
            self._snapfb.discard(lane)
            return
        g = lane.g
        b = int(self._m_base[g])
        retry_ticks = max(4 * lane.cfg.election_rtt, 16)
        now, launch = self.clock.tick, self.launch_no
        is_leader = int(self._m_role[g]) == ROLE.LEADER
        done = []
        for p, sent in lane.snap_inflight.items():
            sent_tick, ss_index, sent_launch, sent_at = sent
            match = b + int(o["match"][g, p])
            if not is_leader or (ss_index > 0 and match >= ss_index):
                done.append(p)  # acked (or leadership moved on)
                if sent_at and is_leader:
                    # sent -> the restored replica's acknowledgement seen
                    prof = self.profiler
                    prof.observe(
                        "snap.install", time.monotonic() - sent_at,
                        engine="snapshot",
                    )
                    prof.fold("n.snapshots_acked", 1)
                continue
            if (
                now - sent_tick > retry_ticks
                and launch - sent_launch >= _RESTORE_LAUNCHES
            ):
                done.append(p)
                from_nid = lane.rev.get(p)
                if from_nid is not None:
                    lane.node.mq.add(
                        Message(
                            type=MT.SNAPSHOT_STATUS,
                            cluster_id=lane.node.cluster_id,
                            to=lane.node.node_id(),
                            from_=from_nid,
                            reject=True,
                        )
                    )
                    self.set_node_ready(lane.key)
        for p in done:
            lane.snap_inflight.pop(p, None)
        if not lane.snap_inflight:
            self._snapfb.discard(lane)

    # --------------------------------------------------------- maintenance
    def _maintain(self, o) -> None:
        lane_by_g = self._lane_by_g
        # the host-log catch-up sweep, timed on sampled iterations as the
        # sub-span `catchup` of `maintain`
        prof = self.profiler
        if prof.sampling:
            t0, c0 = time.monotonic(), time.thread_time()
        sent = 0
        for lane in list(self._catchups):
            sent += self._run_catchups(lane, o)
        # (every sampled sweep folds its entries, none included: the
        # readers tell a sweep that sent nothing from a program without it)
        self._count("catchup_entries", sent)
        if prof.sampling:
            prof.add("catchup", time.monotonic() - t0)
            prof.fold("catchup.cpu", time.thread_time() - c0)
        for lane in list(self._snapfb):
            self._run_snapshot_feedback(lane, o)
        # parked-peer watchdog: a remote in SNAPSHOT state whose host-side
        # recovery (catchup or snapshot feedback) is no longer tracked is
        # permanently wedged — the kernel only reports NEED_SNAPSHOT for
        # UNpaused peers, so nothing would ever re-arm it. Leadership races
        # (a catchup exiting on a stale goal, a feedback entry fast-acked
        # against an older snapshot watermark) can drop the tracker; this
        # sweep re-enters the recovery path. (cf. the reference's
        # unconditional snapshot-status feedback loop, feedback.go:38-128)
        parked = (o["rstate"] == RSTATE.SNAPSHOT) & (
            (o["role"] == ROLE.LEADER)[:, None]
        )
        # (n.peer_steps_parked is every leader's slot in the SNAPSHOT
        # state this launch, tracked or not: the recovery paths' own
        # business. The progress watch, _watch_progress at the head of
        # `place`, counts the slots and lanes that owe progress OUTSIDE
        # those paths and make none, and leaves parked slots, catch-ups
        # and restores out.)
        if prof.sampling:
            prof.fold("n.peer_steps_parked", np.count_nonzero(parked))
        for g, p in zip(*np.nonzero(parked)):
            lane = lane_by_g[g]
            if (
                lane is None
                or not lane.active
                or p in lane.catchup
                or p in lane.snap_inflight
            ):
                continue
            self._start_catchup(lane, int(p), o)
        # periodic snapshot by applied-entry count (node.go:585-601); a
        # wedged window forces one regardless of config. Candidates are
        # found vectorized; only triggering lanes cost Python.
        log_full = o["log_full"]
        snap_due = (
            self._m_active
            & ~self._m_snap_pending
            & (
                log_full
                | (
                    (self._m_snap_every > 0)
                    & (self._m_applied_since >= self._m_snap_every)
                )
            )
        )
        for g in np.nonzero(snap_due)[0].tolist():
            lane = lane_by_g[g]
            if lane is None or lane.node.snapshotter is None:
                continue
            applied, _ = lane.node.sm.get_last_applied()
            if applied > 0 and not lane.cfg.is_witness:
                self._m_snap_pending[g] = True
                self._m_applied_since[g] = 0
                from ..rsm import SSRequest

                lane.node.push_take_snapshot_request(SSRequest())
        # device window compaction: advance first_index once the window is
        # past its mark (_compact_mark: half full, or too full for a whole
        # row); applied entries are recoverable from the host log
        # (catchup path) or a snapshot, so the device needs neither
        used = o["last_index"].astype(np.int64) - self._m_devfirst + 1
        compact_due = self._m_active & ((used > self._compact_mark) | log_full)
        adv_mask = np.zeros(self.kcfg.groups, bool)
        adv_first = np.zeros(self.kcfg.groups, np.int32)
        adv_term = np.zeros(self.kcfg.groups, np.int32)
        for g in np.nonzero(compact_due)[0].tolist():
            lane = lane_by_g[g]
            if lane is None:
                continue
            b = int(self._m_base[g])
            applied, applied_term = lane.node.sm.get_last_applied()
            target = min(applied, self._committed_real(g))
            if target + 1 > b + int(self._m_devfirst[g]):
                first_new = target - b + 1
                self._m_devfirst[g] = first_new
                adv_mask[g] = True
                adv_first[g] = first_new
                adv_term[g] = applied_term
        if adv_mask.any():
            # FIXED-SHAPE masked update: an .at[gs].set scatter would
            # recompile for every distinct batch length (observed as
            # 300-700ms step spikes under load — long enough to pile ticks
            # and trigger spurious elections); whole-G where() compiles once
            s = self._state
            m = jnp.asarray(adv_mask)
            self._state = s._replace(
                first_index=jnp.where(
                    m, jnp.asarray(adv_first), s.first_index
                ),
                marker_term=jnp.where(m, jnp.asarray(adv_term), s.marker_term),
            )
        if bool(np.any(o["last_index"] > _REBASE_THRESHOLD)):
            # never rebase under an in-flight step: the mirrors and the
            # pending output would disagree by the rebase delta. The
            # threshold leaves orders of magnitude more headroom than the
            # one extra step this defers by.
            self._rebase_due = True

    # ------------------------------------------------------ progress watch
    def _set_owes(self, g: int, voting, self_slot: int) -> None:
        """Lane g's peer slots that it owes progress to while it leads:
        the voting members but itself."""
        row = self._m_owes[g]
        row[:] = voting
        if 0 <= self_slot < row.size:
            row[self_slot] = False

    def _watch_progress(self, o: dict) -> None:
        """Once a launch, on its final StepOutput: which stepped lanes owe
        progress and made none since the last sweep. Three debts, each
        with the sweeps it has stood unpaid: a leader's peer slot whose
        match is below the leader's last index and did not move; a lane
        that knows a leader and whose commit index is below its last
        index and did not move; a lane whose state machine's applied
        index is below its commit and did not move (a level taken every
        _STALL_LAUNCHES-th sweep against the one before: the one debt
        that needs a pass over the lanes, an eighth of them a sweep). A
        debt at _STALL_LAUNCHES is
        a stall: counted in step_stats() at every launch it stands,
        reported once where it crosses (_report_stalls).

        A sweep is a launch at least an election timeout (`_w_period`
        ticks of the engine's clock, which reads no clock) after the
        sweep before it. In a serving fleet that is every launch (1.9 s
        against 1 s). Where launches are short (bring-up, an idle loop's
        one a tick, 95 ms upstream) eight of them are no time at all:
        counted by launches alone the watch called 1 527 state machines
        of the fleet stalled at launch 16 of every bring-up, their
        bootstrap entries waiting their turn behind start_clusters
        (PERF.md section 6, PR 37). Between two sweeps the debts stand
        as the last one counted them.

        It is the first thing `place` does, right behind the fetch and
        before `apply` wakes the apply workers, and it hands the GIL to
        nobody: every numpy call in it is one around which numpy keeps
        the GIL (_SWEEP_ELEMENTS), so what it costs is its own CPU, one
        to three ms, and the loop thread has just taken the GIL back
        from the fetch's blocking copy, so those lie inside one switch
        interval. It reads the step's output and the mirrors the host
        keeps (active, recovering, base, who votes), none of which this
        launch's decode has yet to write; the commit and applied indexes
        it compares are levels, so the side of `apply` it reads them on
        changes nothing it counts.

        Lanes that are not active, under restore or quiesced owe nothing,
        nor does a slot parked for a snapshot or served by a catch-up:
        those have their own trackers and counters. A lane without a
        leader owes no commit: elections are counted where they happen.
        (A rebase shifts match and commit alike: it reads as movement,
        and the debts start again.) No per-entry or per-message work,
        and no clock but the sub-span's pair on a sampled iteration."""
        prof = self.profiler
        if prof.sampling:
            t0 = time.monotonic()
        now = self.clock.tick
        if now - self._w_tick >= self._w_period:
            self._w_tick = now
            self._sweep_progress(o)
        st = self._sstats
        st["peer_stall_steps"] += self._w_peer_n
        st["commit_stall_steps"] += self._w_commit_n
        st["apply_stall_steps"] += self._w_apply_n
        if prof.sampling:
            # 0 included: the anchor by which the readers tell a launch
            # without a stall from a program without the watch
            prof.fold("n.peer_stall_steps", self._w_peer_n)
            prof.fold("n.commit_stall_steps", self._w_commit_n)
            prof.fold("n.apply_stall_steps", self._w_apply_n)
            prof.add("watch", time.monotonic() - t0)

    def _sweep_progress(self, o: dict) -> None:
        """One sweep of the progress watch (see _watch_progress), in
        blocks of at most _SWEEP_ELEMENTS elements a numpy call: the
        lanes' own debts block of lanes by block, then the peer debts
        over the leading lanes' rows alone, a third to a fifth of the
        [G, P] planes."""
        self._w_sweeps += 1
        G = self._m_owes.shape[0]
        quiesced, role, leader = o["quiesced"], o["role"], o["leader"]
        last_index, commit_index = o["last_index"], o["commit_index"]
        led, leads_now = self._w_led, np.zeros((G,), bool)
        leaders = []
        n_commit = 0
        commits: List[int] = []  # debts that crossed the line
        applies: List[int] = []
        for b, lo in enumerate(range(0, G, _SWEEP_ELEMENTS)):
            sl = slice(lo, lo + _SWEEP_ELEMENTS)
            stepped = (
                self._m_active[sl] & ~self._m_recovering[sl] & ~quiesced[sl]
            )
            last, commit = last_index[sl], commit_index[sl]
            leads = stepped & (role[sl] == ROLE.LEADER)
            leads_now[sl] = leads
            leaders.append(np.flatnonzero(leads) + lo)
            lost = led[sl] & ~leads
            if lost.any():
                # no longer leading: nothing is owed
                for g in np.flatnonzero(lost).tolist():
                    self._w_peer_age[lo + g] = 0
            # ---- commit debts ---------------------------------------------
            cage, cseen = self._w_commit_age[sl], self._w_commit[sl]
            cage += 1
            cage *= (
                stepped & (leader[sl] != 0)
                & (last > commit) & (commit == cseen)
            )
            cseen[:] = commit
            if cage.max() >= _STALL_LAUNCHES:
                commits.extend(
                    (np.flatnonzero(cage == _STALL_LAUNCHES) + lo).tolist())
                n_commit += int(np.count_nonzero(cage >= _STALL_LAUNCHES))
            # ---- apply debts: a block's level every _STALL_LAUNCHES-th
            # sweep, the blocks taking turns so that no sweep looks at
            # more than an eighth of the state machines ------------------
            if (self._w_sweeps + b) % _STALL_LAUNCHES == 0:
                self._level_applied(lo, sl, stepped, commit, applies)
        self._w_led, self._w_commit_n = leads_now, n_commit
        peers = self._sweep_peers(o, np.concatenate(leaders))
        if peers or commits or applies:
            try:
                self._report_stalls(o, peers, commits, applies)
            except Exception:
                # a report that cannot be made (a lane torn down under
                # it) must not cost the loop its decode
                import traceback

                traceback.print_exc()

    def _sweep_peers(self, o: dict, leaders) -> List[Tuple[int, int]]:
        """The peer debts of one sweep, over the rows of the lanes that
        lead (`leaders`, lane indexes): keeps how many stand at or past
        the line (`_w_peer_n`) and returns those that crossed it, as
        (lane, slot). The [G, P] planes are read and written through
        their flat views by element index: numpy gives up the GIL around
        a fancy index into two dimensions whatever its size, and keeps
        it around one into a single dimension of a block's length."""
        lane_by_g = self._lane_by_g
        P = self._m_owes.shape[1]
        last_index = o["last_index"]
        matches, rstate = o["match"].reshape(-1), o["rstate"].reshape(-1)
        owes, seen = self._m_owes.reshape(-1), self._w_match.reshape(-1)
        ages = self._w_peer_age.reshape(-1)
        chunk = max(1, _SWEEP_ELEMENTS // P)
        slots = np.tile(np.arange(P), chunk)
        n_peer = 0
        peers: List[Tuple[int, int]] = []
        for i in range(0, leaders.size, chunk):
            rows = leaders[i:i + chunk]
            at = np.repeat(rows * P, P) + slots[:rows.size * P]
            match = matches[at]
            age = ages[at] + 1
            age *= (
                owes[at] & (rstate[at] != RSTATE.SNAPSHOT)
                & (match < np.repeat(last_index[rows], P))
                & (match == seen[at])
            )
            seen[at] = match
            if age.max() >= _STALL_LAUNCHES:
                for k in np.flatnonzero(age == _STALL_LAUNCHES).tolist():
                    g, p = divmod(int(at[k]), P)
                    lane = lane_by_g[g]
                    if (
                        lane is None
                        or p in lane.catchup
                        or p in lane.snap_inflight
                    ):
                        # served from the host log or waiting for its
                        # snapshot's acknowledgement: its tracker's,
                        # which retries by itself; the debt starts again
                        age[k] = 0
                    else:
                        peers.append((g, p))
                n_peer += int(np.count_nonzero(age >= _STALL_LAUNCHES))
            ages[at] = age
        self._w_peer_n = n_peer
        return peers

    def _level_applied(self, lo: int, sl, stepped, commit, new: list) -> None:
        """The apply debt's level over one block of lanes: those whose
        state machine stands below their commit index now, stood below
        it at the block's last level, and has not applied an entry
        since. Appends the lanes that are newly so to `new` and keeps
        how many stand so over all blocks (`_w_apply_n`)."""
        lane_by_g = self._lane_by_g
        real_commit = self._m_base[sl] + commit
        applied = self._w_applied[sl]
        was = applied.copy()
        owed = np.zeros(was.shape, bool)
        # a lane whose commit is at or below the applied index it had at
        # the last level owes nothing whatever it has applied since
        look = [
            g for g in np.flatnonzero(stepped & (real_commit > was)).tolist()
            if lane_by_g[lo + g] is not None
        ]
        if look:
            applied[look] = [
                lane_by_g[lo + g].node.sm.applied_level() for g in look
            ]
            owed[look] = real_commit[look] > applied[look]
        stalled = owed & self._w_apply_owed[sl] & (applied == was)
        before = self._w_apply_stalled[sl]
        new.extend((np.flatnonzero(stalled & ~before) + lo).tolist())
        self._w_apply_n += int(
            np.count_nonzero(stalled)) - int(np.count_nonzero(before))
        self._w_apply_owed[sl] = owed
        self._w_apply_stalled[sl] = stalled

    def _report_stalls(self, o: dict, peers, commits, applies) -> None:
        """Debts that crossed _STALL_LAUNCHES at this sweep (peer debts
        as (lane, slot), the others as lanes): each is counted
        (step_stats()['stalls_seen']), the first
        _STALL_EVENTS_PER_LAUNCH leave a `progress_stall` event with what
        a person needs to say why, and the launch leaves ONE warning: the
        count and the first in full. Traced or not, sampled or not: a run
        that dies at its read-back has said which replica stood still and
        since which launch. Rare by construction."""
        lane_by_g = self._lane_by_g
        launch = self.launch_no
        seen = len(peers) + len(commits) + len(applies)
        events: List[dict] = []

        def lane_fields(kind: str, g: int) -> dict:
            node = lane_by_g[g].node
            return dict(
                kind=kind,
                cluster=node.cluster_id,
                node=node.node_id(),
                lane=g,
                launch=launch,
                # sweeps the debt has stood, and the launch at which
                # it cannot have begun later (the apply level is taken
                # every _STALL_LAUNCHES-th sweep: one to two stretches)
                age=_STALL_LAUNCHES,
                since=launch - _STALL_LAUNCHES,
                role=int(o["role"][g]),
                term=int(o["term"][g]),
                leader=int(o["leader"][g]) - 1,
                base=int(self._m_base[g]),
                last=int(o["last_index"][g]),
                commit=int(o["commit_index"][g]),
                applied=node.sm.last_applied_index(),
                steps=self._multi,
            )

        room = _STALL_EVENTS_PER_LAUNCH
        if peers:
            # the one plane the StepOutput does not carry
            # lint: allow(device-sync/cross-function) a stall's crossing
            # only: one blocking read a sweep that found a new stall
            nxt = np.asarray(self._state.next)
        for g, p in peers[:room]:
            lane = lane_by_g[g]
            ev = lane_fields("peer", g)
            peer_nid = lane.rev.get(p)
            ev.update(
                peer_slot=p,
                peer=peer_nid if peer_nid is not None else 0,
                match=int(o["match"][g, p]),
                next=int(nxt[g, p]),
                rstate=int(o["rstate"][g, p]),
                route=int(self._np_route[g, p]),
            )
            dst = self._route.get((lane.node.cluster_id, peer_nid))
            if dst is not None:
                d = dst.g
                ev.update(
                    peer_lane=d,
                    peer_resid=int(self._m_resid[d]),
                    peer_active=bool(self._m_active[d]),
                    peer_recovering=bool(self._m_recovering[d]),
                    peer_role=int(o["role"][d]),
                    peer_term=int(o["term"][d]),
                    peer_last=int(o["last_index"][d]),
                    peer_commit=int(o["commit_index"][d]),
                )
            events.append(ev)
        for kind, gs in (("commit", commits), ("apply", applies)):
            for g in gs[: room - len(events)]:
                events.append(lane_fields(kind, g))
        rec = flight_recorder()
        for ev in events:
            rec.record("progress_stall", **ev)
        _plog.warningf(
            "progress watch: %d debt(s) of progress unpaid for %d sweeps "
            "(launches an election timeout apart or more) at launch %d; "
            "the first: %s",
            seen, _STALL_LAUNCHES, launch,
            " ".join(f"{k}={v}" for k, v in events[0].items()),
        )
        # last: whoever reads the count finds the events and the line
        self._sstats["stalls_seen"] += seen

    def _do_rebase(self) -> None:
        """Shift device indexes down so they never near 2**31. The delta is
        a multiple of W (ring-slot invariant, cf. ops/state.rebase)."""
        W = self.kcfg.log_window
        G = self.kcfg.groups
        delta = np.zeros((G,), np.int32)
        with self._lanes_mu:
            lanes = [ln for ln in self._lanes.values() if ln.active]
        for lane in lanes:
            g = lane.g
            d = int((self._m_devfirst[g] - 1) // W) * W
            if d > 0:
                delta[g] = d
                self._m_base[g] += d
                self._m_devfirst[g] -= d
                self._m_commit[g] -= d
                self._m_last[g] -= d
        if delta.any():
            self._state = rebase(self._state, jnp.asarray(delta))
            # window bases moved: the routing table's per-peer base
            # deltas must be recomputed before the next dispatch
            self._routes_dirty = True
            if self._m_resid.any():
                # the device-resident residual inbox carries indexes in
                # DESTINATION units: shift the index-valued fields of
                # each parked message by its destination lane's delta
                # (type-aware, mirroring which fields _pack_wire stages
                # per message type). Rare path — eager device ops.
                r = self._resid
                d = jnp.asarray(delta)[:, None]
                mt = r.mtype
                idx_t = (
                    (mt == MSG.REPLICATE)
                    | (mt == MSG.REPLICATE_RESP)
                    | (mt == MSG.READ_INDEX_RESP)
                    | (mt == MSG.REQUEST_VOTE)
                )
                commit_t = (mt == MSG.REPLICATE) | (mt == MSG.HEARTBEAT)
                hint_t = mt == MSG.REPLICATE_RESP
                self._resid = r._replace(
                    log_index=jnp.where(
                        idx_t, r.log_index - d, r.log_index
                    ),
                    commit=jnp.where(
                        commit_t, jnp.maximum(r.commit - d, 0), r.commit
                    ),
                    hint=jnp.where(
                        hint_t, jnp.maximum(r.hint - d, 0), r.hint
                    ),
                )

    # ----------------------------------------------------------- reconciles
    def _apply_reconciles(self) -> None:
        batch: List[_Lane] = []
        while True:
            try:
                op = self._reconq.popleft()
            except IndexError:
                break
            if op[0] == "activate":
                batch.extend(op[1])
                continue
            if op[0] == "cc_done":
                # staged below with the other lane patches: one fixed-
                # shape mask op instead of a per-lane scatter (bootstrap
                # emits one per cluster)
                lane = self._lane_of(op[1])
                if lane is not None and lane.active:
                    self._staged_patch()["cc_clear"][lane.g] = True
                    lane.cc_inflight = False
                continue
            if batch:
                self._flush_patch()  # a lane index freed above is reused
                self._activate_batch(batch)
                batch = []
            try:
                kind = op[0]
                if kind == "barrier":
                    op[1].set()
                elif kind == "deactivate":
                    self._deactivate(op[1])
                elif kind == "membership":
                    self._reconcile_membership(op[1])
                elif kind == "restore":
                    self._reconcile_restore(op[1], op[2])
                elif kind == "recover_done":
                    lane = self._lane_of(op[1])
                    if lane is not None:
                        lane.recovering = False
                        self._m_recovering[lane.g] = False
                        self._routes_dirty = True
            except Exception:
                import traceback

                traceback.print_exc()
        self._flush_patch()
        if batch:
            self._activate_batch(batch)

    # the planes one _make_patch_fn call takes, staged host-side
    _PATCH_MASKS = ("remap", "restore", "to_follower", "deact", "cc_clear")
    _PATCH_PEER_FLAGS = ("member", "voting", "observer", "witness")
    _PATCH_COLS = ("self_slot", "term", "marker_term")

    def _staged_patch(self) -> dict:
        """The device patch this iteration's reconciles are staging
        (created on first use, applied by _flush_patch)."""
        v = self._patch
        if v is None:
            G, P = self.kcfg.groups, self.kcfg.peers
            v = {name: np.zeros((G,), bool) for name in self._PATCH_MASKS}
            for name in self._PATCH_PEER_FLAGS:
                v[name] = np.zeros((G, P), bool)
            for name in self._PATCH_COLS:
                v[name] = np.zeros((G,), np.int32)
            v["src"] = np.full((G, P), -1, np.int32)
            self._patch = v
        return v

    def _drop_parked(self, lane: _Lane, gone: bool = False) -> None:
        """Forget what the last K-step launch left parked on the device
        for `lane`: its rows of the residual inbox and the payload copies
        that wait on their acceptance; of a lane that is `gone` also the
        copies it was to be the source of. Lost messages to Raft, which
        resends. Staged, and applied by _flush_patch for every lane of
        the iteration at once: a fleet's stop drops tens of thousands."""
        g = lane.g
        if self._m_resid[g]:
            self._m_resid[g] = 0
            self._resid_drop.append(g)
        if self._pending_rep_copies:
            self._copies_drop.append((lane, gone))

    def _stage_remap(self, lane: _Lane, perm: Dict[int, int], mem) -> dict:
        """Stage lane's re-ranked slots (perm: old slot -> new slot, from
        _Lane.set_slots) and its membership flags; returns the staged
        patch for the caller's own fields. A lane patched twice in one
        iteration takes two calls: the second re-ranks the first's result."""
        g = lane.g
        if self._m_resid[g]:
            # a parked row names its sender by the slot it had when the
            # row was routed; under the new numbering that slot may be
            # another member's (an acknowledgement booked to a joiner
            # that holds nothing), and only the host path carries node
            # ids to translate by
            self._drop_parked(lane)
        if self._patch is not None and self._patch["remap"][g]:
            self._flush_patch()
        v = self._staged_patch()
        P = self.kcfg.peers
        v["remap"][g] = True
        src = v["src"][g]
        for old, new in perm.items():
            if old < P and new < P:
                src[new] = old
        witness = v["witness"][g]
        for nid, slot in lane.slots.items():
            if slot >= P:
                continue
            v["member"][g, slot] = True
            if nid in mem.observers:
                v["observer"][g, slot] = True
            elif nid in mem.witnesses:
                witness[slot] = True
                v["voting"][g, slot] = True
            else:
                v["voting"][g, slot] = True
        lane.wit_slots = frozenset(np.nonzero(witness)[0].tolist())
        self_slot = lane.self_slot()
        if self_slot < 0:
            self_slot = lane.slot_of(lane.node.node_id(), provisional=True)
        v["self_slot"][g] = max(self_slot, 0)
        self._set_owes(g, v["voting"][g], self_slot)
        # the leader mirror is a slot+1 reference too; it stays readable
        # (get_leader_id, leader_snapshot) against the new lane.rev
        old_leader = int(self._m_leader[g]) - 1
        self._m_leader[g] = perm.get(old_leader, -1) + 1
        return v

    def _flush_patch(self) -> None:
        v, self._patch = self._patch, None
        if v is not None:
            self._state = _make_patch_fn(self.kcfg)(
                self._state, {k: jnp.asarray(a) for k, a in v.items()}
            )
        if self._resid_drop:
            gs, self._resid_drop = self._resid_drop, []
            mask = np.zeros((self.kcfg.groups, 1), bool)
            mask[gs] = True
            r = self._resid
            self._resid = r._replace(
                mtype=jnp.where(mask, jnp.int32(MSG.NONE), r.mtype)
            )
        if self._copies_drop:
            drop, self._copies_drop = self._copies_drop, []
            to = {lane for lane, _ in drop}
            gone = {lane for lane, was in drop if was}
            self._pending_rep_copies = [
                c for c in self._pending_rep_copies
                if c[3] not in to and c[2] not in gone
            ]

    def _lane_of(self, node) -> Optional[_Lane]:
        lane = node._vec_lane
        if lane is None:
            return None
        with self._lanes_mu:
            return lane if self._lanes.get(lane.key) is lane else None

    def _compute_activation(self, lane: _Lane) -> Optional[dict]:
        """Host-side half of lane bring-up: bootstrap (initial start),
        restart replay, or join-as-empty. Mirrors Peer.launch +
        node.replayLog (cf. core/peer.py:75-94, node.go:553-583). Returns
        the per-field device values for the batched scatter."""
        node = lane.node
        node.recover_initial_snapshot()
        cfg = lane.cfg
        g = lane.g
        W = self.kcfg.log_window
        P = self.kcfg.peers
        # membership sources: SM image (restart w/ snapshot) else bootstrap
        mem = node.sm.get_membership()
        member_ids = set(mem.addresses) | set(mem.observers) | set(mem.witnesses)
        if not member_ids:
            member_ids = {a.node_id for a in node._vec_addresses}
        bootstrap = node._vec_initial and node._vec_new_node
        lane.set_slots(member_ids)
        self_slot = lane.self_slot()
        if self_slot < 0 and node.node_id() not in lane.slots:
            # join path: self not yet in membership; park on a free slot
            self_slot = lane.slot_of(node.node_id(), provisional=True)
        obs_ids = set(mem.observers)
        wit_ids = set(mem.witnesses)
        if not mem.addresses and bootstrap:
            obs_ids, wit_ids = set(), set()
        lane.mem_sig = (
            frozenset(member_ids), frozenset(obs_ids), frozenset(wit_ids)
        )
        # persisted protocol state
        st = self._logdb_state(node)
        snap = node.snapshotter.get_most_recent_snapshot() if node.snapshotter else None
        snap_index = snap.index if snap is not None and not snap.is_empty() else 0
        first, last = node.log_reader.get_range()
        ents: List[Entry] = []
        if last >= first and last > 0:
            try:
                ents = node.log_reader.entries(first, last + 1, 1 << 30)
            except Exception:
                ents = []
        term = st.term
        vote_nid = st.vote
        committed = st.commit
        if bootstrap and not ents:
            # initial start: membership enters the log as config-change
            # entries at term 1, committed immediately (core/peer.py:273-294)
            addrs = sorted(node._vec_addresses, key=lambda a: a.node_id)
            for i, pa in enumerate(addrs):
                cc = ConfigChange(
                    type=ConfigChangeType.ADD_NODE,
                    node_id=pa.node_id,
                    initialize=True,
                    address=pa.address,
                )
                ents.append(
                    Entry(
                        type=EntryType.CONFIG_CHANGE,
                        term=1,
                        index=i + 1,
                        cmd=encode_config_change(cc),
                    )
                )
            committed = len(ents)
            term = max(term, 1)
        elif node._vec_new_node and not cfg.is_observer and not cfg.is_witness:
            term = max(term, 1)
        b = snap_index
        last_real = ents[-1].index if ents else max(snap_index, last if last else 0)
        dev_last = max(last_real - b, 0)
        dev_first = max(dev_last - W + 1, 1)
        committed = max(committed, snap_index)
        # ring metadata from the replayed entries
        ring_terms = np.zeros((W,), np.int32)
        ring_cc = np.zeros((W,), bool)
        for e in ents:
            lane.arena[e.index] = e
            di = e.index - b
            if dev_first <= di <= dev_last:
                ring_terms[di % W] = e.term
                ring_cc[di % W] = e.type == EntryType.CONFIG_CHANGE
        # arena holds nothing at or below the snapshot: seed the applied
        # watermark there directly (no entries below it to discount) so
        # the first phase-4 mark_applied walks the window, not the whole
        # history from zero
        lane.arena.applied = max(snap_index, lane.arena.applied)
        marker = dev_first - 1
        if marker == 0:
            marker_term = snap.term if snap_index and b == snap_index else 0
        else:
            try:
                marker_term = node.log_reader.term(b + marker)
            except Exception:
                marker_term = 0
        member = np.zeros((P,), bool)
        voting = np.zeros((P,), bool)
        observer = np.zeros((P,), bool)
        witness = np.zeros((P,), bool)
        for nid, slot in lane.slots.items():
            if slot >= P or nid not in member_ids:
                # provisional parkings (the join path parks self and
                # learned senders on free slots) are NOT members: marking
                # them voting would let an empty-membership join lane
                # self-elect as a one-node group and poison its log
                continue
            member[slot] = True
            if nid in obs_ids:
                observer[slot] = True
            elif nid in wit_ids:
                witness[slot] = True
                voting[slot] = True
            else:
                voting[slot] = True
        lane.wit_slots = frozenset(np.nonzero(witness)[0].tolist())
        role = (
            ROLE.OBSERVER if cfg.is_observer
            else ROLE.WITNESS if cfg.is_witness
            else ROLE.FOLLOWER
        )
        vote_slot = lane.slots.get(vote_nid, -1)
        et = max(cfg.election_rtt, 3)
        hb = max(cfg.heartbeat_rtt, 1)
        from ..ops.state import _mix

        rand_to = et + _mix(lane_seed(g), term, max(self_slot, 0)) % et
        # quiesce threshold: 10x the election timeout (cf. quiesce.go:84-86)
        quiesce_on = bool(cfg.quiesce)
        quiesce_threshold = 10 * et
        # ---- numpy mirrors ------------------------------------------------
        self._m_base[g] = b
        self._m_devfirst[g] = dev_first
        self._m_term[g] = term
        self._m_role[g] = role
        self._m_leader[g] = 0
        self._m_commit[g] = committed - b
        self._m_last[g] = dev_last
        # catch-up burst cap: at most this many coalesced ticks apply in
        # one kernel step; the rest of a stall's backlog is shed. The old
        # cap (election RTT) let a single post-stall step add
        # `election_rtt` ticks — every follower lane crossed rand_timeout
        # ∈ [et, 2et) within two steps simultaneously, collapsing the
        # randomized election spread into synchronized split-vote storms
        # (the ROADMAP seed flake). Capping at the heartbeat RTT keeps
        # timers advancing while a live leader's next heartbeat can still
        # land between bursts.
        burst = self._catchup_tick_cap or hb
        self._m_tick_cap[g] = max(1, min(cfg.election_rtt, burst))
        self._m_active[g] = True
        self._m_snap_every[g] = cfg.snapshot_entries
        self._m_applied_since[g] = 0
        self._ctr[g] = 0  # a reused lane must not inherit event counters
        self._m_snap_pending[g] = False
        self._m_quiesced[g] = False  # a reused lane must not inherit this
        self._m_leader_change_tick[g] = self.clock.tick
        self._set_owes(g, voting, self_slot)
        self._w_period = max(self._w_period, int(cfg.election_rtt))
        return dict(
            self_slot=max(self_slot, 0),
            member=member,
            voting=voting,
            observer=observer,
            witness=witness,
            term=term,
            vote=vote_slot + 1 if vote_slot >= 0 else 0,
            role=role,
            election_timeout=et,
            heartbeat_timeout=hb,
            rand_timeout=rand_to,
            check_quorum=cfg.check_quorum,
            prevote_on=bool(cfg.pre_vote),
            lease_on=bool(cfg.lease_read),
            lease_margin=cfg.lease_margin_ticks() if cfg.lease_read else 0,
            first_index=dev_first,
            marker_term=marker_term,
            last_index=dev_last,
            committed=committed - b,
            processed=max(snap_index - b, 0),
            applied=max(snap_index - b, 0),
            unsaved_from=1 if bootstrap else dev_last + 1,
            log_term=ring_terms,
            log_is_cc=ring_cc,
            next=dev_last + 1,
            quiesce_on=quiesce_on,
            quiesce_threshold=quiesce_threshold,
        )

    # per-lane value keys forwarded into the jitted activation scatter
    _ACT_COLS = (
        ("self_slot", np.int32),
        ("term", np.int32),
        ("vote", np.int32),
        ("role", np.int32),
        ("election_timeout", np.int32),
        ("heartbeat_timeout", np.int32),
        ("rand_timeout", np.int32),
        ("check_quorum", bool),
        ("prevote_on", bool),
        ("lease_on", bool),
        ("lease_margin", np.int32),
        ("first_index", np.int32),
        ("marker_term", np.int32),
        ("last_index", np.int32),
        ("committed", np.int32),
        ("processed", np.int32),
        ("applied", np.int32),
        ("unsaved_from", np.int32),
        ("next", np.int32),
        ("quiesce_on", bool),
        ("quiesce_threshold", np.int32),
    )
    _ACT_MATS = (
        ("member", bool),
        ("voting", bool),
        ("observer", bool),
        ("witness", bool),
        ("log_term", np.int32),
        ("log_is_cc", bool),
    )

    def _activate_batch(self, lanes: List[_Lane]) -> None:
        """Activate many lanes with ONE jitted scatter call — the engine
        analogue of ops/state.configure_groups_uniform. Batches pad to
        power-of-4 buckets from 16 up so the compile caches hit. Timed
        into the bring-up account."""
        t0 = time.monotonic()
        if self._bring_launch0 is None:
            self._bring_launch0 = self.launch_no
        try:
            self._activate_lanes(lanes)
        finally:
            self._bring_activate_s += time.monotonic() - t0

    def _activate_lanes(self, lanes: List[_Lane]) -> None:
        vals: List[dict] = []
        gs: List[int] = []
        for lane in lanes:
            try:
                v = self._compute_activation(lane)
            except Exception:
                import traceback

                traceback.print_exc()
                continue
            if v is not None:
                vals.append(v)
                gs.append(lane.g)
                lane.active = True
                self._lanes_gen += 1
        if not vals:
            return
        n = len(vals)
        self._bring_activated += n
        if self.profiler.sampling:
            self.profiler.fold("n.lanes_joined", n)
        # no bucket below 16: replicas that join a running core come one
        # to a few an iteration, and each smaller bucket (1, 4) was one
        # more compile, the second of them inside a serving window
        bucket = 16
        while bucket < n:
            bucket *= 4
        bucket = min(bucket, self.kcfg.groups)
        pad = bucket - n
        # padding repeats the last lane (duplicate scatter indexes with
        # identical values are order-independent)
        gi = np.asarray(gs + [gs[-1]] * pad, np.int32)
        v = {}
        for key, dtype in self._ACT_COLS:
            a = np.asarray([x[key] for x in vals], dtype)
            if pad:
                a = np.concatenate([a, np.repeat(a[-1:], pad, 0)])
            v[key] = jnp.asarray(a)
        for key, dtype in self._ACT_MATS:
            a = np.stack([x[key] for x in vals]).astype(dtype)
            if pad:
                a = np.concatenate([a, np.repeat(a[-1:], pad, 0)])
            v[key] = jnp.asarray(a)
        fn = _make_activate_fn(self.kcfg, bucket)
        self._state = fn(self._state, jnp.asarray(gi), v)
        self._routes_dirty = True
        self._ready.set()

    def _logdb_state(self, node) -> State:
        st, _ = node.log_reader.node_state()
        return st if st is not None else State()

    def _deactivate(self, lane: _Lane) -> None:
        g = lane.g
        with self._lanes_mu:
            if self._lane_by_g[g] is not lane:
                # already reaped (a double remove_node, or a crash path
                # racing a graceful stop): freeing g twice would hand the
                # same lane index to two tenants
                return
        self._staged_patch()["deact"][g] = True
        lane.active = False
        self._lanes_gen += 1
        if self.profiler.sampling:
            self.profiler.fold("n.lanes_left", 1)
        # zero the freed lane's host planes so nothing leaks into the next
        # tenant of g: the inbox staging rows (the next occupant must
        # never see a stale row where _pack left data the kernel has
        # already consumed), the pending-tick row, and every protocol
        # mirror (lane_stats/decode gate on _m_active, but stale bases
        # would corrupt the first reads after a mis-gated access)
        for name, plane in self._buf.items():
            plane[g] = MSG.NONE if name == "mtype" else 0
        self._ticks[g] = 0
        self._m_base[g] = 0
        self._m_devfirst[g] = 1
        self._m_term[g] = 0
        self._m_role[g] = ROLE.FOLLOWER
        self._m_leader[g] = 0
        self._m_commit[g] = 0
        self._m_last[g] = 0
        self._m_tick_cap[g] = 1
        self._m_active[g] = False
        self._m_snap_every[g] = 0
        self._m_applied_since[g] = 0
        self._m_snap_pending[g] = False
        self._m_quiesced[g] = False
        self._m_host[g] = 0
        self._m_leader_change_tick[g] = 0
        self._m_recovering[g] = False
        self._m_commit_owed[g] = False
        self._m_owes[g] = False
        self._w_apply_owed[g] = False
        self._ctr_left += self._ctr[g]
        self._ctr[g] = 0
        self._carry.discard(lane)
        self._catchups.discard(lane)
        self._snapfb.discard(lane)
        # multi-step: the freed lane must not hand its device-routed
        # residual rows or pending payload copies to the next tenant
        self._drop_parked(lane, gone=True)
        self._routes_dirty = True
        lane.node._vec_lane = None
        with self._lanes_mu:
            self._lane_by_g[g] = None
            self._free.append(g)

    def _reconcile_membership(self, node) -> None:
        """Recompute the canonical slot mapping from the applied membership
        image and permute the per-peer device state accordingly."""
        lane = self._lane_of(node)
        if lane is None or not lane.active:
            return
        mem = node.sm.get_membership()
        member_ids = set(mem.addresses) | set(mem.observers) | set(mem.witnesses)
        if not member_ids:
            return
        sig = (
            frozenset(member_ids),
            frozenset(mem.observers),
            frozenset(mem.witnesses),
        )
        if sig == lane.mem_sig:
            return  # image unchanged (bootstrap CCs restate membership)
        lane.mem_sig = sig
        g = lane.g
        perm = lane.set_slots(member_ids)
        patch = self._stage_remap(lane, perm, mem)
        # self-promotion: an observer added as a full member becomes a
        # follower in place, inheriting its replicated log (cf. raft.go
        # addNode / scalar Raft.add_node become_follower path)
        if (
            int(self._m_role[g]) == ROLE.OBSERVER
            and node.node_id() in mem.addresses
        ):
            self._m_role[g] = ROLE.FOLLOWER
            patch["to_follower"][g] = True
        # catchup/snapshot-feedback mirrors use slots: remap
        remapped = {}
        for p, v in lane.catchup.items():
            if p in perm:
                remapped[perm[p]] = v
        lane.catchup = remapped
        if not lane.catchup:
            self._catchups.discard(lane)
        lane.snap_inflight = {
            perm[p]: v for p, v in lane.snap_inflight.items() if p in perm
        }
        if not lane.snap_inflight:
            self._snapfb.discard(lane)
        # the slot mapping changed: rebuild the on-device routing rows
        self._routes_dirty = True

    def _reconcile_restore(self, node, ss: Snapshot) -> None:
        """An InstallSnapshot finished recovering: rebuild the lane at the
        snapshot point (cf. raft.go:439-517 restore + restoreRemotes)."""
        lane = self._lane_of(node)
        if lane is None:
            return
        g = lane.g
        mem = ss.membership or node.sm.get_membership()
        member_ids = set(mem.addresses) | set(mem.observers) | set(mem.witnesses)
        perm = lane.set_slots(member_ids)
        lane.mem_sig = (
            frozenset(member_ids),
            frozenset(mem.observers),
            frozenset(mem.witnesses),
        )
        lane.arena = _Arena(self.kcfg.log_window)
        # everything at or below the installed snapshot is applied; seeding
        # the watermark keeps the next phase-4 mark_applied from walking
        # the whole history from zero (same as the activation path)
        lane.arena.applied = max(ss.index, 0)
        lane.catchup = {}
        lane.snap_inflight = {}
        self._catchups.discard(lane)
        self._snapfb.discard(lane)
        # the lane may carry the snapshot sender's (higher) term, adopted
        # in _handle_install_snapshot; the restore ack must not be
        # droppable as stale by the leader. The term mirror is the
        # device's: nothing is in flight when a reconcile runs.
        term = max(int(self._m_term[g]), ss.term, lane.adopted_term)
        lane.adopted_term = 0
        patch = self._stage_remap(lane, perm, mem)
        patch["restore"][g] = True
        patch["term"][g] = term
        patch["marker_term"][g] = ss.term
        # ---- numpy mirrors ------------------------------------------------
        self._m_base[g] = ss.index
        self._m_devfirst[g] = 1
        self._m_term[g] = term
        self._m_commit[g] = 0
        self._m_last[g] = 0
        self._m_quiesced[g] = False
        lane.recovering = False
        self._m_recovering[g] = False
        # base moved + recovering cleared: recompute routes/base deltas
        self._routes_dirty = True
        # restart/rejoin forensics: a lagging rejoiner whose log was
        # compacted past its index MUST take this path — the longhaul
        # runner and the restart tests assert on this event
        flight_recorder().record(
            "snapshot_installed", cluster=node.cluster_id,
            node=node.node_id(), index=ss.index, term=ss.term,
        )
        # persist the post-restore hard state and ack the leader so its
        # remote leaves the Snapshot state (raft.go handleInstallSnapshot)
        node.logdb.save_raft_state(
            [
                Update(
                    cluster_id=node.cluster_id,
                    node_id=node.node_id(),
                    state=State(term=term, vote=0, commit=ss.index),
                )
            ]
        )
        leader = lane.rev.get(int(self._m_leader[g]) - 1)
        sender = leader if leader and leader != node.node_id() else None
        if sender is None:
            # best effort: ack every voting peer; only the leader cares
            senders = [n for n in lane.slots if n != node.node_id()]
        else:
            senders = [sender]
        for nid in senders:
            node._send_message(
                Message(
                    type=MT.REPLICATE_RESP,
                    cluster_id=node.cluster_id,
                    to=nid,
                    from_=node.node_id(),
                    term=term,
                    log_index=ss.index,
                )
            )

    # --------------------------------------------------------- worker mains
    def _task_worker_main(self, worker: int) -> None:
        batch: list = []
        apply: list = []
        prof = self.profiler
        while not self._stopped.is_set():
            cids = self.task_ready.wait_and_take(worker)
            if not cids:
                continue
            # one span a wake-up, `rsm.handle`: this worker's time over the
            # ready nodes it took. Its start is t_apply0 of the sampled
            # entries they hold (read always: a request's sampling is its
            # own). The loop's flag of the moment samples the span: at
            # ratio N about one wake-up in N is timed.
            t0 = time.monotonic()
            sampled = prof.sampling
            if sampled:
                c0 = time.thread_time()
                n_ents = n_run_ents = n_runs = n_ccs = 0
            for cid in cids:
                node = self.get_node(cid)
                if node is None or node.stopped:
                    continue
                sm = node.sm
                if not sm.loaded(OffloadFrom.COMMIT_WORKER):
                    continue  # lost the race with NodeHost close
                node._apply_t0 = t0
                if sampled:
                    # what the manager applies and how (its own plain
                    # counters: entries, those a run applied, runs)
                    n_ents -= sm.applied_entries
                    n_run_ents -= sm.applied_run_entries
                    n_runs -= sm.applied_runs
                    n_ccs -= sm.config_changes_applied
                try:
                    node.handle_task(batch, apply)
                except Exception:
                    import traceback

                    traceback.print_exc()
                finally:
                    sm.offloaded(OffloadFrom.COMMIT_WORKER)
                if sampled:
                    n_ents += sm.applied_entries
                    n_run_ents += sm.applied_run_entries
                    n_runs += sm.applied_runs
                    n_ccs += sm.config_changes_applied
                if sm.task_queue.size() > 0:
                    self.set_task_ready(cid)
            if sampled:
                prof.observe(
                    "rsm.handle", time.monotonic() - t0,
                    time.thread_time() - c0, engine="rsm",
                )
                prof.fold("n.apply_entries", n_ents)
                prof.fold("n.apply_run_entries", n_run_ents)
                prof.fold("n.apply_runs", n_runs)
                prof.fold("n.config_changes_applied", n_ccs)

    def _snapshot_worker_main(self, worker: int) -> None:
        """A replica waiting to be restored is out of service, a periodic
        save is not: installs go first, and the worker looks for new ones
        between two tasks. With two workers under one GIL and a save of
        10 ms and more, a restore that queued behind the saves of every
        node its worker had taken waited whole launches for its turn, and
        a fleet's joiners piled up behind the fleet's saves."""
        prof = self.profiler
        ready = self.snapshot_ready
        installs: list = []
        saves: list = []

        def admit(cids) -> None:
            for cid in cids:
                node = self.get_node(cid)
                if node is not None and not node.stopped:
                    if node.ss.recovering_from_snapshot():
                        installs.append(node)
                    else:
                        saves.append(node)

        while not self._stopped.is_set():
            admit(ready.wait_and_take(worker))
            while installs or saves:
                node = installs.pop() if installs else saves.pop()
                # one span a task (`snap.save`, `snap.recover`,
                # `snap.compact`) and the node's own plain counts, on
                # sampled tasks only: the loop's flag of the moment
                # decides, as for `rsm.handle`
                observe = (
                    self._observe_snapshot_task if prof.sampling else None
                )
                if node.sm.loaded(OffloadFrom.SNAPSHOT_WORKER):
                    if observe is not None:
                        before = (
                            node.snapshots_saved, node.snapshots_installed,
                            node.log_compactions,
                        )
                    try:
                        node.run_snapshot_work(observe)
                    except Exception:
                        import traceback

                        traceback.print_exc()
                    finally:
                        node.sm.offloaded(OffloadFrom.SNAPSHOT_WORKER)
                    if observe is not None:
                        prof.fold(
                            "n.snapshots_saved",
                            node.snapshots_saved - before[0],
                        )
                        prof.fold(
                            "n.snapshots_installed",
                            node.snapshots_installed - before[1],
                        )
                        prof.fold(
                            "n.log_compactions",
                            node.log_compactions - before[2],
                        )
                    lane = self._lane_of(node)
                    if lane is not None:
                        self._m_snap_pending[lane.g] = False
                # (else: lost the race with NodeHost close)
                admit(ready.take(worker))

    def _observe_snapshot_task(self, name: str, t0: float, c0: float) -> None:
        self.profiler.observe(
            name, time.monotonic() - t0, time.thread_time() - c0,
            engine="snapshot",
        )

    # --------------------------------------------------------------- control
    def fairness_stats(self) -> dict:
        """Tick-fairness watchdog snapshot: inter-iteration latency vs the
        tick period, the starvation gauge, burst clamps, enforced yields."""
        return self.watchdog.stats()

    def step_stats(self) -> dict:
        """Cumulative per-step columnar counters (kernel steps, outbound
        messages by plane, lanes with commit advance, elections started,
        entries handed to the RSM) — derived host-side from the decoded
        StepOutput, so reading them costs nothing on the device. Also
        the kernel launches dispatched, and the protocol steps the next
        one will run (the engine's own choice where steps_per_sync is
        None)."""
        return dict(
            self._sstats, launches=self.launch_no,
            steps_per_launch=self._multi,
        )

    def lease_stats(self) -> dict:
        """Cumulative lease read counters across all lanes: 'local' =
        linearizable reads served straight off a live leader lease (no
        quorum round), 'fallback' = lease-enabled reads that degraded to
        the ReadIndex quorum path (lease expired / revoked / clock
        suspect). Plain int reads of decode-maintained counters."""
        return {"local": self._lease_local, "fallback": self._lease_fb}

    def counter_stats(self) -> Dict[str, int]:
        """Cumulative protocol-event totals across all lanes, keyed by
        the canonical CTR_NAMES vocabulary (elections started/won,
        heartbeats sent, replicate rejects, commit advances IN INDEX
        UNITS, lease served/fallback, read confirmations). The deltas
        were counted ON DEVICE inside step_batch — including K>1 inner
        steps and device-routed co-hosted traffic — and folded by the
        decode phase; reading them is a plain numpy sum over the
        cumulative mirror, zero device syncs."""
        totals = self._ctr.sum(axis=0) + self._ctr_left
        return {name: int(totals[i]) for i, name in enumerate(CTR_NAMES)}

    def lane_counters(self) -> Dict[tuple, Dict[str, int]]:
        """Per-lane cumulative event counters (lane key -> CTR_NAMES
        dict), same sourcing as counter_stats. Joined with lane_stats on
        the lane key by tools.top's heat ranking."""
        out: Dict[tuple, Dict[str, int]] = {}
        with self._lanes_mu:
            lanes = list(self._lanes.values())
        ctr = self._ctr
        for lane in lanes:
            if not lane.active:
                continue
            row = ctr[lane.g]
            out[lane.key] = {
                name: int(row[i]) for i, name in enumerate(CTR_NAMES)
            }
        return out

    def device_census(self) -> dict:
        """HBM census snapshot: static plane bytes (reported once at
        allocation) + per-lane logical log fill folded from the decode-
        maintained mirrors — zero device syncs, like lane_stats. The
        ROADMAP paged-arena item's measured baseline."""
        return self.census.snapshot(
            last=self._m_last,
            devfirst=self._m_devfirst,
            active=self._m_active,
        )

    def pressure_stats(self) -> dict:
        """Serving-front backpressure probe (serving.backpressure.
        SaturationMonitor): inbox-row occupancy of the last packed step
        (fraction of the worked lanes' K-row capacity actually filled)
        and the staged-row backlog carried between steps. Plain reads of
        the pack-maintained counters — lock-free, zero device syncs."""
        lanes = self._p_inbox_lanes
        if not lanes:
            return {"inbox_occupancy": 0.0, "staged_backlog": 0}
        return {
            "inbox_occupancy": self._p_inbox_rows
            / (lanes * self.kcfg.inbox_depth),
            "staged_backlog": self._p_staged_backlog,
        }

    def _active_lanes(self, host: Optional[int] = None):
        """(active lanes, their lane indexes) of the core, or of one
        host's, listed once for as long as no lane joins, leaves,
        activates or deactivates; the caller reads the columns by index."""
        gen = self._lanes_gen  # first: a bump after it lists again
        hit = self._active_cache.get(host)
        if hit is not None and hit[0] == gen:
            return hit[1], hit[2]
        with self._lanes_mu:
            src = self._lanes if host is None else self._host_lanes.get(host, {})
            lanes = [lane for lane in src.values() if lane.active]
        gs = np.fromiter((lane.g for lane in lanes), np.int64, len(lanes))
        self._active_cache[host] = (gen, lanes, gs)
        return lanes, gs

    def lane_stats(self, host: Optional[int] = None) -> Dict[tuple, dict]:
        """Per-lane introspection derived ENTIRELY from the numpy mirrors
        the decode phase already maintains — zero device syncs: lane key ->
        {node_id, leader_id, term, commit_gap, ticks_since_leader_change}
        of every active lane, or of one host's.
        commit_gap is last_index - commit_index in device units (how far
        the lane's accepted log runs ahead of its quorum commit — a
        persistently large gap flags a lane that cannot reach quorum).
        Exported ~1/s by NodeHost._export_health_gauges as cluster_id-
        labelled engine_lane_* gauges."""
        lanes, gs = self._active_lanes(host)
        last = self._m_last[gs]
        tick = self.clock.tick
        cols = zip(
            lanes,
            (self._m_leader[gs] - 1).tolist(),
            self._m_term[gs].tolist(),
            np.maximum(last - self._m_commit[gs], 0).tolist(),
            last.tolist(),
            np.maximum(tick - self._m_leader_change_tick[gs], 0).tolist(),
            self._m_role[gs].tolist(),
        )
        return {
            lane.key: {
                "node_id": lane.node.node_id(),
                "leader_id": lane.rev.get(lslot, 0),
                "term": term,
                "commit_gap": gap,
                # monotonic append high-water mark in device units: the
                # placement plane's ingest-rate signal is the DELTA of
                # this between two load folds (serving/placement.py) —
                # still a pure mirror read, zero device syncs
                "last_index": li,
                "ticks_since_leader_change": since,
                # lane-variant probes: the replica's role (observer/witness
                # lanes included) and resident client-payload bytes — a
                # witness lane must report payload_bytes == 0 (the
                # observer_witness_churn verdict and tests assert on it)
                "role": role,
                "payload_bytes": lane.arena.payload_bytes,
            }
            for lane, lslot, term, gap, li, since, role in cols
        }

    def hot_lane_stats(
        self, k: int, host: Optional[int] = None
    ) -> Tuple[Dict[tuple, dict], int]:
        """The k hottest active lanes by commit gap (optionally filtered
        to one co-hosted NodeHost), plus the total count the cap hides:
        (lane key -> lane_stats row + heat-relevant counter columns,
        total_active). Selection is one numpy gather + argpartition over
        the decode-maintained mirrors — a 50k-lane host pays the
        per-lane dict cost only for the k lanes somebody will look at
        (the history sampler's slot-bounded lane table, tools.top's
        default ranking input). Counter columns (HOT_LANE_COUNTERS) come
        off the same cumulative host mirror as counter_stats — zero
        device syncs, like everything on this surface."""
        with self._lanes_mu:
            lanes = [
                lane
                for lane in self._lanes.values()
                if lane.active and (host is None or lane.key[0] == host)
            ]
        total = len(lanes)
        out: Dict[tuple, dict] = {}
        if not lanes:
            return out, 0
        k = max(1, int(k))
        gs = np.fromiter((lane.g for lane in lanes), np.int64, total)
        gaps = np.maximum(self._m_last[gs] - self._m_commit[gs], 0)
        if total > k:
            pick = np.argpartition(gaps, total - k)[total - k:]
            # hottest-first order inside the cap (stable for renderers)
            pick = pick[np.argsort(-gaps[pick], kind="stable")]
        else:
            pick = np.argsort(-gaps, kind="stable")
        leader = self._m_leader
        term = self._m_term
        last = self._m_last
        role = self._m_role
        chg = self._m_leader_change_tick
        tick = self.clock.tick
        ctr = self._ctr
        ctr_cols = [
            (name, CTR_NAMES.index(name)) for name in HOT_LANE_COUNTERS
        ]
        for i in pick:
            lane = lanes[int(i)]
            g = lane.g
            row = ctr[g]
            out[lane.key] = {
                "node_id": lane.node.node_id(),
                "leader_id": lane.rev.get(int(leader[g]) - 1, 0),
                "term": int(term[g]),
                "commit_gap": int(gaps[int(i)]),
                "last_index": int(last[g]),
                "ticks_since_leader_change": max(int(tick - chg[g]), 0),
                "role": int(role[g]),
                "payload_bytes": lane.arena.payload_bytes,
                "counters": {n: int(row[ci]) for n, ci in ctr_cols},
            }
        return out, total

    def leader_snapshot(
        self, host: Optional[int] = None
    ) -> Dict[tuple, Tuple[int, int]]:
        """One vectorized pass over the numpy mirrors: lane key ->
        (leader_node_id, term) for every active lane, or for one host's.
        Replaces per-group get_leader_id polling at fleet bring-up (50k
        lanes = one call)."""
        lanes, gs = self._active_lanes(host)
        return {
            lane.key: (lane.rev.get(lslot, 0), term)
            for lane, lslot, term in zip(
                lanes,
                (self._m_leader[gs] - 1).tolist(),
                self._m_term[gs].tolist(),
            )
        }

    def note_start_clusters(self, host: int, parts: dict) -> None:
        """A NodeHost's start_clusters, timed by its parts (seconds and
        nodes), into the bring-up account: summed over its calls."""
        mine = self._bring_hosts.setdefault(host, {})
        for name, v in parts.items():
            mine[name] = mine.get(name, 0) + v

    def bringup_stats(self) -> dict:
        """The bring-up account: `start_clusters_s` (the wall seconds of
        every host's start_clusters, summed over hosts) with each host's
        parts under `hosts`; `activate_s` (lane activation: the host half
        and the batched scatter's dispatch, summed over batches) and
        `activated` lanes; `elect_launches`, the kernel launches from the
        first lane activated to the first launch after which every
        active lane knows a leader (None until then)."""
        hosts = {h: dict(p) for h, p in self._bring_hosts.items()}
        return {
            "start_clusters_s": sum(p.get("total_s", 0.0) for p in hosts.values()),
            "hosts": hosts,
            "activate_s": self._bring_activate_s,
            "activated": self._bring_activated,
            "first_activation_launch": self._bring_launch0,
            "elect_launches": self._bring_elect,
        }

    def attach_host(self) -> int:
        with self._hosts_mu:
            host = self._next_host
            self._next_host += 1
            self._host_refs.add(host)
        return host

    def release(self, host: int, flush: bool = True) -> None:
        """Detach one NodeHost handle; the core stops when the last handle
        releases (a shared core outlives any single host). The last-ref
        check and the registry removal happen under _shared_mu so a
        concurrent get_vector_engine() can never attach to a core that is
        about to stop. A non-last release drains the loop once so the
        departing host's lanes are fully deactivated before its NodeHost
        closes the logdb under them.

        flush=False is the CRASH path (NodeHost.crash): a sole-tenant core
        discards its un-decoded in-flight step instead of landing it — a
        SIGKILL'd process would never have decoded or saved that output.
        On a shared core the in-flight step belongs to the surviving
        hosts too, so crash granularity there is the lane teardown and
        the shared step still decodes."""
        with _shared_mu:
            with self._hosts_mu:
                self._host_refs.discard(host)
                self._blocked_hosts.discard(host)
                last = not self._host_refs
            if last:
                _forget_shared_core_locked(self)
        if last:
            self.stop(flush=flush)
        else:
            self.drain()

    def drain(self, timeout: float = 30.0) -> None:
        """Block until the loop has applied every queued reconcile (incl.
        deactivations) and finished its in-flight iteration. The restart
        plane's ordering barrier: after NodeHost.stop_cluster /
        crash_cluster drain, the freed lane is on the free list and a
        restart_cluster can reuse it immediately."""
        if self._stopped.is_set():
            return
        ev = threading.Event()
        self._reconq.append(("barrier", ev))
        self._ready.set()
        ev.wait(timeout)

    def stop(self, flush: bool = True) -> None:
        self.watchdog.close()
        if not flush:
            self._discard_pending = True
        self._stopped.set()
        self._ready.set()
        self.task_ready.wake_all()
        self.snapshot_ready.wake_all()
        # the step thread must fully drain its in-flight iteration before
        # the caller closes the logdb under it; a short join here would let
        # a slow device step race the close (observed as "write to closed
        # file" + a C++ abort at interpreter teardown)
        for t in self._threads:
            t.join(timeout=30 if t.name == "vec-step" else 2)


class VectorEngineHandle:
    """Per-NodeHost facade over a (possibly shared) VectorEngine core.

    Lanes inside the core are keyed (host, cluster_id); the handle carries
    the host id so the Node/NodeHost side keeps addressing the engine by
    bare cluster_id. Attribute access falls through to the core, so the
    VectorNode status mirrors (_m_leader etc.) and the reconcile bridges
    work unchanged."""

    __slots__ = ("core", "host", "kcfg", "clock")

    def __init__(self, core: VectorEngine, host: int) -> None:
        self.core = core
        self.host = host
        self.kcfg = core.kcfg
        self.clock = core.clock

    def add_node(self, node) -> None:
        self.core.add_node(node, self.host)

    def add_nodes(self, nodes) -> None:
        self.core.add_nodes(nodes, self.host)

    def note_start_clusters(self, parts: dict) -> None:
        self.core.note_start_clusters(self.host, parts)

    def remove_node(self, cluster_id: int) -> None:
        self.core.remove_node((self.host, cluster_id))

    def get_node(self, cluster_id: int):
        return self.core.get_node((self.host, cluster_id))

    def set_node_ready(self, cluster_id: int) -> None:
        self.core.set_node_ready((self.host, cluster_id))

    def set_task_ready(self, cluster_id: int) -> None:
        self.core.set_task_ready((self.host, cluster_id))

    def set_snapshot_ready(self, cluster_id: int) -> None:
        self.core.set_snapshot_ready((self.host, cluster_id))

    def global_tick(self) -> None:
        self.core.global_tick(self.host)

    def try_local_deliver(self, m: Message) -> bool:
        return self.core.try_local_deliver(m)

    def set_host_partitioned(self, partitioned: bool) -> None:
        self.core.set_host_partitioned(self.host, partitioned)

    def set_clock_suspect(self, hold_s: float) -> None:
        """Clock-anomaly report scoped to THIS host's lanes (a shared
        core serves several NodeHosts, each with its own tick worker)."""
        self.core.set_clock_suspect(self.host, hold_s)

    def lease_valid(self, cluster_id: int) -> bool:
        return self.core.lease_valid((self.host, cluster_id))

    def leader_snapshot(self) -> Dict[int, Tuple[int, int]]:
        """cluster_id -> (leader_node_id, term) for this host's lanes."""
        return {
            key[1]: v
            for key, v in self.core.leader_snapshot(self.host).items()
        }

    def lane_stats(self) -> Dict[int, dict]:
        """cluster_id -> per-lane introspection for this host's lanes."""
        return {
            key[1]: v for key, v in self.core.lane_stats(self.host).items()
        }

    def lane_counters(self) -> Dict[int, Dict[str, int]]:
        """cluster_id -> cumulative event counters for this host's
        lanes (see VectorEngine.lane_counters)."""
        return {
            key[1]: v
            for key, v in self.core.lane_counters().items()
            if key[0] == self.host
        }

    def hot_lane_stats(self, k: int) -> Tuple[Dict[int, dict], int]:
        """This host's k hottest lanes by commit gap + its total active
        lane count (see VectorEngine.hot_lane_stats). Host filtering
        happens BEFORE the cap so a co-hosted fleet's noisy neighbour
        can never crowd this host's lanes out of its own sample."""
        rows, total = self.core.hot_lane_stats(k, host=self.host)
        return {key[1]: v for key, v in rows.items()}, total

    def stop(self) -> None:
        self.core.release(self.host)

    def crash(self) -> None:
        """SIGKILL-equivalent detach (NodeHost.crash): a sole-tenant core
        discards its un-decoded in-flight step; a shared core keeps
        serving its surviving hosts (see VectorEngine.release)."""
        self.core.release(self.host, flush=False)

    def __getattr__(self, name):
        return getattr(self.core, name)


# process-global registry of shared cores (EngineConfig.share_scope)
_shared_mu = threading.Lock()
_shared_cores: Dict[str, VectorEngine] = {}


def get_vector_engine(logdb, nh_config: NodeHostConfig) -> VectorEngineHandle:
    """Engine factory for NodeHost: returns a handle on a fresh core, or on
    the process-shared core named by EngineConfig.share_scope (co-hosted
    replicas then advance in ONE kernel step and exchange messages without
    touching the transport)."""
    scope = getattr(nh_config.engine, "share_scope", None)
    if scope is None:
        core = VectorEngine(logdb, nh_config=nh_config)
        return VectorEngineHandle(core, core.attach_host())
    with _shared_mu:
        core = _shared_cores.get(scope)
        if core is None:
            core = _shared_cores[scope] = VectorEngine(
                logdb, nh_config=nh_config
            )
        else:
            want = nh_config.engine
            mismatches = [
                name
                for name, got, exp in (
                    # requested, not kcfg.groups: the sharded round-up
                    # pads the kernel shape, not the declared capacity
                    ("max_groups", core._groups_requested, want.max_groups),
                    ("max_peers", core.kcfg.peers, want.max_peers),
                    ("log_window", core.kcfg.log_window, want.log_window),
                    ("inbox_depth", core.kcfg.inbox_depth, want.inbox_depth),
                    (
                        "max_entries_per_msg",
                        core.kcfg.max_entries_per_msg,
                        getattr(want, "max_entries_per_msg", 8),
                    ),
                    (
                        "readindex_depth",
                        core.kcfg.readindex_depth,
                        want.readindex_depth,
                    ),
                    # one core runs one loop: every co-hosted host
                    # asks for the same steps a launch, or all leave
                    # the choice to the engine
                    ("steps_per_sync", core._steps_cfg, _steps_option(want)),
                )
                if got != exp
            ]
            if mismatches:
                raise ValueError(
                    f"share_scope {scope!r}: engine shape mismatch on "
                    f"{mismatches} (every co-hosted NodeHost must declare "
                    f"the same EngineConfig shapes)"
                )
        host = core.attach_host()
    return VectorEngineHandle(core, host)


def _forget_shared_core_locked(core: VectorEngine) -> None:
    """Caller holds _shared_mu."""
    for k, v in list(_shared_cores.items()):
        if v is core:
            del _shared_cores[k]


__all__ = [
    "VectorEngine",
    "VectorEngineHandle",
    "VectorNode",
    "get_vector_engine",
]
