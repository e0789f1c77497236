"""Device state layout for the vectorized Raft kernel.

All protocol state lives in int32/bool struct-of-arrays over a fixed
(G groups, P peers) shape. Node identity on device is the *peer slot*
(0..P-1); the host keeps the slot <-> 64-bit node-id mapping per group.
Vote/leader fields store slot+1 with 0 meaning "none".

Log entries never carry payloads on device: the ring buffer log_term[G, W]
holds per-entry term metadata only (slot = index % W), mirroring how the
reference's raft core only needs (index, term) pairs for the protocol while
payload bytes flow host-side (cf. internal/raft/logentry.go). Indexes are
int32 *rebased* values: the host owns a 64-bit base per group and calls
`rebase` before any index nears 2**31.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ROLE:
    """Replica roles; values match core.raft.RaftNodeState / reference
    raft.go:63-70 (PRE_CANDIDATE extends the table for pre-vote)."""

    FOLLOWER = 0
    CANDIDATE = 1
    LEADER = 2
    OBSERVER = 3
    WITNESS = 4
    PRE_CANDIDATE = 5


class RSTATE:
    """Per-follower flow control FSM (cf. internal/raft/remote.go:44-49)."""

    RETRY = 0
    WAIT = 1
    REPLICATE = 2
    SNAPSHOT = 3


class MSG:
    """Kernel message types. Values match types.MessageType for the wire
    types; local/engine-only types reuse the same numbering."""

    NONE = -1  # empty inbox slot
    LOCAL_TICK = 0
    ELECTION = 1
    LEADER_HEARTBEAT = 2
    NOOP = 4
    PROPOSE = 7
    SNAPSHOT_STATUS = 8
    UNREACHABLE = 9
    CHECK_QUORUM = 10
    REPLICATE = 12
    REPLICATE_RESP = 13
    REQUEST_VOTE = 14
    REQUEST_VOTE_RESP = 15
    INSTALL_SNAPSHOT = 16
    HEARTBEAT = 17
    HEARTBEAT_RESP = 18
    READ_INDEX = 19
    READ_INDEX_RESP = 20
    LEADER_TRANSFER = 23
    TIMEOUT_NOW = 24
    REQUEST_PREVOTE = 26
    REQUEST_PREVOTE_RESP = 27


# send_flags bits in StepOutput
SEND_REPLICATE = 1
SEND_HEARTBEAT = 2
SEND_VOTE_REQ = 4
SEND_TIMEOUT_NOW = 8
NEED_SNAPSHOT = 16


class CTR:
    """Slots of the per-lane event-counter plane (StepOutput.counters
    [:, CTR.*], u32 per-step deltas). Each slot counts the protocol event
    at the point the SCALAR core would fire it (campaign(), become_leader(),
    a heartbeat send, ...), so kernel counters are differential-comparable
    against core.raft event counts — a descriptor suppressed by the
    end-of-step role gate still counts, exactly like the scalar core's
    already-sent message does."""

    ELECTIONS_STARTED = 0  # real campaigns (pre-vote polls excluded)
    ELECTIONS_WON = 1  # become-leader transitions
    HEARTBEATS_SENT = 2  # per-target heartbeat sends (tick + readindex)
    REPLICATE_REJECTS = 3  # Replicate messages rejected (log mismatch)
    # commit advances count INDEX UNITS, not events: the kernel commits
    # once per step at the quorum fold while the scalar core commits per
    # message, so event counts differ by construction — units advanced
    # are identical in lockstep (both end each round at the same commit)
    COMMIT_ADVANCES = 4  # commit index units advanced (leader + follower)
    LEASE_SERVED = 5  # reads served locally off a live lease
    LEASE_FALLBACK = 6  # lease-on reads that fell back to quorum
    READ_CONFIRMED = 7  # readindex confirmations delivered (ready pops)
    COUNT = 8


#: stats key per CTR slot, in slot order (the one canonical naming
#: shared by engine counter_stats(), gauges and tools.top)
CTR_NAMES = (
    "elections_started",
    "elections_won",
    "heartbeats_sent",
    "replicate_rejects",
    "commit_advances",
    "lease_served",
    "lease_fallback",
    "read_confirmations",
)


class KernelConfig(NamedTuple):
    """Static shape configuration compiled into the kernel."""

    groups: int = 1024  # G
    peers: int = 8  # P (max replicas per group incl. observers/witnesses)
    log_window: int = 512  # W (device-resident per-group log metadata window)
    inbox_depth: int = 8  # K (messages consumed per group per step)
    max_entries_per_msg: int = 8  # E (entries attached to one Replicate)
    readindex_depth: int = 4  # R (outstanding ReadIndex ctx per group)


class RaftTensors(NamedTuple):
    """The complete protocol state of G groups as tensors."""

    # identity / membership
    active: jax.Array  # bool[G] lane holds a live replica
    self_slot: jax.Array  # i32[G] this replica's peer slot
    member: jax.Array  # bool[G,P] slot holds any member
    voting: jax.Array  # bool[G,P] slot is a voting member (full or witness)
    observer: jax.Array  # bool[G,P]
    witness: jax.Array  # bool[G,P]
    # durable raft state
    term: jax.Array  # i32[G]
    vote: jax.Array  # i32[G] slot+1, 0=none
    # volatile role state
    role: jax.Array  # i32[G] ROLE.*
    leader: jax.Array  # i32[G] slot+1, 0=none
    # timers (ticks)
    tick_count: jax.Array  # i32[G]
    election_tick: jax.Array  # i32[G]
    heartbeat_tick: jax.Array  # i32[G]
    rand_timeout: jax.Array  # i32[G] randomized election timeout
    election_timeout: jax.Array  # i32[G] per-group config
    heartbeat_timeout: jax.Array  # i32[G]
    check_quorum: jax.Array  # bool[G]
    # pre-vote gate (Config.pre_vote): lanes with the bit clear can never
    # reach PRE_CANDIDATE — the False path is bit-identical to the
    # pre-knob kernel
    prevote_on: jax.Array  # bool[G]
    # leader-lease read gate (Config.lease_read): lanes with lease_on
    # clear can never open a lease round — the False path is bit-identical
    # to the pre-knob kernel. Lease bookkeeping is tick-denominated (NOT
    # log-index-denominated): none of these fields participate in rebase.
    lease_on: jax.Array  # bool[G]
    lease_margin: jax.Array  # i32[G] clock-skew margin (ticks)
    lease_until: jax.Array  # i32[G] lease live while tick_count < this
    hb_round_tick: jax.Array  # i32[G] tick tag of the open heartbeat round
    hb_ack_bits: jax.Array  # i32[G] bitmask of peer slots acking that round
    clock_ok: jax.Array  # bool[G] host clears while the tick clock is suspect
    # log metadata (rebased int32 indexes)
    first_index: jax.Array  # i32[G] lowest index with term in the ring
    marker_term: jax.Array  # i32[G] term at first_index-1 (snapshot/compaction marker)
    last_index: jax.Array  # i32[G]
    committed: jax.Array  # i32[G]
    processed: jax.Array  # i32[G] committed entries already handed to engine
    applied: jax.Array  # i32[G] applied index confirmed by the RSM
    unsaved_from: jax.Array  # i32[G] first index not yet persisted by engine
    log_term: jax.Array  # i32[G,W] ring: term of entry at index i in slot i%W
    log_is_cc: jax.Array  # bool[G,W] ring: entry is a config change
    # leader replication bookkeeping (cf. remote.go)
    match: jax.Array  # i32[G,P]
    next: jax.Array  # i32[G,P]
    rstate: jax.Array  # i32[G,P] RSTATE.*
    ract: jax.Array  # bool[G,P] active flag for check-quorum
    snap_sent: jax.Array  # i32[G,P] pending snapshot index per peer
    # election bookkeeping
    vresp: jax.Array  # bool[G,P] peer responded to vote request
    vgrant: jax.Array  # bool[G,P] peer granted vote
    # leadership transfer
    transfer_to: jax.Array  # i32[G] slot+1, 0=none
    transfer_flag: jax.Array  # bool[G] this node is a sanctioned transfer target
    # membership change guard
    pending_cc: jax.Array  # bool[G] uncommitted config change in flight
    # quiesce (cf. quiesce.go:23-123): idle lanes freeze their timers and
    # stop exchanging heartbeats; any non-heartbeat inbox message exits
    quiesce_on: jax.Array  # bool[G] per-lane config enable
    quiesce_threshold: jax.Array  # i32[G] idle ticks before entering
    quiesced: jax.Array  # bool[G]
    idle_ticks: jax.Array  # i32[G] ticks since last non-heartbeat activity
    # read index queue (FIFO of R slots, ctx 0 = empty). The context is
    # carried full-width in two planes: ri_ctx holds (origin_slot+1)<<24 |
    # ctx.low[0:24], ri_ctx2 holds ctx.low[24:55] — 55 bits of the node's
    # sequential read counter plus the origin slot, collision-free for any
    # realistic pending window (the reference carries a 128-bit random
    # SystemCtx in the message envelope instead, requests.go:365-381)
    ri_ctx: jax.Array  # i32[G,R]
    ri_ctx2: jax.Array  # i32[G,R]
    ri_index: jax.Array  # i32[G,R]
    ri_acks: jax.Array  # i32[G,R] bitmask of peer slots that acked
    ri_count: jax.Array  # i32[G] live queue length
    # randomness
    seed: jax.Array  # u32[G]


class Inbox(NamedTuple):
    """K inbound messages per group per step; empty slots have mtype NONE.

    Replicate messages carry up to E (term, is_cc) metadata pairs for their
    entries; payload bytes stay host-side keyed by (group, index)."""

    mtype: jax.Array  # i32[G,K]
    from_slot: jax.Array  # i32[G,K]
    term: jax.Array  # i32[G,K]
    log_index: jax.Array  # i32[G,K]
    log_term: jax.Array  # i32[G,K]
    commit: jax.Array  # i32[G,K]
    reject: jax.Array  # bool[G,K]
    hint: jax.Array  # i32[G,K]
    hint_high: jax.Array  # i32[G,K] upper half of a readindex ctx
    n_entries: jax.Array  # i32[G,K]
    entry_terms: jax.Array  # i32[G,K,E]
    entry_cc: jax.Array  # bool[G,K,E]


class StepOutput(NamedTuple):
    """Per-step engine directives; the host materializes real messages from
    the [G,P] descriptor plane plus its payload arenas."""

    # broadcast/send plane
    send_flags: jax.Array  # i32[G,P] bitmask SEND_*
    send_prev_index: jax.Array  # i32[G,P] Replicate: prev log index (next-1)
    send_prev_term: jax.Array  # i32[G,P] Replicate: term at prev
    send_n_entries: jax.Array  # i32[G,P] Replicate: entries to attach
    send_commit: jax.Array  # i32[G,P] Replicate commit index
    # Heartbeat commit is capped at min(match, committed) per peer so a
    # lagging follower never commits a divergent suffix (cf. raft.go:810-816)
    send_hb_commit: jax.Array  # i32[G,P]
    send_hint: jax.Array  # i32[G,P] readindex ctx (heartbeat) / transfer hint
    send_hint2: jax.Array  # i32[G,P] upper ctx half for heartbeats
    vote_last_index: jax.Array  # i32[G] RequestVote: candidate last log index
    vote_last_term: jax.Array  # i32[G]
    # response plane: one reply per consumed inbox slot
    resp_type: jax.Array  # i32[G,K] MSG.* or NONE
    resp_to: jax.Array  # i32[G,K] peer slot
    resp_term: jax.Array  # i32[G,K]
    resp_log_index: jax.Array  # i32[G,K]
    resp_reject: jax.Array  # bool[G,K]
    resp_hint: jax.Array  # i32[G,K]
    resp_hint2: jax.Array  # i32[G,K] (hint_high echo for readindex)
    # engine directives
    save_from: jax.Array  # i32[G] first entry to persist (0 = nothing)
    save_to: jax.Array  # i32[G] last entry to persist
    apply_from: jax.Array  # i32[G] committed entries to hand to the RSM
    apply_to: jax.Array  # i32[G]
    commit_index: jax.Array  # i32[G] (for hard-state persistence)
    hard_changed: jax.Array  # bool[G] term/vote/commit changed this step
    ready_ctx: jax.Array  # i32[G,R] confirmed readindex contexts
    ready_ctx2: jax.Array  # i32[G,R] upper ctx halves
    ready_index: jax.Array  # i32[G,R]
    ready_count: jax.Array  # i32[G]
    # (a dropped proposal shows as prop_base 0 on its slot, below)
    dropped_readindex: jax.Array  # i32[G] ReadIndex contexts a leader lane
    #   dropped: nothing committed in its term yet, or its ri queue full
    dropped_cc: jax.Array  # bool[G] config-change replaced (pending invariant)
    fwd_leader: jax.Array  # i32[G] slot+1 to forward host proposals to
    noop_appended: jax.Array  # i32[G] index of new-leader noop entry (0=none)
    noop_term: jax.Array  # i32[G] term of that noop entry (0=none)
    log_full: jax.Array  # bool[G] window exhausted; engine must snapshot
    # per-inbox-slot append bases (0 = message appended nothing): the host
    # places payload bytes at these device-assigned indexes
    prop_base: jax.Array  # i32[G,K] first index appended for a PROPOSE slot
    rep_base: jax.Array  # i32[G,K] first entry index of an accepted Replicate
    # post-step state mirror for the host engine (leader/term tracking,
    # status queries, host-side catch-up of lagging peers)
    leader: jax.Array  # i32[G] slot+1, 0=none
    term: jax.Array  # i32[G]
    vote: jax.Array  # i32[G] slot+1, 0=none (for hard-state persistence)
    role: jax.Array  # i32[G] ROLE.*
    match: jax.Array  # i32[G,P]
    rstate: jax.Array  # i32[G,P] flow-control state (host watchdog re-arms
    #   parked peers whose recovery tracker was lost to a leadership race)
    last_index: jax.Array  # i32[G]
    quiesced: jax.Array  # bool[G] lane idle-frozen (host packs a wake NOOP
    #   before staging work for a quiesced lane)
    # lease plane: lease_round rides outbound heartbeats as the wire tag
    # (Message.log_index, 0 when leases off); the counters are per-step
    # deltas the host accumulates into engine lease_stats()
    lease_round: jax.Array  # i32[G] open heartbeat-round tag for wire stamp
    lease_served: jax.Array  # i32[G] reads served locally off the lease
    lease_fallback: jax.Array  # i32[G] lease-on reads that fell back to quorum
    lease_ok: jax.Array  # bool[G] lane holds a live lease after this step
    # event-counter plane: per-step u32 deltas, one column per CTR slot,
    # accumulated INSIDE the step (so K inner steps and device-routed
    # traffic are counted where they happen) and folded host-side into
    # cumulative per-lane counters at decode. None of these are
    # index-valued: rebase never touches them.
    counters: jax.Array  # u32[G, CTR.COUNT]


class RoutePlan(NamedTuple):
    """Which of a step's outbound messages were routed ON DEVICE into a
    co-hosted destination lane's next-step inbox (multi_step_batch). The
    host decode uses these masks to (a) skip materializing wire Messages
    for routed traffic and (b) replay the deterministic slot assignment
    so Replicate payload bytes land in the destination lane's arena.
    A candidate that could not route (no co-hosted lane, inbox overflow,
    below-window reject) stays False and falls back to the host path."""

    rep: jax.Array  # bool[G,P] SEND_REPLICATE routed
    vote: jax.Array  # bool[G,P] SEND_VOTE_REQ routed
    hb: jax.Array  # bool[G,P] SEND_HEARTBEAT routed
    tn: jax.Array  # bool[G,P] SEND_TIMEOUT_NOW routed
    resp: jax.Array  # bool[G,K] response-plane slot routed
    rir: jax.Array  # bool[G,R] confirmed forwarded-read resp routed


def init_state(cfg: KernelConfig) -> RaftTensors:
    G, P, W, R = cfg.groups, cfg.peers, cfg.log_window, cfg.readindex_depth
    i32 = jnp.int32
    # each field gets its own buffer: aliased buffers break jit donation
    # (the engine donates the state pytree every step)
    z_g = lambda: jnp.zeros((G,), i32)
    z_gp = lambda: jnp.zeros((G, P), i32)
    f_g = lambda: jnp.zeros((G,), bool)
    f_gp = lambda: jnp.zeros((G, P), bool)
    return RaftTensors(
        active=f_g(),
        self_slot=z_g(),
        member=f_gp(),
        voting=f_gp(),
        observer=f_gp(),
        witness=f_gp(),
        term=z_g(),
        vote=z_g(),
        role=z_g(),
        leader=z_g(),
        tick_count=z_g(),
        election_tick=z_g(),
        heartbeat_tick=z_g(),
        rand_timeout=jnp.full((G,), 10, i32),
        election_timeout=jnp.full((G,), 10, i32),
        heartbeat_timeout=jnp.full((G,), 1, i32),
        check_quorum=f_g(),
        prevote_on=f_g(),
        lease_on=f_g(),
        lease_margin=z_g(),
        lease_until=z_g(),
        hb_round_tick=z_g(),
        hb_ack_bits=z_g(),
        clock_ok=jnp.ones((G,), bool),
        first_index=jnp.ones((G,), i32),
        marker_term=z_g(),
        last_index=z_g(),
        committed=z_g(),
        processed=z_g(),
        applied=z_g(),
        unsaved_from=jnp.ones((G,), i32),
        log_term=jnp.zeros((G, W), i32),
        log_is_cc=jnp.zeros((G, W), bool),
        match=z_gp(),
        next=jnp.ones((G, P), i32),
        rstate=z_gp(),
        ract=f_gp(),
        snap_sent=z_gp(),
        vresp=f_gp(),
        vgrant=f_gp(),
        transfer_to=z_g(),
        transfer_flag=f_g(),
        pending_cc=f_g(),
        quiesce_on=f_g(),
        quiesce_threshold=jnp.full((G,), 100, i32),
        quiesced=f_g(),
        idle_ticks=z_g(),
        ri_ctx=jnp.zeros((G, R), i32),
        ri_ctx2=jnp.zeros((G, R), i32),
        ri_index=jnp.zeros((G, R), i32),
        ri_acks=jnp.zeros((G, R), i32),
        ri_count=z_g(),
        seed=jnp.arange(1, G + 1, dtype=jnp.uint32) * jnp.uint32(2654435761),
    )


def make_empty_inbox(cfg: KernelConfig) -> Inbox:
    G, K, E = cfg.groups, cfg.inbox_depth, cfg.max_entries_per_msg
    i32 = jnp.int32
    return Inbox(
        mtype=jnp.full((G, K), MSG.NONE, i32),
        from_slot=jnp.zeros((G, K), i32),
        term=jnp.zeros((G, K), i32),
        log_index=jnp.zeros((G, K), i32),
        log_term=jnp.zeros((G, K), i32),
        commit=jnp.zeros((G, K), i32),
        reject=jnp.zeros((G, K), bool),
        hint=jnp.zeros((G, K), i32),
        hint_high=jnp.zeros((G, K), i32),
        n_entries=jnp.zeros((G, K), i32),
        entry_terms=jnp.zeros((G, K, E), i32),
        entry_cc=jnp.zeros((G, K, E), bool),
    )


# ---------------------------------------------------------------- host side


def configure_group(
    state: RaftTensors,
    g: int,
    self_slot: int,
    voting_slots,
    observer_slots=(),
    witness_slots=(),
    election_timeout: int = 10,
    heartbeat_timeout: int = 1,
    check_quorum: bool = False,
    is_observer: bool = False,
    is_witness: bool = False,
    prevote: bool = False,
    lease_read: bool = False,
    lease_margin: int = 0,
) -> RaftTensors:
    """Host-side reconcile: activate lane g with the given membership.
    Rare-path (StartCluster / config change), so clarity over speed."""
    P = state.member.shape[1]
    member = np.array(state.member[g])
    voting = np.array(state.voting[g])
    observer = np.array(state.observer[g])
    witness = np.array(state.witness[g])
    member[:] = False
    voting[:] = False
    observer[:] = False
    witness[:] = False
    for s in voting_slots:
        member[s] = True
        voting[s] = True
    for s in observer_slots:
        member[s] = True
        observer[s] = True
    for s in witness_slots:
        member[s] = True
        voting[s] = True
        witness[s] = True
    role = (
        ROLE.OBSERVER if is_observer else ROLE.WITNESS if is_witness else ROLE.FOLLOWER
    )
    upd = {
        "active": state.active.at[g].set(True),
        "self_slot": state.self_slot.at[g].set(self_slot),
        "member": state.member.at[g].set(jnp.asarray(member)),
        "voting": state.voting.at[g].set(jnp.asarray(voting)),
        "observer": state.observer.at[g].set(jnp.asarray(observer)),
        "witness": state.witness.at[g].set(jnp.asarray(witness)),
        "role": state.role.at[g].set(role),
        "election_timeout": state.election_timeout.at[g].set(election_timeout),
        "heartbeat_timeout": state.heartbeat_timeout.at[g].set(heartbeat_timeout),
        "rand_timeout": state.rand_timeout.at[g].set(
            election_timeout
            + _mix(int(np.asarray(state.seed)[g]), 0, self_slot) % election_timeout
        ),
        "check_quorum": state.check_quorum.at[g].set(check_quorum),
        "prevote_on": state.prevote_on.at[g].set(prevote),
        "lease_on": state.lease_on.at[g].set(lease_read),
        "lease_margin": state.lease_margin.at[g].set(lease_margin),
    }
    return state._replace(**upd)


def configure_groups_uniform(
    state: RaftTensors,
    self_slot: int,
    voting_slots,
    election_timeout: int = 10,
    heartbeat_timeout: int = 1,
    check_quorum: bool = False,
    prevote: bool = False,
    lease_read: bool = False,
    lease_margin: int = 0,
) -> RaftTensors:
    """Vectorized configure for ALL lanes with identical membership shape —
    one whole-array update instead of G scalar dispatches. This is the bulk
    path benchmarks and fleet bring-up use (configure_group remains the
    per-lane reconcile for StartCluster / config change)."""
    G, P = state.member.shape
    member = np.zeros((P,), bool)
    voting = np.zeros((P,), bool)
    for s in voting_slots:
        member[s] = True
        voting[s] = True
    seeds = np.asarray(state.seed).astype(np.uint64)
    # same mix as _mix() below, vectorized with uint64 headroom
    M = np.uint64(0xFFFFFFFF)
    x = ((seeds * np.uint64(2654435761)) ^ np.uint64(self_slot * 2246822519)) & M
    x ^= x >> np.uint64(15)
    x = (x * np.uint64(2246822519)) & M
    x ^= x >> np.uint64(13)
    rand_to = (election_timeout + (x % np.uint64(election_timeout))).astype(
        np.int32
    )
    return state._replace(
        active=jnp.ones((G,), bool),
        self_slot=jnp.full((G,), self_slot, jnp.int32),
        member=jnp.broadcast_to(jnp.asarray(member), (G, P)),
        voting=jnp.broadcast_to(jnp.asarray(voting), (G, P)),
        observer=jnp.zeros((G, P), bool),
        witness=jnp.zeros((G, P), bool),
        role=jnp.full((G,), ROLE.FOLLOWER, jnp.int32),
        election_timeout=jnp.full((G,), election_timeout, jnp.int32),
        heartbeat_timeout=jnp.full((G,), heartbeat_timeout, jnp.int32),
        rand_timeout=jnp.asarray(rand_to),
        check_quorum=jnp.full((G,), check_quorum, bool),
        prevote_on=jnp.full((G,), prevote, bool),
        lease_on=jnp.full((G,), lease_read, bool),
        lease_margin=jnp.full((G,), lease_margin, jnp.int32),
    )


def lane_seed(g: int) -> int:
    """Host-side replica of init_state's per-lane PRNG seed. The kernel
    reads but never writes the seed tensor, so this stays a pure function
    of the lane index — the engine uses it to compute randomized election
    timeouts during bulk activation without a device round-trip."""
    return ((g + 1) * 2654435761) & 0xFFFFFFFF


def _mix(a, b, c):
    """Cheap deterministic integer mix (xorshift-multiply), used for
    randomized election timeouts; must match kernel._mix (uint32 wraparound
    done in Python ints to avoid numpy overflow warnings)."""
    M = 0xFFFFFFFF
    x = ((int(a) * 2654435761) ^ (int(b) * 40503) ^ (int(c) * 2246822519)) & M
    x ^= x >> 15
    x = (x * 2246822519) & M
    x ^= x >> 13
    return x


def rebase(state: RaftTensors, delta) -> RaftTensors:
    """Subtract delta[G] from every index-valued tensor. The host calls this
    (through the engine) before any rebased index nears 2**31; ring slots are
    invariant when delta % W == 0."""
    d = jnp.asarray(delta, jnp.int32)
    dp = d[:, None]
    return state._replace(
        first_index=state.first_index - d,
        last_index=state.last_index - d,
        committed=state.committed - d,
        processed=state.processed - d,
        applied=state.applied - d,
        unsaved_from=state.unsaved_from - d,
        match=jnp.maximum(state.match - dp, 0),
        next=jnp.maximum(state.next - dp, 1),
        snap_sent=jnp.maximum(state.snap_sent - dp, 0),
        ri_index=jnp.maximum(state.ri_index - dp, 0),
    )
