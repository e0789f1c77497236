"""step_batch: advance all Raft groups one protocol step in one compiled call.

The reference advances each group with a per-group handler table dispatch
(internal/raft/raft.go:2030-2098) inside 16 worker goroutines. Here the whole
fleet advances at once:

  1. tick phase      — election/heartbeat/check-quorum timers as tensor ops
                       (cf. raft.go:523-634)
  2. inbox scan      — lax.scan over K message slots; each iteration applies
                       one message per group, the handler table realized as
                       masked lane updates
  3. replication fan-out — for every (group, peer) with next <= last_index
                       and an unpaused flow-control lane, emit a Replicate
                       send descriptor (unifies the reference's
                       broadcastReplicateMessage + lagging-peer catch-up,
                       cf. raft.go:794-815, 1679-1684)
  4. quorum commit   — k-th order statistic over match[G,P] with the
                       current-term restriction (cf. raft.go:859-907)
  5. output assembly — save/apply ranges and send descriptors for the engine

Control flow never branches per group: every handler computes its candidate
update for every lane and reality is selected by masks. This trades FLOPs
(cheap, elementwise) for the absence of divergence — the shape XLA wants.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .state import (
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
    make_empty_inbox,
)
from .slab import Slabs

i32 = jnp.int32


def _mix(a, b, c):
    """Deterministic integer mix for randomized election timeouts. Seeded by
    (group seed, term, slot) so replicas of one group never tie forever —
    replaces the reference's global locked RNG (raft.go:631-634)."""
    u = jnp.uint32
    x = (a * u(2654435761)) ^ (b.astype(u) * u(40503)) ^ (c.astype(u) * u(2246822519))
    x = x ^ (x >> 15)
    x = x * u(2246822519)
    x = x ^ (x >> 13)
    return x


def _rand_timeout(seed, term, slot, et):
    return et + (_mix(seed, term, slot) % et.astype(jnp.uint32)).astype(i32)


def _term_at(s: RaftTensors, idx):
    """Term of entry idx (i32[G]): ring lookup, marker, or 0 out-of-window
    (cf. logentry.go term())."""
    W = s.log_term.shape[1]
    in_ring = (idx >= s.first_index) & (idx <= s.last_index) & (idx >= 1)
    ring = jnp.take_along_axis(s.log_term, (idx % W)[:, None], axis=1)[:, 0]
    marker = idx == (s.first_index - 1)
    return jnp.where(in_ring, ring, jnp.where(marker, s.marker_term, 0))


def _rotate_rows(x, shift):
    """x[..., (j + shift) % n] for every column j of the last axis (length n):
    each row rotated left by its own traced amount, shift[...] of any sign.
    A contiguous run of ring slots modulo W is such a rotation, and a
    take_along_axis with a full index plane lowers on the TPU to a general
    gather at ~10 ns an element; this is ceil(log2 n) static rolls, each
    selected on one bit of shift, exact for any n."""
    n = x.shape[-1]
    shift = shift % n
    for k in range((n - 1).bit_length()):
        bit = ((shift >> k) & 1).astype(bool)[..., None]
        x = jnp.where(bit, jnp.roll(x, -(1 << k), axis=-1), x)
    return x


def _ring_run(ring, start, n: int):
    """ring[..., (start + e) % W] for e in 0..n-1: n consecutive slots."""
    return _rotate_rows(ring, start)[..., :n]


def _run_to_ring(run, start, W: int):
    """[G,W] plane whose slot (start + e) % W holds run[:, e], e in 0..E-1
    (E <= W); the other slots are padding the caller masks out."""
    return _rotate_rows(jnp.pad(run, ((0, 0), (0, W - run.shape[1]))), -start)


def _self_mask(s: RaftTensors):
    """bool[G,P]: True at each group's own slot."""
    P = s.member.shape[1]
    return jax.nn.one_hot(s.self_slot, P, dtype=bool)


def _num_voting(s: RaftTensors):
    return jnp.sum(s.voting, axis=1).astype(i32)


def _quorum(s: RaftTensors):
    return _num_voting(s) // 2 + 1


def _reset(s: RaftTensors, new_term, keep_term_vote=False) -> RaftTensors:
    """The shared reset on any role change (cf. raft.go reset()):
    vote cleared on term change, timers rewound, randomized timeout
    refreshed, votes/readindex/transfer/pending-cc cleared, remotes reset to
    next = last+1 (match = last for self)."""
    term_changed = new_term != s.term
    vote = jnp.where(term_changed, 0, s.vote)
    selfm = _self_mask(s)
    last = s.last_index
    return s._replace(
        term=new_term,
        vote=vote,
        election_tick=jnp.zeros_like(s.election_tick),
        heartbeat_tick=jnp.zeros_like(s.heartbeat_tick),
        rand_timeout=_rand_timeout(
            s.seed, new_term, s.self_slot, s.election_timeout
        ),
        vresp=jnp.zeros_like(s.vresp),
        vgrant=jnp.zeros_like(s.vgrant),
        transfer_to=jnp.zeros_like(s.transfer_to),
        pending_cc=jnp.zeros_like(s.pending_cc),
        ri_ctx=jnp.zeros_like(s.ri_ctx),
        ri_ctx2=jnp.zeros_like(s.ri_ctx2),
        ri_index=jnp.zeros_like(s.ri_index),
        ri_acks=jnp.zeros_like(s.ri_acks),
        ri_count=jnp.zeros_like(s.ri_count),
        # any role transition revokes the lease outright — new leadership
        # must re-earn it via a fresh quorum heartbeat round (scalar: the
        # lease clears in core.raft._reset)
        lease_until=jnp.zeros_like(s.lease_until),
        hb_round_tick=jnp.zeros_like(s.hb_round_tick),
        hb_ack_bits=jnp.zeros_like(s.hb_ack_bits),
        match=jnp.where(selfm, last[:, None], 0),
        next=jnp.broadcast_to((last + 1)[:, None], s.next.shape),
        rstate=jnp.zeros_like(s.rstate),
        snap_sent=jnp.zeros_like(s.snap_sent),
    )


def _merge(mask, new: RaftTensors, old: RaftTensors) -> RaftTensors:
    """Select new state for lanes where mask[G] is True."""
    def sel(n, o):
        if n is o:
            return o
        m = mask
        while m.ndim < n.ndim:
            m = m[..., None]
        return jnp.where(m, n, o)

    return jax.tree.map(sel, new, old)


def _become_follower(s: RaftTensors, mask, new_term, leader) -> RaftTensors:
    """Follower/observer/witness demotion preserving the special roles
    (cf. raft.go becomeFollower/becomeObserver/becomeWitness)."""
    ns = _reset(s, jnp.where(mask, new_term, s.term))
    new_role = jnp.where(
        (s.role == ROLE.OBSERVER) | (s.role == ROLE.WITNESS), s.role, ROLE.FOLLOWER
    )
    ns = ns._replace(role=new_role, leader=leader)
    return _merge(mask, ns, s)


def _append_one(s: RaftTensors, mask, is_cc) -> RaftTensors:
    """Append one entry at the current term on masked lanes (leader path)."""
    W = s.log_term.shape[1]
    idx = s.last_index + 1
    slot = idx % W
    onehot = jax.nn.one_hot(slot, W, dtype=bool) & mask[:, None]
    log_term = jnp.where(onehot, s.term[:, None], s.log_term)
    log_cc = jnp.where(onehot, is_cc[:, None], s.log_is_cc)
    last = jnp.where(mask, idx, s.last_index)
    selfm = _self_mask(s)
    match = jnp.where(selfm & mask[:, None], last[:, None], s.match)
    return s._replace(
        log_term=log_term, log_is_cc=log_cc, last_index=last, match=match
    )


def _become_leader(s: RaftTensors, mask) -> RaftTensors:
    """Candidate -> leader on masked lanes: reset remotes, append the
    new-term noop entry (cf. raft.go:975-987). The caller records the noop
    index for the host."""
    ns = _reset(s, s.term)
    ns = ns._replace(
        role=jnp.where(mask, ROLE.LEADER, ns.role),
        leader=jnp.where(mask, s.self_slot + 1, ns.leader),
        # pending config change is re-armed if an uncommitted cc exists in
        # the log window (cf. preLeaderPromotionHandleConfigChange); computed
        # by scanning the uncommitted window's cc bits.
        pending_cc=jnp.where(mask, _has_uncommitted_cc(s), ns.pending_cc),
    )
    ns = _append_one(ns, mask, jnp.zeros_like(mask))
    return _merge(mask, ns, s)


def _has_uncommitted_cc(s: RaftTensors):
    """bool[G]: any config-change entry in (committed, last_index]."""
    W = s.log_is_cc.shape[1]
    idxs = jnp.arange(W, dtype=i32)[None, :]
    # reconstruct each ring slot's absolute index: the slot holds the largest
    # index <= last with index % W == slot and index >= first
    # simpler: an entry at absolute index i is live iff first<=i<=last; slot
    # i%W. For the uncommitted window check we scan all live slots.
    base = (s.last_index[:, None] // W) * W
    cand = base + idxs
    cand = jnp.where(cand > s.last_index[:, None], cand - W, cand)
    live = (cand > s.committed[:, None]) & (cand >= s.first_index[:, None]) & (
        cand <= s.last_index[:, None]
    )
    return jnp.any(live & s.log_is_cc, axis=1)


def _campaign(
    s: RaftTensors, mask, out, transfer_hint, force_real=None
) -> Tuple[RaftTensors, dict]:
    """Start an election on masked lanes (cf. raft.go campaign()):
    become candidate (term+1, vote self), emit RequestVote descriptors;
    single-node quorum becomes leader instantly. Lanes with prevote_on
    first run the NON-DISRUPTIVE poll (thesis 9.6): role flips to
    PRE_CANDIDATE and REQUEST_PREVOTE descriptors go out, but term, vote
    and timers stay untouched — ``force_real`` (a won poll) and
    ``transfer_hint`` (a sanctioned leadership transfer) skip the poll."""
    can = (
        mask
        & s.active
        & (s.role != ROLE.LEADER)
        & (s.role != ROLE.OBSERVER)
        & (s.role != ROLE.WITNESS)
        # campaign blocked while config changes are committed-but-unapplied
        # (cf. raft.go:1484-1508)
        & ~_has_cc_to_apply(s)
        # self still a member
        & jnp.any(s.voting & _self_mask(s), axis=1)
    )
    selfm = _self_mask(s)
    single_now = _num_voting(s) == 1
    pre = can & s.prevote_on & ~transfer_hint & ~single_now
    if force_real is not None:
        pre = pre & ~force_real
    real = can & ~pre
    # --- pre-vote poll: visible only in role/tally state ------------------
    s = s._replace(
        role=jnp.where(pre, ROLE.PRE_CANDIDATE, s.role),
        leader=jnp.where(pre, 0, s.leader),
        vresp=jnp.where(pre[:, None], selfm, s.vresp),
        vgrant=jnp.where(pre[:, None], selfm, s.vgrant),
    )
    # --- real election ----------------------------------------------------
    ns = _reset(s, s.term + 1)
    ns = ns._replace(
        role=jnp.where(real, ROLE.CANDIDATE, ns.role),
        leader=jnp.where(real, 0, ns.leader),
        vote=jnp.where(real, s.self_slot + 1, ns.vote),
        vresp=jnp.where(real[:, None], selfm, ns.vresp),
        vgrant=jnp.where(real[:, None], selfm, ns.vgrant),
    )
    ns = _merge(real, ns, s)
    # single voting member: leader immediately
    single = real & (_num_voting(ns) == 1)
    noop_at = jnp.where(single, ns.last_index + 1, 0)
    ns = _become_leader(ns, single)
    # counter plane: a real campaign is an election started (pre-vote
    # polls are not — the scalar core's campaign() vs pre_campaign()
    # split), and the single-voter instant win is an election won
    out["ctr_elections_started"] = out["ctr_elections_started"] + jnp.where(
        real, 1, 0
    )
    out["ctr_elections_won"] = out["ctr_elections_won"] + jnp.where(
        single, 1, 0
    )
    # vote/pre-vote requests to all other voting members (one shared
    # descriptor plane: the wire type and term are selected downstream
    # from the end-of-step role — a lane is never both roles at once)
    others = ns.voting & ~_self_mask(ns)
    flags = jnp.where(
        ((real & ~single) | pre)[:, None] & others,
        out["send_flags"] | SEND_VOTE_REQ,
        out["send_flags"],
    )
    hint = jnp.where(
        (real & ~single & transfer_hint)[:, None] & others,
        ns.self_slot[:, None] + 1,
        out["send_hint"],
    )
    out = dict(out, send_flags=flags, send_hint=hint)
    out["noop_appended"] = jnp.maximum(out["noop_appended"], noop_at)
    out["noop_term"] = jnp.maximum(
        out["noop_term"], jnp.where(single, ns.term, 0)
    )
    return ns, out


def _has_cc_to_apply(s: RaftTensors):
    """bool[G]: config-change entry in (applied, committed]."""
    W = s.log_is_cc.shape[1]
    idxs = jnp.arange(W, dtype=i32)[None, :]
    base = (s.last_index[:, None] // W) * W
    cand = base + idxs
    cand = jnp.where(cand > s.last_index[:, None], cand - W, cand)
    live = (
        (cand > s.applied[:, None])
        & (cand <= s.committed[:, None])
        & (cand >= s.first_index[:, None])
    )
    return jnp.any(live & s.log_is_cc, axis=1)


# ---------------------------------------------------------------------------
# message handling (one inbox slot across all groups)
# ---------------------------------------------------------------------------


def _is_leader_msg(t):
    return (
        (t == MSG.REPLICATE)
        | (t == MSG.INSTALL_SNAPSHOT)
        | (t == MSG.HEARTBEAT)
        | (t == MSG.TIMEOUT_NOW)
        | (t == MSG.READ_INDEX_RESP)
    )


def _handle_message(s: RaftTensors, m, out, cfg: KernelConfig):
    """Apply one message per group (the k-th inbox slot). Implements the
    term-matching preamble (raft.go:1415-1449) then the handler table as
    masked updates."""
    P = s.member.shape[1]
    W = s.log_term.shape[1]
    E = cfg.max_entries_per_msg
    mtype = m["mtype"]
    present = mtype != MSG.NONE
    from_slot = m["from_slot"]
    mterm = m["term"]

    with jax.named_scope("election"):
        # ---- term preamble -----------------------------------------------------
        local = mterm == 0
        higher = present & ~local & (mterm > s.term)
        lower = present & ~local & (mterm < s.term)
        is_pv = mtype == MSG.REQUEST_PREVOTE
        is_pvr = mtype == MSG.REQUEST_PREVOTE_RESP
        # disruption defense (raft.go:1387-1409); a live leader's lease
        # refuses a pre-vote poll the same way it refuses the vote
        drop_rv = (
            higher
            & ((mtype == MSG.REQUEST_VOTE) | is_pv)
            & s.check_quorum
            & (m["hint"] != from_slot + 1)
            & (s.leader != 0)
            & (s.election_tick < s.election_timeout)
        )
        # a pre-vote poll never changes our term, and a GRANTED poll response
        # echoes our prospective term back (the real bump happens only when
        # the poll wins and the real campaign runs)
        step_down = higher & ~drop_rv & ~is_pv & ~(is_pvr & ~m["reject"])
        new_leader = jnp.where(_is_leader_msg(mtype), from_slot + 1, 0)
        s = _become_follower(s, step_down, mterm, jnp.where(step_down, new_leader, s.leader))
        # lower-term leader msg + check-quorum => NOOP response to free a stuck
        # candidate (raft.go:1441-1447); a lower-term pre-vote poll is answered
        # with a reject at OUR term so the poller abandons it; everything
        # lower-term is then dropped
        noop_resp = lower & _is_leader_msg(mtype) & s.check_quorum
        pv_stale = lower & is_pv
        dropped = lower | drop_rv
        act = present & ~dropped

        is_leader = s.role == ROLE.LEADER
        is_cand = s.role == ROLE.CANDIDATE
        is_precand = s.role == ROLE.PRE_CANDIDATE
        is_obs = s.role == ROLE.OBSERVER
        is_wit = s.role == ROLE.WITNESS
        is_fol = s.role == ROLE.FOLLOWER

        resp_type = jnp.where(noop_resp, MSG.NOOP, MSG.NONE)
        resp_type = jnp.where(pv_stale, MSG.REQUEST_PREVOTE_RESP, resp_type)
        resp_to = from_slot
        resp_log_index = jnp.zeros_like(mterm)
        resp_reject = pv_stale
        resp_hint = jnp.zeros_like(mterm)
        resp_hint2 = jnp.zeros_like(mterm)
        # per-slot response term override (0 = stamp the lane's current term):
        # pre-vote grants echo the poll's prospective term
        pv_resp_term = jnp.zeros_like(mterm)

        selfm = _self_mask(s)
        from_onehot = jax.nn.one_hot(from_slot, P, dtype=bool)
        known_from = jnp.any(s.member & from_onehot, axis=1)

        # ---- RequestVote (any state) ------------------------------------------
        rv = act & (mtype == MSG.REQUEST_VOTE) & (
            is_fol | is_cand | is_precand | is_leader | is_wit
        )
        can_grant = (s.vote == 0) | (s.vote == from_slot + 1)
        last_term = _term_at(s, s.last_index)
        utd = (m["log_term"] > last_term) | (
            (m["log_term"] == last_term) & (m["log_index"] >= s.last_index)
        )
        grant = rv & can_grant & utd
        s = s._replace(
            vote=jnp.where(grant, from_slot + 1, s.vote),
            election_tick=jnp.where(grant, 0, s.election_tick),
        )
        resp_type = jnp.where(rv, MSG.REQUEST_VOTE_RESP, resp_type)
        resp_reject = jnp.where(rv, ~grant, resp_reject)

        # ---- RequestPreVote (voting states, cf. scalar handler tables) --------
        # grant iff the poll's prospective term beats ours AND the poller's log
        # is up to date; NOTHING in our state changes either way (no vote, no
        # term adoption, no election-timer reset) — that is the phase's point
        pv = act & is_pv & (is_fol | is_cand | is_precand | is_leader | is_wit)
        grant_pv = pv & (mterm > s.term) & utd
        resp_type = jnp.where(pv, MSG.REQUEST_PREVOTE_RESP, resp_type)
        resp_reject = jnp.where(pv, ~grant_pv, resp_reject)
        pv_resp_term = jnp.where(grant_pv, mterm, pv_resp_term)

        # ---- RequestVoteResp (candidate) --------------------------------------
        rvr = act & (mtype == MSG.REQUEST_VOTE_RESP) & is_cand & known_from
        first_resp = rvr & ~jnp.any(s.vresp & from_onehot, axis=1)
        s = s._replace(
            vresp=jnp.where(first_resp[:, None] & from_onehot, True, s.vresp),
            vgrant=jnp.where(
                first_resp[:, None] & from_onehot, ~m["reject"][:, None], s.vgrant
            ),
        )
        granted = jnp.sum(s.vgrant & s.voting, axis=1).astype(i32)
        rejected = jnp.sum(s.vresp & ~s.vgrant & s.voting, axis=1).astype(i32)
        q = _quorum(s)
        win = rvr & (granted >= q)
        lose = rvr & ~win & (rejected >= q)
        noop_at = jnp.where(win, s.last_index + 1, 0)
        s = _become_leader(s, win)
        out["ctr_elections_won"] = out["ctr_elections_won"] + jnp.where(win, 1, 0)
        out["noop_appended"] = jnp.maximum(out["noop_appended"], noop_at)
        out["noop_term"] = jnp.maximum(out["noop_term"], jnp.where(win, s.term, 0))
        s = _become_follower(s, lose, s.term, jnp.zeros_like(s.leader))

        # ---- RequestPreVoteResp (pre-candidate) -------------------------------
        # same tally planes as the real election (a lane is never candidate
        # and pre-candidate at once); a won poll runs the REAL campaign, a
        # lost one falls back to follower at the UNCHANGED term
        pvr = act & is_pvr & is_precand & known_from
        first_pvr = pvr & ~jnp.any(s.vresp & from_onehot, axis=1)
        s = s._replace(
            vresp=jnp.where(first_pvr[:, None] & from_onehot, True, s.vresp),
            vgrant=jnp.where(
                first_pvr[:, None] & from_onehot, ~m["reject"][:, None], s.vgrant
            ),
        )
        granted_pv = jnp.sum(s.vgrant & s.voting, axis=1).astype(i32)
        rejected_pv = jnp.sum(s.vresp & ~s.vgrant & s.voting, axis=1).astype(i32)
        q = _quorum(s)
        win_pv = pvr & (granted_pv >= q)
        lose_pv = pvr & ~win_pv & (rejected_pv >= q)
        s, out = _campaign(
            s, win_pv, out, jnp.zeros_like(win_pv), force_real=win_pv
        )
        s = _become_follower(s, lose_pv, s.term, jnp.zeros_like(s.leader))

        # ---- Election / TimeoutNow --------------------------------------------
        ele = act & (mtype == MSG.ELECTION)
        tno = act & (mtype == MSG.TIMEOUT_NOW) & is_fol
        s, out = _campaign(s, ele | tno, out, transfer_hint=tno)

        # per-slot append bases reported to the engine so the host can place
        # payload bytes at the device-assigned indexes without guessing
        prop_base = jnp.zeros_like(mterm)
        rep_base = jnp.zeros_like(mterm)

    with jax.named_scope("follower_append"):
        # ---- Replicate (non-leader) -------------------------------------------
        rep = act & (mtype == MSG.REPLICATE) & (
            is_fol | is_obs | is_wit | is_cand | is_precand
        )
        # (pre-)candidate at same term: a leader exists -> become follower
        # (raft.go:1944)
        rep_demote = rep & (is_cand | is_precand)
        s = _become_follower(
            s, rep_demote, s.term, jnp.where(rep_demote, from_slot + 1, s.leader)
        )
        s = s._replace(
            leader=jnp.where(rep, from_slot + 1, s.leader),
            election_tick=jnp.where(rep, 0, s.election_tick),
        )
        prev = m["log_index"]
        nent = m["n_entries"]
        stale = rep & (prev < s.committed)
        match_prev = _term_at(s, prev) == m["log_term"]
        in_window = (prev >= s.first_index - 1) & (prev <= s.last_index)
        ok = rep & ~stale & match_prev & in_window
        rej = rep & ~stale & ~ok
        out["ctr_replicate_rejects"] = out["ctr_replicate_rejects"] + jnp.where(
            rej, 1, 0
        )
        # conflict scan over the E attached entries
        if E > 0:
            e_idx = prev[:, None] + 1 + jnp.arange(E, dtype=i32)[None, :]
            e_valid = jnp.arange(E, dtype=i32)[None, :] < nent[:, None]
            have = e_idx <= s.last_index[:, None]
            exist_term = _ring_run(s.log_term, prev + 1, E)
            conflict = e_valid & (~have | (exist_term != m["entry_terms"]))
            first_conf = jnp.min(
                jnp.where(conflict, e_idx, jnp.iinfo(jnp.int32).max), axis=1
            )
            any_conf = jnp.any(conflict, axis=1)
            do_append = ok & any_conf
            # ring-slot write WITHOUT a per-entry loop: slot w receives absolute
            # index i(w) = lo + ((w - lo) mod W) — the unique index in the
            # written span congruent to w (nent <= E <= W guarantees at most
            # one) — and that index's entry is e = (w - (prev + 1)) mod W, so
            # the whole scatter is the message's run rotated onto the ring and
            # one (G,W) select, at a cost independent of E
            w_idx = jnp.arange(W, dtype=i32)[None, :]
            lo = jnp.where(do_append, first_conf, 1)
            hi = prev + nent
            i_w = lo[:, None] + jnp.mod(w_idx - lo[:, None], W)
            written = do_append[:, None] & (i_w <= hi[:, None])
            terms_w = _run_to_ring(m["entry_terms"], prev + 1, W)
            cc_w = _run_to_ring(m["entry_cc"], prev + 1, W)
            log_term = jnp.where(written, terms_w, s.log_term)
            log_cc = jnp.where(written, cc_w, s.log_is_cc)
            new_last = jnp.where(do_append, prev + nent, s.last_index)
            s = s._replace(
                log_term=log_term,
                log_is_cc=log_cc,
                last_index=new_last,
                unsaved_from=jnp.where(
                    do_append, jnp.minimum(s.unsaved_from, first_conf), s.unsaved_from
                ),
            )
        ack_to = prev + nent
        new_commit = jnp.clip(jnp.minimum(ack_to, m["commit"]), s.committed, s.last_index)
        s = s._replace(committed=jnp.where(ok, new_commit, s.committed))
        rep_base = jnp.where(ok, prev + 1, rep_base)
        resp_type = jnp.where(rep, MSG.REPLICATE_RESP, resp_type)
        resp_log_index = jnp.where(
            stale, s.committed, jnp.where(ok, ack_to, jnp.where(rej, prev, resp_log_index))
        )
        resp_reject = jnp.where(rej, True, resp_reject)
        resp_hint = jnp.where(rej, s.last_index, resp_hint)

        # ---- Heartbeat (non-leader) -------------------------------------------
        hb = act & (mtype == MSG.HEARTBEAT) & (
            is_fol | is_obs | is_wit | is_cand | is_precand
        )
        hb_demote = hb & (is_cand | is_precand)
        s = _become_follower(
            s, hb_demote, s.term, jnp.where(hb_demote, from_slot + 1, s.leader)
        )
        s = s._replace(
            leader=jnp.where(hb, from_slot + 1, s.leader),
            election_tick=jnp.where(hb, 0, s.election_tick),
            committed=jnp.where(
                hb, jnp.clip(m["commit"], s.committed, s.last_index), s.committed
            ),
        )
        resp_type = jnp.where(hb, MSG.HEARTBEAT_RESP, resp_type)
        # echo the leader's lease round tag (log_index, 0 when leases off)
        resp_log_index = jnp.where(hb, m["log_index"], resp_log_index)
        resp_hint = jnp.where(hb, m["hint"], resp_hint)
        resp_hint2 = jnp.where(hb, m["hint_high"], resp_hint2)

    with jax.named_scope("leader_acks"):
        # ---- ReplicateResp (leader) -------------------------------------------
        rr = act & (mtype == MSG.REPLICATE_RESP) & (s.role == ROLE.LEADER) & known_from
        fr = from_onehot  # [G,P]
        prev_rstate = s.rstate
        racc = rr & ~m["reject"]
        moved = racc & (m["log_index"] > jnp.sum(jnp.where(fr, s.match, 0), axis=1))
        s = s._replace(
            ract=jnp.where(rr[:, None] & fr, True, s.ract),
            match=jnp.where(
                racc[:, None] & fr, jnp.maximum(s.match, m["log_index"][:, None]), s.match
            ),
            next=jnp.where(
                racc[:, None] & fr,
                jnp.maximum(s.next, m["log_index"][:, None] + 1),
                s.next,
            ),
        )
        # respondedTo(): RETRY -> REPLICATE; SNAPSHOT -> RETRY once caught up
        # (remote.go:145-153); WAIT -> RETRY on movement (tryUpdate)
        st = s.rstate
        st = jnp.where(
            moved[:, None] & fr & (st == RSTATE.WAIT), RSTATE.RETRY, st
        )
        st = jnp.where(moved[:, None] & fr & (st == RSTATE.RETRY), RSTATE.REPLICATE, st)
        caught = s.match >= s.snap_sent
        st = jnp.where(
            moved[:, None] & fr & (st == RSTATE.SNAPSHOT) & caught, RSTATE.RETRY, st
        )
        s = s._replace(rstate=st)
        # rejection: flow-control backoff (remote.go:155-171)
        rrej = rr & m["reject"]
        in_repl = jnp.any(fr & (prev_rstate == RSTATE.REPLICATE), axis=1)
        cur_match = jnp.sum(jnp.where(fr, s.match, 0), axis=1)
        cur_next = jnp.sum(jnp.where(fr, s.next, 0), axis=1)
        valid_repl = rrej & in_repl & (m["log_index"] > cur_match)
        valid_probe = rrej & ~in_repl & (cur_next - 1 == m["log_index"])
        nn = jnp.where(
            valid_repl,
            cur_match + 1,
            jnp.maximum(1, jnp.minimum(m["log_index"], m["hint"] + 1)),
        )
        # a peer parked for a snapshot stays parked: a reject of a probe
        # sent before the park is stale (raft.go enterRetryState leaves a
        # remote in the snapshot state alone). Un-parking it here made
        # the next step ask for another snapshot of the same peer
        parked = jnp.any(fr & (prev_rstate == RSTATE.SNAPSHOT), axis=1)
        dec = (valid_repl | valid_probe) & ~parked
        s = s._replace(
            next=jnp.where(dec[:, None] & fr, nn[:, None], s.next),
            rstate=jnp.where(
                dec[:, None] & fr, RSTATE.RETRY, s.rstate
            ),
        )
        # transfer fast path: target caught up => TimeoutNow (raft.go:1679-1684)
        tt = s.transfer_to
        t_caught = (
            racc
            & (tt != 0)
            & (from_slot + 1 == tt)
            & (jnp.sum(jnp.where(fr, s.match, 0), axis=1) == s.last_index)
        )
        out["send_flags"] = jnp.where(
            t_caught[:, None] & fr, out["send_flags"] | SEND_TIMEOUT_NOW, out["send_flags"]
        )

        # ---- HeartbeatResp (leader) -------------------------------------------
        hr = act & (mtype == MSG.HEARTBEAT_RESP) & (s.role == ROLE.LEADER) & known_from
        s = s._replace(
            ract=jnp.where(hr[:, None] & fr, True, s.ract),
            rstate=jnp.where(
                hr[:, None] & fr & (s.rstate == RSTATE.WAIT), RSTATE.RETRY, s.rstate
            ),
        )
        # a peer whose match lags gets a (possibly empty) Replicate probe; the
        # reject/backoff cycle then recovers lost optimistic sends
        # (cf. raft.go:1794-1800 handleLeaderHeartbeatResp)
        out["force_probe"] = out["force_probe"] | (
            hr[:, None] & fr & (s.match < s.last_index[:, None])
        )
        # readindex leadership confirmation (raft.go:1736-1756)
        R = s.ri_ctx.shape[1]
        hint_match = (
            hr[:, None]
            & (s.ri_ctx == m["hint"][:, None])
            & (s.ri_ctx2 == m["hint_high"][:, None])
            & (s.ri_ctx != 0)
        )
        frombit = (jnp.int32(1) << from_slot)[:, None]
        s = s._replace(ri_acks=jnp.where(hint_match, s.ri_acks | frombit, s.ri_acks))
        # lease round ack (scalar: _handle_leader_heartbeat_resp): the follower
        # echoed the open round's tick tag in log_index; collect voting acks and
        # at quorum extend the lease to round-start + election_timeout - margin —
        # strictly inside the window in which no other node can win an election
        tag_match = (
            hr
            & s.lease_on
            & (m["log_index"] != 0)
            & (m["log_index"] == s.hb_round_tick)
            & jnp.any(fr & s.voting, axis=1)
        )
        new_bits = jnp.where(tag_match, s.hb_ack_bits | frombit[:, 0], s.hb_ack_bits)
        ackn = _popcount(new_bits)
        grant = (
            hr
            & s.lease_on
            & s.clock_ok
            & (s.hb_round_tick != 0)
            & (ackn + 1 >= _quorum(s))
        )
        s = s._replace(
            hb_ack_bits=new_bits,
            lease_until=jnp.where(
                grant,
                jnp.maximum(
                    s.lease_until,
                    s.hb_round_tick + s.election_timeout - s.lease_margin,
                ),
                s.lease_until,
            ),
        )

    with jax.named_scope("readindex"):
        # ---- ReadIndex (leader) ------------------------------------------------
        ri = act & (mtype == MSG.READ_INDEX) & (s.role == ROLE.LEADER)
        qq = _quorum(s)
        single = _num_voting(s) == 1
        committed_this_term = _term_at(s, s.committed) == s.term
        ok_ri = ri & (single | committed_this_term)
        slot_free = s.ri_count < R
        # lease fast path: a live lease makes the local committed index the
        # linearization point — the read rides the immediate-ready mechanism
        # (acks = -1) instead of opening a quorum heartbeat round. Expired /
        # revoked / suspect lanes fall through to the quorum path below
        # (degradation, not danger).
        lease_valid = (
            s.lease_on
            & s.clock_ok
            & (s.tick_count < s.lease_until)
            & (s.transfer_to == 0)
        )
        imm_lease = ok_ri & ~single & lease_valid & slot_free
        enq = ok_ri & ~single & ~lease_valid & slot_free
        pos = s.ri_count
        posm = jax.nn.one_hot(pos, R, dtype=bool) & enq[:, None]
        s = s._replace(
            ri_ctx=jnp.where(posm, m["hint"][:, None], s.ri_ctx),
            ri_ctx2=jnp.where(posm, m["hint_high"][:, None], s.ri_ctx2),
            ri_index=jnp.where(posm, s.committed[:, None], s.ri_index),
            ri_acks=jnp.where(posm, 0, s.ri_acks),
            ri_count=jnp.where(enq, s.ri_count + 1, s.ri_count),
        )
        # heartbeat broadcast with ctx hint
        others_v = s.voting & ~selfm
        out["send_flags"] = jnp.where(
            enq[:, None] & others_v, out["send_flags"] | SEND_HEARTBEAT, out["send_flags"]
        )
        # counted at the send decision (the scalar core's per-target
        # broadcast_heartbeat_message(ctx)), not at end-of-step gating
        out["ctr_heartbeats_sent"] = out["ctr_heartbeats_sent"] + jnp.sum(
            enq[:, None] & others_v, axis=1
        ).astype(i32)
        out["send_hint"] = jnp.where(
            enq[:, None] & others_v, m["hint"][:, None], out["send_hint"]
        )
        out["send_hint2"] = jnp.where(
            enq[:, None] & others_v, m["hint_high"][:, None], out["send_hint2"]
        )
        # single-node or lease-served: instantly ready (delivered via the ready
        # queue at step end)
        imm = (ok_ri & single) | imm_lease
        posm2 = jax.nn.one_hot(s.ri_count, R, dtype=bool) & imm[:, None]
        s = s._replace(
            ri_ctx=jnp.where(posm2, m["hint"][:, None], s.ri_ctx),
            ri_ctx2=jnp.where(posm2, m["hint_high"][:, None], s.ri_ctx2),
            ri_index=jnp.where(posm2, s.committed[:, None], s.ri_index),
            ri_acks=jnp.where(posm2, jnp.int32(-1), s.ri_acks),
            ri_count=jnp.where(imm, s.ri_count + 1, s.ri_count),
        )
        out["dropped_readindex"] = out["dropped_readindex"] + jnp.where(
            (ri & ~ok_ri) | (ok_ri & ~single & ~slot_free), 1, 0
        )
        out["lease_served"] = out["lease_served"] + jnp.where(imm_lease, 1, 0)
        out["lease_fallback"] = out["lease_fallback"] + jnp.where(
            enq & s.lease_on, 1, 0
        )

    with jax.named_scope("propose_append"):
        # ---- Propose (leader) --------------------------------------------------
        # Host routes proposals to the group's leader replica; a lane that is not
        # leader reports the forward target instead (host-side forwarding
        # replaces the reference's follower Propose relay, raft.go:1839-1851).
        pp = act & (mtype == MSG.PROPOSE)
        pok = pp & (s.role == ROLE.LEADER) & (s.transfer_to == 0)
        # config-change entries: at most one pending (raft.go:1587-1606).
        # HOST INVARIANT: the engine packs a config-change entry alone in its own
        # single-entry PROPOSE message (never mixed with regular entries), so the
        # pending check is all-or-nothing per message.
        e_in_msg = jnp.arange(E, dtype=i32)[None, :] < nent[:, None]
        has_cc = jnp.any(m["entry_cc"] & e_in_msg, axis=1)
        cc_allowed = pok & has_cc & ~s.pending_cc
        cc_stripped = pok & has_cc & s.pending_cc
        s = s._replace(pending_cc=jnp.where(cc_allowed, True, s.pending_cc))
        out["dropped_cc"] = out["dropped_cc"] | cc_stripped
        room = s.last_index - s.first_index + 1 + nent <= W
        can_append = pok & room
        prop_base = jnp.where(can_append, s.last_index + 1, prop_base)
        # append up to E entries at the current term — same loop-free ring-slot
        # scatter as the Replicate path: slot w gets index lo + ((w - lo) mod W)
        if E > 0:
            eff_cc = m["entry_cc"] & cc_allowed[:, None]
            w_idx = jnp.arange(W, dtype=i32)[None, :]
            a_lo = s.last_index + 1
            a_hi = s.last_index + nent
            i_w = a_lo[:, None] + jnp.mod(w_idx - a_lo[:, None], W)
            written = can_append[:, None] & (i_w <= a_hi[:, None])
            cc_w = _run_to_ring(eff_cc, a_lo, W)
            log_term = jnp.where(written, s.term[:, None], s.log_term)
            log_cc = jnp.where(written, cc_w, s.log_is_cc)
            new_last = jnp.where(can_append, s.last_index + nent, s.last_index)
            s = s._replace(
                log_term=log_term,
                log_is_cc=log_cc,
                last_index=new_last,
                match=jnp.where(selfm & can_append[:, None], new_last[:, None], s.match),
            )
        out["fwd_leader"] = jnp.where(pp & ~pok, s.leader, out["fwd_leader"])
        out["log_full"] = out["log_full"] | (pok & ~room)

    with jax.named_scope("misc"):
        # ---- ReadIndexResp (follower/observer) --------------------------------
        rir = act & (mtype == MSG.READ_INDEX_RESP) & (is_fol | is_obs)
        s = s._replace(
            leader=jnp.where(rir, from_slot + 1, s.leader),
            election_tick=jnp.where(rir, 0, s.election_tick),
        )
        # deliver through the ready queue
        posm3 = jax.nn.one_hot(s.ri_count, R, dtype=bool) & (
            rir & (s.ri_count < R)
        )[:, None]
        s = s._replace(
            ri_ctx=jnp.where(posm3, m["hint"][:, None], s.ri_ctx),
            ri_ctx2=jnp.where(posm3, m["hint_high"][:, None], s.ri_ctx2),
            ri_index=jnp.where(posm3, m["log_index"][:, None], s.ri_index),
            ri_acks=jnp.where(posm3, jnp.int32(-1), s.ri_acks),
            ri_count=jnp.where(rir & (s.ri_count < R), s.ri_count + 1, s.ri_count),
        )

        # ---- LeaderTransfer (leader) ------------------------------------------
        lt = act & (mtype == MSG.LEADER_TRANSFER) & (s.role == ROLE.LEADER)
        target = m["hint"]  # slot+1
        lt_ok = lt & (s.transfer_to == 0) & (target != s.self_slot + 1) & (target != 0)
        s = s._replace(
            transfer_to=jnp.where(lt_ok, target, s.transfer_to),
            election_tick=jnp.where(lt_ok, 0, s.election_tick),
        )
        t_oh = jax.nn.one_hot(jnp.maximum(target - 1, 0), P, dtype=bool)
        t_match = jnp.sum(jnp.where(t_oh, s.match, 0), axis=1)
        fast = lt_ok & (t_match == s.last_index)
        out["send_flags"] = jnp.where(
            fast[:, None] & t_oh, out["send_flags"] | SEND_TIMEOUT_NOW, out["send_flags"]
        )

        # ---- Unreachable / SnapshotStatus (leader) -----------------------------
        un = act & (mtype == MSG.UNREACHABLE) & (s.role == ROLE.LEADER) & known_from
        s = s._replace(
            rstate=jnp.where(
                un[:, None] & fr & (s.rstate == RSTATE.REPLICATE), RSTATE.RETRY, s.rstate
            )
        )
        st2 = act & (mtype == MSG.SNAPSHOT_STATUS) & (s.role == ROLE.LEADER) & known_from
        in_snap = fr & (s.rstate == RSTATE.SNAPSHOT)
        s = s._replace(
            snap_sent=jnp.where(
                st2[:, None] & in_snap & m["reject"][:, None], 0, s.snap_sent
            ),
            # becomeWait: next = max(match+1, snap_sent+1), state WAIT
            next=jnp.where(
                st2[:, None] & in_snap,
                jnp.maximum(s.match + 1, s.snap_sent + 1),
                s.next,
            ),
            rstate=jnp.where(st2[:, None] & in_snap, RSTATE.WAIT, s.rstate),
        )

    resps = {
        "resp_type": jnp.where(act | noop_resp | pv_stale, resp_type, MSG.NONE),
        "resp_to": resp_to,
        # pre-vote grants echo the poll's prospective term; everything
        # else stamps the lane's (end-of-slot) current term
        "resp_term": jnp.where(pv_resp_term > 0, pv_resp_term, s.term),
        "resp_log_index": resp_log_index,
        "resp_reject": resp_reject,
        "resp_hint": resp_hint,
        "resp_hint2": resp_hint2,
        "prop_base": prop_base,
        "rep_base": rep_base,
    }
    return s, out, resps


# ---------------------------------------------------------------------------
# tick phase
# ---------------------------------------------------------------------------


def _quiesce(s: RaftTensors, inbox: Inbox, ticks):
    """Idle-lane freeze (cf. quiesce.go:23-123): a lane with quiesce
    enabled that sees no non-heartbeat inbox traffic for quiesce_threshold
    ticks enters the quiesced state; while quiesced its election/heartbeat
    timers do not advance (so leaders stop heartbeating and followers stop
    campaigning), making 10k+ idle groups cost zero host fan-out. Any
    non-heartbeat message (Replicate, Propose, RequestVote, the engine's
    wake NOOP) exits quiesce, with the election timer rewound so the exit
    cannot itself trigger an election."""
    t = inbox.mtype
    activity = jnp.any(
        (t != MSG.NONE) & (t != MSG.HEARTBEAT) & (t != MSG.HEARTBEAT_RESP),
        axis=1,
    )
    idle = jnp.where(
        activity | ~s.quiesce_on, 0, s.idle_ticks + jnp.maximum(ticks, 0)
    )
    entering = s.quiesce_on & s.active & ~s.quiesced & (
        idle >= s.quiesce_threshold
    )
    exiting = s.quiesced & activity
    return s._replace(
        idle_ticks=idle,
        quiesced=(s.quiesced | entering) & ~activity,
        election_tick=jnp.where(exiting, 0, s.election_tick),
    )


def _tick(s: RaftTensors, ticks, out):
    """Advance logical clocks for lanes with ticks > 0 (cf. raft.go:551-629).
    Multiple coalesced ticks advance timers by that amount, matching the
    reference's LocalTick coalescing (node.go:1152-1159). Quiesced lanes
    freeze (cf. quiescedTick raft.go:623-629)."""
    do = s.active & (ticks > 0) & ~s.quiesced
    s = s._replace(
        tick_count=s.tick_count + jnp.where(do, ticks, 0),
        election_tick=s.election_tick + jnp.where(do, ticks, 0),
    )
    is_leader = s.role == ROLE.LEADER
    # --- non-leader: election timeout
    can_campaign = (
        do
        & ~is_leader
        & (s.role != ROLE.OBSERVER)
        & (s.role != ROLE.WITNESS)
        & (s.election_tick >= s.rand_timeout)
    )
    s = s._replace(
        election_tick=jnp.where(can_campaign, 0, s.election_tick)
    )
    s, out = _campaign(s, can_campaign, out, jnp.zeros_like(can_campaign))
    # --- leader: check quorum + transfer abort at election timeout
    cq_due = do & is_leader & (s.election_tick >= s.election_timeout)
    s = s._replace(
        election_tick=jnp.where(cq_due, 0, s.election_tick),
        transfer_to=jnp.where(cq_due, 0, s.transfer_to),
    )
    active_cnt = jnp.sum((s.ract | _self_mask(s)) & s.voting, axis=1).astype(i32)
    down = cq_due & s.check_quorum & (active_cnt < _quorum(s))
    s = s._replace(ract=jnp.where(cq_due[:, None], False, s.ract))
    s = _become_follower(s, down, s.term, jnp.zeros_like(s.leader))
    # --- leader: heartbeat timeout
    is_leader = s.role == ROLE.LEADER
    s = s._replace(heartbeat_tick=s.heartbeat_tick + jnp.where(do & is_leader, ticks, 0))
    hb_due = do & is_leader & (s.heartbeat_tick >= s.heartbeat_timeout)
    s = s._replace(heartbeat_tick=jnp.where(hb_due, 0, s.heartbeat_tick))
    # open a new lease round, tagged with the just-advanced tick count:
    # followers echo the tag in HEARTBEAT_RESP.log_index and quorum acks
    # grant the lease (HeartbeatResp handler). tick_count >= 1 by the time
    # any heartbeat fires, so tag 0 always reads "no round / leases off".
    open_round = hb_due & s.lease_on
    s = s._replace(
        hb_round_tick=jnp.where(open_round, s.tick_count, s.hb_round_tick),
        hb_ack_bits=jnp.where(open_round, 0, s.hb_ack_bits),
    )
    # heartbeat to voting members; with a pending readindex ctx attach the
    # newest ctx as hint (raft.go:828-846)
    R = s.ri_ctx.shape[1]
    newest_pos = jnp.maximum(s.ri_count - 1, 0)
    newest_ctx = jnp.take_along_axis(s.ri_ctx, newest_pos[:, None], axis=1)[:, 0]
    newest_ctx2 = jnp.take_along_axis(
        s.ri_ctx2, newest_pos[:, None], axis=1
    )[:, 0]
    pending = s.ri_count > 0
    hint = jnp.where(pending, newest_ctx, 0)
    hint2 = jnp.where(pending, newest_ctx2, 0)
    others_v = s.voting & ~_self_mask(s)
    obs = s.observer
    tgt = jnp.where(pending[:, None], others_v, others_v | obs)
    out["send_flags"] = jnp.where(
        hb_due[:, None] & tgt, out["send_flags"] | SEND_HEARTBEAT, out["send_flags"]
    )
    out["ctr_heartbeats_sent"] = out["ctr_heartbeats_sent"] + jnp.sum(
        hb_due[:, None] & tgt, axis=1
    ).astype(i32)
    out["send_hint"] = jnp.where(hb_due[:, None] & tgt, hint[:, None], out["send_hint"])
    out["send_hint2"] = jnp.where(
        hb_due[:, None] & tgt, hint2[:, None], out["send_hint2"]
    )
    return s, out


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def step_batch(
    s: RaftTensors, inbox: Inbox, ticks: jax.Array, cfg: KernelConfig
) -> Tuple[RaftTensors, StepOutput]:
    """One protocol step for all groups: tick + drain K inbox slots + commit
    + emit engine directives. Jit this (see make_step_fn)."""
    G, P = s.member.shape
    K = inbox.mtype.shape[1]
    R = s.ri_ctx.shape[1]

    prev_term, prev_vote, prev_commit = s.term, s.vote, s.committed
    save_base_floor = s.unsaved_from

    out = {
        "send_flags": jnp.zeros((G, P), i32),
        "send_hint": jnp.zeros((G, P), i32),
        "send_hint2": jnp.zeros((G, P), i32),
        "noop_appended": jnp.zeros((G,), i32),
        "noop_term": jnp.zeros((G,), i32),
        "dropped_readindex": jnp.zeros((G,), i32),
        "lease_served": jnp.zeros((G,), i32),
        "lease_fallback": jnp.zeros((G,), i32),
        "dropped_cc": jnp.zeros((G,), bool),
        "fwd_leader": jnp.zeros((G,), i32),
        "log_full": jnp.zeros((G,), bool),
        "force_probe": jnp.zeros((G, P), bool),
        # event-counter plane accumulators (CTR slots computed elsewhere:
        # commit advances from the step-end commit delta, lease counters
        # shared with the lease plane, read confirmations = ready pops)
        "ctr_elections_started": jnp.zeros((G,), i32),
        "ctr_elections_won": jnp.zeros((G,), i32),
        "ctr_heartbeats_sent": jnp.zeros((G,), i32),
        "ctr_replicate_rejects": jnp.zeros((G,), i32),
    }

    with jax.named_scope("tick"):
        s = _quiesce(s, inbox, ticks)
        s, out = _tick(s, ticks, out)

    # drain inbox via scan: iteration k applies slot k for every group
    def body(carry, slot):
        s, out = carry
        m = {
            "mtype": slot[0],
            "from_slot": slot[1],
            "term": slot[2],
            "log_index": slot[3],
            "log_term": slot[4],
            "commit": slot[5],
            "reject": slot[6].astype(bool),
            "hint": slot[7],
            "hint_high": slot[8],
            "n_entries": slot[9],
            "entry_terms": slot[10],
            "entry_cc": slot[11].astype(bool),
        }
        s, out, resps = _handle_message(s, m, out, cfg)
        return (s, out), resps

    E = cfg.max_entries_per_msg
    slots = (
        jnp.moveaxis(inbox.mtype, 1, 0),
        jnp.moveaxis(inbox.from_slot, 1, 0),
        jnp.moveaxis(inbox.term, 1, 0),
        jnp.moveaxis(inbox.log_index, 1, 0),
        jnp.moveaxis(inbox.log_term, 1, 0),
        jnp.moveaxis(inbox.commit, 1, 0),
        jnp.moveaxis(inbox.reject.astype(i32), 1, 0),
        jnp.moveaxis(inbox.hint, 1, 0),
        jnp.moveaxis(inbox.hint_high, 1, 0),
        jnp.moveaxis(inbox.n_entries, 1, 0),
        jnp.moveaxis(inbox.entry_terms, 1, 0),
        jnp.moveaxis(inbox.entry_cc.astype(i32), 1, 0),
    )
    with jax.named_scope("inbox"):
        (s, out), resps = jax.lax.scan(body, (s, out), slots)
    resps = {k: jnp.moveaxis(v, 0, 1) for k, v in resps.items()}

    with jax.named_scope("commit"):
        # ---- quorum commit (leader lanes), cf. raft.go:859-907 -----------------
        is_leader = s.role == ROLE.LEADER
        nv = _num_voting(s)
        q = _quorum(s)
        masked_match = jnp.where(s.voting, s.match, jnp.iinfo(jnp.int32).max)
        sorted_match = jnp.sort(masked_match, axis=1)  # ascending; non-voting = +inf last
        # k-th smallest with k = nv - q gives the quorum-replicated index
        qpos = jnp.clip(nv - q, 0, P - 1)
        qidx = jnp.take_along_axis(sorted_match, qpos[:, None], axis=1)[:, 0]
        qterm = _term_at(s, qidx)
        can_commit = (
            is_leader & (nv > 0) & (qidx > s.committed) & (qterm == s.term)
        )
        s = s._replace(committed=jnp.where(can_commit, qidx, s.committed))

    with jax.named_scope("replicate_fanout"):
        # ---- replication fan-out ----------------------------------------------
        # invariant: a peer parked for a snapshot un-parks as soon as its match
        # covers the snapshot watermark, regardless of WHICH message moved it
        # (the restore ack can arrive as a ReplicateResp the host already
        # folded, or the watermark can be lowered by the host reconciling the
        # actually-sent snapshot index; cf. remote.go:145-153 respondedTo)
        s = s._replace(
            rstate=jnp.where(
                (s.rstate == RSTATE.SNAPSHOT) & (s.match >= s.snap_sent),
                RSTATE.RETRY,
                s.rstate,
            )
        )
        # send to every lagging, unpaused peer; optimistically advance next for
        # peers in REPLICATE state (pipelining, remote.go progress())
        selfm = _self_mask(s)
        peer_tgt = s.member & ~selfm
        lag = s.next <= s.last_index[:, None]
        # commit advanced this step: also ping up-to-date peers with an empty
        # Replicate so their commit index stays fresh (the reference gets this
        # from broadcastReplicateMessage after tryCommit, raft.go:1675-1677)
        commit_moved = (s.committed != prev_commit)[:, None]
        paused = (s.rstate == RSTATE.WAIT) | (s.rstate == RSTATE.SNAPSHOT)
        # peers whose next has been compacted away need a snapshot (host path)
        compacted = s.next < s.first_index[:, None]
        send = (
            is_leader[:, None]
            & peer_tgt
            & (lag | commit_moved | out["force_probe"])
            & ~paused
            & ~compacted
        )
        need_snap = is_leader[:, None] & peer_tgt & lag & ~paused & compacted & s.ract
        n_send = jnp.clip(s.last_index[:, None] - s.next + 1, 0, E)
        prev_idx = s.next - 1
        W = s.log_term.shape[1]
        prev_term_pp = jnp.where(
            prev_idx == s.first_index[:, None] - 1,
            s.marker_term[:, None],
            jnp.take_along_axis(s.log_term, prev_idx % W, axis=1),
        )
        out["send_flags"] = jnp.where(
            send, out["send_flags"] | SEND_REPLICATE, out["send_flags"]
        )
        out["send_flags"] = jnp.where(
            need_snap, out["send_flags"] | NEED_SNAPSHOT, out["send_flags"]
        )
        s = s._replace(
            snap_sent=jnp.where(need_snap, s.last_index[:, None], s.snap_sent),
            rstate=jnp.where(need_snap, RSTATE.SNAPSHOT, s.rstate),
        )
        send_prev_index = jnp.where(send, prev_idx, 0)
        send_n = jnp.where(send, n_send, 0)
        # optimistic next advance (REPLICATE state); a RETRY probe carrying
        # entries transitions to WAIT until acked (remote.go progress()); empty
        # commit-refresh sends leave flow-control state untouched
        adv = send & (s.rstate == RSTATE.REPLICATE) & (n_send > 0)
        probe = send & (s.rstate == RSTATE.RETRY) & (n_send > 0)
        s = s._replace(
            next=jnp.where(adv, s.next + n_send, s.next),
            rstate=jnp.where(probe, RSTATE.WAIT, s.rstate),
        )
        send_commit = jnp.where(send, s.committed[:, None], 0)
        send_hb_commit = jnp.minimum(s.match, s.committed[:, None])

    with jax.named_scope("readindex_pop"):
        # ---- readindex ready queue pop ----------------------------------------
        # ack bits only ever come from voting peers' HeartbeatResp; +1 counts the
        # leader itself. acks == -1 marks an immediately-ready entry.
        acks = s.ri_acks
        popc = _popcount(acks)
        confirmed = (popc + 1 >= q[:, None]) | (acks == -1)
        live = (jnp.arange(R, dtype=i32)[None, :] < s.ri_count[:, None]) & (
            s.ri_ctx != 0
        )
        confirmed = confirmed & live
        # pop the longest confirmed prefix... any confirmed slot releases all
        # earlier slots (readindex.go:77-116)
        idxs = jnp.arange(R, dtype=i32)[None, :]
        last_conf = jnp.max(jnp.where(confirmed, idxs + 1, 0), axis=1)  # count to pop
        popmask = idxs < last_conf[:, None]
        ready_ctx = jnp.where(popmask, s.ri_ctx, 0)
        ready_ctx2 = jnp.where(popmask, s.ri_ctx2, 0)
        # released entries read at the confirming slot's index
        conf_idx = jnp.max(jnp.where(confirmed, s.ri_index, 0), axis=1)
        ready_index = jnp.where(popmask, jnp.minimum(s.ri_index, conf_idx[:, None]), 0)
        ready_count = last_conf
        # compact the queue
        shift = last_conf
        new_pos = idxs - shift[:, None]
        def shift_left(a, fill):
            take = jnp.clip(idxs + shift[:, None], 0, R - 1)
            v = jnp.take_along_axis(a, take, axis=1)
            return jnp.where(idxs < (s.ri_count - shift)[:, None], v, fill)
        s = s._replace(
            ri_ctx=shift_left(s.ri_ctx, 0),
            ri_ctx2=shift_left(s.ri_ctx2, 0),
            ri_index=shift_left(s.ri_index, 0),
            ri_acks=shift_left(s.ri_acks, 0),
            ri_count=s.ri_count - shift,
        )

    with jax.named_scope("directives"):
        # ---- engine directives -------------------------------------------------
        save_from = jnp.minimum(save_base_floor, s.unsaved_from)
        has_save = s.last_index >= save_from
        out_save_from = jnp.where(has_save & s.active, save_from, 0)
        out_save_to = jnp.where(has_save & s.active, s.last_index, 0)
        s = s._replace(unsaved_from=s.last_index + 1)

        apply_from = s.processed + 1
        apply_to = s.committed
        has_apply = apply_to >= apply_from
        out_apply_from = jnp.where(has_apply & s.active, apply_from, 0)
        out_apply_to = jnp.where(has_apply & s.active, apply_to, 0)
        s = s._replace(processed=jnp.maximum(s.processed, s.committed))
        # entries handed to the engine are applied synchronously by the engine
        # loop this round; mirror the reference's applied cursor via engine
        # notifications (host may override through reconcile).
        s = s._replace(applied=jnp.maximum(s.applied, out_apply_to))

        hard_changed = (
            (s.term != prev_term) | (s.vote != prev_vote) | (s.committed != prev_commit)
        )

        last_term_out = _term_at(s, s.last_index)

    with jax.named_scope("counters"):
        # counter plane assembly, one column per CTR slot. Commit advances
        # are the step-end commit delta (INDEX UNITS — see state.CTR), which
        # folds the leader quorum fold and every follower commit move into
        # the one number that is lockstep-comparable to the scalar core.
        counters = jnp.stack(
            [
                out["ctr_elections_started"],
                out["ctr_elections_won"],
                out["ctr_heartbeats_sent"],
                out["ctr_replicate_rejects"],
                s.committed - prev_commit,
                out["lease_served"],
                out["lease_fallback"],
                ready_count * s.active,
            ],
            axis=1,
        ).astype(jnp.uint32)

    with jax.named_scope("directives"):
        # suppress send directives whose issuing role died mid-step: a lane that
        # was leader during the tick phase but stepped down while draining the
        # inbox must not emit leader traffic stamped with its new term (the
        # scalar core sequences message creation with state changes; here the
        # planes are assembled at step end, so the end-of-step role gates them)
        leader_bits = SEND_REPLICATE | SEND_HEARTBEAT | SEND_TIMEOUT_NOW | NEED_SNAPSHOT
        end_leader = (s.role == ROLE.LEADER)[:, None]
        # the shared vote plane serves both election phases: candidates send
        # REQUEST_VOTE, pre-candidates REQUEST_PREVOTE (type/term selected
        # downstream from the end-of-step role)
        end_cand = (
            (s.role == ROLE.CANDIDATE) | (s.role == ROLE.PRE_CANDIDATE)
        )[:, None]
        flags = out["send_flags"]
        flags = jnp.where(end_leader, flags, flags & ~leader_bits)
        flags = jnp.where(end_cand, flags, flags & ~SEND_VOTE_REQ)
        out["send_flags"] = flags

        output = StepOutput(
            send_flags=out["send_flags"] * s.active[:, None],
            send_prev_index=send_prev_index,
            send_prev_term=jnp.where(send, prev_term_pp, 0),
            send_n_entries=send_n,
            send_commit=send_commit,
            send_hb_commit=send_hb_commit,
            send_hint=out["send_hint"],
            send_hint2=out["send_hint2"],
            vote_last_index=s.last_index,
            vote_last_term=last_term_out,
            resp_type=resps["resp_type"],
            resp_to=resps["resp_to"],
            resp_term=resps["resp_term"],
            resp_log_index=resps["resp_log_index"],
            resp_reject=resps["resp_reject"],
            resp_hint=resps["resp_hint"],
            resp_hint2=resps["resp_hint2"],
            save_from=out_save_from,
            save_to=out_save_to,
            apply_from=out_apply_from,
            apply_to=out_apply_to,
            commit_index=s.committed,
            hard_changed=hard_changed & s.active,
            ready_ctx=ready_ctx,
            ready_ctx2=ready_ctx2,
            ready_index=ready_index,
            ready_count=ready_count * s.active,
            dropped_readindex=out["dropped_readindex"],
            dropped_cc=out["dropped_cc"],
            fwd_leader=out["fwd_leader"],
            noop_appended=out["noop_appended"],
            noop_term=out["noop_term"],
            log_full=out["log_full"],
            prop_base=resps["prop_base"],
            rep_base=resps["rep_base"],
            leader=s.leader,
            term=s.term,
            vote=s.vote,
            role=s.role,
            match=s.match,
            rstate=s.rstate,
            last_index=s.last_index,
            quiesced=s.quiesced,
            lease_round=jnp.where(
                s.lease_on & (s.role == ROLE.LEADER), s.hb_round_tick, 0
            ),
            lease_served=out["lease_served"],
            lease_fallback=out["lease_fallback"],
            lease_ok=(
                s.lease_on & s.clock_ok & (s.role == ROLE.LEADER)
                & (s.tick_count < s.lease_until) & (s.transfer_to == 0)
            ),
            counters=counters,
        )
    return s, output


def _popcount(x):
    return jax.lax.population_count(x.astype(jnp.uint32)).astype(i32)


def _named(fn, name: str):
    """jax names a compiled program after its function (`jit_<name>` in a
    device trace); a functools.partial or a shard_map has no name of its
    own and shows as `jit__unknown`."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@functools.lru_cache(maxsize=None)
def make_step_fn(cfg: KernelConfig, donate: bool = True):
    """Return a jitted step(state, inbox, ticks) -> (state, output).
    Cached per (cfg, donate) so every engine/cluster with the same static
    shapes shares one compiled executable."""
    f = _named(functools.partial(step_batch, cfg=cfg), "step_batch")
    if donate:
        return jax.jit(f, donate_argnums=(0,))
    return jax.jit(f)


# ---------------------------------------------------------------------------
# device-resident multi-step: K protocol steps per kernel launch, with
# co-hosted traffic routed between lanes INSIDE the kernel
# ---------------------------------------------------------------------------


@jax.named_scope("router")
def route_step_output(
    s: RaftTensors,
    out: StepOutput,
    route: jax.Array,
    rdelta: jax.Array,
    cfg: KernelConfig,
) -> Tuple[Inbox, RoutePlan]:
    """Build the NEXT inner step's inbox from this step's outputs by
    routing co-hosted traffic on device (the engine's try_local_deliver
    without the host round trip).

    ``route[g, p]`` is the lane index of the co-hosted replica behind
    peer slot p of lane g (-1 = not device-routable: cross-host, blocked,
    recovering, chaos hook installed); ``rdelta[g, p]`` is the window
    base difference ``base[g] - base[route[g, p]]`` added to every
    index-valued field so the destination reads indexes in ITS device
    units (the host path converts through real indexes the same way).

    Candidates are ordered kind-major (Replicate, RequestVote, Heartbeat,
    TimeoutNow, response plane, forwarded-read responses) then row-major
    — exactly the order the host decode dispatches them in — and a STABLE
    sort by destination lane assigns inbox slots, so per-destination
    arrival order matches the host message-queue path bit for bit. A
    candidate ranked past the destination's K slots is NOT routed (its
    RoutePlan bit stays False) and falls back to the host path, exactly
    like a full receive queue does."""
    G, P = s.member.shape
    K = cfg.inbox_depth
    R = cfg.readindex_depth
    dest, fields, efields = _route_columns(s, out, route, rdelta, cfg)
    nxt, routed = _route_scatter(dest, fields, efields, G, K)
    return nxt, _split_plan(routed, G, P, K, R)


def _route_columns(s: RaftTensors, out: StepOutput, route, rdelta, cfg):
    """The router's candidate planes, flattened kind-major then row-major
    (the host dispatch order). Returns (dest, fields, (entry_terms,
    entry_cc)): ``dest`` is the destination lane per candidate (-1 = not
    a candidate), ``fields`` the ten scalar message columns in Inbox
    staging order, and the entry planes carry Replicate payload metadata
    for the Replicate candidates alone, the first G * P of them.
    Lane indexes in ``route``/``dest`` are GLOBAL — on a sharded mesh a
    local block emits candidates addressed across the whole fleet."""
    G, P = s.member.shape
    K = cfg.inbox_depth
    E = cfg.max_entries_per_msg
    R = cfg.readindex_depth
    flags = out.send_flags
    self_col = s.self_slot[:, None]
    self_gp = jnp.broadcast_to(self_col, (G, P))
    term_gp = jnp.broadcast_to(out.term[:, None], (G, P))
    zero_gp = jnp.zeros((G, P), i32)
    false_gp = jnp.zeros((G, P), bool)
    zero_gk = jnp.zeros((G, K), i32)
    zero_gr = jnp.zeros((G, R), i32)

    has_dest = route >= 0
    rep_want = ((flags & SEND_REPLICATE) != 0) & has_dest
    vote_want = ((flags & SEND_VOTE_REQ) != 0) & has_dest
    hb_want = ((flags & SEND_HEARTBEAT) != 0) & has_dest
    tn_want = ((flags & SEND_TIMEOUT_NOW) != 0) & has_dest
    precand_gp = jnp.broadcast_to(
        (out.role == ROLE.PRE_CANDIDATE)[:, None], (G, P)
    )

    # response plane: destination is the lane behind the replied-to slot.
    # Self-addressed responses are skipped (the host path skips them too)
    # and a below-window REPLICATE_RESP reject (its backoff hint falls
    # under the destination leader's window base) stays host-side: the
    # kernel cannot back off past first_index, only the host catchup path
    # can serve that gap (see VectorEngine._below_window_reject).
    resp_to = jnp.clip(out.resp_to, 0, P - 1)
    resp_dest = jnp.take_along_axis(route, resp_to, axis=1)
    resp_delta = jnp.take_along_axis(rdelta, resp_to, axis=1)
    is_rresp = out.resp_type == MSG.REPLICATE_RESP
    is_hbresp = out.resp_type == MSG.HEARTBEAT_RESP
    below_window = is_rresp & out.resp_reject & (out.resp_hint + resp_delta < 0)
    resp_want = (
        (out.resp_type != MSG.NONE)
        & (resp_dest >= 0)
        & (out.resp_to != self_col)
        & ~below_window
    )

    # confirmed forwarded reads: READ_INDEX_RESP back to the origin slot
    # encoded in the ctx (engine/vector._ctx_origin)
    ridx = jnp.arange(R, dtype=i32)[None, :]
    live = (ridx < out.ready_count[:, None]) & (out.ready_ctx != 0)
    origin = (out.ready_ctx >> 24) - 1
    origin_cl = jnp.clip(origin, 0, P - 1)
    rir_dest = jnp.take_along_axis(route, origin_cl, axis=1)
    rir_delta = jnp.take_along_axis(rdelta, origin_cl, axis=1)
    rir_want = live & (origin >= 0) & (origin != self_col) & (rir_dest >= 0)

    # Replicate entry metadata comes straight from the sender's ring (the
    # host path reads the same (term, is_cc) pairs off the arena entries)
    e_off = jnp.arange(E, dtype=i32)[None, None, :]
    e_live = (e_off < out.send_n_entries[:, :, None]) & rep_want[:, :, None]
    ring_t = _ring_run(s.log_term[:, None, :], out.send_prev_index + 1, E)
    ring_cc = _ring_run(s.log_is_cc[:, None, :], out.send_prev_index + 1, E)
    rep_terms = jnp.where(e_live, ring_t, 0)
    rep_cc = e_live & ring_cc

    # candidate field planes, kind-major (= the host dispatch order)
    kinds = (
        # (want, dest, mtype, from, term, log_index, log_term, commit,
        #  reject, hint, hint2, n_entries)
        (
            rep_want, route, jnp.full((G, P), MSG.REPLICATE, i32), self_gp,
            term_gp, out.send_prev_index + rdelta, out.send_prev_term,
            jnp.maximum(out.send_commit + rdelta, 0), false_gp, zero_gp,
            zero_gp, out.send_n_entries,
        ),
        (
            # the vote plane serves both election phases: a PRE_CANDIDATE
            # lane's requests are REQUEST_PREVOTE at the PROSPECTIVE term
            vote_want, route,
            jnp.where(precand_gp, MSG.REQUEST_PREVOTE, MSG.REQUEST_VOTE),
            self_gp, jnp.where(precand_gp, term_gp + 1, term_gp),
            out.vote_last_index[:, None] + rdelta,
            jnp.broadcast_to(out.vote_last_term[:, None], (G, P)), zero_gp,
            false_gp, out.send_hint, zero_gp, zero_gp,
        ),
        (
            # log_index carries the lease round tag — an opaque tick stamp
            # the follower echoes back verbatim, so NO rdelta translation
            # (0 when leases off, matching the host wire path)
            hb_want, route, jnp.full((G, P), MSG.HEARTBEAT, i32), self_gp,
            term_gp, jnp.broadcast_to(out.lease_round[:, None], (G, P)),
            zero_gp,
            jnp.maximum(out.send_hb_commit + rdelta, 0), false_gp,
            out.send_hint, out.send_hint2, zero_gp,
        ),
        (
            tn_want, route, jnp.full((G, P), MSG.TIMEOUT_NOW, i32), self_gp,
            term_gp, zero_gp, zero_gp, zero_gp, false_gp, zero_gp, zero_gp,
            zero_gp,
        ),
        (
            resp_want, resp_dest, out.resp_type,
            jnp.broadcast_to(self_col, (G, K)),
            out.resp_term,
            # HEARTBEAT_RESP echoes the lease round tag untranslated (an
            # opaque tick stamp, not an index — no resp_delta)
            jnp.where(
                is_rresp,
                out.resp_log_index + resp_delta,
                jnp.where(is_hbresp, out.resp_log_index, 0),
            ),
            zero_gk, zero_gk,
            out.resp_reject
            & (
                is_rresp
                | (out.resp_type == MSG.REQUEST_VOTE_RESP)
                | (out.resp_type == MSG.REQUEST_PREVOTE_RESP)
            ),
            # per-type staging, mirroring _pack_wire: REPLICATE_RESP
            # carries a (translated, clamped) backoff hint, HEARTBEAT_RESP
            # the readindex ctx pair; every other response type carries
            # neither
            jnp.where(
                is_rresp,
                jnp.maximum(out.resp_hint + resp_delta, 0),
                jnp.where(is_hbresp, out.resp_hint, 0),
            ),
            jnp.where(is_hbresp, out.resp_hint2, 0),
            zero_gk,
        ),
        (
            rir_want, rir_dest, jnp.full((G, R), MSG.READ_INDEX_RESP, i32),
            jnp.broadcast_to(self_col, (G, R)),
            jnp.broadcast_to(out.term[:, None], (G, R)),
            out.ready_index + rir_delta, zero_gr, zero_gr,
            jnp.zeros((G, R), bool), out.ready_ctx, out.ready_ctx2, zero_gr,
        ),
    )

    def cat(col):
        return jnp.concatenate([k[col].reshape(-1) for k in kinds])

    dest = jnp.where(cat(0), cat(1), -1)
    fields = tuple(cat(c) for c in range(2, 12))
    # only Replicate carries entries, and its candidates come first
    return dest, fields, (rep_terms.reshape(-1, E), rep_cc.reshape(-1, E))


def _route_segments(P: int, K: int, R: int) -> Tuple[int, ...]:
    """Per-kind candidate counts PER LANE ROW in the flattened kind-major
    layout (rep, vote, hb, tn, resp, rir). A G-lane block contributes
    ``G * seg`` candidates per kind; the sharded router uses this to
    splice per-shard segments back into the global kind-major order."""
    return (P, P, P, P, K, R)


def _route_scatter(dest, fields, efields, G: int, K: int):
    """Stable-sort the flattened candidates by destination lane and
    scatter the first K arrivals per destination into a fresh Inbox.
    Returns (inbox, routed) where ``routed`` is the flat per-candidate
    accepted mask in the ORIGINAL (pre-sort) candidate order.

    ``efields`` are the entry planes of the first ``len(efields[0])``
    candidates (the Replicate kind); every later candidate carries none.

    Every gather and scatter is sized by the inbox (G x K), not by the
    candidates: inbox slot (g, k) takes the k-th candidate of lane g's
    run in the sorted order, which starts where a binary search over
    the sorted keys puts g. Per-candidate gathers were the router's
    cost on the chip, and they grow with G x (4P + K + R)."""
    M = dest.shape[0]
    n_rep = efields[0].shape[0]
    key = jnp.where(dest >= 0, dest, G).astype(i32)
    skey, order = jax.lax.sort(
        (key, jnp.arange(M, dtype=i32)), num_keys=1, is_stable=True
    )
    lanes = jnp.arange(G, dtype=i32)
    start = jnp.searchsorted(skey, lanes, side="left").astype(i32)
    pos = jnp.minimum(start[:, None] + jnp.arange(K, dtype=i32), M - 1)
    ok = skey[pos] == lanes[:, None]  # [G, K]; a short run ends early
    src = jnp.where(ok, order[pos], 0)

    def pick(default, vals):
        return jnp.where(ok, vals[src], default)

    rep = ok & (src < n_rep)
    rsrc = jnp.where(rep, src, 0)
    nxt = Inbox(
        mtype=pick(MSG.NONE, fields[0]),
        from_slot=pick(0, fields[1]),
        term=pick(0, fields[2]),
        log_index=pick(0, fields[3]),
        log_term=pick(0, fields[4]),
        commit=pick(0, fields[5]),
        reject=pick(False, fields[6]),
        hint=pick(0, fields[7]),
        hint_high=pick(0, fields[8]),
        n_entries=pick(0, fields[9]),
        entry_terms=jnp.where(rep[..., None], efields[0][rsrc], 0),
        entry_cc=rep[..., None] & efields[1][rsrc],
    )
    routed = jnp.zeros((M,), bool).at[jnp.where(ok, src, M)].set(
        True, mode="drop"
    )
    return nxt, routed


def _split_plan(routed, G: int, P: int, K: int, R: int) -> RoutePlan:
    """Reshape the flat accepted mask back into per-kind RoutePlan planes
    (inverse of the kind-major flattening in _route_columns)."""
    gp, gk = G * P, G * K
    return RoutePlan(
        rep=routed[0:gp].reshape(G, P),
        vote=routed[gp : 2 * gp].reshape(G, P),
        hb=routed[2 * gp : 3 * gp].reshape(G, P),
        tn=routed[3 * gp : 4 * gp].reshape(G, P),
        resp=routed[4 * gp : 4 * gp + gk].reshape(G, K),
        rir=routed[4 * gp + gk :].reshape(G, R),
    )


def multi_step_batch(
    s: RaftTensors,
    inbox: Inbox,
    ticks: jax.Array,
    resid: Inbox,
    route: jax.Array,
    rdelta: jax.Array,
    cfg: KernelConfig,
    steps: int,
):
    """``steps`` protocol steps in ONE kernel launch (lax.scan over the
    step_batch body), with co-hosted traffic routed between lanes inside
    the kernel (route_step_output) — zero host Message objects for
    shared-core traffic, one dispatch + one fetch per super-step.

    ``steps`` MUST be a static Python int (make_multi_step_fn closes over
    it); a traced value here would rebuild the scan per distinct K.

    Inner step 0 consumes ``resid`` (the previous super-step's last inner
    step's routed messages, carried device-resident) merged with the
    host-packed ``inbox`` — the host packs its rows at slots >=
    resid_count, so the merge is a disjoint elementwise select. Host
    ticks apply to inner step 0 only: one engine iteration charges
    timers once whether it runs 1 or K protocol steps (tick counts come
    from the host clock, so total tick throughput is unchanged).

    Returns (state, stacked per-step StepOutput, stacked per-step
    RoutePlan, residual Inbox, residual per-lane occupancy)."""
    s, (outs, plans), resid_out = _scan_steps(
        s, inbox, ticks, resid, route, rdelta, cfg, steps,
        lambda out, plan, nxt: (out, plan),
    )
    return s, outs, plans, resid_out, _occupancy(resid_out)


def _occupancy(inbox: Inbox) -> jax.Array:
    """The rows each lane holds in an inbox."""
    return jnp.sum(inbox.mtype != MSG.NONE, axis=1).astype(i32)


def _scan_steps(s, inbox, ticks, resid, route, rdelta, cfg, steps, emit):
    """multi_step_batch's scan: the residual merged under the host's
    inbox, then ``steps`` routed steps, each handing ``emit(out, plan,
    nxt)`` to the stacked result. Returns (state, stacked emits,
    residual Inbox)."""
    occ = resid.mtype != MSG.NONE

    def mg(r, h):
        m = occ
        while m.ndim < r.ndim:
            m = m[..., None]
        return jnp.where(m, r, h)

    inbox0 = jax.tree.map(mg, resid, inbox)

    def body(carry, _):
        st, ibx, tks = carry
        st, out = step_batch(st, ibx, tks, cfg)
        nxt, plan = route_step_output(st, out, route, rdelta, cfg)
        return (st, nxt, jnp.zeros_like(tks)), emit(out, plan, nxt)

    (s, resid_out, _), emitted = jax.lax.scan(
        body, (s, inbox0, ticks), None, length=steps
    )
    return s, emitted, resid_out


@functools.lru_cache(maxsize=None)
def make_multi_step_fn(cfg: KernelConfig, steps: int, donate: bool = True):
    """Jitted multi_step(state, inbox, ticks, resid, route, rdelta) ->
    (state, outs, plans, resid, resid_count). ``steps`` is baked into
    the executable as a static scan length (K is a compile-time
    constant by design: the recompilation-hazard rules treat a traced
    K as a finding). Cached per (cfg, steps, donate)."""
    f = _named(
        functools.partial(multi_step_batch, cfg=cfg, steps=steps),
        "multi_step_batch",
    )
    if donate:
        return jax.jit(f, donate_argnums=(0, 3))
    return jax.jit(f)


def _launch_in_specs(cfg: KernelConfig):
    G, P = cfg.groups, cfg.peers
    gp = jax.ShapeDtypeStruct((G, P), i32)
    return (
        jax.eval_shape(lambda: make_empty_inbox(cfg)),
        jax.ShapeDtypeStruct((G,), i32), gp, gp,
    )


@functools.lru_cache(maxsize=None)
def launch_in_slabs(cfg: KernelConfig) -> Slabs:
    """What the host puts for a packed K-step launch: (inbox, ticks,
    route, rdelta) as one int32 and one bool slab of lanes by columns."""
    return Slabs(_launch_in_specs(cfg), lead=1)


def _step_fetched(out, plan, nxt):
    """What the host fetches of one inner step of a packed launch: its
    StepOutput, its RoutePlan and the residual occupancy after it (the
    last step's is the launch's)."""
    return out, plan, _occupancy(nxt)


@functools.lru_cache(maxsize=None)
def launch_out_slabs(cfg: KernelConfig) -> Slabs:
    """What the host fetches from a packed K-step launch: each inner
    step's `_step_fetched` as one flat int32 and one flat bool row, so
    the launch's slabs are [K, columns] and a step's plane is contiguous
    on the host. Shaped by tracing one step abstractly."""
    inbox, ticks, route, rdelta = _launch_in_specs(cfg)
    _s, fetched, _r = jax.eval_shape(
        functools.partial(
            _scan_steps, cfg=cfg, steps=1, emit=_step_fetched
        ),
        jax.eval_shape(lambda: init_state(cfg)),
        inbox, ticks, inbox, route, rdelta,
    )
    return Slabs(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), fetched
    ))


def packed_multi_step_batch(
    s: RaftTensors,
    ints: jax.Array,
    bools: jax.Array,
    resid: Inbox,
    cfg: KernelConfig,
    steps: int,
):
    """multi_step_batch with the host's planes put as two slabs and its
    outputs fetched as two (ops/slab.py), inside this one program: the
    slabs are cut into the inbox, ticks, route and rdelta at the top,
    and each inner step's outputs are packed into its row of the output
    slabs as the scan emits them, so no plane is copied after the scan.
    State and residual inbox stay device-resident planes. Returns
    (state, int32 slab, bool slab, residual Inbox)."""
    inbox, ticks, route, rdelta = launch_in_slabs(cfg).unpack(ints, bools)
    rows = launch_out_slabs(cfg)
    s, (out_ints, out_bools), resid = _scan_steps(
        s, inbox, ticks, resid, route, rdelta, cfg, steps,
        lambda out, plan, nxt: rows.pack(_step_fetched(out, plan, nxt)),
    )
    return s, out_ints, out_bools, resid


@functools.lru_cache(maxsize=None)
def make_packed_multi_step_fn(
    cfg: KernelConfig, steps: int, donate: bool = True
):
    """Jitted packed_multi_step_batch(state, ints, bools, resid) ->
    (state, ints, bools, resid), the one-chip K-step launch. It keeps
    multi_step_batch's program name, so a device trace finds the same
    program. Cached per (cfg, steps, donate)."""
    f = _named(
        functools.partial(packed_multi_step_batch, cfg=cfg, steps=steps),
        "multi_step_batch",
    )
    if donate:
        return jax.jit(f, donate_argnums=(0, 3))
    return jax.jit(f)


# ---------------------------------------------------------------------------
# sharded multi-step: the K-step kernel over an N-device mesh, with
# cross-shard lane traffic routed device-to-device between inner steps
# ---------------------------------------------------------------------------


@jax.named_scope("router")
def _shard_route(
    s: RaftTensors,
    out: StepOutput,
    route: jax.Array,
    rdelta: jax.Array,
    cfg: KernelConfig,
    axis_name: str,
    n_shards: int,
) -> Tuple[Inbox, RoutePlan]:
    """route_step_output for a LOCAL shard block running under shard_map:
    every shard's candidate planes are all-gathered across the mesh, each
    shard replays the identical global stable-sort scatter, then keeps
    only its own rows of the resulting inbox and its own candidates' bits
    of the plan.

    ``route`` holds GLOBAL lane indexes, so a candidate whose destination
    lane lives on another shard lands in that shard's inbox rows without
    touching the host. Replaying the global scatter on every shard is
    redundant compute but buys determinism: all shards agree on arrival
    order by construction, so the result is byte-identical to the
    unsharded router on the concatenated state."""
    Gl, P = s.member.shape
    K = cfg.inbox_depth
    R = cfg.readindex_depth
    E = cfg.max_entries_per_msg
    n = n_shards
    G = n * Gl
    dest, fields, efields = _route_columns(s, out, route, rdelta, cfg)

    # pack dest + the 10 scalar columns + the 2E entry columns into one
    # i32 slab so the cross-shard exchange is a single transfer
    Ml = dest.shape[0]
    cols = [dest] + [f.astype(i32) for f in fields]
    slab = jnp.concatenate([jnp.stack(cols)] + [
        jnp.pad(ef.astype(i32).T, ((0, 0), (0, Ml - ef.shape[0])))
        for ef in efields
    ])  # (C, Ml): dest, 10 scalar rows, then E entry_terms + E entry_cc
    # rows, filled over the Replicate candidates (the first Gl * P)
    g = jax.lax.all_gather(slab, axis_name, axis=0, tiled=False)  # (n, C, Ml)

    # splice per-shard segments back into the GLOBAL kind-major layout:
    # within one kind, shard-major == global row-major because shards
    # hold contiguous lane blocks
    segs = _route_segments(P, K, R)
    parts, off = [], 0
    for seg in segs:
        L = Gl * seg
        parts.append(jnp.swapaxes(g[:, :, off : off + L], 0, 1).reshape(
            g.shape[1], n * L
        ))
        off += L
    gcols = jnp.concatenate(parts, axis=1)  # (C, Mg)
    gdest = gcols[0]
    gfields = list(gcols[1 : 11])
    gfields[6] = gfields[6].astype(bool)  # reject
    ge_terms = gcols[11 : 11 + E, : G * P].T  # the Replicate kind's
    ge_cc = gcols[11 + E : 11 + 2 * E, : G * P].T.astype(bool)

    nxt_g, routed_g = _route_scatter(
        gdest, tuple(gfields), (ge_terms, ge_cc), G, K
    )

    # keep this shard's slice: inbox rows by lane block, plan bits by
    # per-kind candidate block
    my = jax.lax.axis_index(axis_name)
    nxt = jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, my * Gl, Gl, 0), nxt_g
    )
    lparts, goff = [], 0
    for seg in segs:
        L = Gl * seg
        lparts.append(jax.lax.dynamic_slice(routed_g, (goff + my * L,), (L,)))
        goff += n * L
    routed = jnp.concatenate(lparts)
    return nxt, _split_plan(routed, Gl, P, K, R)


def sharded_multi_step_batch(
    s: RaftTensors,
    inbox: Inbox,
    ticks: jax.Array,
    resid: Inbox,
    route: jax.Array,
    rdelta: jax.Array,
    cfg: KernelConfig,
    steps: int,
    axis_name: str,
    n_shards: int,
):
    """multi_step_batch on a LOCAL shard block: step_batch is lane-local
    (every shape derives from the arrays, never from cfg.groups), so it
    runs unchanged on the block; only the inter-step router needs the
    cross-shard exchange. Same contract and same results as the
    unsharded kernel on the concatenated state."""
    occ = resid.mtype != MSG.NONE

    def mg(r, h):
        m = occ
        while m.ndim < r.ndim:
            m = m[..., None]
        return jnp.where(m, r, h)

    inbox0 = jax.tree.map(mg, resid, inbox)

    def body(carry, _):
        st, ibx, tks = carry
        st, out = step_batch(st, ibx, tks, cfg)
        nxt, plan = _shard_route(
            st, out, route, rdelta, cfg, axis_name, n_shards
        )
        return (st, nxt, jnp.zeros_like(tks)), (out, plan)

    (s, resid_out, _), (outs, plans) = jax.lax.scan(
        body, (s, inbox0, ticks), None, length=steps
    )
    resid_count = jnp.sum(resid_out.mtype != MSG.NONE, axis=1).astype(i32)
    return s, outs, plans, resid_out, resid_count


@functools.lru_cache(maxsize=None)
def make_sharded_multi_step_fn(
    cfg: KernelConfig, steps: int, mesh, donate: bool = True
):
    """Jitted sharded multi_step(state, inbox, ticks, resid, route,
    rdelta) -> (state, outs, plans, resid, resid_count) with every lane
    axis sharded over ``mesh``'s single "groups" axis via shard_map.
    cfg.groups must be a multiple of the mesh size (the engine pads).
    Cached per (cfg, steps, mesh, donate) — jax.sharding.Mesh hashes by
    device set + axis names, so engines on the same mesh share the
    executable exactly like the unsharded factories."""
    from jax.sharding import NamedSharding, PartitionSpec

    axis = mesh.axis_names[0]
    n = mesh.devices.size
    body = functools.partial(
        sharded_multi_step_batch,
        cfg=cfg, steps=steps, axis_name=axis, n_shards=n,
    )
    lane = PartitionSpec(axis)
    step_lane = PartitionSpec(None, axis)  # (K, G, ...) stacked outputs
    sm = _named(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(lane,) * 6,
            out_specs=(lane, step_lane, step_lane, lane, lane),
            check_vma=False,
        ),
        "sharded_multi_step_batch",
    )
    in_sh = NamedSharding(mesh, lane)
    out_sh = NamedSharding(mesh, step_lane)
    kw = dict(
        in_shardings=(in_sh,) * 6,
        out_shardings=(in_sh, out_sh, out_sh, in_sh, in_sh),
    )
    if donate:
        return jax.jit(sm, donate_argnums=(0, 3), **kw)
    return jax.jit(sm, **kw)
