"""Device-resident multi-step engine: K protocol steps per kernel launch.

Three layers of coverage (test_fanout_columnar.py style — every fast path
is compared against a straightforward per-element reference):

  1. route_step_output fuzz — the kernel's on-device co-hosted routing
     (stable-sort slot assignment, per-type field translation, overflow
     fallback) must match a per-element numpy reference that mirrors the
     host path's dispatch order and _pack_wire's per-type staging,
     across randomized StepOutputs, routes and window-base deltas.

  2. super-step differential — multi_step_batch over K inner steps must
     produce BYTE-IDENTICAL protocol state, per-step output planes (the
     send set and save directives), route plans and residual inbox to K
     sequential step_batch calls glued by the reference router, across
     seeded traffic that includes an election completing mid-window, a
     leader change mid-window and a config-change entry committing
     mid-window.

  3. live engine e2e at steps_per_sync=4 — a 3-replica shared-core
     cluster elects, commits, serves forwarded reads, moves ZERO host
     Message objects for co-hosted traffic, and (the `-m perf` gate at
     K>1) performs zero out-of-seam device syncs with a measured
     steps-per-sync ratio of K and no steady-state retraces.
"""
from __future__ import annotations

import os
import random
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dragonboat_tpu.ops.kernel import (
    make_multi_step_fn,
    make_step_fn,
    route_step_output,
    step_batch,
)
from dragonboat_tpu.ops.state import (
    CTR,
    MSG,
    SEND_HEARTBEAT,
    SEND_REPLICATE,
    SEND_TIMEOUT_NOW,
    SEND_VOTE_REQ,
    Inbox,
    KernelConfig,
    StepOutput,
    configure_group,
    init_state,
    make_empty_inbox,
)

KCFG = KernelConfig(
    groups=6, peers=4, log_window=32, inbox_depth=4,
    max_entries_per_msg=4, readindex_depth=4,
)


# ---------------------------------------------------------------------------
# per-element reference router (mirrors host dispatch order + _pack_wire)
# ---------------------------------------------------------------------------


def _empty_inbox_np(cfg):
    G, K, E = cfg.groups, cfg.inbox_depth, cfg.max_entries_per_msg
    return {
        "mtype": np.full((G, K), MSG.NONE, np.int32),
        "from_slot": np.zeros((G, K), np.int32),
        "term": np.zeros((G, K), np.int32),
        "log_index": np.zeros((G, K), np.int32),
        "log_term": np.zeros((G, K), np.int32),
        "commit": np.zeros((G, K), np.int32),
        "reject": np.zeros((G, K), bool),
        "hint": np.zeros((G, K), np.int32),
        "hint_high": np.zeros((G, K), np.int32),
        "n_entries": np.zeros((G, K), np.int32),
        "entry_terms": np.zeros((G, K, E), np.int32),
        "entry_cc": np.zeros((G, K, E), bool),
    }


def _ref_route(s, o, route, rdelta, cfg):
    """Reference routing: candidates in the host decode's dispatch order
    (Replicates, votes, heartbeats, TimeoutNow, response plane,
    forwarded-read responses; row-major within each kind), FIFO'd into
    each destination lane's K inbox slots with _pack_wire's per-type
    field staging. Returns (next inbox planes, routed masks)."""
    G, P = route.shape
    K = cfg.inbox_depth
    R = cfg.readindex_depth
    W = cfg.log_window
    nxt = _empty_inbox_np(cfg)
    counts = [0] * G
    masks = {
        "rep": np.zeros((G, P), bool),
        "vote": np.zeros((G, P), bool),
        "hb": np.zeros((G, P), bool),
        "tn": np.zeros((G, P), bool),
        "resp": np.zeros((G, K), bool),
        "rir": np.zeros((G, R), bool),
    }
    self_slot = np.asarray(s.self_slot)
    log_term = np.asarray(s.log_term)
    log_cc = np.asarray(s.log_is_cc)
    term = o["term"]

    def stage(d, mtype, from_slot, term, log_index=0, log_term_=0,
              commit=0, reject=False, hint=0, hint_high=0, n_entries=0,
              entry_terms=(), entry_cc=()):
        k = counts[d]
        if k >= K:
            return False
        counts[d] = k + 1
        nxt["mtype"][d, k] = mtype
        nxt["from_slot"][d, k] = from_slot
        nxt["term"][d, k] = term
        nxt["log_index"][d, k] = log_index
        nxt["log_term"][d, k] = log_term_
        nxt["commit"][d, k] = commit
        nxt["reject"][d, k] = reject
        nxt["hint"][d, k] = hint
        nxt["hint_high"][d, k] = hint_high
        nxt["n_entries"][d, k] = n_entries
        for i, t in enumerate(entry_terms):
            nxt["entry_terms"][d, k, i] = t
        for i, c in enumerate(entry_cc):
            nxt["entry_cc"][d, k, i] = c
        return True

    flags = o["send_flags"]
    for g in range(G):
        for p in range(P):
            d = route[g, p]
            if d < 0 or not (flags[g, p] & SEND_REPLICATE):
                continue
            delta = int(rdelta[g, p])
            prev = int(o["send_prev_index"][g, p])
            n = int(o["send_n_entries"][g, p])
            terms = [int(log_term[g, (prev + 1 + i) % W]) for i in range(n)]
            ccs = [bool(log_cc[g, (prev + 1 + i) % W]) for i in range(n)]
            if stage(
                d, MSG.REPLICATE, int(self_slot[g]), int(term[g]),
                log_index=prev + delta,
                log_term_=int(o["send_prev_term"][g, p]),
                commit=max(int(o["send_commit"][g, p]) + delta, 0),
                n_entries=n, entry_terms=terms, entry_cc=ccs,
            ):
                masks["rep"][g, p] = True
    for g in range(G):
        for p in range(P):
            d = route[g, p]
            if d < 0 or not (flags[g, p] & SEND_VOTE_REQ):
                continue
            if stage(
                d, MSG.REQUEST_VOTE, int(self_slot[g]), int(term[g]),
                log_index=int(o["vote_last_index"][g]) + int(rdelta[g, p]),
                log_term_=int(o["vote_last_term"][g]),
                hint=int(o["send_hint"][g, p]),
            ):
                masks["vote"][g, p] = True
    for g in range(G):
        for p in range(P):
            d = route[g, p]
            if d < 0 or not (flags[g, p] & SEND_HEARTBEAT):
                continue
            if stage(
                d, MSG.HEARTBEAT, int(self_slot[g]), int(term[g]),
                # the lease round tag rides log_index UNTRANSLATED (an
                # opaque tick stamp, not an index — no rdelta)
                log_index=int(o["lease_round"][g]),
                commit=max(
                    int(o["send_hb_commit"][g, p]) + int(rdelta[g, p]), 0
                ),
                hint=int(o["send_hint"][g, p]),
                hint_high=int(o["send_hint2"][g, p]),
            ):
                masks["hb"][g, p] = True
    for g in range(G):
        for p in range(P):
            d = route[g, p]
            if d < 0 or not (flags[g, p] & SEND_TIMEOUT_NOW):
                continue
            if stage(d, MSG.TIMEOUT_NOW, int(self_slot[g]), int(term[g])):
                masks["tn"][g, p] = True
    for g in range(G):
        for k in range(K):
            t = int(o["resp_type"][g, k])
            if t == MSG.NONE:
                continue
            to = int(o["resp_to"][g, k])
            if to < 0 or to >= P or to == int(self_slot[g]):
                continue
            d = route[g, to]
            if d < 0:
                continue
            delta = int(rdelta[g, to])
            rej = bool(o["resp_reject"][g, k])
            if t == MSG.REPLICATE_RESP:
                if rej and int(o["resp_hint"][g, k]) + delta < 0:
                    continue  # below-window reject stays host-side
                ok = stage(
                    d, t, int(self_slot[g]), int(o["resp_term"][g, k]),
                    log_index=int(o["resp_log_index"][g, k]) + delta,
                    reject=rej,
                    hint=max(int(o["resp_hint"][g, k]) + delta, 0),
                )
            elif t == MSG.REQUEST_VOTE_RESP:
                ok = stage(
                    d, t, int(self_slot[g]), int(o["resp_term"][g, k]),
                    reject=rej,
                )
            elif t == MSG.HEARTBEAT_RESP:
                ok = stage(
                    d, t, int(self_slot[g]), int(o["resp_term"][g, k]),
                    # echoes the lease round tag untranslated (no delta)
                    log_index=int(o["resp_log_index"][g, k]),
                    hint=int(o["resp_hint"][g, k]),
                    hint_high=int(o["resp_hint2"][g, k]),
                )
            else:  # NOOP
                ok = stage(
                    d, t, int(self_slot[g]), int(o["resp_term"][g, k])
                )
            if ok:
                masks["resp"][g, k] = True
    for g in range(G):
        for r in range(int(o["ready_count"][g])):
            ctx = int(o["ready_ctx"][g, r])
            if ctx == 0:
                continue
            origin = (ctx >> 24) - 1
            if origin < 0 or origin == int(self_slot[g]) or origin >= P:
                continue
            d = route[g, origin]
            if d < 0:
                continue
            if stage(
                d, MSG.READ_INDEX_RESP, int(self_slot[g]), int(term[g]),
                log_index=int(o["ready_index"][g, r]) + int(rdelta[g, origin]),
                hint=ctx, hint_high=int(o["ready_ctx2"][g, r]),
            ):
                masks["rir"][g, r] = True
    return nxt, masks


# ---------------------------------------------------------------------------
# 1. route_step_output fuzz vs the reference
# ---------------------------------------------------------------------------


def _rng_i32(rng, shape, lo, hi):
    n = int(np.prod(shape))
    return np.asarray(
        [rng.randint(lo, hi) for _ in range(n)], np.int32
    ).reshape(shape)


def _random_state_and_output(rng):
    G, P, K = KCFG.groups, KCFG.peers, KCFG.inbox_depth
    R, E, W = KCFG.readindex_depth, KCFG.max_entries_per_msg, KCFG.log_window
    s = init_state(KCFG)
    s = s._replace(
        self_slot=jnp.asarray(_rng_i32(rng, (G,), 0, P - 1)),
        log_term=jnp.asarray(_rng_i32(rng, (G, W), 1, 5)),
        log_is_cc=jnp.asarray(_rng_i32(rng, (G, W), 0, 1).astype(bool)),
    )
    z = dict.fromkeys(StepOutput._fields)
    flag_choices = (
        0, 0, SEND_REPLICATE, SEND_HEARTBEAT, SEND_VOTE_REQ,
        SEND_TIMEOUT_NOW, SEND_REPLICATE | SEND_HEARTBEAT,
        SEND_VOTE_REQ | SEND_TIMEOUT_NOW,
    )
    resp_choices = (
        int(MSG.NONE), int(MSG.NONE), int(MSG.REPLICATE_RESP),
        int(MSG.REQUEST_VOTE_RESP), int(MSG.HEARTBEAT_RESP), int(MSG.NOOP),
    )
    flags = np.asarray(
        [[rng.choice(flag_choices) for _ in range(P)] for _ in range(G)],
        np.int32,
    )
    resp_type = np.asarray(
        [[rng.choice(resp_choices) for _ in range(K)] for _ in range(G)],
        np.int32,
    )
    ready_count = _rng_i32(rng, (G,), 0, R)
    ready_ctx = np.asarray(
        [
            [
                rng.choice([0, ((rng.randint(1, P)) << 24) | rng.randint(0, 99)])
                for _ in range(R)
            ]
            for _ in range(G)
        ],
        np.int32,
    )
    o = dict(
        send_flags=flags,
        send_prev_index=_rng_i32(rng, (G, P), 0, W - E - 2),
        send_prev_term=_rng_i32(rng, (G, P), 0, 5),
        send_n_entries=_rng_i32(rng, (G, P), 0, E),
        send_commit=_rng_i32(rng, (G, P), 0, W - 2),
        send_hb_commit=_rng_i32(rng, (G, P), 0, W - 2),
        send_hint=_rng_i32(rng, (G, P), 0, 1 << 20),
        send_hint2=_rng_i32(rng, (G, P), 0, 1 << 20),
        vote_last_index=_rng_i32(rng, (G,), 0, W - 2),
        vote_last_term=_rng_i32(rng, (G,), 0, 5),
        term=_rng_i32(rng, (G,), 1, 6),
        resp_type=resp_type,
        resp_to=_rng_i32(rng, (G, K), 0, P - 1),
        resp_term=_rng_i32(rng, (G, K), 1, 6),
        resp_log_index=_rng_i32(rng, (G, K), 0, W - 2),
        resp_reject=_rng_i32(rng, (G, K), 0, 1).astype(bool),
        resp_hint=_rng_i32(rng, (G, K), 0, W - 2),
        resp_hint2=_rng_i32(rng, (G, K), 0, 1 << 20),
        ready_count=ready_count,
        ready_ctx=ready_ctx,
        ready_ctx2=_rng_i32(rng, (G, R), 0, 1 << 20),
        ready_index=_rng_i32(rng, (G, R), 0, W - 2),
        # opaque lease round tag: rides heartbeat log_index untranslated
        lease_round=_rng_i32(rng, (G,), 0, 1 << 16),
    )
    for f in StepOutput._fields:
        if z[f] is None and f not in o:
            # planes the router never reads: zero-filled with the right
            # shape so the NamedTuple constructs
            shape = {
                "save_from": (KCFG.groups,), "save_to": (KCFG.groups,),
                "apply_from": (KCFG.groups,), "apply_to": (KCFG.groups,),
                "commit_index": (KCFG.groups,),
                "hard_changed": (KCFG.groups,),
                "dropped_readindex": (KCFG.groups,),
                "dropped_cc": (KCFG.groups,),
                "fwd_leader": (KCFG.groups,),
                "noop_appended": (KCFG.groups,),
                "noop_term": (KCFG.groups,),
                "log_full": (KCFG.groups,),
                "prop_base": (KCFG.groups, K),
                "rep_base": (KCFG.groups, K),
                "leader": (KCFG.groups,), "vote": (KCFG.groups,),
                "role": (KCFG.groups,),
                "match": (KCFG.groups, P), "rstate": (KCFG.groups, P),
                "last_index": (KCFG.groups,),
                "quiesced": (KCFG.groups,),
                "lease_round": (KCFG.groups,),
                "lease_ok": (KCFG.groups,),
                "lease_served": (KCFG.groups,),
                "lease_fallback": (KCFG.groups,),
                "counters": (KCFG.groups, CTR.COUNT),
            }[f]
            o[f] = np.zeros(shape, np.int32)
    out = StepOutput(**{f: jnp.asarray(o[f]) for f in StepOutput._fields})
    return s, o, out


@pytest.mark.parametrize("seed", range(8))
def test_route_matches_reference(seed):
    rng = random.Random(4000 + seed)
    G, P = KCFG.groups, KCFG.peers
    s, o_np, out = _random_state_and_output(rng)
    route = np.full((G, P), -1, np.int32)
    rdelta = np.zeros((G, P), np.int32)
    self_slot = np.asarray(s.self_slot)
    for g in range(G):
        for p in range(P):
            if p == self_slot[g]:
                continue
            if rng.random() < 0.6:
                route[g, p] = rng.randrange(G)
                rdelta[g, p] = rng.choice([0, 0, 0, 2, -2, -40])
    nxt, plan = route_step_output(
        s, out, jnp.asarray(route), jnp.asarray(rdelta), KCFG
    )
    nxt = jax.device_get(nxt)._asdict()
    plan = {k: np.asarray(v) for k, v in jax.device_get(plan)._asdict().items()}
    ref_nxt, ref_masks = _ref_route(s, o_np, route, rdelta, KCFG)
    for k in ref_masks:
        assert np.array_equal(plan[k], ref_masks[k]), (seed, k)
    for k in ref_nxt:
        assert np.array_equal(np.asarray(nxt[k]), ref_nxt[k]), (seed, k)
    # the trial must exercise the router
    assert sum(int(m.sum()) for m in ref_masks.values()) > 0


# ---------------------------------------------------------------------------
# 2. super-step differential: multi_step_batch vs K sequential steps
# ---------------------------------------------------------------------------


def _cluster_state():
    """3 co-hosted replicas of cluster A on lanes 0/1/2 (slots 0/1/2),
    plus a single-voter lane 3 (different cluster: never routed) and a
    partial cluster whose third replica is 'cross-host' (lane 4 routes to
    lane 5 but slot 2 routes nowhere)."""
    s = init_state(KCFG)
    for g, slot in ((0, 0), (1, 1), (2, 2)):
        s = configure_group(
            s, g, slot, (0, 1, 2), election_timeout=10, heartbeat_timeout=2
        )
    s = configure_group(s, 3, 0, (0,), election_timeout=10)
    for g, slot in ((4, 0), (5, 1)):
        s = configure_group(
            s, g, slot, (0, 1, 2), election_timeout=10, heartbeat_timeout=2
        )
    G, P = KCFG.groups, KCFG.peers
    route = np.full((G, P), -1, np.int32)
    for g, slot in ((0, 0), (1, 1), (2, 2)):
        for p, pg in ((0, 0), (1, 1), (2, 2)):
            if pg != g:
                route[g, p] = pg
    route[4, 1] = 5
    route[5, 0] = 4  # slot 2 of lanes 4/5 is cross-host: stays -1
    rdelta = np.zeros((G, P), np.int32)
    return s, route, rdelta


def _merge_inbox(resid, host):
    out = {}
    occ = resid["mtype"] != MSG.NONE
    for k in resid:
        m = occ
        while m.ndim < resid[k].ndim:
            m = m[..., None]
        out[k] = np.where(m, resid[k], host[k])
    return out


def _jnp_inbox(planes):
    return Inbox(**{k: jnp.asarray(v) for k, v in planes.items()})


def _host_events(window, counts):
    """Seeded host events per super-step boundary, placed at the first
    free slot after the residual rows (exactly like _pack). Scenario:
    window 0 elects lane 0; window 1 proposes (incl. a config-change
    entry that commits MID-window via routed replication); window 2
    campaigns lane 1 — a leader change whose vote handshake and
    step-down land mid-window."""
    host = _empty_inbox_np(KCFG)

    def put(g, **fields):
        k = counts[g]
        assert k < KCFG.inbox_depth, "scenario overflowed the inbox"
        counts[g] += 1
        for name, v in fields.items():
            if name in ("entry_terms", "entry_cc"):
                for i, x in enumerate(v):
                    host[name][g, k, i] = x
            else:
                host[name][g, k] = v

    if window == 0:
        put(0, mtype=MSG.ELECTION)
        put(3, mtype=MSG.ELECTION)
        put(4, mtype=MSG.ELECTION)
    elif window == 1:
        # lane 0 is leader of cluster A by now: a 2-entry proposal and a
        # lone config-change proposal (the host invariant packs ccs alone)
        put(0, mtype=MSG.PROPOSE, from_slot=0, n_entries=2)
        put(
            0, mtype=MSG.PROPOSE, from_slot=0, n_entries=1,
            entry_cc=(True,),
        )
        put(3, mtype=MSG.PROPOSE, from_slot=0, n_entries=3)
    elif window == 2:
        put(1, mtype=MSG.ELECTION)  # leader change mid-window
    elif window == 3:
        # the NEW leader serves proposals after the mid-window change
        put(1, mtype=MSG.PROPOSE, from_slot=1, n_entries=1)
    return host


def _np_tree(x):
    return jax.tree.map(np.asarray, jax.device_get(x))


def test_superstep_differential():
    """A K-step super-step must be byte-identical to K sequential
    one-step kernel calls glued by the reference router: final protocol
    state, every per-step output plane (send set + save directives),
    the route plans and the carried residual inbox."""
    steps = 4
    windows = 4
    G = KCFG.groups
    s_multi, route, rdelta = _cluster_state()
    s_seq = jax.tree.map(lambda x: x, s_multi)  # same initial values
    multi = make_multi_step_fn(KCFG, steps, donate=False)
    step = make_step_fn(KCFG, donate=False)
    route_j, rdelta_j = jnp.asarray(route), jnp.asarray(rdelta)
    ticks = jnp.zeros((G,), jnp.int32)

    resid_np = _empty_inbox_np(KCFG)  # seq side's carried residual
    resid_multi = make_empty_inbox(KCFG)
    for window in range(windows):
        counts = [
            int((resid_np["mtype"][g] != MSG.NONE).sum()) for g in range(G)
        ]
        host = _host_events(window, counts)
        # ---- multi path: one kernel launch -------------------------------
        s_multi, outs, plans, resid_multi, rc = multi(
            s_multi, _jnp_inbox(host), ticks, resid_multi, route_j, rdelta_j
        )
        outs = _np_tree(outs)._asdict()
        plans = _np_tree(plans)._asdict()
        rc = np.asarray(jax.device_get(rc))
        # ---- seq path: K steps + reference routing -----------------------
        inbox = _merge_inbox(resid_np, host)
        for t in range(steps):
            tk = ticks  # all-zero either way; ticks enter step 0 only
            s_seq, out = step(s_seq, _jnp_inbox(inbox), tk)
            o = _np_tree(out)._asdict()
            nxt, masks = _ref_route(s_seq, o, route, rdelta, KCFG)
            for k in o:
                assert np.array_equal(outs[k][t], o[k]), (window, t, k)
            for k in masks:
                assert np.array_equal(plans[k][t], masks[k]), (window, t, k)
            inbox = nxt
        resid_np = inbox
        # residual + state must match bit for bit
        rm = _np_tree(resid_multi)._asdict()
        for k in resid_np:
            assert np.array_equal(rm[k], resid_np[k]), (window, k)
        exp_rc = (resid_np["mtype"] != MSG.NONE).sum(axis=1)
        assert np.array_equal(rc, exp_rc), window
        sm = _np_tree(s_multi)._asdict()
        sq = _np_tree(s_seq)._asdict()
        for k in sm:
            assert np.array_equal(sm[k], sq[k]), (window, k)

    # the scenario really exercised what it claims: cluster A elected in
    # window 0, committed entries (incl. the cc) mid-window in window 1,
    # and changed leader in window 2
    final = _np_tree(s_multi)._asdict()
    assert final["leader"][0] == 2  # lane 1 (slot 1) led after window 2
    assert final["term"][0] == 2
    # noop + 2 props + cc + new-term noop + post-change proposal
    assert final["committed"][1] >= 6
    assert final["committed"][3] >= 4  # the never-routed lane progressed too


def test_superstep_counters_exact_sum_at_k8():
    """The counter plane sums EXACTLY across inner steps at K=8: the
    cumulative fold an engine keeps from one K=8 launch (sum over the
    stacked (K, G, CTR.COUNT) output, the _decode_super path) equals the
    fold from 8 sequential one-step launches glued by the reference
    router — no event lost or double-counted at any launch boundary."""
    steps = 8
    G = KCFG.groups
    s_multi, route, rdelta = _cluster_state()
    s_seq = jax.tree.map(lambda x: x, s_multi)
    multi = make_multi_step_fn(KCFG, steps, donate=False)
    step = make_step_fn(KCFG, donate=False)
    route_j, rdelta_j = jnp.asarray(route), jnp.asarray(rdelta)
    ticks = jnp.zeros((G,), jnp.int32)
    resid_np = _empty_inbox_np(KCFG)
    resid_multi = make_empty_inbox(KCFG)
    tot_multi = np.zeros((G, CTR.COUNT), np.uint64)
    tot_seq = np.zeros((G, CTR.COUNT), np.uint64)
    for window in range(3):
        counts = [
            int((resid_np["mtype"][g] != MSG.NONE).sum()) for g in range(G)
        ]
        host = _host_events(window, counts)
        s_multi, outs, plans, resid_multi, rc = multi(
            s_multi, _jnp_inbox(host), ticks, resid_multi, route_j, rdelta_j
        )
        ctr = np.asarray(jax.device_get(outs.counters))
        assert ctr.shape == (steps, G, CTR.COUNT)
        assert ctr.dtype == np.uint32
        tot_multi += ctr.astype(np.uint64).sum(axis=0)
        inbox = _merge_inbox(resid_np, host)
        for _t in range(steps):
            s_seq, out = step(s_seq, _jnp_inbox(inbox), ticks)
            o = _np_tree(out)._asdict()
            tot_seq += o["counters"].astype(np.uint64)
            inbox, _masks = _ref_route(s_seq, o, route, rdelta, KCFG)
        resid_np = inbox
        assert np.array_equal(tot_multi, tot_seq), window
    # the scenario moved what it claims: window 0 elected lane 0, window
    # 1 committed proposals, window 2 handed leadership to lane 1
    assert int(tot_multi[0, CTR.ELECTIONS_WON]) >= 1
    assert int(tot_multi[1, CTR.ELECTIONS_WON]) >= 1
    assert int(tot_multi[:, CTR.COMMIT_ADVANCES].sum()) > 0


def test_superstep_consumes_residual_without_host_work():
    """Routed messages parked in the residual must drive the next
    super-step even when the host packs nothing (the engine's skip path
    dispatches a residual-only super-step)."""
    steps = 2
    s, route, rdelta = _cluster_state()
    multi = make_multi_step_fn(KCFG, steps, donate=False)
    route_j, rdelta_j = jnp.asarray(route), jnp.asarray(rdelta)
    ticks = jnp.zeros((KCFG.groups,), jnp.int32)
    host = _empty_inbox_np(KCFG)
    host["mtype"][0, 0] = MSG.ELECTION
    resid = make_empty_inbox(KCFG)
    s, outs, plans, resid, rc = multi(
        s, _jnp_inbox(host), ticks, resid, route_j, rdelta_j
    )
    # with K=2 the vote responses are still in flight: carried as residual
    assert int(np.asarray(jax.device_get(rc)).sum()) > 0
    empty = _empty_inbox_np(KCFG)
    for _ in range(3):
        s, outs, plans, resid, rc = multi(
            s, _jnp_inbox(empty), ticks, resid, route_j, rdelta_j
        )
    assert int(np.asarray(s.leader)[0]) == 1  # election completed
    assert int(np.asarray(s.committed)[0]) >= 1


# ---------------------------------------------------------------------------
# 3. live engine e2e at steps_per_sync=4
# ---------------------------------------------------------------------------


class _CounterSM:
    pass


def _make_sm_cls():
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class SM(IStateMachine):
        def __init__(self, cluster_id, node_id):
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

    return SM


def _bring_up(tmp_path, scope, k, members, sm_cls=None, **engine):
    from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import loopback_factory, _Registry

    reg = _Registry()
    sm_cls = sm_cls or _make_sm_cls()
    shapes = dict(
        max_groups=8, max_peers=4, log_window=64, inbox_depth=8,
        max_entries_per_msg=8,
    )
    shapes.update(engine)
    hosts = {}
    for nid, addr in members.items():
        cfg = NodeHostConfig(
            raft_address=addr,
            rtt_millisecond=10,
            nodehost_dir=str(tmp_path / f"nh-{scope}-{nid}"),
            raft_rpc_factory=lambda a: loopback_factory(a, reg),
            engine=EngineConfig(
                kind="vector", share_scope=scope, steps_per_sync=k, **shapes
            ),
        )
        hosts[nid] = NodeHost(cfg)
    for nid in members:
        hosts[nid].start_clusters([
            (
                dict(members), False,
                lambda c, n: sm_cls(c, n),
                Config(
                    node_id=nid, cluster_id=1, election_rtt=20,
                    heartbeat_rtt=2,
                ),
            )
        ])
    deadline = time.monotonic() + 120
    lead = 0
    while time.monotonic() < deadline:
        lid, ok = hosts[1].get_leader_id(1)
        if ok and lid:
            lead = lid
            break
        time.sleep(0.02)
    assert lead, "no leader elected"
    # steps left to the engine: taken up at the first launch boundary
    # that finds every peer routable
    core = hosts[lead].engine.core
    while core.step_stats()["steps_per_launch"] != (k or 3):
        assert time.monotonic() < deadline, core.step_stats()
        time.sleep(0.02)
    return hosts, lead


@pytest.mark.perf
def test_multistep_engine_e2e(tmp_path):
    """K=4 shared-core cluster: commits, forwarded reads, ZERO host
    Message objects for co-hosted traffic, one blessed sync per K steps,
    zero out-of-seam syncs, zero steady-state retraces."""
    from dragonboat_tpu.profile import compile_watch, sync_audit

    members = {1: "ms4:1", 2: "ms4:2", 3: "ms4:3"}
    hosts, lead = _bring_up(tmp_path, "test-multistep4", 4, members)
    try:
        core = hosts[1].engine.core
        assert core._multi == 4
        assert core._overlap is False  # super-steps replace overlap
        sess = hosts[lead].get_noop_session(1)
        # warm steady state, then mark the audit window
        for i in range(5):
            assert hosts[lead].propose(sess, b"warm%d" % i, 10).wait(10)
        sync_mark = sync_audit().snapshot()
        compile_mark = compile_watch().snapshot()
        stats_mark = core.step_stats()
        ok = 0
        for i in range(30):
            r = hosts[lead].propose(sess, b"x%d" % i, timeout_s=10).wait(10)
            if r is not None and r.completed:
                ok += 1
        assert ok == 30
        # forwarded linearizable read from a follower host: the routed
        # READ_INDEX / READ_INDEX_RESP round trip
        fol = [n for n in members if n != lead][0]
        r = hosts[fol].read_index(1, 10).wait(10)
        assert r is not None and r.completed
        stats = core.step_stats()
        # zero host Messages for co-hosted traffic in the whole window
        for key in ("msgs_replicate", "msgs_broadcast", "msgs_resp"):
            assert stats[key] == stats_mark[key], (key, stats)
        assert stats["msgs_routed_device"] > stats_mark["msgs_routed_device"]
        # one blessed sync per K protocol steps, nothing out of seam
        from dragonboat_tpu.profile import diff_sync

        d = diff_sync(sync_mark, sync_audit().snapshot())
        assert d["in_seam"] > 0
        assert d["engine_steps"] == 4 * d["in_seam"]
        bad = {
            s: n
            for s, n in sync_audit().out_of_seam_in_package().items()
        }
        assert not bad, bad
        # steady state compiles nothing (the scanned kernel is warm)
        from dragonboat_tpu.profile import diff_compiles

        dc = diff_compiles(compile_mark, compile_watch().snapshot())
        assert not dc["per_function"], dc
    finally:
        for nh in hosts.values():
            nh.stop()


class _SaveOrder:
    """What each replica's log holds durably, as a wrapped logdb saw it:
    a command is `written` once a save has handed it to the store and
    `durable` once the barrier that save owed has returned (one group,
    so a save touches one shard of a host's logdb and owes one barrier)."""

    def __init__(self, core):
        self.core = core
        self.mu = threading.Lock()
        self.written = {}  # node id -> {cmd: launch that wrote it}
        self.durable = {}  # node id -> {cmd: launch that wrote it}
        self.done = []  # (batch id, replicas holding all of it durably)
        self.batches = {}  # batch id -> its commands

    def wrote(self, nid, updates):
        launch = self.core.launch_no
        with self.mu:
            w = self.written.setdefault(nid, {})
            for ud in updates:
                for e in ud.entries_to_save:
                    w[e.cmd] = launch

    def synced(self, nid):
        with self.mu:
            self.durable.setdefault(nid, {}).update(
                self.written.pop(nid, {})
            )

    def completed(self, bid):
        with self.mu:
            cmds = self.batches.get(bid)
            if cmds is None:
                return
            self.done.append((bid, sum(
                all(c in d for c in cmds) for d in self.durable.values()
            )))


class _SyncProxy:
    def __init__(self, kv, order, nid):
        self._kv, self._order, self._nid = kv, order, nid

    def __getattr__(self, name):
        return getattr(self._kv, name)

    def sync(self):
        self._kv.sync()
        self._order.synced(self._nid)


class _OrderLogDB:
    """A NodeHost's logdb with both of its save doors recorded."""

    def __init__(self, inner, order, nid):
        self._inner, self._order, self._nid = inner, order, nid

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def save_raft_state(self, updates):
        self._order.wrote(self._nid, updates)
        self._inner.save_raft_state(updates)
        self._order.synced(self._nid)

    def save_raft_state_deferred(self, updates):
        self._order.wrote(self._nid, updates)
        kvs = self._inner.save_raft_state_deferred(updates)
        if not kvs:  # nothing owed a barrier
            self._order.synced(self._nid)
        return [_SyncProxy(kv, self._order, self._nid) for kv in kvs]


def _make_log_sm_cls():
    from dragonboat_tpu.statemachine import IStateMachine, Result

    class SM(IStateMachine):
        def __init__(self, cluster_id, node_id):
            self.cmds = []

        def update(self, data):
            self.cmds.append(bytes(data))
            return Result(value=len(self.cmds))

        def lookup(self, q):
            return list(self.cmds)

        def save_snapshot(self, w, fc, done):
            raise NotImplementedError

        def recover_from_snapshot(self, r, fc, done):
            raise NotImplementedError

        def close(self):
            pass

    return SM


@pytest.mark.parametrize("k, steps", [(8, 8), (None, 3)], ids=["k8", "auto"])
def test_k8_batch_is_acknowledged_in_one_launch_behind_its_save_wave(
    tmp_path, monkeypatch, k, steps
):
    """fleet-1024x3-k8's guarantee at the NodeHost level: three co-hosted
    hosts at steps_per_sync 8, unsharded, and the same hosts with the
    steps left to the engine (None: three a launch here, the default
    path of every co-hosted deployment). Co-hosted replicas acknowledge
    on the device before the host has written anything, so a batch of 64
    commits inside the launch that packed it; "a majority has it durable"
    holds at the client's boundary only because every replica's entries
    are in that launch's one save wave and the wave returns before any
    completion. Held here through a wrapped logdb: at every completion of
    a batch all three replicas have the whole batch behind a returned
    barrier, written by one and the same launch."""
    from dragonboat_tpu.requests import BatchRequestState

    members = {n: f"ms{steps}:{n}" for n in (1, 2, 3)}
    hosts, lead = _bring_up(
        tmp_path, f"test-multistep{steps}", k, members,
        sm_cls=_make_log_sm_cls(),
        log_window=256, inbox_depth=4, max_entries_per_msg=64,
        profile_sample_ratio=1,
    )
    try:
        core = hosts[1].engine.core
        assert core._multi == steps and core._mesh is None
        core.request_sampler.ratio = 1  # stamp every batch's path
        order = _SaveOrder(core)
        for nid, nh in hosts.items():
            node = nh._get_node(1)
            node.logdb = _OrderLogDB(node.logdb, order, nid)
        add_done = BatchRequestState.add_done

        def recorded(self, completed=0, dropped=0):
            if completed:
                order.completed(self.batch_id)
            add_done(self, completed, dropped)

        monkeypatch.setattr(BatchRequestState, "add_done", recorded)
        sess = hosts[lead].get_noop_session(1)
        samples = core.profiler.samples

        def seen(name):  # Σ of a count-and-sum sample
            s = samples.get(name)
            return s.mean() * len(s) if s is not None else 0.0

        n0, l0 = seen("req.w.n"), seen("req.w.launches")
        sent = []
        for b in range(6):
            cmds = [b"b%02d-%02d" % (b, i) for i in range(64)]
            with order.mu:  # registered before a completion can look it up
                h = hosts[lead].propose_batch_async(sess, cmds, 10)
                order.batches[h.batch_id] = cmds
            assert h.wait(10)
            assert (h.completed, h.dropped) == (64, 0)
            sent.extend(cmds)
        # every completion found the whole batch durable on all three
        assert len(order.done) >= 6
        assert {n for _bid, n in order.done} == {3}, order.done
        # and one launch wrote it on all three: the merged wave
        for cmds in order.batches.values():
            launches = {
                d[c] for d in order.durable.values() for c in cmds
            }
            assert len(launches) == 1, launches
        # pack to commit inside one launch, every batch
        deadline = time.monotonic() + 5
        while seen("req.w.n") - n0 < 6 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert seen("req.w.n") - n0 == 6
        assert seen("req.w.launches") - l0 == 6
        # read back from all three replicas
        for nid, nh in hosts.items():
            deadline = time.monotonic() + 10
            while nh.stale_read(1, None) != sent:
                assert time.monotonic() < deadline, nid
                time.sleep(0.01)
    finally:
        for nh in hosts.values():
            nh.stop()


@pytest.mark.slow
def test_multistep_matches_k1_outcome(tmp_path):
    """The same workload through a K=1 and a K=4 cluster converges to
    the same applied SM state (the engine-level half of the
    differential: the kernel-level one proves byte equality, this one
    proves the host decode orchestration commits the same history)."""
    results = {}
    for k, scope, members in (
        (1, "test-ms-k1", {1: "msk1:1", 2: "msk1:2", 3: "msk1:3"}),
        (4, "test-ms-k4", {1: "msk4:1", 2: "msk4:2", 3: "msk4:3"}),
        (None, "test-ms-auto", {1: "mska:1", 2: "mska:2", 3: "mska:3"}),
    ):
        hosts, lead = _bring_up(tmp_path, scope, k, members)
        try:
            assert hosts[1].engine.core._multi == (k or 3)
            sess = hosts[lead].get_noop_session(1)
            vals = []
            for i in range(40):
                r = hosts[lead].propose(sess, b"p%d" % i, 10).wait(10)
                assert r is not None and r.completed, (k, i)
                vals.append(r.result.value)
            results[k] = vals
        finally:
            for nh in hosts.values():
                nh.stop()
    assert results[1] == results[4] == results[None]
