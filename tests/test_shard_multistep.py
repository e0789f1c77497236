"""Sharded lane-mesh differential tests: the K-step kernel with its
group axis spread over the device mesh must be BYTE-IDENTICAL to the
unsharded K=1 reference — same protocol state, same per-step output
planes, same route plans, same carried residual — across seeded traffic
that covers elections, a config-change commit mid-window, and a leader
change mid-window. All protocol state is int32/bool, so bit equality is
the contract, not a tolerance.

Layered like test_multistep:
  1. property test: the cross-shard router (_shard_route under
     shard_map) vs the per-element host-dispatch reference router, on
     randomized states/outputs whose destinations span shards;
  2. scenario differential: sharded K-step super-steps vs K sequential
     unsharded steps glued by the reference router.

conftest pins an 8-device CPU platform; with 8 lanes each lane lives on
its own device, so every routed co-hosted message crosses a shard
boundary — the strongest setting for the exchange+replay path.
"""
from __future__ import annotations

import functools
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

import test_multistep as tm
from test_multistep import (
    _empty_inbox_np,
    _jnp_inbox,
    _merge_inbox,
    _np_tree,
    _ref_route,
)

from dragonboat_tpu.ops.kernel import (
    _shard_route,
    make_sharded_multi_step_fn,
    make_step_fn,
)
from dragonboat_tpu.ops.state import (
    MSG,
    KernelConfig,
    configure_group,
    init_state,
    make_empty_inbox,
)

N_DEV = jax.device_count()

# the canonical test shape at the smallest lane count the mesh divides:
# one lane per device on the conftest's 8-device CPU platform
SKCFG = KernelConfig(
    groups=8, peers=4, log_window=32, inbox_depth=4,
    max_entries_per_msg=4, readindex_depth=4,
)

needs_mesh = pytest.mark.skipif(
    N_DEV < 2 or SKCFG.groups % N_DEV != 0,
    reason="needs a multi-device mesh that divides the lane count",
)


def _mesh():
    return Mesh(np.asarray(jax.devices()), ("groups",))


# ---------------------------------------------------------------------------
# 1. cross-shard router property test vs the host-dispatch reference
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("seed", range(6))
def test_shard_route_matches_reference(seed, monkeypatch):
    """_shard_route under shard_map — exchange every shard's candidate
    columns, replay the global scatter, keep the local slice — must
    reproduce the reference router bit for bit, including candidates
    whose destination lane lives on another shard."""
    # reuse test_multistep's randomized state/output generator at this
    # file's lane count (it reads the module-global KCFG)
    monkeypatch.setattr(tm, "KCFG", SKCFG)
    rng = random.Random(7000 + seed)
    G, P = SKCFG.groups, SKCFG.peers
    s, o_np, out = tm._random_state_and_output(rng)
    route = np.full((G, P), -1, np.int32)
    rdelta = np.zeros((G, P), np.int32)
    self_slot = np.asarray(s.self_slot)
    for g in range(G):
        for p in range(P):
            if p == self_slot[g]:
                continue
            if rng.random() < 0.6:
                route[g, p] = rng.randrange(G)  # GLOBAL lane index
                rdelta[g, p] = rng.choice([0, 0, 0, 2, -2, -40])

    lane = PartitionSpec("groups")
    fn = jax.shard_map(
        functools.partial(
            _shard_route, cfg=SKCFG, axis_name="groups", n_shards=N_DEV
        ),
        mesh=_mesh(),
        in_specs=(lane,) * 4,
        out_specs=(lane, lane),
        check_vma=False,
    )
    nxt, plan = jax.jit(fn)(s, out, jnp.asarray(route), jnp.asarray(rdelta))
    nxt = _np_tree(nxt)._asdict()
    plan = _np_tree(plan)._asdict()
    ref_nxt, ref_masks = _ref_route(s, o_np, route, rdelta, SKCFG)
    for k in ref_masks:
        assert np.array_equal(plan[k], ref_masks[k]), (seed, k)
    for k in ref_nxt:
        assert np.array_equal(nxt[k], ref_nxt[k]), (seed, k)
    # the trial must actually cross shard boundaries: count accepted
    # peer-plane candidates whose destination lane lives on another shard
    Gl = G // N_DEV
    cross = sum(
        int(ref_masks[kind][g, p])
        for kind in ("rep", "vote", "hb", "tn")
        for g in range(G)
        for p in range(P)
        if route[g, p] >= 0 and route[g, p] // Gl != g // Gl
    )
    assert cross > 0, "seed routed nothing across shards"


# ---------------------------------------------------------------------------
# 2. sharded super-step differential vs unsharded K=1 + reference router
# ---------------------------------------------------------------------------


def _cluster_state8():
    """test_multistep's canonical cluster layout at this file's lane
    count: 3 co-hosted replicas of cluster A on lanes 0/1/2, a
    single-voter lane 3, a partial cluster on lanes 4/5 with a
    cross-host third slot, and two unconfigured lanes (6/7) that must
    stay inert — the padded-lane shape the sharded engine produces."""
    s = init_state(SKCFG)
    for g, slot in ((0, 0), (1, 1), (2, 2)):
        s = configure_group(
            s, g, slot, (0, 1, 2), election_timeout=10, heartbeat_timeout=2
        )
    s = configure_group(s, 3, 0, (0,), election_timeout=10)
    for g, slot in ((4, 0), (5, 1)):
        s = configure_group(
            s, g, slot, (0, 1, 2), election_timeout=10, heartbeat_timeout=2
        )
    G, P = SKCFG.groups, SKCFG.peers
    route = np.full((G, P), -1, np.int32)
    for g, slot in ((0, 0), (1, 1), (2, 2)):
        for p, pg in ((0, 0), (1, 1), (2, 2)):
            if pg != g:
                route[g, p] = pg
    route[4, 1] = 5
    route[5, 0] = 4  # slot 2 of lanes 4/5 is cross-host: stays -1
    rdelta = np.zeros((G, P), np.int32)
    return s, route, rdelta


def _host_events8(window, counts):
    """test_multistep's 4-window scenario (election; proposals + a
    config change that commits mid-window; leader change; post-change
    proposal) padded out to this file's lane count."""
    h6 = tm._host_events(window, counts)
    out = _empty_inbox_np(SKCFG)
    for k in out:
        out[k][: tm.KCFG.groups] = h6[k]
    return out


@needs_mesh
def test_sharded_superstep_matches_k1_reference():
    """The sharded K-step super-step must be byte-identical to K
    sequential UNSHARDED one-step kernel calls glued by the reference
    router: final protocol state, every per-step output plane, the
    route plans, and the carried residual inbox — across a scenario
    with an election, a config-change commit mid-window, and a leader
    change mid-window (the traffic shapes the on-device cross-shard
    exchange must not perturb)."""
    steps = 4
    windows = 4
    G = SKCFG.groups
    s_sh, route, rdelta = _cluster_state8()
    s_seq = jax.tree.map(lambda x: x, s_sh)  # same initial values
    smulti = make_sharded_multi_step_fn(SKCFG, steps, _mesh(), donate=False)
    step = make_step_fn(SKCFG, donate=False)
    route_j, rdelta_j = jnp.asarray(route), jnp.asarray(rdelta)
    ticks = jnp.zeros((G,), jnp.int32)

    resid_np = _empty_inbox_np(SKCFG)  # seq side's carried residual
    resid_sh = make_empty_inbox(SKCFG)
    for window in range(windows):
        counts = [
            int((resid_np["mtype"][g] != MSG.NONE).sum()) for g in range(G)
        ]
        host = _host_events8(window, counts)
        # ---- sharded path: one kernel launch over the mesh ---------------
        s_sh, outs, plans, resid_sh, rc = smulti(
            s_sh, _jnp_inbox(host), ticks, resid_sh, route_j, rdelta_j
        )
        # the state really lives spread over the mesh between windows
        assert len(s_sh.term.sharding.device_set) == N_DEV
        outs = _np_tree(outs)._asdict()
        plans = _np_tree(plans)._asdict()
        rc = np.asarray(jax.device_get(rc))
        # ---- reference path: K unsharded steps + reference routing -------
        inbox = _merge_inbox(resid_np, host)
        for t in range(steps):
            s_seq, out = step(s_seq, _jnp_inbox(inbox), ticks)
            o = _np_tree(out)._asdict()
            nxt, masks = _ref_route(s_seq, o, route, rdelta, SKCFG)
            for k in o:
                assert np.array_equal(outs[k][t], o[k]), (window, t, k)
            for k in masks:
                assert np.array_equal(plans[k][t], masks[k]), (window, t, k)
            inbox = nxt
        resid_np = inbox
        rm = _np_tree(resid_sh)._asdict()
        for k in resid_np:
            assert np.array_equal(rm[k], resid_np[k]), (window, k)
        exp_rc = (resid_np["mtype"] != MSG.NONE).sum(axis=1)
        assert np.array_equal(rc, exp_rc), window
        sm = _np_tree(s_sh)._asdict()
        sq = _np_tree(s_seq)._asdict()
        for k in sm:
            assert np.array_equal(sm[k], sq[k]), (window, k)

    # the scenario really exercised what it claims (same verdicts as
    # test_multistep's unsharded differential): cluster A elected in
    # window 0, committed entries (incl. the cc) mid-window in window 1,
    # changed leader in window 2 — and the unconfigured tail lanes that
    # model engine padding stayed inert
    final = _np_tree(s_sh)._asdict()
    assert final["leader"][0] == 2  # lane 1 (slot 1) led after window 2
    assert final["term"][0] == 2
    assert final["committed"][1] >= 6
    assert final["committed"][3] >= 4
    assert final["term"][6] == 0 and final["term"][7] == 0
    assert final["committed"][6] == 0 and final["committed"][7] == 0
