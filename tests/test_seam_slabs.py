"""The K-step launch's two slabs each way across the host<->device seam
(ops/slab.py): the layouts cut back into the very planes they were made
of, bit for bit, in every direction the engine uses them; the engine's
staging planes are views into the slabs it puts; and what a launch moves
(`n.seam_buffers`, read as `seam.buffers_per_launch`): four arrays at K
steps on one chip, a plane each in the one-step loop and over the mesh.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

import test_auto_steps as ta
import test_multistep as tm
from benchmark.run import load_plugin
from dragonboat_tpu.ops.kernel import (
    launch_in_slabs,
    launch_out_slabs,
    make_multi_step_fn,
    make_packed_multi_step_fn,
)
from dragonboat_tpu.ops.state import (
    CTR,
    MSG,
    Inbox,
    KernelConfig,
    RoutePlan,
    StepOutput,
    make_empty_inbox,
)

P, K_IN, R, E = 4, 3, 5, 2  # distinct, so a misplaced axis shows


def _cfg(G):
    return KernelConfig(
        groups=G, peers=P, log_window=16, inbox_depth=K_IN,
        max_entries_per_msg=E, readindex_depth=R,
    )


def _random(layout, rng):
    leaves = []
    for p in layout.planes:
        if p.is_bool:
            leaves.append(rng.random(p.shape) < 0.5)
        else:
            # every bit pattern of the word, the sign bit among them
            bits = rng.integers(0, 1 << 32, p.shape, dtype=np.uint64)
            leaves.append(bits.astype(np.uint32).view(p.dtype))
    return layout.treedef.unflatten(leaves)


def _same(got, want):
    got = jax.tree_util.tree_leaves(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a = np.asarray(a)
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        assert np.array_equal(a, b)


@pytest.mark.parametrize("kind, G, steps", [
    ("out", 5, 1),
    ("out", 13, 3),
    ("in", 13, None),
], ids=["outputs-k1-g5", "outputs-k3-g13", "staging-g13"])
def test_a_layout_round_trips_bit_for_bit(kind, G, steps):
    """The host's views, the device's unpack and the device's pack agree
    with the tree they were handed, bit for bit: an inner step's outputs
    (StepOutput, RoutePlan, residual occupancy), stacked over the K rows
    of a launch, or the host's staging planes (Inbox, ticks, route,
    rdelta). Written through the views of two host slabs, the tree is
    those slabs; cut on the device, it is the tree again; packed on the
    device, it is the same slabs."""
    cfg = _cfg(G)
    if kind == "out":
        layout = launch_out_slabs(cfg)
        out, plan, count = layout.treedef.unflatten(layout.planes)
        assert isinstance(out, StepOutput) and isinstance(plan, RoutePlan)
        assert out.match.shape == (G, P)
        assert out.resp_type.shape == (G, K_IN)
        assert out.ready_ctx.shape == (G, R)
        assert out.counters.shape == (G, CTR.COUNT)
        assert out.counters.dtype == np.uint32
        assert plan.rep.shape == (G, P) and plan.rep.dtype == bool
        assert count.shape == (G,)
        assert len(layout.int_shape) == 1  # a step's row is flat
    else:
        layout = launch_in_slabs(cfg)
        inbox, ticks, route, rdelta = layout.treedef.unflatten(layout.planes)
        assert isinstance(inbox, Inbox)
        assert inbox.entry_cc.shape == (G, K_IN, E)
        assert inbox.reject.dtype == bool
        assert ticks.shape == (G,) and route.shape == rdelta.shape == (G, P)
        # the lane axis leads: a plane is a block of columns
        assert layout.int_shape[0] == layout.bool_shape[0] == G
    rng = np.random.default_rng(G * 10 + (steps or 0))
    trees = [_random(layout, rng) for _ in range(steps or 1)]
    rows = []
    for tree in trees:
        ints = np.zeros(layout.int_shape, np.int32)
        bools = np.zeros(layout.bool_shape, bool)
        views = layout.unpack(ints, bools)
        for v, x in zip(jax.tree_util.tree_leaves(views),
                        jax.tree_util.tree_leaves(tree)):
            assert np.shares_memory(v, ints) or np.shares_memory(v, bools)
            v[...] = x
        assert ints.any() and bools.any()
        _same(layout.unpack(ints, bools), tree)
        _same(jax.jit(layout.unpack)(ints, bools), tree)
        _same(jax.device_get(jax.jit(layout.pack)(tree)), [ints, bools])
        rows.append((ints, bools))
    if steps:
        # a launch's rows, stacked as the scan stacks them
        stacked = layout.unpack(*(np.stack(r) for r in zip(*rows)))
        _same(stacked, jax.tree.map(lambda *xs: np.stack(xs), *trees))


def test_the_packed_launch_is_the_k_step_program_bit_for_bit():
    """The one-chip launch over its slabs and the K-step program over
    the planes, launch after launch of the differential scenario of
    test_multistep (an election, proposals with a config change that
    commits mid-launch, a leader change): the same state, residual
    inbox, per-step outputs, route plans and residual occupancy."""
    cfg, steps = tm.KCFG, 3
    G = cfg.groups
    plain = make_multi_step_fn(cfg, steps, donate=False)
    packed = make_packed_multi_step_fn(cfg, steps, donate=False)
    ins, rows = launch_in_slabs(cfg), launch_out_slabs(cfg)
    s_a, route, rdelta = tm._cluster_state()
    s_b = jax.tree.map(lambda x: x, s_a)
    resid_a = resid_b = make_empty_inbox(cfg)
    ticks = np.ones((G,), np.int32)
    ints = np.zeros(ins.int_shape, np.int32)
    bools = np.zeros(ins.bool_shape, bool)
    inbox_v, ticks_v, route_v, rdelta_v = ins.unpack(ints, bools)
    ticks_v[...], route_v[...], rdelta_v[...] = ticks, route, rdelta
    for window in range(4):
        counts = np.asarray(jax.device_get(resid_a.mtype != MSG.NONE))
        host = tm._host_events(window, list(counts.sum(axis=1)))
        for name in Inbox._fields:
            getattr(inbox_v, name)[...] = host[name]
        s_a, outs, plans, resid_a, count = plain(
            s_a, tm._jnp_inbox(host), ticks, resid_a, route, rdelta
        )
        s_b, out_ints, out_bools, resid_b = packed(
            s_b, ints, bools, resid_b
        )
        o, pl, occ = rows.unpack(*jax.device_get((out_ints, out_bools)))
        assert out_ints.shape[0] == out_bools.shape[0] == steps
        _same((o, pl, occ[-1]), jax.device_get((outs, plans, count)))
        _same(resid_b, jax.device_get(resid_a))
        _same(s_b, jax.device_get(s_a))
    assert int(jax.device_get(s_b.leader)[0]) == 2  # the scenario ran


def _moved(core):
    samples = core.profiler.samples
    return {
        name: s.mean() * len(s)
        for name, s in list(samples.items())
        if name in ("n.launches", "n.seam_buffers")
    }


@pytest.mark.parametrize("engine, steps, want", [
    (dict(), 3, 4),
    (dict(steps_per_sync=1), 1, 13 + 48),
    (dict(steps_per_sync=2, shard_over_mesh=True), 2, 15 + 48 + 6 + 1),
], ids=["one-chip-k3", "one-step", "mesh-k2"])
def test_a_launch_moves(tmp_path, engine, steps, want):
    """At full sampling every launch folds the arrays it put and
    fetched: the packed launch its two slabs each way, the one-step
    loop the 12 inbox planes, ticks and 48 output planes, the mesh at
    K > 1 those, route, rdelta, 6 plan planes and the occupancy, one
    by one. The staging planes are views into the slabs either way."""
    hosts, lead = ta._bring_up(
        tmp_path, f"seam-{steps}", f"seam{steps}", profile_sample_ratio=1,
        **engine,
    )
    try:
        core = hosts[1].engine.core
        ta._wait_steps(core, steps)
        ints, bools = core._in_slabs
        assert np.shares_memory(core._buf["mtype"], ints)
        assert np.shares_memory(core._buf["entry_cc"], bools)
        assert np.shares_memory(core._np_route, ints)
        assert (core._out_slabs is not None) == (steps == 3)
        a = _moved(core)
        _propose_and_check(hosts, lead)
        b = _moved(core)
        run = ta._window(**{n: b[n] - a.get(n, 0.0) for n in b})
        read = load_plugin("layer_metrics", "seam.buffers_per_launch").read
        assert read(run) == pytest.approx(want)
    finally:
        ta._stop(hosts)


def _propose_and_check(hosts, lead):
    sent = []
    ta._propose_n(hosts[lead], 10, b"s", sent)
    ta._converged(hosts, sent)
