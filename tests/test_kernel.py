"""Tests for the vectorized Raft kernel: protocol behavior on the loopback
simulation cluster, plus invariant checks across randomized runs."""
import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu.ops import KernelConfig, ROLE
from dragonboat_tpu.ops.kernel import _ring_run, _rotate_rows, _run_to_ring
from dragonboat_tpu.ops.loopback import LoopbackCluster


def make(n=3, groups=2, **kw):
    return LoopbackCluster(n_replicas=n, n_groups=groups, **kw)


# ---------------------------------------------------------------- elections


def test_kernel_single_leader_emerges():
    c = make()
    c.run(30)
    for g in range(c.n_groups):
        roles = c.roles(g)
        assert roles.count(ROLE.LEADER) == 1, f"group {g}: {roles}"
        terms = c.field("term", g)
        assert len(set(terms)) == 1


def test_kernel_all_groups_elect_independently():
    c = make(groups=8)
    c.run(40)
    for g in range(8):
        assert c.leader_of(g) is not None


def test_kernel_leader_stable_after_election():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    term = c.field("term", 0)[lead]
    c.run(30)
    assert c.leader_of(0) == lead
    assert c.field("term", 0)[lead] == term  # no spurious re-elections


def test_kernel_reelection_after_leader_isolated():
    c = make()
    c.run(30)
    old = c.leader_of(0)
    c.isolated.add(old)
    c.run(35)
    survivors = [h for h in range(3) if h != old]
    new_leaders = [h for h in survivors if c.roles(0)[h] == ROLE.LEADER]
    assert len(new_leaders) == 1
    # heal: old leader rejoins and steps down
    c.isolated.clear()
    c.run(10)
    assert c.roles(0).count(ROLE.LEADER) == 1
    assert c.roles(0)[old] != ROLE.LEADER


# ---------------------------------------------------------------- replication


def test_kernel_propose_commits_everywhere():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    c.propose(lead, 0, n=3)
    c.run(3)
    commits = c.field("committed", 0)
    lasts = c.field("last_index", 0)
    assert len(set(commits)) == 1
    assert commits[0] == lasts[0] == 4  # noop + 3 proposals
    # log terms identical across replicas
    t0 = c.ring_terms(0, 0, 1, 4)
    assert t0 == c.ring_terms(1, 0, 1, 4) == c.ring_terms(2, 0, 1, 4)


def test_kernel_save_ranges_reported():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    c.propose(lead, 0, n=2)
    c.step(tick=False)
    out = c.last_outputs[lead]
    sf, st_ = int(np.asarray(out.save_from)[0]), int(np.asarray(out.save_to)[0])
    assert sf > 0 and st_ >= sf  # the two new entries must be persisted


def test_kernel_commit_requires_quorum():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    others = [h for h in range(3) if h != lead]
    c.isolated.update(others)  # leader alone: no quorum
    before = c.field("committed", 0)[lead]
    c.propose(lead, 0, n=1)
    for _ in range(5):
        c.step(tick=False)
    assert c.field("committed", 0)[lead] == before
    c.isolated.clear()
    c.run(3)
    assert c.field("committed", 0)[lead] == before + 1


def test_kernel_divergent_follower_converges():
    """A replica that accepted uncommitted entries from a deposed leader must
    overwrite them with the new leader's log (paper 5.3)."""
    c = make()
    c.run(30)
    old = c.leader_of(0)
    # strand proposals on the old leader only
    c.isolated.update(h for h in range(3) if h != old)
    c.propose(old, 0, n=3)
    for _ in range(3):
        c.step(tick=False)
    assert c.field("last_index", 0)[old] > c.field("committed", 0)[old]
    # partition flips: old leader cut off, others elect
    c.isolated.clear()
    c.isolated.add(old)
    c.run(35)
    new = [h for h in range(3) if h != old and c.roles(0)[h] == ROLE.LEADER][0]
    c.propose(new, 0, n=2)
    c.run(3)
    # heal; old leader must converge to the new log
    c.isolated.clear()
    c.run(12)
    lasts = c.field("last_index", 0)
    commits = c.field("committed", 0)
    assert len(set(commits)) == 1
    hi = commits[0]
    ref = c.ring_terms(new, 0, 1, hi)
    assert c.ring_terms(old, 0, 1, hi) == ref


def test_kernel_follower_catchup_from_empty():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    straggler = [h for h in range(3) if h != lead][0]
    c.isolated.add(straggler)
    for _ in range(4):
        c.propose(lead, 0, n=2)
        c.run(2)
    c.isolated.clear()
    c.run(12)
    assert c.field("last_index", 0)[straggler] == c.field("last_index", 0)[lead]
    assert c.field("committed", 0)[straggler] == c.field("committed", 0)[lead]


# ---------------------------------------------------------------- readindex


def test_kernel_readindex_quorum_roundtrip():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    c.read_index(lead, 0, ctx=4242)
    c.run(3)
    hits = [r for r in c.ready_reads[lead] if r[0] == 0 and r[1] == 4242]
    assert hits, f"no ready read: {c.ready_reads[lead]}"
    assert hits[0][2] == c.field("committed", 0)[lead]


def test_kernel_readindex_multiple_ctxs_fifo():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    c.read_index(lead, 0, ctx=11)
    c.read_index(lead, 0, ctx=12)
    c.run(4)
    ctxs = [r[1] for r in c.ready_reads[lead] if r[0] == 0]
    assert ctxs[:2] == [11, 12]


# ---------------------------------------------------------------- transfer


def test_kernel_leader_transfer():
    c = make()
    c.run(30)
    lead = c.leader_of(0)
    target = [h for h in range(3) if h != lead][0]
    c.transfer_leader(lead, 0, target)
    c.run(8)
    assert c.leader_of(0) == target
    assert c.roles(0)[lead] != ROLE.LEADER


# ---------------------------------------------------------------- witnesses


def test_kernel_witness_in_quorum():
    """2 full replicas + 1 witness: witness vote/ack counts toward quorum."""
    c = make(n=3, witnesses=(2,))
    c.run(40)
    lead = c.leader_of(0)
    assert lead in (0, 1)
    assert c.roles(0)[2] == ROLE.WITNESS
    # kill the other full replica: leader + witness still form a quorum
    other = 1 - lead
    c.isolated.add(other)
    before = c.field("committed", 0)[lead]
    c.propose(lead, 0, n=1)
    c.run(4)
    assert c.field("committed", 0)[lead] == before + 1


def test_kernel_observer_replicates_without_voting():
    c = make(n=3, observers=(2,))
    c.run(40)
    lead = c.leader_of(0)
    assert lead in (0, 1)
    assert c.roles(0)[2] == ROLE.OBSERVER
    c.propose(lead, 0, n=2)
    c.run(4)
    # observer received the data
    assert c.field("last_index", 0)[2] == c.field("last_index", 0)[lead]
    # but quorum is the 2 voting members: isolating the other full member
    # blocks commit even though the observer acks
    other = 1 - lead
    c.isolated.add(other)
    before = c.field("committed", 0)[lead]
    c.propose(lead, 0, n=1)
    c.run(4)
    assert c.field("committed", 0)[lead] == before


# ---------------------------------------------------------------- check quorum


def test_kernel_check_quorum_step_down():
    c = make(check_quorum=True)
    c.run(30)
    lead = c.leader_of(0)
    c.isolated.update(h for h in range(3) if h != lead)
    # two election periods without responses => step down
    for _ in range(25):
        c.step(tick=True)
    assert c.roles(0)[lead] != ROLE.LEADER


# ---------------------------------------------------------------- randomized


def test_kernel_randomized_chaos_invariants():
    """Random drops/partitions/proposals; at all times: at most one leader
    per term, committed prefixes never diverge, commit never regresses."""
    rng = np.random.default_rng(3)
    c = make(groups=2)
    c.run(30)
    max_commit = {g: 0 for g in range(2)}
    for it in range(60):
        # random link chaos
        c.dropped_links.clear()
        for _ in range(rng.integers(0, 3)):
            a, b = rng.integers(0, 3, 2)
            if a != b:
                c.dropped_links.add((int(a), int(b)))
        for g in range(2):
            lead = c.leader_of(g)
            if lead is not None and rng.random() < 0.7:
                c.propose(lead, g, n=int(rng.integers(1, 4)))
        c.step(tick=True)
        if rng.random() < 0.5:
            c.settle(5)
        for g in range(2):
            commits = c.field("committed", g)
            terms = c.field("term", g)
            # at most one leader per term
            lt = [
                (terms[h], h)
                for h in range(3)
                if c.roles(g)[h] == ROLE.LEADER
            ]
            assert len({t for t, _ in lt}) == len(lt), f"two leaders one term: {lt}"
            # committed prefix equality on the common committed prefix
            m = min(commits)
            if m >= 1:
                r0 = c.ring_terms(0, g, 1, m)
                assert r0 == c.ring_terms(1, g, 1, m) == c.ring_terms(2, g, 1, m)
            assert max(commits) >= max_commit[g]
            max_commit[g] = max(commits)
    # heal and converge
    c.dropped_links.clear()
    c.run(20)
    for g in range(2):
        assert len(set(c.field("committed", g))) == 1


# ---------------------------------------------------------------- ring runs


@pytest.mark.parametrize("dtype", [np.int32, np.bool_], ids=["int32", "bool"])
@pytest.mark.parametrize("W,E", [(256, 64), (96, 32), (64, 64), (12, 5), (7, 1)])
def test_ring_run_helpers_match_the_gather_forms(W, E, dtype):
    """A contiguous run of ring slots modulo W read (`_ring_run`) and written
    (`_run_to_ring`) as a row rotation gives what take_along_axis gave on the
    index formulas the kernel used before: everywhere on the read side, and
    wherever `written` holds on the write side."""
    rng = np.random.default_rng(W * 1000 + E)
    # hand-made lanes first: a run that ends on the ring's last slot, one that
    # wraps it, n = 0, n = E, prev = 0, prev far beyond W; then random ones
    prev = np.array([W - E - 1, W - 2, W - 1, 0, 5 * W + 3, 2 * W - 1], np.int32)
    nent = np.array([E, E, 0, E, E // 2, 1], np.int32)
    skip = np.array([0, 0, 0, 0, E // 4, 0], np.int32)  # first_conf - (prev + 1)
    n_rand = 40
    prev = np.concatenate([prev, rng.integers(0, 9 * W, n_rand)]).astype(np.int32)
    nent = np.concatenate([nent, rng.integers(0, E + 1, n_rand)]).astype(np.int32)
    skip = np.concatenate(
        [skip, rng.integers(0, E + 1, n_rand) % np.maximum(nent[6:], 1)]
    ).astype(np.int32)
    G = len(prev)

    def plane(shape):
        if dtype == np.bool_:
            return rng.integers(0, 2, shape).astype(bool)
        return rng.integers(1, 1 << 30, shape).astype(np.int32)

    ring, ents = plane((G, W)), plane((G, E))
    e = np.arange(E, dtype=np.int32)[None, :]
    w = np.arange(W, dtype=np.int32)[None, :]

    # read side: inbox/follower_append's exist_term
    e_idx = prev[:, None] + 1 + e
    want = np.take_along_axis(ring, e_idx % W, axis=1)
    got = np.asarray(_ring_run(jnp.asarray(ring), jnp.asarray(prev) + 1, E))
    assert got.dtype == ring.dtype and np.array_equal(got, want)

    # read side over peers: replicate_fanout's ring_t / ring_cc at K > 1
    prev_gp = np.stack([prev, prev[::-1], prev + 1], axis=1)
    e_idx = (prev_gp + 1)[:, :, None] + e[None]
    want = np.take_along_axis(ring[:, None, :], e_idx % W, axis=2)
    got = _ring_run(jnp.asarray(ring)[:, None, :], jnp.asarray(prev_gp) + 1, E)
    assert np.array_equal(np.asarray(got), want)

    # write side: follower_append (lo = first_conf) and propose_append (skip 0)
    for lo in (prev + 1 + skip, prev + 1):
        hi = prev + nent
        i_w = lo[:, None] + np.mod(w - lo[:, None], W)
        written = (nent > 0)[:, None] & (i_w <= hi[:, None])
        e_pos = np.clip(i_w - (prev[:, None] + 1), 0, E - 1)
        want = np.where(written, np.take_along_axis(ents, e_pos, axis=1), ring)
        run = _run_to_ring(jnp.asarray(ents), jnp.asarray(prev) + 1, W)
        got = np.where(written, np.asarray(run), ring)
        assert run.shape == (G, W) and np.array_equal(got, want)
        assert written.sum() == np.maximum(hi - lo + 1, 0)[nent > 0].sum()

    # the rotation itself, any sign and size of shift
    shift = np.concatenate([prev, -prev - 1])
    both = np.concatenate([ring, ring])
    want = np.take_along_axis(both, (w + shift[:, None]) % W, axis=1)
    got = _rotate_rows(jnp.asarray(both), jnp.asarray(shift))
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("W,E", [(12, 5), (16, 8)])
def test_kernel_append_that_wraps_the_ring(W, E):
    """Appends whose run of slots crosses the ring's end, on the leader
    (propose_append) and on the followers (follower_append), leave the rings
    a plain NumPy replay of the log leaves."""
    cfg = KernelConfig(
        groups=1, peers=3, log_window=W, inbox_depth=8,
        max_entries_per_msg=E, readindex_depth=2,
    )
    c = LoopbackCluster(n_replicas=3, n_groups=1, cfg=cfg)
    c.run(30)
    lead = c.leader_of(0)
    assert lead is not None
    term = c.field("term", 0)[lead]
    ref_t, ref_cc = np.zeros(W, np.int32), np.zeros(W, bool)
    ref_t[1 % W] = term  # the leader's no-op at index 1
    last = 1

    def compact():
        # what the engine's maintain does once entries are applied
        for h, st in enumerate(c.states):
            done = st.committed
            c.states[h] = st._replace(
                first_index=done + 1, marker_term=jnp.full_like(done, term)
            )

    def propose(ns, cc_at=None):
        nonlocal last
        for j, n in enumerate(ns):
            c.propose(lead, 0, n=n, cc_first=(j == cc_at))
            for i in range(last + 1, last + n + 1):
                ref_t[i % W], ref_cc[i % W] = term, (j == cc_at)
            last += n
        c.run(3)
        assert c.field("committed", 0) == [last] * 3
        compact()

    while last < W - 2:
        propose([min(E, W - 2 - last)])
    # one launch: a proposal that crosses the ring's end on the leader, a
    # config change alone in its message, one more; the followers get all of
    # them in one Replicate whose run crosses the end too
    assert last == W - 2 and c.leader_of(0) == lead
    propose([2, 1, 1], cc_at=1)
    assert last == W + 2 and ref_cc[W % W + 1] and ref_cc.sum() == 1
    for st in c.states:
        assert np.array_equal(np.asarray(st.log_term)[0], ref_t)
        assert np.array_equal(np.asarray(st.log_is_cc)[0], ref_cc)
