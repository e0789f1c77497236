"""YCSB workload A over NodeHost (ISSUE 26): the record state machine
and its plain reference, the seeded Zipfian stream, the whole system
against the reference at a small size, and the pack counters that say
what a launch of uneven lanes carried.
"""
from __future__ import annotations

import io
import os
import time
from collections import deque

import numpy as np
import pytest

from dragonboat_tpu.lincheck import (
    UNKNOWN,
    Model,
    Operation,
    check_linearizable,
    partition_by_key,
)
from dragonboat_tpu.types import Entry
from benchmark.statemachines import kvrecords as kv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659  # more than 32 signed bits hold


class _E:
    def __init__(self, cmd):
        self.cmd, self.result = cmd, None


def _apply(sm, cmds):
    return [e.result.value for e in sm.update([_E(c) for c in cmds])]


# ------------------------------------------------------- the state machine
def test_commands_are_whole_words_at_ycsbs_sizes():
    fields = [bytes([i]) * 100 for i in range(10)]
    key = b"user" + b"7" * 20
    ins, upd = kv.insert_cmd(key, fields), kv.update_cmd(key, 3, b"x" * 100)
    assert (len(ins), len(upd)) == (1032, 136)
    assert kv.sum64(upd) == sum(
        int.from_bytes(upd[i:i + 8], "little") for i in range(0, 136, 8)
    ) % 2 ** 64
    wl = kv.Workload(SEED, 4, 64)
    assert [len(c) for c in wl.cmds(2, 15, 18)] == [1032, 136, 136]
    assert wl.cmds(2, 3, 4)[0][8:32] == wl.key(2, 3)
    t = kv.Table()
    t.apply(ins)
    t.apply(upd)
    assert t.rows[key] == tuple(fields[:3] + [b"x" * 100] + fields[4:])
    with pytest.raises(ValueError):
        t.apply(upd[:-8])
    with pytest.raises(ValueError):
        t.apply(b"\x09" + upd[1:])


def test_update_answers_with_the_apply_sequence_and_lookups_do_not_tear():
    wl = kv.Workload(SEED, 4, 64)
    sm = kv.StateMachine(1, 1)
    cmds = wl.cmds(1, 0, 40)
    assert _apply(sm, cmds[:16]) == list(range(1, 17))
    assert _apply(sm, cmds[16:]) == list(range(17, 41))
    assert sm.lookup(None) == (40, wl.sum64(1, 40))
    ref = kv.Reference()
    for c in cmds:
        ref.apply(c)
    for slot in range(16):
        record, applied = sm.lookup(wl.key(1, slot))
        assert applied == 40 and record == ref.lookup(wl.key(1, slot))
        assert len(record) == 10 and {len(f) for f in record} == {100}
    assert sm.lookup(b"user" + b"0" * 20) == (None, 40)
    # a record handed out is the record as it stood: updates build new ones
    held, _n = sm.lookup(wl.key(1, 0))
    copy = tuple(bytes(f) for f in held)
    _apply(sm, wl.cmds(1, 40, 400))
    assert held == copy and sm.lookup(wl.key(1, 0))[0] != held


def test_snapshot_round_trip():
    wl = kv.Workload(SEED, 4, 64)
    sm = kv.StateMachine(1, 1)
    _apply(sm, wl.cmds(3, 0, 100))
    buf = io.BytesIO()
    ctx = sm.prepare_snapshot()
    _apply(sm, wl.cmds(3, 100, 120))  # saving runs beside later updates
    sm.save_snapshot(ctx, buf, None, None)
    back = kv.StateMachine(1, 2)
    back.recover_from_snapshot(io.BytesIO(buf.getvalue()), None, None)
    assert back.lookup(None) == (100, wl.sum64(3, 100))
    _apply(back, wl.cmds(3, 100, 120))
    assert back.lookup(None) == sm.lookup(None)
    assert back.table.rows == sm.table.rows


# ------------------------------------------------------------ the reference
def _history(wl, g, rows, sm=None):
    """Rows [per, rows) of group g applied after its inserts, a read of
    the updated key after each: (updates, reads) as a client would hold
    them, from the state machine `sm`."""
    sm = sm or kv.StateMachine(1, 1)
    per = wl.per_group
    _apply(sm, wl.cmds(g, 0, per))
    updates, reads = [], []
    for cmd in wl.cmds(g, per, rows):
        (n,) = _apply(sm, [cmd])
        updates.append((n, cmd))
        key = cmd[8:32]
        record, applied = sm.lookup(key)
        reads.append((applied, key, record))
    return updates, reads


def _fresh_reference(wl, g):
    ref = kv.Reference()
    for cmd in wl.cmds(g, 0, wl.per_group):
        ref.apply(cmd)
    return ref


@pytest.mark.parametrize(
    "fault", ["none", "dropped", "reordered", "stale_read", "twice"]
)
def test_reference_replay_is_exact(fault):
    wl = kv.Workload(SEED, 4, 64)
    updates, reads = _history(wl, 0, 80)
    # two updates of one key and field, to swap
    seen, pair = {}, None
    for i, (_n, cmd) in enumerate(updates):
        at = cmd[:32]
        if at in seen and cmd != updates[seen[at]][1]:
            pair = (seen[at], i)
        seen[at] = i
    assert pair is not None
    if fault == "dropped":
        del updates[pair[0]]
    elif fault == "reordered":
        a, b = pair
        updates[a], updates[b] = (
            (updates[a][0], updates[b][1]), (updates[b][0], updates[a][1]),
        )
    elif fault == "stale_read":  # the record as the older update left it
        a, b = pair
        reads[b] = (reads[b][0], reads[b][1], reads[a][2])
    elif fault == "twice":
        updates.append(updates[-1])
    wrong = _fresh_reference(wl, 0).replay(updates, reads)
    assert (wrong == 0) == (fault == "none"), wrong


# --------------------------------------------------------------- the stream
def test_same_seed_same_stream():
    a, b = kv.Workload(SEED, 8, 256), kv.Workload(SEED, 8, 256)
    other = kv.Workload(SEED + 1, 8, 256)
    ops = [a.op(i) for i in range(70000)]  # over a block's edge
    assert ops == [b.op(i) for i in range(70000)]
    assert ops != [other.op(i) for i in range(70000)]
    for g in range(8):
        assert a.cmds(g, 0, 300) == b.cmds(g, 0, 300)
        assert [a.read_slot(g, j) for j in range(300)] == [
            b.read_slot(g, j) for j in range(300)
        ]
    assert a.cmds(0, 32, 300) != other.cmds(0, 32, 300)
    assert a.key(0, 0) != other.key(0, 0)
    shares = [is_read for is_read, _g in ops]
    assert abs(sum(shares) / len(shares) - 0.5) < 0.01


def test_a_groups_rows_do_not_depend_on_how_they_are_asked_for():
    a, b = kv.Workload(SEED, 8, 256), kv.Workload(SEED, 8, 256)
    whole = a.cmds(5, 0, 700)
    pieces = []
    for lo in range(699, -1, -1):  # one at a time, backwards
        pieces[:0] = b.cmds(5, lo, lo + 1)
    assert pieces == whole
    assert a.sum64(5, 700) == sum(kv.sum64(c) for c in whole) % 2 ** 64
    assert b.sum64(5, 20) == sum(kv.sum64(c) for c in whole[:20]) % 2 ** 64


def _java_fnvhash64(val: int) -> int:
    """site.ycsb.Utils.fnvhash64 in Java's signed 64-bit arithmetic."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (val & 0xFF)) * 1099511628211) & (2 ** 64 - 1)
        val >>= 8
    return abs(h - 2 ** 64 if h >= 2 ** 63 else h)


def _ycsb_next_keys(rng, n: int, recordcount: int) -> list:
    """ScrambledZipfianGenerator.nextValue as YCSB runs it: a rank from
    ZipfianGenerator.nextLong over ITEM_COUNT items (Gray et al.'s
    method, constant 0.99, zeta(ITEM_COUNT) = ZETAN), then fnvhash64 of
    the rank modulo the table's size."""
    items, theta, zetan = kv.ITEM_COUNT, 0.99, kv.ZETAN
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2 / zetan)
    keys = []
    for u in rng.random(n).tolist():
        uz = u * zetan
        if uz < 1.0:
            rank = 0
        elif uz < zeta2:
            rank = 1
        else:
            rank = int(items * (eta * u - eta + 1) ** alpha)
        keys.append(_java_fnvhash64(rank) % recordcount)
    return keys


def test_zetan_is_the_sum_it_stands_for():
    head = (np.arange(1, 2 ** 22 + 1, dtype=np.float64) ** -0.99).sum()
    total = head + kv.zeta(2 ** 22, kv.ITEM_COUNT, 0.99)
    assert abs(total - kv.ZETAN) < 1e-8


def test_fnvhash64_is_javas():
    ranks = [0, 1, 2, 255, 256, 131071, 2 ** 22, kv.ITEM_COUNT - 1]
    assert kv.fnvhash64(np.array(ranks, np.uint64)).tolist() == [
        _java_fnvhash64(r) for r in ranks
    ]


def test_the_popularity_is_ycsbs_scrambled_zipfian():
    """The table p against YCSB's own generator, ported line by line and
    run for 400 000 draws over 256 keys. Bound: the two hottest keys
    within 5 standard deviations of their binomial expectation, the whole
    histogram within 0.02 in total variation (its own sampling noise is
    0.01), and the hottest key takes 1 / ZETAN of the draws, not the
    1 / H(recordcount) of a Zipfian over the table."""
    records, draws = 256, 400_000
    p = kv.scrambled_zipfian(records, 0.99)
    assert abs(p.sum() - 1.0) < 1e-12
    assert abs(p.max() - (1 / kv.ZETAN + (1 - 17.05 / kv.ZETAN) / records)) < 2e-3
    keys = _ycsb_next_keys(np.random.default_rng(SEED), draws, records)
    counts = np.bincount(keys, minlength=records)
    top = np.argsort(-p)[:2]
    assert top.tolist() == [_java_fnvhash64(0) % records, _java_fnvhash64(1) % records]
    sd = np.sqrt(draws * p * (1 - p))
    assert (np.abs(counts - draws * p)[top] < 5 * sd[top]).all()
    assert 0.5 * np.abs(counts / draws - p).sum() < 0.02


def test_group_then_conditional_draw_reproduces_the_global_popularity():
    """An operation picks its group by the groups' shares and the group
    its key by its own conditional distribution; together that is the
    scrambled Zipfian. Bound: each of the 32 most popular keys within 5
    standard deviations of its binomial expectation over 200 000 draws
    (one in 10^5 runs would fail by chance if the seed were not fixed),
    and the whole histogram within 0.02 in total variation of p."""
    groups, records, draws = 8, 256, 200_000
    wl = kv.Workload(SEED, groups, records)
    p = wl.p_item
    counts = np.zeros(records)
    updates = [0] * groups
    reads = [0] * groups
    for i in range(draws):
        is_read, g = wl.op(i)
        if is_read:
            counts[wl.item(g, wl.read_slot(g, reads[g]))] += 1
            reads[g] += 1
        else:
            updates[g] += 1
    for g in range(groups):  # the updates' keys, from the commands
        keys = {wl.key(g, s): wl.item(g, s) for s in range(wl.per_group)}
        for cmd in wl.cmds(g, wl.per_group, wl.per_group + updates[g]):
            counts[keys[cmd[8:32]]] += 1
    assert counts.sum() == draws
    hot = wl.by_popularity[:32]
    sd = np.sqrt(draws * p * (1 - p))
    assert (np.abs(counts - draws * p)[hot] < 5 * sd[hot]).all()
    assert 0.5 * np.abs(counts / draws - p).sum() < 0.02


def test_the_hottest_group_at_the_cells_size():
    """YCSB's hottest key takes 1 / ZETAN = 3.8 % whatever the table's
    size, and its group little more: half of what a Zipfian over the
    131 072 records alone would give it (1 / H = 7.6 %)."""
    wl = kv.Workload(SEED, 1024, 131072)
    assert wl.per_group == 128
    assert 0.0375 <= wl.p_item.max() <= 0.0380
    assert 0.038 <= wl.group_share.max() <= 0.042
    assert abs(wl.group_share.sum() - 1.0) < 1e-9
    hot_item = int(wl.by_popularity[0])
    assert hot_item == _java_fnvhash64(0) % 131072
    assert int(np.argmax(wl.group_share)) == hot_item % 1024
    # 8 192 clients: the hottest lane holds hundreds, the median a handful
    assert wl.group_share.max() * 8192 > 255
    assert 5 <= np.median(wl.group_share) * 8192 <= 8
    assert len({wl.key(g, s) for g in range(0, 1024, 97) for s in range(128)}) \
        == 11 * 128


# ----------------------------------------- the system against the reference
GROUPS, RECORDS, CLIENTS, OPS = 8, 256, 64, 600


class _Tampering(kv.StateMachine):
    """kvrecords with a fault between a command and the table: the
    counts and sums stay right, so only a comparison of records sees it."""

    def update(self, entries):
        with self._mu:
            n, acc = self.state
            for e in entries:
                n += 1
                for cmd in self.commands(e.cmd, n):
                    self.table.apply(cmd)
                acc += kv.sum64(e.cmd)
                e.result = kv.Result(value=n)
            self.state = (n, acc & (2 ** 64 - 1))
        return entries


class _DropsOne(_Tampering):
    """Replica 2 loses the 40th command of every group, an update."""

    def commands(self, cmd, n):
        return () if self.node_id == 2 and n == 40 else (cmd,)


class _AppliesOutOfOrder(_Tampering):
    """Every replica applies the update of a field before this one once
    more after it: two updates of one key out of order."""

    def commands(self, cmd, n):
        older = self.seen.get(cmd[:32])
        self.seen[cmd[:32]] = cmd
        return (cmd,) if older is None or cmd[0] != kv.OP_UPDATE else (cmd, older)


def _factory(cls):
    def make(cluster_id, node_id):
        sm = cls(cluster_id, node_id)
        sm.node_id, sm.seen = node_id, {}
        return sm
    return make


class _Cluster:
    def __init__(self, tmp, name, sm_cls, rtt_ms=5, election_rtt=20,
                 heartbeat_rtt=2, **engine):
        from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
        from dragonboat_tpu.nodehost import NodeHost
        from dragonboat_tpu.transport.loopback import (
            _Registry,
            loopback_factory,
        )

        reg = _Registry()
        members = {n: f"ycsb{n}:1" for n in (1, 2, 3)}
        self.hosts = {}
        for n, addr in members.items():
            self.hosts[n] = NodeHost(NodeHostConfig(
                deployment_id=1, rtt_millisecond=rtt_ms, raft_address=addr,
                nodehost_dir=str(tmp / f"nh{n}"),
                raft_rpc_factory=lambda a: loopback_factory(a, reg),
                engine=EngineConfig(
                    kind="vector", max_groups=3 * GROUPS, max_peers=4,
                    log_window=32, inbox_depth=4, max_entries_per_msg=8,
                    readindex_depth=8, share_scope=f"ycsb-{name}",
                    profile_sample_ratio=1, **engine,
                ),
            ))
        try:
            for n, nh in self.hosts.items():
                nh.start_clusters([
                    (dict(members), False, _factory(sm_cls),
                     Config(cluster_id=g + 1, node_id=n,
                            election_rtt=election_rtt,
                            heartbeat_rtt=heartbeat_rtt))
                    for g in range(GROUPS)
                ])
            self.core = self.hosts[1].engine.core
            self.leaders = [self._leader(g) for g in range(GROUPS)]
        except BaseException:
            self.stop()
            raise

    def _leader(self, g):
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            lid, ok = self.hosts[1].get_leader_id(g + 1)
            if ok and lid:
                return lid
            time.sleep(0.02)
        raise AssertionError(f"group {g + 1} elected no leader")

    def stop(self):
        for nh in self.hosts.values():
            nh.stop()


def _run_workload(cluster, wl):
    """YCSB's load phase, then CLIENTS clients with one operation each
    until OPS were issued. Returns per group the acknowledged updates
    [(n, cmd)] and reads [(applied, key, record)], and the history."""
    hosts, leaders = cluster.hosts, cluster.leaders
    used = [wl.per_group] * GROUPS
    for g in range(GROUPS):
        nh = hosts[leaders[g]]
        h = nh.propose_batch_async(
            nh.get_noop_session(g + 1), wl.cmds(g, 0, wl.per_group), 20.0
        )
        assert h.wait(30.0) and h.completed == wl.per_group
    done = deque()
    read_rows = [0] * GROUPS
    updates = [[] for _ in range(GROUPS)]
    reads = [[] for _ in range(GROUPS)]
    history = []
    issued = 0

    def issue(c):
        nonlocal issued
        is_read, g = wl.op(issued)
        issued += 1
        nh = hosts[leaders[g]]
        if is_read:
            key = wl.key(g, wl.read_slot(g, read_rows[g]))
            read_rows[g] += 1
            op = Operation(c, ("get", (g, key)), invoke=time.monotonic(),
                           op_id=issued)

            def read_done(rs, nh=nh, g=g, key=key, op=op):
                out = nh.read_local_node(g + 1, key) \
                    if rs.result.completed else None
                done.append((op, time.monotonic(), out))

            nh.read_index(g + 1, 20.0).on_complete(read_done)
        else:
            (cmd,) = wl.cmds(g, used[g], used[g] + 1)
            used[g] += 1
            op = Operation(
                c, ("put", (g, cmd[8:32]), (cmd[1], cmd[32:132]), cmd),
                invoke=time.monotonic(), op_id=issued,
            )

            def update_done(rs, op=op):
                r = rs.result
                done.append((op, time.monotonic(),
                             r.result.value if r.completed else None))

            nh.propose(nh.get_noop_session(g + 1), cmd, 20.0).on_complete(
                update_done
            )

    for c in range(CLIENTS):
        issue(c)
    outstanding = CLIENTS
    deadline = time.monotonic() + 120
    while outstanding:
        assert time.monotonic() < deadline, "the closed loop stalled"
        if not done:
            time.sleep(0.002)
            continue
        op, t, out = done.popleft()
        outstanding -= 1
        assert out is not None, f"{op.input[0]} of client {op.client} failed"
        g = op.input[1][0]
        if op.input[0] == "get":
            record, applied = out
            reads[g].append((applied, op.input[1][1], record))
            op.output = record
        else:
            updates[g].append((out, op.input[3]))
        op.ret = t
        history.append(op)
        if issued < OPS:
            issue(op.client)
            outstanding += 1
    return used, updates, reads, history


def _record_model(first):
    """One record as a register of ten fields; `first` maps a key to the
    record its insert left."""

    def step(state, inp, output):
        if state is None:
            state = first[inp[1]]
        if inp[0] == "put":
            f, value = inp[2]
            return True, state[:f] + (value,) + state[f + 1:]
        return output is UNKNOWN or output == state, state

    return Model(init=lambda: None, step=step)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    c = _Cluster(tmp_path_factory.mktemp("sound"), "sound", kv.StateMachine)
    wl = kv.Workload(SEED, GROUPS, RECORDS)
    try:
        yield c, wl, _run_workload(c, wl)
    finally:
        c.stop()


def test_every_read_is_the_reference_at_its_applied(sound):
    _c, wl, (used, updates, reads, _h) = sound
    assert sum(map(len, reads)) > OPS // 3
    for g in range(GROUPS):
        assert sorted(n for n, _cmd in updates[g]) == list(
            range(wl.per_group + 1, used[g] + 1)
        ), "every update was answered with its own apply sequence number"
        assert _fresh_reference(wl, g).replay(updates[g], reads[g]) == 0


def test_final_records_on_all_three_replicas(sound):
    c, wl, (used, updates, _reads, _h) = sound
    for g in range(GROUPS):
        ref = _fresh_reference(wl, g)
        assert ref.replay(updates[g], []) == 0
        want = (used[g], wl.sum64(g, used[g]))
        deadline = time.monotonic() + 30
        while {nh.stale_read(g + 1, None) for nh in c.hosts.values()} != {want}:
            assert time.monotonic() < deadline, f"group {g + 1} diverged"
            time.sleep(0.02)
        for nh in c.hosts.values():
            for slot in range(wl.per_group):
                key = wl.key(g, slot)
                assert nh.stale_read(g + 1, key) == (ref.lookup(key), used[g])
        lead = c.hosts[c.leaders[g]]
        assert lead.sync_read(g + 1, wl.key(g, 0), 20.0) == (
            ref.lookup(wl.key(g, 0)), used[g]
        )


def test_history_is_linearizable(sound):
    _c, wl, (_used, _updates, _reads, history) = sound
    first = {}
    for g in range(GROUPS):
        ref = _fresh_reference(wl, g)
        first.update({(g, k): rec for k, rec in ref.table.rows.items()})
    model = _record_model(first)
    parts = partition_by_key(history)
    assert max(map(len, parts)) > 20, "no hot key in the history"
    for part in parts:
        assert check_linearizable(model, part)
    # and the checker sees a stale read: hand the hottest key's last
    # read the record as its insert left it
    hot = max(parts, key=len)
    assert any(op.input[0] == "put" for op in hot)
    last = max((op for op in hot if op.input[0] == "get"),
               key=lambda op: op.invoke)
    assert last.output != first[last.input[1]]
    last.output = first[last.input[1]]
    assert not check_linearizable(model, hot)


def test_uneven_lanes_show_in_the_counters(sound):
    """The traffic above, seen by the n.* counters: W = 32 and 64 clients
    put more on the hot lane than its window's free space."""
    c, _wl, (_used, updates, reads, _h) = sound
    s = c.core.profiler.samples

    def total(name):
        return round(s[name].mean() * len(s[name]))

    n_updates, n_reads = sum(map(len, updates)), sum(map(len, reads))
    launches = total("n.packs")
    assert {len(s["n." + k]) for k in (
        "lanes_packed", "entries_packed", "hot_lane_entries",
        "lanes_window_cut", "staged_left", "reads_bound", "read_contexts",
    )} == {launches}
    assert total("n.entries_packed") == RECORDS + n_updates
    # the workload's reads and one a group of the tests before this one
    assert n_reads <= total("n.reads_bound") <= n_reads + GROUPS
    assert 0 < total("n.read_contexts") < n_reads  # many reads a context
    assert total("n.hot_lane_entries") <= total("n.entries_packed")
    # leader lanes with their clients' rows: never more than the rows
    assert 0 < total("n.lanes_packed") <= (
        total("n.entries_packed") + total("n.read_contexts")
    )
    assert total("n.lanes_window_cut") > 0 and total("n.staged_left") > 0
    # every replica saves every command
    bytes_in = 3 * (RECORDS * 1032 + n_updates * 136)
    assert bytes_in <= total("n.save_bytes") < bytes_in + 4096  # + bootstrap


@pytest.mark.parametrize("sm_cls", [_DropsOne, _AppliesOutOfOrder])
def test_a_broken_state_machine_is_found(sm_cls, tmp_path):
    c = _Cluster(tmp_path, sm_cls.__name__, sm_cls)
    wl = kv.Workload(SEED, GROUPS, RECORDS)
    try:
        used, updates, reads, _h = _run_workload(c, wl)
        wrong = 0
        for g in range(GROUPS):
            ref = _fresh_reference(wl, g)
            wrong += ref.replay(updates[g], reads[g])
            for nh in c.hosts.values():
                deadline = time.monotonic() + 30
                while nh.stale_read(g + 1, None)[0] < used[g]:
                    assert time.monotonic() < deadline
                    time.sleep(0.02)
                for slot in range(wl.per_group):
                    key = wl.key(g, slot)
                    wrong += nh.stale_read(g + 1, key)[0] != ref.lookup(key)
        assert wrong > 0
    finally:
        c.stop()


@pytest.mark.parametrize("overlap, steps", [
    (False, 1), (True, 1), (True, None),
], ids=["False", "True", "auto"])
def test_a_saturated_lane_fills_every_launch(overlap, steps, tmp_path):
    """One group offered far more than a launch can take: its leader
    puts a row of E = 8 proposals into (nearly) every launch, in the
    plain one-step loop and in the overlapped one, through the inbox
    slot kept for its proposals. Left to its followers' acknowledgements
    the inbox let a window's worth (W - 1 = 31) through every eight
    launches, under 4 a launch. With the steps left to the engine
    (three a launch here, the acknowledgements arriving through the
    residual rows) a launch takes at least as much."""
    # a heartbeat a millisecond: every launch brings the leader both
    # followers' heartbeat responses beside their Replicate responses,
    # as a 0.5 s step does at the benchmark's 200 ms heartbeats
    c = _Cluster(tmp_path, f"saturated-{overlap}-{steps}", kv.StateMachine,
                 rtt_ms=1, election_rtt=400, heartbeat_rtt=1,
                 overlap_decode=overlap, steps_per_sync=steps)
    try:
        deadline = time.monotonic() + 60
        while c.core._multi != (steps or 3):  # auto: at a launch boundary
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert c.core._overlap == (overlap and steps == 1)
        wl = kv.Workload(SEED, GROUPS, RECORDS)
        nh = c.hosts[c.leaders[0]]
        n = 640
        first = c.core.launch_no
        h = nh.propose_batch_async(
            nh.get_noop_session(1), wl.cmds(0, 0, n), 60.0
        )
        assert h.wait(90.0) and h.completed == n
        launches = c.core.launch_no - first
        assert n / launches >= 0.7 * 8, (n, launches)
        want = (n, wl.sum64(0, n))
        deadline = time.monotonic() + 30
        while {x.stale_read(1, None) for x in c.hosts.values()} != {want}:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert c.core.step_stats()["loop_exceptions"] == 0
    finally:
        c.stop()


# ---------------------------------------------------- the counters of a pack
PACKS = {
    # staged proposals, free window slots -> entries packed, left, cut
    "even": (5, 40, 5, 0, 0),
    "cut_by_the_window": (25, 10, 10, 15, 1),
    "cut_by_the_inbox": (40, 60, 32, 8, 1),  # K = 4 rows of E = 8
    "window_full": (3, 0, 0, 3, 1),
}


@pytest.fixture()
def stopped_leader(tmp_path):
    """A single-replica group whose engine loop has stopped, so a test
    can build a pack by hand: (core, lane, node)."""
    from tests.test_profile import _single_host

    with _single_host(
        tmp_path, inbox_depth=4, max_entries_per_msg=8,
        profile_sample_ratio=1 << 30,
    ) as nh:
        core = nh.engine.core
        core._stopped.set()
        core._ready.set()
        (loop,) = [t for t in core._threads if t.name == "vec-step"]
        loop.join(30)
        assert not loop.is_alive()
        (lane,) = [ln for ln in core._lanes.values() if ln.active]
        assert lane.packed_pending == 0 and not lane.has_staged()
        assert core.profiler.samples == {}
        yield core, lane, lane.node


def _pack_with(core, lane, staged, free):
    W = core.kcfg.log_window
    g = lane.g
    core._m_devfirst[g] = 1
    core._m_last[g] = W - 1 - free  # free = W - 1 - (last - devfirst + 1)
    lane.staged_props.extend(Entry(cmd=b"x" * 136) for _ in range(staged))
    core._carry.discard(lane)
    return core._pack({lane})


@pytest.mark.parametrize("case", list(PACKS))
def test_pack_counters(case, stopped_leader):
    core, lane, _node = stopped_leader
    staged, free, packed, left, cut = PACKS[case]
    core.profiler.sampling = True
    had, _packs = _pack_with(core, lane, staged, free)
    s = core.profiler.samples
    # the one leader lane is the fullest: what it holds uncommitted, in
    # rows of E = 8; its commit has moved by nothing it was seen at before
    waiting = int(core._m_last[lane.g] - core._m_commit[lane.g])
    if not packed:
        # nothing staged a row: no launch follows, nothing is recorded
        assert not had and not any(k.startswith("n.") for k in s)
        assert len(lane.staged_props) == left
        return
    got = {k[2:]: (len(v), v.mean()) for k, v in s.items() if k[:2] == "n."}
    assert got == {
        "packs": (1, 1.0),
        "lanes_packed": (1, 1.0),
        "entries_packed": (1, float(packed)),
        "hot_lane_entries": (1, float(packed)),
        "lanes_window_cut": (1, float(cut)),  # a cut lane counts once
        "staged_left": (1, float(left)),
        "reads_bound": (1, 0.0),
        "read_contexts": (1, 0.0),
        "hot_commit_entries": (1, 0.0),
        "hot_uncommitted_rows": (1, float(-(-waiting // 8))),
    }
    assert len(lane.staged_props) == left


def test_pack_counts_the_reads_of_a_context(stopped_leader):
    core, lane, node = stopped_leader
    for _ in range(7):
        node.read(100)
    core.profiler.sampling = True
    had, _packs = _pack_with(core, lane, 2, 40)
    assert had
    s = core.profiler.samples
    assert s["n.reads_bound"].mean() == 7.0
    assert s["n.read_contexts"].mean() == 1.0
    assert s["n.entries_packed"].mean() == 2.0
    assert s["n.lanes_packed"].mean() == 1.0


def test_an_unsampled_pack_records_nothing(stopped_leader):
    core, lane, node = stopped_leader
    node.read(100)
    assert not core.profiler.sampling
    had, _packs = _pack_with(core, lane, 25, 10)
    assert had and len(lane.staged_props) == 15
    assert core.profiler.samples == {}


# ------------------------------------- a leader's inbox under slot pressure
def _ack(kind, from_, index=0, term=1, reject=False, hint=0):
    from dragonboat_tpu.types import Message
    from dragonboat_tpu.types import MessageType as MT

    return Message(type=getattr(MT, kind), from_=from_, to=1, cluster_id=1,
                   term=term, log_index=index, reject=reject, hint=hint)


def test_protocol_rows_are_no_clients_work(stopped_leader):
    """n.lanes_packed counts a leader lane for a row of its clients'
    proposals or a ReadIndex context, never for the protocol's own rows:
    every lane stages one of those in every launch."""
    core, lane, node = stopped_leader
    lane.slots.update({2: 1, 3: 2})  # two followers, by hand
    lane.rev.update({1: 2, 2: 3})
    core.profiler.sampling = True
    lane.msg_backlog.extend([_ack("REPLICATE_RESP", 2, 5), _ack("HEARTBEAT_RESP", 3)])
    had, _packs = _pack_with(core, lane, 0, 40)
    s = core.profiler.samples
    assert had and s["n.packs"].mean() == 1.0
    assert s["n.lanes_packed"].mean() == 0.0 == s["n.entries_packed"].mean()
    node.read(100)
    _pack_with(core, lane, 0, 40)
    assert round(s["n.lanes_packed"].mean() * len(s["n.lanes_packed"])) == 1


def test_coalesce_acks_folds_and_takes_turns():
    from dragonboat_tpu.engine.vector import _coalesce_acks

    vote = _ack("REQUEST_VOTE_RESP", 3)
    rej = _ack("REPLICATE_RESP", 2, 7, reject=True)
    q = deque([
        _ack("REPLICATE_RESP", 3, 10), _ack("HEARTBEAT_RESP", 3, hint=5),
        _ack("REPLICATE_RESP", 2, 9), vote, rej,
        _ack("REPLICATE_RESP", 3, 12), _ack("HEARTBEAT_RESP", 3, hint=6),
        _ack("REPLICATE_RESP", 3, 11, term=2), _ack("REPLICATE_RESP", 2, 14),
    ])
    _coalesce_acks(q)
    assert [(m.type.name, m.from_, m.term, m.log_index or m.hint) for m in q] == [
        # what is no acknowledgement first, as it came
        ("REQUEST_VOTE_RESP", 3, 1, 0), ("REPLICATE_RESP", 2, 1, 7),
        # then the two kinds in turn, each the newest of its sender and
        # term in the order their oldest came
        ("REPLICATE_RESP", 3, 1, 12), ("HEARTBEAT_RESP", 3, 1, 6),
        ("REPLICATE_RESP", 2, 1, 14), ("REPLICATE_RESP", 3, 2, 11),
    ]
    assert q[0] is vote and q[1] is rej
    once = list(q)
    _coalesce_acks(q)  # nothing left to fold: untouched
    assert list(q) == once


INBOXES = {
    # staged proposals, free window slots, reads -> the lane's four rows,
    # wire messages left waiting
    "its_own_work_keeps_two_slots":
        (5, 40, 2, ["REPLICATE_RESP", "HEARTBEAT_RESP", "PROPOSE", "READ_INDEX"], 2),
    "reads_alone_keep_one":
        (0, 40, 2, ["REPLICATE_RESP", "HEARTBEAT_RESP", "REPLICATE_RESP", "READ_INDEX"], 1),
    "proposals_alone_keep_one":
        (5, 40, 0, ["REPLICATE_RESP", "HEARTBEAT_RESP", "REPLICATE_RESP", "PROPOSE"], 1),
    "a_full_window_keeps_none":
        (5, 0, 0, ["REPLICATE_RESP", "HEARTBEAT_RESP", "REPLICATE_RESP", "HEARTBEAT_RESP"], 0),
    "nothing_staged_nothing_kept":
        (0, 40, 0, ["REPLICATE_RESP", "HEARTBEAT_RESP", "REPLICATE_RESP", "HEARTBEAT_RESP"], 0),
}


@pytest.mark.parametrize("case", list(INBOXES))
def test_a_leaders_acknowledgements_leave_room_for_its_own_work(
    case, stopped_leader
):
    from dragonboat_tpu.ops.state import MSG

    core, lane, node = stopped_leader
    staged, free, n_reads, rows, waiting = INBOXES[case]
    lane.slots.update({2: 1, 3: 2})  # two followers, by hand
    lane.rev.update({1: 2, 2: 3})
    for _ in range(n_reads):
        node.read(100)
    lane.msg_backlog.extend([
        _ack("REPLICATE_RESP", 2, 5), _ack("HEARTBEAT_RESP", 2),
        _ack("REPLICATE_RESP", 3, 5), _ack("HEARTBEAT_RESP", 3),
    ])
    had, _packs = _pack_with(core, lane, staged, free)
    names = {int(getattr(MSG, n)): n for n in dir(MSG) if n.isupper()}
    assert had
    assert [names[t] for t in core._buf["mtype"][lane.g].tolist()] == rows
    assert len(lane.msg_backlog) == waiting
    if waiting == 2:
        # the next step's answers fold into the ones that waited, and the
        # follower that waited goes first
        lane.msg_backlog.extend([
            _ack("REPLICATE_RESP", 2, 8), _ack("HEARTBEAT_RESP", 2),
            _ack("REPLICATE_RESP", 3, 8), _ack("HEARTBEAT_RESP", 3),
        ])
        node.read(100)
        lane.packed_pending = 0
        _pack_with(core, lane, 5, 40)
        buf = core._buf
        assert buf["from_slot"][lane.g].tolist()[:2] == [2, 2]
        assert buf["log_index"][lane.g].tolist()[0] == 8 - int(core._m_base[lane.g])
        assert [(m.from_, m.log_index) for m in lane.msg_backlog] == [(2, 8), (2, 0)]
        assert [m.type.name for m in lane.msg_backlog] == [
            "REPLICATE_RESP", "HEARTBEAT_RESP",
        ]
