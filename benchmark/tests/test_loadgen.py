"""The generators against a fake server, and the ledger as reference."""
import time

import pytest

import run
from benchmark.lib import check, loadgen

open_loop = run.load_plugin("generators", "open_loop_rate")
closed_loop = run.load_plugin("generators", "closed_loop_batch")

PARAMS = {
    "rate_ops_per_s": 2000, "reads_per_write": 0, "slot_ms": 2,
    "timeout_s": 2, "run_in_s": 0.2, "warm_batches": 1, "warm_batch": 4,
}


class Handle:
    def __init__(self, n, ready_at):
        self.n = self.completed = n
        self._ready_at = ready_at

    @property
    def finished(self):
        return time.monotonic() >= self._ready_at


class Host:
    """Acknowledges a batch `service_s` after it was submitted, but
    nothing between `stall` = (from, to) on the clock; submitting itself
    blocks for `submit_block_s` once, at `block_at`."""

    def __init__(self, service_s=0.01, stall=None, block_at=None,
                 submit_block_s=0.0):
        self.service_s, self.stall = service_s, stall
        self.block_at, self.submit_block_s = block_at, submit_block_s
        self.submitted = 0

    def propose_batch_async(self, session, cmds, timeout_s):
        now = time.monotonic()
        if self.block_at is not None and now >= self.block_at:
            self.block_at = None
            time.sleep(self.submit_block_s)
        ready = now + self.service_s
        if self.stall and self.stall[0] <= ready < self.stall[1]:
            ready = self.stall[1]
        self.submitted += len(cmds)
        return Handle(len(cmds), ready)


class Cluster:
    def __init__(self, host, groups):
        self.hosts = {1: host}
        self.groups = groups

    def leaders(self):
        return [1] * self.groups

    def session(self, nid, g):
        return None


def _run_open(host, seconds=1.0, seed=5):
    groups = 4
    ledger = loadgen.Ledger(loadgen.Payloads(seed, groups), groups)
    gen = open_loop.Generator(PARAMS, groups, ledger, seed, seconds, 1.0)
    gen.measure(Cluster(host, groups), lambda t: None, lambda t: None)
    return gen, gen.results()


def test_open_loop_offers_the_fixed_amount_of_work():
    _gen, res = _run_open(Host())
    assert res["attempted"] == 2000 and res["failed"] == 0
    assert res["committed_ops_per_s"] == 2000.0
    # service 10 ms + up to one 2 ms slot + the look's resolution
    assert 10.0 <= res["commit_latency_p50_ms"] < 30.0
    assert res["client.late_p99_ms"] < 20.0


def test_a_stalled_server_raises_latency_and_does_not_lower_the_load():
    now = time.monotonic()
    host = Host(stall=(now + 0.5, now + 0.9))
    gen, res = _run_open(host)
    # every operation of the schedule went down, on schedule
    assert res["attempted"] == 2000 and host.submitted == gen.n
    assert res["client.late_p99_ms"] < 20.0
    # and the ones due during the stall waited it out: the tail shows it
    assert res["client.commit_latency_p99_ms"] > 300.0
    assert res["commit_latency_p50_ms"] < 100.0


def test_a_stalled_generator_is_reported_and_counts_from_due_time():
    now = time.monotonic()
    host = Host(block_at=now + 0.6, submit_block_s=0.3)
    _gen, res = _run_open(host)
    assert res["attempted"] == 2000
    assert res["client.late_p99_ms"] > 200.0
    # operations issued late are still timed from when they were due
    assert res["client.commit_latency_p99_ms"] > 200.0


def test_same_seed_same_inputs():
    a = loadgen.Payloads(7, 3)
    b = loadgen.Payloads(7, 3)
    assert a.cmds(2, 0, 5000) == b.cmds(2, 0, 5000)
    assert a.cmds(2, 0, 8) != loadgen.Payloads(8, 3).cmds(2, 0, 8)
    assert a.cmds(2, 0, 8) != a.cmds(1, 0, 8)
    total = 0
    for cmd in a.cmds(2, 0, 5000):
        total += int.from_bytes(cmd[:8], "little")
        total += int.from_bytes(cmd[8:], "little")
    assert a.sum64(2, 5000) == total & ((1 << 64) - 1)
    g1 = open_loop.Generator(PARAMS, 4, None, 3, 1.0, 1.0)
    g2 = open_loop.Generator(PARAMS, 4, None, 3, 1.0, 1.0)
    assert (g1.due, g1.group) == (g2.due, g2.group)


def _ledger_with(rows):
    ledger = loadgen.Ledger(loadgen.Payloads(3, 1), 1)
    lo, hi, _cmds = ledger.take(0, rows)
    ledger.settle(0, lo, hi, rows, 0)
    return ledger


def test_ledger_rejects_a_lost_and_a_duplicated_write():
    ledger = _ledger_with(4)
    good = ledger.expected(0)
    ledger.check(0, "read", good)
    lost = (3, ledger.payloads.sum64(0, 3))
    with pytest.raises(loadgen.CheckFailure, match="acknowledged"):
        ledger.check(0, "read", lost)
    first = ledger.payloads.cmds(0, 0, 1)[0]
    dup = int.from_bytes(first[:8], "little") + int.from_bytes(first[8:], "little")
    twice = (5, (good[1] + dup) & ((1 << 64) - 1))
    with pytest.raises(loadgen.CheckFailure, match="acknowledged"):
        ledger.check(0, "read", twice)
    # a replaced payload byte keeps the count and breaks the sum
    with pytest.raises(loadgen.CheckFailure):
        ledger.check(0, "read", (4, good[1] ^ 1))
    # a batch cut short leaves a range, and still refuses a lost write
    lo, hi, _ = ledger.take(0, 2)
    ledger.settle(0, lo, hi, 0, 2)
    ledger.check(0, "read", (5, 0))
    with pytest.raises(loadgen.CheckFailure, match=r"outside \[4, 6\]"):
        ledger.check(0, "read", lost)


class ReadHost:
    class _Db:
        def name(self):
            return "sharded-walkv"

        def shard_dirs(self):
            return ["x"]

    class _State:
        class _R:
            completed = True

        def wait(self, t):
            return self._R()

    def __init__(self, state):
        self.state = state
        self.logdb = self._Db()

    def read_index(self, cid, t):
        return self._State()

    def read_local_node(self, cid, q):
        return self.state

    stale_read = read_local_node


def test_read_back_holds_every_host_to_the_reference(monkeypatch):
    monkeypatch.setattr(check, "CONVERGE_S", 0.2)
    ledger = _ledger_with(4)
    good = ledger.expected(0)

    class C:
        groups = 1

        class core:
            @staticmethod
            def step_stats():
                return {"loop_exceptions": 0}

        def wait_leaders(self, s):
            return [1]

    c = C()
    c.hosts = {n: ReadHost(good) for n in (1, 2, 3)}
    assert check.read_back(c, ledger, 1)["groups_exact"] == 1
    c.hosts[2] = ReadHost((3, ledger.payloads.sum64(0, 3)))
    with pytest.raises(loadgen.CheckFailure, match="follower-host"):
        check.read_back(c, ledger, 1)
    c.hosts[2] = ReadHost(good)
    c.hosts[3] = ReadHost((3, ledger.payloads.sum64(0, 3)))
    with pytest.raises(loadgen.CheckFailure, match="did not converge"):
        check.read_back(c, ledger, 1)


def test_closed_loop_rate_is_the_sum_of_the_groups_own_rates():
    gen = closed_loop.Generator(
        {"batch": 64, "timeout_s": 5, "warm_rounds": 1, "poll_ms": 5},
        2, None, 1, 10.0, 1.0,
    )
    gen.t_open, gen.t_close = 100.0, 110.0
    gen.batches = [
        (0, 99.0, 101.0, 64, 0),   # began before the window: not a whole cycle
        (0, 101.0, 103.0, 64, 0), (0, 103.0, 105.0, 64, 0),
        (0, 109.0, 111.0, 64, 0),  # ended after it
        (1, 100.5, 104.5, 64, 0), (1, 104.5, 108.5, 64, 0),
    ]
    res = gen.results()
    assert res["committed_ops_per_s"] == pytest.approx(128 / 4 + 128 / 8)
    assert res["attempted"] == 5 * 64 and res["failed"] == 0
    assert res["client.commit_latency_p50_ms"] == 2000.0
