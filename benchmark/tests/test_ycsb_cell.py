"""The YCSB cell on the CPU: its rehearsal end to end, its readers on a
recorded window, that the generator reads every parameter of its traffic
file, and that a state machine broken on purpose turns `correct` false."""
import json
import os
import shutil
import types

import pytest
from conftest import ROOT
from test_run import _result_lines, _run

from benchmark import run as harness
from benchmark.lib import loadgen

CELL = "ycsb1024.a"
DESCRIPTIVE = {"kind", "what", "who", "warm", "faults"}


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _new_metrics(spec):
    return [
        m for m in spec["per_layer"]
        if m.get("workloads") == [CELL]
        or m["name"] == "storage.save_bytes_per_step"
    ]


def _run_line(stdout):
    (line,) = [ln for ln in stdout.splitlines() if ln.startswith("[run] ")]
    return json.loads(line[6:])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace):
    r = _run(ROOT, "--workload", CELL, "--seed", "2147483999",
             "--seconds", "3", "--trace", str(trace), "--rehearsal")
    assert r.returncode == 0, r.stderr[-2000:]
    (line,) = _result_lines(r.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 200
    client = _run_line(r.stdout)["client"]
    # run.sizes hands the generator 4 groups and 4/1024: the deployment
    # scales, the record does not
    assert client["clients"] == 32
    assert client["records_read_back"] == 4 * 512  # leader + 3 replicas
    assert client["reads_checked"] >= client["reads"] > 50
    assert client["groups_exact"] == 4
    if trace:
        for m in _new_metrics(_spec()):
            assert line["metrics"][m["name"]]["value"] is not None, m["name"]
        assert line["metrics"]["lanes.window_cut_per_step"]["value"] >= 0
        assert "step_batch_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"committed_ops_per_s", "setup_s"}
        assert line["metrics"]["committed_ops_per_s"]["value"] > 0


def test_the_cell_is_the_issues():
    spec = _spec()
    _spec2, cell, config, traffic = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ycsb-1024x3", "ycsb-a.closed8192", 1,
    )
    want = {
        "kind": "ycsb_closed", "clients": 8192, "readproportion": 0.5,
        "updateproportion": 0.5, "requestdistribution": "zipfian",
        "zipfian_constant": 0.99, "recordcount": 131072, "fieldcount": 10,
        "fieldlength": 100, "readallfields": True, "writeallfields": False,
        "timeout_s": 15, "poll_ms": 5, "load_batch": 64, "warm_ops": 2,
        "readback_keys": 4096, "readback_hot": 64,
    }
    assert {k: traffic[k] for k in want} == want
    fleet = harness.load_json(ROOT, "benchmark/configs/fleet-1024x3.json")
    for part in ("deployment", "nodehost", "raft", "guarantees"):
        got, ref = dict(config[part]), dict(fleet[part])
        got.pop("layout", None), ref.pop("layout", None)
        assert got == ref, part
    assert config["engine"] == dict(fleet["engine"], readindex_depth=8)
    assert config["statemachine"] == "kvrecords"
    assert config["reduced"] == ["recordcount"] == next(
        c["reduced"] for c in spec["configs"] if c["name"] == "ycsb-1024x3"
    )
    assert config["recordcount"] == traffic["recordcount"]
    assert len(_new_metrics(spec)) == 11
    assert {m["moves"] for m in _new_metrics(spec)} == {"committed_ops_per_s"}


def test_readers_on_a_recorded_window():
    counts = {
        "n.lanes_packed": 5800.0, "n.entries_packed": 11600.0,
        "n.hot_lane_entries": 700.0, "n.lanes_window_cut": 3000.0,
        "n.staged_left": 75000.0, "n.reads_bound": 5600.0,
        "n.read_contexts": 2000.0, "n.save_bytes": 2.4e6, "n.packs": 10.0,
    }
    client = {
        "client.ycsb_update_p50_ms": 2100.0, "client.ycsb_read_p50_ms": 2200.0,
        "client.ycsb_hot_group_share": 0.039, "client.ycsb_issue_ms_p99": 600.0,
    }
    run = types.SimpleNamespace(
        client=client,
        window={"phase_ratio": 1, "phases": counts, "launches": 10.0},
    )
    want = {
        "lanes.active_per_step": 580.0, "lanes.entries_per_active_lane": 2.0,
        "lanes.hot_lane_entries_per_step": 70.0,
        "lanes.window_cut_per_step": 300.0,
        "lanes.staged_left_per_step": 7500.0, "reads.per_context": 2.8,
        "storage.save_bytes_per_step": 2.4e5, **client,
    }
    readers = {
        m["name"]: harness.load_plugin("layer_metrics", m["name"]).read
        for m in _new_metrics(_spec())
    }
    assert set(readers) == set(want)
    for name, read in readers.items():
        assert read(run) == pytest.approx(want[name]), name
    # a program without the counters, a run below full sampling, a cell
    # without reads, a generator without the numbers: nothing, no raise
    older = types.SimpleNamespace(
        client={}, window={"phase_ratio": 1, "phases": {}, "launches": 10.0}
    )
    sparse = types.SimpleNamespace(client={}, window=dict(run.window, phase_ratio=16))
    idle = types.SimpleNamespace(client={}, window=dict(
        run.window, launches=0.0, phases=dict(counts, **{"n.packs": 0.0})
    ))
    no_reads = types.SimpleNamespace(client={}, window=dict(
        run.window, phases=dict(counts, **{"n.read_contexts": 0.0})
    ))
    for name, read in readers.items():
        assert read(older) is None and read(sparse) is None, name
    assert readers["lanes.active_per_step"](idle) is None
    assert readers["storage.save_bytes_per_step"](idle) is None
    assert readers["reads.per_context"](no_reads) is None


class _Watched(dict):
    def __init__(self, *a):
        super().__init__(*a)
        self.read = set()

    def __getitem__(self, key):
        self.read.add(key)
        return super().__getitem__(key)


def _generator(seconds=2.0):
    _spec2, _cell, config, traffic = harness.load_cell(CELL)
    params = _Watched(traffic)
    groups, scale = harness.sizes(config, rehearsal=True)
    ledger = loadgen.Ledger(loadgen.Payloads(7, groups), groups)
    gen = harness.load_plugin("generators", traffic["kind"]).Generator(
        params, groups, ledger, 7, seconds, scale
    )
    return gen, params, traffic, groups, ledger, scale


def test_the_rate_is_all_the_work_over_all_the_window():
    """An acknowledged operation is one unit of work spread evenly over
    its life, and the rate is the work inside [t_open, t_close) over its
    length: an operation that straddles an edge counts by its share
    inside, a failed one nothing. `completed_in_window_per_s` counts
    whole operations by the instant of their acknowledgement."""
    gen = _generator(10.0)[0]
    gen.t_open, gen.t_close = 100.0, 110.0
    ops = [  # client, group, row (an update's; reads below 0), issue, done, ok
        (3, 2, 128, 90.0, 99.9, True),  # ended before the window
        (0, 0, 128, 95.0, 101.0, True),  # a sixth of its life inside
        (1, 1, -2, 99.0, 100.0, True),  # ended at the opening instant
        (0, 0, 129, 101.0, 104.0, True),  # whole inside
        (1, 1, -1, 102.0, 111.0, True),  # eight ninths inside
        (2, 1, 128, 103.0, 109.0, False),  # failed
        (3, 2, 129, 104.0, 0.0, False),  # never told
    ]
    for c, g, row, issue, done, ok in ops:
        gen.o_client.append(c)
        gen.o_group.append(g)
        gen.o_row.append(row)
        gen.o_issue.append(issue)
        gen.o_done.append(done)
        gen.o_ok.append(ok)
    gen.check = {"reads_differ": 0, "reads_stale": 0, "records_differ": 0}
    out = gen.results()
    assert out["committed_ops_per_s"] == pytest.approx((1 / 6 + 1 + 8 / 9) / 10.0)
    assert out["completed_in_window_per_s"] == 3 / 10.0
    assert (out["attempted"], out["failed"]) == (4, 2)
    assert (out["reads"], out["writes"], out["writes_acked"]) == (1, 3, 1)
    assert out["client.ycsb_hot_group_share"] == 0.5
    assert out["client.ycsb_update_p50_ms"] == pytest.approx(3000.0)
    assert "update_p99_ms_by_third" not in out  # a third without an update


def test_the_generator_reads_every_parameter_of_its_traffic_file():
    gen, params, traffic, groups, ledger, scale = _generator()
    assert set(traffic) - params.read == DESCRIPTIVE
    assert (gen.clients, gen.workload.recordcount) == (32, 512)
    assert gen.workload.per_group == 128 and gen.fields == (10, 100)
    assert ledger.payloads is gen.workload
    for key, bad in (("requestdistribution", "uniform"),
                     ("writeallfields", True), ("updateproportion", 0.4)):
        with pytest.raises(ValueError):
            type(gen)(dict(traffic, **{key: bad}), groups, ledger, 7, 2.0, scale)


LOSSY = '''"""kvrecords with a fault put in on purpose (benchmark/tests)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "kvrecords_sound",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "kvrecords.py"),
)
kv = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kv)


class StateMachine(kv.StateMachine):
    def __init__(self, cluster_id, node_id):
        super().__init__(cluster_id, node_id)
        self.node_id, self.seen = node_id, {}

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

    def commands(self, cmd, n):
        FAULT
'''
FAULTS = {
    # replica 2 loses the 140th command of every group, an update
    "drops_one_update":
        "return () if self.node_id == 2 and n == 140 else (cmd,)",
    # every replica applies the update of a field before this one once
    # more after it: two updates of one key out of order
    "applies_two_updates_out_of_order":
        "older = self.seen.get(cmd[:32])\n"
        "        self.seen[cmd[:32]] = cmd\n"
        "        return (cmd, older) if older and cmd[0] == 2 else (cmd,)",
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_broken_state_machine_turns_correct_false(fault, tmp_path):
    """The counts and sum64 stay right under both faults, so
    check.read_back passes: the record comparisons decide."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns(".work", "__pycache__"),
    )
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "statemachines", "kvrecords_lossy.py"), "w") as f:
        f.write(LOSSY.replace("FAULT", FAULTS[fault]))
    path = os.path.join(bench, "configs", "ycsb-1024x3.json")
    with open(path, encoding="utf-8") as f:
        config = json.load(f)
    config["statemachine"] = "kvrecords_lossy"
    with open(path, "w", encoding="utf-8") as f:
        json.dump(config, f)
    r = _run(root, "--workload", CELL, "--seed", "2147483999",
             "--seconds", "3", "--trace", "0", "--rehearsal")
    assert r.returncode == 0, r.stderr[-2000:]
    (line,) = _result_lines(r.stdout)
    client = _run_line(r.stdout)["client"]
    assert line["correct"] is False
    assert client["reads_wrong"] > 0
    assert client["reads_differ"] + client["records_differ"] > 0
    assert "[check] FAILED" not in r.stdout  # count and sum64 still agree
