"""The run-level apply path's counters (ISSUE 29): `n.apply_entries`,
`n.apply_run_entries` and `n.apply_runs` as the apply workers fold them
from the managers' own counts, and the two per-layer readers of the
benchmark over them (benchmark/tests/test_span_readers.py is the
pattern; this file is under tests/ so that tier-1 runs it)."""
import json
import os
import time
import types

import pytest

from benchmark.run import load_plugin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ("n.apply_entries", "n.apply_run_entries", "n.apply_runs")
WANT = {"rsm.run_share": 0.96, "rsm.entries_per_run": 16.0}


def window(ratio=1, **phases):
    return types.SimpleNamespace(window={
        "seconds": 2.0, "launches": 10.0, "phase_ratio": ratio,
        "phases": phases,
    })


def full(ratio=1):
    return window(ratio, **{
        "n.apply_entries": 200.0, "n.apply_run_entries": 192.0,
        "n.apply_runs": 12.0, "rsm.handle": 0.6,
    })


def test_both_metrics_are_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    got = {m["name"]: m for m in spec["per_layer"] if m["name"] in WANT}
    assert set(got) == set(WANT)
    for m in got.values():
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "rsm", "committed_ops_per_s", "program_counter", "higher")
        assert "workloads" not in m


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    read = load_plugin("layer_metrics", name).read
    assert read(full()) == pytest.approx(WANT[name])
    assert read(full(ratio=32)) is None  # whole only at full sampling
    # a program without the counters, as the parent: nothing, no raise
    assert read(window(**{"rsm.handle": 0.6})) is None
    # a window in which nothing was applied
    assert read(window(**dict.fromkeys(NAMES, 0.0))) is None


@pytest.mark.parametrize("ratio", [1, 1 << 30], ids=["sampled", "unsampled"])
def test_a_wake_up_folds_what_its_managers_counted(ratio, tmp_path):
    """A sampled wake-up folds the deltas of its nodes' managers; an
    unsampled one folds nothing and the managers count all the same."""
    from dragonboat_tpu.client import Session
    from tests.test_profile import _single_host

    with _single_host(tmp_path, profile_sample_ratio=ratio) as nh:
        core = nh.engine.core
        sm = nh._get_node(1).sm
        samples = core.profiler.samples

        def folded():
            return tuple(
                round(samples[n]._sum) if n in samples else 0 for n in NAMES
            )

        def counted():
            return sm.applied_entries, sm.applied_run_entries, sm.applied_runs

        nh.sync_propose(Session.noop_session(1), b"k0=v", 10.0)  # settle
        f0, c0 = folded(), counted()
        h = nh.propose_batch_async(
            Session.noop_session(1), [b"k%d=v" % i for i in range(24)], 10.0
        )
        assert h.wait(30) and h.completed == 24
        c1 = counted()
        d = tuple(b - a for a, b in zip(c0, c1))
        assert d[0] == d[1] == 24 and 1 <= d[2] <= 24, d
        # the worker folds after the wake-up that completed the batch
        deadline = time.monotonic() + 10
        while ratio == 1 and time.monotonic() < deadline:
            if tuple(b - a for a, b in zip(f0, folded())) == d:
                break
            time.sleep(0.01)
        f1 = folded()
    if ratio == 1:
        assert tuple(b - a for a, b in zip(f0, f1)) == d
    else:
        assert f1 == (0, 0, 0) and not any(n in samples for n in NAMES)
