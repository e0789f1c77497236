"""The single-group cell's two readers (`upstream1.write16`): the fullest
leader lane's commit movement and its uncommitted rows, per pack, on a
recorded window; None on a program without the counters, below full
sampling and where no launch was packed."""
import json
import os
import types

import pytest
from conftest import ROOT

from benchmark import run as harness

CELL = "upstream1.write16"
READERS = {
    "lanes.hot_commit_entries": "n.hot_commit_entries",
    "lanes.hot_uncommitted_rows": "n.hot_uncommitted_rows",
}


def _run(phases, ratio=1, launches=20.0):
    return types.SimpleNamespace(
        client={},
        window={"phase_ratio": ratio, "phases": phases, "launches": launches},
    )


def test_the_readers_are_the_cells_own():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    ours = [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(ours) == sorted(READERS)


@pytest.mark.parametrize("name, counter, value", [
    ("lanes.hot_commit_entries", "n.hot_commit_entries", 1290.0),
    ("lanes.hot_uncommitted_rows", "n.hot_uncommitted_rows", 1.5),
])
def test_reader_on_a_recorded_window(name, counter, value):
    read = harness.load_plugin("layer_metrics", name).read
    # twenty packs; the window's launches are not what it divides by
    phases = {counter: value * 20, "n.packs": 20.0, "n.staged_left": 3.0}
    assert read(_run(phases, launches=19.0)) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_where_nothing_was_counted(name):
    read = harness.load_plugin("layer_metrics", name).read
    counter = READERS[name]
    # the parent program keeps neither counter
    assert read(_run({"n.packs": 20.0, "n.staged_left": 3.0})) is None
    # a run below full sampling
    assert read(_run({counter: 40.0, "n.packs": 20.0}, ratio=16)) is None
    # no pack counted in the window
    assert read(_run({counter: 0.0, "n.packs": 0.0})) is None
