"""The readers of the engine profiler's window sums (lib/spans.py and the
per-layer metrics built on it) against a synthetic run: the arithmetic,
None at a sampling ratio other than 1, None where the program recorded
nothing under a name (a program older than the name), never a raise."""
import json
import os
import types

import pytest
from conftest import ROOT

from benchmark.lib import spans
from benchmark.run import load_plugin

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW = [
    m for m in SPEC["per_layer"]
    if m["name"].split(".")[0] in ("seam", "rsm", "request")
    or m["name"] in (
        "engine.wait_ms_per_step", "engine.prepare_ms_per_step",
        "engine.uncovered_ms_per_step", "engine.host_cpu_ms_per_step",
        "storage.save_cpu_ms_per_step", "run.readindex_dropped_in_window",
        "run.spans_dropped",
    )
]


def synthetic(ratio=1, launches=10.0, drop=()):
    """A 2 s window of 10 launches: every top-level span 0.1 s but wait
    (0.7 s), so that 0.2 s are uncovered; CPU half of wall; 4 writes and
    5 reads sampled."""
    phases = {name: 0.1 for name in spans.TOP_LEVEL}
    phases["wait"] = 0.7
    phases.update({name + ".cpu": v / 2 for name, v in list(phases.items())})
    phases.update({
        "put": 0.02, "launch": 0.03, "device_wait": 0.04, "copy": 0.05,
        "rsm.handle": 0.6, "rsm.handle.cpu": 0.5,
        "req.w.queue": 0.4, "req.w.replicate": 1.2, "req.w.apply_wait": 0.2,
        "req.w.apply": 0.04, "req.w.launches": 24.0, "req.w.n": 4.0,
        "req.r.queue": 0.5, "req.r.confirm": 1.0, "req.r.complete": 0.05,
        "req.r.launches": 15.0, "req.r.n": 5.0,
        "n.readindex_dropped": 3.0, "n.spans_dropped": 0.0,
    })
    for name in drop:
        del phases[name]
    return types.SimpleNamespace(window={
        "seconds": 2.0, "launches": launches, "phase_ratio": ratio,
        "phases": phases,
    })


WANT = {
    "engine.wait_ms_per_step": 70.0,
    "engine.prepare_ms_per_step": 10.0,
    "engine.uncovered_ms_per_step": 20.0,
    "engine.host_cpu_ms_per_step": 35.0,  # seven phases, 0.05 s each
    "storage.save_cpu_ms_per_step": 5.0,
    "seam.put_ms_per_step": 2.0,
    "seam.launch_ms_per_step": 3.0,
    "seam.device_wait_ms_per_step": 4.0,
    "seam.copy_ms_per_step": 5.0,
    "rsm.handle_ms_per_step": 60.0,
    "rsm.handle_cpu_ms_per_step": 50.0,
    "request.write_queue_ms": 100.0,
    "request.write_replicate_ms": 300.0,
    "request.write_apply_wait_ms": 50.0,
    "request.write_apply_ms": 10.0,
    "request.write_launches": 6.0,
    "request.read_queue_ms": 100.0,
    "request.read_confirm_ms": 200.0,
    "request.read_complete_ms": 10.0,
    "request.read_launches": 3.0,
    "run.readindex_dropped_in_window": 3.0,
    "run.spans_dropped": 0.0,
}


def test_every_new_metric_has_a_case():
    assert {m["name"] for m in NEW} == set(WANT) and len(NEW) == 22


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    read = load_plugin("layer_metrics", name).read
    assert read(synthetic()) == pytest.approx(WANT[name])
    assert read(synthetic(ratio=32)) is None
    # a program that records none of what this PR added, as the parent
    assert read(synthetic(drop=[
        n for n in synthetic().window["phases"]
        if n not in ("pack", "dispatch", "fetch", "place", "send_rep",
                     "save", "send_resp", "apply", "reads", "maintain")
    ])) is None


def test_no_launches_and_no_requests_give_nothing():
    assert spans.per_step_ms(synthetic(launches=0.0), "wait") is None
    assert spans.uncovered_ms_per_step(synthetic(launches=0.0)) is None
    run = synthetic()
    run.window["phases"]["req.w.n"] = 0.0
    assert spans.per_request(run, "w", "queue") is None
