"""Every cell of BENCHMARK.json, rehearsed: the command the driver runs on
the chip, here on the CPU at a tiny size (`--rehearsal`), untraced and
traced. A program change that breaks a cell's bring-up, its read-back or a
per-layer reader fails here and not after the PR's chip time is spent. A
cell added to BENCHMARK.json joins by that alone. Nothing here is a
result: a rehearsal's numbers are the CPU's."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(_REPO, "BENCHMARK.json")) as _f:
    _SPEC = json.load(_f)
CELLS = [w["name"] for w in _SPEC["workloads"]]
# per launch by the program's own count (ISSUE 35's and ISSUE 37's):
# declared for every cell, and a number in every traced run of one
PER_LAUNCH = [m["name"] for m in _SPEC["per_layer"]
              if m["name"].endswith("_per_launch")]


# A deployment of fewer groups than run.py's REHEARSAL_GROUPS is
# rehearsed at its own size. Four groups of the one-group cell would be
# another deployment: its 2 048 writers, scaled by four and spread over
# four groups at random, put more writes on one leader at times than its
# proposal queue holds (2 048), and the ones past it are refused.
_AT_SIZE = (
    "import sys; import benchmark.run as r; "
    "r.REHEARSAL_GROUPS = int(sys.argv[1]); sys.exit(r.main(sys.argv[2:]))"
)


def _small(cell):
    """The cell's groups where they are fewer than a rehearsal's."""
    from benchmark.run import REHEARSAL_GROUPS, load_cell

    groups = int(load_cell(cell)[2]["deployment"]["groups"])
    return groups if groups < REHEARSAL_GROUPS else None


def _run(*args, groups=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    run = [os.path.join(_REPO, "benchmark", "run.py")]
    if groups is not None:
        run = ["-c", _AT_SIZE, str(groups)]
    # niced: a rehearsal keeps two to three cores busy, and gives way to
    # the load-sensitive tests the other workers run meanwhile
    r = subprocess.run(
        ["nice", sys.executable, *run, *args],
        capture_output=True, text=True, env=env, cwd=_REPO, timeout=120,
    )
    results = [
        json.loads(line) for line in r.stdout.splitlines()
        if line.startswith("{")
    ]
    return r, results


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    r, results = _run(
        "--workload", cell, "--seed", "1", "--seconds", "2",
        "--trace", str(trace), "--rehearsal", groups=_small(cell),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert results, r.stdout[-2000:]
    line = results[-1]
    assert line["rehearsal"] is True
    assert line["correct"] is True
    assert line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        _check_save_parts(cell, line["metrics"])


# the progress watch's three (ISSUE 37): 0 wherever no message is lost
# and no replica replaced
STALLS = (
    "replication.stalled_peers_per_launch",
    "replication.commit_stalled_lanes_per_launch",
    "rsm.apply_stalled_lanes_per_launch",
)
FAULTED = ("fleet1024x5.drops", "fleet1024x5.churn")


def _check_save_parts(cell, metrics):
    """What `save` is made of and the progress watch (ISSUE 37): every
    one of the sixteen a number (and `engine.steps_per_launch`, ISSUE
    35's, and `seam.buffers_per_launch`, the arrays a launch moves),
    nothing shed, no stall where there is no fault, and the six parts
    together the `save` span but a twentieth."""
    from benchmark.run import load_cell

    assert len(PER_LAUNCH) == 18 and set(STALLS) < set(PER_LAUNCH)
    for name in PER_LAUNCH:
        assert isinstance(metrics[name]["value"], (int, float)), name
    assert metrics["run.spans_dropped"]["value"] == 0
    if cell not in FAULTED:
        for name in STALLS:
            assert metrics[name]["value"] == 0, name
    # `save` a launch: where the cell's file sets `steps_per_sync` the
    # harness's step is a launch already, else a protocol step
    _spec, _cell, config, _traffic = load_cell(cell)
    save = metrics["storage.save_ms_per_step"]["value"]
    if config["engine"].get("steps_per_sync") is None:
        save *= metrics["engine.steps_per_launch"]["value"]
    uncovered = metrics["storage.save_parts_uncovered_ms_per_launch"]["value"]
    assert -0.05 * save <= uncovered <= max(0.10 * save, 0.5)


def test_without_a_tpu_a_cell_exits_non_zero_and_prints_no_result():
    r, results = _run(
        "--workload", CELLS[0], "--seed", "1", "--seconds", "2",
        "--trace", "0",
    )
    assert r.returncode != 0
    assert results == []
    assert "no CPU fallback" in r.stderr
