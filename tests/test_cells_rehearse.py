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
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    # niced: a rehearsal keeps two to three cores busy, and gives way to
    # the load-sensitive tests the other workers run meanwhile
    r = subprocess.run(
        ["nice", sys.executable, os.path.join(_REPO, "benchmark", "run.py"),
         *args],
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
        "--trace", str(trace), "--rehearsal",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert results, r.stdout[-2000:]
    line = results[-1]
    assert line["rehearsal"] is True
    assert line["correct"] is True
    assert line["failed"] == 0
    assert line["device"]["platform"] == "cpu"


def test_without_a_tpu_a_cell_exits_non_zero_and_prints_no_result():
    r, results = _run(
        "--workload", CELLS[0], "--seed", "1", "--seconds", "2",
        "--trace", "0",
    )
    assert r.returncode != 0
    assert results == []
    assert "no CPU fallback" in r.stderr
