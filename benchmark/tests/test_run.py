"""run.py end to end on the CPU: the rehearsal, the refusal to run
without a TPU, and a fifth cell added as nothing but data."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, *args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=root, timeout=timeout,
    )


def _result_lines(stdout: str) -> list:
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_rehearsal_says_so_and_prints_a_contract_shaped_line():
    r = _run(ROOT, "--workload", "upstream48.mixed9to1", "--seed", "4",
             "--seconds", "2", "--trace", "0", "--rehearsal")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert lines[-1].startswith("REHEARSAL")  # the last line is no result
    (line,) = _result_lines(r.stdout)
    assert CONTRACT_KEYS <= set(line) and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {
        m["name"] for m in spec["end_to_end"]
        if "workloads" not in m or "upstream48.mixed9to1" in m["workloads"]
    }
    assert set(line["metrics"]) == want
    assert all(
        set(m) == {"value", "unit"} and m["value"] > 0
        for m in line["metrics"].values()
    )
    assert not os.path.exists(os.path.join(ROOT, "benchmark", ".work",
                                           "upstream48.mixed9to1"))


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    r = _run(ROOT, "--workload", "fleet1024.write16", "--seed", "1",
             "--seconds", "2", "--trace", "0", timeout=120)
    assert r.returncode != 0
    assert _result_lines(r.stdout) == []
    assert "no CPU fallback" in r.stderr


def test_a_fifth_cell_is_added_as_data_alone(tmp_path):
    """One configuration file, one traffic file, one per-layer metric
    file and the entries that name them; no file that was there is
    edited except BENCHMARK.json, which only gains entries."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns(".work", "__pycache__"),
    )
    bench = os.path.join(root, "benchmark")
    config = json.load(open(os.path.join(bench, "configs", "upstream-48x3.json")))
    config["name"] = "upstream-6x3"
    config["deployment"]["groups"] = 6
    json.dump(config, open(os.path.join(bench, "configs", "upstream-6x3.json"), "w"))
    traffic = json.load(open(os.path.join(bench, "traffic", "mixed9to1.open.json")))
    traffic.update(rate_ops_per_s=900, reads_per_write=1)
    json.dump(traffic, open(os.path.join(bench, "traffic", "mixed1to1.open.json"), "w"))
    with open(os.path.join(bench, "layer_metrics", "client.reads_in_window.py"), "w") as f:
        f.write('def read(run):\n    return run.client["reads"]\n')
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({
        "name": "upstream-6x3", "source": "test", "reduced": ["groups"],
        "file": "benchmark/configs/upstream-6x3.json", "why": "test",
    })
    spec["workloads"].append({
        "name": "upstream6.mixed1to1", "config": "upstream-6x3",
        "traffic": "mixed1to1.open", "chips": 1, "why": "test",
    })
    spec["per_layer"].append({
        "name": "client.reads_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "client",
        "moves": "committed_ops_per_s", "workloads": ["upstream6.mixed1to1"],
    })
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    r = _run(root, "--workload", "upstream6.mixed1to1", "--seed", "9",
             "--seconds", "2", "--trace", "1", "--rehearsal")
    assert r.returncode == 0, r.stderr[-2000:]
    (line,) = _result_lines(r.stdout)
    assert line["correct"] is True
    # 900 ops/s x 4/6 (a rehearsal runs four of the groups) x 2 s, half reads
    assert line["metrics"]["client.reads_in_window"] == {"value": 600, "unit": "count"}
    assert "mesh.collective_ms_per_step" not in line["metrics"]


def test_run_py_names_no_cell_configuration_mix_or_metric():
    source = open(os.path.join(ROOT, "benchmark", "run.py")).read()
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [w["name"] for w in spec["workloads"]]
    names += [c["name"] for c in spec["configs"]]
    names += [w["traffic"] for w in spec["workloads"]]
    names += [m["name"] for m in spec["per_layer"]]
    # setup_s is the harness's own: it alone can stamp the process start
    names += [m["name"] for m in spec["end_to_end"] if m["name"] != "setup_s"]
    assert [n for n in names if n in source] == []
