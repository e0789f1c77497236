"""The 10 000-group cell's generator and readers at a small size: 16 groups
x 5 replicas under closed_loop_mixed's closed loop of nine reads to one
write, every read held exactly to the seeded rows and the deployment to
check.read_back; the stream's exact share; the process bound that ends a
run by its own exit; and the three bring-up readers on a made-up run."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import check, deploy, loadgen
from benchmark.run import load_cell, load_plugin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fleet10kx5.mixed9to1"
GROUPS = 16
mixed = load_plugin("generators", "closed_loop_mixed")
kv16 = load_plugin("statemachines", "kv16")
SEED = 2147483913  # past 31 bits, as a run's seed may be


def _cell(**traffic_over):
    _spec, _cell_, config, traffic = load_cell(CELL)
    # run_bound_s 0: no process watchdog inside pytest
    return config, {**traffic, "run_bound_s": 0, **traffic_over}


@pytest.mark.parametrize("kind", ["vector", "vector-overlap"])
def test_nine_reads_to_a_write_hold_the_reference(kind, tmp_path):
    config, traffic = _cell(clients=2 * GROUPS)
    ledger = loadgen.Ledger(loadgen.Payloads(SEED, GROUPS), GROUPS)
    gen = mixed.Generator(traffic, GROUPS, ledger, SEED, 3.0, 1.0)
    over = {"overlap_decode": True} if kind == "vector-overlap" else {}
    cluster = deploy.Cluster(
        config, GROUPS, kv16.StateMachine, str(tmp_path), over)
    try:
        cluster.start()
        cluster.wait_leaders(120.0)
        gen.warm(cluster)
        opened = []
        gen.measure(cluster, opened.append, opened.append)
        got = gen.results()
        checked = check.read_back(cluster, ledger, SEED)
    finally:
        cluster.stop()
    assert len(opened) == 2
    assert got["failed"] == 0 and got["attempted"] > 0
    assert got["reads_wrong"] == 0
    assert got["committed_ops_per_s"] > 0
    assert checked["groups_exact"] == GROUPS
    # every group took its warm batch and some writes of the loop
    assert all(u >= traffic["warm_batch"] for u in ledger.used)
    # every operation issued was drawn from the stream: exactly nine
    # reads to a write in each block, and both kinds in the window
    drawn = len(gen.s_read)
    assert drawn >= len(gen.o_client) and drawn % mixed.BLOCK == 0
    for lo in range(0, drawn, mixed.BLOCK):
        assert sum(gen.s_read[lo:lo + mixed.BLOCK]) == 900
    assert got["reads"] > 0 and got["writes"] > 0
    assert got["writes_acked"] == got["writes"]  # failed is 0
    # the program's bring-up account under the metrics' names
    assert got["setup.start_clusters_s"] > 0
    assert got["setup.activate_s"] > 0
    assert got["setup.elect_launches"] >= 1
    assert got["bringup"]["activated"] == GROUPS * cluster.replicas


def test_the_stream_is_the_seed_s():
    _config, traffic = _cell()
    a = mixed.Generator(traffic, 100, None, SEED, 1.0, 1.0)
    b = mixed.Generator(traffic, 100, None, SEED, 1.0, 1.0)
    c = mixed.Generator(traffic, 100, None, SEED + 1, 1.0, 1.0)
    for g in (a, b, c):
        g._draw(3 * mixed.BLOCK - 1)
    assert a.s_group == b.s_group and a.s_read == b.s_read
    assert a.s_group != c.s_group
    assert sum(a.s_read) == 3 * 900
    assert set(a.s_group) <= set(range(100))


def test_a_rehearsal_scales_the_clients():
    _config, traffic = _cell()
    gen = mixed.Generator(traffic, 4, None, SEED, 1.0, 4 / 10000)
    assert gen.clients == 8


def test_the_bound_ends_the_process_by_its_own_exit():
    """The generator arms the bound when it is made, before any bring-up:
    a process that overruns it dumps its threads and exits non-zero."""
    code = (
        "import json, time, sys\n"
        "from benchmark.run import load_cell, load_plugin\n"
        f"_s, _c, _cfg, traffic = load_cell({CELL!r})\n"
        "traffic['run_bound_s'] = 2\n"
        "load_plugin('generators', 'closed_loop_mixed').Generator(\n"
        "    traffic, 4, None, 1, 1.0, 1.0)\n"
        "time.sleep(60)\n"
        "print('not ended')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT), timeout=50,
    )
    assert r.returncode != 0
    assert "not ended" not in r.stdout
    assert "most recent call first" in r.stderr


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "fleet-10kx5-9to1",
                    "traffic": "mixed9to1.closed20k", "chips": 1}
    _spec, _cell_, config, traffic = load_cell(CELL)
    assert config["deployment"]["groups"] == 10000
    assert config["deployment"]["replicas"] == 5
    assert config["engine"]["readindex_depth"] == 8
    assert "steps_per_sync" not in config["engine"]
    assert traffic["clients"] == 20000 and traffic["reads_per_write"] == 9
    assert traffic["run_bound_s"] == 330
    names = [m["name"] for m in spec["per_layer"]
             if m["name"].startswith("setup.")]
    assert names == list(mixed.BRINGUP.values())
    for m in spec["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == [CELL] and m["moves"] == "setup_s"
            assert m["layer"] == "setup"


@pytest.mark.parametrize("name", sorted(mixed.BRINGUP.values()))
def test_bring_up_reader(name):
    reader = load_plugin("layer_metrics", name)
    run = types.SimpleNamespace(client={name: 7.5, "other": 1.0})
    assert reader.read(run) == 7.5
    # a program that keeps no bring-up account (the parent) reads None
    assert reader.read(types.SimpleNamespace(client={"other": 1.0})) is None
