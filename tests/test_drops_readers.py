"""The per-layer readers ISSUE 34 adds, on a made-up window: the value
right, None on a program without the counters (as the parent is), None
below full sampling, 0 where the program swept its catch-ups and counted
none; and that an unsampled catch-up records nothing in the profiler
while the engine's plain counters count all the same."""
import json
import os
import time
import types

import pytest

from benchmark.lib import deploy
from benchmark.run import load_plugin
from tests.test_drops_cell import SMALL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fleet1024x5.drops"

PHASES = {
    "n.catchup_entries": 4096.0, "n.catchups_started": 12.0,
    "n.replicate_resends": 6.0, "n.snapshot_fallbacks": 2.0,
    "catchup": 0.05, "catchup.cpu": 0.04, "maintain": 0.4,
}
CLIENT = {
    "client.dropped_share": 0.0994, "client.lagging_followers_share": 0.002,
    "client.follower_lag_p99_entries": 64.0,
    "client.heal_to_converged_ms": 9600.0,
}
PROGRAM = {"program_in_window": {"replicate_rejects": 1320}}
WANT = {
    "replication.rejects_per_step": 165.0,
    "replication.resends_per_step": 0.75,
    "replication.catchups_started_per_step": 1.5,
    "replication.catchup_entries_per_step": 512.0,
    "replication.catchup_cpu_ms_per_step": 5.0,
    "replication.snapshot_fallbacks_in_window": 2.0,
    **CLIENT,
}
FROM_THE_PROFILER = sorted(
    n for n in WANT if n.startswith("replication.") and "rejects" not in n
)
# metrics that read 0, not None, where the program swept and counted none
ZERO_WHEN_QUIET = {
    "replication.resends_per_step": "n.replicate_resends",
    "replication.catchups_started_per_step": "n.catchups_started",
    "replication.snapshot_fallbacks_in_window": "n.snapshot_fallbacks",
}


def run_of(phases, ratio=1, client=None):
    client = {**CLIENT, **PROGRAM} if client is None else client
    return types.SimpleNamespace(client=dict(client), window={
        "seconds": 15.0, "launches": 8.0, "phase_ratio": ratio,
        "phases": dict(phases),
    })


def test_every_new_metric_is_declared_for_the_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    got = {m["name"]: m for m in spec["per_layer"] if m["name"] in WANT}
    assert set(got) == set(WANT)
    for name, m in got.items():
        assert m["workloads"] == [CELL], name
        assert m["moves"] == "committed_ops_per_s"
        assert m["layer"] == name.split(".")[0]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    for name in ("step_batch_roofline", "client.commit_latency_p50_ms"):
        m = next(m for m in spec["per_layer"] if m["name"] == name)
        assert m["workloads"][-1] == CELL
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "drops128.closed64")
    assert len(spec["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1


def test_the_traffic_file_holds_what_the_issue_names():
    with open(os.path.join(
            ROOT, "benchmark", "traffic", "drops128.closed64.json")) as f:
        t = json.load(f)
    assert (t["kind"], t["batch"], t["payload_bytes"]) == (
        "closed_loop_drops", 64, 128)
    assert (t["timeout_s"], t["poll_ms"]) == (30, 5)
    assert (t["warm_rounds"], t["loss_rounds"]) == (2, 2)
    assert t["drop_probability"] == 0.10 and t["drop_to_leader"] is False
    assert (t["bring_up_bound_s"], t["warm_bound_s"], t["run_bound_s"]) == (
        120, 60, 295)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    read = load_plugin("layer_metrics", name).read
    assert read(run_of(PHASES)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", FROM_THE_PROFILER)
def test_reader_on_a_program_without_the_counters(name):
    read = load_plugin("layer_metrics", name).read
    # the parent: the loop's spans and nothing of this PR
    assert read(run_of({"maintain": 0.4, "save": 1.0})) is None
    assert read(run_of(PHASES, ratio=32)) is None  # whole at ratio 1 only


@pytest.mark.parametrize("name", sorted(ZERO_WHEN_QUIET))
def test_reader_reads_zero_where_the_program_counted_none(name):
    read = load_plugin("layer_metrics", name).read
    quiet = {k: v for k, v in PHASES.items() if k != ZERO_WHEN_QUIET[name]}
    assert read(run_of(quiet)) == 0


@pytest.mark.parametrize("name", sorted(CLIENT) + [
    "replication.rejects_per_step"])
def test_client_reader_without_the_number(name):
    assert load_plugin("layer_metrics", name).read(
        run_of(PHASES, client={})) is None


NAMES = ("catchup", "catchup.cpu", "n.catchup_entries", "n.catchups_started")


@pytest.mark.parametrize("ratio", [1, 1 << 30], ids=["sampled", "unsampled"])
def test_an_unsampled_catchup_records_nothing(ratio, tmp_path):
    """A follower cut off for three device windows and served from the
    host log: on sampled iterations the sweep's span and counters are in
    the profiler; with sampling off the engine's plain counters count the
    same catch-up and the profiler holds nothing of it."""
    kv128 = load_plugin("statemachines", "kv128")
    cluster = deploy.Cluster(
        SMALL, 1, kv128.StateMachine, str(tmp_path),
        {"profile_sample_ratio": ratio},
    )
    payloads = kv128.Payloads(3, 1)
    try:
        cluster.start()
        leader = cluster.wait_leaders(60.0)[0]
        core = cluster.core
        # (the engine's three-step program is compiled by its first
        # launch; that stall would meet the silenced follower's election
        # timeout mid-batch)
        deadline = time.monotonic() + 60
        while core.step_stats()["steps_per_launch"] != 3:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        victim = next(n for n in cluster.hosts if n != leader)
        cut = [True]
        core.set_local_drop_hook(lambda m: cut[0] and m.to == victim)
        nh = cluster.hosts[leader]
        for i in range(7):
            h = nh.propose_batch_async(
                cluster.session(leader, 0),
                payloads.cmds(0, 16 * i, 16 * i + 16), 10.0)
            assert h.wait(20) and h.completed == 16
        cut[0] = False
        want = nh.stale_read(1, None)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if cluster.hosts[victim].stale_read(1, None) == want:
                break
            time.sleep(0.02)
        assert cluster.hosts[victim].stale_read(1, None) == want
        stats = core.step_stats()
        assert stats["catchups_started"] >= 1
        assert stats["catchup_entries"] >= 64
        assert stats["snapshot_fallbacks"] == 0
        samples = core.profiler.samples
        seen = {n: samples[n]._sum for n in NAMES if n in samples}
    finally:
        cluster.stop()
    if ratio == 1:
        assert set(seen) == set(NAMES), seen
        assert seen["n.catchups_started"] == stats["catchups_started"]
        assert seen["n.catchup_entries"] == stats["catchup_entries"]
        assert seen["catchup"] > 0
    else:
        assert seen == {}
        assert not any("catchup" in n or "resend" in n for n in samples)
