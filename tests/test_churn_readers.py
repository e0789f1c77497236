"""The per-layer readers ISSUE 31 adds, on a made-up window: the value
right, None on a program without the counters (as the parent is, and as
benchmark/lib/counters.py's readers answer), None below full sampling;
and that unsampled snapshot work records nothing."""
import json
import os
import time
import types

import pytest

from benchmark.run import load_plugin

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fleet1024x5.churn"

PHASES = {
    "n.snapshots_saved": 400.0, "snap.save": 3.2, "snap.save.cpu": 2.0,
    "snap.recover": 0.5, "snap.recover.cpu": 0.25,
    "snap.compact": 0.4, "snap.compact.cpu": 0.15,
    "n.snapshots_installed": 30.0, "n.snapshots_sent": 36.0,
    "n.snapshots_acked": 28.0, "snap.install": 70.0,
    "n.log_compactions": 380.0, "n.config_changes_applied": 270.0,
    "n.lanes_joined": 29.0, "n.lanes_left": 31.0,
    "n.peer_steps_parked": 120.0,
}
CLIENT = {
    "client.replace_p50_ms": 21000.0, "client.replacements_done_in_window": 29,
    "client.leader_moves_in_window": 0, "client.stalled_groups": 0,
}
WANT = {
    "snapshot.saves_per_step": 50.0,
    "snapshot.save_ms": 8.0,
    "snapshot.cpu_ms_per_step": 300.0,
    "snapshot.installs_in_window": 30.0,
    "snapshot.install_ms": 2500.0,
    "snapshot.sent_in_window": 36.0,
    "snapshot.compactions_per_step": 47.5,
    "membership.changes_in_window": 270.0,
    "membership.parked_peer_steps_per_step": 15.0,
    "membership.lanes_joined_in_window": 29.0,
    "membership.lanes_left_in_window": 31.0,
    **CLIENT,
}
FROM_THE_PROGRAM = sorted(n for n in WANT if not n.startswith("client."))
# metrics that read 0, not None, where the program counted and found none
ZERO_WHEN_QUIET = {
    "snapshot.installs_in_window": "n.snapshots_installed",
    "snapshot.sent_in_window": "n.snapshots_sent",
    "membership.parked_peer_steps_per_step": "n.peer_steps_parked",
    "membership.lanes_joined_in_window": "n.lanes_joined",
    "membership.lanes_left_in_window": "n.lanes_left",
    "snapshot.install_ms": "n.snapshots_acked",
}


def run_of(phases, ratio=1, client=CLIENT):
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
        assert CELL in m["workloads"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    read = load_plugin("layer_metrics", name).read
    assert read(run_of(PHASES)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", FROM_THE_PROGRAM)
def test_reader_on_a_program_without_the_counters(name):
    read = load_plugin("layer_metrics", name).read
    # the parent: the loop's spans and nothing of this PR
    assert read(run_of({"save": 1.0, "rsm.handle": 0.6})) is None
    assert read(run_of(PHASES, ratio=32)) is None  # whole at ratio 1 only


@pytest.mark.parametrize("name", sorted(ZERO_WHEN_QUIET))
def test_reader_reads_zero_where_the_program_counted_none(name):
    read = load_plugin("layer_metrics", name).read
    quiet = {k: v for k, v in PHASES.items() if k != ZERO_WHEN_QUIET[name]}
    assert read(run_of(quiet)) == 0


@pytest.mark.parametrize("name", sorted(CLIENT))
def test_client_reader_without_the_number(name):
    assert load_plugin("layer_metrics", name).read(run_of({}, client={})) is None


@pytest.mark.parametrize("ratio", [1, 1 << 30], ids=["sampled", "unsampled"])
def test_snapshot_work_is_recorded_on_sampled_wake_ups_only(ratio, tmp_path):
    """A save, its compaction and the config changes a sampled worker
    handled are in the profiler; with sampling off the nodes count all the
    same and the profiler holds nothing of it."""
    from dragonboat_tpu.client import Session
    from tests.test_profile import _single_host

    names = ("snap.save", "snap.save.cpu", "snap.compact", "n.snapshots_saved",
             "n.log_compactions", "n.config_changes_applied")
    with _single_host(tmp_path, profile_sample_ratio=ratio) as nh:
        node = nh._get_node(1)
        samples = nh.engine.core.profiler.samples
        h = nh.propose_batch_async(
            Session.noop_session(1), [b"k%d=v" % i for i in range(40)], 10.0
        )
        assert h.wait(30) and h.completed == 40
        assert nh.sync_request_snapshot(1, timeout_s=20.0) > 0
        deadline = time.monotonic() + 10
        while node.log_compactions == 0 and time.monotonic() < deadline:
            nh.sync_propose(Session.noop_session(1), b"more=v", 10.0)
            nh.request_snapshot(1, compaction_overhead=5).wait(10)
            time.sleep(0.05)
        assert node.snapshots_saved >= 1 and node.log_compactions >= 1
        assert node.sm.config_changes_applied >= 1  # the bootstrap's
        deadline = time.monotonic() + 10
        while ratio == 1 and time.monotonic() < deadline:
            if all(n in samples for n in names):
                break
            time.sleep(0.02)
        seen = {n: samples[n]._sum for n in names if n in samples}
    if ratio == 1:
        assert set(seen) == set(names), seen
        assert seen["n.snapshots_saved"] >= 1 and seen["snap.save"] > 0
        assert seen["n.log_compactions"] >= 1
    else:
        assert seen == {}
        assert not any(n.startswith("snap.") for n in samples)


def test_the_roofline_bytes_hold_at_the_cells_shapes():
    """step_batch_roofline takes its bytes from the cell's shapes through
    benchmark/lib/shape_bytes.py: at P = 8 and inbox_depth 8 they are
    still the program's own arrays (jax.eval_shape; nothing runs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import shape_bytes as sb
    from dragonboat_tpu.ops import kernel
    from dragonboat_tpu.ops.state import (
        KernelConfig, init_state, make_empty_inbox,
    )

    def nbytes(tree):
        return sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(tree)
        )

    G, P, W, K, E, R = 5120, 8, 256, 8, 64, 4
    cfg = KernelConfig(groups=G, peers=P, log_window=W, inbox_depth=K,
                       max_entries_per_msg=E, readindex_depth=R)
    state = jax.eval_shape(lambda: init_state(cfg))
    inbox = jax.eval_shape(lambda: make_empty_inbox(cfg))
    ticks = jax.ShapeDtypeStruct((G,), jnp.int32)
    _s, out = jax.eval_shape(
        kernel.make_step_fn(cfg, donate=False), state, inbox, ticks)
    assert nbytes(state) == sb.state_bytes(G, P, W, R)
    assert nbytes(inbox) == sb.inbox_bytes(G, K, E)
    assert nbytes(out) == sb.output_bytes(G, P, K, R)
    assert sb.launch_bytes(G, P, W, K, E, R, 1) == (
        2 * nbytes(state) + nbytes(inbox) + nbytes(ticks) + nbytes(out))
