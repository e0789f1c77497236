"""The single-group deployment (`upstream-1x3`, cell `upstream1.write16`)
at a small size: one Raft group x 3 replicas under closed_loop_mixed's
closed loop of writes alone, every replica held to the plain reference
(loadgen.Ledger / loadgen.Payloads, what check.read_back holds a run to)
once the device window has compacted several times, at a shape whose
row and window caps bind and at one where they do not; the row caps a
deployment may give the engine, and a follower served from the host log
at max_entries_per_msg >= log_window / 2, which used to deadlock; and
the two counters `_pack` keeps of the fullest leader lane."""
import json
import os
import time
import types

import pytest

from benchmark.lib import check, deploy, loadgen
from benchmark.run import load_cell, load_plugin
from dragonboat_tpu.config import EngineConfig, NodeHostConfig
from dragonboat_tpu.engine.vector import VectorEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "upstream1.write16"
mixed = load_plugin("generators", "closed_loop_mixed")
kv16 = load_plugin("statemachines", "kv16")
SEED = 2147483789  # past 31 bits, as a run's seed may be


def _cell(E, W, **traffic_over):
    _spec, _cell_, config, traffic = load_cell(CELL)
    config = json.loads(json.dumps(config))
    config["engine"].update(max_entries_per_msg=E, log_window=W)
    # run_bound_s 0: no process watchdog inside pytest
    return config, {**traffic, "run_bound_s": 0, **traffic_over}


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "upstream-1x3",
                    "traffic": "write16.closed2k", "chips": 1}
    _spec, _cell_, config, traffic = load_cell(CELL)
    assert config["deployment"]["groups"] == 1
    assert config["deployment"]["replicas"] == 3
    assert config["reduced"] == []
    upstream = load_cell("upstream48.write16")[2]
    assert config["guarantees"] == upstream["guarantees"]
    for part in ("nodehost", "raft", "statemachine", "payload_bytes"):
        assert config[part] == upstream[part], part
    shapes = ("max_entries_per_msg", "log_window")
    assert {k: v for k, v in config["engine"].items() if k not in shapes} == {
        k: v for k, v in upstream["engine"].items() if k not in shapes}
    E, W = (config["engine"][k] for k in shapes)
    assert W == 4 * E
    assert traffic["kind"] == "closed_loop_mixed"
    assert traffic["clients"] == 2048 and traffic["reads_per_write"] == 0
    assert traffic["payload_bytes"] == 16
    # read by counters.per_pack in this cell alone; not of the
    # *_per_launch family the rehearsals pin
    ours = {m["name"]: m for m in spec["per_layer"]
            if m["name"] in ("lanes.hot_commit_entries",
                             "lanes.hot_uncommitted_rows")}
    assert {n: m["better"] for n, m in ours.items()} == {
        "lanes.hot_commit_entries": "higher",
        "lanes.hot_uncommitted_rows": "lower"}
    for m in ours.values():
        assert m["workloads"] == [CELL] and m["layer"] == "engine"
        assert m["source"] == "program_counter"
        assert m["moves"] == "committed_ops_per_s"


# ---- one group against the plain reference ---------------------------------

# (E, W, clients, seconds): 64 writers outstanding are more than rows
# of 8 and a window of 32 take in a launch, and fewer than one row of 64
SHAPES = {"caps_bind": (8, 32, 64, 3.0), "caps_free": (64, 256, 64, 4.0)}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_one_group_holds_the_reference(shape, tmp_path):
    E, W, clients, seconds = SHAPES[shape]
    config, traffic = _cell(E, W, clients=clients)
    ledger = loadgen.Ledger(loadgen.Payloads(SEED, 1), 1)
    gen = mixed.Generator(traffic, 1, ledger, SEED, seconds, 1.0)
    cluster = deploy.Cluster(config, 1, kv16.StateMachine, str(tmp_path),
                             {"profile_sample_ratio": 1})
    try:
        cluster.start()
        cluster.wait_leaders(120.0)
        gen.warm(cluster)
        opened = []
        gen.measure(cluster, opened.append, opened.append)
        got = gen.results()
        checked = check.read_back(cluster, ledger, SEED)
        core = cluster.core
        # where each replica's device window starts now, as a real index
        firsts = [int(core._m_base[ln.g] + core._m_devfirst[ln.g])
                  for ln in core._lanes.values() if ln.active]
        s = core.profiler.samples
        stats = core.step_stats()
    finally:
        cluster.stop()
    assert len(opened) == 2
    assert got["failed"] == 0 and got["attempted"] > 0
    assert got["reads"] == 0 and got["writes_acked"] == got["writes"]
    assert got["clients"] == clients
    assert checked["groups_exact"] == 1
    # every replica holds every acknowledged write and nothing else
    # (check.read_back: leader and follower reads, every replica's state)
    acked = ledger.expected(0)[0]
    assert acked >= 4 * W, acked
    # a compaction moves the window's start by at most W - 1 entries:
    # past 3 W, every replica's window has compacted at least three times
    assert len(firsts) == 3 and min(firsts) > 3 * W, firsts
    assert stats["loop_exceptions"] == 0
    # the fullest leader lane, counted at every pack: commits moved, and
    # uncommitted rows never past what the window holds in rows of E
    def total(name):
        return s[name].mean() * len(s[name])

    assert total("n.hot_commit_entries") > 0
    assert len(s["n.hot_uncommitted_rows"]) == len(s["n.packs"])
    assert s["n.hot_uncommitted_rows"].percentile(1.0) <= -(-W // E)


# ---- the row caps a deployment may give the engine (ROADMAP A9) -------------


def _engine(E, W):
    return VectorEngine(None, NodeHostConfig(
        raft_address="a9:1", engine=EngineConfig(
            kind="vector", max_groups=4, max_peers=4, log_window=W,
            max_entries_per_msg=E)))


@pytest.mark.parametrize("E, W", [(32, 32), (64, 32), (16, 16)])
def test_a_row_no_window_can_take_is_refused(E, W):
    """A follower takes a Replicate only where its window has room for it
    (W - 1 slots at most: one is kept for a new leader's noop), so a row
    of E >= W entries would wait for ever: refused, naming both knobs."""
    with pytest.raises(ValueError) as e:
        _engine(E, W)
    assert "max_entries_per_msg" in str(e.value)
    assert "log_window" in str(e.value)


def test_the_cells_own_shape_passes():
    config = load_cell(CELL)[2]["engine"]
    E, W = config["max_entries_per_msg"], config["log_window"]
    eng = _engine(E, W)
    try:
        assert (eng.kcfg.max_entries_per_msg, eng.kcfg.log_window) == (E, W)
        # below A9's shapes: the window compacts at half full, as every
        # cell's does
        assert eng._compact_mark == W // 2
    finally:
        eng.stop()


# a single group whose lagging member can only be served from the log,
# at E = W / 2: the shape that deadlocked
A9 = {
    "deployment": {"groups": 1, "replicas": 3},
    "statemachine": "kv16",
    "nodehost": {"rtt_millisecond": 5},
    "raft": {"election_rtt": 40, "heartbeat_rtt": 4, "snapshot_entries": 0},
    "engine": {"max_peers": 4, "log_window": 32, "inbox_depth": 8,
               "max_entries_per_msg": 16},
}


@pytest.mark.parametrize("kind", ["vector", "vector-overlap"])
def test_a_follower_served_from_the_log_at_half_a_window_catches_up(
        kind, tmp_path):
    """A follower cut off until it is three device windows behind, in a
    deployment without snapshots, at max_entries_per_msg = log_window / 2.
    Its pack held a catch-up Replicate of 16 entries that did not fit
    the room its window had (W - 1 - used = 15 at used 16) while
    compaction waited for used > W / 2 = 16: it never caught up. Now a
    window compacts once it has no room for a whole row."""
    over = {"overlap_decode": True} if kind == "vector-overlap" else {}
    cluster = deploy.Cluster(A9, 1, kv16.StateMachine, str(tmp_path), over)
    payloads = loadgen.Payloads(5, 1)
    try:
        cluster.start()
        leader = cluster.wait_leaders(60.0)[0]
        core = cluster.core
        assert core._compact_mark == 32 - 1 - 16
        # the three-step program is compiled by its first launch: let
        # that pass before the follower is silenced
        deadline = time.monotonic() + 60
        while core.step_stats()["steps_per_launch"] != 3:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        victim = next(n for n in cluster.hosts if n != leader)
        nh = cluster.hosts[leader]
        state = types.SimpleNamespace(cut=True)
        core.set_local_drop_hook(lambda m: state.cut and m.to == victim)
        rows = 0
        for _ in range(7):  # 112 entries: over three windows of 32
            h = nh.propose_batch_async(
                cluster.session(leader, 0),
                payloads.cmds(0, rows, rows + 16), 10.0)
            assert h.wait(20) and h.completed == 16  # the other two commit
            rows += 16
        assert cluster.hosts[victim].stale_read(1, None)[0] == 0
        state.cut = False
        want = (rows, payloads.sum64(0, rows))
        assert nh.stale_read(1, None) == want
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if cluster.hosts[victim].stale_read(1, None) == want:
                break
            time.sleep(0.02)
        assert cluster.hosts[victim].stale_read(1, None) == want
        stats = core.step_stats()
        assert stats["catchups_started"] >= 1
        assert stats["snapshot_fallbacks"] == 0
        assert cluster.hosts[victim]._get_node(1).snapshots_installed == 0
        assert stats["loop_exceptions"] == 0
    finally:
        core.set_local_drop_hook(None)
        cluster.stop()


# ---- the fullest leader lane, as _pack counts it ---------------------------


@pytest.fixture()
def two_stopped_leaders(tmp_path):
    """Two single-replica groups on one host whose engine loop has
    stopped, so a test can build packs by hand: (core, lane 1, lane 2)."""
    from dragonboat_tpu.config import Config
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport.loopback import _Registry, loopback_factory
    from tests.test_nodehost import KVSM

    reg = _Registry()
    nh = NodeHost(NodeHostConfig(
        deployment_id=1, rtt_millisecond=5, raft_address="hot1:1",
        nodehost_dir=str(tmp_path),
        raft_rpc_factory=lambda a: loopback_factory(a, reg),
        engine=EngineConfig(kind="vector", max_groups=8, max_peers=4,
                            log_window=64, inbox_depth=4,
                            max_entries_per_msg=8,
                            profile_sample_ratio=1 << 30),
    ))
    try:
        for cid in (1, 2):
            nh.start_cluster({1: "hot1:1"}, False, lambda c, n: KVSM(c, n),
                             Config(cluster_id=cid, node_id=1,
                                    election_rtt=10, heartbeat_rtt=2))
        deadline = time.monotonic() + 60
        while not all(nh.get_leader_id(c) == (1, True) for c in (1, 2)):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        core = nh.engine.core
        core._stopped.set()
        core._ready.set()
        (loop,) = [t for t in core._threads if t.name == "vec-step"]
        loop.join(30)
        assert not loop.is_alive()
        lanes = sorted((ln for ln in core._lanes.values() if ln.active),
                       key=lambda ln: ln.node.cluster_id)
        assert len(lanes) == 2
        yield core, lanes[0], lanes[1]
    finally:
        nh.stop()


def _mirror(core, lane, first, commit, last):
    g = lane.g
    core._m_devfirst[g] = first
    core._m_commit[g] = commit
    core._m_last[g] = last


def _pack(core, hot, idle, staged=3):
    """One sampled pack of both lanes with `staged` proposals on the hot
    one: (a launch follows, n.hot_commit_entries, n.hot_uncommitted_rows)."""
    from dragonboat_tpu.types import Entry

    hot.staged_props.extend(Entry(cmd=b"x" * 16) for _ in range(staged))
    core._carry.discard(hot)
    core.profiler.samples.clear()
    core.profiler.sampling = True
    had, _packs = core._pack({hot, idle})
    s = core.profiler.samples
    return had, s["n.hot_commit_entries"].mean(), s[
        "n.hot_uncommitted_rows"].mean()


def test_pack_counts_the_fullest_leader_lane(two_stopped_leaders):
    core, hot, idle = two_stopped_leaders
    E = core.kcfg.max_entries_per_msg
    _mirror(core, idle, 1, 5, 5)  # nothing uncommitted, nothing moves
    _mirror(core, hot, 1, 10, 30)
    # first sight of both: no commit moved yet; 20 waiting, 3 rows of 8
    assert _pack(core, hot, idle) == (True, 0.0, 3.0)
    # the hot lane commits 12 and appends 10; the idle one stands
    _mirror(core, hot, 1, 22, 40)
    assert _pack(core, hot, idle) == (True, 12.0, float(-(-18 // E)))
    # the idle lane's commit moves with nothing waiting: the fullest is
    # still the hot lane, whose commit stood still
    _mirror(core, idle, 1, 9, 9)
    assert _pack(core, hot, idle) == (True, 0.0, 3.0)
    # nothing uncommitted anywhere: the lane whose commit moved the most
    _mirror(core, hot, 1, 40, 40)
    _mirror(core, idle, 1, 11, 11)
    assert _pack(core, hot, idle) == (True, 18.0, 0.0)


def test_an_unsampled_pack_counts_no_lane(two_stopped_leaders):
    core, hot, idle = two_stopped_leaders
    from dragonboat_tpu.types import Entry

    _mirror(core, hot, 1, 10, 30)
    hot.staged_props.append(Entry(cmd=b"x" * 16))
    core._carry.discard(hot)
    assert not core.profiler.sampling
    had, _packs = core._pack({hot, idle})
    assert had and core.profiler.samples == {}
    assert hot.commit_seen is None and idle.commit_seen is None

