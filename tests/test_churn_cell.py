"""The churn cell's deployment at a small size (ISSUE 31): 8 groups x 5
replicas that snapshot, compact and have followers replaced while the
closed loop runs, in both orders of the K=1 loop, held to the two plain
references (the ledger and the membership dict); and the generator's own
accounting on made-up batches. The benchmark's generator drives the
system here exactly as it does on the chip, only smaller and shorter."""
import os
import types

import pytest

from benchmark.lib import check, deploy, loadgen
from benchmark.run import load_cell, load_plugin
from dragonboat_tpu.profile import compile_watch, diff_compiles

CELL = "fleet1024x5.churn"
GROUPS = 8
churn = load_plugin("generators", "closed_loop_churn")


def _cell(**traffic_over):
    _spec, _cell_, config, traffic = load_cell(CELL)
    # run_bound_s 0: no process watchdog inside pytest
    return config, {**traffic, "run_bound_s": 0, **traffic_over}


@pytest.mark.parametrize("kind", ["vector", "vector-overlap"])
def test_replacements_under_load_hold_both_references(kind, tmp_path):
    config, traffic = _cell(run_in_s=4, drain_s=60)
    seed = 31
    ledger = loadgen.Ledger(loadgen.Payloads(seed, GROUPS), GROUPS)
    gen = churn.Generator(traffic, GROUPS, ledger, seed, 3.0, 1.0)
    sm = load_plugin("statemachines", config["statemachine"]).StateMachine
    over = {"overlap_decode": True} if kind == "vector-overlap" else {}
    cluster = deploy.Cluster(config, GROUPS, sm, str(tmp_path), over)
    marks = {}
    try:
        assert cluster.core._overlap is (kind == "vector-overlap")
        cluster.start()
        cluster.wait_leaders(120.0)
        gen.warm(cluster)
        gen.measure(
            cluster,
            lambda t: marks.setdefault("open", compile_watch().snapshot()),
            lambda t: marks.setdefault("close", compile_watch().snapshot()),
        )
        got = gen.results()
        # the plain ledger, read back on all five hosts (check.read_back:
        # linearizable on leader and follower, every replica converged,
        # the re-created ones too)
        checked = check.read_back(cluster, ledger, seed)
        assert checked["groups_exact"] == GROUPS
        # the plain membership reference, equal on every host
        assert gen.wrong == [] and got["reads_wrong"] == 0
        done = [r for r in gen.replacements if r.state == churn.DONE]
        assert len(done) == len(gen.replacements) >= 4
        for r in done:
            for nh in cluster.hosts.values():
                m = nh.get_cluster_membership(r.g + 1)
                assert r.victim in m.removed
                # (a group's turn may have come twice: 2 -> 7 -> 12)
                assert r.fresh in m.addresses or r.fresh in m.removed
            # the joiner came up from a snapshot: the log behind it was
            # compacted, so its own log starts past index 1
            node = cluster.hosts[r.fresh]._get_node(r.g + 1)
            if node.node_id() == r.fresh:  # not replaced again since
                assert node.log_reader.get_range()[0] > 1
                assert node.snapshots_installed >= 1
        # nothing a client would feel
        assert got["failed"] == 0 and got["attempted"] > 0
        assert got["client.stalled_groups"] == 0
        assert got["client.leader_moves_in_window"] == 0, got["leader_moves"]
        assert cluster.core.counter_stats()["elections_started"] >= GROUPS
        compiles = diff_compiles(marks["open"], marks["close"])
        assert compiles["total"] == 0, compiles
        assert cluster.core.step_stats()["loop_exceptions"] == 0
        # every group snapshotted, on every replica that served all along
        saved = sum(
            nh._get_node(g + 1).snapshots_saved
            for nh in cluster.hosts.values() for g in range(GROUPS)
        )
        assert saved >= GROUPS
    finally:
        cluster.stop()


# ---- the references reject what they exist to reject ----------------------


def _ledger_after(batches: int):
    ledger = loadgen.Ledger(loadgen.Payloads(7, 1), 1)
    for _ in range(batches):
        lo, hi, _cmds = ledger.take(0, 64)
        ledger.settle(0, lo, hi, 64, 0)
    return ledger


@pytest.mark.parametrize("fault", ["run_applied_twice", "write_missing"])
def test_the_ledger_rejects(fault):
    ledger = _ledger_after(3)
    count, total = ledger.expected(0)
    ledger.check(0, "a sound replica", (count, total))
    if fault == "run_applied_twice":
        got = (count + 64, (total + ledger.payloads.sum64(0, 64)) % (1 << 64))
    else:
        got = (count - 1, total)
    with pytest.raises(loadgen.CheckFailure):
        ledger.check(0, "a re-created replica", got)


def _membership(addresses, removed=(), ccid=9):
    return types.SimpleNamespace(
        addresses=dict(addresses), removed=dict.fromkeys(removed, True),
        config_change_id=ccid,
    )


@pytest.mark.parametrize("fault", [
    "none", "one_host_kept_the_victim", "one_host_lacks_the_joiner",
    "removed_differs", "config_change_id_differs",
])
def test_the_membership_reference(fault):
    members = {n: f"bench:{n}" for n in range(1, 6)}
    ref = churn.MembershipReference(2, members)
    ref.delete(1, 3)
    ref.add(1, 8, "bench:3")
    want = {**{n: a for n, a in members.items() if n != 3}, 8: "bench:3"}
    views = [(f"host {h}", _membership(want, [3])) for h in range(1, 6)]
    if fault == "one_host_kept_the_victim":
        views[2] = ("host 3", _membership({**want, 3: "bench:3"}, []))
    elif fault == "one_host_lacks_the_joiner":
        views[4] = ("host 5", _membership(
            {n: a for n, a in want.items() if n != 8}, [3]))
    elif fault == "removed_differs":
        views[0] = ("host 1", _membership(want, []))
    elif fault == "config_change_id_differs":
        views[1] = ("host 2", _membership(want, [3], ccid=7))
    wrong = ref.wrong(1, views)
    assert (wrong == []) == (fault == "none"), wrong
    # the group nobody touched still has its five
    assert ref.wrong(0, [("host 1", _membership(members))]) == []
    with pytest.raises(AssertionError):
        ref.add(1, 3, "bench:3")  # a removed id never returns


# ---- the generator's accounting on made-up batches -------------------------


def test_a_batch_counts_by_the_share_of_its_life_inside_the_window():
    # window [10, 20): group 0 has two whole cycles and one that straddles
    # the close; group 1 straddles both edges; group 2 spans the window
    batches = [
        (0, 11.0, 14.0, 64, 0), (0, 14.0, 18.0, 64, 0),
        (0, 18.0, 22.0, 64, 0),  # submitted inside, looked after: no cycle
        (1, 8.0, 15.0, 64, 0), (1, 15.0, 26.0, 60, 4),
        (2, 5.0, 30.0, 64, 0),
    ]
    a = churn.account(batches, 3, 10.0, 20.0)
    assert a["stalled_groups"] == 2
    assert a["cycles"] == 2
    assert a["whole_cycle_ops_per_s"] == pytest.approx(128 / 7.0)
    work = 64 + 64 + 64 * 2 / 4 + 64 * 5 / 7 + 60 * 5 / 11 + 64 * 10 / 25
    assert a["committed_ops_per_s"] == pytest.approx(work / 10.0)
    # every write submitted in the window and not acknowledged
    assert (a["attempted"], a["failed"]) == (64 * 4, 4)


def test_in_flight_by_thirds_is_a_time_average():
    log = [(0.0, 2), (10.0, 4), (12.0, 8), (17.0, 6)]
    got = churn.thirds(log, 9.0, 18.0)
    assert got == pytest.approx([(2 * 1 + 4 * 2) / 3, 8.0, (8 * 2 + 6) / 3])


def _generator(**over):
    _config, traffic = _cell(**over)
    ledger = loadgen.Ledger(loadgen.Payloads(1, 4), 4)
    return churn.Generator(traffic, 4, ledger, 1, 2.0, 1.0)


def _fake_cluster(groups=4, replicas=5):
    hosts = {
        n: types.SimpleNamespace(
            has_node=lambda cid: True, raft_address=lambda n=n: f"bench:{n}")
        for n in range(1, replicas + 1)
    }
    return types.SimpleNamespace(
        groups=groups, replicas=replicas,
        hosts=churn._Hosts(hosts, replicas),
        _members={n: f"bench:{n}" for n in range(1, replicas + 1)},
    )


def test_a_start_over_the_cap_is_skipped_and_counted():
    gen = _generator(replace_max_inflight=2)
    cluster = _fake_cluster()
    gen.ref = churn.MembershipReference(4, cluster._members)
    leaders = [1, 2, 3, 4]
    for i in range(5):
        gen._start_one(cluster, leaders, float(i))
    assert len(gen.replacements) == 2 and gen.skipped == 3
    for r in gen.replacements:
        assert r.victim != leaders[r.g]  # a follower
        assert r.fresh == r.victim + 5  # a fresh id that names its host
        assert cluster.hosts[r.fresh] is cluster.hosts[r.victim]
    assert len({r.g for r in gen.replacements}) == 2  # one a group at a time


def test_a_replacement_that_never_finishes_is_counted_wrong():
    gen = _generator()
    cluster = _fake_cluster()
    gen.ref = churn.MembershipReference(4, cluster._members)
    gen._start_one(cluster, [1, 1, 1, 1], 0.0)
    gen._start_one(cluster, [1, 1, 1, 1], 0.5)
    gen.replacements[0].state = churn.DONE
    gen.replacements[0].t_done = 1.0
    gen.replacements[1].state = churn.CATCH_UP  # stuck there
    gen._close_replacements(cluster)
    assert len(gen.wrong) == 1 and "catch_up" in gen.wrong[0]
    gen.t_open, gen.t_close = 0.0, 2.0
    gen.batches = [(g, 0.1, 1.0, 64, 0) for g in range(4)]
    got = gen.results()
    assert got["reads_wrong"] == 1  # run.py folds it into `correct`
    assert got["client.replacements_done_in_window"] == 1
    assert got["replacements_by_state"] == {"done": 1, "catch_up": 1}


@pytest.mark.parametrize("lost, ends", [(1, False), (2, True)])
def test_a_fleet_that_stops_serving_ends_the_run(lost, ends):
    # 4 groups at a bound of a quarter: over one batch with a lost write
    # since the first replacement, the run ends at that look
    gen = _generator(lost_batches_bound_share=0.25)
    handle = types.SimpleNamespace(n=64, completed=60)
    for g in range(lost):
        lo, hi, _cmds = gen.ledger.take(g, 64)
        gen._finish(g, (handle, 0.0, lo, hi), 1.0)
    lo, hi, _cmds = gen.ledger.take(3, 64)
    whole = types.SimpleNamespace(n=64, completed=64)
    gen._finish(3, (whole, 0.0, lo, hi), 1.0)  # loses nothing, counts nothing
    assert gen.lost == lost
    if ends:
        with pytest.raises(churn.PhaseOverrun, match="not serving"):
            gen._hold_service(2.0)
    else:
        gen._hold_service(2.0)


def test_the_cell_is_declared_as_the_issue_names_it():
    spec, cell, config, traffic = load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fleet-1024x5-churn", "churn16.closed64", 1)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["groups"]
    assert os.path.basename(entry["file"]) == "fleet-1024x5-churn.json"
    d, r, e = config["deployment"], config["raft"], config["engine"]
    assert (d["groups"], d["replicas"], d["chips"]) == (1024, 5, 1)
    assert (r["snapshot_entries"], r["compaction_overhead"]) == (256, 64)
    assert (r["election_rtt"], r["heartbeat_rtt"]) == (100, 20)
    assert (e["max_peers"], e["inbox_depth"]) == (8, 8)
    assert (e["log_window"], e["max_entries_per_msg"]) == (256, 64)
    assert (traffic["batch"], traffic["timeout_s"], traffic["poll_ms"]) == (
        64, 15, 5)
    assert (traffic["replace_every_ms"], traffic["replace_max_inflight"],
            traffic["run_in_s"], traffic["drain_s"]) == (500, 64, 30, 60)
    assert traffic["lost_batches_bound_share"] == 0.25
