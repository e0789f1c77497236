"""A follower takes no more Replicate entries in one step than its device
window has room for (ISSUE 31). The leader's own window bounds what it
replicates a step, but not a backlog released at once: Replicates held
host-side while a snapshot restored, or host-log catch-up sent one a
launch while launches were slow. On the chip five rows of 64 entries into
a window of 256 wrapped the ring; the replica lost entries it had
acknowledged and the loop raised `log hole`."""
import time

import pytest

from benchmark.lib import deploy, loadgen
from benchmark.run import load_plugin

CONFIG = {
    "deployment": {"groups": 1, "replicas": 3},
    "statemachine": "kv16",
    "nodehost": {"rtt_millisecond": 5},
    "raft": {"election_rtt": 40, "heartbeat_rtt": 4},
    "engine": {"max_peers": 4, "log_window": 32, "inbox_depth": 16,
               "max_entries_per_msg": 16},
}
BATCHES = 6  # x 16 entries: three windows' worth


@pytest.mark.parametrize("kind", ["vector", "vector-overlap"])
def test_a_released_backlog_never_overruns_the_window(kind, tmp_path):
    sm = load_plugin("statemachines", "kv16").StateMachine
    over = {"overlap_decode": True} if kind == "vector-overlap" else {}
    cluster = deploy.Cluster(CONFIG, 1, sm, str(tmp_path), over)
    payloads = loadgen.Payloads(5, 1)
    try:
        cluster.start()
        leader = cluster.wait_leaders(60.0)[0]
        core = cluster.core
        follower = next(n for n in cluster.hosts if n != leader)
        lane = cluster.hosts[follower]._get_node(1)._vec_lane
        nh = cluster.hosts[leader]
        h = nh.propose_batch_async(
            cluster.session(leader, 0), payloads.cmds(0, 0, 16), 10.0)
        assert h.wait(20) and h.completed == 16
        # a restore that takes a while: the lane's messages are held
        # (as _handle_install_snapshot marks it: off the device's routes)
        lane.recovering = True
        core._m_recovering[lane.g] = True
        core._routes_dirty = True
        for i in range(1, 1 + BATCHES):
            h = nh.propose_batch_async(
                cluster.session(leader, 0),
                payloads.cmds(0, 16 * i, 16 * i + 16), 10.0)
            assert h.wait(20) and h.completed == 16  # the other two commit
        deadline = time.monotonic() + 20
        held = 0
        while time.monotonic() < deadline:
            held = sum(len(m.entries) for m in list(lane.msg_backlog))
            if held > 2 * CONFIG["engine"]["log_window"]:
                break
            time.sleep(0.02)
        assert held > 2 * CONFIG["engine"]["log_window"], held
        lane.recovering = False
        core._m_recovering[lane.g] = False
        core._routes_dirty = True
        core.set_node_ready(lane.key)
        want = nh.stale_read(1, None)
        assert want[0] == 16 * (1 + BATCHES)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if cluster.hosts[follower].stale_read(1, None) == want:
                break
            time.sleep(0.05)
        assert cluster.hosts[follower].stale_read(1, None) == want
        assert core.step_stats()["loop_exceptions"] == 0
        first, last = cluster.hosts[follower]._get_node(1).log_reader.get_range()
        assert last >= 16 * (1 + BATCHES)
    finally:
        cluster.stop()
