"""trace_reduce on a trace recorded on the v5e and on a synthetic one."""
import os

import pytest
from jax.profiler import ProfileData

from benchmark.lib import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


def test_recorded_v5e_trace_gives_the_known_busy_share():
    """Recorded in PR 22 on one TPU v5 lite chip: three executions of a
    jitted scan of four sorts, 50 ms apart, Python tracer off."""
    profile = tr.load(RECORDED)
    ops = tr.device_events(profile)
    modules = tr.device_events(profile, tr.MODULES_LINE)
    assert list(ops) == [0] and len(ops[0]) == 72
    name, runs = tr.main_program(modules[0], 0.0, 1e12)
    assert name.startswith("jit_f(") and len(runs) == 3
    # the operations fill the three executions and nothing else
    assert tr.busy_ns(ops[0]) == pytest.approx(2_313_709, abs=2)
    assert sum(e - s for s, e in runs) == pytest.approx(2_313_740, abs=2)
    window = (90e6, 210e6)  # ns; holds all three
    share = tr.busy_ns(tr.clip(ops[0], *window)) / (window[1] - window[0])
    assert share == pytest.approx(0.019281, abs=1e-6)
    # the loop's event spans its body: own time goes to the sorts
    top = tr.top_ops(ops[0], 2)
    assert top[0][0] == "sort.9" and top[0][1] == pytest.approx(2.172544e-3)
    idle = tr.gaps(ops[0], *window)
    assert [round((b - a) / 1e6, 1) for a, b in idle[:2]] == [51.3, 51.2]
    assert tr.anchor_ns(profile) == 45504790.0


SYNTHETIC = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 1500000000 duration_ps: 1000000000 }
    events { metadata_id: 3 offset_ps: 3000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 8000000000 duration_ps: 1000000000 }
  }
  lines {
    name: "XLA Modules"
    events { metadata_id: 4 offset_ps: 1000000000 duration_ps: 4000000000 }
    events { metadata_id: 4 offset_ps: 8000000000 duration_ps: 1000000000 }
    events { metadata_id: 5 offset_ps: 6000000000 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while = (s32[]) while(x)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = s32[8]{0} fusion(y)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-gather.2 = s32[32]{0} all-gather(z)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(1)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_where(2)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "t" events { metadata_id: 1 offset_ps: 500000000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "benchmark_anchor" } }
}
"""


def test_union_nesting_gaps_and_attribution_on_a_synthetic_trace():
    """One device, milliseconds: a loop over [1, 5) holding a fusion over
    [1.5, 2.5) and an all-gather over [3, 5); a fusion over [8, 9)."""
    profile = ProfileData.from_text_proto(SYNTHETIC)
    ev = tr.device_events(profile)[0]
    ms = 1e6
    assert tr.busy_ns(ev) == 5 * ms  # the union, not the sum (8)
    own = tr.self_times(ev)
    assert own["%while = (s32[]) while(x)"] == 1 * ms
    assert own["%fusion.1 = s32[8]{0} fusion(y)"] == 2 * ms
    assert tr.top_ops(ev, 1) == [["fusion.1 s32[8]{0}", 0.002]]
    assert tr.collective_ns(ev) == 2 * ms
    assert tr.gaps(ev, 0.0, 10 * ms) == [(5 * ms, 8 * ms), (0.0, 1 * ms), (9 * ms, 10 * ms)]
    assert tr.busy_ns(tr.clip(ev, 2 * ms, 4 * ms)) == 2 * ms
    name, runs = tr.main_program(
        tr.device_events(profile, tr.MODULES_LINE)[0], 0.0, 10 * ms
    )
    assert name == "jit_step(1)" and runs == [(1 * ms, 5 * ms), (8 * ms, 9 * ms)]
    assert tr.anchor_ns(profile) == 0.5 * ms
    spans = [(4.5 * ms, 6 * ms, "save"), (6 * ms, 7.5 * ms, "apply")]
    assert tr.attribute_gaps(tr.gaps(ev, 0.0, 10 * ms), spans) == [
        ["host", 0.0025], ["apply", 0.0015], ["save", 0.001]
    ]


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    host_only = SYNTHETIC[SYNTHETIC.index('planes {\n  name: "/host:CPU"'):]
    assert tr.device_events(ProfileData.from_text_proto(host_only)) == {}
