"""Host-log catch-ups started per launch (`n.catchups_started`: one a
peer that VectorEngine._start_catchup or ._below_window_reject began to
serve from the leader's host log, because it fell below the device
window). 0 where the program ran its catch-up sweep and started none;
None on a program without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "catchup_entries") is None:
        return None
    launches = run.window["launches"]
    if not launches:
        return None
    return (spans.count(run, "catchups_started") or 0) / launches
