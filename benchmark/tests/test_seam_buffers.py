"""The reader of `seam.buffers_per_launch` against synthetic windows: the
arrays the launches moved over the launches, None where the program
folded no such counter (a program older than it) and below full
sampling."""
import types

import pytest

from benchmark.run import load_plugin


def _window(ratio=1, **phases):
    return types.SimpleNamespace(window={
        "seconds": 15.0, "launches": 2.0, "phase_ratio": ratio,
        "phases": dict(phases),
    })


@pytest.mark.parametrize("moved, want", [
    (8.0, 4.0),  # two packed launches: two slabs each way
    (140.0, 70.0),  # two mesh launches: a plane each
    (65.0, 32.5),  # one of each kind
])
def test_two_launches(moved, want):
    read = load_plugin("layer_metrics", "seam.buffers_per_launch").read
    assert read(_window(**{"n.launches": 2.0, "n.seam_buffers": moved})) \
        == pytest.approx(want)


def test_nothing_without_the_counter():
    read = load_plugin("layer_metrics", "seam.buffers_per_launch").read
    # the parent: launches folded, the seam's arrays not
    assert read(_window(**{"n.launches": 2.0, "put": 0.01})) is None
    assert read(_window(32, **{"n.launches": 2.0, "n.seam_buffers": 8.0})) \
        is None
    assert read(_window(**{"n.launches": 0.0, "n.seam_buffers": 0.0})) is None
