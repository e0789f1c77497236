"""Back-compat conformance shim over `dragonboat_tpu.analysis`.

The four rule families that used to live HERE as ~460 lines of ad-hoc
AST walking — columnar (PR 1), lock-amortization (PR 2), telemetry-guard
(PR 3), trace-guard (PR 4) — now run on the shared rule engine
(dragonboat_tpu/analysis/, targets declared in analysis/targets.py,
suppression via `# lint: allow(rule) reason` pragmas). This file keeps
the historical test names alive as thin assertions over the engine so
existing CI habits (`pytest tests/test_hot_path_lint.py`) keep guarding
exactly the same regressions; the full gate (all seven families + the
meta-tests) is tests/test_static_analysis.py and
`python -m dragonboat_tpu.tools.check`.
"""
from __future__ import annotations

import pytest

from dragonboat_tpu.analysis import build_analyzer, unsuppressed
from dragonboat_tpu.analysis.engine import SourceModule
from dragonboat_tpu.analysis.targets import DEFAULT_TARGETS

pytestmark = pytest.mark.lint

# back-compat names: the target lists now live in analysis/targets.py
HOT_FUNCTIONS = sorted(DEFAULT_TARGETS.hot_functions)
HOT_LOCK_FUNCTIONS = sorted(DEFAULT_TARGETS.hot_lock_functions)
HOT_TELEMETRY_FUNCTIONS = sorted(DEFAULT_TARGETS.hot_telemetry_functions)
HOT_TRACE_FUNCTIONS = sorted(DEFAULT_TARGETS.hot_trace_functions)


def _family_clean(*families):
    findings = unsuppressed(build_analyzer(families=families).run())
    assert not findings, "\n" + "\n".join(f.render() for f in findings)


def _snippet(src, relpath, *families):
    a = build_analyzer(families=families)
    return [
        f
        for f in a.run_module(SourceModule.from_snippet(src, relpath))
        if not f.suppressed
    ]


def test_hot_path_stays_columnar():
    _family_clean("columnar")


def test_transport_send_path_amortizes_locks():
    _family_clean("locks")


def test_hot_path_telemetry_is_sampling_guarded():
    _family_clean("telemetry")


def test_trace_stamping_is_sampling_guarded():
    _family_clean("trace")


def test_lint_catches_regressions():
    """The lint itself must flag the banned patterns (meta-test: a broken
    linter silently passing everything is worse than no linter)."""
    got = _snippet(
        """
        def gather_post_sends(o, gs):
            for g in gs.tolist():
                x = int(o['term'][g])
                y = o['match'][g].tolist()
                z = o['vote'][g].item()
        """,
        "engine/vector.py",
        "columnar",
    )
    assert len(got) == 3, got


def test_lock_lint_catches_regressions():
    got = _snippet(
        """
        class _SendQueue:
            def put_many(self, msgs):
                n = 0
                for m in msgs:
                    with self._cv:
                        n += 1
                with self._cv:
                    pass
                return n
        """,
        "transport/transport.py",
        "locks",
    )
    assert len(got) == 1, got


def test_telemetry_lint_catches_regressions():
    got = _snippet(
        """
        class Transport:
            def send_many(self, msgs):
                for m in msgs:
                    self.metrics.observe('x', (0, 0), 1.0)
                recorder.record('evt', a=1)
                if self.profiler.sampling:
                    self.metrics.observe('x', (0, 0), 1.0)
                if lat_sampler.sample():
                    recorder.record('evt')
        """,
        "transport/transport.py",
        "telemetry",
    )
    assert len(got) == 2, got


def test_trace_lint_catches_regressions():
    got = _snippet(
        """
        class Node:
            def propose(self, session, cmd, timeout_ticks):
                entry.trace_id = mint_trace_id()
                recorder.record('propose_enqueue', trace=entry.trace_id)
                if self._req_sampler.sample():
                    entry.trace_id = mint_trace_id()
                    recorder.record('propose_enqueue')
                if entry.trace_id:
                    recorder.record('replicate_send')
        """,
        "engine/node.py",
        "trace",
    )
    assert len(got) == 3, got
