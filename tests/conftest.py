"""Test configuration: force JAX onto a virtual 8-device CPU mesh so that
multi-chip sharding paths are exercised without TPU hardware. The pin
(cpu platform + host device count) lives in dragonboat_tpu._jaxenv."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dragonboat_tpu._jaxenv import enable_compile_cache, pin_cpu

pin_cpu(n_devices=8)
# warm XLA compiles across pytest processes: the step kernel costs seconds
# per distinct KernelConfig, and election-deadline tests race exactly that
# first compile on slow boxes
enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration/chaos tests"
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded FaultPlane chaos tests — bounded enough for tier-1; "
        "select the matrix alone with `-m chaos` (seeds print on failure "
        "so any run replays from the CI log)",
    )
    config.addinivalue_line(
        "markers",
        "lint: the static-analysis gate (dragonboat_tpu.analysis over the "
        "whole package + per-rule meta-tests) — the pure-AST, jax-free "
        "slice of tier-1; run it alone with `-m lint` for a sub-second "
        "pre-commit check (same gate as `python -m "
        "dragonboat_tpu.tools.check`)",
    )
    config.addinivalue_line(
        "markers",
        "perf: the perf-attribution gate — the runtime device-sync/retrace "
        "audit assertions over a live vector-engine scenario; run it "
        "alone with `-m perf` alongside the `-m lint` gate",
    )
    config.addinivalue_line(
        "markers",
        "serving: the overload robustness gate (dragonboat_tpu.serving) — "
        "admission control, backpressure folding, deadline-aware retry, "
        "quiesce wake-on-admit, and the seeded overload_storm graceful-"
        "degradation verdict; run it alone with `-m serving`",
    )
    config.addinivalue_line(
        "markers",
        "longhaul: the drummer-style long-haul runner's bounded smoke "
        "profile (tools.longhaul with a tight --budget, <60s) — tier-1 "
        "proves the runner end to end (rounds, verdicts, failure "
        "bundles); the hours-long profile stays opt-in via "
        "`python -m dragonboat_tpu.tools.longhaul --budget <secs>`",
    )


# ---- hang diagnosis (the Python half of the race-detection story; see
# SURVEY §5: no -race exists for Python, so concurrency bugs here surface
# as deadlocks/stalls under the chaos + differential suites) ----
# If any single test wedges for 10 minutes, dump every thread's stack so
# the lock cycle is visible in CI output instead of an opaque timeout.
import faulthandler  # noqa: E402

import pytest  # noqa: E402

_HANG_DUMP_S = 600

# ---- engine-kind test ids. "vector-overlap" is the vector engine in the
# K=1 loop order every one-chip cell of the benchmark runs: decode of step
# t-1 at the top of the iteration, maintain owed behind the launch, a
# step in flight across iterations. `overlap_decode` is auto-on only off
# the CPU, so a plain "vector" case here runs the other order. ----
VECTOR_KINDS = ("vector", "vector-overlap")
ENGINE_KINDS = ("scalar",) + VECTOR_KINDS


def pytest_collection_modifyitems(items):
    """The cells' rehearsals go last: each is a subprocess that keeps two
    to three cores and the disk busy (every fsync honoured), and beside
    the suite's load-sensitive tests it cost them whole runs. Last, one
    worker runs them while the others finish and then go idle."""
    items.sort(key=lambda item: item.path.name == "test_cells_rehearse.py")


def engine_kw(kind_id: str) -> dict:
    """The EngineConfig keywords an engine-kind test id stands for."""
    if kind_id == "vector-overlap":
        return {"kind": "vector", "overlap_decode": True}
    return {"kind": kind_id}


def make_native(*targets: str):
    """`make -C native <targets>`, one at a time: every xdist worker
    imports every test file, and in a fresh checkout their builds would
    otherwise race each other in native/build."""
    import fcntl
    import subprocess

    native = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "native"
    )
    os.makedirs(os.path.join(native, "build"), exist_ok=True)
    with open(os.path.join(native, "build", ".lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        return subprocess.run(
            ["make", "-C", native, *targets],
            capture_output=True, text=True, timeout=300,
        )


def host_of_kind(nh, kind_id: str):
    """`nh`, once its engine is up, checked to run the order its id names."""
    if kind_id == "vector-overlap":
        assert nh.engine.core._overlap is True
    return nh

# ---- crash-persistent ring (the timeout-kill half of the forensics
# story): JSONL failure dumps only happen when pytest survives to report —
# a pytest-timeout / `timeout -k` SIGKILL leaves nothing. The session-wide
# mmap ring persists every recorded event the moment it happens (mmap
# pages live in the kernel page cache, so they survive ANY process death);
# after a killed run, `python -m dragonboat_tpu.tools.timeline
# .pytest_flight/live.ring` (`live-gw<n>.ring` per pytest-xdist worker)
# replays the tail, and the per-test
# `_test_start` markers show which test was running when the axe fell. ----
import atexit  # noqa: E402
import signal  # noqa: E402


def _flight_dump_dir() -> str:
    d = os.environ.get("FLIGHT_DUMP_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", ".pytest_flight"
    )
    return os.path.abspath(d)


def _attach_session_ring():
    try:
        from dragonboat_tpu.trace import flight_recorder

        # one ring per process: attach_mmap rotates a ring that is there
        # to .prev, so xdist workers handed one path would rename each
        # other's rings away and read back somebody else's
        worker = os.environ.get("PYTEST_XDIST_WORKER")
        path = os.environ.get("FLIGHT_RING_PATH") or os.path.join(
            _flight_dump_dir(),
            f"live-{worker}.ring" if worker else "live.ring",
        )
        rec = flight_recorder()
        rec.attach_mmap(path)
        atexit.register(rec.flush)
        # `timeout -k` sends SIGTERM first: flush the ring and fall back
        # to the default action so the artifact is complete even when the
        # follow-up SIGKILL never becomes necessary
        if signal.getsignal(signal.SIGTERM) in (
            signal.SIG_DFL, signal.default_int_handler,
        ):
            def _on_term(signum, frame):
                try:
                    rec.flush()
                finally:
                    signal.signal(signal.SIGTERM, signal.SIG_DFL)
                    os.kill(os.getpid(), signal.SIGTERM)

            signal.signal(signal.SIGTERM, _on_term)
    except Exception:
        pass  # forensics must never block the test run


_attach_session_ring()


def pytest_runtest_setup(item):
    faulthandler.dump_traceback_later(_HANG_DUMP_S, exit=False)
    # fresh flight-recorder timeline per test: a failure dump must show
    # THIS test's events, not the tail of whatever ran before it. The
    # mmap ring is NOT reset — it spans the session so a timeout kill
    # keeps the recent cross-test tail; the marker delimits tests.
    try:
        from dragonboat_tpu.trace import flight_recorder

        rec = flight_recorder()
        rec.reset()
        # nodeid clipped so the marker always fits one mmap ring slot
        rec.record("_test_start", nodeid=item.nodeid[-160:])
    except Exception:
        pass


def pytest_runtest_teardown(item, nextitem):
    faulthandler.cancel_dump_traceback_later()


# ---- flight recorder failure dump (the forensic half of the CHAOS_SEED
# story): any test failure writes the process-global FlightRecorder ring
# as JSONL next to the printed seed, so a chaos replay comes with the
# timeline of what the cluster actually did — leader changes, breaker
# trips, queue evictions, fault injections, fairness clamps. ----
import json as _json  # noqa: E402
import re as _re  # noqa: E402


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if not rep.failed:
        return  # dump on ANY failing phase: setup failures (cluster never
        # elected) and teardown assertions need the timeline most
    try:
        from dragonboat_tpu.trace import flight_recorder

        rec = flight_recorder()
        events = rec.dump()
        if not events:
            return
        dump_dir = os.environ.get("FLIGHT_DUMP_DIR") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..", ".pytest_flight"
        )
        dump_dir = os.path.abspath(dump_dir)
        os.makedirs(dump_dir, exist_ok=True)
        safe = _re.sub(r"[^A-Za-z0-9_.-]+", "_", item.nodeid)[-120:]
        suffix = "" if rep.when == "call" else f"-{rep.when}"
        path = os.path.join(dump_dir, safe + suffix + ".jsonl")
        with open(path, "w") as f:
            # the _meta header carries this process's mono->wall offset so
            # tools.timeline can merge this dump with other hosts'/rings'
            f.write(rec.to_jsonl(meta={"source": safe}) + "\n")
        tail = "\n".join(
            _json.dumps(e, default=str, sort_keys=True) for e in events[-25:]
        )
        rep.sections.append(
            (
                "flight recorder",
                f"{len(events)} events -> {path}\nlast events:\n{tail}",
            )
        )
    except Exception:
        pass  # the dump must never turn a failure into an error
