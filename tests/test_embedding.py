"""Embedding C ABI test: builds native/binding (libdbtpu.so + embed_demo)
and runs the pure-C++ demo app — NodeHost lifecycle, cluster start with a
C++ SM plugin, propose, linearizable read, missing-key read, stop — all
through the flat C API with no Python in the app
(cf. reference binding/binding.go + binding/cpp tests)."""
import os
import subprocess

import pytest

from conftest import make_native

_NATIVE = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "native"))
_DEMO = os.path.join(_NATIVE, "build", "embed_demo")
_OO_DEMO = os.path.join(_NATIVE, "build", "oo_demo")
_PLUGIN = os.path.join(_NATIVE, "build", "libkvstore_sm.so")
_ONDISK_PLUGIN = os.path.join(_NATIVE, "build", "libdiskkv_sm.so")


def _built() -> bool:
    import shutil

    if shutil.which("g++") is None or shutil.which("python3-config") is None:
        return False  # genuinely no toolchain: skip
    # toolchain present: a build FAILURE must fail loudly, not skip —
    # except a missing libpython dev install, which is a missing optional
    # dependency like an absent compiler
    proc = make_native("all", "embed")
    if proc.returncode != 0:
        if "Python.h" in proc.stderr:
            return False
        raise RuntimeError(f"native build failed:\n{proc.stderr}")
    return os.path.exists(_DEMO) and os.path.exists(_PLUGIN)


pytestmark = pytest.mark.skipif(
    not _built(), reason="native toolchain unavailable"
)


@pytest.mark.slow
def test_embed_demo_runs(tmp_path):
    env = dict(os.environ)
    repo = os.path.abspath(os.path.join(_NATIVE, ".."))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [_DEMO, str(tmp_path), _PLUGIN],
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "EMBED DEMO PASS" in proc.stdout


@pytest.mark.slow
def test_oo_demo_runs(tmp_path):
    """Pure-C++ app over the OO wrapper (dragonboat_tpu.hpp): sessions,
    sync/async proposals (RequestState + Event), ReadIndex/ReadLocal,
    membership + observer add, snapshot request, restart with the on-disk
    C++ plugin recovering its applied index (cf. reference dragonboat.h
    NodeHost/Session/RequestState surface)."""
    env = dict(os.environ)
    repo = os.path.abspath(os.path.join(_NATIVE, ".."))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DBTPU_DISKKV_DIR"] = str(tmp_path / "diskkv")
    proc = subprocess.run(
        [_OO_DEMO, str(tmp_path), _ONDISK_PLUGIN],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "OO DEMO PASS" in proc.stdout
