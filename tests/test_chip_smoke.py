"""chip_smoke.py on the CPU: the scenario in rehearsal at a tiny size, the
no-fallback exit, the compile-cache placement and the one-device mesh
refusal. The chip itself is reached only through `python chip_smoke.py` on
a machine that has one; nothing here is a device result."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from dragonboat_tpu import _jaxenv
from dragonboat_tpu.config import EngineConfig, NodeHostConfig
from dragonboat_tpu.engine import vector

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rehearsal_scenario_both_passes(tmp_path):
    """8 groups x 3 replicas, K=1 then K=8, with the smoke's own read-back
    checks (run_pass raises on an acknowledged write that is not read back
    from leader and follower hosts, or a replica that never converges)."""
    groups = chip_smoke.REHEARSAL_GROUPS
    passes = chip_smoke.run_smoke(
        groups, seed=7, mesh=False, workdir=str(tmp_path / "work")
    )
    per_group = chip_smoke.WAVES * chip_smoke.WAVE + chip_smoke.TAIL_WAVE
    assert [p["steps_per_sync"] for p in passes] == [1, 8]
    for p in passes:
        assert p["acknowledged"] == groups * per_group
        assert p["loop_exceptions"] == 0
        assert p["engine_steps"] > 0
    # K=8 is the on-device router: co-hosted traffic never left the device
    assert passes[1]["msgs_routed_device"] > 0
    assert not os.path.exists(tmp_path / "work")


def test_read_back_rejects_a_missing_write():
    """The check the smoke exists for: a replica whose state lacks an
    acknowledged payload fails the read-back."""

    class Host:
        def __init__(self, state):
            self.state = state

        def sync_read(self, c, q, timeout_s):
            return self.state

        def stale_read(self, c, q):
            return self.state

    ledger = chip_smoke._Ledger(groups=1, rows=8, seed=3)
    ledger.take(1, 4)
    ledger.acked[1] = 4
    good = ledger.expected(1)
    words = ledger.payloads[0, :3].view("<u8")
    lost = (3, int(words.sum(dtype="uint64")))
    hosts = {1: Host(good), 2: Host(good), 3: Host(good)}
    chip_smoke._read_back(hosts, {1: 1}, ledger)
    hosts[2] = Host(lost)
    with pytest.raises(chip_smoke.SmokeFailure, match="follower-host"):
        chip_smoke._read_back(hosts, {1: 1}, ledger)
    # a batch cut short leaves a range, and still refuses a lost write
    ledger.indeterminate[1] = 2
    hosts = {n: Host((5, 0)) for n in (1, 2, 3)}
    chip_smoke._read_back(hosts, {1: 1}, ledger)
    hosts[2] = Host(lost)
    with pytest.raises(chip_smoke.SmokeFailure, match=r"outside \[4, 6\]"):
        chip_smoke._read_back(hosts, {1: 1}, ledger)


def test_plain_invocation_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_placement(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: JAX honours the variable, code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert _jaxenv.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        # otherwise: the one fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert _jaxenv.enable_compile_cache() == _jaxenv.COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            _REPO, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_shard_over_mesh_on_one_device_raises(monkeypatch):
    one = jax.devices()[:1]
    monkeypatch.setattr(vector.jax, "devices", lambda: one)
    cfg = NodeHostConfig(
        engine=EngineConfig(kind="vector", max_groups=8, shard_over_mesh=True)
    )
    with pytest.raises(ValueError, match="more than one visible jax device"):
        vector.VectorEngine(None, nh_config=cfg)
