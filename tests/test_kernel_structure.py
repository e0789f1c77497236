"""Structure of the lowered step kernel: no gather over a whole ring plane.

A contiguous run of ring slots modulo W is a per-row rotation
(`ops.kernel._rotate_rows`). Written as a `take_along_axis` with a full
index plane it lowers on the TPU to a general gather at about 10 ns an
element, which at 3 072 lanes was 98 % of the kernel (PERF.md, PR 27). The
single-index lookups (`_term_at`, the fan-out's `prev_term`, the quorum and
ReadIndex picks) gather G, G·P or G·R elements and stay.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from dragonboat_tpu.ops.kernel import multi_step_batch, step_batch
from dragonboat_tpu.ops.state import KernelConfig, init_state, make_empty_inbox

pytestmark = pytest.mark.lint

# sizes whose products are all distinct, so a gather's element count names
# the plane it was taken over
CFG = KernelConfig(
    groups=6, peers=3, log_window=20, inbox_depth=2, max_entries_per_msg=7,
    readindex_depth=5,
)
G, P, W, E = CFG.groups, CFG.peers, CFG.log_window, CFG.max_entries_per_msg
RING_PLANES = {G * W: "G*W", G * E: "G*E", G * P * E: "G*P*E"}

_GATHER = re.compile(r"stablehlo\.gather.*->\s*tensor<((?:\d+x)*)[a-z]\w*>\s*$")


def _gather_sizes(lowered_text: str):
    sizes = []
    for line in lowered_text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        m = _GATHER.search(line)
        assert m, f"a gather this test cannot read: {line.strip()[:200]}"
        n = 1
        for d in m.group(1).split("x")[:-1]:
            n *= int(d)
        sizes.append(n)
    return sizes


def _lowered(steps: int) -> str:
    s, ib = init_state(CFG), make_empty_inbox(CFG)
    ticks = jnp.ones((G,), jnp.int32)
    if steps == 1:
        return jax.jit(functools.partial(step_batch, cfg=CFG)).lower(
            s, ib, ticks
        ).as_text()
    route = jnp.full((G, P), -1, jnp.int32)
    f = functools.partial(multi_step_batch, cfg=CFG, steps=steps)
    return jax.jit(f).lower(s, ib, ticks, ib, route, jnp.zeros_like(route)).as_text()


@pytest.mark.parametrize("steps", [1, 2], ids=["step_batch", "multi_step_batch"])
def test_no_gather_over_a_ring_plane(steps):
    sizes = _gather_sizes(_lowered(steps))
    assert sizes, "the single-index lookups are gathers: none found, so the reader is blind"
    found = sorted({RING_PLANES[n] for n in sizes if n in RING_PLANES})
    assert not found, (
        f"gather(s) with {found} result elements in the lowered kernel: a run "
        "of ring slots is a row rotation (ops.kernel._ring_run, _run_to_ring)"
    )


def test_the_reader_sees_the_gather_form():
    def old_form(ring, prev):
        e_idx = prev[:, None] + 1 + jnp.arange(E, dtype=jnp.int32)[None, :]
        return jnp.take_along_axis(ring, e_idx % W, axis=1)

    text = jax.jit(old_form).lower(
        jnp.zeros((G, W), jnp.int32), jnp.zeros((G,), jnp.int32)
    ).as_text()
    assert _gather_sizes(text) == [G * E]
