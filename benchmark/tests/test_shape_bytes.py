"""shape_bytes against the program's own arrays (jax.eval_shape)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import shape_bytes as sb
from dragonboat_tpu.ops import kernel
from dragonboat_tpu.ops.state import KernelConfig, init_state, make_empty_inbox


def _nbytes(tree) -> int:
    return sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(tree)
    )


@pytest.mark.parametrize("G,P,W,K,E,R", [
    (3072, 4, 256, 4, 64, 4),  # chip_smoke.py's and the fleet cells' shape
    (144, 4, 256, 4, 64, 8),  # upstream-48x3
    (64, 8, 128, 6, 8, 2),  # every dimension different
])
def test_bytes_from_shapes_match_the_programs_arrays(G, P, W, K, E, R):
    cfg = KernelConfig(groups=G, peers=P, log_window=W, inbox_depth=K,
                       max_entries_per_msg=E, readindex_depth=R)
    state = jax.eval_shape(lambda: init_state(cfg))
    inbox = jax.eval_shape(lambda: make_empty_inbox(cfg))
    ticks = jax.ShapeDtypeStruct((G,), jnp.int32)
    assert _nbytes(state) == sb.state_bytes(G, P, W, R)
    assert _nbytes(inbox) == sb.inbox_bytes(G, K, E)
    _s, out = jax.eval_shape(kernel.make_step_fn(cfg, donate=False),
                             state, inbox, ticks)
    assert _nbytes(out) == sb.output_bytes(G, P, K, R)
    route = jax.ShapeDtypeStruct((G, P), jnp.int32)
    _s, outs, plans, resid, count = jax.eval_shape(
        kernel.make_multi_step_fn(cfg, 8, donate=False),
        state, inbox, ticks, inbox, route, route,
    )
    assert _nbytes(outs) == 8 * sb.output_bytes(G, P, K, R)
    assert _nbytes(plans) == 8 * sb.plan_bytes(G, P, K, R)
    one = sb.launch_bytes(G, P, W, K, E, R, 1)
    assert one == 2 * _nbytes(state) + _nbytes(inbox) + _nbytes(ticks) + _nbytes(out)
    eight = sb.launch_bytes(G, P, W, K, E, R, 8)
    assert eight == (
        2 * _nbytes(state) + 3 * _nbytes(inbox) + _nbytes(ticks)
        + _nbytes(outs) + _nbytes(plans) + 2 * _nbytes(route) + _nbytes(count)
    )


def test_the_smokes_shape_is_1553_bytes_a_lane():
    assert sb.state_bytes(3072, 4, 256, 4) == 3072 * 1553 == 4_770_816
