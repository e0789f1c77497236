"""The router's slot assignment against its plain definition.

`ops.kernel._route_scatter` gives inbox slot (g, k) the k-th candidate,
in candidate order, whose destination is lane g, for k < K; the rest
stay on the host path. The reference below states that with a stable
sort over every candidate and a scatter of each into its slot; the
program must give the same inbox and the same routed mask bit for bit,
whatever the mix of full, short and empty destination runs.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from dragonboat_tpu.ops.kernel import _route_scatter
from dragonboat_tpu.ops.state import MSG

_FIELDS = 10  # Inbox's scalar columns, in staging order; 6 is `reject`


def _reference(dest, fields, eterms, ecc, G, K):
    M = len(dest)
    E = eterms.shape[1]
    key = np.where(dest >= 0, dest, G)
    order = np.argsort(key, kind="stable")
    inbox = [np.full((G, K), MSG.NONE, np.int32)] + [
        np.zeros((G, K), bool if c == 6 else np.int32)
        for c in range(1, _FIELDS)
    ]
    terms = np.zeros((G, K, E), np.int32)
    cc = np.zeros((G, K, E), bool)
    routed = np.zeros(M, bool)
    used = np.zeros(G, np.int64)
    for j in order:
        g = key[j]
        if g >= G or used[g] >= K:
            continue
        k = used[g]
        used[g] += 1
        for c in range(_FIELDS):
            inbox[c][g, k] = fields[c][j]
        if j < len(eterms):
            terms[g, k] = eterms[j]
            cc[g, k] = ecc[j]
        routed[j] = True
    return inbox + [terms, cc], routed


@pytest.mark.parametrize("G,K,M,n_rep,fill,seed", [
    (1, 4, 6, 2, 0.9, 1),
    (8, 4, 96, 32, 0.2, 2),
    (8, 4, 96, 32, 0.9, 3),  # most runs longer than K
    (16, 8, 384, 128, 0.5, 4),
    (33, 2, 330, 99, 0.7, 5),  # an odd width
    (16, 8, 384, 128, 0.0, 6),  # nothing to route
])
def test_slots_match_the_stable_sort_definition(G, K, M, n_rep, fill, seed):
    rng = np.random.default_rng(seed)
    E = 3
    dest = np.where(
        rng.random(M) < fill, rng.integers(0, G, M), -1
    ).astype(np.int32)
    fields = [rng.integers(1, 1 << 20, M).astype(np.int32)
              for _ in range(_FIELDS)]
    fields[6] = rng.random(M) < 0.5
    eterms = rng.integers(1, 50, (n_rep, E)).astype(np.int32)
    ecc = rng.random((n_rep, E)) < 0.3
    want, want_routed = _reference(dest, fields, eterms, ecc, G, K)
    nxt, routed = _route_scatter(
        jnp.asarray(dest), tuple(jnp.asarray(f) for f in fields),
        (jnp.asarray(eterms), jnp.asarray(ecc)), G, K,
    )
    np.testing.assert_array_equal(np.asarray(routed), want_routed)
    for name, got, exp in zip(nxt._fields, nxt, want):
        np.testing.assert_array_equal(np.asarray(got), exp, err_msg=name)
