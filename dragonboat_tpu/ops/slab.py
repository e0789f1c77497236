"""A pytree of planes laid out in two buffers: one slab of int32 and one
of bool.

A K-step launch puts a dozen inbox planes and fetches some fifty output
planes, each a few KB at the fleets' sizes. What the host<->device seam
charges then follows the number of buffers and not their bytes, so the
launch moves its planes as one slab of each kind instead: the host
stages its planes as views into the slabs it puts, the program cuts them
back with static slices at its top, and each inner step packs its
outputs into its row of the slabs it returns.

Planes lie in the tree's leaf order along a slab's last axis, so
cutting a slab back into the tree is a slice and a reshape per plane: on
the host those are numpy views, in a trace static slices. A layout may
keep leading axes that every plane shares. The launch's inputs keep the
lane axis, so their slab is lanes by columns and a plane a block of
columns, which the TPU cuts without a relayout copy of the whole inbox
(a flat input slab cost the three-step program at 3 072 lanes 113 MB
more temporaries, by the compiler's count for a v5e). A step's outputs
keep none: its row is flat, plane after plane, and a plane's host view
is contiguous. uint32 planes ride in the int32 slab bit for bit.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_INT = (np.dtype(np.int32), np.dtype(np.uint32))
_BOOL = np.dtype(np.bool_)


class _Plane(NamedTuple):
    is_bool: bool
    start: int
    size: int
    shape: Tuple[int, ...]
    dtype: np.dtype


class Slabs:
    """Where each leaf of a pytree lies in the int32 and bool slabs, whose
    shapes are `int_shape` and `bool_shape`: the `lead` leading axes that
    every leaf shares, then the columns. Built from anything with
    `.shape` and `.dtype` per leaf (arrays, `jax.ShapeDtypeStruct`s);
    leaves of another dtype or other leading axes are refused."""

    def __init__(self, tree, lead: int = 0) -> None:
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        self.lead = tuple(leaves[0].shape[:lead])
        self.planes = []
        ends = {False: 0, True: 0}
        for x in leaves:
            dt = np.dtype(x.dtype)
            if dt not in _INT and dt != _BOOL:
                raise TypeError(f"no slab holds a {dt} plane")
            if tuple(x.shape[:lead]) != self.lead:
                raise ValueError(
                    f"a plane of shape {tuple(x.shape)} does not lead "
                    f"with {self.lead}"
                )
            b = dt == _BOOL
            n = int(np.prod(x.shape[lead:], dtype=np.int64))
            self.planes.append(_Plane(b, ends[b], n, tuple(x.shape), dt))
            ends[b] += n
        self.int_shape = self.lead + (ends[False],)
        self.bool_shape = self.lead + (ends[True],)

    def pack(self, tree):
        """(int32 slab, bool slab) of a tree of this layout, inside a
        trace: one concatenation each."""
        cols = self.lead + (-1,)
        ints, bools = [], []
        for x, p in zip(self.treedef.flatten_up_to(tree), self.planes):
            if p.dtype == np.uint32:
                x = jax.lax.bitcast_convert_type(x, jnp.int32)
            (bools if p.is_bool else ints).append(x.reshape(cols))
        return jnp.concatenate(ints, -1), jnp.concatenate(bools, -1)

    def unpack(self, ints, bools):
        """The tree again: views into numpy slabs (writes through them
        land in the slab), static slices of traced ones. Slabs stacked
        on axes ahead of the layout's (a scan's rows) give planes
        stacked on those axes."""
        out = []
        n_lead = len(self.lead)
        for p in self.planes:
            x = (bools if p.is_bool else ints)[..., p.start : p.start + p.size]
            x = x.reshape(x.shape[:-1] + p.shape[n_lead:])
            if p.dtype == np.uint32:
                x = (x.view(np.uint32) if isinstance(x, np.ndarray)
                     else jax.lax.bitcast_convert_type(x, jnp.uint32))
            out.append(x)
        return self.treedef.unflatten(out)
