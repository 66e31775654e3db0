"""Prefix scans that XLA's TPU compiler takes in seconds.

`lax.associative_scan` slices with strides, and at the row counts of a
batch (hundreds of thousands of rows per owner) XLA's TPU compiler takes
minutes over it. Recursive doubling uses log2(n) contiguous shifts
instead: O(n log n) elementwise work, about 19 passes over the rows at
524,288 rows per owner. Its runtime on the chip has not been measured.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def inclusive_scan(fn, elems):
    """Inclusive scan of the associative `fn(left, right)` over axis 0 of
    the 1-D leaves of `elems`, by recursive doubling."""
    n = jax.tree_util.tree_leaves(elems)[0].shape[0]
    idx = jnp.arange(n)
    s = 1
    while s < n:
        left = jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x[:s], x[:-s]]), elems)
        new = fn(left, elems)
        elems = jax.tree_util.tree_map(
            lambda a, b: jnp.where(idx >= s, a, b), new, elems)
        s *= 2
    return elems


def segmented_scan(binop, first: jax.Array, x: jax.Array) -> jax.Array:
    """Inclusive scan of `binop` over x, restarting where `first` is set."""
    def comb(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], binop(a[1], b[1]))

    return inclusive_scan(comb, (first, x))[1]
