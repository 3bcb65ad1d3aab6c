"""Jitted elementwise kernels for the relational data plane.

The jax plane's filter/project kernels are pure elementwise programs
(adds, compares, boolean combine — the multiply program is jitted
separately; see ``engine/plane/jax_plane.py``).  ``build_elementwise``
runs such a body as one ``jax.jit`` program on the default device,
zero-padding every operand to a power-of-two bucket so one compile serves
every row count in the bucket.

The plane calls these kernels under x64 with float64 operands, which is
what its byte-identity contract needs.  A Pallas lowering cannot serve
them: Mosaic refuses 64-bit element types on TPU, so an exact Pallas
formulation would have to be float32 or integer.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (>= 1).  Operands are zero-padded to
    bucket sizes before jit so compiled kernels are reused across row
    counts (jit specializes per shape; filter selectivity would otherwise
    force a recompile on every chain execution)."""
    return 1 << max(0, int(n - 1).bit_length()) if n > 1 else 1


def _pad_to(a, b: int):
    n = int(a.shape[0])
    if n == b:
        return a
    pad_val = False if a.dtype == jnp.bool_ else 0
    return jnp.pad(a, (0, b - n), constant_values=pad_val)


def build_elementwise(body: Callable) -> Callable:
    """A callable running ``body`` as one jitted program.

    ``body`` maps 1-D arrays to a 1-D array or tuple of 1-D arrays, all of
    one common length.  The returned callable accepts numpy or jax arrays
    and returns numpy.  Operands are zero-padded to a power-of-two bucket:
    bodies are elementwise (a pad lane never influences a real lane), so
    slicing the outputs back to n is exact, and the jit cache is hit for
    every row count in the same bucket.
    """
    jitted = jax.jit(body)

    def call(*arrs):
        xs = [jnp.asarray(x) for x in arrs]
        n = int(xs[0].shape[0])
        b = pow2_bucket(n)
        out = jitted(*[_pad_to(x, b) for x in xs])
        if isinstance(out, (tuple, list)):
            return tuple(np.asarray(o)[:n] for o in out)
        return np.asarray(out)[:n]

    return call
