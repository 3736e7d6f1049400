"""Lattice representations for the 2-D Ising model.

Three layouts are used throughout the framework:

* ``full``   — ``[H, W]`` array of spins in {-1, +1} (torus boundary).
* ``quads``  — ``[4, H/2, W/2]`` compact parity sub-lattices (paper Fig. 3-(2)):
               index 0 = sigma_00 (even row, even col)   "A"  (black)
               index 1 = sigma_01 (even row, odd  col)   "B"  (white)
               index 2 = sigma_10 (odd  row, even col)   "C"  (white)
               index 3 = sigma_11 (odd  row, odd  col)   "D"  (black)
* ``blocked``— ``[mr, mc, b, b]`` grid of b x b tiles of a 2-D array
               (b = 128 on TPU so each tile feeds the MXU directly).

All conversions are exact and round-trip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Quad indices (paper notation sigma_{rc} = sigma[r::2, c::2]).
Q00, Q01, Q10, Q11 = 0, 1, 2, 3
BLACK_QUADS = (Q00, Q11)
WHITE_QUADS = (Q01, Q10)

MXU_BLOCK = 128

# Device scopes (``jax.named_scope``) naming the sweep's work in a profile.
# A compiled op carries the scope path in its ``op_name`` metadata and is
# charged to the innermost of these names: the parent sweep, the random
# draw, moves between layouts, halo lines, acceptance, and the streamed
# (m, E). The benchmark's trace reader (chipbench/scopes.py) keeps its own
# copy of the tuple.
SCOPES = ("sweep", "rng", "layout", "halo", "flip", "measure")
SWEEP, RNG, LAYOUT, HALO, FLIP, MEASURE = SCOPES


def random_lattice(key: jax.Array, height: int, width: int,
                   dtype=jnp.bfloat16) -> jax.Array:
    """Uniform random +-1 spin configuration, shape [height, width]."""
    bits = jax.random.bernoulli(key, 0.5, (height, width))
    return jnp.where(bits, 1, -1).astype(dtype)


def cold_lattice(height: int, width: int, dtype=jnp.bfloat16) -> jax.Array:
    """All-up configuration (ground state)."""
    return jnp.ones((height, width), dtype)


def to_quads(full: jax.Array) -> jax.Array:
    """[H, W] -> [4, H/2, W/2] compact parity decomposition."""
    h, w = full.shape
    if h % 2 or w % 2:
        raise ValueError(f"lattice dims must be even, got {full.shape}")
    return jnp.stack([
        full[0::2, 0::2],   # A = sigma_00
        full[0::2, 1::2],   # B = sigma_01
        full[1::2, 0::2],   # C = sigma_10
        full[1::2, 1::2],   # D = sigma_11
    ])


def from_quads(quads: jax.Array) -> jax.Array:
    """[4, R, C] -> [2R, 2C]; inverse of :func:`to_quads`."""
    _, r, c = quads.shape
    full = jnp.zeros((2 * r, 2 * c), quads.dtype)
    full = full.at[0::2, 0::2].set(quads[Q00])
    full = full.at[0::2, 1::2].set(quads[Q01])
    full = full.at[1::2, 0::2].set(quads[Q10])
    full = full.at[1::2, 1::2].set(quads[Q11])
    return full


def block(x: jax.Array, bs: int = MXU_BLOCK) -> jax.Array:
    """[R, C] -> [R/bs, C/bs, bs, bs] tile grid."""
    r, c = x.shape
    if r % bs or c % bs:
        raise ValueError(f"{x.shape} not divisible by block {bs}")
    with jax.named_scope(LAYOUT):
        return x.reshape(r // bs, bs, c // bs, bs).transpose(0, 2, 1, 3)


def unblock(xb: jax.Array) -> jax.Array:
    """[mr, mc, bs, bs] -> [mr*bs, mc*bs]; inverse of :func:`block`."""
    mr, mc, bs, _ = xb.shape
    with jax.named_scope(LAYOUT):
        return xb.transpose(0, 2, 1, 3).reshape(mr * bs, mc * bs)


def kernel_naive(n: int, dtype=jnp.bfloat16) -> jax.Array:
    """Paper's K: tridiagonal, zero diagonal, ones on sub/super diagonals.

    matmul(sigma, K) + matmul(K, sigma) == sum of 4 in-block neighbours.
    """
    i = jnp.arange(n)
    return (jnp.abs(i[:, None] - i[None, :]) == 1).astype(dtype)


def kernel_compact(n: int, dtype=jnp.bfloat16) -> jax.Array:
    """Paper's K-hat: upper bidiagonal (ones on diag and superdiag)."""
    i = jnp.arange(n)
    d = i[None, :] - i[:, None]
    return ((d == 0) | (d == 1)).astype(dtype)


def color_mask(n: int, color: int, dtype=jnp.bfloat16) -> jax.Array:
    """Paper's M: checkerboard mask; color 0 selects (i+j) even sites."""
    i = jnp.arange(n)
    m = ((i[:, None] + i[None, :]) % 2 == color)
    return m.astype(dtype)
