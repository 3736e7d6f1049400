"""Pure-jnp oracle for the Pallas checkerboard kernel.

Mirrors the kernel bit-for-bit: identical bits->uniform conversion, identical
f32 table acceptance, identical flip rule — all supplied by the same
``repro.core.update_rules`` registry the kernel compiles against, applied to
the independently-validated ``repro.core.checkerboard`` compact math (which
itself is tested against the brute-force full-lattice oracle).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import checkerboard as cb
from repro.core import lattice as L
from repro.core import update_rules

_INV_2_24 = 1.0 / float(1 << 24)

bits_to_uniform = update_rules.bits_to_uniform


def lut_acceptance(x: jax.Array, beta: float) -> jax.Array:
    t = [math.exp(-2.0 * beta * v) for v in (-4.0, -2.0, 0.0, 2.0, 4.0)]
    return update_rules._select5(x, t)


def update_color_ref(quads_blocked: jax.Array, bits: jax.Array, kh: jax.Array,
                     beta: float, color: int,
                     rule: str = "metropolis_lut") -> jax.Array:
    """Oracle with the exact kernel semantics (f32 nn, f32 table, f32
    compare) for any registry rule.

    Same signature as ``update_color_pallas``.
    """
    a, b, c, d = (quads_blocked[i] for i in range(4))
    khf = kh
    if color == 0:
        nn0, nn1 = cb.nn_black(a, b, c, d, khf)
        s0, s1 = a, d
    else:
        nn0, nn1 = cb.nn_white(a, b, c, d, khf)
        s0, s1 = b, c

    flip = update_rules.get_rule(rule).kernel_form(float(beta))
    new0 = flip(s0, nn0.astype(jnp.float32), bits[0])
    new1 = flip(s1, nn1.astype(jnp.float32), bits[1])
    if color == 0:
        return jnp.stack([new0, b, c, new1])
    return jnp.stack([a, new0, new1, d])
