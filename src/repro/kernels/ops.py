"""Jit'd public wrappers around the Pallas checkerboard kernel.

``sweep(quads, key, beta)`` runs one full lattice sweep (black + white) with
counter-based RNG, dispatching to one of three backends:

* ``pallas`` — the fused Pallas kernel (Mosaic on TPU, interpreted elsewhere)
* ``ref``    — the pure-jnp oracle with identical bit-level semantics
* ``xla``    — the paper-faithful Algorithm-2 XLA path (repro.core), its own RNG

``pallas`` and ``ref`` are bitwise identical; ``xla`` is statistically
equivalent (different uniform-generation path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import lattice as L
from repro.kernels import checkerboard as kern
from repro.kernels import ref as kref


def _block_quads(quads: jax.Array, bs: int) -> jax.Array:
    with jax.named_scope(L.LAYOUT):
        return jnp.stack([L.block(quads[i], bs) for i in range(4)])


def _unblock_quads(qb: jax.Array) -> jax.Array:
    with jax.named_scope(L.LAYOUT):
        return jnp.stack([L.unblock(qb[i]) for i in range(4)])


def color_bits(key: jax.Array, step, color: int, shape) -> jax.Array:
    """uint32 bits for the two active quads of one colour update."""
    with jax.named_scope(L.RNG):
        k = jax.random.fold_in(jax.random.fold_in(key, step), color)
        return jax.random.bits(k, (2,) + tuple(shape), jnp.uint32)


def update_color(quads_blocked: jax.Array, bits: jax.Array, beta: float,
                 color: int, backend: str = "pallas", edges=None,
                 rule: str = "metropolis_lut") -> jax.Array:
    """backend: 'pallas' (tile-fetch halo), 'pallas_lines' (edge-line halo,
    distribution-capable), or 'ref' (pure-jnp oracle). ``rule`` names a
    ``repro.core.update_rules`` entry compiled into the kernel."""
    bs = quads_blocked.shape[-1]
    kh = L.kernel_compact(bs, quads_blocked.dtype)
    if backend == "pallas":
        return kern.update_color_pallas(quads_blocked, bits, kh, beta, color,
                                        rule=rule)
    if backend == "pallas_lines":
        return kern.update_color_pallas_lines(quads_blocked, bits, kh, beta,
                                              color, edges=edges, rule=rule)
    if backend == "ref":
        return kref.update_color_ref(quads_blocked, bits, kh, beta, color,
                                     rule=rule)
    raise ValueError(f"unknown backend {backend!r}")


@functools.partial(jax.jit,
                   static_argnames=("beta", "bs", "backend", "rule"))
def sweep(quads: jax.Array, key: jax.Array, step, *, beta: float,
          bs: int = L.MXU_BLOCK, backend: str = "pallas",
          rule: str = "metropolis_lut") -> jax.Array:
    """One full sweep of [4, R, C] compact quads. Returns updated quads."""
    qb = _block_quads(quads, bs)
    blk = qb.shape[1:]
    with jax.named_scope(L.SWEEP):
        for color in (0, 1):
            bits = color_bits(key, step, color, blk)
            qb = update_color(qb, bits, beta, color, backend, rule=rule)
    return _unblock_quads(qb)


@functools.partial(jax.jit,
                   static_argnames=("n_sweeps", "beta", "bs", "backend",
                                    "rule"))
def run_sweeps(quads: jax.Array, key: jax.Array, *, n_sweeps: int, beta: float,
               bs: int = L.MXU_BLOCK, backend: str = "pallas",
               rule: str = "metropolis_lut") -> jax.Array:
    """Measurement-free multi-sweep loop on the kernel path."""
    qb = _block_quads(quads, bs)
    blk = qb.shape[1:]

    def body(i, q):
        with jax.named_scope(L.SWEEP):
            for color in (0, 1):
                bits = color_bits(key, i, color, blk)
                q = update_color(q, bits, beta, color, backend, rule=rule)
            return q

    qb = jax.lax.fori_loop(0, n_sweeps, body, qb)
    return _unblock_quads(qb)
