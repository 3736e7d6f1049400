"""Pallas TPU kernel: fused compact checkerboard half-sweep (paper Alg. 2).

One ``pallas_call`` updates one colour of the lattice. Per 128x128 grid cell
it performs, entirely in VMEM:

  * 4 MXU matmuls against the bidiagonal kernel K-hat (the paper's trick that
    moves the neighbour-sum stencil onto the matrix unit),
  * halo compensation rows/cols read from the neighbouring blocks and added
    under ``broadcasted_iota`` masks (the TPU lowering has no scatter-add),
  * acceptance via a compile-time 5-entry LUT (sigma*nn in {-4,-2,0,2,4}; the
    paper uses exp(), the LUT is exact and avoids the transcendental),
  * uniform generation from raw uint32 bits and the Metropolis flip.

RNG: the bits arrive as an operand drawn by counter-addressed
``jax.random.bits`` outside the kernel, so the kernel is bitwise equal to
the pure-jnp oracle in :mod:`repro.kernels.ref` on every backend. The TPU's
stateful in-kernel generator (``pltpu.prng_random_bits``) would break that
contract; generating the bits in-kernel needs a hash of the global site index
instead.

Block layout: quads arrive blocked ``[mr, mc, bs, bs]`` with ``bs=128``
(MXU-native). Halo lines travel as ``[mr, mc, 1, bs]`` rows, so every block
meets the TPU's (8, 128) tiling rule. On a TPU the kernels compile through
Mosaic; everywhere else they run in the Pallas interpreter
(:func:`interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import lattice as L
from repro.core import update_rules


def interpret_mode() -> bool:
    """True where Pallas must interpret the kernels: any backend but a TPU."""
    return jax.default_backend() != "tpu"


def _add_row(nn, line, i: int):
    """``nn`` with the ``[1, bs]`` halo ``line`` added to row ``i``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, nn.shape, 0)
    return nn + jnp.where(rows == i, line.astype(jnp.float32), 0.0)


def _add_col(nn, col, j: int):
    """``nn`` with the halo column ``col`` (``[bs, 1]``) added to column
    ``j``."""
    cols = jax.lax.broadcasted_iota(jnp.int32, nn.shape, 1)
    return nn + jnp.where(cols == j, col.astype(jnp.float32), 0.0)


def _row_to_col(line, shape):
    """A ``[1, bs]`` line as a column: broadcast over rows, transposed."""
    return jnp.broadcast_to(line, shape).T


def _update_kernel(s0_ref, s1_ref,
                   p0_ref, p0a_ref, p0b_ref,
                   p1_ref, p1a_ref, p1b_ref,
                   kh_ref, bits0_ref, bits1_ref,
                   out0_ref, out1_ref, *, color: int, beta: float,
                   rule: str = "metropolis_lut"):
    """Update the two active quads of one (bs x bs) block.

    black (color=0): s0=A, s1=D; p0*=B tiles, p1*=C tiles
      nn(A) = B@Kh + KhT@C  (+ west col of B, + north row of C)
      nn(D) = Kh@B + C@KhT  (+ south row of B, + east col of C)
    white (color=1): s0=B, s1=C; p0*=A tiles, p1*=D tiles
      nn(B) = A@KhT + KhT@D (+ east col of A, + north row of D)
      nn(C) = Kh@A + D@Kh   (+ south row of A, + west col of D)

    p0a/p1a are the row-shifted (north/south) neighbour tiles, p0b/p1b the
    col-shifted (west/east) ones — which shift is which depends on colour and
    is wired up by the index maps in :func:`update_color_pallas`. Edge lines
    are read with static non-negative indices.
    """
    kh = kh_ref[0, 0]
    kht = kh.T
    p0 = p0_ref[0, 0]
    p1 = p1_ref[0, 0]
    f32 = jnp.float32
    last = kh.shape[0] - 1

    def row(ref, i):                       # row i of a tile, [1, bs]
        return ref[0, 0, pl.ds(i, 1), :]

    def col(ref, j):                       # column j of a tile, [bs, 1]
        return ref[0, 0, :, pl.ds(j, 1)]

    if color == 0:  # black: p0=B, p1=C
        nn0 = (jnp.dot(p0, kh, preferred_element_type=f32)
               + jnp.dot(kht, p1, preferred_element_type=f32))
        nn0 = _add_col(nn0, col(p0b_ref, last), 0)       # B west
        nn0 = _add_row(nn0, row(p1a_ref, last), 0)       # C north
        nn1 = (jnp.dot(kh, p0, preferred_element_type=f32)
               + jnp.dot(p1, kht, preferred_element_type=f32))
        nn1 = _add_row(nn1, row(p0a_ref, 0), last)       # B south
        nn1 = _add_col(nn1, col(p1b_ref, 0), last)       # C east
    else:           # white: p0=A, p1=D
        nn0 = (jnp.dot(p0, kht, preferred_element_type=f32)
               + jnp.dot(kht, p1, preferred_element_type=f32))
        nn0 = _add_col(nn0, col(p0b_ref, 0), last)       # A east
        nn0 = _add_row(nn0, row(p1a_ref, last), 0)       # D north
        nn1 = (jnp.dot(kh, p0, preferred_element_type=f32)
               + jnp.dot(p1, kh, preferred_element_type=f32))
        nn1 = _add_row(nn1, row(p0a_ref, 0), last)       # A south
        nn1 = _add_col(nn1, col(p1b_ref, last), 0)       # D west

    flip = update_rules.get_rule(rule).kernel_form(beta)
    out0_ref[0, 0] = flip(s0_ref[0, 0], nn0, bits0_ref[0, 0])
    out1_ref[0, 0] = flip(s1_ref[0, 0], nn1, bits1_ref[0, 0])


def _update_kernel_lines(s0_ref, s1_ref, p0_ref, p1_ref, kh_ref,
                         bits0_ref, bits1_ref,
                         row0_ref, col0_ref, row1_ref, col1_ref,
                         out0_ref, out1_ref, *, color: int, beta: float,
                         rule: str = "metropolis_lut"):
    """Edge-lines variant: halo lines are precomputed outside the kernel
    (``[mr, mc, 1, bs]`` arrays), so each passive quad tile is streamed from
    HBM exactly once (the tile-fetch variant reads them 3x).
    """
    kh = kh_ref[0, 0]
    kht = kh.T
    p0 = p0_ref[0, 0]
    p1 = p1_ref[0, 0]
    f32 = jnp.float32
    last = kh.shape[0] - 1
    r0, r1 = row0_ref[0, 0], row1_ref[0, 0]
    c0 = _row_to_col(col0_ref[0, 0], p0.shape)
    c1 = _row_to_col(col1_ref[0, 0], p0.shape)

    if color == 0:  # p0=B, p1=C -> nn(A), nn(D)
        nn0 = (jnp.dot(p0, kh, preferred_element_type=f32)
               + jnp.dot(kht, p1, preferred_element_type=f32))
        nn0 = _add_col(_add_row(nn0, r0, 0), c0, 0)
        nn1 = (jnp.dot(kh, p0, preferred_element_type=f32)
               + jnp.dot(p1, kht, preferred_element_type=f32))
        nn1 = _add_col(_add_row(nn1, r1, last), c1, last)
    else:           # p0=A, p1=D -> nn(B), nn(C)
        nn0 = (jnp.dot(p0, kht, preferred_element_type=f32)
               + jnp.dot(kht, p1, preferred_element_type=f32))
        nn0 = _add_col(_add_row(nn0, r0, 0), c0, last)
        nn1 = (jnp.dot(kh, p0, preferred_element_type=f32)
               + jnp.dot(p1, kh, preferred_element_type=f32))
        nn1 = _add_col(_add_row(nn1, r1, last), c1, 0)

    flip = update_rules.get_rule(rule).kernel_form(beta)
    out0_ref[0, 0] = flip(s0_ref[0, 0], nn0, bits0_ref[0, 0])
    out1_ref[0, 0] = flip(s1_ref[0, 0], nn1, bits1_ref[0, 0])


def update_color_pallas_lines(quads_blocked, bits, kh, beta: float, color: int,
                              edges=None, rule: str = "metropolis_lut"):
    """Edge-lines kernel wrapper. ``edges(xb, side) -> [mr, mc, bs]`` supplies
    halo lines (default: single-device torus rolls). Distributed samplers pass
    the ppermute-based provider — the kernel itself is distribution-agnostic.
    """
    from repro.core import checkerboard as cb
    if edges is None:
        edges = cb.default_edges
    a, b, c, d = (quads_blocked[i] for i in range(4))
    _, mr, mc, bs, _ = quads_blocked.shape
    dtype = quads_blocked.dtype

    with jax.named_scope(L.HALO):
        lines = [x.reshape(mr, mc, 1, bs)
                 for x in cb.edge_lines(a, b, c, d, color, edges)]
    s0, s1 = (a, d) if color == 0 else (b, c)
    p0, p1 = (b, c) if color == 0 else (a, d)

    tile = pl.BlockSpec((1, 1, bs, bs), lambda r, q: (r, q, 0, 0))
    line = pl.BlockSpec((1, 1, 1, bs), lambda r, q: (r, q, 0, 0))
    kspec = pl.BlockSpec((1, 1) + kh.shape, lambda r, q: (0, 0, 0, 0))

    out0, out1 = pl.pallas_call(
        functools.partial(_update_kernel_lines, color=color,
                          beta=float(beta), rule=rule),
        name="checkerboard_update_lines",
        grid=(mr, mc),
        in_specs=[tile, tile, tile, tile, kspec, tile, tile,
                  line, line, line, line],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((mr, mc, bs, bs), dtype)] * 2,
        interpret=interpret_mode(),
    )(s0, s1, p0, p1, kh.reshape(1, 1, *kh.shape), bits[0], bits[1], *lines)

    if color == 0:
        return jnp.stack([out0, b, c, out1])
    return jnp.stack([a, out0, out1, d])


def update_color_pallas(quads_blocked, bits, kh, beta: float, color: int,
                        rule: str = "metropolis_lut"):
    """One colour update of blocked compact quads.

    quads_blocked: [4, mr, mc, bs, bs]  (A, B, C, D)
    bits:          [2, mr, mc, bs, bs] uint32 random bits for the two active
                   quads (A,D when black; B,C when white)
    kh:            [bs, bs] bidiagonal kernel
    Returns the updated [4, mr, mc, bs, bs] stack.
    """
    a, b, c, d = quads_blocked[0], quads_blocked[1], quads_blocked[2], quads_blocked[3]
    _, mr, mc, bs, _ = quads_blocked.shape
    dtype = quads_blocked.dtype

    tile = lambda fn: pl.BlockSpec((1, 1, bs, bs), fn)
    center = tile(lambda r, q: (r, q, 0, 0))
    north = tile(lambda r, q: ((r - 1) % mr, q, 0, 0))
    south = tile(lambda r, q: ((r + 1) % mr, q, 0, 0))
    west = tile(lambda r, q: (r, (q - 1) % mc, 0, 0))
    east = tile(lambda r, q: (r, (q + 1) % mc, 0, 0))
    kspec = pl.BlockSpec((1, 1) + kh.shape, lambda r, q: (0, 0, 0, 0))

    if color == 0:
        s0, s1, pas0, pas1 = a, d, b, c
        # nn0 halo: p0b = B west, p1a = C north; nn1 halo: p0a = B south, p1b = C east
        specs = [center, center,
                 center, south, west,     # p0 (B): center, row-shift, col-shift
                 center, north, east,     # p1 (C)
                 kspec, center, center]
    else:
        s0, s1, pas0, pas1 = b, c, a, d
        specs = [center, center,
                 center, south, east,     # p0 (A)
                 center, north, west,     # p1 (D)
                 kspec, center, center]

    out0, out1 = pl.pallas_call(
        functools.partial(_update_kernel, color=color, beta=float(beta),
                          rule=rule),
        name="checkerboard_update",
        grid=(mr, mc),
        in_specs=specs,
        out_specs=[center, center],
        out_shape=[jax.ShapeDtypeStruct((mr, mc, bs, bs), dtype)] * 2,
        interpret=interpret_mode(),
    )(s0, s1, pas0, pas0, pas0, pas1, pas1, pas1,
      kh.reshape(1, 1, *kh.shape), bits[0], bits[1])

    if color == 0:
        return jnp.stack([out0, b, c, out1])
    return jnp.stack([a, out0, out1, d])
