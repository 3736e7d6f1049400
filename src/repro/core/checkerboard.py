"""Checkerboard Metropolis updates for the 2-D Ising model (paper §3).

Three implementations, all bitwise-comparable when fed the same uniforms:

* :func:`update_color_full`    — brute-force oracle on the full [H, W] lattice
                                 (``jnp.roll`` neighbour sums). Ground truth.
* :func:`update_naive`         — paper Algorithm 1: blocked matmuls against the
                                 tridiagonal kernel ``K`` + colour mask ``M``.
* :func:`update_color_blocked` — paper Algorithm 2: compact parity quads,
                                 matmuls against the bidiagonal kernel K-hat.
                                 ~3x less work (no wasted RNG / nn / mask).
                                 Takes and returns a 4-tuple of blocked
                                 quads; :func:`update_color_compact` and
                                 :func:`sweep_compact` wrap it for
                                 [4, R, C] quads.

Site updates dispatch on :mod:`repro.core.update_rules` — ``accept``
names a registry rule: ``exp`` (paper), ``lut`` (exact 5-entry table;
sigma*nn only takes values in {-4,-2,0,2,4}), or ``heat_bath`` (Glauber).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import lattice as L
from repro.core import update_rules as rules

# ---------------------------------------------------------------------------
# Acceptance probability — the math now lives in repro.core.update_rules
# (one registry serving this module, the Pallas kernels, and the
# distributed integer pipeline). These names remain the public API.
# ---------------------------------------------------------------------------

acceptance_table = rules.acceptance_table
acceptance_thresholds_u24 = rules.metropolis_thresholds_u24
acceptance = rules.metropolis_acceptance


def _flip(sigma: jax.Array, nn: jax.Array, probs: jax.Array, beta,
          accept: str, field: float = 0.0) -> jax.Array:
    """One colour's site update: dispatch on the update-rule registry.

    ``accept`` is a rule name or alias: 'lut' / 'exp' (Metropolis, bitwise
    identical to the pre-registry implementations) or 'heat_bath'.
    """
    with jax.named_scope(L.FLIP):
        return rules.get_rule(accept).flip_probs(sigma, nn, probs, beta,
                                                 field)


# ---------------------------------------------------------------------------
# Oracle: full-lattice rolls
# ---------------------------------------------------------------------------


def nn_full(full: jax.Array) -> jax.Array:
    """Sum of the 4 nearest neighbours on the torus, shape [H, W]."""
    return (jnp.roll(full, 1, 0) + jnp.roll(full, -1, 0)
            + jnp.roll(full, 1, 1) + jnp.roll(full, -1, 1))


def update_color_full(full: jax.Array, probs: jax.Array, beta, color: int,
                      accept: str = "lut", field: float = 0.0) -> jax.Array:
    """Oracle checkerboard half-sweep; probs is a full [H, W] uniform array."""
    h, w = full.shape
    i = jnp.arange(h)[:, None] + jnp.arange(w)[None, :]
    mask = (i % 2 == color)
    flipped = _flip(full, nn_full(full).astype(full.dtype), probs, beta,
                    accept, field)
    return jnp.where(mask, flipped, full)


def sweep_full(full: jax.Array, probs_black: jax.Array, probs_white: jax.Array,
               beta, accept: str = "lut", field: float = 0.0) -> jax.Array:
    full = update_color_full(full, probs_black, beta, 0, accept, field)
    return update_color_full(full, probs_white, beta, 1, accept, field)


# ---------------------------------------------------------------------------
# Paper Algorithm 1 — naive blocked matmul update
# ---------------------------------------------------------------------------


def nn_naive(blocked: jax.Array, k: jax.Array) -> jax.Array:
    """Neighbour sums for a [mr, mc, b, b] blocked lattice (Algorithm 1 l.2-6)."""
    # In-block: sigma @ K sums left+right, K @ sigma sums up+down.
    nn = (jnp.einsum("rcij,jk->rcik", blocked, k)
          + jnp.einsum("ij,rcjk->rcik", k, blocked))
    # Boundary compensation from neighbouring blocks (torus wrap via roll).
    nn = nn.at[:, :, 0, :].add(jnp.roll(blocked, 1, 0)[:, :, -1, :])   # north
    nn = nn.at[:, :, -1, :].add(jnp.roll(blocked, -1, 0)[:, :, 0, :])  # south
    nn = nn.at[:, :, :, 0].add(jnp.roll(blocked, 1, 1)[:, :, :, -1])   # west
    nn = nn.at[:, :, :, -1].add(jnp.roll(blocked, -1, 1)[:, :, :, 0])  # east
    return nn


def update_naive(full: jax.Array, probs: jax.Array, beta, color: int,
                 block_size: int = L.MXU_BLOCK, accept: str = "lut") -> jax.Array:
    """Paper Algorithm 1 on a full [H, W] lattice (blocked internally)."""
    sig = L.block(full, block_size)
    k = L.kernel_naive(block_size, full.dtype)
    nn = nn_naive(sig, k).astype(full.dtype)
    p = L.block(probs, block_size)
    acc = acceptance(nn, sig, beta, accept)
    # The global checkerboard mask: block origin (r*b+i, c*b+j); parity of
    # (i+j) within a block equals global parity iff b is even (it is).
    mask = L.color_mask(block_size, color, jnp.bool_)
    flips = (p.astype(acc.dtype) < acc) & mask
    sig = jnp.where(flips, -sig, sig)
    return L.unblock(sig)


# ---------------------------------------------------------------------------
# Paper Algorithm 2 — compact parity-quad update
# ---------------------------------------------------------------------------
#
# Derivation (validated against nn_full in tests): with A=s00, B=s01, C=s10,
# D=s11 and K-hat upper-bidiagonal,
#   nn(A) = B@Kh + KhT@C   (+west-wrap of B, +north-wrap of C)
#   nn(D) = Kh@B + C@KhT   (+south-wrap of B, +east-wrap of C)
#   nn(B) = A@KhT + KhT@D  (+east-wrap of A, +north-wrap of D)
#   nn(C) = Kh@A + D@Kh    (+south-wrap of A, +west-wrap of D)
# "wrap" terms live on the neighbouring 128x128 block (or, across devices, on
# the neighbouring core — see repro.distributed.halo).


def _bmm(x, k):          # per-block x @ k
    return jnp.einsum("...ij,jk->...ik", x, k)


def _bmm_t(k, x):        # per-block k @ x
    return jnp.einsum("ij,...jk->...ik", k, x)


def default_edges(xb: jax.Array, side: str) -> jax.Array:
    """Edge line each block borrows from its ``side`` neighbour (torus).

    xb: [mr, mc, bs, bs] blocked quad. Returns [mr, mc, bs]: e.g. for
    side="north", entry (r, c) is row bs-1 of block (r-1, c). Distributed
    samplers substitute a halo-exchange version (repro.distributed.halo) —
    the wrap at device boundaries then crosses the interconnect instead of
    rolling locally.
    """
    # Slice the boundary line FIRST, then roll the small [mr, mc, bs]
    # tensor: rolling the full [mr, mc, bs, bs] quad and slicing after is
    # semantically identical but moves the whole lattice through HBM
    # (§Perf Ising iteration 4: −16% memory term).
    if side == "north":
        return jnp.roll(xb[:, :, -1, :], 1, 0)
    if side == "south":
        return jnp.roll(xb[:, :, 0, :], -1, 0)
    if side == "west":
        return jnp.roll(xb[:, :, :, -1], 1, 1)
    if side == "east":
        return jnp.roll(xb[:, :, :, 0], -1, 1)
    raise ValueError(side)


def edge_lines(a, b, c, d, color: int, edges=default_edges):
    """The 4 halo lines one colour update needs: (row0, col0, row1, col1).

    row0 is added to row 0 of nn0, col0 to a column of nn0 (col 0 for black,
    col -1 for white), row1 to row -1 of nn1, col1 to a column of nn1
    (col -1 black, col 0 white).
    """
    if color == 0:   # nn(A), nn(D)
        return (edges(c, "north"), edges(b, "west"),
                edges(b, "south"), edges(c, "east"))
    else:            # nn(B), nn(C)
        return (edges(d, "north"), edges(a, "east"),
                edges(a, "south"), edges(d, "west"))


def nn_black(a, b, c, d, kh, edges=default_edges):
    """nn sums for the black quads (A, D); inputs are [mr, mc, bs, bs]."""
    kht = kh.T
    with jax.named_scope(L.HALO):
        row0, col0, row1, col1 = edge_lines(a, b, c, d, 0, edges)
    nn_a = _bmm(b, kh) + _bmm_t(kht, c)
    with jax.named_scope(L.HALO):
        nn_a = nn_a.at[:, :, :, 0].add(col0)    # west col of B
        nn_a = nn_a.at[:, :, 0, :].add(row0)    # north row of C
    nn_d = _bmm_t(kh, b) + _bmm(c, kht)
    with jax.named_scope(L.HALO):
        nn_d = nn_d.at[:, :, -1, :].add(row1)   # south row of B
        nn_d = nn_d.at[:, :, :, -1].add(col1)   # east col of C
    return nn_a, nn_d


def nn_white(a, b, c, d, kh, edges=default_edges):
    """nn sums for the white quads (B, C)."""
    kht = kh.T
    with jax.named_scope(L.HALO):
        row0, col0, row1, col1 = edge_lines(a, b, c, d, 1, edges)
    nn_b = _bmm(a, kht) + _bmm_t(kht, d)
    with jax.named_scope(L.HALO):
        nn_b = nn_b.at[:, :, :, -1].add(col0)   # east col of A
        nn_b = nn_b.at[:, :, 0, :].add(row0)    # north row of D
    nn_c = _bmm_t(kh, a) + _bmm(d, kh)
    with jax.named_scope(L.HALO):
        nn_c = nn_c.at[:, :, -1, :].add(row1)   # south row of A
        nn_c = nn_c.at[:, :, :, 0].add(col1)    # west col of D
    return nn_b, nn_c


def update_color_blocked(qb, p0: jax.Array, p1: jax.Array, beta,
                         color: int, accept: str = "lut",
                         edges=default_edges, field: float = 0.0,
                         return_stats: bool = False):
    """Paper Algorithm 2: update one colour of the blocked quads.

    qb:     4-tuple (A, B, C, D) of [mr, mc, bs, bs] blocked parity quads.
    p0:     [mr, mc, bs, bs] uniforms for the first quad of the colour (A if
            black, B else), blocked like the quads.
    p1:     uniforms for the second quad (D if black, C else).
    edges:  halo provider (default: single-device torus rolls).
    return_stats: also return ``(new0, new1, nn0, nn1)`` — the inputs the
        streaming measurement plane (:mod:`repro.core.measure`) turns into
        the bond energy without recomputing neighbour sums.

    The quads stay in the tuple layout, so a loop that carries them pays no
    block, unblock or restack per colour.
    """
    a, b, c, d = qb
    kh = L.kernel_compact(a.shape[-1], a.dtype)
    if color == 0:  # black: flip A and D
        nn0, nn1 = nn_black(a, b, c, d, kh, edges)
        s0, s1 = a, d
    else:           # white: flip B and C
        nn0, nn1 = nn_white(a, b, c, d, kh, edges)
        s0, s1 = b, c
    with jax.named_scope(L.RNG):   # exact even where the draw fuses in
        p0, p1 = (rules.uniforms_in(p, s0.dtype) for p in (p0, p1))
    new0 = _flip(s0, nn0.astype(s0.dtype), p0, beta, accept, field)
    new1 = _flip(s1, nn1.astype(s1.dtype), p1, beta, accept, field)
    out = (new0, b, c, new1) if color == 0 else (a, new0, new1, d)
    if return_stats:
        return out, (new0, new1, nn0, nn1)
    return out


def sweep_blocked(qb, probs, beta, accept: str = "lut",
                  edges=default_edges, field: float = 0.0) -> tuple:
    """One full sweep (black then white) of the blocked 4-tuple. probs: 4
    blocked uniform planes [black0, black1, white0, white1]."""
    qb = update_color_blocked(qb, probs[0], probs[1], beta, 0, accept,
                              edges, field)
    return update_color_blocked(qb, probs[2], probs[3], beta, 1, accept,
                                edges, field)


def update_color_compact(quads: jax.Array, probs0: jax.Array,
                         probs1: jax.Array, beta, color: int,
                         block_size: int = L.MXU_BLOCK,
                         accept: str = "lut", edges=default_edges,
                         field: float = 0.0, return_stats: bool = False):
    """:func:`update_color_blocked` on [4, R, C] quads and [R, C] uniforms:
    blocks them, updates, and unblocks the colour's two quads.
    ``return_stats`` gives the blocked ``(new0, new1, nn0, nn1)``."""
    qb = L.block_quads(quads, block_size)
    p0, p1 = L.block(probs0, block_size), L.block(probs1, block_size)
    out, stats = update_color_blocked(qb, p0, p1, beta, color, accept,
                                      edges, field, return_stats=True)
    flipped = L.BLACK_QUADS if color == 0 else L.WHITE_QUADS
    with jax.named_scope(L.LAYOUT):
        out = jnp.stack([L.unblock(out[i]) if i in flipped else quads[i]
                         for i in range(4)])
    return (out, stats) if return_stats else out


def sweep_compact(quads: jax.Array, probs: jax.Array, beta,
                  block_size: int = L.MXU_BLOCK,
                  accept: str = "lut", edges=default_edges,
                  field: float = 0.0) -> jax.Array:
    """:func:`sweep_blocked` on [4, R, C] quads and [4, R, C] uniforms
    (laid out as [black0, black1, white0, white1]): blocks once, sweeps,
    unblocks once."""
    qb = L.block_quads(quads, block_size)
    pb = L.block_quads(probs, block_size)
    qb = sweep_blocked(qb, pb, beta, accept, edges, field)
    return L.unblock_quads(qb)


def quad_probs_from_full(probs_black: jax.Array,
                         probs_white: jax.Array) -> jax.Array:
    """Slice full-lattice uniform arrays into the compact layout, so the
    compact update is bitwise-identical to the oracle fed the same arrays."""
    pb = L.to_quads(probs_black)
    pw = L.to_quads(probs_white)
    return jnp.stack([pb[L.Q00], pb[L.Q11], pw[L.Q01], pw[L.Q10]])
