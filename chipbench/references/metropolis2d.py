"""Plain reference for 2-D Ising checkerboard Metropolis on one chain.

It redoes, from the program's own input lattice, what the timed path did in
the last chunk of the window, and reports the numbers ``run.py`` compares:

* ``spins_differ``: sites where the program's final lattice and the
  reference's disagree (exact, limit 0);
* ``moments_gap``: the largest gap between the program's streamed moments
  of that chunk (|m|, m^2, m^4, and E/2, each on a scale of at most 1) and
  the reference's, whose per-sweep sums are exact integers;
* ``onsager_gap`` (over the window): the window's mean chunk |m| against
  Onsager's exact value for the infinite lattice.

:func:`validate` refuses a configuration whose dynamics or streams this
file does not redo (see :data:`MODELLED`).

The reference imports nothing of the program. It follows the published
update (Yang et al., arXiv:1903.11714, Algorithm 2 on the four parity
sub-lattices A=s[0::2,0::2], B=s[0::2,1::2], C=s[1::2,0::2],
D=s[1::2,1::2]; black A, D first, then white B, C) with plain rolls for the
neighbour sums, and draws the same counter-based uniforms as the program's
documented streams:

* one device, compact quads ``[4, R, C]``: ``uniform(fold_in(key, step),
  (4, R, C), f32)`` in the order A, D, B, C;
* a mesh, blocked quads ``[4, MR, MC, bs, bs]`` sharded over (rows, cols):
  device ``row * ncols + col`` draws ``uniform(fold_in(fold_in(fold_in(key,
  device), step), colour), (2, mr, mc, bs, bs), f32)`` for (A, D) or (B, C).

Acceptance is the configuration's: ``exp(-2 beta s nn)`` in float32, held
and compared in the spin dtype, as the paper keeps its lattice in bfloat16.
On a mesh the neighbour lines cross devices through ``lax.ppermute`` in
this file, not through the program's halo code.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

NUMBERS = ("spins_differ", "moments_gap", "onsager_gap")

# The configuration keys whose values this file redoes, with the value it
# redoes; a key left out of a configuration takes the engine's default,
# which is the value given here.
MODELLED = {"model": "ising", "dims": 2, "algorithm": "metropolis",
            "rule": "metropolis", "accept": "lut", "pipeline": "paper",
            "ensemble": "independent", "betas": [], "field": 0.0,
            "measure": True, "measure_every": 1}


def validate(config: dict) -> None:
    """Raise where ``config`` sets a key to a value this file does not
    redo: another model, dimension, algorithm or acceptance rule, the
    ``opt`` pipeline's other uniform stream, a replica ensemble, a field,
    or moments thinned or not streamed."""
    for key, want in MODELLED.items():
        got = config.get(key, want)
        if (list(got) if isinstance(got, (list, tuple)) else got) != want:
            raise ValueError(
                f"references/metropolis2d does not model {key}={got!r} of "
                f"configuration {config.get('name')!r} (it redoes "
                f"{key}={want!r})")


def acceptance_table(beta: float, dtype) -> jax.Array:
    """exp(-2 beta x) for x = s * nn in (-4, -2, 0, 2, 4): float32, then
    rounded to the spin dtype."""
    x = np.arange(-4, 5, 2, dtype=np.float32)
    arg = np.float32(-2.0) * np.float32(beta) * x
    return jnp.asarray(np.exp(arg.astype(np.float64)).astype(np.float32),
                       jnp.float32).astype(dtype)


def _flip(s, nn, u, table):
    x = s.astype(jnp.float32) * nn
    acc = table[((x + 4.0) * 0.5).astype(jnp.int32)]
    return jnp.where(u.astype(s.dtype) < acc, -s, s)


def _sweep(quads, uniforms, table, roll):
    """One sweep of the four parity sub-lattices; ``roll(x, shift, axis)``
    is a torus roll of the (possibly device-local) sub-lattice. Returns the
    new quads and the per-row sums of spins and of white s*nn."""
    a, b, c, d = quads
    u_a, u_d, u_b, u_c = uniforms
    f = lambda x: x.astype(jnp.float32)  # noqa: E731
    nn_a = f(b) + f(roll(b, 1, 1)) + f(c) + f(roll(c, 1, 0))
    nn_d = f(c) + f(roll(c, -1, 1)) + f(b) + f(roll(b, -1, 0))
    a, d = _flip(a, nn_a, u_a, table), _flip(d, nn_d, u_d, table)
    nn_b = f(a) + f(roll(a, -1, 1)) + f(d) + f(roll(d, 1, 0))
    nn_c = f(a) + f(roll(a, -1, 0)) + f(d) + f(roll(d, 1, 1))
    b, c = _flip(b, nn_b, u_b, table), _flip(c, nn_c, u_c, table)
    i32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    spins = jnp.sum(i32(a) + i32(b) + i32(c) + i32(d), axis=1)
    bonds = jnp.sum(i32(f(b) * nn_b) + i32(f(c) * nn_c), axis=1)
    return (a, b, c, d), (spins, bonds)


def _unblock(x):
    """[mr, mc, bs, bs] tile grid -> [mr * bs, mc * bs]."""
    mr, mc, bs, _ = x.shape
    return x.transpose(0, 2, 1, 3).reshape(mr * bs, mc * bs)


def _block(x, bs):
    r, c = x.shape
    return x.reshape(r // bs, bs, c // bs, bs).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("n_sweeps", "beta"))
def _chunk_single(quads, final, key, *, n_sweeps, beta):
    table = acceptance_table(beta, quads.dtype)

    def body(carry, step):
        u = jax.random.uniform(jax.random.fold_in(key, step),
                               (4,) + carry[0].shape, jnp.float32)
        return _sweep(carry, (u[0], u[1], u[2], u[3]), table, jnp.roll)

    out, (spins, bonds) = lax.scan(body, tuple(quads),
                                   jnp.arange(n_sweeps))
    differ = jnp.sum(jnp.stack(out) != final, dtype=jnp.int32)
    return differ, spins, bonds


def _halo_roll(row_axis, nrows, col_axis, ncols):
    """Torus roll of a device-local patch whose edge lines come from the
    neighbouring devices (identity exchange on an unsharded axis)."""
    names = {0: (row_axis, nrows), 1: (col_axis, ncols)}

    def roll(x, shift, axis):
        name, n = names[axis]
        if n == 1:
            return jnp.roll(x, shift, axis)
        last = x.shape[axis] - 1
        if shift == 1:   # out[i] = x[i - 1]; row 0 from the previous device
            edge = lax.slice_in_dim(x, last, last + 1, axis=axis)
            got = lax.ppermute(edge, name, [(k, (k + 1) % n) for k in range(n)])
            return jnp.concatenate(
                [got, lax.slice_in_dim(x, 0, last, axis=axis)], axis)
        edge = lax.slice_in_dim(x, 0, 1, axis=axis)   # shift == -1
        got = lax.ppermute(edge, name, [(k, (k - 1) % n) for k in range(n)])
        return jnp.concatenate(
            [lax.slice_in_dim(x, 1, last + 1, axis=axis), got], axis)

    return roll


@functools.lru_cache(maxsize=None)
def _chunk_mesh_fn(mesh, row_axis, col_axis, n_sweeps, beta):
    nrows, ncols = mesh.shape[row_axis], mesh.shape[col_axis]
    spec = P(None, row_axis, col_axis, None, None)
    roll = _halo_roll(row_axis, nrows, col_axis, ncols)

    def local(qb, final, key):
        bs = qb.shape[-1]
        table = acceptance_table(beta, qb.dtype)
        device = (lax.axis_index(row_axis) * ncols
                  + lax.axis_index(col_axis))
        dkey = jax.random.fold_in(key, device)

        def body(carry, step):
            k = jax.random.fold_in(dkey, step)
            u = [jax.random.uniform(jax.random.fold_in(k, colour),
                                    (2,) + qb.shape[1:], jnp.float32)
                 for colour in (0, 1)]
            uniforms = (_unblock(u[0][0]), _unblock(u[0][1]),
                        _unblock(u[1][0]), _unblock(u[1][1]))
            return _sweep(carry, uniforms, table, roll)

        quads = tuple(_unblock(qb[i]) for i in range(4))
        out, (spins, bonds) = lax.scan(body, quads, jnp.arange(n_sweeps))
        out = jnp.stack([_block(q, bs) for q in out])
        differ = lax.psum(jnp.sum(out != final, dtype=jnp.int32),
                          (row_axis, col_axis))
        return (differ, lax.psum(spins, col_axis),
                lax.psum(bonds, col_axis))

    mapped = jax.shard_map(
        local, mesh=mesh, check_vma=False, in_specs=(spec, spec, P()),
        out_specs=(P(), P(None, row_axis), P(None, row_axis)))
    return jax.jit(mapped)


def check_chunk(config: dict, start, final, key, n_sweeps: int,
                moments: dict) -> dict:
    """Redo one chunk from ``start`` and compare with the program's
    ``final`` lattice and its streamed ``moments`` of the chunk."""
    beta = config["beta"]
    if start.ndim == 3:
        differ, spins, bonds = _chunk_single(
            start, final, key, n_sweeps=n_sweeps, beta=float(beta))
    else:
        spec = start.sharding.spec
        fn = _chunk_mesh_fn(start.sharding.mesh, spec[1], spec[2],
                            n_sweeps, float(beta))
        differ, spins, bonds = fn(start, final, key)
    n_sites = start.size
    m = np.asarray(spins, np.int64).sum(axis=1) / n_sites
    e = -np.asarray(bonds, np.int64).sum(axis=1) / n_sites
    ref = {"m_abs": np.mean(np.abs(m)), "m2": np.mean(m ** 2),
           "m4": np.mean(m ** 4), "E": np.mean(e)}
    scale = {"m_abs": 1.0, "m2": 1.0, "m4": 1.0, "E": 2.0}   # |E| <= 2
    gap = max(abs(float(moments[k]) - v) / scale[k] for k, v in ref.items())
    return {"spins_differ": int(differ), "moments_gap": float(gap)}


def onsager_m(beta: float) -> float:
    """Spontaneous magnetization of the infinite square lattice (Onsager,
    Yang): (1 - sinh(2 beta)^-4)^(1/8) below T_c, 0 above."""
    s = math.sinh(2.0 * beta) ** -4
    return (1.0 - s) ** 0.125 if s < 1.0 else 0.0


def check_window(config: dict, chunk_moments: list) -> dict:
    """The window's mean chunk |m| against Onsager's exact value."""
    m_abs_sum = 0.0
    for moments in chunk_moments:
        m_abs_sum += moments["m_abs"]
    return {"onsager_gap": abs(m_abs_sum / len(chunk_moments)
                               - onsager_m(config["beta"]))}
