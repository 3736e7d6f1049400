"""MCMC chain drivers for the Ising model.

Two compiled entry points:

* :func:`run_chain`    — `lax.scan` over sweeps collecting per-sweep (m, E)
                         scalars; used for physics (Fig. 4) runs.
* :func:`run_sweeps`   — measurement-free `lax.fori_loop`; used for benchmarks
                         (paper Tables 1-2 measure pure sweep throughput).

RNG: the chain key is folded once per sweep, and that sweep's uniforms are
``jax.random.uniform(fold_in(key, step), (4, R, C))``: every draw is
counter-indexed, reproducible and independent of execution order. The
chains draw them straight into the blocked layout
(:func:`sweep_probs_blocked`): under jax's partitionable threefry each
value depends only on the key and its flat index, so hashing the flat
indices of the blocked positions gives the same values with no relayout.

Both loops carry the lattice as a 4-tuple of blocked quads
(:func:`repro.core.checkerboard.sweep_blocked`), blocked once on entry and
unblocked once on exit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.random import threefry2x32_p

from repro.core import checkerboard as cb
from repro.core import lattice as L
from repro.core import measure as ms
from repro.core import observables as obs


@dataclasses.dataclass(frozen=True)
class ChainConfig:
    beta: float
    n_sweeps: int
    block_size: int = L.MXU_BLOCK
    accept: str = "lut"          # update rule: "lut" | "exp" | "heat_bath"
    dtype: str = "bfloat16"      # lattice/acceptance dtype
    prob_dtype: str = "float32"  # dtype of the uniform draws
    measure: bool = True
    field: float = 0.0           # external field h (paper: h = 0)


def sweep_probs(key: jax.Array, step, shape, dtype) -> jax.Array:
    """Uniforms for one sweep: [4, R, C] (black A, black D, white B, white C)."""
    with jax.named_scope(L.RNG):
        k = jax.random.fold_in(key, step)
        return jax.random.uniform(k, (4,) + shape, dtype)


def _uniform_from_bits(bits: jax.Array, dtype) -> jax.Array:
    """``jax.random.uniform``'s map of 32 threefry bits to [0, 1) in
    ``dtype`` (a float of at most 32 bits): the top bits become the
    mantissa of a float in [1, 2), less one."""
    finfo = jnp.finfo(dtype)
    nbits, nmant = finfo.bits, finfo.nmant
    rng_bits = 8 if nmant < 8 else nbits
    uint = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}
    bits = bits.astype(uint[rng_bits]).astype(uint[nbits])
    one = np.array(1.0, dtype).view(uint[nbits])
    float_bits = lax.shift_right_logical(
        bits, jnp.asarray(rng_bits - nmant, uint[nbits])) | one
    # x * (maxval - minval) + minval and the max with minval that jax.random
    # applies for [0, 1) leave these values unchanged.
    return lax.bitcast_convert_type(float_bits, dtype) - jnp.array(1., dtype)


def _counter_words(site: jax.Array, base: int):
    """(hi, lo) uint32 words of the 64-bit counters ``base + site``."""
    base_lo = jnp.uint32(base % 2 ** 32)
    lo = site + base_lo
    hi = jnp.uint32(base // 2 ** 32) + (lo < base_lo).astype(jnp.uint32)
    return hi, lo


def _blocked_counters(plane: int, shape, bs: int):
    """(hi, lo) words of the flat index in [4, R, C] of every site of one
    uniform plane, laid out [R/bs, C/bs, bs, bs]."""
    r, c = shape
    if r % bs or c % bs:
        raise ValueError(f"{shape} not divisible by block {bs}")
    if r * c >= 2 ** 32:
        raise ValueError(f"a uniform plane of {shape} has 2**32 sites or "
                         f"more; the blocked draw indexes them in 32 bits")
    grid = (r // bs, c // bs, bs, bs)

    def iota(d):
        return lax.broadcasted_iota(jnp.uint32, grid, d)

    site = ((iota(0) * bs + iota(2)) * jnp.uint32(c)
            + iota(1) * bs + iota(3))
    return _counter_words(site, plane * r * c)


def _counter_draw_matches(key: jax.Array, dtype) -> bool:
    """Whether :func:`_blocked_uniform` reproduces ``jax.random.uniform``:
    the partitionable threefry, and a float of at most 32 bits."""
    return (jax.config.jax_threefry_partitionable
            and str(jax.random.key_impl(key)) == "threefry2x32"
            and jnp.finfo(dtype).bits <= 32)


def _blocked_uniform(key: jax.Array, plane: int, shape, bs: int, dtype):
    """Plane ``plane`` of ``jax.random.uniform(key, (4,) + shape, dtype)``,
    blocked: each value is threefry of the key and of its flat index."""
    k1, k2 = jax.random.key_data(key)
    hi, lo = _blocked_counters(plane, shape, bs)
    bits1, bits2 = threefry2x32_p.bind(k1, k2, hi, lo)
    return _uniform_from_bits(bits1 ^ bits2, dtype)


def sweep_probs_blocked(key: jax.Array, step, shape, dtype,
                        block_size: int = L.MXU_BLOCK) -> tuple:
    """The uniforms of :func:`sweep_probs` as 4 blocked planes
    [mr, mc, bs, bs] (black A, black D, white B, white C), drawn in that
    layout. Where jax's draw could not be reproduced (another PRNG, the
    non-partitionable threefry, a 64-bit float) it draws as
    :func:`sweep_probs` and blocks."""
    with jax.named_scope(L.RNG):
        k = jax.random.fold_in(key, step)
        if _counter_draw_matches(k, dtype):
            return tuple(_blocked_uniform(k, q, shape, block_size, dtype)
                         for q in range(4))
        probs = jax.random.uniform(k, (4,) + shape, dtype)
    return L.block_quads(probs, block_size)


@functools.partial(jax.jit, static_argnums=(2,))
def _run_chain_impl(quads, key, cfg: ChainConfig):
    """Measured chain: per-sweep (m, E) stream from the white half-update's
    own nn sums (repro.core.measure) — the compiled loop never rebuilds the
    full lattice (`from_quads`) or re-rolls neighbour sums. The scan carries
    the blocked 4-tuple: one block on entry, one unblock on exit."""
    pdt = jnp.dtype(cfg.prob_dtype)
    shape = quads.shape[1:]
    qb = L.block_quads(quads, cfg.block_size)

    def body(carry, step):
        with jax.named_scope(L.SWEEP):
            probs = sweep_probs_blocked(key, step, shape, pdt,
                                        cfg.block_size)
            return ms.sweep_blocked_measured(carry, probs, cfg.beta,
                                             cfg.accept, field=cfg.field)

    final, (m_t, e_t) = jax.lax.scan(body, qb, jnp.arange(cfg.n_sweeps))
    return L.unblock_quads(final), m_t, e_t


def run_chain(quads: jax.Array, key: jax.Array, cfg: ChainConfig):
    """Run cfg.n_sweeps sweeps; returns (final_quads, m[T], E[T])."""
    return _run_chain_impl(quads, key, cfg)


@functools.partial(jax.jit, static_argnums=(2,))
def _run_sweeps_impl(quads, key, cfg: ChainConfig):
    pdt = jnp.dtype(cfg.prob_dtype)
    shape = quads.shape[1:]

    def body(step, qb):
        with jax.named_scope(L.SWEEP):
            probs = sweep_probs_blocked(key, step, shape, pdt,
                                        cfg.block_size)
            return cb.sweep_blocked(qb, probs, cfg.beta, cfg.accept,
                                    field=cfg.field)

    qb = L.block_quads(quads, cfg.block_size)
    return L.unblock_quads(jax.lax.fori_loop(0, cfg.n_sweeps, body, qb))


def run_sweeps(quads: jax.Array, key: jax.Array, cfg: ChainConfig):
    """Measurement-free sweep loop (throughput benchmarks)."""
    return _run_sweeps_impl(quads, key, cfg)


def init_state(key: jax.Array, height: int, width: int,
               dtype=jnp.bfloat16, hot: bool = True) -> jax.Array:
    full = (L.random_lattice(key, height, width, dtype) if hot
            else L.cold_lattice(height, width, dtype))
    return L.to_quads(full)


def run_chains_batched(quads_batch: jax.Array, key: jax.Array,
                       cfg: ChainConfig):
    """N independent chains in one compiled program (vmap over the leading
    dim of [N, 4, R, C]; per-chain RNG from fold_in). The natural TPU
    batching axis for error bars — beyond-paper convenience.

    Returns (final [N, 4, R, C], m [N, T], E [N, T])."""
    n = quads_batch.shape[0]
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    return jax.vmap(lambda q, k: _run_chain_impl(q, k, cfg))(
        quads_batch, keys)


def measure_curve(key: jax.Array, size: int, temperatures, n_sweeps: int,
                  burnin: int, dtype="bfloat16", accept="lut",
                  block_size: int = 0) -> list[dict]:
    """Paper Fig. 4 driver: U4 and |m| vs T for one lattice size."""
    block_size = block_size or min(L.MXU_BLOCK, size // 2)
    from repro.core import observables as obs_mod
    tc = obs_mod.critical_temperature()
    results = []
    for t in temperatures:
        cfg = ChainConfig(beta=1.0 / t, n_sweeps=n_sweeps,
                          block_size=block_size, accept=accept, dtype=dtype)
        k_init, k_chain = jax.random.split(jax.random.fold_in(key, hash(t) % (2**31)))
        # cold start below Tc (ordered phase), hot above — the standard trick
        # to keep burn-in short on both sides of the transition.
        quads = init_state(k_init, size, size, jnp.dtype(dtype),
                           hot=bool(t > tc))
        _, ms, es = run_chain(quads, k_chain, cfg)
        stats = obs.chain_statistics(ms, es, burnin)
        stats["T"] = float(t)
        stats["size"] = size
        results.append(stats)
    return results
