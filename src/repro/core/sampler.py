"""MCMC chain drivers for the Ising model.

Two compiled entry points:

* :func:`run_chain`    — `lax.scan` over sweeps collecting per-sweep (m, E)
                         scalars; used for physics (Fig. 4) runs.
* :func:`run_sweeps`   — measurement-free `lax.fori_loop`; used for benchmarks
                         (paper Tables 1-2 measure pure sweep throughput).

RNG: a single threefry key folded per (sweep, colour) so every uniform draw is
counter-indexed — reproducible and independent of execution order, matching
how the distributed sampler derives per-device streams.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

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


def make_sweep_fn(cfg: ChainConfig):
    dtype = jnp.dtype(cfg.prob_dtype)

    def one_sweep(quads: jax.Array, key: jax.Array, step) -> jax.Array:
        with jax.named_scope(L.SWEEP):
            probs = sweep_probs(key, step, quads.shape[1:], dtype)
            return cb.sweep_compact(quads, probs, cfg.beta, cfg.block_size,
                                    cfg.accept, field=cfg.field)

    return one_sweep


@functools.partial(jax.jit, static_argnums=(2,))
def _run_chain_impl(quads, key, cfg: ChainConfig):
    """Measured chain: per-sweep (m, E) stream from the white half-update's
    own nn sums (repro.core.measure) — the compiled loop never rebuilds the
    full lattice (`from_quads`) or re-rolls neighbour sums."""
    pdt = jnp.dtype(cfg.prob_dtype)

    def body(carry, step):
        with jax.named_scope(L.SWEEP):
            probs = sweep_probs(key, step, carry.shape[1:], pdt)
            return ms.sweep_compact_measured(carry, probs, cfg.beta,
                                             cfg.block_size, cfg.accept,
                                             field=cfg.field)

    final, (m_t, e_t) = jax.lax.scan(body, quads, jnp.arange(cfg.n_sweeps))
    return final, m_t, e_t


def run_chain(quads: jax.Array, key: jax.Array, cfg: ChainConfig):
    """Run cfg.n_sweeps sweeps; returns (final_quads, m[T], E[T])."""
    return _run_chain_impl(quads, key, cfg)


@functools.partial(jax.jit, static_argnums=(2,))
def _run_sweeps_impl(quads, key, cfg: ChainConfig):
    one_sweep = make_sweep_fn(cfg)

    def body(i, q):
        return one_sweep(q, key, i)

    return jax.lax.fori_loop(0, cfg.n_sweeps, body, quads)


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
