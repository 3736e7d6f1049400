"""Generic domain-decomposition driver: one shard_map scaffold, any model.

Every decomposed sampler in the repo — 2-D Ising quads, the 3-D cube,
Potts checkerboard colours, Ising/Potts cluster updates — runs the same
loop: shard the state over the mesh, fori_loop device-local sweeps with
halo exchange inside, psum per-sweep scalars, accumulate running
:class:`repro.core.measure.Moments`. That scaffold used to be copied into
``distributed/ising.py``, ``cluster/mesh.py``, and ``potts/mesh.py``; it
now lives here once, parameterized by a :class:`MeshModel`:

* ``sweep(local_state, key, step)`` — one full device-local sweep (the
  *update-site rule*: halos, RNG, and acceptance are the model's business;
  ``key`` is the replicated chain key and ``step`` the loop counter, so
  counter-based models reproduce single-device chains bitwise);
* ``stats(local_state)`` — per-sweep ``(m, E/spin)`` global scalars,
  already psum-reduced over the model's mesh axes;
* ``sweep_measured`` (optional) — fused sweep+stats when the update
  already holds the sums measurement needs (the 2-D XLA path reuses the
  white half-update's nn tensors at zero extra matmul cost);
* ``unpack`` / ``pack`` (optional) — loop-carry layout converters so a
  model can, e.g., carry a 4-tuple of quads through the loop and only
  restack once at the end (§Perf Ising iteration 3).

The three entry points mirror the historical per-plane APIs:
:func:`make_run_sweeps_fn` (measurement-free throughput loop),
:func:`make_run_chain_fn` (streamed Moments), :func:`global_stats`
(standalone exact psum stats for logging between compiled chunks).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import lattice as L
from repro.core import measure


@dataclasses.dataclass(frozen=True)
class MeshModel:
    """One spin model x state layout bound to the generic driver.

    ``state_spec`` is the PartitionSpec of the global state array;
    ``sweep``/``stats`` operate on the device-local shard (and on the
    unpacked loop carry, which defaults to the local shard itself).
    """
    state_spec: P
    sweep: Callable          # (carry, key, step) -> carry
    stats: Callable          # (carry) -> (m, e)   psum-reduced scalars
    sweep_measured: Optional[Callable] = None   # (carry, key, step)
    unpack: Optional[Callable] = None           # local state -> carry
    pack: Optional[Callable] = None             # carry -> local state

    def _unpack(self, st):
        with jax.named_scope(L.LAYOUT):
            return self.unpack(st) if self.unpack is not None else st

    def _pack(self, carry):
        with jax.named_scope(L.LAYOUT):
            return self.pack(carry) if self.pack is not None else carry

    def _sweep_measured(self):
        if self.sweep_measured is not None:
            return self.sweep_measured

        def fused(carry, key, step):
            carry = self.sweep(carry, key, step)
            return carry, self.stats(carry)

        return fused


def make_run_sweeps_fn(mesh, model: MeshModel, n_sweeps: int):
    """Jitted measurement-free chain ``run(state, key) -> state`` — the
    paper's throughput-benchmark loop."""

    def local_run(st, key):
        carry = lax.fori_loop(0, n_sweeps,
                              lambda step, c: model.sweep(c, key, step),
                              model._unpack(st))
        return model._pack(carry)

    mapped = shard_map(local_run, mesh=mesh, check_vma=False,
                       in_specs=(model.state_spec, P()),
                       out_specs=model.state_spec)
    return jax.jit(mapped, donate_argnums=(0,))


def make_run_chain_fn(mesh, model: MeshModel, n_sweeps: int,
                      measure_every: int = 1):
    """Jitted measured chain ``run(state, key) -> (state, Moments)``: the
    streaming measurement plane inside the shard_map loop — per-sweep
    (m, E) psum-reduced to exact global scalars and accumulated with
    ``measure_every`` thinning; no per-sweep series ever reaches the host."""
    measured = model._sweep_measured()

    def local_run(st, key):
        def body(step, carry):
            c, mom = carry
            c, (m, e) = measured(c, key, step)
            return c, measure.accumulate(mom, m, e, step, measure_every)

        carry, mom = lax.fori_loop(
            0, n_sweeps, body, (model._unpack(st), measure.init_moments()))
        return model._pack(carry), mom

    mapped = shard_map(local_run, mesh=mesh, check_vma=False,
                       in_specs=(model.state_spec, P()),
                       out_specs=(model.state_spec,
                                  measure.Moments(
                                      *([P()] * measure.N_FIELDS))))
    return jax.jit(mapped, donate_argnums=(0,))


def global_stats(mesh, model: MeshModel):
    """Jitted exact global ``(m, E/spin)`` of the sharded state without
    gathering it — the standalone companion of :func:`make_run_chain_fn`
    for logging between compiled chunks."""

    def local_stats(st):
        return model.stats(model._unpack(st))

    mapped = shard_map(local_stats, mesh=mesh, check_vma=False,
                       in_specs=(model.state_spec,), out_specs=(P(), P()))
    return jax.jit(mapped)
