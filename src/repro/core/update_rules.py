"""Pluggable single-site update rules — one registry, every backend.

Before this module the Metropolis flip lived in four places (the float
``_flip`` in ``core.checkerboard``, the bits-based ``_metropolis`` in the
Pallas kernel, its jnp mirror in ``kernels.ref``, and the integer-threshold
``_flip_int`` in ``distributed.ising``). They are now call sites of this
registry, so a new dynamics (e.g. heat-bath/Glauber) drops into the XLA,
Pallas, ref, and integer-opt pipelines at once.

Each :class:`UpdateRule` exposes three forms of the same transition kernel:

``flip_probs(sigma, nn, probs, beta, field=0.0)``
    Float-uniform form (paper pipeline): ``probs`` are uniforms in [0, 1)
    of any float dtype; comparison happens in the lattice dtype, exactly as
    the historical ``core.checkerboard._flip``.

``flip_bits(sigma, nn, bits, beta)``
    Raw-bits form (kernel semantics): uint32 bits, top 24 bits -> f32
    uniform, f32 select-chain table, f32 compare — bitwise identical to the
    Pallas kernel and its ref oracle. ``beta`` must be a Python float
    (tables are built at trace time).

``flip_bits_int(sigma, nn, bits, beta)``
    Integer-threshold form (``pipeline='opt'``): no floats touch the
    uniforms at all; ``u_int < ceil(p * 2^24)`` is exact because the f32
    probabilities are dyadic rationals. Accepts uint32 (top 24 bits) or
    uint16 (thresholds rescaled with ceil) bits.

``kernel_form(beta)``
    Compile-time specialization for Pallas: returns ``fn(sigma, nn, bits)``
    with ``beta`` and the probability table baked in as Python constants
    (the form ``pallas_call`` kernel bodies consume; ``nn`` is the f32 MXU
    accumulator output).

Rules
-----
* ``metropolis_exp`` — paper acceptance ``exp(-2*beta*sigma*nn)`` evaluated
  per site (the only rule that supports an external field ``h``).
* ``metropolis_lut`` — exact 5-entry table (``sigma*nn`` takes values in
  {-4,-2,0,2,4}); bitwise-equal probabilities to ``metropolis_exp``.
* ``metropolis_int`` — the u24 integer-threshold path; decisions bitwise
  identical to ``metropolis_lut`` fed the same bits.
* ``heat_bath`` — Glauber dynamics: the new spin is drawn from the exact
  conditional ``P(+1) = 1 / (1 + exp(-2*beta*(nn + h)))`` independent of
  the current spin. Same Boltzmann stationary distribution, different
  (rejection-free) dynamics.

Names accepted by :func:`get_rule` include the historical ``accept=``
aliases ``"lut"`` and ``"exp"`` so existing signatures keep working.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import lattice as L

_INV_2_24 = 1.0 / float(1 << 24)

# x = sigma * nn (metropolis) or nn (heat-bath) lattice values, 2-D torus.
_X_VALUES = (-4.0, -2.0, 0.0, 2.0, 4.0)


def bits_to_uniform(bits: jax.Array) -> jax.Array:
    """uint32 -> f32 uniform in [0, 1): keep the top 24 bits (exact in f32).

    The shifted value is below 2^24, so the detour through int32 is exact;
    the TPU lowering has no direct uint32 -> f32 cast."""
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) * _INV_2_24


def _select5(x: jax.Array, t) -> jax.Array:
    """5-entry table lookup over x in {-4,-2,0,2,4} as a select chain
    (cheaper than a gather on the VPU, exact)."""
    return jnp.where(
        x <= -3.0, t[0],
        jnp.where(x <= -1.0, t[1],
                  jnp.where(x <= 1.0, t[2],
                            jnp.where(x <= 3.0, t[3], t[4]))))


def _thresholds_u24(probs_f32) -> list[int]:
    """ceil(p * 2^24) per table entry — exact for f32 dyadic rationals, so
    ``u_int < t`` decides identically to ``u_int/2^24 < p`` (see
    tests/test_ising_opt.py for the exhaustive boundary check)."""
    import fractions

    out = []
    for p in probs_f32:
        t = int(math.ceil(fractions.Fraction(float(p)) * (1 << 24)))
        out.append(min(t, 1 << 24))  # p >= 1: every u accepted
    return out


def _select5_u32(x: jax.Array, ts, lim: int) -> jax.Array:
    return jnp.where(
        x <= -3.0, jnp.uint32(min(ts[0], lim)),
        jnp.where(x <= -1.0, jnp.uint32(min(ts[1], lim)),
                  jnp.where(x <= 1.0, jnp.uint32(min(ts[2], lim)),
                            jnp.where(x <= 3.0, jnp.uint32(ts[3]),
                                      jnp.uint32(ts[4])))))


def _int_compare(bits: jax.Array, ts24: list[int], x: jax.Array) -> jax.Array:
    """True where the integer uniform falls below the per-x threshold.

    uint16 bits rescale the u24 thresholds to 2^16 with ceil — a
    2^-16-granular acceptance, statistically indistinguishable and half the
    RNG traffic."""
    if bits.dtype == jnp.uint16:
        ts = [min((t + 255) >> 8, 1 << 16) for t in ts24]
        u = bits.astype(jnp.uint32)
        lim = 1 << 16
    else:
        ts = ts24
        u = bits >> 8
        lim = 1 << 24
    return u < _select5_u32(x, ts, lim)


# ---------------------------------------------------------------------------
# Rule definition / registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class UpdateRule:
    """One single-site dynamics, in every form a backend needs."""
    name: str
    flip_probs: Callable        # (sigma, nn, probs, beta, field=0.0)
    flip_bits: Callable         # (sigma, nn, bits, beta)  float-compare
    flip_bits_int: Callable     # (sigma, nn, bits, beta)  integer-compare
    kernel_form: Callable       # (beta) -> fn(sigma, nn_f32, bits)
    supports_field: bool = False


_REGISTRY: dict = {}
_ALIASES = {
    "lut": "metropolis_lut",
    "exp": "metropolis_exp",
    "metropolis": "metropolis_lut",
    "int": "metropolis_int",
    "glauber": "heat_bath",
}


def register_rule(rule: UpdateRule) -> UpdateRule:
    _REGISTRY[rule.name] = rule
    return rule


def get_rule(name: str) -> UpdateRule:
    """Look up a rule by canonical name or alias ('lut', 'exp', ...)."""
    key = _ALIASES.get(name, name)
    try:
        return _REGISTRY[key]
    except KeyError:
        raise ValueError(
            f"unknown update rule {name!r}; known: "
            f"{sorted(_REGISTRY)} (aliases {sorted(_ALIASES)})") from None


def rule_names() -> tuple:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Metropolis probability tables
# ---------------------------------------------------------------------------


def acceptance_table(beta, dtype=jnp.float32) -> jax.Array:
    """acc[k] = exp(-2*beta*x) for x = 2k-4, k=0..4 (x = sigma*nn)."""
    x = jnp.arange(-4.0, 5.0, 2.0, dtype=jnp.float32)
    return jnp.exp(-2.0 * jnp.float32(beta) * x).astype(dtype)


def metropolis_thresholds_u24(beta) -> list[int]:
    """Integer acceptance thresholds: flip iff (bits >> 8) < t[(x+4)/2]."""
    import numpy as _np
    return _thresholds_u24(
        [_np.float32(math.exp(-2.0 * float(beta) * x)) for x in _X_VALUES])


def heat_bath_table_f32(beta) -> list:
    """p_up[k] = f32 sigmoid(2*beta*nn) for nn = 2k-4 — P(new spin = +1)."""
    import numpy as _np
    return [_np.float32(1.0 / (1.0 + math.exp(-2.0 * float(beta) * nn)))
            for nn in _X_VALUES]


def heat_bath_thresholds_u24(beta) -> list[int]:
    return _thresholds_u24(heat_bath_table_f32(beta))


def metropolis_acceptance(nn: jax.Array, sigma: jax.Array, beta,
                          method: str = "lut",
                          field: float = 0.0) -> jax.Array:
    """P(accept flip of sigma) given neighbour sum nn. Same dtype as sigma.

    field = external magnetic field h (paper assumes h=0): flipping sigma
    costs dE = 2*sigma*(J*nn + h), so acceptance = exp(-2*beta*(x + s*h))
    with x = sigma*nn. The h term forces the exp path (x + s*h is no
    longer 5-valued).
    """
    x = nn * sigma  # in {-4,-2,0,2,4}, exact in bf16
    if field:
        arg = (x.astype(jnp.float32)
               + sigma.astype(jnp.float32) * jnp.float32(field))
        acc = jnp.exp(-2.0 * jnp.asarray(beta, jnp.float32) * arg)
        return acc.astype(sigma.dtype)
    if method == "exp":
        # paper: acceptance = exp(-2 * beta * nn * sigma)
        acc = jnp.exp(-2.0 * jnp.asarray(beta, jnp.float32)
                      * x.astype(jnp.float32))
        return acc.astype(sigma.dtype)
    if method == "lut":
        table = acceptance_table(beta, sigma.dtype)
        idx = ((x.astype(jnp.float32) + 4.0) * 0.5).astype(jnp.int32)
        return jnp.take(table, idx)
    raise ValueError(f"unknown acceptance method {method!r}")


# ---------------------------------------------------------------------------
# Metropolis forms (bitwise-identical to the historical implementations)
# ---------------------------------------------------------------------------


def uniforms_in(probs: jax.Array, dtype) -> jax.Array:
    """The uniforms as ``flip_probs`` compares them: cast to ``dtype``, the
    lattice dtype, rounding to nearest even as ``astype`` does; from f32 to
    bf16 by integer ops on the bits.

    A loop that fuses the draw into the compare needs this (the blocked
    chain does, ``checkerboard.update_color_blocked``): the TPU compiler
    keeps a fusion's bf16 intermediates in f32 and may drop the rounding of
    an ``astype`` there, which changes the decisions near each acceptance
    value. Integer rounding cannot be dropped. Values must be finite
    (uniforms in [0, 1) are)."""
    if probs.dtype == jnp.float32 and jnp.dtype(dtype) == jnp.bfloat16:
        b = jax.lax.bitcast_convert_type(probs, jnp.uint32)
        b = b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))
        return jax.lax.bitcast_convert_type((b >> 16).astype(jnp.uint16),
                                            jnp.bfloat16)
    return probs.astype(dtype)


def _as_uniforms(probs: jax.Array, dtype) -> jax.Array:
    """The uniforms cast to the acceptance dtype, under the ``rng`` scope:
    XLA fuses the draw into this cast, which ends the fusion, so the draw's
    time is charged to ``rng`` and not to ``flip``."""
    with jax.named_scope(L.RNG):
        return probs.astype(dtype)


def _metropolis_flip_probs(method):
    def flip(sigma, nn, probs, beta, field: float = 0.0):
        acc = metropolis_acceptance(nn, sigma, beta, method, field)
        flips = (_as_uniforms(probs, acc.dtype) < acc)
        # sigma - 2*flips*sigma, but branch-free select keeps spins exact.
        return jnp.where(flips, -sigma, sigma)
    return flip


def _metropolis_kernel_form(beta: float):
    t = [math.exp(-2.0 * float(beta) * v) for v in _X_VALUES]

    def flip(sigma, nn, bits):
        x = nn * sigma.astype(jnp.float32)
        acc = _select5(x, t)
        flips = bits_to_uniform(bits) < acc
        return jnp.where(flips, -sigma, sigma)

    return flip


def _metropolis_flip_bits(sigma, nn, bits, beta):
    return _metropolis_kernel_form(float(beta))(
        sigma, nn.astype(jnp.float32), bits)


def _metropolis_flip_bits_int(sigma, nn, bits, beta):
    x = nn * sigma  # bf16, exact
    flips = _int_compare(bits, metropolis_thresholds_u24(beta), x)
    return jnp.where(flips, -sigma, sigma)


def _metropolis_exp_flip_bits(sigma, nn, bits, beta):
    """Bits form of the exp rule: same probabilities as the LUT (the table
    IS exp), so this is the LUT bits path."""
    return _metropolis_flip_bits(sigma, nn, bits, beta)


# ---------------------------------------------------------------------------
# Heat-bath (Glauber) forms
# ---------------------------------------------------------------------------


def _heat_bath_flip_probs(sigma, nn, probs, beta, field: float = 0.0):
    """Draw the new spin from the exact conditional, ignoring the old one:
    P(+1) = sigmoid(2*beta*(nn + h)). Comparison conventions mirror the
    Metropolis probs form (compare in the lattice dtype)."""
    arg = nn.astype(jnp.float32)
    if field:
        arg = arg + jnp.float32(field)
    p_up = jax.nn.sigmoid(2.0 * jnp.asarray(beta, jnp.float32) * arg)
    p_up = p_up.astype(sigma.dtype)
    up = _as_uniforms(probs, p_up.dtype) < p_up
    return jnp.where(up, jnp.ones_like(sigma), -jnp.ones_like(sigma))


def _heat_bath_kernel_form(beta: float):
    t = [1.0 / (1.0 + math.exp(-2.0 * float(beta) * v)) for v in _X_VALUES]

    def draw(sigma, nn, bits):
        p_up = _select5(nn, t)                     # keyed on nn, not sigma*nn
        up = bits_to_uniform(bits) < p_up
        # select in f32, then cast: Mosaic cannot relayout a bf16 select
        return jnp.where(up, 1.0, -1.0).astype(sigma.dtype)

    return draw


def _heat_bath_flip_bits(sigma, nn, bits, beta):
    return _heat_bath_kernel_form(float(beta))(
        sigma, nn.astype(jnp.float32), bits)


def _heat_bath_flip_bits_int(sigma, nn, bits, beta):
    up = _int_compare(bits, heat_bath_thresholds_u24(beta),
                      nn.astype(sigma.dtype))
    one = jnp.ones((), sigma.dtype)
    return jnp.where(up, one, -one)


# ---------------------------------------------------------------------------
# Registry contents
# ---------------------------------------------------------------------------

metropolis_lut = register_rule(UpdateRule(
    name="metropolis_lut",
    flip_probs=_metropolis_flip_probs("lut"),
    flip_bits=_metropolis_flip_bits,
    flip_bits_int=_metropolis_flip_bits_int,
    kernel_form=_metropolis_kernel_form,
    supports_field=True,        # field forces the exp path internally
))

metropolis_exp = register_rule(UpdateRule(
    name="metropolis_exp",
    flip_probs=_metropolis_flip_probs("exp"),
    flip_bits=_metropolis_exp_flip_bits,
    flip_bits_int=_metropolis_flip_bits_int,
    kernel_form=_metropolis_kernel_form,
    supports_field=True,
))

metropolis_int = register_rule(UpdateRule(
    name="metropolis_int",
    flip_probs=_metropolis_flip_probs("lut"),
    flip_bits=_metropolis_flip_bits,
    flip_bits_int=_metropolis_flip_bits_int,
    kernel_form=_metropolis_kernel_form,
))

heat_bath = register_rule(UpdateRule(
    name="heat_bath",
    flip_probs=_heat_bath_flip_probs,
    flip_bits=_heat_bath_flip_bits,
    flip_bits_int=_heat_bath_flip_bits_int,
    kernel_form=_heat_bath_kernel_form,
    supports_field=True,
))
