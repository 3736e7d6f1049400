"""The single-chip chain carries blocked quads for the whole chunk.

``sampler._run_chain_impl`` and ``_run_sweeps_impl`` block the [4, R, C]
quads once on entry, scan a 4-tuple of [mr, mc, bs, bs] quads, and unblock
once on exit; each sweep's uniforms are drawn straight into that layout
(``sampler.sweep_probs_blocked``). Nothing about the chain's results may
change: the blocked draw is ``jax.random.uniform(fold_in(key, step),
(4, R, C))`` bit for bit, and the chain is the roll oracle (``sweep_full``)
fed those uniforms, bit for bit. The structural tests count the relayouts
left inside the compiled loop.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import checkerboard as cb
from repro.core import lattice as L
from repro.core import measure
from repro.core import sampler
from repro.core import update_rules as rules

KEY = jax.random.PRNGKey(20260)


def blocked_planes(probs, bs):
    return [np.asarray(L.block(probs[i], bs)) for i in range(4)]


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# The blocked draw
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,bs", [((64, 64), 8), ((64, 64), 16),
                                      ((32, 96), 16), ((96, 32), 8),
                                      ((128, 256), 128), ((256, 128), 128)])
@pytest.mark.parametrize("step", [0, 3, 1234567])
def test_blocked_uniforms_equal_jax_draw(shape, bs, step):
    want = jax.random.uniform(jax.random.fold_in(KEY, step), (4,) + shape,
                              jnp.float32)
    got = jax.jit(sampler.sweep_probs_blocked, static_argnums=(2, 3, 4))(
        KEY, step, shape, jnp.float32, bs)
    assert len(got) == 4
    for g, w in zip(got, blocked_planes(want, bs)):
        assert same_bits(g, w)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_blocked_uniforms_equal_jax_draw_in_narrow_dtypes(dtype):
    """A prob_dtype other than f32 gives what jax.random.uniform gives in
    that dtype (bf16 is the benchmark's control)."""
    want = jax.random.uniform(jax.random.fold_in(KEY, 5), (4, 32, 64), dtype)
    got = sampler.sweep_probs_blocked(KEY, 5, (32, 64), dtype, 16)
    for g, w in zip(got, blocked_planes(want, 16)):
        assert g.dtype == dtype and same_bits(g, w)


def test_blocked_uniforms_under_vmap_over_keys():
    keys = jax.vmap(lambda i: jax.random.fold_in(KEY, i))(jnp.arange(3))
    got = jax.vmap(lambda k: sampler.sweep_probs_blocked(
        k, 7, (32, 32), jnp.float32, 16))(keys)
    for n in range(3):
        want = jax.random.uniform(jax.random.fold_in(keys[n], 7),
                                  (4, 32, 32), jnp.float32)
        for g, w in zip(got, blocked_planes(want, 16)):
            assert same_bits(g[n], w)


def test_blocked_uniforms_with_typed_key():
    key = jax.random.key(11)
    want = jax.random.uniform(jax.random.fold_in(key, 2), (4, 32, 32))
    got = sampler.sweep_probs_blocked(key, 2, (32, 32), jnp.float32, 8)
    for g, w in zip(got, blocked_planes(want, 8)):
        assert same_bits(g, w)


def test_blocked_uniforms_without_partitionable_threefry():
    """Under the original threefry the values depend on the whole array's
    shape; the draw then falls back to jax's and blocks it."""
    with jax.threefry_partitionable(False):
        want = jax.random.uniform(jax.random.fold_in(KEY, 4), (4, 32, 32))
        got = sampler.sweep_probs_blocked(KEY, 4, (32, 32), jnp.float32, 16)
    for g, w in zip(got, blocked_planes(want, 16)):
        assert same_bits(g, w)


@pytest.mark.parametrize("base", [0, 3 * 2 ** 20, 2 ** 32 - 5, 2 ** 32,
                                  3 * 2 ** 32 - 2])
def test_counter_words_carry_into_the_high_word(base):
    site = jnp.arange(16, dtype=jnp.uint32).reshape(4, 4)
    hi, lo = sampler._counter_words(site, base)
    want = base + np.arange(16, dtype=np.uint64).reshape(4, 4)
    assert np.array_equal(np.asarray(hi, np.uint64), want >> np.uint64(32))
    assert np.array_equal(np.asarray(lo, np.uint64),
                          want & np.uint64(2 ** 32 - 1))


def test_blocked_counters_are_the_blocked_flat_index():
    r, c, bs = 16, 24, 8
    for plane in range(4):
        hi, lo = sampler._blocked_counters(plane, (r, c), bs)
        flat = plane * r * c + np.arange(r * c).reshape(r, c)
        want = flat.reshape(r // bs, bs, c // bs, bs).transpose(0, 2, 1, 3)
        assert not np.asarray(hi).any()
        assert np.array_equal(np.asarray(lo), want)


def test_blocked_counters_refuse_planes_of_2_32_sites():
    with pytest.raises(ValueError, match="2\\*\\*32"):
        sampler._blocked_counters(0, (2 ** 16, 2 ** 16), 128)
    with pytest.raises(ValueError, match="divisible"):
        sampler._blocked_counters(0, (48, 48), 32)


def test_uniforms_in_bf16_rounds_as_astype_for_every_f32_uniform():
    """Every value jax.random.uniform can give in f32 (m * 2**-23), and the
    values halfway between two bf16 numbers, round as ``astype`` rounds."""
    u = jnp.arange(2 ** 23, dtype=jnp.uint32)
    probs = jax.lax.bitcast_convert_type(u | jnp.uint32(0x3F800000),
                                         jnp.float32) - 1.0
    halfway = jax.lax.bitcast_convert_type(
        (u << 16 | jnp.uint32(0x8000))[:0x3F80], jnp.float32)
    for x in (probs, halfway):
        got = rules.uniforms_in(x, jnp.bfloat16)
        assert got.dtype == jnp.bfloat16
        assert same_bits(got, x.astype(jnp.bfloat16))


@pytest.mark.parametrize("src,dst", [(jnp.float32, jnp.float32),
                                     (jnp.bfloat16, jnp.bfloat16),
                                     (jnp.float32, jnp.float16)])
def test_uniforms_in_other_dtypes_is_astype(src, dst):
    x = jax.random.uniform(KEY, (64,), src)
    assert same_bits(rules.uniforms_in(x, dst), x.astype(dst))


# ---------------------------------------------------------------------------
# The chain against the roll oracle
# ---------------------------------------------------------------------------


def full_probs(probs):
    """[4, R, C] sweep uniforms -> full-lattice (black, white) arrays."""
    _, r, c = probs.shape
    pb = jnp.zeros((2 * r, 2 * c), probs.dtype)
    pb = pb.at[0::2, 0::2].set(probs[0]).at[1::2, 1::2].set(probs[1])
    pw = jnp.zeros((2 * r, 2 * c), probs.dtype)
    pw = pw.at[0::2, 1::2].set(probs[2]).at[1::2, 0::2].set(probs[3])
    return pb, pw


def oracle_chain(quads, key, cfg):
    """Per-sweep loop of sweep_full fed the chain's uniforms; (m, E/spin)
    from exact integer sums of the full lattice, divided by the spin count
    as compiled code divides (XLA may multiply by the reciprocal)."""
    full = L.from_quads(quads)
    n = full.size
    per_spin = jax.jit(lambda x: x / jnp.float32(n))
    white = (np.add.outer(np.arange(full.shape[0]),
                          np.arange(full.shape[1])) % 2 == 1)
    ms, es = [], []
    for step in range(cfg.n_sweeps):
        probs = sampler.sweep_probs(key, step, quads.shape[1:],
                                    jnp.dtype(cfg.prob_dtype))
        pb, pw = full_probs(probs)
        full = cb.sweep_full(full, pb, pw, cfg.beta, cfg.accept, cfg.field)
        s = np.asarray(full, np.int64)
        nn = np.asarray(cb.nn_full(full.astype(jnp.float32)), np.int64)
        ms.append(per_spin(np.float32(s.sum())))
        es.append(-per_spin(np.float32((s * nn)[white].sum())))
    return L.to_quads(full), np.array(ms), np.array(es)


@pytest.mark.parametrize("accept,field", [("lut", 0.0), ("exp", 0.0),
                                          ("heat_bath", 0.0), ("exp", 0.3),
                                          ("heat_bath", -0.2)])
@pytest.mark.parametrize("size,bs", [((64, 64), 16), ((32, 96), 16)])
def test_chain_equals_roll_oracle(accept, field, size, bs):
    cfg = sampler.ChainConfig(beta=0.44, n_sweeps=4, block_size=bs,
                              accept=accept, field=field)
    quads = sampler.init_state(jax.random.PRNGKey(1), *size)
    final, m_t, e_t = sampler.run_chain(quads, KEY, cfg)
    want, want_m, want_e = oracle_chain(quads, KEY, cfg)
    assert same_bits(final, want)
    assert same_bits(m_t, want_m)
    assert same_bits(e_t, want_e)


@pytest.mark.parametrize("accept", ["lut", "heat_bath"])
def test_run_sweeps_equals_run_chain(accept):
    cfg = sampler.ChainConfig(beta=0.5, n_sweeps=6, block_size=16,
                              accept=accept)
    quads = sampler.init_state(jax.random.PRNGKey(2), 64, 128)
    final, _, _ = sampler.run_chain(quads, KEY, cfg)
    assert same_bits(sampler.run_sweeps(quads, KEY, cfg), final)


def test_batched_chains_equal_single_chains():
    cfg = sampler.ChainConfig(beta=0.44, n_sweeps=3, block_size=16)
    batch = jnp.stack([sampler.init_state(jax.random.PRNGKey(i), 64, 64)
                       for i in range(3)])
    finals, m_b, e_b = sampler.run_chains_batched(batch, KEY, cfg)
    for n in range(3):
        final, m_t, e_t = sampler.run_chain(
            batch[n], jax.random.fold_in(KEY, n), cfg)
        assert same_bits(finals[n], final)
        assert same_bits(m_b[n], m_t) and same_bits(e_b[n], e_t)


@pytest.mark.parametrize("accept,field", [("lut", 0.0), ("heat_bath", 0.0),
                                          ("exp", 0.25)])
def test_sweep_compact_wrapper_equals_per_colour_updates(accept, field):
    """sweep_compact (block once, sweep, unblock once) against its
    per-colour form from update_color_compact; sweep_compact_measured
    against both."""
    quads = sampler.init_state(jax.random.PRNGKey(3), 64, 64)
    probs = sampler.sweep_probs(KEY, 9, (32, 32), jnp.float32)
    want = cb.update_color_compact(quads, probs[0], probs[1], 0.44, 0, 16,
                                   accept, field=field)
    want = cb.update_color_compact(want, probs[2], probs[3], 0.44, 1, 16,
                                   accept, field=field)
    got = cb.sweep_compact(quads, probs, 0.44, 16, accept, field=field)
    assert same_bits(got, want)
    measured, (m, e) = measure.sweep_compact_measured(
        quads, probs, 0.44, 16, accept, field=field)
    assert same_bits(measured, want)
    assert float(m) == float(jnp.mean(want.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# Structure: no relayout inside the compiled loop
# ---------------------------------------------------------------------------

BLOCK_PERM = "dimensions={0,2,1,3}"


def hlo_computations(text):
    """{name: body text} of an HLO module's computations, and the entry's
    name."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif name is not None:
            comps[name].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}, entry


def reachable(comps, roots):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        todo += re.findall(r"(?:to_apply|body|condition|calls)=%?([\w.\-]+)",
                           comps[c])
    return seen


def lattice_transposes(text, sites):
    """Transposes whose result has ``sites`` elements: (permutation, ...)."""
    out = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* transpose\([^)]*\), "
                         r"(dimensions=\{[\d,]*\})", text):
        if int(np.prod([int(d) for d in m.group(1).split(",") if d])) \
                == sites:
            out.append(m.group(2))
    return out


def loop_computations(impl, quad=(256, 256)):
    """The HLO computations of the chain lowered at 2 * quad for bs = 128,
    those its chunk loop runs, and the entry's name."""
    cfg = sampler.ChainConfig(beta=0.44, n_sweeps=50)
    low = impl.lower(jax.ShapeDtypeStruct((4,) + quad, jnp.bfloat16),
                     jax.ShapeDtypeStruct((2,), jnp.uint32), cfg)
    comps, entry = hlo_computations(low.as_text(dialect="hlo"))
    bodies = re.findall(r"while\([^)]*\), condition=%?([\w.\-]+), "
                        r"body=%?([\w.\-]+)", comps[entry])
    assert len(bodies) == 1, bodies
    return comps, reachable(comps, bodies[0]), entry


def loop_and_edge_transposes(impl, quad=(256, 256)):
    """Lattice-sized transposes inside the chunk's loop body and in the
    entry computation, of the 512^2 chain lowered for bs = 128."""
    comps, inside, entry = loop_computations(impl, quad)
    sites = quad[0] * quad[1]
    loop = [p for c in inside for p in lattice_transposes(comps[c], sites)]
    edge = lattice_transposes(comps[entry], sites)
    return loop, edge


@pytest.mark.parametrize("impl", [sampler._run_chain_impl,
                                  sampler._run_sweeps_impl],
                         ids=["run_chain", "run_sweeps"])
def test_no_relayout_inside_the_chunk_loop(impl):
    """A loop that relayouts every colour holds 14 block and unblock
    transposes per sweep in this HLO besides the 4 of the ``kh @ x``
    einsums; only the einsums' (last two axes swapped) may stay. The entry
    computation blocks and unblocks the 4 quads once each."""
    loop, edge = loop_and_edge_transposes(impl)
    assert BLOCK_PERM not in loop, loop
    assert len(loop) == 4, loop
    assert edge == [BLOCK_PERM] * 8, edge


@pytest.mark.parametrize("impl", [sampler._run_chain_impl,
                                  sampler._run_sweeps_impl],
                         ids=["run_chain", "run_sweeps"])
def test_chain_rounds_its_uniforms_by_integer_ops(impl):
    """The loop's 4 uniform planes reach the bf16 compare as bitcasts of
    integer-rounded bits (``update_rules.uniforms_in`` in
    ``update_color_blocked``): the draw fuses into the flip, and a TPU
    fusion may skip the rounding of a float convert to bf16."""
    comps, inside, _ = loop_computations(impl)
    text = "\n".join(comps[c] for c in inside)
    casts = re.findall(r"= bf16\[(\d+),(\d+),128,128\]\S* bitcast-convert\(",
                       text)
    assert casts == [("2", "2")] * 4, casts
