"""Gradient compression: quantization error bounds (property-style sweeps)
and the compressed cross-pod all-reduce."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import compression as C


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("shape", [(16,), (8, 32), (4, 8, 16)])
def test_quantize_roundtrip_error_bound(seed, shape):
    """|x - deq(q(x))| <= scale/2 per element (symmetric rounding)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    q, scale = C.quantize(x)
    back = C.dequantize(q, scale)
    err = jnp.abs(back - x)
    bound = jnp.broadcast_to(scale * 0.5 + 1e-7, x.shape)
    assert bool(jnp.all(err <= bound))


def test_quantize_payload_is_int8():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 8)) * 100
    q, scale = C.quantize(x)
    assert q.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(q))) <= 127


def test_zero_tensor_stable():
    q, scale = C.quantize(jnp.zeros((4, 4)))
    back = C.dequantize(q, scale)
    assert bool(jnp.all(back == 0))


def test_tree_roundtrip():
    tree = {"a": jnp.ones((4, 8)), "b": {"c": jnp.full((3,), -2.0)}}
    ctree = C.compress_tree(tree)
    back = C.decompress_tree(ctree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=0.05)


def test_compression_ratio():
    """int8 + per-row f32 scale: ~4x fewer bytes than f32 for wide rows."""
    x = jnp.zeros((64, 1024), jnp.float32)
    q, scale = C.quantize(x)
    ratio = x.nbytes / (q.nbytes + scale.nbytes)
    assert ratio > 3.9


def test_psum_compressed_across_pod_axis(subproc):
    """Compressed all-reduce over a 2-member axis approximates the exact
    psum within the quantization bound."""
    out = subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.distributed import compression as C
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_mesh((2, 2), ("pod", "data"))
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 16), jnp.float32)

    def f(x):
        exact = jax.lax.psum(x, "pod")
        approx = C.psum_compressed({"g": x}, "pod")["g"]
        return exact, approx

    mapped = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("pod", "data"),
        out_specs=P("pod", "data"), check_vma=False))
    exact, approx = mapped(g)
    err = float(jnp.max(jnp.abs(exact - approx)))
    scale = float(jnp.max(jnp.abs(exact)))
    assert err < 0.05 * max(scale, 1.0), (err, scale)
    print("PSUM_OK", err)
    """, devices=4)
    assert "PSUM_OK" in out
