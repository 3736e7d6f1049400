"""Sharding engine: logical-axis resolution, divisibility fallback, FSDP
rules, and activation hints. Uses AbstractMesh (no devices needed) for spec
resolution; device-level placement is covered by the dry-run tests."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from jax.sharding import AbstractMesh
from repro.distributed import sharding as SH


def _mesh(shape=(2, 16, 16), axes=("pod", "data", "model")):
    return AbstractMesh(shape, axes)


def test_basic_resolution():
    mesh = _mesh()
    spec = SH.resolve_spec(mesh, ("embed", "heads", "head"), (2560, 32, 128))
    assert spec == P(None, "model", None)


def test_batch_uses_pod_and_data():
    mesh = _mesh()
    spec = SH.resolve_spec(mesh, ("batch", "seq"), (256, 4096))
    assert spec == P(("pod", "data"), None)


def test_divisibility_fallback_heads():
    """llama4: 40 heads don't divide 16 -> replicated, not an error."""
    mesh = _mesh()
    spec = SH.resolve_spec(mesh, ("embed", "heads", "head"), (5120, 40, 128))
    assert spec == P(None, None, None)


def test_divisibility_fallback_partial_batch():
    """global_batch=1 (long_500k): batch can't shard anywhere."""
    mesh = _mesh()
    spec = SH.resolve_spec(mesh, ("batch", None), (1, 1))
    assert spec == P(None, None)


def test_no_mesh_axis_reuse_within_tensor():
    """Two dims must not claim the same mesh axis (invalid PartitionSpec)."""
    mesh = _mesh()
    spec = SH.resolve_spec(mesh, ("vocab", "ffn"), (151936, 9728))
    axes = [a for a in spec if a is not None]
    assert len(axes) == len(set(axes)) == 1  # only one gets "model"


def test_fsdp_rules_shard_embed_over_data():
    mesh = _mesh()
    spec = SH.resolve_spec(mesh, ("embed", "ffn"), (7168, 2048),
                           SH.FSDP_RULES)
    assert spec == P("data", "model")


def test_fsdp_ffn_falls_back_to_data():
    """If d_ff doesn't divide model(16) but divides data, FSDP rules allow
    the secondary candidate."""
    mesh = _mesh()
    spec = SH.resolve_spec(mesh, (None, "ffn"), (4, 24),
                           SH.FSDP_RULES)
    # 24 % 16 != 0 -> falls to ("data",): 24 % 16... also fails; stays None
    assert spec == P(None, None)
    spec = SH.resolve_spec(mesh, (None, "ffn"), (4, 32), SH.FSDP_RULES)
    assert spec == P(None, "model")  # 32 % 16 == 0 -> primary wins


def test_single_pod_mesh_has_no_pod_axis():
    mesh = _mesh((16, 16), ("data", "model"))
    spec = SH.resolve_spec(mesh, ("batch", "seq"), (256, 4096))
    assert spec == P("data", None)


def test_resolve_tree_mixed_leaves():
    mesh = _mesh((4, 2), ("data", "model"))
    params = {"w": jnp.zeros((8, 6)), "b": jnp.zeros((3,))}
    specs = {"w": ("embed", "ffn"), "b": (None,)}
    tree = SH.resolve_tree(mesh, specs, params)
    assert tree["w"].spec == P(None, "model")
    assert tree["b"].spec == P(None)


def test_shard_hint_noop_outside_context():
    x = jnp.ones((4, 4))
    y = SH.shard_hint(x, ("batch", "embed"))
    assert y is x


def test_all_arch_params_resolve_on_production_mesh():
    """Every param of every FULL arch must resolve to a valid spec on the
    production meshes (divisibility respected) without raising."""
    from repro.configs import get_config, list_configs
    from repro.models import transformer

    mesh = _mesh()
    for arch in list_configs():
        cfg = get_config(arch)
        rules = SH.FSDP_RULES if cfg.fsdp else SH.DEFAULT_RULES

        box = {}

        def go(key):
            params, specs = transformer.init_model(key, cfg)
            box["specs"] = specs
            return params

        params = jax.eval_shape(go, jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = box["specs"]
        # resolve every leaf; raises if a spec is malformed
        def one(dims, leaf):
            if dims is None:
                return P()
            return SH.resolve_spec(mesh, tuple(dims), leaf.shape, rules)
        tree = jax.tree.map(one, specs, params,
                            is_leaf=lambda x: isinstance(x, tuple) or x is None)
        for spec, leaf in zip(jax.tree.leaves(tree, is_leaf=lambda s: isinstance(s, P)),
                              jax.tree.leaves(params)):
            used = [a for a in spec if a is not None]
            flat = []
            for a in used:
                flat.extend(a if isinstance(a, tuple) else (a,))
            assert len(flat) == len(set(flat)), (arch, spec)
            # sharded dims divide the axis product
            for dim_axes, size in zip(spec, leaf.shape):
                if dim_axes is None:
                    continue
                ax = dim_axes if isinstance(dim_axes, tuple) else (dim_axes,)
                n = 1
                for a in ax:
                    n *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
                assert size % n == 0, (arch, spec, leaf.shape)
