"""Roofline analysis substrate: the HLO text cost model against programs
with known costs, and the collective parser against sharded programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo as H
from repro.analysis import hlo_cost as HC
from repro.analysis import roofline as RL


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_matmul_flops_counted():
    m, k, n = 128, 256, 64
    a = jnp.zeros((m, k), jnp.float32)
    b = jnp.zeros((k, n), jnp.float32)
    c = _compiled(lambda x, y: x @ y, a, b)
    r = HC.analyze(c.as_text())
    want = 2 * m * k * n
    assert abs(r["flops"] - want) / want < 0.05


def test_while_loop_trip_count_multiplies():
    """A scan of T matmuls must cost ~T x one matmul (cost_analysis counts
    the body once — the whole reason hlo_cost exists)."""
    a = jnp.zeros((128, 128), jnp.float32)

    def once(x):
        return x @ x

    def scanned(x):
        def body(carry, _):
            return carry @ carry, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    f1 = HC.analyze(_compiled(once, a).as_text())["flops"]
    f10 = HC.analyze(_compiled(scanned, a).as_text())["flops"]
    assert 8 <= f10 / f1 <= 12


def test_elementwise_bytes_reasonable():
    x = jnp.zeros((1 << 20,), jnp.float32)  # 4 MB
    c = _compiled(lambda v: v * 2.0 + 1.0, x)
    r = HC.analyze(c.as_text())
    # read 4 MB + write 4 MB, fusion keeps intermediates in registers
    assert 7e6 < r["bytes"] < 20e6


def test_collective_parse_all_reduce(subproc):
    out = subproc("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis import hlo as H
    mesh = jax.make_mesh((4,), ("d",))
    x = jax.ShapeDtypeStruct((1024, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P("d", None)))

    def f(v):
        return jnp.sum(v * v)  # cross-shard sum -> all-reduce

    c = jax.jit(f).lower(x).compile()
    s = H.collective_summary(c.as_text(), 4)
    assert s["count"] >= 1, c.as_text()
    assert "all-reduce" in s["by_kind"], s
    print("COLL_OK", s["by_kind"])
    """, devices=4)
    assert "COLL_OK" in out


def test_ppermute_wire_bytes(subproc):
    """collective-permute moves exactly the operand bytes per device."""
    out = subproc("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.analysis import hlo as H
    from jax import shard_map
    mesh = jax.make_mesh((4,), ("d",))

    def f(x):
        return jax.lax.ppermute(x, "d", [(i, (i + 1) % 4) for i in range(4)])

    m = shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
                  check_vma=False)
    x = jnp.zeros((4 * 1024, 128), jnp.float32)   # 512 KB/device shard
    c = jax.jit(m).lower(x).compile()
    s = H.collective_summary(c.as_text(), 4)
    per_dev = 1024 * 128 * 4
    assert "collective-permute" in s["by_kind"]
    got = s["by_kind"]["collective-permute"]
    assert abs(got - per_dev) / per_dev < 0.05, (got, per_dev)
    print("PPERM_OK")
    """, devices=4)
    assert "PPERM_OK" in out


def test_ring_cost_formulas():
    c = H.Collective("all-reduce", result_bytes=1000, operand_bytes=1000,
                     group_size=4)
    assert c.wire_bytes == pytest.approx(2 * 3 / 4 * 1000)
    c = H.Collective("all-gather", 4000, 1000, 4)
    assert c.wire_bytes == pytest.approx(3 / 4 * 4000)
    c = H.Collective("reduce-scatter", 1000, 4000, 4)
    assert c.wire_bytes == pytest.approx(3 / 4 * 4000)
    c = H.Collective("collective-permute", 1000, 1000, 4)
    assert c.wire_bytes == 1000.0
    c = H.Collective("all-reduce", 1000, 1000, 1)
    assert c.wire_bytes == 0.0


def test_roofline_terms_and_dominant():
    r = RL.Roofline(compute_s=1.0, memory_s=2.0, collective_s=0.5,
                    flops_per_device=RL.PEAK_FLOPS,
                    hbm_bytes_per_device=2 * RL.HBM_BW,
                    wire_bytes_per_device=0.5 * RL.ICI_BW,
                    model_flops=RL.PEAK_FLOPS / 2, n_devices=1)
    assert r.dominant == "memory"
    assert r.step_time_s == 2.0
    assert r.useful_flop_ratio == pytest.approx(0.5)
    assert r.mfu == pytest.approx(0.25)


def test_lm_model_flops_train_vs_decode():
    from repro.configs import get_config
    from repro.configs.base import LM_SHAPES
    cfg = get_config("qwen3-0.6b")
    train = RL.lm_model_flops(cfg, LM_SHAPES["train_4k"])
    decode = RL.lm_model_flops(cfg, LM_SHAPES["decode_32k"])
    # train: 6ND over 1M tokens; decode: 2ND over 128 tokens
    assert train > 1000 * decode
    n = cfg.param_count()
    toks = 4096 * 256
    assert train > 6 * n * toks  # attention term adds on top


def test_ising_model_flops_scale():
    f1 = RL.ising_model_flops(2, 2, 128, 1)
    f4 = RL.ising_model_flops(2, 2, 128, 4)
    assert f4 == 4 * f1
    assert f1 == 10.0 * 4 * 2 * 2 * 128 * 128
