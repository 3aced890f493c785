"""Property test: tensor-parallel qgemm == unsharded qgemm, bit for bit.

The contract under test (kernels/dispatch.py, TP section):
  * row-parallel — K-sharded packed weights, replicated full-K activation
    prep, per-shard integer partial dots, ONE int32 psum BEFORE the requant
    epilogue — must match the unsharded path exactly for every registered
    cell, including bias and the expert axis. Integer psum is associative,
    prep/requant are shared verbatim, so equality is exact, not approximate.
  * column-parallel — N-sharded weights, no collective — exact per slice.
  * non-dividing shapes (e.g. a packed K whose word count doesn't split —
    32-operand bit-plane words AND 8-nibble s4 words, via cell.k_quantum)
    and narrow-accumulator (weight-only) row cells must FALL BACK to the
    replicated path rather than shard mid-word / psum in bf16 — the property
    holds trivially there, which is exactly the point: tp_plan may never
    choose an inexact plan.

The sweep is registry-driven (sorted(dispatch.cells())), so the mixed
w-ternary×a-int8 and int4 cells are covered automatically, keyed by
OperatingPoint.

Hypothesis (or the deterministic fallback shim) draws the operating point,
bias/expert/TP-degree/K/M/backend configuration; the whole property runs in
a subprocess with --xla_force_host_platform_device_count=8 (the flag cannot
be set once jax is initialized in the main pytest process).
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8
from _hypothesis_compat import given, settings, st
from repro.core import qlinear
from repro.core.precision import LayerQuant
from repro.core.quantize import QuantSpec
from repro.kernels import dispatch
from repro.kernels.dispatch import OperatingPoint

CELLS = sorted(dispatch.cells())
from repro.launch.mesh import make_mesh
MESHES = {ns: make_mesh((8 // ns, ns), ("data", "model")) for ns in (2, 4)}
checked = [0]
sharded_plans = [0]


def build(wprec, aprec, bias, experts, k, parallel, seed=0):
    spec = qlinear.QLinearSpec(
        k, 32, LayerQuant(QuantSpec(wprec), QuantSpec(aprec)),
        use_bias=bias, experts=experts, parallel=parallel)
    p = qlinear.init(jax.random.PRNGKey(seed), spec)
    if bias:
        p["b"] = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                   p["b"].shape) * 0.1
    return spec, qlinear.pack_params(p, spec)


@settings(max_examples=24, deadline=None)
@given(st.sampled_from(CELLS), st.booleans(), st.sampled_from([0, 2]),
       st.sampled_from([2, 4]), st.sampled_from([64, 96, 128]),
       st.sampled_from(["jnp", "pallas"]), st.integers(1, 9))
def row_parallel_matches_unsharded(cellkey, bias, experts, ns, k, backend, m):
    wprec, aprec, impl = cellkey
    impl_arg = "popcount" if impl == "*" else impl
    spec, p = build(wprec, aprec, bias, experts, k, "row")
    op = OperatingPoint.for_spec(spec, impl=impl_arg, backend=backend)
    shape = (experts, m, k) if experts else (m, k)
    x = jax.random.normal(jax.random.PRNGKey(m), shape) * 0.2
    ref = dispatch.qgemm(p, x, spec, op)
    tp = dispatch.TPSpec(MESHES[ns])
    cell = dispatch.lookup(op)
    plan = dispatch.tp_plan(cell, spec, "row", tp)
    # the plan is only allowed when it can be exact: wide cells, whole
    # packed storage units (cell.k_quantum: 32-bit-plane words, s4 nibble
    # words, int8 elements) per shard
    if plan == "row":
        assert cell.wide
        assert k % (cell.k_quantum * ns) == 0
        sharded_plans[0] += 1
    y = dispatch.qgemm(p, x, spec, op, tp=tp, parallel="row")
    assert y.shape == ref.shape and y.dtype == ref.dtype
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(ref, np.float32),
        err_msg=str((cellkey, bias, experts, ns, k, backend, m, plan)))
    checked[0] += 1


row_parallel_matches_unsharded()
assert checked[0] >= 24, checked
assert sharded_plans[0] > 0, "property never exercised a sharded row plan"

# column-parallel sweep (bit-exact, no collective) — every cell once
for (wprec, aprec, impl) in CELLS:
    impl_arg = "popcount" if impl == "*" else impl
    for experts in (0, 3):
        spec, p = build(wprec, aprec, True, experts, 64, "column")
        op = OperatingPoint.for_spec(spec, impl=impl_arg)
        shape = (experts, 5, 64) if experts else (5, 64)
        x = jax.random.normal(jax.random.PRNGKey(9), shape) * 0.2
        ref = dispatch.qgemm(p, x, spec, op)
        y = dispatch.qgemm(p, x, spec, op,
                           tp=dispatch.TPSpec(MESHES[4]), parallel="column")
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(ref, np.float32),
                                      err_msg=str((wprec, aprec, impl, experts)))

print("DISPATCH_TP_OK", checked[0], sharded_plans[0])
'''


def test_row_parallel_qgemm_property():
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(
               [os.path.join(REPO, "src"), os.path.join(REPO, "tests")])}
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "DISPATCH_TP_OK" in r.stdout, r.stdout[-2000:]
