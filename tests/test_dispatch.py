"""The dispatch-layer contract: one qgemm entry point, every operating point.

Guarantees the OperatingPoint redesign must keep forever:
  1. jnp and Pallas backends agree for EVERY registered cell — including
     bias fusion, the expert axis, and the mixed w/a + int4 cells — because
     they share one activation-prep and one requant implementation per cell.
  2. every cell is BIT-exact against a dequantize-then-fp32 reference oracle
     built only from the `core.pack` codec contract (hypothesis property:
     the integer dot of the stored codes times the stored scales IS the
     output, to bf16 resolution, on both backends, with bias and experts).
  3. every operating point the POLICIES table can produce resolves to a
     registered cell — the sweep is REGENERATED from
     `precision.policy_operating_points()`, so adding a policy without a
     kernel is a test failure, not a runtime KeyError.
  4. the OperatingPoint/TuneTable API invariants: registry keys are
     structured, lookup failures suggest the nearest cell, tune tables
     round-trip through JSON, and a point that contradicts the layer's
     policy assignment is rejected loudly.

Row-parallel/column-parallel TP exactness for every cell (including the new
ones — the sweep is registry-driven) lives in tests/test_dispatch_tp.py.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import pack, qlinear
from repro.core.precision import (LayerQuant, POLICIES,
                                  policy_operating_points)
from repro.core.quantize import QuantSpec, int8_codes
from repro.kernels import dispatch, harness
from repro.kernels.dispatch import OperatingPoint, Tile, TuneTable

CELLS = sorted(dispatch.cells())
NEW_CELLS = [k for k in CELLS
             if k[:2] in (("ternary", "int8"), ("int4", "int8"),
                          ("int4", "none"))]


def _spec(wprec, aprec, *, bias=False, experts=0, k=64, n=32):
    return qlinear.QLinearSpec(
        k, n, LayerQuant(QuantSpec(wprec), QuantSpec(aprec)),
        use_bias=bias, experts=experts)


def _packed(spec, seed=0):
    p = qlinear.init(jax.random.PRNGKey(seed), spec)
    if spec.use_bias:
        p["b"] = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                   p["b"].shape) * 0.1
    return qlinear.pack_params(p, spec)


def _op(spec, impl, backend="jnp"):
    return OperatingPoint.for_spec(spec, impl=impl, backend=backend)


def _impl_arg(impl):
    return "popcount" if impl == "*" else impl


# ---------------------------------------------------------------------------
# 1. jnp-vs-pallas equivalence, all cells × bias × experts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wprec,aprec,impl", CELLS)
@pytest.mark.parametrize("bias", [False, True])
def test_qgemm_backends_agree(wprec, aprec, impl, bias):
    spec = _spec(wprec, aprec, bias=bias)
    p = _packed(spec)
    x = jax.random.normal(jax.random.PRNGKey(2), (5, spec.in_dim)) * 0.2
    yj = dispatch.qgemm(p, x, spec, _op(spec, _impl_arg(impl)))
    yp = dispatch.qgemm(p, x, spec, _op(spec, _impl_arg(impl), "pallas"))
    assert yj.shape == yp.shape == (5, spec.out_dim)
    np.testing.assert_allclose(np.asarray(yj, np.float32),
                               np.asarray(yp, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("wprec,aprec,impl", CELLS)
def test_qgemm_expert_axis(wprec, aprec, impl):
    spec = _spec(wprec, aprec, bias=True, experts=3)
    p = _packed(spec)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 4, spec.in_dim)) * 0.2
    yj = dispatch.qgemm(p, x, spec, _op(spec, _impl_arg(impl)))
    yp = dispatch.qgemm(p, x, spec, _op(spec, _impl_arg(impl), "pallas"))
    assert yj.shape == yp.shape == (3, 4, spec.out_dim)
    np.testing.assert_allclose(np.asarray(yj, np.float32),
                               np.asarray(yp, np.float32),
                               rtol=2e-2, atol=2e-2)
    # expert slices differ (the vmap really maps the weights)
    y0, y1 = np.asarray(yj, np.float32)[0], np.asarray(yj, np.float32)[1]
    assert np.abs(y0 - y1).max() > 0


def test_qgemm_bias_fused_matches_manual():
    """Bias must land inside the requant epilogue, not as a post-hoc add in a
    different dtype — fused-vs-manual must agree to bf16 resolution."""
    spec = _spec("int8", "int8", bias=True)
    p = _packed(spec)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, spec.in_dim)) * 0.2
    for backend in ("jnp", "pallas"):
        op = _op(spec, "popcount", backend)
        y = dispatch.qgemm(p, x, spec, op)
        p_nob = {k: v for k, v in p.items() if k != "b"}
        y_nob = dispatch.qgemm(p_nob, x, spec, op)
        manual = np.asarray(y_nob, np.float32) + np.asarray(p["b"], np.float32)
        np.testing.assert_allclose(np.asarray(y, np.float32), manual,
                                   rtol=2e-2, atol=2e-2)


def test_qgemm_nonaligned_rows_padded():
    """M not a sublane multiple: dispatch pads, runs, unpads."""
    spec = _spec("binary", "binary")
    p = _packed(spec)
    for m in (1, 3, 7, 13):
        x = jax.random.normal(jax.random.PRNGKey(m), (m, spec.in_dim)) * 0.2
        yj = dispatch.qgemm(p, x, spec, _op(spec, "popcount"))
        yp = dispatch.qgemm(p, x, spec, _op(spec, "popcount", "pallas"))
        assert yj.shape == yp.shape == (m, spec.out_dim)
        np.testing.assert_allclose(np.asarray(yj, np.float32),
                                   np.asarray(yp, np.float32),
                                   rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# 2. dequantize-then-fp32 oracle: stored codes × stored scales == the output
# ---------------------------------------------------------------------------

def _dequant_codes_w(p, spec):
    """(N, K) integer/trit weight codes straight from the packed storage —
    decoded ONLY via the `core.pack` codec contract, no dispatch code."""
    k = spec.in_dim
    wprec = spec.lq.weights.precision
    if wprec == "binary":
        return pack.unpack_binary(p["w_packed"], k)
    if wprec == "ternary":
        return pack.unpack_ternary(p["w_mask"], p["w_sign"], k)
    if wprec == "int4":
        return pack.unpack_int4_i8(p["w_q4"], k).astype(jnp.float32)
    if wprec == "int8":
        return jnp.swapaxes(p["w_q"], -1, -2).astype(jnp.float32)
    return jnp.swapaxes(p["w"], -1, -2).astype(jnp.float32)


def _quant_codes_x(p, x2d, spec):
    """Activation codes + per-row scale exactly as the serve prep defines
    them (the codec is the contract; the arithmetic below is independent)."""
    from repro.core.quantize import ternarize
    aprec = spec.lq.acts.precision
    xf = x2d.astype(jnp.float32)
    if aprec == "binary":
        return jnp.where(xf >= 0, 1.0, -1.0), jnp.mean(jnp.abs(xf), axis=-1)
    if aprec == "ternary":
        q = ternarize(xf, spec.lq.acts.ternary_threshold, axis=-1)
        return jax.lax.stop_gradient(q), jnp.mean(jnp.abs(xf), axis=-1)
    if aprec == "int8":
        a = p["a_scale"]
        return int8_codes(xf, a).astype(jnp.float32), \
            jnp.full((x2d.shape[0],), a, jnp.float32)
    return None, None   # "none": bf16 activations, handled separately


@jax.jit
def _wide_epilogue(acc, ws, asc, bias):
    """acc -> scales -> bias -> bf16, jitted like the kernels so that
    XLA:CPU contracts the bias epilogue into one fused multiply-add on both
    sides (see kernels/ref.py)."""
    y = acc.astype(jnp.float32)
    if ws is not None:
        y = y * ws[None, :]
    y = y * asc[:, None]
    if bias is not None:
        y = y + bias[None, :]
    return y.astype(jnp.bfloat16)


def _oracle(p, x2d, spec):
    """Dequantize-then-fp32 reference, factored so every float product is
    exact: integer-code dot (exact in f32 at these ranges) -> scales ->
    bias -> bf16. Must match qgemm BIT for bit."""
    wq = _dequant_codes_w(p, spec)
    xq, asc = _quant_codes_x(p, x2d, spec)
    ws, bias = p.get("w_scale"), p.get("b")
    if xq is not None:                      # W&A cell: wide f32 requant
        return _wide_epilogue(xq @ wq.T, ws, asc, bias)
    # weight-only cell: bf16 accumulate, bf16 scale, f32 bias
    acc = x2d.astype(jnp.bfloat16) @ wq.astype(jnp.bfloat16).T
    y = acc if ws is None else acc * ws.astype(acc.dtype)
    if bias is not None:
        y = y.astype(jnp.float32) + bias[None, :]
    return y.astype(jnp.bfloat16)


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(NEW_CELLS), st.booleans(), st.sampled_from([0, 2]),
       st.sampled_from([64, 96, 128]), st.integers(1, 9),
       st.sampled_from(["jnp", "pallas"]), st.integers(0, 10))
def test_new_cells_bit_exact_vs_dequant_oracle(cellkey, bias, experts, k, m,
                                               backend, seed):
    """Hypothesis property: the mixed w-ternary×a-int8 and int4 cells are
    BIT-exact against the dequantize-then-fp32 oracle on both backends,
    including bias and the expert axis."""
    wprec, aprec, impl = cellkey
    spec = _spec(wprec, aprec, bias=bias, experts=experts, k=k)
    p = _packed(spec, seed=seed)
    shape = (experts, m, k) if experts else (m, k)
    x = jax.random.normal(jax.random.PRNGKey(seed + m), shape) * 0.2
    y = dispatch.qgemm(p, x, spec, _op(spec, _impl_arg(impl), backend))
    if experts:
        want = jnp.stack([
            _oracle({nm: (v if v.ndim == 0 or nm == "a_scale" else v[e])
                     for nm, v in p.items()}, x[e],
                    dataclasses.replace(spec, experts=0))
            for e in range(experts)])
    else:
        want = _oracle(p, x, spec)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(want, np.float32),
        err_msg=str((cellkey, bias, experts, k, m, backend, seed)))


@pytest.mark.parametrize("wprec,aprec,impl", CELLS)
def test_all_cells_match_dequant_oracle(wprec, aprec, impl):
    """The same oracle, every registered cell once (deterministic sweep)."""
    spec = _spec(wprec, aprec, bias=True)
    p = _packed(spec)
    x = jax.random.normal(jax.random.PRNGKey(5), (8, spec.in_dim)) * 0.2
    y = dispatch.qgemm(p, x, spec, _op(spec, _impl_arg(impl)))
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(_oracle(p, x, spec), np.float32),
                                  err_msg=str((wprec, aprec, impl)))


@settings(max_examples=16, deadline=None)
@given(st.sampled_from([("int4", "int8"), ("int8", "int8")]),
       st.integers(1, 8), st.sampled_from(["jnp", "pallas"]),
       st.integers(0, 5))
def test_plane_truncation_matches_snapped_code_oracle(pair, keep, backend,
                                                      seed):
    """OperatingPoint.planes truncation (the self-speculative draft's
    contract): running a plane cell on its P leading MSB planes with the
    ORIGINAL coefficients is bit-identical to the full fp32 oracle over
    floor-snapped codes floor(c / 2^(b-P)) * 2^(b-P) — and the full-depth
    stack is bit-identical to the formulation-agnostic direct cell."""
    wprec, aprec = pair
    bits = pack.PLANE_BITS[wprec]
    keep = min(keep, bits)
    spec = _spec(wprec, aprec)
    p = _packed(spec, seed=seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 11),
                          (4, spec.in_dim)) * 0.2
    op = dataclasses.replace(_op(spec, "planes", backend), planes=keep)
    y = dispatch.qgemm(p, x, spec, op)
    codes = np.asarray(pack.unpack_planes_i8(
        p["w_planes"], spec.in_dim, bits)).astype(np.int32)
    snapped = (codes >> (bits - keep)) << (bits - keep)      # (N, K) floor
    xq, asc = _quant_codes_x(p, x, spec)
    want = (xq @ jnp.asarray(snapped, jnp.float32).T
            ).astype(jnp.float32) * p["w_scale"][None, :] * asc[:, None]
    np.testing.assert_array_equal(
        np.asarray(y, np.float32),
        np.asarray(want.astype(jnp.bfloat16), np.float32),
        err_msg=str((pair, keep, backend, seed)))
    if keep == bits:
        direct = dispatch.qgemm(p, x, spec, _op(spec, "popcount", backend))
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(direct, np.float32))


# ---------------------------------------------------------------------------
# 3. registry completeness — regenerated from the POLICIES table
# ---------------------------------------------------------------------------

def test_every_policy_operating_point_resolves():
    """Every (wprec, aprec) pair any policy can assign to any layer class —
    `policy_operating_points()` regenerates the list, so a new POLICIES
    entry automatically extends this obligation — resolves under both
    formulations, and every W&A cell carries a Pallas body."""
    pts = policy_operating_points()
    assert ("ternary", "int8") in pts and ("int4", "int8") in pts  # new cells
    seen = set()
    for wprec, aprec in pts:
        for impl in ("popcount", "mxu"):
            cell = dispatch.lookup(wprec, aprec, impl)
            seen.add(cell.key)
    for key, cell in dispatch.cells().items():
        if cell.aprec != "none":
            assert cell.body is not None, key
    assert seen  # sanity: the sweep visited the registry


def test_policies_cover_every_registered_cell():
    """The converse: no registry cell is policy-unreachable (dead kernels
    rot — every cell must be nameable by some POLICIES entry)."""
    pts = policy_operating_points()
    for key, cell in dispatch.cells().items():
        assert (cell.wprec, cell.aprec) in pts, key


def test_unknown_operating_point_raises_with_suggestion():
    with pytest.raises(KeyError, match="no GEMM registered") as ei:
        dispatch.lookup("int4", "int4", "popcount")
    # wildcard-aware nearest-cell suggestion, not a raw registry dump
    assert "nearest registered cell" in str(ei.value)
    assert "wprec='int4'" in str(ei.value)
    assert "--list" in str(ei.value)


def test_duplicate_registration_rejected():
    cell = dispatch.lookup("binary", "binary", "popcount")
    with pytest.raises(ValueError, match="duplicate"):
        dispatch.register(cell)


def test_vmem_tile_model_within_budget():
    """Every registered Pallas body fits VMEM at its tuned/default tile."""
    tune = dispatch.default_tune()
    for key, cell in dispatch.cells().items():
        if cell.body is None:
            continue
        tile = tune.tile_for(cell.op)
        assert harness.vmem_tile_bytes(cell.body, tile) < 16 * 2**20, key


# ---------------------------------------------------------------------------
# 4. OperatingPoint / TuneTable API invariants
# ---------------------------------------------------------------------------

def test_operating_point_mismatch_rejected():
    """An op whose precisions contradict the layer's policy assignment is a
    loud error — per-layer resolution may never silently run the wrong cell."""
    spec = _spec("ternary", "int8")
    p = _packed(spec)
    x = jnp.zeros((2, spec.in_dim))
    with pytest.raises(ValueError, match="does not match"):
        dispatch.qgemm(p, x, spec, OperatingPoint("int8", "int8"))


def test_legacy_kwargs_still_resolve():
    """Out-of-tree form: qgemm(..., impl=, backend=) == the op form."""
    spec = _spec("ternary", "ternary")
    p = _packed(spec)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, spec.in_dim)) * 0.2
    a = dispatch.qgemm(p, x, spec, impl="mxu", backend="pallas")
    b = dispatch.qgemm(p, x, spec,
                       OperatingPoint.for_spec(spec, impl="mxu",
                                               backend="pallas"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="not both"):
        dispatch.qgemm(p, x, spec, OperatingPoint.for_spec(spec), impl="mxu")


def test_operating_point_validates_backend():
    with pytest.raises(ValueError, match="backend"):
        OperatingPoint("int8", "int8", backend="tpu")


def test_tile_override_changes_blocks_not_results():
    """An explicit OperatingPoint tile is honored (block-size invariance of
    the harness) and the TuneTable default gives identical values."""
    spec = _spec("binary", "binary", k=128, n=64)
    p = _packed(spec)
    x = jax.random.normal(jax.random.PRNGKey(7), (8, spec.in_dim)) * 0.2
    base = dispatch.qgemm(p, x, spec, _op(spec, "popcount", "pallas"))
    tiled = dispatch.qgemm(
        p, x, spec, dataclasses.replace(_op(spec, "popcount", "pallas"),
                                        tile=Tile(bm=8, bn=32, bkq=1)))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tiled))


def test_tune_table_roundtrip(tmp_path):
    t = TuneTable(tiles={("binary", "binary", "popcount"): Tile(64, 128, 8),
                         ("int4", "int8", "*"): Tile(128, 128, 32)},
                  source="unit test")
    path = str(tmp_path / "tune.json")
    t.save(path)
    back = TuneTable.load(path)
    assert back.tiles == dict(t.tiles) and back.source == "unit test"
    # wildcard-aware resolution, same fallback as lookup()
    assert back.tile_for(OperatingPoint("int4", "int8", "mxu")) == \
        Tile(128, 128, 32)
    assert back.tile_for(OperatingPoint("none", "none")) is None
    with open(path) as f:
        assert set(json.load(f)) == {"source", "cells"}


def test_exact_key_beats_wildcard_regardless_of_order(tmp_path):
    """Precedence pin: an exact (wprec, aprec, impl) row wins over the
    (wprec, aprec, '*') wildcard in BOTH lookup() and TuneTable.tile_for,
    independent of JSON/registration order. The plane-composed cells coexist
    with the formulation-agnostic int4/int8 wildcard cell exactly because of
    this rule — a regression here silently reroutes --impl planes to the
    dense-code cell."""
    # registry side: the exact planes cell resolves, other impls hit '*'
    planes = dispatch.lookup("int4", "int8", "planes")
    assert planes.key == ("int4", "int8", "planes")
    assert "w_planes" in planes.weight_names
    assert dispatch.lookup("int4", "int8", "mxu").key == ("int4", "int8", "*")
    assert dispatch.lookup("int8", "int8", "planes").key == \
        ("int8", "int8", "planes")
    # tune-table side: exact-over-wildcard for either insertion order
    rows = {"int4/int8/*": {"bm": 128, "bn": 128, "bkq": 64},
            "int4/int8/planes": {"bm": 32, "bn": 32, "bkq": 8}}
    for name, order in (("wild_first", list(rows)),
                        ("exact_first", list(rows)[::-1])):
        path = str(tmp_path / f"{name}.json")
        with open(path, "w") as f:
            json.dump({"source": name,
                       "cells": {k: rows[k] for k in order}}, f)
        tune = TuneTable.load(path)
        assert tune.tile_for(OperatingPoint("int4", "int8", "planes")) == \
            Tile(32, 32, 8), name
        assert tune.tile_for(OperatingPoint("int4", "int8", "popcount")) == \
            Tile(128, 128, 64), name
    # the shipped table pins the plane cells explicitly
    shipped = dispatch.default_tune()
    for key in (("int4", "int8", "planes"), ("int8", "int8", "planes")):
        assert key in shipped.tiles, key


def test_shipped_tune_table_keys_are_registered():
    """The in-repo CPU table may only name live registry cells (a retune
    after a registry change must not leave stale keys behind) — plus the one
    non-GEMM pseudo-cell, the paged-attention decode kernel's pages-per-block
    Tile (kernels/paged_attn.TUNE_KEY)."""
    from repro.kernels.paged_attn import TUNE_KEY
    tune = dispatch.default_tune()
    assert tune.tiles, "shipped tune_cpu.json missing or empty"
    for key in tune.tiles:
        assert key in dispatch.cells() or key == TUNE_KEY, key
    assert TUNE_KEY in tune.tiles, "paged-attn Tile missing from shipped table"


def test_prune_stale_tiles_drops_unresolvable_keys():
    """`kernel_bench --retune` prunes tune-table rows no registered cell can
    resolve (renamed impl, retired precision pair) while keeping every live
    row, the `(w, a, "*")` wildcards of registered pairs, and the paged-attn
    pseudo-cell."""
    from repro.kernels.paged_attn import TUNE_KEY
    tune = dispatch.default_tune()
    stale = {("int3", "int8", "*"): Tile(64, 64, 16),
             ("binary", "binary", "gone-impl"): Tile(128, 128, 8)}
    wild = ("binary", "binary", "*")          # registered pair: must survive
    assert wild in dispatch.valid_tune_keys()
    tiles = {**tune.tiles, **stale, wild: Tile(64, 64, 8)}
    kept, dropped = dispatch.prune_stale_tiles(tiles, extra_keys=(TUNE_KEY,))
    assert dropped == sorted(stale)
    assert set(kept) == set(tune.tiles) | {wild}
    # without the extra key, the pseudo-cell row is pruned too (the prune is
    # exactly as permissive as its caller declares)
    kept2, dropped2 = dispatch.prune_stale_tiles(tune.tiles)
    assert TUNE_KEY in dropped2 and TUNE_KEY not in kept2


def test_registry_table_renders():
    table = dispatch.registry_table()
    assert "wprec" in table and "int4" in table and "w_q4" in table
