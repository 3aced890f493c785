"""QuantizedLinear — BrainTTA's vMAC as a composable JAX module.

One module covers every matmul in the model zoo (QKV/O, FFN, experts, SSM
projections, LM head). It has three execution backends:

  mode="train"  QAT: STE fake-quant of weights/activations, bf16 MXU matmul.
                This is what `train_step` lowers; the SoC does not train, a
                pod framework must (DESIGN.md §2).
  mode="serve"  packed inference: weights stored in the packed format of
                `core.pack` (32/16/8 operands per word for
                binary/ternary/int4, int8 codes for 8-bit), activations
                quantized on the fly. The layer's `dispatch.OperatingPoint`
                (`op=`) selects the registered cell and its execution:
                  impl="popcount"  paper-faithful XNOR/gated-XNOR + popcount
                                   (VPU path on TPU)
                  impl="mxu"       beyond-paper: unpack packed planes to ±1
                                   int8 *in VMEM* and use the int8 MXU path —
                                   packed HBM storage, dense-rate compute.
                  backend="pallas" runs the Pallas TPU kernels registered in
                                   `repro.kernels.dispatch` (interpret-
                                   validated on CPU); "jnp" runs the same
                                   registry's XLA formulations.
                  tile             optional harness.Tile block override
                                   (else the per-cell TuneTable).
                Weight and activation precisions may differ per layer
                (mixed w/a cells — see docs/DISPATCH.md).

Weight layout (train): w[in, out] (+ optional expert axis in front).
Weight layout (serve): precision-dependent, produced by `pack_params`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import pack
from .precision import LayerQuant
from .quantize import (QuantSpec, binarize, fake_quant, int4_codes,
                       int4_scale, int8_codes, int8_scale, ternarize)

Params = dict[str, jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class QLinearSpec:
    in_dim: int
    out_dim: int
    lq: LayerQuant = LayerQuant()
    use_bias: bool = False
    experts: int = 0           # 0 = dense; >0 = leading expert axis on weights
    name: str = "qlinear"
    #: tensor-parallel role of this layer on a ("data","model") mesh
    #: (Megatron pairing): "column" = out-dim sharded, no collective;
    #: "row" = packed-K sharded, one pre-requant int32 psum; "none" =
    #: replicated. Only consulted when the caller threads a TPSpec (serve
    #: mesh mode); train and single-device serve ignore it.
    parallel: str = "none"


# ---------------------------------------------------------------------------
# init (train layout)
# ---------------------------------------------------------------------------

def init(rng: jax.Array, spec: QLinearSpec, dtype=jnp.float32) -> Params:
    shape = (spec.in_dim, spec.out_dim)
    if spec.experts:
        shape = (spec.experts,) + shape
    scale = 1.0 / (spec.in_dim ** 0.5)
    p: Params = {"w": jax.random.normal(rng, shape, dtype) * scale}
    if spec.use_bias:
        bshape = (spec.experts, spec.out_dim) if spec.experts else (spec.out_dim,)
        p["b"] = jnp.zeros(bshape, dtype)
    return p


# ---------------------------------------------------------------------------
# train path (QAT)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _ste_attach(q_wire, w, alpha):
    """Forward: the packed-path value. Backward: straight-through to w
    (hard-tanh mask). Crucially there is NO full-precision forward value to
    gather — `q_wire + (ste - stop_grad(ste))` does not work because XLA will
    not simplify float `a - a` to 0, so the bf16 `ste` got gathered anyway
    (measured: identical 12.5 TB all-gather; see EXPERIMENTS.md §Perf B)."""
    return q_wire


def _ste_attach_fwd(q_wire, w, alpha):
    return q_wire, (w, alpha)


def _ste_attach_bwd(res, g):
    w, alpha = res
    return None, (g * alpha * (jnp.abs(w) <= 1.0)).astype(w.dtype), None


_ste_attach.defvjp(_ste_attach_fwd, _ste_attach_bwd)


def _packed_wire_weight(w: jnp.ndarray, spec: QLinearSpec) -> jnp.ndarray:
    """QAT weight whose *value* flows through the packed bit-plane format.

    §Perf B (beyond paper, built from the paper's own format): under FSDP,
    XLA all-gathers the weight at every use — in bf16 that wire dominates
    large-model training. The QAT forward only needs the *quantized* weight,
    so its value is computed from `core.pack` planes pinned replicated-over-
    data: GSPMD must place the data-axis all-gather on the 1/2/8-bit planes
    (16x/8x/2x less wire than bf16). The STE gradient reaches the sharded
    master weight through `_ste_attach` (custom_vjp), so no full-precision
    forward tensor ever exists to be gathered."""
    from jax.sharding import PartitionSpec as P
    prec = spec.lq.weights.precision

    def rep(t):
        """Pin replicated-over-data (out-dim stays on model) — forces the
        FSDP all-gather HERE, on the packed planes. No-op without a mesh."""
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or "model" not in (mesh.axis_names or ()):
            return t
        return jax.lax.with_sharding_constraint(
            t, P(*([None] * (t.ndim - 1)), "model"))

    wq = jax.lax.stop_gradient(w)
    if prec == "ternary":
        q = jax.lax.stop_gradient(ternarize(wq, spec.lq.weights.ternary_threshold))
        qa = jnp.abs(q)
        alpha = (jnp.sum(jnp.abs(wq) * qa, axis=-2, keepdims=True)
                 / (jnp.sum(qa, axis=-2, keepdims=True) + 1e-6))
        m, sgn = pack.pack_ternary(jnp.swapaxes(q, -1, -2))  # pack along in-dim
        q_wire = jnp.swapaxes(pack.unpack_ternary(
            rep(m), rep(sgn), w.shape[-2]), -1, -2) * alpha
        q_wire = q_wire.astype(jnp.bfloat16)
    elif prec == "binary":
        q = jax.lax.stop_gradient(binarize(wq))
        alpha = jnp.mean(jnp.abs(wq), axis=-2, keepdims=True)
        words = pack.pack_binary(jnp.swapaxes(q, -1, -2))
        q_wire = (jnp.swapaxes(pack.unpack_binary(
            rep(words), w.shape[-2]), -1, -2) * alpha).astype(jnp.bfloat16)
    elif prec == "int8":
        axis = tuple(range(w.ndim - 1))
        sc = int8_scale(wq, axis=axis)
        codes = rep(int8_codes(wq, sc))
        q_wire = codes.astype(jnp.float32) * sc
        alpha = jnp.ones((), w.dtype)
    else:
        return fake_quant(w, spec.lq.weights, scale_axis=-2)
    return _ste_attach(q_wire, w, jax.lax.stop_gradient(alpha))


def _apply_train(p: Params, x: jnp.ndarray, spec: QLinearSpec,
                 wire: str = "dense") -> jnp.ndarray:
    # keep the master dtype through fake-quant: upcasting to f32 here made
    # every FSDP weight gather (and the STE backward reshard) move 2x the
    # bytes — nemotron-340b train: 3.7 TiB f32(18432,18432) gathers
    # (EXPERIMENTS.md §Perf B iter-5)
    wf = p["w"]
    if wire == "packed" and not spec.experts and wf.shape[-2] % 32 == 0:
        w = _packed_wire_weight(wf, spec).astype(x.dtype)
    else:
        # alpha per out-channel (reduce the in-dim) == serve w_scale algebra
        w = fake_quant(wf, spec.lq.weights, scale_axis=-2).astype(x.dtype)
    # name the gathered+quantized weight so the remat policy can SAVE it:
    # re-gathering weights during backward recompute tripled the FSDP
    # all-gather volume (§Perf B iter-6)
    from jax.ad_checkpoint import checkpoint_name
    w = checkpoint_name(w, "qweight")
    xq = fake_quant(x, spec.lq.acts, scale_axis=-1)  # per-row a_alpha
    if spec.experts:
        y = jnp.einsum("e...k,ekn->e...n", xq, w)
    else:
        y = xq @ w
    if "b" in p:
        b = p["b"]
        y = y + (b[:, None, :] if spec.experts and b.ndim == 2 else b)
    return y


# ---------------------------------------------------------------------------
# serve layout: pack_params + spec tree for the dry-run
# ---------------------------------------------------------------------------

def pack_params(p: Params, spec: QLinearSpec) -> Params:
    """Convert train-layout params to the packed serve layout.

    binary : w_packed  uint32[(E,) out, in/32]     (bit = +1)
             w_scale   f32[(E,) out]               (XNOR-Net per-channel alpha)
    ternary: w_mask/w_sign uint32[(E,) out, in/32]
             w_scale   f32[(E,) out]
    int4   : w_q4      uint32[(E,) out, in/8]      (s4 nibble codes, v_C=8)
             w_scale   f32[(E,) out]
    int8   : w_q       int8[(E,) in, out]
             w_scale   f32[(E,) out]
    int4/int8 weights with int8 acts additionally carry the stacked
    bit-plane twin of the same codes (word-aligned in_dim only):
             w_planes  uint32[(E,) bits, out, in/32]  (MSB-first 2c planes)
    feeding the impl="planes" cells and their truncated-plane drafts.
    none   : w         bf16 (dense weights, cast)
    `a_scale` (f32 scalar) is a calibrated activation scale for int8 acts.
    Weight and activation precisions are independent (mixed w/a operating
    points): the weight layout above composes with whatever `a_scale` the
    activation precision needs.
    """
    w = p["w"].astype(jnp.float32)
    prec = spec.lq.weights.precision
    out: Params = {}
    # channel-last -> put out_dim first for the packed (K-last) layouts
    wt = jnp.swapaxes(w, -1, -2)  # (E,) out, in
    if prec == "binary":
        out["w_packed"] = pack.pack_binary(jnp.sign(wt) + (wt == 0))
        out["w_scale"] = jnp.mean(jnp.abs(wt), axis=-1)
    elif prec == "ternary":
        q = ternarize(wt, spec.lq.weights.ternary_threshold)
        m, s = pack.pack_ternary(jax.lax.stop_gradient(q))
        out["w_mask"], out["w_sign"] = m, s
        nz = jnp.sum(jnp.abs(q), axis=-1) + 1e-6
        out["w_scale"] = jnp.sum(jnp.abs(wt) * jnp.abs(q), axis=-1) / nz
    elif prec == "int4":
        s = int4_scale(wt, axis=-1)            # per-out-channel, reduce in
        codes = int4_codes(wt, s)
        out["w_q4"] = pack.pack_int4(codes)
        if spec.lq.acts.precision == "int8" and spec.in_dim % pack.WORD == 0:
            # stacked bit-plane twin of the SAME codes (plane-composed cells
            # + truncated-plane speculative drafts); word-aligned K only
            out["w_planes"] = pack.pack_planes(codes, pack.PLANE_BITS[prec])
        out["w_scale"] = jnp.squeeze(s, axis=-1)
    elif prec == "int8":
        s = int8_scale(w, axis=(w.ndim - 2,))  # reduce in_dim, keep experts
        codes = int8_codes(w, s)
        out["w_q"] = codes
        if spec.lq.acts.precision == "int8" and spec.in_dim % pack.WORD == 0:
            out["w_planes"] = pack.pack_planes(
                jnp.swapaxes(codes, -1, -2), pack.PLANE_BITS[prec])
        out["w_scale"] = jnp.squeeze(s, axis=w.ndim - 2)
    else:
        out["w"] = w.astype(jnp.bfloat16)
    if spec.lq.acts.precision == "int8":
        out["a_scale"] = jnp.float32(0.05)  # calibration constant
    if "b" in p:
        out["b"] = p["b"].astype(jnp.float32)
    return out


def serve_param_shapes(spec: QLinearSpec) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct tree of the serve layout (dry-run, no allocation)."""
    e = (spec.experts,) if spec.experts else ()
    k, n = spec.in_dim, spec.out_dim
    prec = spec.lq.weights.precision
    sd = jax.ShapeDtypeStruct
    out: dict[str, jax.ShapeDtypeStruct] = {}
    if prec == "binary":
        out["w_packed"] = sd(e + (n, k // 32), jnp.uint32)
        out["w_scale"] = sd(e + (n,), jnp.float32)
    elif prec == "ternary":
        out["w_mask"] = sd(e + (n, k // 32), jnp.uint32)
        out["w_sign"] = sd(e + (n, k // 32), jnp.uint32)
        out["w_scale"] = sd(e + (n,), jnp.float32)
    elif prec == "int4":
        out["w_q4"] = sd(e + (n, k // pack.NIBBLES), jnp.uint32)
        if spec.lq.acts.precision == "int8" and k % pack.WORD == 0:
            out["w_planes"] = sd(e + (pack.PLANE_BITS[prec], n, k // pack.WORD),
                                 jnp.uint32)
        out["w_scale"] = sd(e + (n,), jnp.float32)
    elif prec == "int8":
        out["w_q"] = sd(e + (k, n), jnp.int8)
        if spec.lq.acts.precision == "int8" and k % pack.WORD == 0:
            out["w_planes"] = sd(e + (pack.PLANE_BITS[prec], n, k // pack.WORD),
                                 jnp.uint32)
        out["w_scale"] = sd(e + (n,), jnp.float32)
    else:
        out["w"] = sd(e + (k, n), jnp.bfloat16)
    if spec.lq.acts.precision == "int8":
        out["a_scale"] = sd((), jnp.float32)
    if spec.use_bias:
        out["b"] = sd(e + (n,) if e else (n,), jnp.float32)
    return out


# ---------------------------------------------------------------------------
# serve path — one dispatch into the precision-keyed GEMM registry
# ---------------------------------------------------------------------------

def apply(p: Params, x: jnp.ndarray, spec: QLinearSpec, *,
          mode: str = "train", op=None, impl: str | None = None,
          backend: str | None = None, wire: str = "dense",
          tp=None, ep=None) -> jnp.ndarray:
    """Apply the quantized linear. See module docstring for modes.

    Serve mode routes every operating point through
    `repro.kernels.dispatch.qgemm` — the single owner of activation
    packing, expert vmap and the fused bias/requant epilogue for both the
    jnp and Pallas backends. `op` (a `dispatch.OperatingPoint`) names the
    layer's operating point — precisions from the policy's LayerQuant,
    formulation/backend/tile from the execution context; None derives it
    from the spec plus the legacy `impl=`/`backend=` string kwargs. `tp`
    (a `dispatch.TPSpec`) runs the GEMM under shard_map in the layer's
    `spec.parallel` role (tensor-parallel serve); `ep` (a
    `dispatch.EPSpec`) runs expert stacks via the grouped expert-parallel
    dispatch instead of the replicated dense vmap."""
    if mode == "train":
        return _apply_train(p, x, spec, wire)
    if mode != "serve":
        raise ValueError(f"mode={mode!r}")
    from repro.kernels.dispatch import qgemm   # deferred: core must not pull
    return qgemm(p, x, spec, op, impl=impl, backend=backend,  # pallas at import
                 tp=tp, ep=ep, parallel=spec.parallel)
