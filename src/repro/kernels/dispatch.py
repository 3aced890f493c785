"""Precision-keyed GEMM dispatch — the single entry point of the serve stack.

BrainTTA serves binary, ternary and int8 operands through one flexible
datapath (§III); this module is that datapath's software twin. Every serve
GEMM in the repo — `core.qlinear.apply(mode="serve")`, the Pallas backend
that used to live in `kernels.ops`, the launch drivers and the benches —
funnels through

    qgemm(p, x, spec, op)

where `op` is an `OperatingPoint`: the frozen, structured description of one
datapath configuration — weight precision, activation precision, kernel
formulation (`impl`), execution backend, and an optional `Tile` block-shape
override. `qgemm` owns, exactly once, everything the four call sites used to
copy: activation quantization/packing, M-padding, block-size selection
(explicit `Tile` or the per-cell `TuneTable`), expert vmap, and the
bias/requant epilogue (fused in-kernel on the Pallas backend, single f32
requant on the jnp backend — no separate bias round-trip).

The registry maps operating points to `GemmCell`s, keyed by
(wprec, aprec, impl) — backend and tile are execution choices, not cells:
every cell serves both backends. Each cell holds the ONE implementation of
its formulation:

  prep  — activation quantize/pack (shared verbatim by both backends, so
          jnp-vs-pallas equivalence is an algebra check, not a tolerance
          dance)
  acc   — the jnp accumulator formulation (XLA backend / CPU dry-run)
  body  — the Pallas `MacBody` riding `harness.gemm`'s shared skeleton
          (None = no packed kernel; the jnp formulation serves both
          backends, e.g. the weight-only cells whose activations stay bf16
          on the MXU — quantizing them here would silently change the
          algebra vs QAT)

Weight and activation precisions may DIFFER per cell (mixed w/a datapath,
§II-A "some layers are more resilient to quantization than others"): the
w-ternary × a-int8 cell contracts trit weight planes against int8 activation
codes, and the int4 cells unpack s4 nibble words — the requant epilogue
composes the per-channel weight scale with the per-row activation scale
regardless of how the two sides were quantized.

Adding a precision or kernel variant = one prep/acc/body triple + one
`register()` call. `impl="*"` marks formulation-agnostic cells (int8/int4
have no popcount/mxu split; weight-only cells ignore impl).

`python -m repro.kernels.dispatch --list` prints the live registry.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import pack
from repro.core.quantize import int8_codes, ternarize

from . import bgemm, i4gemm, i8gemm, pgemm, tgemm
from . import harness
from .harness import Tile  # re-export: the OperatingPoint tile override type


def pallas_interpret() -> bool:
    """Whether Pallas kernels run interpreted: exactly when the default
    backend is not a TPU. The one place the repo decides it; kernels
    called with interpret=None ask here on first trace, never at import."""
    return jax.default_backend() != "tpu"


#: Pallas kernels need M padded to the sublane multiple.
PAD_M = 8

_BACKENDS = ("jnp", "pallas")


@dataclasses.dataclass(frozen=True)
class OperatingPoint:
    """One configuration of the flexible datapath, as a first-class value.

    Replaces the loose (wprec, aprec, impl) string tuples and scattered
    `impl=`/`backend=` kwargs of the old API. wprec/aprec name the registry
    cell; impl selects the kernel formulation ("popcount" | "mxu", or "*"
    when the cell is formulation-agnostic); backend selects where the cell's
    formulation executes; tile (a `harness.Tile`) overrides the block shapes
    — when None, `qgemm` consults the per-cell `TuneTable`.
    """
    wprec: str = "none"
    aprec: str = "none"
    impl: str = "popcount"
    backend: str = "jnp"
    tile: Tile | None = None
    #: leading (MSB-first) plane count a plane-composed cell contracts; None
    #: = the full stack. An execution choice like backend/tile, NOT part of
    #: the registry key — the self-speculative draft pass runs the SAME cell
    #: over the same weights with planes=1/2 (truncated-plane approximation).
    planes: int | None = None

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend={self.backend!r}; have {_BACKENDS}")
        if self.planes is not None and self.planes < 1:
            raise ValueError(f"planes={self.planes!r}: need >= 1 or None")

    @property
    def key(self) -> tuple[str, str, str]:
        """The structured registry key (backend/tile are execution choices)."""
        return (self.wprec, self.aprec, self.impl)

    @property
    def tag(self) -> str:
        trunc = "" if self.planes is None else f":p{self.planes}"
        return (f"w{self.wprec[:4]}/a{self.aprec[:4]}/{self.impl}{trunc}"
                f"@{self.backend}")

    @classmethod
    def for_spec(cls, spec, *, impl: str = "popcount", backend: str = "jnp",
                 tile: Tile | None = None) -> "OperatingPoint":
        """The per-layer operating point: precisions from the layer's
        `LayerQuant` (set by the `PrecisionPolicy`), formulation/backend from
        the execution context. This is how the serve path resolves a
        heterogeneous policy layer by layer instead of from one global flag
        pair."""
        return cls(spec.lq.weights.precision, spec.lq.acts.precision,
                   impl=impl, backend=backend, tile=tile)


@dataclasses.dataclass(frozen=True)
class GemmCell:
    """One registered operating point of the datapath.

    `op` is the structured registry key (its backend/tile fields are ignored
    at registration — a cell serves both backends; tiles come from the
    caller's OperatingPoint or the TuneTable)."""
    op: OperatingPoint
    weight_names: tuple[str, ...]   # packed-param entries feeding the GEMM
    prep: Callable                  # (x2d, p, spec) -> (x_ops, a_scale|None)
    acc: Callable                   # (x_ops, w_ops, k) -> (M, N) accumulator
    body: harness.MacBody | None = None   # Pallas tile body (None = jnp only)
    wide: bool = True               # f32 requant (W&A) vs bf16 (weight-only)

    @property
    def wprec(self) -> str:
        return self.op.wprec

    @property
    def aprec(self) -> str:
        return self.op.aprec

    @property
    def impl(self) -> str:
        return self.op.impl

    @property
    def key(self) -> tuple[str, str, str]:
        return self.op.key

    @property
    def tag(self) -> str:
        return f"w{self.wprec[:3]}/a{self.aprec[:3]}/{self.impl}"

    @property
    def k_quantum(self) -> int:
        """K elements per storage unit of the cell's packed weight axis —
        the pack factor tensor-parallel K-sharding must respect (32 for the
        bit-plane formats, 8 for s4 nibbles, 1 for int8/dense)."""
        return max((pack.K_QUANTUM.get(nm, 1) for nm in self.weight_names),
                   default=1)


_REGISTRY: dict[tuple[str, str, str], GemmCell] = {}


def register(cell: GemmCell) -> GemmCell:
    if cell.key in _REGISTRY:
        raise ValueError(f"duplicate GEMM registration for {cell.key}")
    _REGISTRY[cell.key] = cell
    return cell


def _nearest_key(key: tuple[str, str, str]) -> tuple[str, str, str] | None:
    """Closest registered cell to an unknown key, wildcard-aware: rank by
    matching wprec, then aprec, then impl (a '*' cell matches any impl)."""
    def score(have: tuple[str, str, str]) -> tuple[int, int, int]:
        return (int(have[0] == key[0]), int(have[1] == key[1]),
                int(have[2] in (key[2], "*")))
    return max(sorted(_REGISTRY), key=score, default=None)


def lookup(op, aprec: str | None = None, impl: str = "popcount") -> GemmCell:
    """Resolve an operating point to its cell; impl falls back to '*'.

    Primary form: lookup(OperatingPoint(...)). The legacy
    lookup(wprec, aprec, impl) string form resolves identically.
    """
    key = op.key if isinstance(op, OperatingPoint) else (op, aprec, impl)
    for k in (key, (key[0], key[1], "*")):
        if k in _REGISTRY:
            return _REGISTRY[k]
    near = _nearest_key(key)
    hint = ""
    if near is not None:
        hint = (f"; nearest registered cell is (wprec={near[0]!r}, "
                f"aprec={near[1]!r}, impl={near[2]!r})")
    raise KeyError(
        f"no GEMM registered for (wprec={key[0]!r}, aprec={key[1]!r}, "
        f"impl={key[2]!r}){hint} — run `python -m repro.kernels.dispatch "
        f"--list` for the full registry")


def cells() -> dict[tuple[str, str, str], GemmCell]:
    """Snapshot of the registry (tests / benches iterate this)."""
    return dict(_REGISTRY)


def operating_points(backend: str = "jnp") -> list[OperatingPoint]:
    """Every registered cell as a concrete OperatingPoint on `backend`."""
    return [dataclasses.replace(c.op, backend=backend)
            for _, c in sorted(_REGISTRY.items())]


# ---------------------------------------------------------------------------
# TuneTable — per-cell Tile choices as data, not code
# ---------------------------------------------------------------------------

DEFAULT_TUNE_PATH = os.path.join(os.path.dirname(__file__), "tune_cpu.json")


@dataclasses.dataclass(frozen=True)
class TuneTable:
    """Per-cell `Tile` map consulted when an OperatingPoint carries no
    explicit tile — the ROADMAP's "autotune per operating point" as a JSON
    data file. Keys are registry keys; an impl of "*" matches any
    formulation of that (wprec, aprec) pair (same fallback as `lookup`).

    The in-repo default (`tune_cpu.json`, regenerated by
    `python -m benchmarks.kernel_bench --retune`) is measured in
    interpret mode on CPU — a correctness-scale baseline; a real-TPU sweep
    drops in as another JSON file via `load()` / `launch.serve --tune`.
    """
    tiles: Mapping[tuple[str, str, str], Tile]
    source: str = ""

    def tile_for(self, op: OperatingPoint) -> Tile | None:
        for key in (op.key, (op.wprec, op.aprec, "*")):
            if key in self.tiles:
                return self.tiles[key]
        return None

    @classmethod
    def load(cls, path: str) -> "TuneTable":
        with open(path) as f:
            raw = json.load(f)
        tiles = {}
        for name, t in raw.get("cells", {}).items():
            wprec, aprec, impl = name.split("/")
            tiles[(wprec, aprec, impl)] = Tile(
                bm=int(t["bm"]), bn=int(t["bn"]),
                bkq=None if t.get("bkq") is None else int(t["bkq"]))
        return cls(tiles=tiles, source=str(raw.get("source", path)))

    def save(self, path: str) -> None:
        cells_json = {
            "/".join(key): {"bm": t.bm, "bn": t.bn, "bkq": t.bkq}
            for key, t in sorted(self.tiles.items())}
        with open(path, "w") as f:
            json.dump({"source": self.source, "cells": cells_json}, f,
                      indent=2, sort_keys=True)
            f.write("\n")


def valid_tune_keys(extra_keys=()) -> set:
    """Every tune-table key the CURRENT registry can resolve: exact cell
    keys, the `(wprec, aprec, "*")` wildcard of each registered pair (the
    `tile_for` fallback), plus pseudo-cell keys owned by non-qgemm kernels
    (the paged-attn decode walk passes its own via `extra_keys`)."""
    keys = set(_REGISTRY)
    keys |= {(w, a, "*") for (w, a, _i) in _REGISTRY}
    keys |= set(extra_keys)
    return keys


def prune_stale_tiles(tiles: Mapping, extra_keys=()
                      ) -> tuple[dict, list]:
    """Split a tune-table tile map into (kept, dropped_keys): rows whose op
    key no longer matches any registered cell (a renamed impl, a retired
    precision pair) are dead data — `tile_for` can never reach them — and
    `kernel_bench --retune` prunes them instead of carrying them forever."""
    valid = valid_tune_keys(extra_keys)
    kept = {k: t for k, t in tiles.items() if k in valid}
    dropped = sorted(k for k in tiles if k not in valid)
    return kept, dropped


@functools.lru_cache(maxsize=1)
def default_tune() -> TuneTable:
    if os.path.exists(DEFAULT_TUNE_PATH):
        return TuneTable.load(DEFAULT_TUNE_PATH)
    return TuneTable(tiles={}, source="(no tune table shipped)")


def _resolve_tile(op: OperatingPoint) -> Tile | None:
    """Explicit OperatingPoint tile, else the shipped TuneTable's choice."""
    return op.tile if op.tile is not None else default_tune().tile_for(op)


# ---------------------------------------------------------------------------
# activation prep — ONE quantize+pack per activation precision
# ---------------------------------------------------------------------------

def _prep_binary(x2d, p, spec):
    xf = x2d.astype(jnp.float32)
    a_scale = jnp.mean(jnp.abs(xf), axis=-1)          # XNOR-Net per-row alpha
    xp = pack.pack_binary(jnp.where(xf >= 0, 1.0, -1.0))
    return (xp,), a_scale


def _prep_ternary(x2d, p, spec):
    xf = x2d.astype(jnp.float32)
    a_scale = jnp.mean(jnp.abs(xf), axis=-1)
    # per-row threshold (axis=-1): under continuous batching a per-tensor
    # threshold couples co-batched requests — one slot's activations would
    # move every other slot's ternarization cut
    xq = jax.lax.stop_gradient(
        ternarize(xf, spec.lq.acts.ternary_threshold, axis=-1))
    xm, xs = pack.pack_ternary(xq)
    return (xm, xs), a_scale


def _prep_int8(x2d, p, spec):
    a_s = p["a_scale"]     # calibrated constant; KeyError = packing bug,
    xq = int8_codes(x2d.astype(jnp.float32), a_s)  # not a default to paper over
    return (xq,), jnp.full((x2d.shape[0],), a_s, jnp.float32)


def _prep_bf16(x2d, p, spec):
    """Weight-only / dense: activations stay bf16 (MXU path)."""
    return (x2d.astype(jnp.bfloat16),), None


# ---------------------------------------------------------------------------
# jnp accumulator formulations — ONE per registered cell
# ---------------------------------------------------------------------------

def _acc_binary_popcount(x_ops, w_ops, k):
    return pack.binary_dot_words(x_ops[0][:, None, :], w_ops[0], k)


def _acc_binary_mxu(x_ops, w_ops, k):
    x = pack.unpack_pm1_i8(x_ops[0], k)                # (M, K) ±1 int8
    w = pack.unpack_pm1_i8(w_ops[0], k)                # (N, K)
    return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _acc_ternary_popcount(x_ops, w_ops, k):
    return pack.ternary_dot_words(x_ops[0][:, None, :], x_ops[1][:, None, :],
                                  w_ops[0], w_ops[1])


def _acc_ternary_mxu(x_ops, w_ops, k):
    x = pack.unpack_ternary_i8(x_ops[0], x_ops[1], k)  # (M, K) trits int8
    w = pack.unpack_ternary_i8(w_ops[0], w_ops[1], k)  # (N, K)
    return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _acc_int8(x_ops, w_ops, k):
    return jax.lax.dot_general(x_ops[0], w_ops[0], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _acc_wternary_aint8(x_ops, w_ops, k):
    """Mixed w-ternary × a-int8: int8 codes against unpacked trit planes."""
    w = pack.unpack_ternary_i8(w_ops[0], w_ops[1], k)  # (N, K) trits int8
    return jax.lax.dot_general(x_ops[0], w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _acc_wint4_aint8(x_ops, w_ops, k):
    """int4 weights (s4 nibble words) × int8 activation codes."""
    w = pack.unpack_int4_i8(w_ops[0], k)               # (N, K) s4-as-int8
    return jax.lax.dot_general(x_ops[0], w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _acc_planes(x_ops, w_ops, k, *, bits):
    """Plane-composed weights x int8 activation codes: the stacked binary
    planes compose back to the exact b-bit codes (or their leading-P
    truncation), then one int8 MXU dot — integer arithmetic end to end, so
    the result is bit-identical to the direct int4/int8 cells."""
    w = pack.unpack_planes_i8(w_ops[0], k, bits)       # (N, K) composed codes
    return jax.lax.dot_general(x_ops[0], w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _acc_wonly_binary(x_ops, w_ops, k):
    w = pack.unpack_pm1_i8(w_ops[0], k)                # (N, K)
    return x_ops[0] @ w.astype(x_ops[0].dtype).T


def _acc_wonly_ternary(x_ops, w_ops, k):
    w = pack.unpack_ternary_i8(w_ops[0], w_ops[1], k)
    return x_ops[0] @ w.astype(x_ops[0].dtype).T


def _acc_wonly_int4(x_ops, w_ops, k):
    w = pack.unpack_int4_i8(w_ops[0], k)               # (N, K) s4 codes
    return x_ops[0] @ w.astype(x_ops[0].dtype).T


def _acc_wonly_int8(x_ops, w_ops, k):
    return x_ops[0] @ w_ops[0].astype(x_ops[0].dtype)  # w_q is (K, N)


def _acc_dense(x_ops, w_ops, k):
    return x_ops[0] @ w_ops[0]


# ---------------------------------------------------------------------------
# the registry — every operating point of the POLICIES table
# ---------------------------------------------------------------------------

def _op(wprec, aprec, impl):
    return OperatingPoint(wprec, aprec, impl)


# W&A-quantized cells: packed operands, int accumulators, Pallas bodies.
register(GemmCell(_op("binary", "binary", "popcount"), ("w_packed",),
                  _prep_binary, _acc_binary_popcount, body=bgemm.BINARY_POPCOUNT))
register(GemmCell(_op("binary", "binary", "mxu"), ("w_packed",),
                  _prep_binary, _acc_binary_mxu, body=bgemm.BINARY_MXU))
register(GemmCell(_op("ternary", "ternary", "popcount"), ("w_mask", "w_sign"),
                  _prep_ternary, _acc_ternary_popcount,
                  body=tgemm.TERNARY_POPCOUNT))
register(GemmCell(_op("ternary", "ternary", "mxu"), ("w_mask", "w_sign"),
                  _prep_ternary, _acc_ternary_mxu, body=tgemm.TERNARY_MXU))
register(GemmCell(_op("int8", "int8", "*"), ("w_q",),
                  _prep_int8, _acc_int8, body=i8gemm.I8_DOT))

# mixed w/a cells: the two operand sides quantize independently; the shared
# requant epilogue composes the per-channel weight scale (ternary alpha / s4
# scale) with the per-row int8 activation scale — no matched-precision
# assumption anywhere.
register(GemmCell(_op("ternary", "int8", "*"), ("w_mask", "w_sign"),
                  _prep_int8, _acc_wternary_aint8, body=tgemm.TERNARY_W_I8A))
register(GemmCell(_op("int4", "int8", "*"), ("w_q4",),
                  _prep_int8, _acc_wint4_aint8, body=i4gemm.INT4_W_I8A))

# plane-composed cells: int4/int8 weights stored as stacked binary planes
# (pack.pack_planes, MSB-first two's complement), contracted by looping the
# binary plane datapath with shifted coefficients — bit-exact vs the direct
# cells above. impl="planes" coexists with the "*" wildcard rows: exact key
# wins in lookup(), so a policy pair resolves to these only when asked.
# OperatingPoint.planes truncates the stack (the self-speculative draft).
register(GemmCell(_op("int4", "int8", "planes"), ("w_planes",),
                  _prep_int8, functools.partial(_acc_planes, bits=4),
                  body=pgemm.PLANES_W4_I8A))
register(GemmCell(_op("int8", "int8", "planes"), ("w_planes",),
                  _prep_int8, functools.partial(_acc_planes, bits=8),
                  body=pgemm.PLANES_W8_I8A))

# weight-only cells: bf16 acts end-to-end so the row-parallel TP partial-sum
# reduces in bf16 (2x wire, §Perf A); requant stays in bf16 (wide=False).
register(GemmCell(_op("binary", "none", "*"), ("w_packed",),
                  _prep_bf16, _acc_wonly_binary, wide=False))
register(GemmCell(_op("ternary", "none", "*"), ("w_mask", "w_sign"),
                  _prep_bf16, _acc_wonly_ternary, wide=False))
register(GemmCell(_op("int4", "none", "*"), ("w_q4",),
                  _prep_bf16, _acc_wonly_int4, wide=False))
register(GemmCell(_op("int8", "none", "*"), ("w_q",),
                  _prep_bf16, _acc_wonly_int8, wide=False))
register(GemmCell(_op("none", "none", "*"), ("w",),
                  _prep_bf16, _acc_dense, wide=False))


# ---------------------------------------------------------------------------
# tensor parallelism: qgemm under shard_map
# ---------------------------------------------------------------------------
#
# Megatron pairing over the ("data", "model") mesh:
#
#   column-parallel (qkv/up):  weights N-sharded over the model axis; every
#       shard sees the full K, runs the COMPLETE plain qgemm (prep + acc +
#       requant) on its N slice — no collective, bit-exact per slice.
#   row-parallel (out/down):   packed K-sharded. Activation prep (per-row
#       alpha / trit threshold / int8 codes) runs REPLICATED inside the
#       shard_map body on the full K — the per-row statistics must see every
#       K element, and computing them on the full row keeps the algebra
#       identical to the single-device path. Each shard then slices its own
#       packed-K chunk, accumulates its partial dot, and the partials are
#       psum'd on the int32 accumulator BEFORE requant: integer addition is
#       associative, so the TP sum is bit-exact; requantizing per-shard
#       partials would be numerically wrong (and f32/bf16 psum inexact).
#
# Weight-only cells (wide=False) keep bf16 accumulators — a narrow psum is
# NOT bit-exact, so row-parallel falls back to replicated compute for them
# (column-parallel still shards: it needs no collective). The batch/M dim
# additionally shards over the "data" axis when it divides.

def _shard_map(f, *, mesh, in_specs, out_specs):
    """shard_map with varying-manual-axes checking off: the bodies mix
    replicated prep with per-shard Pallas calls, which carry no vma rule."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


@dataclasses.dataclass(frozen=True)
class TPSpec:
    """Tensor-parallel context threaded from the serve driver into qgemm."""
    mesh: Any                       # jax.sharding.Mesh
    axis: str = "model"             # TP (contraction/out-dim) axis name

    @property
    def size(self) -> int:
        return int(self.mesh.shape[self.axis])


#: per-leaf axis positions (negative = from the end; leading expert axis ok)
_N_AXIS = {"w_packed": -2, "w_mask": -2, "w_sign": -2, "w_q4": -2,
           "w_planes": -2, "w_q": -1, "w": -1, "w_scale": -1, "b": -1}
_K_AXIS = {"w_packed": -1, "w_mask": -1, "w_sign": -1, "w_q4": -1,
           "w_planes": -1, "w_q": -2, "w": -2}


# ---------------------------------------------------------------------------
# expert parallelism: grouped qgemm under shard_map
# ---------------------------------------------------------------------------
#
# MoE expert stacks carry a leading E axis that launch/sharding.py already
# places over the "model" mesh axis. EPSpec makes the COMPUTE exploit that
# placement: instead of every shard running all E experts (the dense expert
# vmap, replicated work), each shard runs only its E/ns local experts on
# their capacity-dispatched token slabs — the grouped expert dispatch.
#
#   up (parallel="column"):  each shard runs the COMPLETE per-expert qgemm
#       (prep + acc + requant) on its local expert stack; the output stays
#       expert-sharded over the model axis and the elementwise activation
#       between up and down needs no collective.
#   down (parallel="row"):   each shard's local int accumulators are zero-
#       embedded into the full (E, M, N) at offset shard*e_loc and psum'd —
#       ONE collective, before requant, mirroring row-parallel TP's recipe.
#       Unlike TP-row's true K-reduction, every (e, m, n) element here is
#       produced by exactly ONE shard (zeros elsewhere), so the psum is a
#       disjoint ASSEMBLY — exact at any accumulator width (x + 0 == x in
#       IEEE) — and narrow weight-only cells (bf16 accumulators, e.g. the
#       w-ternary deepseek policy) are EP-shardable where TP-row must fall
#       back to replicated compute. The replicated assembled output then
#       feeds the combine einsum exactly as the single-device oracle does.
#
# Routing (models/moe.py) stays replicated: the router is tiny, and running
# it identically everywhere keeps top-k/capacity drops deterministic and
# bit-identical to the dense-vmap oracle.

@dataclasses.dataclass(frozen=True)
class EPSpec:
    """Expert-parallel context threaded from the serve driver into qgemm.

    Lives beside TPSpec: tp shards WITHIN a (possibly expert-stacked) layer
    (N or packed-K over the model axis), ep shards the expert stack ITSELF
    (leading E axis over the same axis). `ep_plan` arbitrates; when EP does
    not apply the call falls through to the tp/vmap paths unchanged."""
    mesh: Any                       # jax.sharding.Mesh
    axis: str = "model"             # expert axis name on the mesh

    @property
    def size(self) -> int:
        return int(self.mesh.shape[self.axis])


def ep_shardable(n_experts: int, n_shards: int) -> bool:
    """Whole-expert placement predicate: each shard must own an integral
    number of expert stacks. Shared with launch/sharding's `fit_spec` drop
    (E % shards != 0 replicates the leading axis there, and falls back to
    the dense vmap here), so device layout and compute always agree."""
    return n_experts > 0 and n_shards > 0 and n_experts % n_shards == 0


def ep_plan(cell: GemmCell, spec, parallel: str, ep: "EPSpec | None"
            ) -> str | None:
    """Resolve the effective EP mode, or None => dense-vmap/TP fallback.

    Guards: an expert stack, a live mesh axis with size > 1, whole experts
    per shard (`ep_shardable`, the same predicate behind the sharding rules'
    fit_spec drop), and a K axis that is a whole number of packed storage
    units (`cell.k_quantum`, reusing pack.K_QUANTUM). E-axis sharding never
    splits a packed word by construction — each expert's (N, K/q) planes
    move whole — so the k-quantum check is a layout-integrity invariant,
    not a divisibility-by-shards constraint like TP-row's."""
    if ep is None or not spec.experts or parallel not in ("column", "row"):
        return None
    if ep.axis not in ep.mesh.axis_names:
        return None
    ns = ep.size
    if ns <= 1 or not ep_shardable(spec.experts, ns):
        return None
    if spec.in_dim % cell.k_quantum:
        return None
    return parallel


def _ep_pspec(ep: EPSpec, nm: str, v) -> P:
    """Expert-stacked leaves shard their LEADING E axis; scalars and shared
    leaves (a_scale) replicate."""
    if v.ndim == 0 or nm == "a_scale":
        return P(*([None] * v.ndim))
    return P(ep.axis, *([None] * (v.ndim - 1)))


def _ep_column(cell, p, x, spec, op, ep):
    """Expert-sharded up projection: each shard runs the plain per-expert
    qgemm (the dense-vmap path) on its local E/ns expert stack and token
    slabs. No collective — the output stays expert-sharded over the model
    axis, which is exactly the layout the elementwise activation and the
    row-parallel down projection consume."""
    mesh, ax, ns = ep.mesh, ep.axis, ep.size
    e, k, n = spec.experts, spec.in_dim, spec.out_dim
    lead = x.shape[:-1]
    x3 = x.reshape(e, -1, k)
    sub = dataclasses.replace(spec, experts=e // ns)
    pspecs = {nm: _ep_pspec(ep, nm, v) for nm, v in p.items()}
    fn = lambda pl_, xl: qgemm(pl_, xl, sub, op)
    y = _shard_map(fn, mesh=mesh, in_specs=(pspecs, P(ax, None, None)),
                   out_specs=P(ax, None, None))(p, x3)
    return y.reshape(*lead, n)


def _ep_row(cell, p, x, spec, op, ep):
    """Expert-sharded down projection: per-local-expert accumulators, zero-
    embedded into the full (E, M, N) at this shard's expert offset, ONE psum
    over the model axis (the scatter-back), deferred global requant.

    Exactness: the psum sums one real accumulator block with ns-1 zero
    blocks per element — a disjoint assembly, exact at any width — so both
    wide (int32) and narrow (bf16, weight-only) cells keep bit-identical
    results vs the dense-vmap oracle. a_scale (per-row activation stats,
    computed per expert slab exactly as the oracle computes them) is
    assembled the same way for the wide cells' requant."""
    mesh, ax, ns = ep.mesh, ep.axis, ep.size
    e, k, n = spec.experts, spec.in_dim, spec.out_dim
    e_loc = e // ns
    lead = x.shape[:-1]
    x3 = x.reshape(e, -1, k)
    m = x3.shape[-2]
    w_ops = _weight_ops(cell, op, p)
    shared = {nm: p[nm] for nm in ("a_scale",) if nm in p}
    use_pallas = op.backend == "pallas" and cell.body is not None
    tile = _resolve_tile(op)
    sub = dataclasses.replace(spec, experts=0)
    has_ascale = cell.aprec != "none"   # _prep_bf16 returns a_scale=None

    def local(x_loc, w_loc, sh):
        idx = jax.lax.axis_index(ax)
        x_ops, a_scale = jax.vmap(lambda x2d: cell.prep(x2d, sh, sub))(x_loc)
        if use_pallas:
            padm = (-m) % PAD_M
            if padm:
                x_ops = tuple(jnp.pad(v, ((0, 0), (0, padm), (0, 0)))
                              for v in x_ops)
            acc = harness.gemm_grouped(cell.body, x_ops, w_loc,
                                       k=k, tile=tile, out="acc")[:, :m]
        else:
            acc = jax.vmap(lambda xo, wl: cell.acc(xo, wl, k))(x_ops, w_loc)

        def scatter(v):
            full = jnp.zeros((e,) + v.shape[1:], v.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                full, v, idx * e_loc, axis=0)

        # THE expert-parallel collective: disjoint-embedding psum (assembly)
        out = (scatter(acc), scatter(a_scale)) if has_ascale \
            else (scatter(acc),)
        return jax.tree.map(lambda v: jax.lax.psum(v, ax), out)

    wspecs = tuple(_ep_pspec(ep, nm, p[nm]) for nm in cell.weight_names)
    out_specs = (P(*([None] * x3.ndim)),)
    if has_ascale:
        out_specs = out_specs + (P(None, None),)
    res = _shard_map(local, mesh=mesh,
                     in_specs=(P(ax, None, None), wspecs,
                               {nm: P() for nm in shared}),
                     out_specs=out_specs)(x3, w_ops, shared)
    acc = res[0]
    a_scale = res[1] if has_ascale else None
    w_scale, bias = p.get("w_scale"), p.get("b")
    if cell.wide:
        rq = lambda a, ws, asc, b=None: harness.requant(a, ws, asc, b)
        if bias is not None:
            y = jax.vmap(rq)(acc, w_scale, a_scale, bias)
        else:
            y = jax.vmap(rq)(acc, w_scale, a_scale)
    else:
        rqn = lambda a, ws, b=None: _requant_narrow(a, ws, b)
        y = (jax.vmap(rqn)(acc, w_scale, bias) if bias is not None
             else jax.vmap(rqn)(acc, w_scale))
    return round_to(y).reshape(*lead, n)


def tp_plan(cell: GemmCell, spec, parallel: str, tp: TPSpec | None) -> str | None:
    """Resolve the effective TP mode, or None => replicated fallback.

    Guards: the axis must exist with size > 1; column needs N % shards == 0;
    row needs a wide (integer-accumulator) cell and a K axis that splits into
    whole packed storage units per shard — `cell.k_quantum` is the pack
    factor (32-operand bit-plane words, 8-nibble s4 words, or 1 for int8)
    and `pack.shardable_words` the predicate, shared with the device-layout
    rules in launch.sharding so compute and placement agree.
    """
    if tp is None or parallel == "none":
        return None
    if parallel not in ("column", "row"):
        raise ValueError(f"parallel={parallel!r}")
    if tp.axis not in tp.mesh.axis_names:
        return None
    ns = tp.size
    if ns <= 1:
        return None
    if parallel == "column":
        return "column" if spec.out_dim % ns == 0 else None
    if not cell.wide:
        return None
    q = cell.k_quantum
    if spec.in_dim % q:
        return None
    return "row" if pack.shardable_words(spec.in_dim // q, ns) else None


def _weight_ops(cell: GemmCell, op: OperatingPoint, p: Mapping) -> tuple:
    """Fetch the cell's weight operands, applying the OperatingPoint's plane
    truncation (leading MSB-first slice; coefficients are positional, so the
    sliced stack needs no re-scaling). planes on a cell without a stacked
    leaf is a loud error — silently running full precision would make a
    draft pass lie about its cost."""
    w_ops = tuple(p[nm] for nm in cell.weight_names)
    if op.planes is None:
        return w_ops
    if "w_planes" not in cell.weight_names:
        raise ValueError(
            f"OperatingPoint planes={op.planes} needs a plane-composed cell; "
            f"{cell.key} has no stacked w_planes leaf")
    out = []
    for nm, wv in zip(cell.weight_names, w_ops):
        if nm == "w_planes":
            ax = wv.ndim - 3                 # plane axis (skips expert lead)
            if not 1 <= op.planes <= wv.shape[ax]:
                raise ValueError(
                    f"planes={op.planes} outside the stored stack depth "
                    f"{wv.shape[ax]} for {cell.key}")
            wv = jax.lax.slice_in_dim(wv, 0, op.planes, axis=ax)
        out.append(wv)
    return tuple(out)


def _dp_axis(tp: TPSpec, dim: int) -> str | None:
    """The single data axis of the mesh, when it divides `dim`."""
    dp = [a for a in tp.mesh.axis_names if a != tp.axis]
    if len(dp) == 1 and dim % int(tp.mesh.shape[dp[0]]) == 0:
        return dp[0]
    return None


def _tp_column(cell, p, x, spec, op, tp):
    """N-sharded qgemm: each shard runs the full plain path on its slice."""
    mesh, ax, ns = tp.mesh, tp.axis, tp.size
    sub = dataclasses.replace(spec, out_dim=spec.out_dim // ns)

    def pspec(nm, v):
        if v.ndim == 0 or nm not in _N_AXIS:
            return P(*([None] * v.ndim))
        dims = [None] * v.ndim
        dims[_N_AXIS[nm]] = ax
        return P(*dims)

    xdims = [None] * x.ndim
    odims = [None] * x.ndim
    dp = _dp_axis(tp, x.shape[0]) if (not spec.experts and x.ndim >= 2) else None
    if dp:
        xdims[0] = odims[0] = dp
    odims[-1] = ax
    pspecs = {nm: pspec(nm, v) for nm, v in p.items()}
    fn = lambda pl_, xl: qgemm(pl_, xl, sub, op)
    return _shard_map(fn, mesh=mesh, in_specs=(pspecs, P(*xdims)),
                      out_specs=P(*odims))(p, x)


def _tp_row(cell, p, x, spec, op, tp):
    """Packed-K-sharded qgemm: replicated full-K prep, per-shard integer
    partial dot, ONE int32 psum per call, deferred (global) requant."""
    mesh, ax, ns = tp.mesh, tp.axis, tp.size
    k, n = spec.in_dim, spec.out_dim
    lead = x.shape[:-1]
    e = spec.experts
    x3 = x.reshape((e, -1, k) if e else (-1, k))
    m = x3.shape[-2]
    w_ops = _weight_ops(cell, op, p)
    shared = {nm: p[nm] for nm in ("a_scale",) if nm in p}
    use_pallas = op.backend == "pallas" and cell.body is not None
    tile = _resolve_tile(op)
    k_loc = k // ns

    def wspec(nm):
        dims = [None] * p[nm].ndim
        dims[_K_AXIS[nm]] = ax
        return P(*dims)

    dp = None if e else _dp_axis(tp, m)
    xdims = [dp] + [None] * (x3.ndim - 1)
    accdims = list(xdims)           # acc: (E,) M, N — leading dims like x3
    asdims = xdims[:-1]             # a_scale: (E,) M

    def local(x_loc, w_loc, sh):
        idx = jax.lax.axis_index(ax)

        def one(x2d, wl):
            # full-K prep: per-row stats identical to the unsharded path.
            # Each prep output slices its OWN storage axis (mixed w/a cells
            # have different x/w densities, e.g. int8 codes vs trit words).
            x_ops, a_scale = cell.prep(x2d, sh, spec)
            xl = tuple(
                jax.lax.dynamic_slice_in_dim(
                    xo, idx * (xo.shape[-1] // ns), xo.shape[-1] // ns,
                    axis=-1) for xo in x_ops)
            if use_pallas:
                mm = x2d.shape[0]
                padm = (-mm) % PAD_M
                if padm:
                    xl = tuple(jnp.pad(v, ((0, padm), (0, 0))) for v in xl)
                acc = harness.gemm(cell.body, xl, wl, None, None, None,
                                   k=k_loc, tile=tile, out="acc")[:mm]
            else:
                acc = cell.acc(xl, wl, k_loc)
            return acc, a_scale

        if e:
            acc, a_scale = jax.vmap(one)(x_loc, w_loc)
        else:
            acc, a_scale = one(x_loc, w_loc)
        # THE tensor-parallel collective: integer partial sums, pre-requant
        return jax.lax.psum(acc, ax), a_scale

    acc, a_scale = _shard_map(
        local, mesh=mesh,
        in_specs=(P(*xdims), tuple(wspec(nm) for nm in cell.weight_names),
                  {nm: P() for nm in shared}),
        out_specs=(P(*accdims), P(*asdims)))(x3, w_ops, shared)

    w_scale, bias = p.get("w_scale"), p.get("b")
    if e:
        rq = lambda a, ws, asc, b=None: harness.requant(a, ws, asc, b)
        y = (jax.vmap(rq)(acc, w_scale, a_scale, bias) if bias is not None
             else jax.vmap(rq)(acc, w_scale, a_scale))
    else:
        y = harness.requant(acc, w_scale, a_scale, bias)
    return round_to(y).reshape(*lead, n)


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------

def round_to(y, dtype=jnp.bfloat16):
    """`y` cast to `dtype`, rounded to bf16 where a Pallas kernel's output
    rounds. A plain cast is not enough in XLA code: with excess precision
    allowed (XLA's default) the TPU compiler drops an f32→bf16→f32 round
    trip into the next elementwise op, so XLA code would hand its consumer
    unrounded values and drift from the kernels. reduce_precision is never
    dropped."""
    if y.dtype == jnp.float32 and dtype == jnp.bfloat16:
        y = jax.lax.reduce_precision(y, exponent_bits=8, mantissa_bits=7)
    return y.astype(dtype)


def _requant_narrow(acc, w_scale, bias):
    """Weight-only epilogue: scale in the accumulator dtype (bf16 TP wire),
    bias folded in f32 — the one place bias touches the narrow path."""
    y = acc if w_scale is None else acc * w_scale.astype(acc.dtype)
    if bias is not None:
        y = y.astype(jnp.float32) + bias
    return y


def qgemm(p: dict, x: jnp.ndarray, spec, op: OperatingPoint | None = None, *,
          tp: TPSpec | None = None, ep: "EPSpec | None" = None,
          parallel: str = "none",
          impl: str | None = None, backend: str | None = None) -> jnp.ndarray:
    """The serve-mode quantized GEMM: (..., K) -> (..., N) bf16.

    p: packed params from `core.qlinear.pack_params`; spec: QLinearSpec;
    op: the `OperatingPoint` to run — its wprec/aprec must match the spec's
    LayerQuant (the per-layer policy assignment), impl/backend select the
    formulation and where it executes, and tile (explicit, else the
    `TuneTable`) sets the Pallas block shapes. op=None derives the point
    from the spec plus the legacy `impl=`/`backend=` string kwargs (kept
    for out-of-tree callers; in-tree code passes `op`).

    backend="pallas" routes cells with a MacBody through `harness.gemm`
    (fused bias); backend="jnp" (and cells with no Pallas body) run the
    identical formulation via XLA. Both share prep and the requant algebra.

    tp + parallel ("column" | "row") run the GEMM under shard_map on the
    tensor-parallel mesh axis (see the TP section above): column shards N
    with no collective; row shards the packed K and psums the int32
    accumulator before requant. Both modes are bit-exact vs. the unsharded
    path; non-dividing shapes (and narrow-accumulator row cells) fall back
    to replicated compute — `tp_plan` is the single arbiter.

    ep (expert stacks only) runs the grouped expert dispatch: each shard
    computes only its local experts (see the EP section above). Checked
    before tp — when `ep_plan` declines (non-dividing expert count, dead
    axis) the call falls through to TP-within-expert, then the dense
    expert vmap, all bit-exact vs. each other.
    """
    if op is None:
        op = OperatingPoint.for_spec(spec, impl=impl or "popcount",
                                     backend=backend or "jnp")
    elif impl is not None or backend is not None:
        raise ValueError("pass either op= or the legacy impl=/backend= "
                         "kwargs, not both")
    if (op.wprec, op.aprec) != (spec.lq.weights.precision,
                                spec.lq.acts.precision):
        raise ValueError(
            f"OperatingPoint {op.tag} does not match the layer's policy "
            f"assignment {spec.lq.tag} for {spec.name!r}")
    cell = lookup(op)
    if spec.experts and ep is not None:
        plan = ep_plan(cell, spec, parallel, ep)
        if plan == "column":
            return _ep_column(cell, p, x, spec, op, ep)
        if plan == "row":
            return _ep_row(cell, p, x, spec, op, ep)
    if tp is not None and parallel != "none":
        plan = tp_plan(cell, spec, parallel, tp)
        if plan == "column":
            return _tp_column(cell, p, x, spec, op, tp)
        if plan == "row":
            return _tp_row(cell, p, x, spec, op, tp)
    if spec.experts:
        sub = dataclasses.replace(spec, experts=0)
        shared = {nm: p[nm] for nm in ("a_scale",) if nm in p}
        per_e = {nm: v for nm, v in p.items() if nm not in shared}
        fn = lambda pp, xx: qgemm({**pp, **shared}, xx, sub, op)
        return jax.vmap(fn)(per_e, x)

    k, n = spec.in_dim, spec.out_dim
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    x_ops, a_scale = cell.prep(x2d, p, spec)
    w_ops = _weight_ops(cell, op, p)
    w_scale = p.get("w_scale")
    bias = p.get("b")

    if op.backend == "pallas" and cell.body is not None:
        m = x2d.shape[0]
        padm = (-m) % PAD_M
        if padm:
            x_ops = tuple(jnp.pad(xo, ((0, padm), (0, 0))) for xo in x_ops)
            a_scale = jnp.pad(a_scale, (0, padm))
        y = harness.gemm(cell.body, x_ops, w_ops, w_scale, a_scale, bias,
                         k=k, tile=_resolve_tile(op))[:m]
    else:
        acc = cell.acc(x_ops, w_ops, k)
        if cell.wide:
            y = harness.requant(acc, w_scale, a_scale, bias)
        else:
            y = _requant_narrow(acc, w_scale, bias)
    return round_to(y).reshape(*lead, n)


# ---------------------------------------------------------------------------
# CLI: the live registry as a table
# ---------------------------------------------------------------------------

def registry_table() -> str:
    """The registry rendered as an aligned text table (CI prints this)."""
    tune = default_tune()
    rows = [("wprec", "aprec", "impl", "backends", "weights", "acc",
             "tile(bm,bn,bkq)", "vmem")]
    for key in sorted(_REGISTRY):
        cell = _REGISTRY[key]
        backends = "jnp+pallas" if cell.body is not None else "jnp"
        tile = tune.tile_for(cell.op)
        if tile is None and cell.body is not None:
            tile = Tile(bkq=cell.body.default_bkq)
        tstr = f"{tile.bm},{tile.bn},{tile.bkq}" if tile else "-"
        vmem = (f"{harness.vmem_tile_bytes(cell.body, tile) / 2**10:.0f}KiB"
                if cell.body is not None else "-")
        rows.append((cell.wprec, cell.aprec, cell.impl, backends,
                     "+".join(cell.weight_names),
                     "int32" if cell.wide else "bf16", tstr, vmem))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines = ["  ".join(v.ljust(w) for v, w in zip(r, widths)).rstrip()
             for r in rows]
    lines.insert(1, "  ".join("-" * w for w in widths))
    return "\n".join(lines)


def _main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="qGEMM dispatch registry inspector")
    ap.add_argument("--list", action="store_true",
                    help="print the registered operating points as a table")
    args = ap.parse_args(argv)
    if args.list:
        print(f"# qgemm registry — {len(_REGISTRY)} cells "
              f"(tune: {default_tune().source or 'none'})")
        print(registry_table())
    else:
        ap.print_help()


if __name__ == "__main__":
    _main()
