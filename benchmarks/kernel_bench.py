"""Dispatch-registry micro-bench: interpret-mode wall time (correctness-scale)
+ the analytic TPU tile model, for EVERY registered operating point.

Driven by the `repro.kernels.dispatch` registry: each cell is benched through
the single `qgemm` entry point keyed by its `OperatingPoint` (so the bench
exercises exactly the code the serve stack runs — activation prep, padding,
TuneTable tile resolution, fused bias epilogue and all). Cells with a Pallas
MacBody run on the pallas backend; weight-only/dense cells run their jnp
formulation. Registering a new precision/kernel variant adds a bench row
automatically.

Wall time in interpret mode is NOT TPU performance — it validates the
kernels execute and lets us compare formulations structurally. The derived
column is the VMEM working set of the resolved tile (must be << 128 MiB),
from `harness.vmem_tile_bytes`.

Outputs:
  * a CSV-ish table on stdout (the `benchmarks.run` report format)
  * `BENCH_dispatch.json` — the machine-readable per-operating-point
    baseline the perf trajectory tracks across PRs (--out to relocate)
  * `--retune` — sweep candidate `Tile`s per cell and rewrite the shipped
    `kernels/tune_cpu.json` TuneTable (the "autotune per operating point"
    data file; on a TPU host the kernels compile instead of interpreting)
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.core import qlinear
from repro.core.precision import LayerQuant
from repro.core.quantize import QuantSpec
from repro.kernels import dispatch, harness
from repro.kernels.dispatch import OperatingPoint, Tile, TuneTable

M, K, N = 128, 1024, 128


def _cell_problem(cell, seed=0):
    spec = qlinear.QLinearSpec(
        K, N, LayerQuant(QuantSpec(cell.wprec), QuantSpec(cell.aprec)))
    p = qlinear.pack_params(
        qlinear.init(jax.random.PRNGKey(seed), spec), spec)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, K)) * 0.2
    return spec, p, x


def _cell_op(cell, tile: Tile | None = None) -> OperatingPoint:
    impl = "popcount" if cell.impl == "*" else cell.impl
    backend = "pallas" if cell.body is not None else "jnp"
    return OperatingPoint(cell.wprec, cell.aprec, impl, backend, tile=tile)


def _time_us(fn, reps: int = 3) -> float:
    jax.block_until_ready(fn())                       # compile outside timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def run():
    rows = []
    for key in sorted(dispatch.cells()):
        cell = dispatch.cells()[key]
        spec, p, x = _cell_problem(cell)
        op = _cell_op(cell)
        us = _time_us(lambda: dispatch.qgemm(p, x, spec, op))
        tile = op.tile or dispatch.default_tune().tile_for(op) or Tile()
        vmem = (harness.vmem_tile_bytes(cell.body, tile)
                if cell.body is not None else None)
        rows.append({
            "op": {"wprec": op.wprec, "aprec": op.aprec, "impl": op.impl,
                   "backend": op.backend},
            "name": cell.body.name if cell.body is not None else cell.tag,
            "us_per_call": round(us, 1),
            "tile": {"bm": tile.bm, "bn": tile.bn, "bkq": tile.bkq},
            "vmem_tile_bytes": vmem,
        })

    from repro.kernels.flash_attn import flash_attention
    ks3 = jax.random.split(jax.random.PRNGKey(3), 3)
    qf = jax.random.normal(ks3[0], (4, 256, 64), jnp.float32)
    kf = jax.random.normal(ks3[1], (2, 256, 64), jnp.float32)
    vf = jax.random.normal(ks3[2], (2, 256, 64), jnp.float32)
    fa_us = _time_us(lambda: flash_attention(qf, kf, vf, causal=True,
                                             bq=128, bk=128))
    rows.append({"op": None, "name": "flash_attn",
                 "us_per_call": round(fa_us, 1), "tile": None,
                 "vmem_tile_bytes": 128 * 64 * 4 * 2 + 128 * 64 * 4 + 2 * 128 * 4})
    rows.extend(_paged_attn_rows())
    return rows


# decode-attention geometry for the paged-attn sweep/retune: the reduced-
# llama serve head shape over `slots` continuous-batching rows
PA_SLOTS, PA_HK, PA_HQ, PA_DH = 4, 2, 4, 32


def _paged_attn_problem(page_size: int, table_pages: int, active: int,
                        seed: int = 5):
    """Int8 pool + fully-provisioned disjoint page tables + a uniform active
    length: the paged_flash_decode bench unit (pool size x active length x
    page size)."""
    import numpy as np
    num_pages = 1 + PA_SLOTS * table_pages
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (PA_SLOTS, PA_HQ, PA_DH), jnp.float32)
    kp = jax.random.randint(ks[1], (num_pages, page_size, PA_HK, PA_DH),
                            -127, 128, jnp.int8)
    vp = jax.random.randint(ks[2], (num_pages, page_size, PA_HK, PA_DH),
                            -127, 128, jnp.int8)
    pages = jnp.asarray(np.stack(
        [1 + r * table_pages + np.arange(table_pages)
         for r in range(PA_SLOTS)]).astype(np.int32))
    pos = jnp.full((PA_SLOTS,), active - 1, jnp.int32)
    return q, kp, vp, pages, pos


def _paged_attn_rows():
    """Sweep the paged-attn decode kernel like the qgemm cells: rows keyed by
    its TuneTable pseudo-cell, one per (pool size x active length x page
    size) point. The active-length column is where the in-kernel early bound
    shows up (same provisioned table, shorter walk)."""
    from repro.kernels import paged_attn as pa
    bkp = pa.resolve_pages_per_block()
    rows = []
    for page_size, table_pages, active in [
        (16, 64, 256), (16, 64, 1024),          # 1k-token pool, small pages
        (64, 64, 1024), (64, 64, 4096),         # 4k-token pool
        (64, 128, 4096),                        # 8k-token pool, half active
    ]:
        q, kp, vp, pages, pos = _paged_attn_problem(page_size, table_pages,
                                                    active)
        us = _time_us(lambda: pa.paged_flash_decode(
            q, kp, vp, pages, pos, pages_per_block=bkp))
        rows.append({
            "op": {"wprec": "paged_attn", "aprec": "decode", "impl": "*",
                   "backend": "pallas"},
            "name": f"paged_attn P{page_size}xT{table_pages}@a{active}",
            "us_per_call": round(us, 1),
            "tile": {"bm": 1, "bn": 1, "bkq": bkp},
            "vmem_tile_bytes": pa.vmem_decode_tile_bytes(
                page_size, PA_HK, PA_DH, PA_HQ, bkp, kv_bytes=1),
        })
    return rows


def retune(out_path: str, reps: int = 2) -> TuneTable:
    """Sweep candidate Tiles per Pallas cell, keep the fastest, save a
    TuneTable. On CPU the kernels run interpreted, so the numbers are a
    structural baseline; run on a TPU host for production tables."""
    tiles: dict[tuple, Tile] = {}
    for key in sorted(dispatch.cells()):
        cell = dispatch.cells()[key]
        if cell.body is None:
            continue
        spec, p, x = _cell_problem(cell)
        dflt = cell.body.default_bkq
        candidates = [Tile(128, 128, dflt), Tile(64, 128, dflt),
                      Tile(128, 128, max(dflt // 2, 1)),
                      Tile(128, 128, dflt * 2)]
        best, best_us = None, float("inf")
        for tile in candidates:
            op = _cell_op(cell, tile=tile)
            us = _time_us(lambda: dispatch.qgemm(p, x, spec, op), reps=reps)
            if us < best_us:
                best, best_us = tile, us
        tiles[cell.key] = best
        print(f"  {cell.tag:24s} -> bm={best.bm} bn={best.bn} "
              f"bkq={best.bkq} ({best_us:.0f}us)")

    # paged-attn pseudo-cell: bkq = pages per kv block of the decode page
    # walk (bm/bn unused). Representative point: 4k-token pool, 1k active.
    from repro.kernels import paged_attn as pa
    q, kp, vp, pages, pos = _paged_attn_problem(64, 64, 1024)
    best_bkp, best_us = None, float("inf")
    for bkp in (1, 2, 4, 8):
        us = _time_us(lambda: pa.paged_flash_decode(
            q, kp, vp, pages, pos, pages_per_block=bkp), reps=reps)
        if us < best_us:
            best_bkp, best_us = bkp, us
    tiles[pa.TUNE_KEY] = Tile(1, 1, best_bkp)
    print(f"  {'paged_attn/decode/*':24s} -> bkq={best_bkp} "
          f"(pages/block, {best_us:.0f}us)")
    # carry rows the sweep didn't remeasure (e.g. a real-TPU table's wildcard
    # entries), then prune keys no registered cell can resolve anymore —
    # renamed impls / retired precision pairs must not ride along forever
    import os
    if os.path.exists(out_path):
        for key, tile in TuneTable.load(out_path).tiles.items():
            tiles.setdefault(key, tile)
    tiles, dropped = dispatch.prune_stale_tiles(tiles,
                                                extra_keys=(pa.TUNE_KEY,))
    for key in dropped:
        print(f"  pruned stale row {'/'.join(key)} (no registered cell)")
    table = TuneTable(
        tiles=tiles,
        source=f"kernel_bench --retune: interpret-mode CPU, m{M} k{K} n{N}, "
               f"jax {jax.__version__}")
    table.save(out_path)
    print(f"wrote {len(tiles)} cell tiles to {out_path}")
    return table


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_dispatch.json",
                    help="per-operating-point baseline JSON (perf trajectory)")
    ap.add_argument("--no-json", action="store_true",
                    help="stdout table only (benchmarks.run aggregate mode)")
    ap.add_argument("--retune", action="store_true",
                    help="sweep Tiles per cell and rewrite the shipped "
                         "TuneTable instead of benching")
    ap.add_argument("--tune-out", default=dispatch.DEFAULT_TUNE_PATH)
    args = ap.parse_args(argv)

    if args.retune:
        print("# kernel_bench --retune (per-cell Tile sweep)")
        retune(args.tune_out)
        return

    print("# kernel_bench (interpret-mode validation + VMEM tile model)")
    print("op,name,us_per_call,tile,vmem")
    rows = run()
    for r in rows:
        op = r["op"]
        optag = (f"w{op['wprec']}/a{op['aprec']}/{op['impl']}@{op['backend']}"
                 if op else "-")
        tile = r["tile"]
        tstr = f"{tile['bm']}x{tile['bn']}x{tile['bkq']}" if tile else "-"
        vm = (f"{r['vmem_tile_bytes']/2**10:.0f}KiB"
              if r["vmem_tile_bytes"] else "-")
        print(f"{optag},{r['name']},{r['us_per_call']:.0f},{tstr},{vm}")
    if not args.no_json:
        with open(args.out, "w") as f:
            json.dump({"bench": "dispatch_qgemm", "m": M, "k": K, "n": N,
                       "interpret": dispatch.pallas_interpret(),
                       "tune_source": dispatch.default_tune().source,
                       "rows": rows}, f, indent=2)
            f.write("\n")
        print(f"(baseline written to {args.out})")


if __name__ == "__main__":
    main()
