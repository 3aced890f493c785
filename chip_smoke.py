#!/usr/bin/env python3
"""Bring-up smoke on one TPU chip: serve phi-3-vision-4.2b (its Phi-3-mini
text decoder) at published widths and full depth, policy "mixed" (ternary
body, int8 first/last layers), through `Server` with the Pallas backend,
every kernel compiled, and check the results on the chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a four-chip host: TP and EP only

Weights are random, made from a fixed seed. The earlier lines report the
device, compile (warm-up) seconds, requests and tokens served, jit
signature counts and peak device memory; none of them is a benchmark
number. The last line is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}},
printed only when every check passed. Without a TPU the script exits 2
and prints no result.

The phases are functions so that tests/test_chip_smoke.py can drive them
on CPU at `ArchConfig.reduced()` size; `main` itself requires the TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.qlinear import QLinearSpec  # noqa: E402
from repro.kernels import dispatch, flash_attn, paged_attn  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import (Request, Server, configure_compile_cache,  # noqa: E402
                                serve_params)
from repro.models import attention, common, transformer  # noqa: E402
from repro.models.common import ModelCtx, operating_point  # noqa: E402

ARCH, MOE_ARCH, POLICY, SEED = "phi-3-vision-4.2b", "deepseek-moe-16b", \
    "mixed", 0
SLOTS, CACHE_LEN, MAX_NEW = 4, 512, 16
#: prompt lengths: six land in the 64 prefill bucket, two in the 256 one —
#: a multiple of 256 is where prefill attention runs the flash kernel
PROMPT_LENS = (40, 57, 200, 33, 61, 240, 48, 64)
#: qgemm rows checked per layer: a decode batch and a prefill bucket
CELL_ROWS = (8, 256)
#: relative L2 bound, Pallas path vs jnp path, on the prefill of the
#: longest prompt (flash bucket), teacher-forced: every layer of both paths
#: is fed the jnp path's input to that layer, and each layer's update (its
#: output minus its input) and the last-position logits are compared.
#: Every qgemm cell is bit-exact between the backends and the model rounds
#: explicitly wherever XLA could keep excess precision, so one layer differs
#: only where the flash kernel and the blockwise scan order or approximate
#: their f32 softmax arithmetic (exp, row sum, divide): a bf16 ulp (2^-8)
#: on a small share of attention outputs, and through the next quantizer
#: only where a value sits at a code boundary. 1e-2 leaves room for that;
#: a wrong layout, scale, head or page moves a layer's update by O(1)
#: (unrelated vectors sit at sqrt 2). On a v5e the worst layer read 8.8e-5.
#: Fed its own outputs instead, the random ternary stack carries such a
#: last-bit difference to O(1) by depth 32 (0.50 on a v5e, where the jnp
#: path against itself with a 128-key kv block reads 0.48): the
#: free-running logits are printed, not bounded, and the first-token gate
#: holds the served path end to end.
LOGIT_REL_TOL = 1e-2
#: attention kernels against jnp / dense f32 references at the model's
#: head geometry, as a share of the largest output. The kernels round the
#: softmax weights and their output to bf16 (2^-9 relative each) and order
#: their f32 sums differently (online vs dense softmax); on CPU at 32x96
#: heads that came to 0 for flash and 0.002 for paged decode. 2^-7 is four
#: bf16 roundings; a wrong page, mask or scale is off by O(1).
ATTN_TOL = 2.0 ** -7


class SmokeFailure(AssertionError):
    """A check of the smoke failed; the script exits non-zero."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def model_config(arch: str = ARCH, *, reduced: bool = False):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    return dataclasses.replace(cfg, policy=POLICY)


def serve_ctx(backend: str) -> ModelCtx:
    return ModelCtx(mode="serve", backend=backend)


def prompts(cfg, lens=PROMPT_LENS, seed: int = SEED) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)
            for n in lens]


# -- the qgemm layers of a model ----------------------------------------------

def _walk(specs, params, name):
    for f in dataclasses.fields(specs):
        v = getattr(specs, f.name)
        if f.name not in params or v is None:
            continue
        if isinstance(v, QLinearSpec):
            yield f"{name}.{f.name}", v, params[f.name]
        elif dataclasses.is_dataclass(v):
            yield from _walk(v, params[f.name], f"{name}.{f.name}")


def stack_layers(sp, sparams):
    """(name, block specs, params) of every layer in serving order: the first
    block, each scanned period's blocks (sliced out one at a time), the
    remainder, the last block."""
    yield "first", sp.first, sparams["first"]
    for i in range(sp.n_periods):
        for t, bs in enumerate(sp.mid):
            yield (f"mid{i}.b{t}", bs,
                   jax.tree.map(lambda a: a[i], sparams["mid"][f"b{t}"]))
    for t, bs in enumerate(sp.rem):
        yield f"rem{t}", bs, sparams[f"rem{t}"]
    yield "last", sp.last, sparams["last"]


def qgemm_layers(cfg, sparams):
    """(name, spec, packed params) of every quantized linear the model
    serves, the lm_head last."""
    sp = transformer.build_specs(cfg)
    for name, bs, p in stack_layers(sp, sparams):
        yield from _walk(bs, p, name)
    if "lm_head" in sparams:
        yield "lm_head", sp.lm_head, sparams["lm_head"]


def check_cells(cfg, sparams, *, rows=CELL_ROWS):
    """Every qgemm layer resolves to a cell with a Pallas body, and each
    distinct (cell, K, N) the policy uses is bit-exact between the Pallas
    and jnp backends on the layer's own packed weights. Where kernels
    compile (not interpreted), the compiled program must hold the kernel
    (`tpu_custom_call`). Returns one row per checked (cell, shape, M)."""
    ctx = serve_ctx("pallas")
    compiled = not dispatch.pallas_interpret()
    seen, out = set(), []
    rng = np.random.default_rng(SEED)
    for name, spec, p in qgemm_layers(cfg, sparams):
        op = operating_point(spec, ctx)
        cell = dispatch.lookup(op)
        check(cell.body is not None,
              f"{name} resolved to {cell.tag}, which has no Pallas body: "
              f"--backend pallas would run it through XLA")
        key = (cell.key, spec.in_dim, spec.out_dim, spec.experts)
        if key in seen:
            continue
        seen.add(key)
        for m in rows:
            lead = (spec.experts, m) if spec.experts else (m,)
            x = jnp.asarray(rng.standard_normal(lead + (spec.in_dim,)),
                            jnp.bfloat16)
            exe = {be: jax.jit(lambda p_, x_, o=dataclasses.replace(
                       op, backend=be), s=spec: dispatch.qgemm(p_, x_, s, o)
                       ).lower(p, x).compile() for be in ("pallas", "jnp")}
            check(not compiled or "tpu_custom_call" in exe["pallas"].as_text(),
                  f"{name} {cell.tag}: no Pallas kernel in the program")
            got, want = (np.asarray(exe[be](p, x), np.float32)
                         for be in ("pallas", "jnp"))
            check(np.array_equal(got, want),
                  f"{name} {cell.tag} K{spec.in_dim} N{spec.out_dim} M{m}: "
                  f"pallas != jnp at {int((got != want).sum())} elements")
            out.append({"layer": name, "cell": cell.tag, "k": spec.in_dim,
                        "n": spec.out_dim, "m": m})
    return out


def _attn_error(got, want) -> tuple[float, int]:
    """(max error as a share of the largest output, elements that differ)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if not np.all(np.isfinite(got)):
        return float("inf"), got.size
    return (float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
            int(np.sum(got != want)))


def attention_errors(cfg, *, t=256, slots=SLOTS, cache_len=CACHE_LEN,
                     page_size=32):
    """The flash prefill kernel against the jnp blockwise attention, and the
    fused paged decode kernel against a dense f32 softmax over the gathered
    pages, at the model's head geometry on random bf16 inputs. Returns
    {name: (max error as a share of the largest output, elements that
    differ, elements)}."""
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(SEED)
    rand = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.bfloat16)
    q, k, v = rand(1, t, h, dh), rand(1, t, hk, dh), rand(1, t, hk, dh)
    heads_first = lambda a: jnp.moveaxis(a, 2, 1).reshape(-1, t, dh)
    flash = jax.jit(lambda q_, k_, v_: flash_attn.flash_attention(
        heads_first(q_), heads_first(k_), heads_first(v_)))(q, k, v)
    blockwise = jax.jit(lambda q_, k_, v_: attention.blockwise_attention(
        q_, k_, v_, causal=True))(q, k, v)
    out = {"flash": _attn_error(flash, heads_first(blockwise))
           + (flash.size,)}

    max_pages = cache_len // page_size
    pool = lambda: rand(1 + slots * max_pages, page_size, hk, dh)
    kp, vp = pool(), pool()
    pages = 1 + np.arange(slots * max_pages, dtype=np.int32).reshape(
        slots, max_pages)
    pos = np.asarray([cache_len - 1, 300, 40, 129][:slots], np.int32)
    qd = rand(slots, h, dh)
    walk = jax.jit(paged_attn.paged_flash_decode)(
        qd, kp, vp, jnp.asarray(pages), jnp.asarray(pos))
    kf = np.asarray(kp, np.float32)[pages].reshape(slots, -1, hk, dh)
    vf = np.asarray(vp, np.float32)[pages].reshape(slots, -1, hk, dh)
    qg = np.asarray(qd, np.float32).reshape(slots, hk, h // hk, dh)
    sc = np.einsum("bhgd,bshd->bhgs", qg, kf) / dh ** 0.5
    sc = np.where(np.arange(kf.shape[1]) <= pos[:, None, None, None], sc,
                  -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    dense = np.einsum("bhgs,bshd->bhgd", pr, vf).reshape(slots, h, dh)
    out["paged decode"] = _attn_error(walk, dense) + (walk.size,)
    return out


# -- serving ------------------------------------------------------------------

def make_server(cfg, sparams, backend: str, *, mesh=None, moe_ep=True,
                slots=SLOTS, cache_len=CACHE_LEN) -> Server:
    return Server(cfg, sparams, slots=slots, cache_len=cache_len,
                  ctx=serve_ctx(backend), mesh=mesh, moe_ep=moe_ep)


def serve(srv: Server, reqs, max_new: int, rid0: int = 0) -> dict:
    """Serve `reqs` to completion; {rid: tokens} of this batch."""
    for i, p in enumerate(reqs):
        srv.submit(Request(rid0 + i, p, max_new))
    srv.run()
    out = {r.rid - rid0: list(r.out) for r in srv.completed
           if rid0 <= r.rid < rid0 + len(reqs)}
    check(len(out) == len(reqs) and all(len(t) == max_new
                                        for t in out.values()),
          f"served {len(out)} of {len(reqs)} requests in full")
    return out


def prefill_logits(srv: Server, prompt) -> np.ndarray:
    """Last-position logits of one prompt through the server's own jitted
    prefill (same bucket and padding as serving)."""
    n = len(prompt)
    toks = np.zeros((1, srv._bucket(n)), np.int32)
    toks[0, :n] = prompt
    res = srv._prefill(srv.params, jnp.asarray(toks),
                       jnp.asarray([n - 1], jnp.int32))
    return np.asarray(res[0], np.float32).reshape(-1)


def teacher_forced(cfg, sparams, prompt, bucket: int, got_ctx: ModelCtx,
                   want_ctx: ModelCtx):
    """Prefill of one prompt with every layer of both paths fed the `want`
    path's input to that layer, padded to `bucket` as the server pads it.
    Returns (per layer: relative L2 of the `got` layer's update against the
    `want` layer's over the real positions; the last-position logits of the
    `got` head on the `got` last layer's output; the `want` ones)."""
    sp = transformer.build_specs(cfg)
    n = len(prompt)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    fns = {}

    def step(bs, ctx):
        key = (id(bs), id(ctx))
        if key not in fns:
            fns[key] = jax.jit(lambda p, x: transformer.block_prefill(
                p, x, bs, cfg, ctx, cache_len=bucket)[0])
        return fns[key]

    x = common.embed_apply(sparams["embed"], jnp.asarray(toks), want_ctx.dtype)
    rels, outs = [], {}
    for _, bs, p in stack_layers(sp, sparams):
        outs = {c: step(bs, ctx)(p, x)
                for c, ctx in (("got", got_ctx), ("want", want_ctx))}
        upd = {c: np.asarray(o[0, :n], np.float32)
               - np.asarray(x[0, :n], np.float32) for c, o in outs.items()}
        rels.append(float(np.linalg.norm(upd["got"] - upd["want"])
                          / np.linalg.norm(upd["want"])))
        x = outs["want"]
    head = {c: np.asarray(jax.jit(lambda p, h, ctx=ctx: transformer._logits(
                p, h, sp, ctx))(sparams, outs[c][:, n - 1:n]),
            np.float32).reshape(-1)
            for c, ctx in (("got", got_ctx), ("want", want_ctx))}
    return rels, head["got"], head["want"]


def compare_logits(got: np.ndarray, want: np.ndarray,
                   tol: float = LOGIT_REL_TOL) -> float:
    check(np.all(np.isfinite(got)), "non-finite prefill logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    check(rel <= tol, f"prefill logits: relative L2 {rel:.3g} > {tol}")
    return rel


def one_chip(cfg, sparams, log=print):
    """The one-chip phases: cells, serve (warm-up then again), the attention
    kernels against their jnp twins, prefill logits and first tokens
    against the jnp path. Each reading is logged before it is gated.
    Returns a summary dict."""
    t = time.perf_counter()
    cells = check_cells(cfg, sparams)
    log(f"qgemm cells bit-exact pallas == jnp: {len(cells)} "
        f"(cell x shape x M) checks, "
        f"{sorted({c['cell'] for c in cells})}, "
        f"{time.perf_counter() - t:.1f}s")

    reqs = prompts(cfg)
    srv = make_server(cfg, sparams, "pallas")
    t = time.perf_counter()
    warm = serve(srv, reqs, MAX_NEW)
    warm_s = time.perf_counter() - t
    t = time.perf_counter()
    toks = serve(srv, reqs, MAX_NEW, rid0=len(reqs))
    again_s = time.perf_counter() - t
    check(toks == warm, "second pass served different tokens")
    n_tok = sum(len(v) for v in toks.values())
    log(f"warm-up (compile + first serve): {warm_s:.1f}s; served "
        f"{len(toks)} requests, {n_tok} tokens again in {again_s:.1f}s "
        f"(host wall time, not a benchmark)")
    log(f"jit signatures: {srv.compile_counts} (buckets {list(srv.buckets)},"
        f" prompt lengths {list(PROMPT_LENS)})")

    attn = attention_errors(cfg)
    log(f"attention kernels at {cfg.n_heads}x{cfg.head_dim} heads, max error "
        f"of the largest output (bound {ATTN_TOL!r}), elements differing: "
        + "; ".join(f"{k} {e!r}, {n}/{size}"
                    for k, (e, n, size) in attn.items()))
    for k, (e, _, _) in attn.items():
        check(e <= ATTN_TOL, f"{k}: max error {e!r} of the largest output "
                             f"> {ATTN_TOL!r}")

    ref = make_server(cfg, sparams, "jnp")
    first = serve(ref, reqs, 1)
    differ = [i for i in toks if toks[i][0] != first[i][0]]
    log(f"first tokens pallas vs jnp: {len(reqs) - len(differ)}/{len(reqs)} "
        f"agree (differ: requests {differ}, prompt lengths "
        f"{[len(reqs[i]) for i in differ]})")
    long = max(range(len(reqs)), key=lambda i: len(reqs[i]))
    bucket = srv._bucket(len(reqs[long]))
    free = {len(reqs[i]): compare_logits(*(prefill_logits(s, reqs[i])
                                           for s in (srv, ref)), tol=np.inf)
            for i in (long, min(range(len(reqs)),
                                key=lambda i: len(reqs[i])))}
    log(f"free-running prefill logits pallas vs jnp, relative L2 by prompt "
        f"length (not bounded, see LOGIT_REL_TOL): {free!r}")
    layer_rel, got, want = teacher_forced(cfg, sparams, reqs[long], bucket,
                                          srv.ctx, ref.ctx)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    worst = int(np.argmax(layer_rel))
    log(f"teacher-forced prefill pallas vs jnp ({len(reqs[long])}-token "
        f"prompt, bucket {bucket}, flash): layer updates relative L2 max "
        f"{layer_rel[worst]!r} (layer {worst} of {len(layer_rel)}), "
        f"{sum(r == 0 for r in layer_rel)} layers identical; logits "
        f"{rel!r}; bound {LOGIT_REL_TOL!r}")
    check(not differ, f"first token differs pallas vs jnp for requests "
                      f"{differ}")
    check(layer_rel[worst] <= LOGIT_REL_TOL,
          f"teacher-forced layer {worst}: update relative L2 "
          f"{layer_rel[worst]:.3g} > {LOGIT_REL_TOL}")
    compare_logits(got, want)
    return {"cells": len(cells), "requests": len(toks), "tokens": n_tok,
            "warmup_s": warm_s, "logit_rel_l2": rel,
            "layer_rel_l2": layer_rel, "free_rel_l2": free,
            "first_token_agree": len(reqs) - len(differ),
            "attn_err": {k: e for k, (e, _, _) in attn.items()}}


# -- four chips ---------------------------------------------------------------

def tp_phase(cfg, sparams, mesh, log=print, *, slots=SLOTS,
             cache_len=CACHE_LEN, max_new=MAX_NEW):
    """TP serving over `mesh` is token-exact against one device, same
    requests, same packed weights, same process."""
    reqs = prompts(cfg)
    want = serve(make_server(cfg, sparams, "pallas", slots=slots,
                             cache_len=cache_len), reqs, max_new)
    got = serve(make_server(cfg, sparams, "pallas", mesh=mesh, slots=slots,
                            cache_len=cache_len), reqs, max_new)
    check(got == want, f"TP mesh {dict(mesh.shape)} diverged from one "
                       f"device: {got} vs {want}")
    log(f"TP {dict(mesh.shape)}: {len(got)} requests token-exact vs one "
        f"device")


def ep_phase(cfg, mesh, log=print, *, lens=(40, 57, 33, 61), max_new=8,
             slots=SLOTS, cache_len=256):
    """EP serving of an MoE model, params created sharded on `mesh`: token-
    and stat-exact against the dense expert vmap on the same mesh."""
    t = time.perf_counter()
    sparams, _ = serve_params(cfg, seed=SEED, mesh=mesh)
    jax.block_until_ready(sparams)
    log(f"{cfg.name}: sharded params built in "
        f"{time.perf_counter() - t:.1f}s")
    reqs = prompts(cfg, lens)
    runs = {}
    for ep in (False, True):
        srv = make_server(cfg, sparams, "pallas", mesh=mesh, moe_ep=ep,
                          slots=slots, cache_len=cache_len)
        check((srv.ctx.ep is not None) == ep, "EP switch not honoured")
        runs[ep] = (serve(srv, reqs, max_new), srv.stats)
    (want, ref), (got, st) = runs[False], runs[True]
    check(got == want, f"EP diverged from the dense expert vmap: "
                       f"{got} vs {want}")
    for k in ("moe_routed", "moe_dropped", "moe_expert_tokens"):
        check(st[k] == ref[k], f"EP stat {k}: {st[k]} vs {ref[k]}")
    log(f"EP {dict(mesh.shape)} {cfg.name}: {len(got)} requests token- and "
        f"stat-exact vs dense expert vmap (routed {st['moe_routed']}, "
        f"dropped {st['moe_dropped']})")


# -- entry point --------------------------------------------------------------

def _device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip TP and EP phases")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devs)}",
              file=sys.stderr)
        return 2
    check(not dispatch.pallas_interpret(), "Pallas would run interpreted")
    cache = configure_compile_cache()
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache}", flush=True)

    cfg = model_config(ARCH)
    t = time.perf_counter()
    sparams, train_b = serve_params(cfg, seed=SEED)
    jax.block_until_ready(sparams)
    serve_b = sum(x.nbytes for x in jax.tree.leaves(sparams))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; policy {cfg.policy}, backend "
          f"pallas; packed {serve_b / 2**30:.2f} GiB (training layout "
          f"{train_b / 2**30:.2f} GiB, never resident) in "
          f"{time.perf_counter() - t:.1f}s", flush=True)
    log = lambda s: print(s, flush=True)

    try:
        if args.four_chips:
            mesh = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
            tp_phase(cfg, sparams, mesh, log)
            del sparams
            ep_phase(model_config(MOE_ARCH), mesh, log)
            devs = devs[:4]
        else:
            one_chip(cfg, sparams, log)
            devs = devs[:1]
    finally:
        stats = dev.memory_stats() or {}
        print(f"peak_bytes_in_use: "
              f"{stats.get('peak_bytes_in_use', 'not reported')} (device 0)",
              flush=True)
    print(json.dumps({"ok": True, "device": _device_line(devs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
