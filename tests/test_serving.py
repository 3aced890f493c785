"""Serving-layer lockdown: continuous batching with per-slot positions and
the paged KV cache must be token-for-token identical to one-request-at-a-time
decode.

The batched-equals-sequential oracle is the test that catches the
aligned-position bug class: if the fused decode step shares one position
across slots, every slot that isn't at max(pos) rotates its query/key with
the wrong RoPE phase and writes KV at the wrong index — outputs still look
plausible, only an exact-token comparison notices. Run in f32 so both paths
compute identical algebra (row-wise ops only, so batch size cannot change
per-row results).
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import Request, Server, serve_params
from repro.models import transformer
from repro.models.common import ModelCtx

# mixed lengths spanning several prefill buckets (buckets: 4/8/16/32)
PROMPT_LENS = (3, 9, 14)
MAX_NEW = 4
CACHE_LEN = 32
PAGE_SIZE = 4


@functools.lru_cache(maxsize=None)
def _built(policy: str):
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(), policy=policy)
    sp = transformer.build_specs(cfg)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = transformer.pack_for_serve(params, cfg, plane_twins=True)
    return cfg, sp, sparams


def _prompts(cfg, lens=PROMPT_LENS, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32) for n in lens]


def _greedy_reference(cfg, sp, sparams, ctx, prompt, max_new):
    """Single-request decode on the seed-validated contiguous scalar-pos path."""
    logits, cache = transformer.prefill(sparams, jnp.asarray(prompt)[None], sp,
                                        ctx, cache_len=CACHE_LEN)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    while len(out) < max_new:
        l, cache = transformer.decode_step(
            sparams, cache, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.int32(pos), sp, ctx)
        out.append(int(jnp.argmax(l[0, 0])))
        pos += 1
    return out


def _serve(cfg, sparams, ctx, prompts, *, paged, slots=2, **kw):
    srv = Server(cfg, sparams, slots=slots, cache_len=CACHE_LEN, paged=paged,
                 page_size=PAGE_SIZE, ctx=ctx, **kw)
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, MAX_NEW))
    srv.run()
    assert len(srv.completed) == len(prompts)
    return srv


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("policy", ["binary", "ternary", "int8"])
def test_batched_equals_sequential(policy, backend):
    """N mixed-length requests through the paged continuous-batching server
    == single-slot sequential greedy decode, token for token, for all three
    W&A policies on both qgemm backends."""
    cfg, sp, sparams = _built(policy)
    ctx = ModelCtx(mode="serve", backend=backend, dtype=jnp.float32)
    prompts = _prompts(cfg)
    want = [_greedy_reference(cfg, sp, sparams, ctx, p, MAX_NEW)
            for p in prompts]
    srv = _serve(cfg, sparams, ctx, prompts, paged=True)
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (policy, backend, i, got[i], w)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_heterogeneous_policy_serves_token_exact(backend):
    """The 'het' policy assigns DIFFERENT operating points per layer class
    (s4 ffn_up next to ternary attn_out, int8 qkv) — the serve path resolves
    each layer's OperatingPoint from its own LayerQuant, not from one global
    flag pair — and the batched server must still be token-exact against the
    single-request reference."""
    cfg, sp, sparams = _built("het")
    mid = sp.mid[0] if sp.mid else sp.first  # per_class overrides first/last
    # the policy really is heterogeneous at the spec level
    assert mid.ffn.up.lq.weights.precision == "int4"
    assert mid.mixer.out.lq.weights.precision == "ternary"
    assert mid.mixer.qkv.lq.weights.precision == "int8"
    assert mid.ffn.up.lq != mid.mixer.out.lq
    # ...and each layer resolves its own registered operating point
    from repro.kernels import dispatch
    from repro.models.common import ModelCtx as _Ctx, operating_point
    ops = {nm: operating_point(s, _Ctx(mode="serve", backend=backend))
           for nm, s in (("ffn_up", mid.ffn.up), ("attn_out", mid.mixer.out))}
    assert ops["ffn_up"].key != ops["attn_out"].key
    for op in ops.values():
        dispatch.lookup(op)   # registered (would KeyError otherwise)
    ctx = ModelCtx(mode="serve", backend=backend, dtype=jnp.float32)
    prompts = _prompts(cfg)
    want = [_greedy_reference(cfg, sp, sparams, ctx, p, MAX_NEW)
            for p in prompts]
    srv = _serve(cfg, sparams, ctx, prompts, paged=True)
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, ("het", backend, i, got[i], w)


@pytest.mark.parametrize("policy", ["wt-a8", "w4a8"])
def test_mixed_wa_policies_serve(policy):
    """The pure mixed-cell policies (w-ternary×a-int8, w4a8) run the full
    continuous-batching path token-exactly vs the sequential reference."""
    cfg, sp, sparams = _built(policy)
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompts = _prompts(cfg, lens=(3, 9), seed=13)
    want = [_greedy_reference(cfg, sp, sparams, ctx, p, MAX_NEW)
            for p in prompts]
    srv = _serve(cfg, sparams, ctx, prompts, paged=True)
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (policy, i, got[i], w)


def test_contiguous_matches_paged():
    """The --contiguous reference layout and the paged layout serve the same
    traffic identically (per-slot positions on both)."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompts = _prompts(cfg, lens=(2, 11, 7, 15), seed=3)
    a = _serve(cfg, sparams, ctx, prompts, paged=True)
    b = _serve(cfg, sparams, ctx, prompts, paged=False)
    assert {r.rid: r.out for r in a.completed} == {r.rid: r.out for r in b.completed}


def test_slots_decode_at_their_own_positions():
    """Requests with different prompt lengths no longer share a decode
    position: some fused tick must carry distinct per-slot positions."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    srv = _serve(cfg, sparams, ctx, _prompts(cfg, lens=(3, 14)), paged=True)
    multi = [t for t in srv.pos_trace if len(t) > 1]
    assert multi, "no tick ever decoded two slots at once"
    assert any(len(set(t.tolist())) > 1 for t in multi), \
        f"slots always shared one position: {srv.pos_trace}"


def test_jit_cache_discipline():
    """Bucketed prefill: mixed prompt lengths compile at most len(buckets)
    prefill signatures plus one decode signature."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(11)
    lens = [int(rng.integers(1, CACHE_LEN + 1)) for _ in range(10)]
    prompts = [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)
               for n in lens]
    srv = _serve(cfg, sparams, ctx, prompts, paged=True, slots=3)
    assert srv.compile_counts["prefill"] <= len(srv.buckets), \
        (srv.compile_counts, srv.buckets)
    assert srv.compile_counts["decode"] == 1, srv.compile_counts
    total = srv.compile_counts["prefill"] + srv.compile_counts["decode"]
    assert total <= len(srv.buckets) + 1


def _pool_moves(hlo: str, layer_shape, stacked_shape) -> list[str]:
    """Ops of a compiled program that move a whole layer pool: a
    dynamic-slice whose result is one layer's pool (unit axes aside), or a
    dynamic-update-slice whose result is the stacked pool."""
    out = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\{[^}]*\} "
                      r"(dynamic-slice|dynamic-update-slice)\(", line)
        if m is None:
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        if (tuple(d for d in dims if d != 1) == layer_shape
                or (m.group(2) == "dynamic-update-slice"
                    and dims == stacked_shape)):
            out.append(line.strip())
    return out


def test_decode_updates_pool_in_place(monkeypatch):
    """The paged decode program updates the pool in place: its cache output
    aliases its cache input (donated: the tick's input cache is deleted),
    no op moves a whole layer pool, and every attention layer counts in
    stats["decode_inplace_layers"]. After several ticks the cache holds bit
    for bit what the layer scan with the pool as xs/ys writes (forced by
    turning `_pool_in_place` off) on the same requests, gather path."""
    cfg = dataclasses.replace(get_config("phi-3-vision-4.2b").reduced(),
                              policy="mixed", n_layers=5)
    params, _ = serve_params(cfg, seed=0)
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompts = _prompts(cfg, lens=(5, 11), seed=13)

    def serve():
        srv = Server(cfg, params, slots=2, cache_len=CACHE_LEN,
                     page_size=PAGE_SIZE, ctx=ctx)
        for i, p in enumerate(prompts):
            srv.submit(Request(i, p, 8))
        for _ in range(5):
            srv.step()
        old, admitted = srv.cache, srv.stats["admitted"]
        srv.step()                                  # a decode-only tick
        assert srv.stats["admitted"] == admitted == len(prompts)
        deleted = {l.is_deleted() for l in jax.tree.leaves(old)}
        P, M = srv.phys_slots, srv.max_pages
        z = lambda *s: jnp.zeros(s, jnp.int32)
        hlo = srv._decode.lower(srv.params, srv.cache, z(P, 1), z(P),
                                z(P, M)).compile().as_text()
        return srv, deleted, hlo

    srv, deleted, hlo = serve()
    with monkeypatch.context() as m:
        m.setattr(transformer, "_pool_in_place", lambda *a: False)
        ref, _, ref_hlo = serve()

    layer = srv.cache["first"]["k"].shape
    stacked = srv.cache["mid"]["b0"]["k"].shape
    assert stacked == (cfg.n_layers - 2,) + layer
    assert srv.stats["decode_inplace_layers"] == cfg.n_layers
    assert ref.stats["decode_inplace_layers"] == 0
    assert deleted == {True}
    n_params = len(jax.tree.leaves(srv.params))
    n_cache = len(jax.tree.leaves(srv.cache))
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}, may-alias\)",
                         hlo.split("\n", 1)[0])
    assert sorted(map(int, aliased)) == list(range(n_params,
                                                   n_params + n_cache))
    assert not _pool_moves(hlo, layer, stacked)
    assert _pool_moves(ref_hlo, layer, stacked)     # what the check catches
    assert (jax.tree.structure(srv.cache)
            == jax.tree.structure(ref.cache))
    for a, b in zip(jax.tree.leaves(srv.cache), jax.tree.leaves(ref.cache)):
        assert a.shape == b.shape and jnp.array_equal(a, b)
    assert ([r.out for r in srv.slot_req]
            == [r.out for r in ref.slot_req])


def test_admission_is_metered_by_page_budget():
    """With a pool that can only back one request's lifetime, two queued
    requests are served one at a time even though a second slot is free —
    and every page returns to the pool at the end."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
               for _ in range(2)]
    # each request needs pages_for(min(8 + 8 - 1, 32), 4) = 4 pages; 5 usable
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=6, ctx=ctx)
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, 8))
    srv.run()
    assert len(srv.completed) == 2
    assert all(len(t) == 1 for t in srv.pos_trace), \
        "page budget should have kept concurrency at 1"
    assert srv.pt.free_pages == srv.pt.usable_pages


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_windowed_arch_oracle(backend):
    """Sliding-window (local) layers: ring caches can't take padded prefill,
    so those archs bucket to the exact prompt length — and must still match
    the sequential reference through ring wraparound. Under "pallas" the
    mixed-arch model decodes with the fused paged-attn kernel on its global
    layers while the window layers keep their ring slabs (the
    `pages is not None and not window` bypass) — still token-exact."""
    cfg = dataclasses.replace(get_config("gemma3-4b").reduced(),
                              policy="ternary", window=8)   # force wraparound
    sp = transformer.build_specs(cfg)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = transformer.pack_for_serve(params, cfg)
    ctx = ModelCtx(mode="serve", dtype=jnp.float32, backend=backend)
    prompts = _prompts(cfg, lens=(3, 13), seed=21)
    max_new = 6          # positions cross the window=8 ring boundary
    want = [_greedy_reference(cfg, sp, sparams, ctx, p, max_new)
            for p in prompts]
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx)
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, max_new))
    srv.run()
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (i, got[i], w)


def test_submit_rejects_unservable_page_demand():
    """A request whose lifetime page demand exceeds the whole pool must be
    rejected at submit — queued, it would livelock run() forever."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=3, ctx=ctx)   # 2 usable pages
    prompt = np.arange(8, dtype=np.int32)
    with pytest.raises(ValueError):
        srv.submit(Request(0, prompt, 8))    # needs 4 pages, pool has 2
    srv.submit(Request(1, prompt[:4], 3))    # 6 tokens -> 2 pages: fits
    srv.run()
    assert len(srv.completed) == 1


def test_paged_long_decode_extends_pages():
    """A request whose decode crosses several page boundaries stays exact
    (extend-on-demand path) vs the sequential reference."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompt = _prompts(cfg, lens=(5,), seed=9)[0]
    max_new = 18     # 5 + 18 - 1 = 22 tokens -> 6 pages of 4
    want = _greedy_reference(cfg, sp, sparams, ctx, prompt, max_new)
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx)
    srv.submit(Request(0, prompt, max_new))
    srv.run()
    assert srv.completed[0].out == want


# -- chunked prefill + dispatch-ahead + EOS (PR 7) ----------------------------


def test_chunked_prefill_kv_byte_identical():
    """The chunked-prefill contract at its strongest: running a prompt
    through `transformer.prefill_chunk` in C-token chunks (C NOT dividing n,
    so the padded final chunk is exercised) writes the SAME BYTES into the
    paged pool as the whole-prompt bucketed `prefill` + `scatter_prefill`
    path, and the final chunk's last-position logits are bit-identical to
    the whole-prompt last-position logits. jit-vs-jit on both sides: eager
    vs jit fuses RoPE differently (~1 ulp in K), and the server only ever
    runs the jitted calls — byte identity is claimed for what actually
    executes, not for an eager reference."""
    import jax.tree_util as jtu

    from repro.launch import kv_cache

    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    P, max_pages = PAGE_SIZE, CACHE_LEN // PAGE_SIZE
    num_pages = 1 + max_pages
    n, bucket, C = 14, 16, 5     # C does not divide n: final chunk is padded
    prompt = _prompts(cfg, lens=(n,), seed=7)[0]

    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    prefill_j = jax.jit(lambda p, t, lp: transformer.prefill(
        p, t, sp, ctx, cache_len=CACHE_LEN, last_pos=lp))
    logits_w, rc = prefill_j(sparams, jnp.asarray(toks),
                             jnp.asarray([n - 1], jnp.int32))
    cache_w = transformer.init_cache(cfg, 1, CACHE_LEN, paged=(num_pages, P),
                                     kv_dtype=ctx.dtype)
    pm = kv_cache.paged_leaf_mask(cfg, 1, CACHE_LEN, num_pages, P)
    ids = np.arange(1, kv_cache.pages_for(n, P) + 1, dtype=np.int32)
    pad = kv_cache.pages_for(bucket, P) - len(ids)
    sc_ids = np.concatenate([ids, np.full(pad, kv_cache.NULL_PAGE, np.int32)])
    cache_w = kv_cache.scatter_prefill(cache_w, rc, 0, paged_mask=pm,
                                       page_ids=sc_ids, page_size=P)

    cache_c = transformer.init_cache(cfg, 1, CACHE_LEN, paged=(num_pages, P),
                                     kv_dtype=ctx.dtype)
    table = np.zeros((1, max_pages), np.int32)
    table[0, :len(ids)] = ids
    step = jax.jit(lambda pr, c, t, p0, rp, wp, nr, li:
                   transformer.prefill_chunk(pr, c, t, p0, sp, ctx,
                                             read_pages=rp, write_pages=wp,
                                             nreal=nr, last_idx=li))
    covered, logits_c = 0, None
    while covered < n:
        creal = min(C, n - covered)
        ct = np.zeros((1, C), np.int32)
        ct[0, :creal] = prompt[covered:covered + creal]
        li = creal - 1 if covered + creal == n else 0
        logits_c, cache_c = step(sparams, cache_c, jnp.asarray(ct),
                                 jnp.asarray([covered], jnp.int32),
                                 jnp.asarray(table), jnp.asarray(table),
                                 jnp.asarray([creal], jnp.int32),
                                 jnp.asarray([li], jnp.int32))
        covered += creal

    compared = 0
    for (pw, aw), (_, ac), (_, ispaged) in zip(
            jtu.tree_leaves_with_path(cache_w),
            jtu.tree_leaves_with_path(cache_c),
            jtu.tree_leaves_with_path(pm)):
        if not ispaged:
            continue
        compared += 1
        if aw.ndim == 5:     # scanned mid stack: (periods, pages, P, Hk, dh)
            gw = np.asarray(aw)[:, ids].reshape(
                aw.shape[0], -1, *aw.shape[-2:])[:, :n]
            gc = np.asarray(ac)[:, ids].reshape(
                ac.shape[0], -1, *ac.shape[-2:])[:, :n]
        else:
            gw = np.asarray(aw)[ids].reshape(-1, *aw.shape[-2:])[:n]
            gc = np.asarray(ac)[ids].reshape(-1, *ac.shape[-2:])[:n]
        assert np.array_equal(gw, gc), \
            f"pool bytes diverge at {jtu.keystr(pw)}"
    assert compared > 0, "no paged leaves compared — mask/layout changed?"
    assert np.array_equal(np.asarray(logits_w[0, -1]),
                          np.asarray(logits_c[0, 0]))


@pytest.mark.parametrize("dispatch_ahead", [True, False])
def test_chunked_serve_matches_sequential(dispatch_ahead):
    """Mixed-length traffic through the server with --chunk-tokens (chunk
    size NOT dividing the prompt lengths) == sequential greedy reference,
    token for token, with and without dispatch-ahead double buffering. The
    jit budget collapses to {chunk, decode}: no prefill bucket signatures."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompts = _prompts(cfg)
    want = [_greedy_reference(cfg, sp, sparams, ctx, p, MAX_NEW)
            for p in prompts]
    srv = _serve(cfg, sparams, ctx, prompts, paged=True, chunk_tokens=5,
                 dispatch_ahead=dispatch_ahead)
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (dispatch_ahead, i, got[i], w)
    assert srv.stats["chunk_ticks"] > 0
    assert srv.compile_counts["prefill"] == 0, srv.compile_counts
    assert srv.compile_counts["chunk"] == 1, srv.compile_counts
    assert srv.compile_counts["decode"] == 1, srv.compile_counts
    if dispatch_ahead:
        assert srv.stats["plan_hits"] > 0, srv.stats
    assert srv.pt.free_pages == srv.pt.usable_pages


def test_eos_retires_slot_and_frees_pages():
    """EOS retirement: a request stops the very step its eos token is
    sampled — output truncated at the EOS, the slot's pages back in the pool
    that same tick, and later ticks neither sample nor write KV for it
    (pos_trace stops growing once the server is drained). A co-running
    request without eos is unaffected."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    p_eos, p_other = _prompts(cfg, lens=(5, 9), seed=17)
    max_new = 6
    ref_eos = _greedy_reference(cfg, sp, sparams, ctx, p_eos, max_new)
    ref_other = _greedy_reference(cfg, sp, sparams, ctx, p_other, max_new)
    eos_tok = ref_eos[2]               # retire after the 3rd sampled token...
    k = ref_eos.index(eos_tok)         # ...or wherever it first appears
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx)
    req = Request(0, p_eos, max_new, eos=eos_tok)
    srv.submit(req)
    srv.submit(Request(1, p_other, max_new))
    while not req.done:
        srv.step()
    assert req.out == ref_eos[:k + 1]
    # pages freed the retire tick, not at drain (slot 1 still holds its own)
    assert srv.pt.held[0] == 0
    out_after_eos = list(req.out)
    srv.run()
    assert req.out == out_after_eos, "sampled past EOS"
    got = {r.rid: r.out for r in srv.completed}
    assert got[1] == ref_other
    assert srv.pt.free_pages == srv.pt.usable_pages
    # once drained, extra steps dispatch nothing (no KV writes, no samples)
    ticks = len(srv.pos_trace)
    for _ in range(3):
        assert srv.step() is False
    assert len(srv.pos_trace) == ticks


def test_byte_tokenizer_roundtrip_and_eos_serves():
    """data.tokenizer.ByteTokenizer: exact text round-trip, ids fit the
    reduced vocab, and an encoded prompt serves through the full path with
    Request.eos = ByteTokenizer.EOS wired up."""
    from repro.data.tokenizer import ByteTokenizer

    cfg, sp, sparams = _built("ternary")
    tok = ByteTokenizer(vocab=cfg.vocab)
    text = "BrainTTA: 35 fJ/op — ñaé"
    ids = tok.encode(text, eos=True)
    assert ids[0] == ByteTokenizer.BOS and ids[-1] == ByteTokenizer.EOS
    assert ids.max() < cfg.vocab
    assert tok.decode(ids) == text
    with pytest.raises(ValueError):
        ByteTokenizer(vocab=128)
    prompt = tok.encode("hi", eos=False)[:8].astype(np.int32)
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    want = _greedy_reference(cfg, sp, sparams, ctx, prompt, MAX_NEW)
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx)
    srv.submit(Request(0, prompt, MAX_NEW, eos=ByteTokenizer.EOS))
    srv.run()
    stop = (want.index(ByteTokenizer.EOS) + 1
            if ByteTokenizer.EOS in want else MAX_NEW)
    assert srv.completed[0].out == want[:stop]


def test_jit_counters_are_signature_exact():
    """compile_counts counts DISTINCT abstract signatures, not call-site
    traces: jax.clear_caches() forces a re-trace of already-seen signatures
    and must NOT inflate any counter, while a genuinely new prompt bucket
    afterwards must raise the prefill count by exactly one."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx)
    prompts = _prompts(cfg, lens=(3, 9), seed=23)   # buckets 4 and 16
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, 2))
    srv.run()
    before = dict(srv.compile_counts)
    assert before["prefill"] == 2 and before["decode"] == 1, before
    jax.clear_caches()          # evict every XLA executable: forced re-trace
    for i, p in enumerate(prompts):
        srv.submit(Request(10 + i, p, 2))
    srv.run()
    assert dict(srv.compile_counts) == before, \
        (srv.compile_counts, before, "re-trace of a seen signature counted")
    # a new bucket (len 5 -> bucket 8) is a genuinely new signature: +1
    srv.submit(Request(20, _prompts(cfg, lens=(5,), seed=29)[0], 2))
    srv.run()
    assert srv.compile_counts["prefill"] == before["prefill"] + 1
    assert srv.compile_counts["decode"] == before["decode"]


# -- self-speculative decoding + EOS truncation (PR 8) ------------------------


def test_retire_truncates_mid_batch_eos():
    """Regression for the `out[-1] == eos` retire test: a multi-token accept
    can land tokens PAST the stop token in one tick. _retire must truncate
    req.out at the FIRST EOS and retire the slot that same tick (pages
    freed), never letting post-EOS tokens survive or the slot keep
    decoding."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompt = _prompts(cfg, lens=(5,), seed=31)[0]
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx)
    req = Request(0, prompt, 8)
    srv.submit(req)
    srv.step()                       # admitted + first tokens sampled
    s = srv.slot_req.index(req)
    eos = int(max(req.out)) + 1      # a token the request never sampled
    req.eos = eos
    # simulate a speculative tick that emitted [x, EOS, y, z] at once
    head = list(req.out)
    req.out.extend([eos, 7, 9])
    srv.slot_pos[s] += 3
    srv._retire()
    assert req.done
    assert req.out == head + [eos], req.out
    assert srv.pt.held[s] == 0, "pages not freed on mid-batch EOS retire"


@pytest.mark.parametrize("policy", ["binary", "ternary", "int8", "w4a8"])
def test_spec_serving_matches_sequential(policy):
    """Self-speculative decoding (sign-plane draft, full-precision verify)
    is TOKEN-EXACT vs the sequential greedy oracle for every policy class —
    plane-composed draft cells where they exist (w4a8), per-layer popcount
    fallback elsewhere — with exactly one draft and one verify signature."""
    cfg, sp, sparams = _built(policy)
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompts = _prompts(cfg)
    want = [_greedy_reference(cfg, sp, sparams, ctx, p, MAX_NEW)
            for p in prompts]
    srv = _serve(cfg, sparams, ctx, prompts, paged=True,
                 spec_draft="planes:1", spec_k=3)
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (policy, i, got[i], w)
    assert srv.stats["spec_ticks"] > 0
    assert srv.compile_counts["draft"] == 1, srv.compile_counts
    assert srv.compile_counts["verify"] == 1, srv.compile_counts
    assert srv.pt.free_pages == srv.pt.usable_pages


def test_spec_serving_with_prefix_share_and_preempt():
    """Speculation composes with the full scheduler: prefix-shared prompts
    (CoW forks must cover the whole lookahead write range) and a pool tight
    enough to preempt mid-decode — still token-exact, and the swap images
    survive coverage extended past the decode position (the _preempt trim).
    Request 1 duplicates request 0 exactly, so the co-running pair shares
    its boundary page and must fork before draft/verify scribble in it."""
    cfg, sp, sparams = _built("w4a8")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(41)
    prefix = rng.integers(0, cfg.vocab, size=(PAGE_SIZE,)).astype(np.int32)
    mk = lambda n: np.concatenate(
        [prefix, rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)])
    p0 = mk(5)
    prompts = [p0, p0.copy(), mk(3), mk(7)]
    max_new = 6
    want = [_greedy_reference(cfg, sp, sparams, ctx, p, max_new)
            for p in prompts]
    srv = Server(cfg, sparams, slots=3, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=11, ctx=ctx,
                 prefix_share=True, preempt=True,
                 spec_draft="planes:2", spec_k=4)
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, max_new))
    srv.run()
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (i, got[i], w)
    assert srv.stats["shared_pages"] > 0, srv.stats
    assert srv.stats["cow_forks"] > 0, srv.stats
    assert srv.pt.free_pages == srv.pt.usable_pages


def test_spec_serving_eos_stops_inside_window():
    """An EOS sampled inside the speculative window retires the request with
    its output truncated exactly where the sequential oracle stops — accepted
    tokens past the stop token must not leak into req.out."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompt = _prompts(cfg, lens=(5,), seed=17)[0]
    max_new = 6
    ref = _greedy_reference(cfg, sp, sparams, ctx, prompt, max_new)
    eos_tok = ref[2]
    k = ref.index(eos_tok)
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx,
                 spec_draft="planes:1", spec_k=4)
    srv.submit(Request(0, prompt, max_new, eos=eos_tok))
    srv.run()
    assert srv.completed[0].out == ref[:k + 1], \
        (srv.completed[0].out, ref, k)
    assert srv.pt.free_pages == srv.pt.usable_pages


def test_spec_falls_back_where_verify_cannot_be_exact():
    """Archs that cannot replay a multi-token range exactly (window/recurrent
    state) silently fall back to sequential decoding instead of serving
    wrong tokens — and stay token-exact."""
    cfg = dataclasses.replace(get_config("gemma3-4b").reduced(),
                              policy="ternary", window=8)
    sp = transformer.build_specs(cfg)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = transformer.pack_for_serve(params, cfg)
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompt = _prompts(cfg, lens=(9,), seed=21)[0]
    want = _greedy_reference(cfg, sp, sparams, ctx, prompt, MAX_NEW)
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, ctx=ctx,
                 spec_draft="planes:1", spec_k=4)
    assert not srv.spec
    srv.submit(Request(0, prompt, MAX_NEW))
    srv.run()
    assert srv.completed[0].out == want
    assert srv.stats["spec_ticks"] == 0
