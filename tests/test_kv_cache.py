"""PageTable invariants under random admit/extend/retire traces — now with
prefix sharing (refcounted hash-indexed pages), copy-on-write forks, and
preemption swap in/out.

The page pool is the correctness foundation of the paged serving path: a
refcount that drifts from the table silently cross-contaminates or leaks
pages, a stale share-index entry hands a freed page to a new request, a CoW
fork that drops the source's bytes corrupts every co-owner, and a coverage
mismatch (pages != tokens) makes the decode write index run off the slot's
page list. Property-test all of it with random traces (hypothesis, or the
deterministic fallback shim).
"""
import random

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.launch import kv_cache
from repro.launch.kv_cache import (NULL_PAGE, PageTable, pages_for,
                                   prefix_keys)


def _check_invariants(pt: PageTable, model: dict):
    owned = [int(p) for s in range(pt.slots) for p in pt.table[s, : pt.held[s]]]
    distinct = set(owned)
    # the scratch page is never handed out
    assert NULL_PAGE not in distinct
    # refcount == number of (slot, index) table mappings, for every page —
    # in particular a page is mapped by at most one slot unless it is shared
    counts: dict[int, int] = {}
    for p in owned:
        counts[p] = counts.get(p, 0) + 1
    for p in range(pt.num_pages):
        assert int(pt.refcount[p]) == counts.get(p, 0), \
            (p, counts.get(p, 0), int(pt.refcount[p]))
    # free + distinct-owned == pool (minus the reserved scratch page):
    # a page is freed exactly when its refcount hits zero
    assert pt.free_pages + len(distinct) == pt.num_pages - 1
    assert distinct.isdisjoint(pt._free)
    # stats() reports occupancy over USABLE pages: page 0 scratch is not
    # demand, live == usable - free == distinct owned, occupancy in [0, 1]
    st = pt.stats()
    assert st["usable_pages"] == pt.num_pages - 1
    assert st["free_pages"] == pt.free_pages
    assert st["live_pages"] == st["usable_pages"] - st["free_pages"] \
        == len(distinct)
    assert st["occupancy"] == pytest.approx(
        len(distinct) / st["usable_pages"])
    assert 0.0 <= st["occupancy"] <= 1.0
    # the share index only ever points at live pages, bijectively
    for key, p in pt._index.items():
        assert int(pt.refcount[p]) >= 1, (key, p)
        assert pt._page_key[p] == key
    assert len(pt._page_key) == len(pt._index)
    for s in range(pt.slots):
        if pt.active[s]:
            # per-slot pages cover exactly the slot's tokens (pos + 1)
            assert int(pt.tokens[s]) == model[s]
            assert int(pt.held[s]) == pages_for(model[s], pt.page_size)
        else:
            assert s not in model
            assert int(pt.held[s]) == 0 and int(pt.tokens[s]) == 0
        # table entries beyond the held count all point at scratch
        assert (pt.table[s, pt.held[s]:] == NULL_PAGE).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_random_traces_maintain_invariants(seed):
    rng = random.Random(seed)
    page_size = rng.choice([1, 2, 4, 8])
    slots = rng.randint(1, 5)
    max_pages = rng.randint(1, 8)
    # sometimes oversubscribed (pool < slots * max_pages), sometimes ample
    num_pages = rng.randint(2, slots * max_pages + 3)
    pt = PageTable(num_pages, page_size, slots, max_pages)
    cap = max_pages * page_size
    model: dict[int, int] = {}

    for _ in range(60):
        s = rng.randrange(slots)
        op = rng.random()
        if not pt.active[s] and op < 0.55:
            n = rng.randint(1, cap)
            if pt.can_admit(n):
                ids = pt.admit(s, n)
                assert len(ids) == pages_for(n, page_size)
                assert NULL_PAGE not in ids
                model[s] = n
            else:
                with pytest.raises(RuntimeError):
                    pt.admit(s, n)
        elif pt.active[s] and op < 0.75:
            n = rng.randint(1, cap)
            need = pages_for(n, page_size) - int(pt.held[s])
            if n <= model[s]:
                assert pt.extend(s, n) == []          # no-op growth
            elif need <= pt.free_pages:
                got = pt.extend(s, n)
                assert len(got) == max(need, 0)
                model[s] = n
            else:
                with pytest.raises(RuntimeError):
                    pt.extend(s, n)
        elif pt.active[s]:
            held = int(pt.held[s])
            free_before = pt.free_pages
            freed = pt.retire(s)
            # retire returns all pages to the pool
            assert len(freed) == held
            assert pt.free_pages == free_before + held
            model.pop(s)
        _check_invariants(pt, model)


def test_admit_rejects_bad_sizes():
    pt = PageTable(9, 4, 2, 2)
    with pytest.raises(ValueError):
        pt.admit(0, 0)
    with pytest.raises(ValueError):
        pt.admit(0, 9)      # > max_pages * page_size
    pt.admit(0, 5)
    with pytest.raises(RuntimeError):
        pt.admit(0, 1)      # already active
    with pytest.raises(ValueError):
        pt.extend(0, 9)
    with pytest.raises(RuntimeError):
        pt.extend(1, 1)     # not active
    with pytest.raises(RuntimeError):
        pt.retire(1)


def test_pool_device_sharded_over_data_host_table_global():
    """Paged-KV + sharding interaction: the PageTable admit/extend/retire
    invariants are pure host-side bookkeeping and must hold unchanged when
    the page pool itself is device-put with a ("data",) sharding (the
    tensor-parallel server's per-data-shard pool layout) — and KV written
    through the table into the sharded pool must read back exactly.

    The host table stays global numpy throughout: device placement of the
    pool is invisible to the allocator. Runs on however many devices the
    process has (1 in the tier-1 suite; the 8-device TP suite exercises the
    genuinely-distributed case end to end in tests/test_serving_tp.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ndev = len(jax.devices())
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((ndev,), ("data",))
    page_size, slots, max_pages = 4, 3, 4
    num_pages = max(8, 4 * ndev)              # divides the data axis exactly
    pool = jnp.zeros((num_pages, page_size, 2, 8), jnp.float32)
    pool = jax.device_put(pool, NamedSharding(mesh, P("data")))
    assert pool.sharding.spec == P("data")

    pt = PageTable(num_pages, page_size, slots, max_pages)
    model: dict[int, int] = {}

    def write_tokens(slot, lo, hi):
        """Store a recognizable value per (slot, logical token) through the
        page table, exercising cross-shard page ids."""
        nonlocal pool
        for tok in range(lo, hi):
            pid = int(pt.table[slot, tok // page_size])
            val = float(slot * 1000 + tok + 1)
            pool = pool.at[pid, tok % page_size].set(val)

    ids = pt.admit(0, 6)
    model[0] = 6
    write_tokens(0, 0, 6)
    pt.admit(1, 3)
    model[1] = 3
    write_tokens(1, 0, 3)
    _check_invariants(pt, model)

    pt.extend(0, 11)                          # grows across a page boundary
    model[0] = 11
    write_tokens(0, 6, 11)
    _check_invariants(pt, model)

    # gather each slot's logical view back from the sharded pool: exact
    for slot, n in model.items():
        view = np.asarray(pool[pt.table[slot]]).reshape(-1, 2, 8)
        for tok in range(n):
            assert view[tok, 0, 0] == slot * 1000 + tok + 1, (slot, tok)

    freed = pt.retire(0)
    model.pop(0)
    assert len(freed) == pages_for(11, page_size)
    _check_invariants(pt, model)
    pt.retire(1)
    model.pop(1)
    _check_invariants(pt, model)
    assert pt.free_pages == pt.usable_pages
    # the table is host numpy, untouched by device placement
    assert isinstance(pt.table, np.ndarray)
    assert pool.sharding.spec == P("data")    # placement survived the writes


def _keys_for(pid: int, n: int, page_size: int) -> list:
    """Deterministic per-"prompt-stream" share keys: two admits with the same
    pid alias pages wherever their covered token counts line up — the same
    exact-coverage contract `prefix_keys` provides for real token prefixes."""
    ks, c = [], 0
    while c < n:
        c = min(c + page_size, n)
        ks.append((pid, c))
    return ks


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_shared_cow_swap_traces_maintain_invariants(seed):
    """Random traces over the FULL action set — shared admit (sometimes with
    deferred indexing + progressive `index_pages`, the chunked-prefill
    protocol), extend, CoW fork, swap out/in, retire — keep every allocator
    invariant: refcounts mirror the table, a page is freed iff its refcount
    hits zero, forks are private and unindexed, decode growth is never
    shared, swapped-in pages are fresh, deferred pages stay unindexed until
    their bytes are declared written, and the share index never points at a
    free page."""
    rng = random.Random(seed)
    page_size = rng.choice([1, 2, 4])
    slots = rng.randint(2, 5)
    max_pages = rng.randint(2, 6)
    num_pages = rng.randint(4, slots * max_pages + 4)
    pt = PageTable(num_pages, page_size, slots, max_pages)
    cap = max_pages * page_size
    model: dict[int, int] = {}
    swapped: list[int] = []         # token counts of swapped-out requests
    pending: dict[int, tuple] = {}  # slot -> (keys, n, covered) deferred

    for _ in range(80):
        s = rng.randrange(slots)
        op = rng.random()
        if not pt.active[s] and op < 0.35:
            n = rng.randint(1, cap)
            keys = _keys_for(rng.randrange(3), n, page_size)
            hits = pt.lookup_keys(keys)
            misses = sum(1 for h in hits if h is None)
            defer = rng.random() < 0.5
            if pt.free_pages >= misses:
                ids, shared = pt.admit_shared(s, n, keys, defer_index=defer)
                assert len(ids) == pages_for(n, page_size)
                assert int(shared.sum()) == len(hits) - misses
                for i, h in enumerate(hits):
                    if h is not None:      # every hit really aliased
                        assert int(ids[i]) == h and shared[i]
                    elif defer:            # misses unindexed until bytes land
                        assert int(ids[i]) not in pt._page_key
                if defer and misses:
                    fresh = {int(ids[i]) for i, h in enumerate(hits)
                             if h is None}
                    pending[s] = (keys, n, 0, fresh)
                model[s] = n
            else:
                with pytest.raises(RuntimeError):
                    pt.admit_shared(s, n, keys, defer_index=defer)
        elif pt.active[s] and s in pending and op < 0.5:
            # a prefill chunk landed: register the now-written leading pages
            keys, n, covered, fresh = pending[s]
            covered = min(n, covered + rng.randint(1, n))
            pt.index_pages(s, keys, covered)
            for i, key in enumerate(keys):
                pid = int(pt.table[s, i])
                # freshly-allocated pages whose bytes are not yet declared
                # written must stay out of the share index (a hit would hand
                # a co-owner garbage KV); hits were indexed all along
                if key[0] > covered and pid in fresh:
                    assert pid not in pt._page_key
            if covered >= n:
                pending.pop(s)
            else:
                pending[s] = (keys, n, covered, fresh)
        elif not pt.active[s] and swapped and op < 0.5:
            n = swapped[-1]
            if pt.can_admit(n):
                ids = pt.swap_in(s, n)
                swapped.pop()
                assert len(ids) == pages_for(n, page_size)
                for p in ids:              # private, fresh, unindexed
                    assert int(pt.refcount[p]) == 1
                    assert int(p) not in pt._page_key
                model[s] = n
        elif pt.active[s] and op < 0.62:
            n = rng.randint(1, cap)
            need = pages_for(n, page_size) - int(pt.held[s])
            if n <= model[s]:
                assert pt.extend(s, n) == []          # no-op growth
            elif need <= pt.free_pages:
                got = pt.extend(s, n)
                for p in got:              # decode growth is never shared
                    assert int(pt.refcount[p]) == 1
                    assert p not in pt._page_key
                model[s] = n
            else:
                with pytest.raises(RuntimeError):
                    pt.extend(s, n)
        elif pt.active[s] and op < 0.78:
            pos = rng.randrange(model[s])
            idx = pos // page_size
            before = int(pt.table[s, idx])
            rc = int(pt.refcount[before])
            assert pt.cow_pending(s, pos) == (rc > 1)
            if rc > 1 and pt.free_pages >= 1:
                src, dst = pt.fork_cow(s, pos)
                assert src == before and dst == int(pt.table[s, idx])
                assert int(pt.refcount[src]) == rc - 1   # co-owners keep it
                assert int(pt.refcount[dst]) == 1
                assert dst not in pt._page_key           # forks never indexed
            elif rc > 1:
                with pytest.raises(RuntimeError):        # dry pool, no state
                    pt.fork_cow(s, pos)                  # change before raise
                assert int(pt.table[s, idx]) == before
                assert int(pt.refcount[before]) == rc
            else:
                assert pt.fork_cow(s, pos) is None       # exclusive: in place
        elif pt.active[s] and op < 0.9:
            held = [int(p) for p in pt.slot_pages(s)]
            freed = pt.swap_out(s)
            # freed exactly the pages whose refcount hit zero
            assert set(freed) == {p for p in held if pt.refcount[p] == 0}
            swapped.append(model.pop(s))
            pending.pop(s, None)
        elif pt.active[s]:
            held = [int(p) for p in pt.slot_pages(s)]
            freed = pt.retire(s)
            assert set(freed) == {p for p in held if pt.refcount[p] == 0}
            model.pop(s)
            pending.pop(s, None)
        _check_invariants(pt, model)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fork_debt_formula_matches_realized_forks(seed):
    """The server's admission reservation (`Server._fork_debt`) prices CoW
    exposure per PHYSICAL page as min(#writers, refcount - 1): of the
    writers poised to dirty a shared page, the first rc-1 must fork (each
    fork drops one reference) and the last finds itself sole owner and
    writes in place. Property: that closed-form count equals the number of
    forks actually realized when every slot performs its pending write, in
    ANY order — so admission can reserve exactly, without double-counting
    aliased writers (the PR 8 `can_admit` fix)."""
    rng = random.Random(seed)
    page_size = rng.choice([1, 2, 4])
    slots = rng.randint(2, 6)
    max_pages = rng.randint(1, 4)
    # pool sized so admits and every predicted fork always fit
    num_pages = slots * (max_pages + 1) + 2
    pt = PageTable(num_pages, page_size, slots, max_pages)
    cap = max_pages * page_size
    model: dict[int, int] = {}
    for s in range(slots):
        n = rng.randint(1, cap)
        # two "prompt streams" only: heavy aliasing across slots
        pt.admit_shared(s, n, _keys_for(rng.randrange(2), n, page_size))
        model[s] = n
    # each slot is about to write one covered position (a decode write into
    # its current page, or a CoW-guarded rewrite) — possibly aliasing
    pos = {s: rng.randrange(model[s]) for s in model}
    writers: dict[int, int] = {}
    for s, p in pos.items():
        pid = int(pt.table[s, p // page_size])
        writers[pid] = writers.get(pid, 0) + 1
    predicted = sum(min(w, int(pt.refcount[pid]) - 1)
                    for pid, w in writers.items())
    # realize the writes in a random order and count actual forks
    order = list(pos)
    rng.shuffle(order)
    forks = 0
    for s in order:
        assert pt.cow_pending(s, pos[s]) == \
            (int(pt.refcount[int(pt.table[s, pos[s] // page_size])]) > 1)
        if pt.fork_cow(s, pos[s]) is not None:
            forks += 1
    assert forks == predicted
    _check_invariants(pt, model)


def test_prefix_keys_exact_coverage_contract():
    """Keys match iff the covered token prefixes are identical: equal
    prefixes agree page-for-page, a divergent tail (or a different length
    into the same page) changes that page's key, and full-page keys survive
    a longer prompt extending past them."""
    P = 4
    a = np.arange(10, dtype=np.int32)
    ka = prefix_keys(a, P)
    assert len(ka) == pages_for(10, P) == 3
    assert [k[0] for k in ka] == [4, 8, 10]          # covered token counts
    # same prefix, longer prompt: full pages agree, partial page differs
    b = np.arange(12, dtype=np.int32)
    kb = prefix_keys(b, P)
    assert kb[:2] == ka[:2] and kb[2] != ka[2]
    # divergent tail inside the last page changes only that key
    c = a.copy(); c[-1] += 1
    kc = prefix_keys(c, P)
    assert kc[:2] == ka[:2] and kc[2] != ka[2]
    # divergence inside the first page changes every key (rolling chain)
    d = a.copy(); d[0] += 1
    kd = prefix_keys(d, P)
    assert all(x != y for x, y in zip(kd, ka))
    # keys within one prompt are distinct (chained)
    assert len(set(ka)) == len(ka)


def test_can_admit_counts_reclaimable_pages():
    """The --preempt admission fix: pages held by preemptable running
    requests count toward admissibility (they can be swapped out), so a
    full pool no longer rejects work the scheduler could make room for."""
    pt = PageTable(9, 4, 2, 4)
    pt.admit(0, 16)                     # slot 0 holds 4 of 8 usable pages
    pt.admit(1, 16)                     # slot 1 holds the rest
    assert pt.free_pages == 0
    assert not pt.can_admit(8)
    assert pt.can_admit(8, reclaimable=int(pt.held[1]))
    assert not pt.can_admit(32, reclaimable=int(pt.held[1]))  # beyond pool


def test_cow_fork_preserves_bytes_and_swap_roundtrips():
    """Device-side halves of the scheduler: copy_page gives the forker a
    bit-exact copy while the source keeps serving its co-owner, and
    swap_out_slot -> swap_in_slot round-trips a slot's pages + slab row
    exactly (into a different slot and different physical pages)."""
    import jax.numpy as jnp
    P, slots = 4, 3
    pt = PageTable(12, P, slots, 4)
    cache = {"k": jnp.zeros((12, P, 2, 4), jnp.float32),
             "state": jnp.zeros((slots, 8), jnp.float32)}
    mask = {"k": True, "state": False}

    # slot 0 admits 6 tokens under share keys and writes recognizable bytes
    keys = _keys_for(7, 6, P)
    ids0, shared0 = pt.admit_shared(0, 6, keys)
    assert not shared0.any()
    for t in range(6):
        pid = int(pt.table[0, t // P])
        cache["k"] = cache["k"].at[pid, t % P].set(float(100 + t))
    cache["state"] = cache["state"].at[0].set(1.0)

    # slot 1 shares both pages (full + partial), then CoW-forks the partial
    ids1, shared1 = pt.admit_shared(1, 6, keys)
    assert shared1.all() and (ids1 == ids0).all()
    src, dst = pt.fork_cow(1, 5)
    cache = kv_cache.copy_page(cache, src, dst, mask)
    # the fork is bit-exact and the source is untouched
    assert (np.asarray(cache["k"][dst]) == np.asarray(cache["k"][src])).all()
    # writer diverges on its fork; the co-owner's page keeps its bytes
    cache["k"] = cache["k"].at[dst, 1].set(-5.0)
    assert float(cache["k"][src, 1, 0, 0]) == 105.0
    assert int(pt.refcount[src]) == 1 and int(pt.refcount[dst]) == 1

    # swap slot 0 out (gather BEFORE releasing), back in at a different slot
    ids = pt.slot_pages(0)
    saved = kv_cache.swap_out_slot(cache, 0, ids, mask)
    assert isinstance(saved["k"], np.ndarray)       # host-side slab
    pt.swap_out(0)
    new_ids = pt.swap_in(2, 6)
    cache = kv_cache.swap_in_slot(cache, saved, 2, new_ids, mask)
    for t in range(6):
        pid = int(pt.table[2, t // P])
        assert float(cache["k"][pid, t % P, 0, 0]) == 100 + t, t
    assert float(cache["state"][2, 0]) == 1.0
    _check_invariants(pt, {1: 6, 2: 6})


def test_lifo_reuse_and_full_cycle():
    pt = PageTable(5, 2, 2, 2)
    a = pt.admit(0, 4)
    assert pt.free_pages == 2
    freed = pt.retire(0)
    assert sorted(freed) == sorted(int(p) for p in a)
    b = pt.admit(1, 4)
    # LIFO free list: the just-freed pages come back first
    assert set(int(p) for p in b) == set(freed)
    assert pt.device_table().shape == (2, 2)
    assert (np.asarray(pt.device_table())[0] == NULL_PAGE).all()
