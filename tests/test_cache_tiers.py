"""Tiered prefix cache lockdown: device-LRU parking, host/disk PageStore,
and the demote/promote lifecycle of `launch.cache_tiers`.

These tests run against the page-table accounting alone (fake page images,
no model): the bytes-level token-exactness of tiered serving is locked by
tests/test_multi_serve.py; here we lock the *allocator* invariants that make
that exactness argument valid — a parked page is in no table row, page
conservation holds across every transition, eviction never takes a page an
in-flight admission is about to map, and a disk slab either round-trips
bit-exactly or is dropped on checksum failure (never served torn).
"""
import os

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.launch.cache_tiers import PageStore, TieredPageTable, _slab_name
from repro.launch.kv_cache import prefix_keys

PAGE = 4


def _toks(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 500, size=(n,)).astype(np.int32)


def _store_keys(keys):
    """Store keys root->leaf for a `prefix_keys` list: the chain is the
    concatenation of every ancestor's verbatim key bytes (restart-stable,
    unlike physical-parent chaining)."""
    chain, out = b"", []
    for covered, h, kb in keys:
        chain += kb
        out.append((covered, h, chain))
    return out


def _img(tag):
    return {"k": np.full((PAGE, 3), tag, np.int8)}


def _conserved(pt):
    """Page conservation: free + parked + live == usable, and no parked page
    appears in any active slot's table row."""
    live = int((pt.refcount[1:] > 0).sum())
    assert len(pt._free) + pt.cached_pages + live == pt.usable_pages, \
        (len(pt._free), pt.cached_pages, live, pt.usable_pages)
    mapped = {int(p) for s in range(pt.slots)
              for p in pt.table[s, : pt.held[s]]}
    assert not (mapped & set(pt._cached)), "parked page reachable by a slot"
    for p in pt._cached:
        assert pt.refcount[p] == 0


def _pt(num_pages=9, slots=2, width=4, **kw):
    return TieredPageTable(num_pages, PAGE, slots, width, **kw)


# -- PageStore -----------------------------------------------------------------

def test_store_host_roundtrip():
    s = PageStore(host_capacity=4)
    key = (8, 0xabc, b"chain")
    s.put(key, _img(7))
    img, tier = s.get(key)
    assert tier == "host"
    np.testing.assert_array_equal(img["k"], _img(7)["k"])
    assert s.get((8, 0xabc, b"other"))[0] is None
    assert s.stats["misses"] == 1


def test_store_lru_spills_to_disk(tmp_path):
    s = PageStore(host_capacity=2, disk_dir=tmp_path)
    keys = [(PAGE * (i + 1), i, bytes([i])) for i in range(3)]
    for i, k in enumerate(keys):
        s.put(k, _img(i))
    assert len(s) == 2 and s.stats["disk_writes"] == 1
    img, tier = s.get(keys[0])         # oldest was demoted
    assert tier == "disk"
    np.testing.assert_array_equal(img["k"], _img(0)["k"])
    assert (tmp_path / _slab_name(keys[0])).exists()


def test_store_overflow_without_disk_drops():
    s = PageStore(host_capacity=1)
    s.put((4, 1, b"a"), _img(1))
    s.put((4, 2, b"b"), _img(2))
    assert s.stats["dropped"] == 1
    assert s.get((4, 1, b"a")) == (None, None)


def test_store_flush_survives_restart(tmp_path):
    s = PageStore(host_capacity=8, disk_dir=tmp_path)
    key = (12, 0x5_5, b"\x01\x02")
    s.put(key, _img(3))
    s.flush()
    assert len(s) == 0
    s2 = PageStore(host_capacity=8, disk_dir=tmp_path)   # "restart"
    img, tier = s2.get(key)
    assert tier == "disk"
    np.testing.assert_array_equal(img["k"], _img(3)["k"])


@pytest.mark.parametrize("damage", ["flip", "truncate", "magic"])
def test_store_corrupt_slab_dropped_not_served(tmp_path, damage):
    """A torn/corrupted slab fails its CRC (or frame check) on read: it is
    unlinked and counted, never deserialized."""
    s = PageStore(host_capacity=1, disk_dir=tmp_path)
    key = (8, 0xdead, b"cc")
    s.put(key, _img(9))
    s.flush()
    path = tmp_path / _slab_name(key)
    raw = bytearray(path.read_bytes())
    if damage == "flip":
        raw[-1] ^= 0xFF
    elif damage == "truncate":
        raw = raw[: len(raw) // 2]
    else:
        raw[:4] = b"XXXX"
    path.write_bytes(bytes(raw))
    assert s.get(key) == (None, None)
    assert s.stats["corrupt_dropped"] == 1
    assert not path.exists()
    assert s.get(key) == (None, None)          # second probe: plain miss
    assert s.stats["corrupt_dropped"] == 1


def test_store_chain_collision_is_benign_miss(tmp_path):
    """Same filename, intact checksum, different chain bytes: a miss, not
    corruption — the verbatim chain comparison is the real gate, the hashed
    filename only a prefilter."""
    s = PageStore(host_capacity=1, disk_dir=tmp_path)
    key_a = (8, 0xf00, b"aaaa")
    key_b = (8, 0xf00, b"bbbb")
    s.put(key_a, _img(1))
    s.flush()
    os.rename(tmp_path / _slab_name(key_a), tmp_path / _slab_name(key_b))
    assert s.get(key_b) == (None, None)
    assert s.stats["corrupt_dropped"] == 0
    assert (tmp_path / _slab_name(key_b)).exists()


# -- TieredPageTable: device tier ----------------------------------------------

def test_retire_parks_indexed_pages():
    pt = _pt()
    keys = prefix_keys(_toks(8), PAGE)
    pt.admit_shared(0, 8, keys)
    _conserved(pt)
    pt.retire(0)
    assert pt.cached_pages == 2                 # parked, not freed...
    assert pt.free_pages == pt.usable_pages     # ...but still counted free
    assert all(p is not None for p in pt.lookup_keys(keys))
    _conserved(pt)


def test_parked_rehit_is_free_and_exact():
    pt = _pt()
    keys = prefix_keys(_toks(8), PAGE)
    first, _ = pt.admit_shared(0, 8, keys)
    pt.retire(0)
    pages, shared = pt.admit_shared(1, 8, keys)
    assert shared.all()
    assert list(pages) == list(first)           # the very same pages
    assert pt.tier_stats["device_hits"] == 2
    assert pt.cached_pages == 0
    _conserved(pt)


def test_unindexed_pages_still_free_normally():
    """Private pages (plain admit, decode-extend growth) never park."""
    pt = _pt()
    pt.admit(0, 8)
    pt.extend(0, 12)
    pt.retire(0)
    assert pt.cached_pages == 0
    assert len(pt._free) == pt.usable_pages
    _conserved(pt)


def test_allocation_pressure_evicts_lru_parked():
    pt = _pt(num_pages=5, slots=2)             # 4 usable pages
    keys = prefix_keys(_toks(8), PAGE)
    pt.admit_shared(0, 8, keys)
    pt.retire(0)                               # 2 parked, 2 free
    pt.admit(1, 12)                            # needs 3: evicts 1 parked
    assert pt.tier_stats["evictions"] == 1
    assert pt.cached_pages == 1
    _conserved(pt)
    # the surviving parked page is the root (children parked before parents
    # -> parents are LRU-newer); its index entry must still be reachable
    assert pt.lookup_keys(keys)[0] is not None


def test_watermark_bounds_parked_set():
    pt = _pt(num_pages=17, slots=2, width=8, watermark=2)
    keys = prefix_keys(_toks(20), PAGE)
    pt.admit_shared(0, 20, keys)
    pt.retire(0)
    assert pt.cached_pages == 2
    assert pt.tier_stats["evictions"] == 3
    _conserved(pt)


def test_admission_never_evicts_its_own_hits():
    """An admission whose misses force eviction must not evict the parked
    pages the SAME admission is about to map (they are pinned)."""
    pt = _pt(num_pages=4, slots=2, width=3)    # 3 usable pages
    a = _toks(8, seed=1)
    keys_a = prefix_keys(a, PAGE)
    pt.admit_shared(0, 8, keys_a)
    pt.retire(0)                               # 2 parked, 1 free
    b = np.concatenate([a[:4], _toks(8, seed=2)]).astype(np.int32)
    keys_b = prefix_keys(b, PAGE)              # hit page 0 of A, 2 misses
    pages, shared = pt.admit_shared(1, 12, keys_b)
    assert shared[0] and not shared[1] and not shared[2]
    assert pt.tier_stats["device_hits"] == 1
    assert pt.tier_stats["evictions"] == 1     # A's tail went, A's root didn't
    _conserved(pt)


def test_exhausted_pool_with_all_pages_pinned_raises():
    pt = _pt(num_pages=3, slots=2, width=2)    # 2 usable pages
    keys = prefix_keys(_toks(8), PAGE)
    pt.admit_shared(0, 8, keys)
    pt.retire(0)                               # both pages parked
    longer = np.concatenate([_toks(8), _toks(4, seed=9)]).astype(np.int32)
    with pytest.raises(RuntimeError, match="exhausted"):
        pt.admit_shared(1, 12, prefix_keys(longer, PAGE))
    _conserved(pt)                             # failed admission leaks nothing


def test_free_pages_for_nets_out_parked_hits():
    pt = _pt(num_pages=5)
    keys = prefix_keys(_toks(8), PAGE)
    pt.admit_shared(0, 8, keys)
    pt.retire(0)
    assert pt.free_pages == 4
    assert pt.free_pages_for(keys) == 2        # the 2 parked hits aren't supply
    assert pt.free_pages_for(prefix_keys(_toks(8, seed=3), PAGE)) == 4


# -- demote / promote ----------------------------------------------------------

def _tiered_with_store(tmp_path, num_pages=5, ns=b"m"):
    store = PageStore(host_capacity=1, disk_dir=tmp_path)
    pt = _pt(num_pages=num_pages, store=store)
    pt._current_ns = ns
    pt.register_demoter(ns, lambda pid: _img(pid))
    return pt, store


def test_eviction_demotes_bytes_under_chain_key(tmp_path):
    pt, store = _tiered_with_store(tmp_path)
    toks = _toks(8)
    keys = prefix_keys(toks, PAGE, namespace=b"m")
    pages, _ = pt.admit_shared(0, 8, keys)
    pt.retire(0)
    pt.flush_cached()
    assert pt.tier_stats["demotions"] == 2
    assert pt.cached_pages == 0 and len(pt._free) == pt.usable_pages
    for (sk, pid) in zip(_store_keys(keys), pages):
        img, tier = store.get(sk)
        assert tier in ("host", "disk")
        np.testing.assert_array_equal(img["k"], _img(int(pid))["k"])
    _conserved(pt)


def test_adopt_promotes_back_to_parked_and_rehits(tmp_path):
    pt, store = _tiered_with_store(tmp_path)
    toks = _toks(8)
    keys = prefix_keys(toks, PAGE, namespace=b"m")
    pt.admit_shared(0, 8, keys)
    pt.retire(0)
    pt.flush_cached()
    assert all(p is None for p in pt.lookup_keys(keys))
    # promotion walk: adopt each store hit in chain order, as the server does
    parent = -1
    for key, sk in zip(keys, _store_keys(keys)):
        img, tier = store.get(sk)
        assert img is not None
        page = pt.adopt(parent, key, sk[2], b"m")
        parent = page
    assert pt.tier_stats["promotions"] == 2
    _conserved(pt)
    pages, shared = pt.admit_shared(0, 8, keys)
    assert shared.all()
    _conserved(pt)


def test_restart_roundtrip_through_disk(tmp_path):
    """Process 1 demotes to disk; a brand-new table + store over the same
    directory promotes the same prefix — physical page ids differ, content
    keys (and thus bytes) match."""
    pt1, store1 = _tiered_with_store(tmp_path)
    keys = prefix_keys(_toks(8), PAGE, namespace=b"m")
    pages1, _ = pt1.admit_shared(0, 8, keys)
    pt1.retire(0)
    pt1.flush_cached()
    store1.flush()

    store2 = PageStore(host_capacity=4, disk_dir=tmp_path)
    pt2 = _pt(num_pages=5, store=store2)
    parent = -1
    for key, sk in zip(keys, _store_keys(keys)):
        img, tier = store2.get(sk)
        assert tier == "disk"
        np.testing.assert_array_equal(
            img["k"], _img(int(pages1[list(keys).index(key)]))["k"])
        parent = pt2.adopt(parent, key, sk[2], b"m")
    _, shared = pt2.admit_shared(0, 8, keys)
    assert shared.all()
    _conserved(pt2)


def test_namespaces_never_alias(tmp_path):
    """Two tenants with identical token streams get disjoint keys, index
    entries, and store slabs."""
    toks = _toks(8)
    ka = prefix_keys(toks, PAGE, namespace=b"A")
    kb = prefix_keys(toks, PAGE, namespace=b"B")
    assert [k[1] for k in ka] != [k[1] for k in kb]      # hashes differ
    assert all(a[2] != b[2] for a, b in zip(ka, kb))     # bytes differ
    store = PageStore(host_capacity=8, disk_dir=tmp_path)
    pt = _pt(num_pages=9, store=store)
    pt._current_ns = b"A"
    pt.register_demoter(b"A", lambda pid: _img(pid))
    pt.admit_shared(0, 8, ka)
    _, shared = pt.admit_shared(1, 8, kb)               # other tenant: miss
    assert not shared.any()
    pt.retire(0)
    pt.retire(1)
    _conserved(pt)


# -- property: random trace keeps every invariant ------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_random_trace_conserves_pages(seed):
    """Random admit/retire/extend/fork/evict traffic over a tight pool with
    tiering + store: page conservation and parked-page isolation hold after
    every single transition, and the pool drains clean at the end."""
    rng = np.random.default_rng(seed)
    store = PageStore(host_capacity=2, disk_dir=None)
    pt = _pt(num_pages=8, slots=3, width=4, store=store,
             watermark=int(rng.integers(0, 4)))
    pt.register_demoter(b"", lambda pid: _img(pid))
    prompts = [_toks(int(n), seed=int(rng.integers(5)))
               for n in rng.integers(1, 13, size=4)]
    busy: dict[int, int] = {}                  # slot -> tokens covered
    for _ in range(60):
        op = rng.integers(4)
        if op == 0 and len(busy) < pt.slots:
            slot = next(s for s in range(pt.slots) if s not in busy)
            p = prompts[int(rng.integers(len(prompts)))]
            try:
                pt.admit_shared(slot, len(p), prefix_keys(p, PAGE))
                busy[slot] = len(p)
            except RuntimeError:
                pass                           # pool genuinely full
        elif op == 1 and busy:
            slot = list(busy)[int(rng.integers(len(busy)))]
            pt.retire(slot)
            del busy[slot]
        elif op == 2 and busy:
            slot = list(busy)[int(rng.integers(len(busy)))]
            want = busy[slot] + int(rng.integers(1, 4))
            if want <= pt.max_pages * PAGE:
                try:
                    pt.extend(slot, want)
                    busy[slot] = want
                except RuntimeError:
                    pass
        elif op == 3 and busy:
            slot = list(busy)[int(rng.integers(len(busy)))]
            try:
                pt.fork_cow(slot, int(rng.integers(busy[slot])))
            except RuntimeError:
                # only a genuinely exhausted pool may refuse a fork: no free
                # page and no parked page left to evict (the server reserves
                # fork debt at admission; this raw trace does not)
                assert not pt._free and not pt._cached
        _conserved(pt)
    for slot in list(busy):
        pt.retire(slot)
        _conserved(pt)
    pt.flush_cached()
    _conserved(pt)
    assert len(pt._free) == pt.usable_pages    # everything returned
