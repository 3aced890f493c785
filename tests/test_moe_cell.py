"""DeepSeekMoE on the benchmark's path, on the CPU at a tiny size: the
`dsmoe16b-mixed-decode-closed` cell rehearsed through `bench.run.measure`,
the plain reference following the program's prefill capacity drops on a
fixed request set served for a fixed number of ticks (no clock), the
`moe_expert_rows` counter counted by hand, and the named scopes that label
the expert GEMMs in the lowered decode program.

The tiny model keeps the configuration's structure (every layer an expert
layer, 2 shared experts, the mixed policy, top-k gates renormalized) at
4 layers, d 128, 8 routed experts top-2 of width 64, and the jnp backend."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, client, reference, run, spec

CELL = "dsmoe16b-mixed-decode-closed"
SEED = 2**31 + 7
E, K, F = 8, 2, 64
TINY = dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
            d_ff=F, vocab=512, n_experts=E, top_k=K)
#: the rehearsal's limits at this size (those of the configuration file are
#: set on the chip, PERF.md): kv_layer0 reads 0 (both sides compute the same
#: int8 ops), kv_layer1 about 0.2, the median token rank 0
LIMITS = {"kv_layer0": 1e-3, "kv_layer1": 0.5, "token_rank_median": 2.0}
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}
PAGE = 32


def tiny_conf(capacity_factor: float | None = None) -> dict:
    conf = spec.load_json(f"{spec.HERE}/configs/dsmoe16b-mixed.json")
    conf["arch_overrides"] = dict(TINY)
    conf["served"].update(
        hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, head_dim=32, intermediate_size=F,
        moe_intermediate_size=F, n_routed_experts=E, num_experts_per_tok=K,
        vocab_size=512)
    if capacity_factor is not None:
        conf["arch_overrides"]["capacity_factor"] = capacity_factor
        conf["served"]["capacity_factor"] = capacity_factor
    conf["backend"] = "jnp"
    conf["check"]["limits"] = dict(LIMITS)
    return conf


def tiny_mix() -> dict:
    """The cell's mix at 4 slots: its own prompt and output lengths, so a
    short window is pure decode here as on the chip."""
    mix = spec.traffic("moe-decode-closed")
    mix.update(slots=4, clients=4, open_after_first_tokens=4, num_pages=None)
    return mix


def test_cell_is_in_the_benchmark():
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dsmoe16b-mixed", "moe-decode-closed", 1)
    conf = spec.config(bench, "dsmoe16b-mixed")
    mix = spec.traffic(cell["traffic"])
    # every request fits its full reservation: all slots admitted at once
    per_req = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"])
                // conf["page_size"])
    assert mix["cache_len"] == per_req * conf["page_size"]
    assert mix["num_pages"] == mix["slots"] * per_req + 1
    assert mix["open_after_first_tokens"] == mix["slots"] == mix["clients"]
    layers = {m["name"]: m["layer"] for m in spec.metrics_for(
        bench, CELL, "per_layer")}
    assert {"expert_gemm_roofline", "expert_row_live_share"} <= set(layers)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    """(a) The cell through `bench.run.measure`: correct, the cell's own
    end-to-end metrics untraced, and traced the live-row share equal to the
    count by hand. Every request is admitted in the first tick and none
    completes before its 640th token, so the window only decodes: each
    tick routes 4 slots x top-2 assignments in each layer and computes
    8 experts x 4 slots x 1 capacity row (a decode's capacity is its one
    token a slot)."""
    bench = spec.benchmark()
    cell = dict(spec.cell(bench, CELL))
    args = types.SimpleNamespace(workload=CELL, seed=SEED, seconds=1.0,
                                 trace=trace)
    res = run.measure(args, bench, cell, tiny_conf(), tiny_mix(),
                      jax.devices(), PEAKS)
    assert res["correct"], res["check"]
    assert res["attempted"] == 4 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    if not trace:
        want = {m["name"] for m in spec.metrics_for(bench, CELL,
                                                    "end_to_end")}
        assert set(res["metrics"]) == want == {"setup_s", "itl_p95_ms",
                                               "tok_s"}
        return
    # no device plane on the CPU: the device readers stay silent
    assert set(res["metrics"]) == {"batch_occupancy", "decode_tick_ms",
                                   "expert_row_live_share"}
    assert res["metrics"]["batch_occupancy"]["value"] == 100.0
    assert res["metrics"]["expert_row_live_share"]["value"] == \
        100.0 * (4 * 2) / (8 * 4 * 1)


def _serve_fixed(conf, ticks: int):
    """A fixed request set served for a fixed number of steps, then
    stopped where it is."""
    from repro.launch.serve import Request
    cfg, srv = client.build_server(conf, tiny_mix(), SEED % 2**32)
    rng = np.random.default_rng(11)
    for rid, n in enumerate((120, 96, 70, 40)):
        srv.submit(Request(rid, rng.integers(0, cfg.vocab, n).astype(
            np.int32), 64))
    for _ in range(ticks):
        srv.step()
    return srv


def test_reference_follows_prefill_drops():
    """(b) The reference follows the program's capacity drops at prefill,
    on a fixed request set served for a fixed number of ticks. A capacity
    factor of 0.5 forces drops: each expert holds at most half its fair
    share of a prompt. Layer 0's keys and values are computed by the same
    int8 ops on both sides (0 exactly). Layer 1's carry layer 0's expert
    output, where the program's bf16 stream against the reference's
    float32 rounding, amplified by layer 1's ternary activation quantizer,
    reads about 0.15 (0.20 at the configuration's factor of 1.25). The
    reference that drops nothing (capacity factor 1e5) adds the expert
    outputs the program dropped and reads about 0.67. LIMIT = 0.3 lies near the
    geometric middle, with twice the sound reading below it."""
    LIMIT = 0.3
    conf = tiny_conf(capacity_factor=0.5)
    srv = _serve_fixed(conf, ticks=6)
    assert srv.stats["moe_dropped"] > 0
    picks = check.gather(srv, SEED, 4, 2)
    assert len(picks) == 4
    check.free(srv)
    m = reference.Model.from_config(conf)
    wseed = SEED % 2**32
    got = check.readings(m, wseed, picks, 256, 2, PAGE)
    dropless = check.readings(dataclasses.replace(m, capacity_factor=1e5),
                              wseed, picks, 256, 2, PAGE)
    assert got["kv_layer0"] == 0.0
    assert got["kv_layer1"] < LIMIT < dropless["kv_layer1"]


def test_expert_rows_counted_by_hand():
    """(c) moe_expert_rows after one prefill and one decode tick is
    E x B·C in every layer of each call. A 40-token prompt is padded to the
    64 bucket: capacity int(64 x 2 / 8 x 1.25) = 20, so 8 x 1 x 20 = 160
    rows a layer; a decode over the 4 slots at capacity 1 (at most one
    assignment an expert from one token) computes 8 x 4 x 1 = 32 a layer.
    The admitting tick runs the prefill and then decodes; the next tick
    only decodes."""
    from repro.launch.serve import Request
    cfg, srv = client.build_server(tiny_conf(), tiny_mix(), 5)
    layers = cfg.n_layers
    srv.submit(Request(0, np.arange(40, dtype=np.int32), 8))
    srv.step()
    assert srv._bucket(40) == 64 and srv.phys_slots == 4
    assert srv.stats["moe_expert_rows"] == layers * (160 + 32)
    # every row of the bucket and every slot is routed, top-2
    assert srv.stats["moe_routed"] == layers * 2 * (64 + 4)
    before = dict(srv.stats)
    srv.step()
    assert srv.stats["moe_expert_rows"] - before["moe_expert_rows"] == \
        layers * 32
    assert srv.stats["moe_routed"] - before["moe_routed"] == layers * 2 * 4
    assert srv.stats["moe_dropped"] == before["moe_dropped"]


def test_decode_program_names_the_moe_phases():
    """(d) The lowered decode program's op metadata carries the MoE scopes,
    the expert GEMMs' among them."""
    cfg, srv = client.build_server(tiny_conf(), tiny_mix(), 5)
    P, M = srv.phys_slots, srv.max_pages
    z = lambda *s: jnp.zeros(s, jnp.int32)
    hlo = srv._decode.lower(srv.params, srv.cache, z(P, 1), z(P),
                            z(P, M)).compile().as_text()
    for scope in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                  "moe_shared"):
        assert f"/{scope}/" in hlo, scope
