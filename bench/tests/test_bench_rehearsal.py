"""A rehearsal of the harness's phases on the CPU at a tiny size: set-up,
warm-up, the window, the correctness check and the metrics, for both
generators; then the same run with a served token altered where it is
produced, and the control's precision put in the program's place, both of
which must come out not correct.

Runs `bench.run.measure` (what `bench/run.py` runs after its chip checks)
with the XLA backend and a 4-layer, 128-wide model; the limits here are
this size's, not the configuration file's (those are set on the chip,
PERF.md)."""
import types

import jax
import pytest

from bench import check, control, reference, run, spec

TINY = dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
            d_ff=256, vocab=512)
#: readings on the CPU at this size: kv_layer0 0 (both sides compute the
#: same ops), kv_layer1 about 0.15, median token rank 0
LIMITS = {"kv_layer0": 1e-3, "kv_layer1": 0.5, "token_rank_median": 2.0}
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}


def tiny(traffic: str):
    conf = spec.load_json(f"{spec.HERE}/configs/phi3-mini-mixed.json")
    conf["arch_overrides"] = TINY
    conf["served"].update(
        hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, head_dim=32, intermediate_size=256,
        vocab_size=512)
    conf["backend"] = "jnp"
    conf["check"]["limits"] = dict(LIMITS)
    mix = spec.traffic(traffic)
    mix.update(slots=4, cache_len=256, num_pages=None,
               open_after_first_tokens=4, rate_rps=20.0, clients=4)
    mix["prompt_len"].update(min=8, max=120, median=40)
    mix["output_len"].update(min=8, max=40, median=16)
    return conf, mix


#: the cells rehearsed: the benchmark's, and the chat cell whose pieces
#: (open loop, queue wait) are kept for when it is proven (PERF.md)
CELLS = {"phi3-mixed-chat-poisson": "chat-poisson",
         "phi3-mixed-longctx-closed": "longctx-closed"}


def measure(workload: str, seed: int = 2**31 + 11, trace: int = 0):
    bench = spec.benchmark()
    cell = {"name": workload, "config": "phi3-mini-mixed",
            "traffic": CELLS[workload], "chips": 1}
    conf, mix = tiny(cell["traffic"])
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=2.0,
                                 trace=trace)
    return run.measure(args, bench, cell, conf, mix, jax.devices(), PEAKS)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_rehearsal_is_correct_and_reports_the_cells_metrics(workload):
    res = measure(workload)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    bench = spec.benchmark()
    want = {m["name"] for m in spec.metrics_for(bench, workload,
                                                "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "check"
    assert res["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_host_metrics():
    res = measure("phi3-mixed-longctx-closed", trace=1)
    assert res["correct"], res["check"]
    # no device plane on the CPU: the device readers stay silent, the host
    # and counter readers do not
    assert set(res["metrics"]) == {"batch_occupancy", "decode_tick_ms"}
    assert 0 < res["metrics"]["batch_occupancy"]["value"] <= 100
    assert res["device"]["window_s"] > 0


def test_altered_token_is_not_correct(monkeypatch):
    from repro.launch.serve import Server
    deliver = Server._deliver

    def altered(self, req, tok):
        return deliver(self, req, (tok + 1) % self.cfg.vocab)
    monkeypatch.setattr(Server, "_deliver", altered)
    res = measure("phi3-mixed-longctx-closed")
    assert not res["correct"]
    assert res["check"]["token_rank_median"]["value"] > \
        10 * LIMITS["token_rank_median"]


def test_control_precision_is_not_correct():
    """The reference at int4 where the configuration states int8, in the
    program's place, on the requests of a sound tiny run."""
    conf, mix = tiny("longctx-closed")
    from bench import client
    cfg, srv = client.build_server(conf, mix, 5)
    client.warm_up(srv, mix, cfg.vocab)
    gen = spec.generator(mix["generator"])(mix, 5, cfg.vocab)
    client.serve(srv, gen, seconds=1.0, open_after=4)
    picks = check.gather(srv, 5, 4, 2)
    check.free(srv)
    model = reference.Model.from_config(conf)
    sound = check.readings(model, 5, picks, 256, 2, 32)
    low = control.control_readings(model, 5, picks, 256, 2, 32)
    assert check.verdict(sound, LIMITS)[0]
    assert not check.verdict(low, LIMITS)[0]
    assert low["kv_layer0"] > 10 * LIMITS["kv_layer0"]
