"""The plain reference against the program at a tiny size on the CPU: it
draws the same weights from the seed (so its quantized codes equal the
program's packed ones), and for an expert model it follows the program's
capacity drops at prefill."""
import dataclasses

import jax
import numpy as np

from bench import check, client, reference, spec
from bench.tests.test_bench_rehearsal import TINY

SEED = 2**31 + 3


def _tiny_conf(name, **served):
    conf = spec.load_json(f"{spec.HERE}/configs/{name}.json")
    conf["arch_overrides"] = dict(TINY)
    conf["served"].update(
        hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, head_dim=32, intermediate_size=256,
        vocab_size=512, **served)
    conf["backend"] = "jnp"
    return conf


def test_reference_draws_the_programs_codes():
    from repro.core import pack
    from repro.launch.serve import serve_params
    conf = _tiny_conf("phi3-mini-mixed")
    cfg = client.arch_config(conf)
    sp, _ = serve_params(cfg, seed=SEED)
    m = reference.Model.from_config(conf)
    keys = reference.top_keys(SEED)
    # jitted, as `reference.run` draws them: XLA computes the int8 codes'
    # w / s the same way inside both jitted programs (eagerly, 7% of the
    # codes came out one apart at this size)
    mid = reference._block_weights_jit(reference.block_key(keys, m, 2), m,
                                       m.body)
    got = jax.tree.map(lambda a: a[1], sp["mid"]["b0"]["mixer"]["qkv"])
    trits = pack.unpack_ternary_i8(got["w_mask"], got["w_sign"], 128)
    np.testing.assert_array_equal(np.asarray(trits).T,
                                  np.asarray(mid["qkv"]["q"]))
    first = reference._block_weights_jit(reference.block_key(keys, m, 0),
                                         m, m.first_last)
    np.testing.assert_array_equal(
        np.asarray(sp["first"]["mixer"]["qkv"]["w_q"]),
        np.asarray(first["qkv"]["q"]))
    np.testing.assert_allclose(np.asarray(sp["first"]["mixer"]["qkv"][
        "w_scale"]), np.asarray(first["qkv"]["s"]), rtol=1e-6)


def test_reference_follows_expert_capacity():
    conf = _tiny_conf("dsmoe16b-mixed", moe_intermediate_size=64,
                      n_routed_experts=8, num_experts_per_tok=2)
    conf["served"]["intermediate_size"] = 64
    conf["arch_overrides"].update(d_ff=64, n_experts=8, top_k=2)
    mix = spec.traffic("decode-closed")
    mix.update(slots=4, cache_len=256, num_pages=None, clients=4,
               open_after_first_tokens=4)
    mix["prompt_len"].update(min=40, max=200, median=100)
    mix["output_len"].update(min=8, max=16)
    cfg, srv = client.build_server(conf, mix, SEED % 2**32)
    client.warm_up(srv, mix, cfg.vocab)
    gen = spec.generator(mix["generator"])(mix, SEED, cfg.vocab)
    client.serve(srv, gen, seconds=1.0, open_after=4)
    assert srv.stats["moe_dropped"] > 0          # the prompts did drop
    picks = check.gather(srv, SEED, 4, 3)
    check.free(srv)
    m = reference.Model.from_config(conf)
    got = check.readings(m, SEED % 2**32, picks, 256, 3, 32)
    dropless = check.readings(dataclasses.replace(m, capacity_factor=1e5),
                              SEED % 2**32, picks, 256, 3, 32)
    assert got["kv_layer0"] == 0.0
    assert got["kv_layer1"] < 0.3 < dropless["kv_layer1"]
