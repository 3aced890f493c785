"""bench/cost.py against operations and bytes counted by hand."""
import pytest

from bench import cost


def test_qgemm_work_by_hand():
    # 4 rows x K 64 x N 32, ternary weights: 2*4*64*32 ops; weights
    # 64*32 trits at 2 bits = 512 B, 32 f32 scales = 128 B; bf16 rows in
    # 4*64*2 = 512 B and out 4*32*2 = 256 B
    lin = cost.Linear("t", 64, 32, "ternary", "ternary")
    assert lin.work(4) == (16384.0, 512 + 128 + 512 + 256)
    assert lin.narrow()
    assert not cost.Linear("b", 64, 32, "none", "none").narrow()


def test_least_time_takes_the_larger_bound():
    pk = {"int8_ops": 100.0, "bf16_flops": 50.0, "hbm_bytes_per_s": 10.0}
    assert cost.least_time(1000.0, 20.0, True, pk) == 10.0    # compute
    assert cost.least_time(1000.0, 200.0, True, pk) == 20.0   # memory
    assert cost.least_time(1000.0, 20.0, False, pk) == 20.0   # bf16 peak


def _conf(layers=2, d=8, f=16, h=2, hk=1, dh=4, v=10):
    return {"served": {"hidden_size": d, "intermediate_size": f,
                       "num_hidden_layers": layers,
                       "num_attention_heads": h, "num_key_value_heads": hk,
                       "head_dim": dh, "vocab_size": v},
            "quant": {"body": ["ternary", "ternary"],
                      "first_last": ["int8", "int8"],
                      "lm_head": ["int8", "int8"]}}


def test_paged_decode_tick_by_hand():
    mc = cost.ModelCost.from_config(_conf())
    # K and V, 1 kv head of 4, bf16: 16 B per token per layer; slots at
    # positions 0 and 9 read 1 and 10 entries
    assert mc.kv_bytes_per_token() == 16
    assert mc.decode_kv_bytes([0, 9]) == 11 * 16
    # QK + PV over 11 positions, 2 heads x 4, 2 layers
    assert mc.attn_ops(11) == 4 * 11 * 2 * 4 * 2


def test_model_layers_take_the_policy_per_layer():
    mc = cost.ModelCost.from_config(_conf(layers=3))
    precs = [{lin.wprec for lin in lins} for lins, _ in mc.layers]
    assert precs == [{"int8"}, {"ternary"}, {"int8"}]
    # qkv of 2 query + 2x1 kv heads of 4 = 16 outputs; up is gated (2f)
    assert [(lin.k, lin.n) for lin in mc.layers[0][0]] == [
        (8, 16), (8, 8), (8, 32), (16, 8)]


def test_moe_layer_by_hand():
    # 3 tokens, top-2 of experts touching 4; d 8, f 4: each assignment
    # 2*(8*8 + 4*8) ops; weights 4 experts x 96 int8 + scales (8+8)*4
    ops, nbytes = cost.expert_work(3, 2, 4, 8, 4, "int8")
    assert ops == 6 * 2 * (64 + 32)
    assert nbytes == 4 * (96 + 4 * 16) + 2 * 6 * ((8 + 8) + (4 + 8))


def test_moe_model_counts_assignments_not_capacity():
    conf = _conf(layers=2, f=4)
    conf["served"].update(n_routed_experts=4, n_shared_experts=1,
                          num_experts_per_tok=2)
    mc = cost.ModelCost.from_config(conf)
    ex = mc.layers[0][1]
    assert (ex.experts, ex.top_k, ex.f) == (4, 2, 4)
    # one token reaches exactly top_k experts; many reach them all
    assert ex.touched(1) == 2.0
    assert abs(ex.touched(1000) - 4.0) < 1e-9
    pk = {"int8_ops": 1.0, "bf16_flops": 1.0, "hbm_bytes_per_s": 1e30}
    # 1 token: per layer qkv 2*8*16 + out 2*8*8 + shared up 2*8*8 + shared
    # down 2*4*8 + experts 2*2*(8*8+4*8) + router 2*8*4; head 2*8*10
    per_layer = 256 + 128 + 128 + 64 + 384 + 64
    assert mc.model_least_s(1, 1, 0, pk) == 2 * per_layer + 160


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in"):
        cost.peaks("TPU v0 imaginary")
    assert cost.peaks("TPU v5 lite")["int8_ops"] == 393e12
