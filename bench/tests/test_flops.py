"""Model FLOPs from shapes, counted by each configuration's own reference
module through ``bench.flops``, against counts worked out by hand."""
import json
import sys
from pathlib import Path

import pytest

from bench.flops import flops_per_step
from bench.reference import dense_decoder
from bench.reference.dense_decoder import matmul_params

BENCH = Path(__file__).resolve().parents[1]
PHI3 = dict(reference="dense_decoder", hidden_size=3072,
            num_attention_heads=32, num_key_value_heads=32, head_dim=96,
            intermediate_size=8192, vocab_size=32064)
NEMO = dict(reference="dense_decoder", hidden_size=5120,
            num_attention_heads=32, num_key_value_heads=8, head_dim=128,
            intermediate_size=14336, vocab_size=16384)
# DeepSeek-V2-Lite (its config.json) cut to one chip's share of an 8-chip
# expert-parallel layer: the dense layer and four expert layers, 8 of the
# 64 routed experts held, an eighth of the vocabulary
V2_LITE = dict(reference="toy_mla_moe", hidden_size=2048,
               num_attention_heads=16, num_key_value_heads=16,
               intermediate_size=10944, kv_lora_rank=512, q_lora_rank=None,
               qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
               moe_intermediate_size=1408, num_experts_per_tok=6,
               n_shared_experts=2, n_routed_experts=8,
               n_routed_experts_total=64, first_k_dense_replace=1,
               num_hidden_layers=5, vocab_size=12800)


def by_hand(d, h, kv, hd, f, v, layers, batch, seq):
    # q and o: d x h*hd each; k and v: d x kv*hd each; SwiGLU: 3 x d x f
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f
    params = layers * per_layer + d * v          # LM head, no embedding
    tokens = batch * seq
    attention = 6 * layers * seq * h * hd        # per token, causal
    return 6 * params * tokens + attention * tokens


@pytest.mark.parametrize("cfg,layers,batch,expect_tflop", [
    (PHI3, 3, 2, 23.4),         # phi3-mini, 3 layers, 2 x 4096
    (NEMO, 3, 4, 93.6),         # mistral-nemo, 3 layers, 4 x 4096
    (PHI3, 2, 2, 17.21),        # the benchmark's phi3 cut
    (NEMO, 2, 4, 65.15),        # the benchmark's nemo cut
])
def test_flops_per_step(cfg, layers, batch, expect_tflop):
    c = dict(cfg, num_hidden_layers=layers)
    got = flops_per_step(c, batch, 4096)
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    assert got == by_hand(d, h, kv, hd, c["intermediate_size"],
                          c["vocab_size"], layers, batch, 4096)
    assert got / 1e12 == pytest.approx(expect_tflop, abs=0.01)


def test_matmul_params_of_phi3_layer():
    # 4 x 3072^2 attention + 3 x 3072 x 8192 MLP = 113,246,208 a layer
    c = dict(PHI3, num_hidden_layers=1, vocab_size=0)
    assert matmul_params(c) == 4 * 3072 ** 2 + 3 * 3072 * 8192


@pytest.mark.parametrize("name", ["phi3-mini-3.8b-l2",
                                  "mistral-nemo-12b-l2-v16k"])
def test_configuration_files_have_the_keys_flops_reads(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        c = json.load(f)
    assert flops_per_step(c, 1, 4096) > 0
    assert c["head_dim"] * c["num_attention_heads"] in (3072, 4096)


@pytest.fixture
def toy(monkeypatch):
    from bench.tests import toy_mla_moe
    monkeypatch.setitem(sys.modules, "bench.reference.toy_mla_moe",
                        toy_mla_moe)
    return toy_mla_moe


def test_a_configuration_is_counted_by_its_own_module(toy):
    # a layer's attention projections: q 2048 x 16 x 192 = 6,291,456,
    # kv down 2048 x 576 = 1,179,648, kv up 512 x 16 x 256 = 2,097,152,
    # o 16 x 128 x 2048 = 4,194,304; the dense MLP 3 x 2048 x 10944; an
    # expert layer's 2 shared experts 3 x 2048 x 1408 x 2 = 17,301,504, its
    # 6 x 8/64 = 0.75 routed experts a token 6,488,064 and its router over
    # all 64, 131,072; the head 2048 x 12800. Attention: QK^T over 192 and
    # PV over 128 on the causal half, 3 x 4096 x 16 x 320 a layer
    matmul = (5 * 13_762_560 + 67_239_936
              + 4 * (17_301_504 + 6_488_064 + 131_072) + 26_214_400)
    per_token = 6 * matmul + 5 * 3 * 4096 * 16 * 320
    got = flops_per_step(V2_LITE, 2, 4096)
    assert got == pytest.approx(per_token * 2 * 4096, rel=1e-12)
    assert got / (2 * 4096) / 1e9 == pytest.approx(1.862, abs=1e-3)
    # the dense count would read the same file 1.57 times too high
    dense = dense_decoder.flops_per_token(V2_LITE, 4096)
    assert dense / 1e9 == pytest.approx(2.929, abs=1e-3)
