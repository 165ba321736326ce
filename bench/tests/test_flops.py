"""Model FLOPs from shapes, against counts worked out by hand."""
import json
from pathlib import Path

import pytest

from bench.flops import flops_per_step, matmul_params

BENCH = Path(__file__).resolve().parents[1]
PHI3 = dict(hidden_size=3072, num_attention_heads=32, num_key_value_heads=32,
            head_dim=96, intermediate_size=8192, vocab_size=32064)
NEMO = dict(hidden_size=5120, num_attention_heads=32, num_key_value_heads=8,
            head_dim=128, intermediate_size=14336, vocab_size=16384)


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
