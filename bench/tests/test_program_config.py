"""The program's configuration from a configuration file, for any layout
(``bench/traffic/train.py:program_config``): the cuts that the reference
module's ``APPLIED`` names are set, nested blocks included; the widths of
the module's ``WIDTHS`` and every field of the file's ``program_arch``,
into nested blocks, are checked; a mismatch is refused, and so is a module
that sets a field it checks. The other architecture is the program's
DeepSeek-V2 ``SMOKE`` configuration (latent attention, shared and routed
experts) with its experts cut from 4 to the 2 held here, read through the
toy reference module ``toy_mla_moe``."""
import dataclasses
import json
import sys
from pathlib import Path

import pytest

from bench.traffic.train import program_config

BENCH = Path(__file__).resolve().parents[1]
SMOKE_FILE = {
    "name": "deepseek-smoke-e2", "reference": "toy_mla_moe",
    "program_config": "repro.configs.deepseek_v2_236b:SMOKE",
    "program_arch": {"family": "moe", "attn_kind": "mla",
                     "moe": {"n_experts": 2, "top_k": 2, "n_shared": 1,
                             "every": 1},
                     "mla": {"kv_lora": 64, "q_lora": 96}},
    "hidden_size": 256, "num_attention_heads": 8, "intermediate_size": 128,
    "kv_lora_rank": 64, "q_lora_rank": 96, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "moe_intermediate_size": 128,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "n_routed_experts": 2,
    "n_routed_experts_total": 4, "first_k_dense_replace": 0,
    "num_hidden_layers": 1, "vocab_size": 512, "param_dtype": "float32",
    "compute_dtype": "float32", "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "remat": False, "scopes": ["router"],
    "reduced": {"n_routed_experts": "4 -> 2, the experts held here",
                "num_hidden_layers": "2 -> 1"}}


@pytest.fixture
def toy(monkeypatch):
    from bench.tests import toy_mla_moe
    monkeypatch.setitem(sys.modules, "bench.reference.toy_mla_moe",
                        toy_mla_moe)
    return toy_mla_moe


def _file(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name,module", [
    ("phi3-mini-3.8b-l2", "repro.configs.phi3_mini_3p8b"),
    ("mistral-nemo-12b-l2-v16k", "repro.configs.mistral_nemo_12b"),
])
def test_dense_files_give_the_program_config_they_gave(name, module):
    import importlib
    c = _file(name)
    base = importlib.import_module(module).CONFIG
    want = dataclasses.replace(
        base, n_layers=c["num_hidden_layers"], vocab=c["vocab_size"],
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"],
        norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        remat=c["remat"])
    assert program_config(c) == want


def test_nested_cuts_are_applied_and_checked(toy):
    from repro.configs.deepseek_v2_236b import SMOKE
    from repro.models.model import param_shape_structs
    cfg = program_config(SMOKE_FILE)
    assert cfg.moe == dataclasses.replace(SMOKE.moe, n_experts=2)
    assert cfg.mla == SMOKE.mla
    assert (cfg.n_layers, cfg.norm_eps, cfg.remat) == (1, 1e-6, False)
    experts = param_shape_structs(cfg)["layers"]["slot0"]["ffn"]
    assert experts["wi_gate"]["w"].shape == (1, 2, 256, 128)


def _with(d, path, value):
    d = json.loads(json.dumps(d))
    *parents, leaf = path.split(".")
    node = d
    for p in parents:
        node = node[p]
    node[leaf] = value
    return d


@pytest.mark.parametrize("path,value", [
    ("program_arch.moe.top_k", 6),          # a nested field stated wrong
    ("program_arch.moe.n_experts", 4),      # the cut not applied
    ("program_arch.mla.kv_lora", 512),
    ("program_arch.moe", None),             # a block the program has
    ("kv_lora_rank", 512),                  # a width in the module's table
    ("num_experts_per_tok", 6),
    ("n_routed_experts", 4),                # the cut left out of the file
])
def test_a_mismatch_is_refused(toy, path, value):
    with pytest.raises(ValueError, match="differs from"):
        program_config(_with(SMOKE_FILE, path, value))


def test_a_stated_block_the_program_lacks_is_refused(toy):
    c = _file("phi3-mini-3.8b-l2")
    c["program_arch"]["moe"] = {"n_experts": 8, "top_k": 2}
    with pytest.raises(ValueError, match="differs from"):
        program_config(c)
    c = dict(SMOKE_FILE,
             program_config="repro.configs.phi3_mini_3p8b:CONFIG")
    with pytest.raises(ValueError, match="has no moe"):
        program_config(c)


@pytest.mark.parametrize("field,key", [
    ("d_ff", "intermediate_size"), ("mla.kv_lora", "kv_lora_rank")])
def test_a_module_that_sets_a_width_it_checks_is_refused(toy, monkeypatch,
                                                         field, key):
    monkeypatch.setattr(toy, "APPLIED", {**toy.APPLIED, field: key})
    with pytest.raises(ValueError, match="both sets and checks"):
        program_config(SMOKE_FILE)
