"""A cell at the program's CPU size: the cell's own configuration file and
traffic mix, with the widths of the program's SMOKE configuration and
sequences of 128, for driving whole runs in the tests."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def small_parts(workload: str):
    from bench.harness import cell_parts, load_benchmark
    cell, config, traffic = cell_parts(load_benchmark(), workload)
    nemo = "nemo" in config["name"]
    config = dict(config, program_config=config["program_config"] + ":SMOKE",
                  hidden_size=256, intermediate_size=512,
                  num_attention_heads=4, num_key_value_heads=2 if nemo else 4,
                  head_dim=32 if nemo else 64, vocab_size=512)
    return cell, config, dict(traffic, seq_len=128)


def limits(workload: str) -> dict:
    with open(BENCH / "limits" / f"{workload}.json") as f:
        return json.load(f)
