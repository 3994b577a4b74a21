"""A CPU-sized stand-in for the benchmark's cells: the same configuration
keys and mix parameters at widths the Pallas interpreter runs in seconds."""
from __future__ import annotations

import copy

from perfbench.common import BENCH_DIR, load_json

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "vocab_size": 2048}


def tiny_spec(config: str, traffic: str, workload: str, **mix_over) -> dict:
    cfg = load_json(BENCH_DIR / "configs" / f"{config}.json")
    cfg.update(TINY_MODEL)
    mix = copy.deepcopy(load_json(BENCH_DIR / "mixes" / f"{traffic}.json"))
    mix.update(mix_over)
    return {"cell": {"name": workload, "config": config, "traffic": traffic,
                     "chips": 1},
            "config": cfg, "mix": mix,
            "limits": load_json(BENCH_DIR / "limits" / f"{workload}.json")}
