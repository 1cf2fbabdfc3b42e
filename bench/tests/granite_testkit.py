"""The tiny stand-in of the scoring cell: a granite-4.0-h-micro cut to
test size (every mechanism of the published layers, two repeats of the
published layer period), scoring short documents on the CPU.

``extend()`` (called from the repository's root ``conftest.py``) adds
the stand-in to ``bench_testkit``'s tiny cells, so that every test that
runs the tiny cells (one per traffic mix of ``BENCHMARK.json``) finds one
for the ``score`` mix too.
"""
from __future__ import annotations

import json
import os

import bench_testkit as kit

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
TINY_GRANITE = {
    "hidden_size": 64, "vocab_size": 512, "mamba_n_heads": 16,
    "mamba_d_head": 8, "mamba_expand": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_conv_bias": True,
    "mamba_chunk_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "shared_intermediate_size": 128,
    "layer_types": PERIOD * 2, "position_embedding_type": "nope",
    "rope_theta": 10000, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "dtype": "bfloat16", "reference": "granite4h",
    "program_arch": "granite-4.0-h-micro"}
CELL = ("score.tiny", "tiny-granite", "score")
SETTINGS = {"length": 64, "documents": 2, "positions": 4,
            "trace_seconds": None, "ssd_block": 16,
            "limits": {"max_logit_gap": 0.2, "mean_logit_gap": 0.04}}


def extend() -> None:
    """Add the tiny scoring cell to ``bench_testkit`` (once)."""
    if CELL in kit.CELLS:
        return
    kit.CELLS.append(CELL)
    kit.TRAFFIC["score"] = {"driver": "score"}
    kit.SETTINGS[CELL[0]] = SETTINGS
    make_root = kit.make_root

    def make_root_with_score(tmp: str) -> str:
        root = make_root(tmp)
        path = os.path.join(root, "bench", "configs", f"{CELL[1]}.json")
        with open(path, "w") as f:
            json.dump(TINY_GRANITE, f)
        bench_json = os.path.join(root, "BENCHMARK.json")
        with open(bench_json) as f:
            bench = json.load(f)
        bench["configs"].append({"name": CELL[1], "source": "test",
                                 "file": f"bench/configs/{CELL[1]}.json",
                                 "reduced": [], "why": "test"})
        with open(bench_json, "w") as f:
            json.dump(bench, f)
        return root
    kit.make_root = make_root_with_score
