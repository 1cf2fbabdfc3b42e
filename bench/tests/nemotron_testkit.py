"""The tiny stand-in of the Nemotron-H scoring cell: NVIDIA-Nemotron-3-
Nano-30B-A3B cut to test size on the CPU, with every mechanism of the
published layers (Mamba-2 of two B/C groups, sigmoid-routed MoE with a
correction bias and a shared expert, NoPE attention, an untied head), the
published pattern's first 20 layers (each kind near its published
share), and a held share of 4 of 8 experts that does not start at
expert 0."""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "nemotron-3-nano-30b-a3b.json")) as f:
    FULL = json.load(f)
TINY_NEMOTRON = dict(
    FULL, hidden_size=64, vocab_size=512, mamba_num_heads=8,
    mamba_head_dim=8, n_groups=2, ssm_state_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=4,
    n_routed_experts_published=8, held_experts_offset=2,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64,
    hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*", num_hidden_layers=20)
CELL = ("score.tiny.nemotron", "tiny-nemotron", "score")
# served in bfloat16 at test size, half the experts are held and a token
# picks 3 of 8, so picks that rounding moves across a near-tie fall on a
# held expert often and dominate the gaps; the float8 control reads
# about 2.4x the program's widest gap and 2.6x its mean

SETTINGS = {"length": 64, "documents": 2, "positions": 4,
            "trace_seconds": None, "ssd_block": 16,
            "limits": {"max_logit_gap": 3.0, "mean_logit_gap": 0.35}}


def add_to(root: str) -> None:
    """Add the tiny Nemotron scoring cell to a test kit checkout."""
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", f"{CELL[1]}.json"), "w") as f:
        json.dump(TINY_NEMOTRON, f)
    with open(os.path.join(bench, "workloads", f"{CELL[0]}.json"), "w") as f:
        json.dump(SETTINGS, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench_json = json.load(f)
    bench_json["configs"].append({"name": CELL[1], "source": "test",
                                  "file": f"bench/configs/{CELL[1]}.json",
                                  "reduced": [], "why": "test"})
    bench_json["workloads"].append({"name": CELL[0], "config": CELL[1],
                                    "traffic": CELL[2], "chips": 1,
                                    "why": "test"})
    with open(path, "w") as f:
        json.dump(bench_json, f)
