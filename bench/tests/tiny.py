"""A tiny copy of the benchmark's data files, for CPU tests: the real
``bench/`` files plus one small configuration and two small cells, under a
temporary root.  Nothing here is a benchmark cell."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "tiny", "source": "test only", "model_type": "qwen2",
    "hidden_size": 64,
    "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "attention_bias": True, "tie_word_embeddings": False,
    "torch_dtype": "float32"}

TINY_MIX = {"name": "tiny-mix", "prompt_len": 16, "output_len": 8,
            "domains": 4, "zipf_s": 1.1, "forget_set": 8, "forget_len": 16}

UNLEARN = {"alpha": 8.0, "lam": 1.0, "b_r": 10.0, "fisher_chunk": 4,
           "retain_sample": 32, "z_loss_global": 1e-4}


def tiny_cell(name="tiny.chat-forget", forget_rate=4.0):
    return {"name": name, "config": "tiny", "traffic": "tiny-mix",
            "chips": 1, "pool_width": 8, "generate_rate": 60.0,
            "forget_rate": forget_rate, "tau": -1, "unlearn": UNLEARN,
            "limits": {"decode_gap": 1e-3, "edit_mismatch": 0.02,
                       "drain_mismatch": 0},
            "why": "test only"}


def make_root(tmp: str, cells=None) -> str:
    """A root holding a copy of ``bench/`` (code and data) and of
    ``BENCHMARK.json``, plus the tiny configuration, mix and cells."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(b, "traffic", "tiny-mix.json"), "w") as f:
        json.dump(TINY_MIX, f)
    for c in cells if cells is not None else [tiny_cell()]:
        with open(os.path.join(b, "workloads", c["name"] + ".json"), "w") as f:
            json.dump(c, f)
    return root


CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
