"""A throwaway benchmark root with tiny cells, built from new files only,
for the CPU tests: the same files a later PR would add for a real cell."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DENSE = dict(name="tiny-dense", family="dense_gqa", num_hidden_layers=2,
             hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=256,
             sliding_window=48, rope_theta=10000.0, rms_norm_eps=1e-5,
             tie_word_embeddings=False, torch_dtype="bfloat16")
SSM = dict(name="tiny-ssm", family="mamba2", n_layer=2, d_model=64,
           expand=2, headdim=16, d_state=16, d_conv=4, vocab_size=256,
           norm_epsilon=1e-5, tie_embeddings=True, torch_dtype="bfloat16")


def traffic(limit: float, sample: int = 8) -> dict:
    return {"kind": "serve", "engine": {"slots": 4, "max_len": 96},
            "arrivals": {"process": "gamma", "cv": 2.0, "rate_per_s": 8.0},
            "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                              "min": 4, "max": 60},
            "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                              "min": 3, "max": 24},
            "fill_s": 0.5, "drain_s": 60,
            "correct": {"sample_requests": sample, "max_logit_gap": limit}}


# Widest logit gaps at these sizes on the CPU over seeds 1-8, 2**31+3 and
# 2**33+9 (0.5 s of fill, 1.5 s windows, 8 requests compared): the bf16
# program reads at most 0.0077 (dense) and 0.0032 (ssm), the float8
# control at least 0.0388 and 0.0164. Each limit lies between the two.
LIMITS = {"tiny-dense": 0.015, "tiny-ssm": 0.007}

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(tmp: str) -> str:
    """A root with BENCHMARK.json, two tiny configs and their traffic."""
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    shutil.copytree(os.path.join(REPO, "chipbench", "metrics"),
                    os.path.join(tmp, "chipbench", "metrics"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"], bench["workloads"] = [], []
    for c in (DENSE, SSM):
        name = c["name"]
        path = os.path.join("chipbench", "configs", name + ".json")
        with open(os.path.join(tmp, path), "w") as f:
            json.dump(dict(c, reduced=[]), f)
        with open(os.path.join(tmp, "chipbench", "traffic",
                               name + ".chat.json"), "w") as f:
            json.dump(traffic(LIMITS[name]), f)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
        bench["workloads"].append({"name": name + ".chat", "config": name,
                                   "traffic": name + ".chat", "chips": 1,
                                   "why": "test"})
    names = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = names
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
