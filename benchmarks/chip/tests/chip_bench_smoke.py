"""A tiny copy of the benchmark's data tree for the CPU tests.

``make_tree(tmp)`` writes ``BENCHMARK.json`` and a benchmark directory
with one smoke-sized configuration, mixes and limits under ``tmp``, and
copies the real metric readers and reference modules, so
``harness.run_cell(..., repo=tmp, root=tmp / "bench",
require_chip=False)`` runs a whole cell in seconds.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
REPO = CHIP.parents[1]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_MODELS = {
    "qwen-smoke": {
        "arch": "qwen2-vl-2b",
        "model": {"num_hidden_layers": 2, "hidden_size": 128,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 32, "intermediate_size": 256,
                  "vocab_size": 512, "hidden_act": "silu",
                  "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
                  "rope_scaling": {"type": "mrope",
                                   "mrope_section": [4, 6, 6]},
                  "tie_word_embeddings": True,
                  "torch_dtype": "float32", "visual_tokens": 16},
        "engine": {"max_batch": 4, "cache_len": 128}},
    "phi-smoke": {
        "arch": "phi4-mini-3.8b",
        "model": {"num_hidden_layers": 2, "hidden_size": 128,
                  "num_attention_heads": 8, "num_key_value_heads": 2,
                  "head_dim": 16, "intermediate_size": 256,
                  "vocab_size": 512, "hidden_act": "silu",
                  "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
                  "partial_rotary_factor": 0.75,
                  "rope_scaling": {"type": "longrope"},
                  "tie_word_embeddings": True, "torch_dtype": "float32"},
        "engine": {"max_batch": 4, "cache_len": 128},
        # as the published configuration's: what the program serves
        "departures": {"served": {"rms_norm_eps": 1e-06,
                                  "partial_rotary_factor": 1.0,
                                  "rope_scaling": None}}},
}

MIXES = {
    "image_fastv": {"arrival": "poisson", "rate_per_s": 4.0,
                    "text_lengths": [8, 16], "visual_tokens": 16,
                    "visual_kept": 8, "images": 2,
                    "compression": "fastv-0.5",
                    "output": {"dist": "lognormal", "median": 6,
                               "sigma": 0.5, "min": 3, "max": 12}},
    "text": {"arrival": "poisson", "rate_per_s": 4.0,
             "text_lengths": [8, 24], "visual_tokens": 0,
             "compression": "none",
             "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                        "min": 3, "max": 12}},
}

CELLS = {"smoke.image_fastv": ("qwen-smoke", "image_fastv"),
         "smoke.text": ("phi-smoke", "text")}

# float32 against float32 at HIGHEST on the CPU: agreement to rounding
SMOKE_LIMIT = 1e-3

# a profiler trace recorded on one TPU v5e: inside the window span, four
# runs of a small jitted program ``tiny_step``, each dispatched under a
# "bench:decode program" span and followed by a 3 ms host sleep under a
# "bench:engine step (host)" span
TPU_TRACE = HERE / "data" / "tiny_tpu.xplane.pb"


def make_tree(tmp: Path) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    root = tmp / "bench"
    for sub in ("configs", "traffic", "limits"):
        (root / sub).mkdir(parents=True)
    shutil.copytree(CHIP / "metrics", root / "metrics")
    shutil.copytree(CHIP / "references", root / "references",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench["configs"] = []
    for name, conf in SMOKE_MODELS.items():
        (root / "configs" / f"{name}.json").write_text(json.dumps(conf))
        bench["configs"].append({"name": name, "source": "smoke",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "CPU test"})
    for name, mix in MIXES.items():
        (root / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench["workloads"] = []
    for cell, (conf, mix) in CELLS.items():
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": mix, "chips": 1,
                                   "why": "CPU test"})
        (root / "limits" / f"{cell}.json").write_text(json.dumps(
            {"logit_gap": SMOKE_LIMIT, "sample": 3}))
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = sorted(CELLS)
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["smoke.image_fastv"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
