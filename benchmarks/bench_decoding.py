"""Benchmark: advanced decoding (survey dim 4), via the ``repro.api``
facade -- the same ``generate()`` signature drives every strategy.

  * speculative decoding: target-model calls saved vs gamma (the memory-
    bound decode loop is the cost unit) for self-draft (upper bound),
    untrained draft, and LANTERN relaxation,
  * early exit: layers used vs confidence threshold.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit
from repro.api import GenerationConfig, LVLM
from repro.launch.cache import enable_compile_cache


def speculative() -> None:
    target = LVLM.from_pretrained("phi4-mini-3.8b", smoke=True)
    draft = LVLM.from_pretrained(
        "phi4-mini-3.8b", smoke=True, seed=1, num_layers=1, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=256, head_dim=32)
    rng = np.random.RandomState(0)
    prompt = list(rng.randint(1, target.cfg.vocab_size, size=24))
    n_new = 24
    for gamma in (2, 4):
        gen = GenerationConfig(decoder="speculative", temperature=0.0,
                               max_new_tokens=n_new, gamma=gamma)
        cases = (
            # self-draft = acceptance upper bound
            ("self", target.generate(prompt, gen)),
            ("draft", target.generate(prompt, gen, draft=draft)),
            ("lantern", target.generate(
                prompt, gen.with_(temperature=0.8, lantern_k=16,
                                  lantern_delta=0.3), draft=draft)),
        )
        for tag, res in cases:
            st = res.stats
            speedup = n_new / max(st["target_calls"], 1)
            emit(f"decode/spec/g{gamma}/{tag}", 0.0,
                 f"accept={st['acceptance']:.3f};"
                 f"target_calls={st['target_calls']};"
                 f"call_reduction={speedup:.2f}x")


def early_exit() -> None:
    lvlm = LVLM.from_pretrained("phi4-mini-3.8b", smoke=True)
    rng = np.random.RandomState(1)
    prompt = list(rng.randint(1, lvlm.cfg.vocab_size, size=24))
    for thr in (1.1, 0.5, 0.0):
        res = lvlm.generate(prompt, GenerationConfig(
            decoder="early_exit", temperature=0.0, max_new_tokens=8,
            exit_threshold=thr, exit_patience=0, exit_min_layers=1))
        st = res.stats
        emit(f"decode/early_exit/thr{thr}", 0.0,
             f"layers={st['layers_used_mean']:.1f}/{lvlm.cfg.num_layers};"
             f"flops_frac={st['layers_used_mean'] / lvlm.cfg.num_layers:.2f}")


def run() -> None:
    speculative()
    early_exit()


if __name__ == "__main__":
    enable_compile_cache()
    run()
