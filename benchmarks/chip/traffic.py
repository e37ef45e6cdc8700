"""One general open-loop generator, parameterised by a traffic file.

A traffic mix is ``traffic/<name>.json``::

    {"arrival": "poisson", "rate_per_s": 4.0,
     "text_lengths": [32, 64, ...],      # prompt text tokens, drawn evenly
     "visual_tokens": 1024,              # per request; 0 = text only
     "visual_kept": 512,                 # of them, after compression
     "images": 16,                       # distinct images drawn from
     "compression": "none",              # the request's strategy name
     "output": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                "min": 16, "max": 256}}

Every seed gets the same work in another order: a window of ``seconds``
holds ``round(rate * seconds)`` requests whose inter-arrival gaps are the
exponential distribution's quantiles, in one fixed shuffled order, so
every seed sees the same arrival times and bursts. Output lengths are
the lognormal's quantiles and text lengths cycle evenly through the set,
each list permuted by the seed; token ids and images are drawn from the
seed. So two seeds differ in which request arrives when, never in the
amount of work or the arrival process. (Below the knee the first-token
tail follows the bursts: with the gaps permuted per seed, the 90th
percentile of six seeds spread by a quarter of its median.)
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""
    rid: int
    due_s: float                 # seconds after the window opens
    tokens: np.ndarray           # prompt text ids, int32
    max_new_tokens: int
    image: Optional[int]         # index into the image pool, None = text


def load_mix(root: Path, name: str) -> Dict:
    path = Path(root) / "traffic" / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if mix.get("arrival") != "poisson":
        raise ValueError(f"{path}: only 'poisson' arrivals are generated")
    if (mix.get("visual_tokens", 0) and mix.get("compression") != "none"
            and "visual_kept" not in mix):
        raise ValueError(f"{path}: a compressed image mix states "
                         "'visual_kept', the visual rows left per request")
    return mix


def visual_kept(mix: Dict) -> int:
    """Visual rows per image request after compression: the mix's
    ``visual_kept``, or all of ``visual_tokens`` where it compresses none."""
    return int(mix.get("visual_kept", mix.get("visual_tokens", 0)))


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any seed >= 0."""
    tag = int.from_bytes(stream.encode(), "little") % (2 ** 32)
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag]))


def _quantile_grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(out: Dict, n: int) -> np.ndarray:
    if out["dist"] != "lognormal":
        raise ValueError(f"unknown output distribution {out['dist']!r}")
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf(p) for p in _quantile_grid(n)])
    lens = np.round(out["median"] * np.exp(out["sigma"] * z))
    return np.clip(lens, out["min"], out["max"]).astype(int)


def schedule(mix: Dict, seed: int, seconds: float,
             vocab_size: int) -> List[Planned]:
    """The window's requests, in due order."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, "schedule")
    gaps = -np.log1p(-_quantile_grid(n)) / rate
    gaps = rng_for(0, "arrivals").permutation(gaps)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    due = np.cumsum(gaps)
    texts = np.resize(np.asarray(mix["text_lengths"], int), n)
    texts = rng.permutation(texts)
    outs = rng.permutation(output_lengths(mix["output"], n))
    images = mix.get("images", 0) if mix.get("visual_tokens", 0) else 0
    toks = rng_for(seed, "tokens")
    plan = []
    for i in range(n):
        plan.append(Planned(
            rid=i, due_s=float(due[i]),
            tokens=toks.integers(0, vocab_size, int(texts[i]),
                                 dtype=np.int32),
            max_new_tokens=int(outs[i]),
            image=int(toks.integers(0, images)) if images else None))
    return plan


def image_pool(mix: Dict, seed: int, width: int) -> List[np.ndarray]:
    """The distinct images requests draw from: [visual_tokens, width]
    float32 patch embeddings (the program's vision tower is a stub)."""
    nv = int(mix.get("visual_tokens", 0))
    if not nv:
        return []
    rng = rng_for(seed, "images")
    return [rng.standard_normal((nv, width), dtype=np.float32)
            for _ in range(int(mix["images"]))]


def max_request_tokens(mix: Dict) -> int:
    """Most cache positions one request of this mix can take."""
    return (int(mix.get("visual_tokens", 0)) + max(mix["text_lengths"])
            + int(mix["output"]["max"]))


def warmup_prompts(mix: Dict, seed: int, vocab_size: int) -> List[Planned]:
    """One request per text length (every prefill shape the window
    uses), each asking for two tokens so the decode step runs too."""
    rng = rng_for(seed, "warmup")
    images = 1 if mix.get("visual_tokens", 0) else 0
    return [Planned(rid=-(i + 1), due_s=0.0,
                    tokens=rng.integers(0, vocab_size, int(t),
                                        dtype=np.int32),
                    max_new_tokens=2, image=0 if images else None)
            for i, t in enumerate(mix["text_lengths"])]


