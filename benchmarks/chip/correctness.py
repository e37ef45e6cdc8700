"""What decides ``correct``: served tokens against the float32 reference.

After the window, a sample of finished requests drawn from the seed (the
one with the longest output always among them) is run through
``reference.py`` with its prompt and the tokens the program served. At
every position the number read is the gap by which the served token's
reference logit lies below the reference's best logit there; a run is
correct when the widest gap of the sample is within the cell's limit
(``limits/<cell>.json``) and every request due in the window finished.

``gaps(..., quant="fp8")`` reads the same gap for the token that the
float8 control puts first at each position: the control's tokens stand
where the served tokens stood, and ``verdict`` judges them as it judges
the program's. ``calibrate.py`` runs it, never a benchmark run.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .traffic import rng_for


@dataclasses.dataclass
class Served:
    """One finished request as the comparison needs it."""
    rid: int
    tokens: List[int]               # prompt text ids
    visual: Optional[np.ndarray]    # raw image embeddings, or None
    served: List[int]               # tokens the program delivered


def load_limits(root: Path, cell: str) -> Dict:
    with open(Path(root) / "limits" / f"{cell}.json") as f:
        return json.load(f)


def sample(done: Sequence[Served], seed: int, k: int) -> List[Served]:
    """``k`` requests drawn from the seed, the longest output among them."""
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.served), s.rid))
    rest = [s for s in done if s is not longest]
    rng = rng_for(seed, "sample")
    picks = rng.permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(picks)]


@jax.jit
def _gap_at(ref, toks):
    """Reference best minus the reference logit of ``toks``, per row."""
    at = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
    return ref.max(-1) - at


def _padded(xs: Sequence[int], n: int) -> jax.Array:
    out = np.zeros(n, np.int32)
    out[: len(xs)] = xs
    return jnp.asarray(out)


def gaps(m: Dict, params, reqs: Sequence[Served], *, visual_kept: int,
         pad_len: int, max_served: int, quant: str = "none"
         ) -> List[np.ndarray]:
    """Per request, the gap of each served token (``quant="none"``) or of
    the token the control puts first (``quant="fp8"``)."""
    out = []
    for r in reqs:
        vis = (None if r.visual is None
               else reference.select_visual(r.visual, visual_kept))
        ref = reference.served_logits(m, params, r.tokens, vis, r.served,
                                      pad_len, max_served)
        if quant == "none":
            toks = _padded(r.served, max_served)
        else:
            ctl = reference.served_logits(m, params, r.tokens, vis,
                                          r.served, pad_len, max_served,
                                          quant=quant)
            toks = jnp.argmax(ctl, axis=-1).astype(jnp.int32)
            del ctl
        g = np.asarray(_gap_at(ref, toks))[: len(r.served)]
        out.append(g)
        del ref
    return out


def widest(per_request: Sequence[np.ndarray]) -> float:
    return float(max((g.max() for g in per_request if len(g)),
                     default=0.0))


def verdict(gap: float, unfinished: int, n_compared: int,
            limits: Dict) -> Tuple[bool, Dict]:
    """``correct`` and the numbers compared, each beside its limit. A run
    that compared no request is not correct."""
    checks = {
        "logit_gap": {"value": gap, "limit": limits["logit_gap"]},
        "unfinished": {"value": unfinished, "limit": 0},
    }
    correct = (gap <= limits["logit_gap"] and unfinished == 0
               and n_compared > 0)
    return bool(correct), checks
