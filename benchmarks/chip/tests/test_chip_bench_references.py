"""Reference modules: found by the name a configuration gives them, the
only files that know a family, and the dense decoder's pinned to what
the benchmark has read on it.

The literals pin what every reading of the dense cells rests on: the
weights ``weights.make`` draws for seed 7 (a digest of each leaf's
bytes), the reference logits on a fixed prompt and the float8 control's,
and the work counts the ``mfu*`` and ``*_roofline`` readers take, at
fixed inputs, for the smoke models and the published configurations. A
change to any of them changes what the benchmark reads.
"""
from __future__ import annotations

import hashlib
import inspect
import json
import re
import time
from pathlib import Path
from types import SimpleNamespace
from typing import List

import jax
import numpy as np
import pytest

from chip_bench_smoke import CHIP, MIXES, SMOKE_LIMIT, SMOKE_MODELS, \
    make_tree
from benchmarks.chip import harness, weights

DENSE = harness.load_reference(CHIP, "dense_gqa")
PUBLISHED = ("qwen2-vl-2b", "phi4-mini-3.8b")

WEIGHTS = {
    "phi-smoke": {
        "embed/tok": "68eeb2dec2ba4331",
        "final_norm/scale": "f8f94a410d56e108",
        "layers/attn/wk": "c473f61ebb0c9a14",
        "layers/attn/wo": "3a8612447a103ea8",
        "layers/attn/wq": "d71df0219bc8a523",
        "layers/attn/wv": "2a52911118f71423",
        "layers/ln1/scale": "7bcb73250904cf14",
        "layers/ln2/scale": "89d2ff7f2c8eada1",
        "layers/mlp/wi_gate": "a14691c1be6eb465",
        "layers/mlp/wi_up": "0762c1f03960ac2d",
        "layers/mlp/wo": "7c0bd6ad59fa8daa"},
    "qwen-smoke": {
        "embed/tok": "68eeb2dec2ba4331",
        "final_norm/scale": "f8f94a410d56e108",
        "layers/attn/wk": "258fefcc3f8a9093",
        "layers/attn/wo": "3a8612447a103ea8",
        "layers/attn/wq": "d71df0219bc8a523",
        "layers/attn/wv": "4e921d0b527658a7",
        "layers/ln1/scale": "7bcb73250904cf14",
        "layers/ln2/scale": "89d2ff7f2c8eada1",
        "layers/mlp/wi_gate": "a14691c1be6eb465",
        "layers/mlp/wi_up": "0762c1f03960ac2d",
        "layers/mlp/wo": "7c0bd6ad59fa8daa",
        "projector/w1": "eed17917ac7e76b6",
        "projector/w2": "1a077a309b80671a"},
}

LOGITS = {("phi-smoke", "none"): "453f12ad02d5db97",
          ("phi-smoke", "fp8"): "0d356441d1756992",
          ("qwen-smoke", "none"): "b0f06fdd4845ce93",
          ("qwen-smoke", "fp8"): "62b39c0004080e8a"}

# prefill (11 text + all visual, 300 + half, 64 + none), decode (one row
# at context 1; three rows), window_ops over RECORDS in [1, 2] s
COUNTS = {
    "phi-smoke": {
        "prefill": [(6326272.0, 693760.0), (213481472.0, 841728.0),
                    (37912576.0, 720896.0)],
        "decode": [(689152.0, 688384.0), (4366336.0, 1263616.0)],
        "window": (170037248.0, 4588544.0)},
    "qwen-smoke": {
        "prefill": [(17491968.0, 807168.0), (231049216.0, 1022976.0),
                    (40009728.0, 770048.0)],
        "decode": [(721920.0, 721408.0), (4464640.0, 1871872.0)],
        "window": (184492032.0, 4826112.0)},
    "phi4-mini-3.8b": {
        "prefill": [(72122105856.0, 7673153536.0),
                    (1951718178816.0, 7712808960.0),
                    (414363942912.0, 7680425984.0)],
        "decode": [(7672037376.0, 7671775232.0),
                   (23898882048.0, 7966294016.0)],
        "window": (1711986966528.0, 46206418944.0)},
    "qwen2-vl-2b": {
        "prefill": [(2814467063808.0, 3129431040.0),
                    (2189840400384.0, 3122352128.0),
                    (168529625088.0, 3089170432.0)],
        "decode": [(3087310848.0, 3087167488.0),
                   (9648144384.0, 3151593472.0)],
        "window": (2071234510848.0, 19040477184.0)},
}

# (text tokens, image index or None, token times)
RECORDS = [(37, 0, [0.5, 1.1, 1.2, 1.9, 2.4]), (200, None, [1.0, 1.5]),
           (64, 1, [1.25, 1.75, 2.0]), (9, None, [2.5])]

# what a family publishes: the keys of dense, latent-attention and expert
# models, and the paths of their weights; only references/ names them
FAMILY_NAMES = re.compile(
    r"num_key_value_heads|num_attention_heads|intermediate_size|head_dim"
    r"|kv_lora_rank|q_lora_rank|qk_nope_head_dim|qk_rope_head_dim"
    r"|v_head_dim|n_routed_experts|n_shared_experts|num_experts_per_tok"
    r"|moe_intermediate_size|routed_scaling_factor|first_k_dense_replace"
    r"|scoring_func|topk_method|(dense_)?layers/|projector/")

# what the harness and the readers take from a reference module
INTERFACE = ("program_fields", "layout", "served_logits", "prefill_cost",
             "decode_cost", "window_ops", "least_time")

MODULES = sorted(p.stem for p in (CHIP / "references").glob("*.py"))


def _digest(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


def _published(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def family_named(root: Path) -> List[str]:
    """The files under ``root``, outside ``references/`` and ``tests/``,
    that name a family's published keys or weight paths."""
    named = []
    for p in sorted(root.rglob("*.py")):
        rel = p.relative_to(root)
        if (rel.parts[0] not in ("references", "tests")
                and FAMILY_NAMES.search(p.read_text())):
            named.append(rel.as_posix())
    return named


def lacks(mod) -> List[str]:
    """What ``mod`` does not give of a reference module's interface."""
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if "served_logits" not in missing and "quant" not in \
            inspect.signature(mod.served_logits).parameters:
        missing.append("served_logits(quant=)")
    return missing


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_dense_weights_are_pinned(name):
    params = weights.make(DENSE.layout(SMOKE_MODELS[name]["model"]),
                          "float32", 7)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {"/".join(k.key for k in path): _digest(x) for path, x in leaves}
    assert got == WEIGHTS[name]


@pytest.mark.parametrize("name,quant", sorted(LOGITS))
def test_dense_reference_logits_are_pinned(name, quant):
    conf = SMOKE_MODELS[name]
    m = harness.served_model(conf)
    params = weights.make(DENSE.layout(conf["model"]), "float32", 7)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, m["vocab_size"], 11).tolist()
    served = rng.integers(0, m["vocab_size"], 6).tolist()
    nv = m.get("visual_tokens", 0)
    visual = (rng.standard_normal((nv, m["hidden_size"])).astype(np.float32)
              if nv else None)
    logits = DENSE.served_logits(m, params, tokens, visual, served,
                                 conf["engine"]["cache_len"], 8, quant=quant)
    assert _digest(logits) == LOGITS[name, quant]


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_dense_counts_are_pinned(name):
    m = (SMOKE_MODELS[name]["model"] if name in SMOKE_MODELS
         else _published(name)["model"])
    nv = m.get("visual_tokens", 0)
    recs = [SimpleNamespace(plan=SimpleNamespace(tokens=np.zeros(n),
                                                 image=img), times=t)
            for n, img, t in RECORDS]
    got = {"prefill": [DENSE.prefill_cost(m, 11, nv),
                       DENSE.prefill_cost(m, 300, nv // 2),
                       DENSE.prefill_cost(m, 64, 0)],
           "decode": [DENSE.decode_cost(m, [1]),
                      DENSE.decode_cost(m, [12, 700, 1536])],
           "window": DENSE.window_ops(m, nv // 2, recs, 1.0, 2.0)}
    assert got == COUNTS[name]


@pytest.mark.parametrize("name", PUBLISHED)
def test_a_configuration_without_a_reference_key_takes_dense_gqa(name):
    conf = _published(name)
    assert "reference" not in conf
    assert harness.reference_of(conf) is DENSE


def test_no_file_outside_references_names_a_family_key():
    assert family_named(CHIP) == []
    # the pattern sees what a module names: the guard is not vacuous
    assert FAMILY_NAMES.search(
        (CHIP / "references" / "dense_gqa.py").read_text())


@pytest.mark.parametrize("name", MODULES)
def test_a_reference_module_gives_the_interface(name):
    assert lacks(harness.load_reference(CHIP, name)) == []


SECOND = '''"""dense_gqa's equations, beside the keys and weight paths of a
latent-attention expert family, named as that family publishes them."""
from pathlib import Path

from benchmarks.chip import harness

dense = harness.load_reference(Path(__file__).parents[1], "dense_gqa")
least_time = dense.least_time
PUBLISHED = ("num_attention_heads", "num_key_value_heads", "head_dim",
             "intermediate_size", "kv_lora_rank", "q_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
             "moe_intermediate_size", "routed_scaling_factor",
             "first_k_dense_replace", "scoring_func", "topk_method")
LEAVES = ("layers/attn/wkv_a", "layers/attn/wkv_b", "layers/moe/router",
          "dense_layers/mlp/wo", "projector/w1")
program_fields, layout = dense.program_fields, dense.layout
served_logits = dense.served_logits
prefill_cost, decode_cost = dense.prefill_cost, dense.decode_cost
window_ops = dense.window_ops
'''

PLANTED = ("num_attention_heads", "qk_rope_head_dim", "n_routed_experts",
           "layers/attn/wkv_a", "dense_layers/mlp/wo", "projector/w1")


def test_a_second_family_module_passes_the_guard(tmp_path):
    root = make_tree(tmp_path)
    (root / "references" / "second.py").write_text(SECOND)
    assert all(name in SECOND for name in PLANTED)
    assert family_named(root) == []
    assert lacks(harness.load_reference(root, "second")) == []


@pytest.mark.parametrize("name", PLANTED)
def test_a_family_name_outside_references_is_caught(tmp_path, name):
    root = make_tree(tmp_path)
    (root / "metrics" / "x.py").write_text(f"KEY = {name!r}\n")
    assert family_named(root) == ["metrics/x.py"]


TOY = '''"""dense_gqa's equations, for a model that publishes its key-value
head count as ``n_kv_heads``."""
from pathlib import Path

from benchmarks.chip import harness

dense = harness.load_reference(Path(__file__).parents[1], "dense_gqa")
least_time = dense.least_time


def _m(m):
    m = dict(m)
    m["num_key_value_heads"] = m.pop("n_kv_heads")
    return m


def program_fields(m):
    return dense.program_fields(_m(m))


def layout(m):
    return dense.layout(_m(m))


def served_logits(m, *args, **kw):
    return dense.served_logits(_m(m), *args, **kw)


def prefill_cost(m, *args):
    return dense.prefill_cost(_m(m), *args)


def decode_cost(m, *args):
    return dense.decode_cost(_m(m), *args)


def window_ops(m, *args):
    return dense.window_ops(_m(m), *args)
'''


def _add_toy_cell(tmp_path, reference):
    """A configuration, mix and limits added as files, and the cell that
    names them; ``reference`` is the configuration's module, if any."""
    root = make_tree(tmp_path)
    (root / "references" / "toy.py").write_text(TOY)
    conf = json.loads(json.dumps(SMOKE_MODELS["phi-smoke"]))
    conf["model"]["n_kv_heads"] = conf["model"].pop("num_key_value_heads")
    if reference:
        conf["reference"] = reference
    (root / "configs" / "toy-smoke.json").write_text(json.dumps(conf))
    (root / "traffic" / "toy_text.json").write_text(
        json.dumps(MIXES["text"]))
    (root / "limits" / "toy.text.json").write_text(json.dumps(
        {"logit_gap": SMOKE_LIMIT, "sample": 3}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-smoke", "source": "smoke",
                             "file": "bench/configs/toy-smoke.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": "toy.text", "config": "toy-smoke",
                               "traffic": "toy_text", "chips": 1,
                               "why": "CPU test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(tmp_path, root):
    return harness.run_cell("toy.text", 2 ** 31 + 14, 2.0, False,
                            t_start=time.perf_counter(), repo=tmp_path,
                            root=root, require_chip=False)


def test_a_reference_module_added_as_a_file_runs_a_cell(tmp_path):
    root = _add_toy_cell(tmp_path, "toy")
    # the module names the keys it maps, as published and as renamed
    assert family_named(root) == []
    out = _run(tmp_path, root)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 8
    assert out["checks"]["logit_gap"]["value"] <= SMOKE_LIMIT


def test_a_configuration_needs_the_module_that_knows_its_keys(tmp_path):
    # the same files without "reference" fall to dense_gqa, which does not
    # know the renamed key: the toy module is what made the cell run
    root = _add_toy_cell(tmp_path, None)
    with pytest.raises(KeyError, match="num_key_value_heads"):
        _run(tmp_path, root)


def test_a_missing_reference_module_fails_naming_its_path(tmp_path):
    root = _add_toy_cell(tmp_path, "no_such_module")
    with pytest.raises(FileNotFoundError) as err:
        _run(tmp_path, root)
    assert str(root / "references" / "no_such_module.py") in str(err.value)
