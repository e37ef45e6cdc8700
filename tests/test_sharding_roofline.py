"""Sharding rules (divisibility over ALL full configs, no allocation) and
the roofline HLO-collective parser."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.models.layers import tree_map_specs
from repro.models.registry import build
from repro.roofline.analysis import collective_bytes_from_hlo
from repro.sharding.specs import ShardingRules

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(spec_tree, rules, pspec_fn):
    bad = []

    def one(path, s):
        pspec = pspec_fn(s)
        for i, axis in enumerate(pspec):
            if axis is None:
                continue
            axes = (axis,) if isinstance(axis, str) else axis
            parts = 1
            for a in axes:
                parts *= rules.mesh.shape[a]
            if s.shape[i] % parts:
                bad.append(("/".join(path), s.shape, pspec))
        return s
    tree_map_specs(one, spec_tree)
    return bad


@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["1pod", "2pod"])
@pytest.mark.parametrize("arch", ARCHS)
def test_param_shardings_divide(arch, mesh):
    cfg = get_config(arch)
    rules = ShardingRules(mesh, fsdp=True)
    bad = _check_divisible(build(cfg).param_specs(), rules,
                           rules.param_pspec)
    assert not bad, bad[:5]


@pytest.mark.parametrize("arch", ARCHS)
def test_cache_shardings_divide(arch):
    cfg = get_config(arch)
    rules = ShardingRules(SINGLE, fsdp=True)
    model = build(cfg)
    cache_len = min(32768, cfg.decoder_max_seq or 32768)
    tree = model.cache_specs(128, cache_len, windowed=False)
    bad = _check_divisible(tree, rules, rules.cache_pspec)
    assert not bad, bad[:5]


def test_big_archs_actually_shard_params():
    """123B+ archs MUST 2D-shard their big matrices (fits-in-HBM proof)."""
    for arch in ("mistral-large-123b", "nemotron-4-340b",
                 "deepseek-v3-671b"):
        cfg = get_config(arch)
        rules = ShardingRules(SINGLE, fsdp=True)
        n_2d = 0

        def one(path, s):
            nonlocal n_2d
            pspec = rules.param_pspec(s)
            used = {a for a in pspec if a is not None}
            if {"data", "model"} <= used:
                n_2d += 1
            return s
        tree_map_specs(one, build(cfg).param_specs())
        assert n_2d > 0, f"{arch}: no 2D-sharded params"


def test_moe_experts_shard_over_model():
    cfg = get_config("deepseek-v3-671b")
    rules = ShardingRules(SINGLE, fsdp=True)
    model = build(cfg)
    specs = model.param_specs()
    moe = specs["layers"]["moe"]
    for name in ("wi_gate", "wo"):
        pspec = rules.param_pspec(moe[name])
        # stacked layer dim first, expert dim second
        assert pspec[1] == "model", f"{name}: experts not model-sharded"


def test_pod_axis_shards_batch_only():
    rules = ShardingRules(MULTI, fsdp=True)
    cfg = get_config("granite-34b")

    def one(path, s):
        pspec = rules.param_pspec(s)
        flat = []
        for a in pspec:
            if isinstance(a, tuple):
                flat.extend(a)
            elif a:
                flat.append(a)
        assert "pod" not in flat, f"param {path} sharded over pod"
        return s
    tree_map_specs(one, build(cfg).param_specs())
    bsp = rules.batch_pspec(2, batch_size=256)
    assert bsp[0] == ("pod", "data")


# ---------------------------------------------------------------- parser --

HLO_SAMPLE = """
ENTRY %main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[2048,256]{1,0} all-gather(f32[128,256]{1,0} %p0), dimensions={0}
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %ag2), to_apply=%add
  %rs = f32[8,256]{1,0} reduce-scatter(f32[128,256]{1,0} %ar), dimensions={0}
  %a2a = f32[128,256]{1,0} all-to-all(f32[128,256]{1,0} %rs), dimensions={0}
  %cp = f32[128,256]{1,0} collective-permute(f32[128,256]{1,0} %a2a)
  %dot = f32[128,128]{1,0} dot(f32[128,256] %cp, f32[256,128] %w)
}
"""


def test_collective_parser_counts_and_bytes():
    out = collective_bytes_from_hlo(HLO_SAMPLE)
    c = out["counts"]
    assert c == {"all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
                 "all-to-all": 1, "collective-permute": 1}
    b = 128 * 256 * 4
    per = out["per_op_operand_bytes"]
    assert per["all-reduce"] == b
    assert per["reduce-scatter"] == b
    # weighted: AG counts output (2048x256), AR counts 2x operand
    expected = (2048 * 256 * 4) + 2 * b + b + b + b
    assert out["collective_bytes"] == expected


def test_parser_ignores_non_collectives():
    out = collective_bytes_from_hlo(
        "%x = f32[4]{0} add(f32[4] %a, f32[4] %b)\n"
        "%s = f32[4]{0} all-gather-fusion-lookalike(f32[4] %x)\n")
    assert out["collective_bytes"] == 0


def test_dryrun_results_exist_and_pass():
    """The recorded dry-run grids must show every pair compiling."""
    import json
    import os
    root = os.path.join(os.path.dirname(__file__), "..", "experiments")
    for tag, expected_chips in (("singlepod", 256), ("multipod", 512)):
        path = os.path.join(root, f"dryrun_{tag}.json")
        if not os.path.exists(path):
            pytest.skip("dry-run grid not yet recorded")
        with open(path) as f:
            results = json.load(f)
        assert len(results) == 40
        statuses = {k: v["status"] for k, v in results.items()}
        fails = [k for k, s in statuses.items() if s == "fail"]
        assert not fails, fails
        assert sum(1 for s in statuses.values() if s == "skipped") == 1
