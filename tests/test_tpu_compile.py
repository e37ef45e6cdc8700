"""Compile rehearsal for one TPU v5e chip at qwen2-vl-2b's published widths.

Nothing here runs on a chip: each test compiles for a described ``v5e:2x2``
topology (one of its chips) with abstract inputs, which raises what the
chip's compiler would raise -- misaligned Pallas blocks, too much fast
memory, a program that does not fit the device. The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker that runs this file loads the TPU compiler.
"""
import pytest

import jax
import jax.numpy as jnp
from functools import partial
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.serving import Engine, EngineConfig
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.models.registry import build

HBM_BYTES = 16 * 10 ** 9            # TPU v5e: 16 GB of HBM per chip
MAX_BATCH, CACHE_LEN = 4, 2048
N_TEXT, PAGE = 40, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A TPU compile written to the persistent cache cannot be read back
    without a chip, so the cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def on_chip(one_chip, no_persistent_cache):
    """Abstract inputs placed on one described v5e chip."""
    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
    return place


@pytest.fixture(scope="module")
def engine():
    """The serving engine at published widths; its params stay abstract
    (only the KV pool is allocated, on the host, and never run)."""
    model = build(get_config("qwen2-vl-2b"))
    return Engine(model, model.abstract_params(),
                  EngineConfig(max_batch=MAX_BATCH, cache_len=CACHE_LEN))


def _fits(compiled) -> None:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, ma


def test_engine_prefill_compiles(engine, on_chip):
    """1024 visual embeddings + 40 text tokens into a 2048-token cache."""
    cfg = engine.cfg
    batch = {"tokens": jax.ShapeDtypeStruct((1, N_TEXT), jnp.int32),
             "visual_embeds": jax.ShapeDtypeStruct(
                 (1, cfg.num_visual_tokens, cfg.d_model), jnp.float32)}
    compiled = engine._jit_prefill.lower(
        on_chip(engine.params), on_chip(batch)).compile()
    _fits(compiled)


def test_engine_decode_step_compiles(engine, on_chip):
    """One fixed-shape decode step over the [4, 2048] slot pool."""
    compiled = engine._jit_decode.lower(
        on_chip(engine.params), on_chip(engine.pool),
        on_chip(jax.ShapeDtypeStruct((MAX_BATCH, 1), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32))).compile()
    _fits(compiled)


def _kernel_cases():
    cfg = get_config("qwen2-vl-2b")
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pages = CACHE_LEN // PAGE
    flash = (partial(flash_attention, causal=True, interpret=False),
             [((1, h, CACHE_LEN, d), jnp.bfloat16),
              ((1, kvh, CACHE_LEN, d), jnp.bfloat16),
              ((1, kvh, CACHE_LEN, d), jnp.bfloat16)])
    paged = (partial(paged_attention, interpret=False),
             [((MAX_BATCH, h, d), jnp.bfloat16),
              ((kvh, MAX_BATCH * pages, PAGE, d), jnp.bfloat16),
              ((kvh, MAX_BATCH * pages, PAGE, d), jnp.bfloat16),
              ((MAX_BATCH, pages), jnp.int32),
              ((MAX_BATCH,), jnp.int32)])
    return {"flash_attention": flash, "paged_attention": paged}


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("kernel", ["flash_attention", "paged_attention"])
def test_pallas_kernel_compiles_natively(kernel, precision, on_chip):
    """Both matmul precisions: ``highest`` is what the kernel tests use."""
    fn, shapes = _kernel_cases()[kernel]
    args = on_chip([jax.ShapeDtypeStruct(s, dt) for s, dt in shapes])
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
