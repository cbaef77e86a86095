"""Compile rehearsal for a TPU v5e that is described, not attached.

The paged attention kernels and the full-width paged steps of the served
path are compiled here by the TPU compiler (Mosaic for the kernels) at
qwen2-0.5b's published widths, with ``interpret=False``. Interpret-mode tests
cannot see what this catches: blocks the TPU tiling refuses, kernels that
need more fast memory than they may use, steps that do not fit the chip.
Nothing runs, so nothing here is a result or a time.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
worker given this file keeps it until it exits.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.paged_decode_attention import paged_flash_decode
from repro.kernels.paged_prefill_attention import paged_flash_prefill
from repro.models import blocks, transformer
from repro.serve import paged_step
from repro.serve.kvcache import PagedCachePool

B, PT, N_PAGES, MAX_PAGES, CHUNK = 8, 16, 512, 64, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_config("qwen2-0.5b")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("tp", [1, 2])     # tp=2: one shard's head slice
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_kernel_compiles(one_chip, cfg, kernel, kv_dtype, tp):
    H, K, hd = cfg.n_heads // tp, cfg.n_kv // tp, cfg.hd
    pages = _sds((N_PAGES, K, PT, hd), kv_dtype, one_chip)
    if kernel == "decode":
        args = [_sds((B, H, hd), jnp.float32, one_chip), pages, pages,
                _sds((B, MAX_PAGES), jnp.int32, one_chip),
                _sds((B,), jnp.int32, one_chip)]
        fn = paged_flash_decode
    else:
        args = [_sds((CHUNK, H, hd), jnp.float32, one_chip), pages, pages,
                _sds((MAX_PAGES,), jnp.int32, one_chip),
                _sds((), jnp.int32, one_chip)]
        fn = paged_flash_prefill
    if kv_dtype == jnp.int8:
        args += [_sds((N_PAGES, K), jnp.float32, one_chip)] * 2
    compiled = _compile(lambda *a: fn(*a, interpret=False), *args)
    # the kernel keeps its name on the chip, so a profile can tell it apart
    assert f"%paged_{kernel}_attention" in compiled.as_text()


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_full_width_paged_step_compiles(one_chip, cfg, step):
    params = jax.eval_shape(lambda: blocks.split_params(
        transformer.init_model(jax.random.PRNGKey(0), cfg))[0])
    pages = jax.eval_shape(lambda: PagedCachePool(
        cfg, max_batch=B, max_seq=MAX_PAGES * PT, n_pages=N_PAGES,
        page_tokens=PT).pages)
    params, pages = _on(params, one_chip), _on(pages, one_chip)
    if step == "decode":
        fn = paged_step.make_paged_decode_step(cfg, PT, interpret=False)
        args = [_sds((B, 1), jnp.int32, one_chip),
                _sds((B, MAX_PAGES), jnp.int32, one_chip),
                _sds((B,), jnp.int32, one_chip),
                _sds((B,), jnp.bool_, one_chip)]
    else:
        fn = paged_step.make_paged_prefill_chunk_step(cfg, PT,
                                                      interpret=False)
        args = [_sds((1, CHUNK), jnp.int32, one_chip),
                _sds((MAX_PAGES,), jnp.int32, one_chip),
                _sds((), jnp.int32, one_chip)]
    compiled = _compile(fn, params, args[0], pages, *args[1:])
    mem = compiled.memory_analysis()
    # the step must fit the chip's 16 GB beside the weights and pool
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
