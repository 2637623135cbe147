"""Compile the serving kernels and one model step for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the TPU compiler lowers for its first chip.  That
refuses what interpret mode accepts — block shapes the TPU tiling cannot
take, more VMEM than a kernel may use, programs larger than the chip —
so these tests catch them before chip time is spent.  Widths are yi-6b's
published attention widths (bf16, 32 query heads over 4 KV heads: G=8,
head dim 128, page 16) plus one deepseek-7b decode (MHA, G=1), and the
decode kernel at the benchmark cell's exact shapes.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every
test file.
"""
import dataclasses
import functools
import importlib
import re
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.models import build_model

fa = importlib.import_module("repro.kernels.flash_attention")

PAGE = 16
N_PAGES = 257                 # 4 lanes x 64 pages of 1024 tokens + null
NBLK = 64
LANES = 4
CHUNK = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _widths(arch):
    cfg = get_arch(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim


def _kernel_args(sharding, arch, op, quant):
    h, hkv, d = _widths(arch)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool_dt = jnp.int8 if quant else jnp.bfloat16
    pools = [sds((N_PAGES, hkv, PAGE, d), pool_dt)] * 2
    if quant:
        pools += [sds((N_PAGES, hkv, PAGE), jnp.float32)] * 2
    if op == "decode":
        return (sds((LANES, h, 1, d), jnp.bfloat16), *pools,
                sds((LANES, NBLK), jnp.int32), sds((LANES,), jnp.int32))
    return (sds((1, h, CHUNK, d), jnp.bfloat16), *pools,
            sds((1, NBLK), jnp.int32), sds((1,), jnp.int32),
            sds((1,), jnp.int32))


KERNELS = {
    ("decode", False): fa.flash_paged_decode,
    ("decode", True): fa.flash_paged_decode_quant,
    ("prefill", False): fa.flash_paged_prefill,
    ("prefill", True): fa.flash_paged_prefill_quant,
}


@pytest.mark.parametrize("arch,op,quant,num_splits", [
    ("yi-6b", "decode", False, 1),
    ("yi-6b", "decode", False, 2),
    ("yi-6b", "decode", True, 1),
    ("yi-6b", "decode", True, 2),
    ("yi-6b", "prefill", False, 1),
    ("yi-6b", "prefill", False, 2),
    ("yi-6b", "prefill", True, 1),
    ("yi-6b", "prefill", True, 2),
    ("deepseek-7b", "decode", False, 1),
])
def test_paged_kernel_compiles_for_v5e(one_chip, arch, op, quant,
                                       num_splits):
    """Each paged kernel lowers and compiles for the chip, and the
    program holds the Pallas kernel, at block_k equal to the page."""
    kernel = functools.partial(KERNELS[op, quant], block_k=PAGE,
                               num_splits=num_splits)
    compiled = jax.jit(kernel).lower(
        *_kernel_args(one_chip, arch, op, quant)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_k", [PAGE // 2, 4 * PAGE])
def test_paged_decode_subpage_tile_compiles_for_v5e(one_chip, block_k):
    """Decode tiles are whole pages: a sub-page request (rounded up to one
    page) and a four-page tile both compile, fp and int8 alike."""
    for quant in (False, True):
        kernel = functools.partial(KERNELS["decode", quant],
                                   block_k=block_k, num_splits=2)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            compiled = jax.jit(kernel).lower(
                *_kernel_args(one_chip, "yi-6b", "decode", quant)).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_k", [None, 64, 128, 256, 512])
def test_paged_decode_compiles_at_cell_shapes(one_chip, block_k):
    """The benchmark cell's decode attention: 19 lanes, a 1,565-page bf16
    pool, an 81-page table, at the default tile and each ladder value.
    The custom call's instruction name starts with ``flash_paged_decode``,
    which is what the benchmark's kernel reader matches."""
    h, hkv, d = _widths("yi-6b")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((1565, hkv, PAGE, d), jnp.bfloat16)
    kernel = functools.partial(fa.flash_paged_decode, block_k=block_k)
    text = jax.jit(kernel).lower(
        sds((19, h, 1, d), jnp.bfloat16), pool, pool,
        sds((19, 81), jnp.int32), sds((19,), jnp.int32)).compile().as_text()
    calls = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert calls
    assert all(re.match(r"flash_paged_decode", c) for c in calls), calls


def test_paged_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """One whole ``Model.paged_decode_step`` at yi-6b widths (2 layers),
    from ``jax.eval_shape`` shapes: the kernel path is on it, and weights
    plus pool plus step temporaries fit one chip's 16 GB."""
    # the dispatch asks the default backend, which is the CPU here: make
    # the step take the branch it takes on the chip
    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    cfg = dataclasses.replace(get_arch("yi-6b"), n_layers=2,
                              param_dtype="bfloat16")
    model = build_model(cfg)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: model.init_paged_caches(N_PAGES, PAGE)))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    compiled = jax.jit(model.paged_decode_step).lower(
        params, caches, i32((LANES, NBLK)), i32((LANES, 1)),
        i32((LANES,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9
