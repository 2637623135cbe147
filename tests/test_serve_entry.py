"""Entry-point plumbing of the serving stack: which config a serve run
builds, how its weights are made, where the compile cache lives, the
wall-clock executor's error path, the bench's in-process mesh cell and
the chip smoke script's refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core import WallClockExecutor
from repro.launch import CHECKOUT, enable_compile_cache
from repro.launch.serve import (ServeConfig, build_serving,
                                init_serving_params, serving_arch_config)
from repro.models import build_model

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("kw,layers,d_model,param_dtype", [
    ({}, 2, 64, "float32"),
    ({"layers": 1}, 1, 64, "float32"),
    ({"reduced": False}, 32, 4096, "bfloat16"),
    ({"reduced": False, "layers": 8}, 8, 4096, "bfloat16"),
])
def test_serving_arch_config(kw, layers, d_model, param_dtype):
    """Reduced by default (the CPU config the tests serve); published
    widths with weights in the compute dtype on request; depth cut in
    whole layers only."""
    cfg = serving_arch_config(ServeConfig(arch="yi-6b", **kw))
    assert (cfg.n_layers, cfg.d_model, cfg.param_dtype) == \
        (layers, d_model, param_dtype)
    if not kw.get("reduced", True):
        pub = get_arch("yi-6b")
        assert (cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size) == \
            (pub.n_heads, pub.n_kv_heads, pub.d_ff, pub.vocab_size)


@pytest.mark.parametrize("layers", [0, 33])
def test_serving_arch_config_rejects_depth_outside_config(layers):
    with pytest.raises(ValueError, match="outside 1..32"):
        serving_arch_config(ServeConfig(reduced=False, layers=layers))


def test_full_width_params_are_bf16_and_made_under_jit():
    """Shapes only (no allocation): the published yi-6b weights come out
    of one jitted init in bf16, 12.1 GB for 32 layers."""
    model = build_model(serving_arch_config(ServeConfig(reduced=False)))
    shapes = jax.eval_shape(lambda: init_serving_params(model, 0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert sum(a.size * a.dtype.itemsize for a in leaves) == 12122071040


def test_init_serving_params_matches_model_shapes():
    model = build_model(get_arch("yi-6b").reduced())
    got = init_serving_params(model, 0)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_build_serving_returns_engine_tuner_prompts(tmp_path):
    engine, tuner, prompts = build_serving(ServeConfig(
        n_requests=3, prompt_len=10, cache="paged", page_size=8,
        prefill_chunk=8, autotune=True, workdir=str(tmp_path)))
    assert engine.kv.kind == "paged" and tuner is not None
    assert len(prompts) == 3 and all(4 <= len(p) < 10 for p in prompts)


def test_compile_cache_fixed_dir_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(os.fspath(CHECKOUT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_wall_clock_executor_raises_device_errors():
    """A variant whose result fails while the executor waits on it must
    fail the measurement, not be timed as a fast variant."""
    class Failing:
        def block_until_ready(self):
            raise RuntimeError("device error")

    ex = WallClockExecutor(lambda assignment: Failing, repeats=1, warmup=0)
    with pytest.raises(RuntimeError, match="device error"):
        ex({})


def test_bench_mesh_cell_runs_in_process():
    """The bench's mesh cell body runs in the calling process over its
    own devices (the accelerator path); a 1x1 mesh must agree exactly."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        from bench_serving import _mesh_cell
    finally:
        sys.path.pop(0)
    row = _mesh_cell({"arch": "yi-6b", "mesh": "1x1", "requests": 2,
                      "lanes": 2, "max_len": 48, "max_new": 3,
                      "page_size": 8, "prefill_chunk": 8, "seed": 0})
    assert row["finished"] == 2 and row["greedy_agreement"] == 1.0


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "ok" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """Without a TPU (and, alone in a directory, without the repo) the
    smoke script exits non-zero and prints no result."""
    cwd = ROOT
    if alone:
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    out = _run_smoke(cwd)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
