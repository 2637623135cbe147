"""The program's spans and named scopes: host spans land in the profiler's
trace under ``repro.``, and the paged steps' device ops carry the scopes
in their ``op_name``."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import build_model
from repro.trace import span


def test_span_records_under_the_profiler(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with span("engine.prefill_chunk", rid=3, start=0, end=16):
        pass
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    got = [(ev.name, dict(ev.stats))
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events
           if ev.name.startswith("repro.")]
    assert got == [("repro.engine.prefill_chunk",
                    {"rid": 3, "start": 0, "end": 16})]


@pytest.mark.parametrize("step", ["paged_decode_step", "paged_prefill_step"])
def test_paged_steps_name_their_scopes(step):
    model = build_model(get_arch("yi-6b").reduced())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.init_paged_caches(9, 16))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = ((i32(2, 4), i32(2, 1), i32(2)) if step == "paged_decode_step"
            else (i32(1, 4), i32(1, 8), i32(1), i32(1), i32(1)))
    text = jax.jit(getattr(model, step)).lower(
        params, caches, *args).compile().as_text()
    for scope in ("/layers/while/body/", "/attn/", "/attn/kv_write/",
                  "/mlp/", "/lm_head/"):
        assert scope in text, scope


def _dispatch_metas(monkeypatch, cfg, block_k=None):
    """The metadata of every ``engine.dispatch.call`` span and the
    positions its step was called with, over two requests on a toy
    engine with pages of 8 tokens; ``block_k`` is published as the
    decode tile while they run."""
    import numpy as np

    from repro import at
    from repro.serving import Request, ServingEngine
    from repro.serving import engine as engine_mod

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, n_lanes=2, max_len=64, cache="paged",
                        page_size=8)
    metas, positions = [], []

    def record(name, **meta):
        if name == "engine.dispatch.call":
            metas.append(meta)
        return span(name, **meta)

    step = eng._decode

    def decode(*args):
        positions.append(np.asarray(args[-1]))
        return step(*args)

    monkeypatch.setattr(engine_mod, "span", record)
    eng._decode = decode
    if block_k is not None:
        at.publish("flash_paged_decode", block_k=block_k)
    try:
        for rid, plen in enumerate((5, 11)):
            eng.submit(Request(rid=rid, prompt=list(range(1, plen + 1)),
                               max_new_tokens=6))
        eng.run(max_steps=50)
    finally:
        at.clear_published()
    assert metas and len(metas) == len(positions)
    return eng, metas, positions


def test_dispatch_call_carries_the_decode_tile(monkeypatch):
    """``engine.dispatch.call`` on a paged cache carries ``kv_tile``, the
    decode kernel's resolved tile, and ``kv_blocks``, the sum over the
    decoding lanes of ceil(context / tile), from host arrays alone."""
    import numpy as np

    from repro.kernels.flash_attention import (paged_decode_block_k,
                                               paged_decode_page_bytes)

    cfg = get_arch("yi-6b").reduced()
    eng, metas, positions = _dispatch_metas(monkeypatch, cfg, block_k=8)
    # with nothing published, the default tile: the whole 8-page table,
    # which the tiny pages fit
    assert eng.kv.decode_tile() == paged_decode_block_k(
        None, page_size=8, table_pages=8,
        page_bytes=paged_decode_page_bytes(cfg.n_kv_heads, 8, cfg.head_dim,
                                           4)) == 64
    for meta, pos in zip(metas, positions):
        live = pos[pos > 0]              # idle lanes ride along at pos 0
        assert meta["lanes"] == live.size
        assert meta["kv_tile"] == 8
        assert meta["kv_blocks"] == int(np.sum(-(-(live + 1) // 8)))
    assert max(m["kv_blocks"] for m in metas) > max(m["lanes"] for m in metas)


@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-7b"])
def test_dispatch_call_carries_the_live_kv_bytes(monkeypatch, arch):
    """``engine.dispatch.call`` on a paged cache carries ``kv_bytes``: the
    decoding lanes' contexts times layers x 2 x KV heads x head dim x the
    cache's itemsize, the live K and V the decode kernel reads."""
    cfg = get_arch(arch).reduced()
    _, metas, positions = _dispatch_metas(monkeypatch, cfg)
    per_token = (cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim
                 * jnp.dtype(cfg.cache_dtype).itemsize)
    for meta, pos in zip(metas, positions):
        live = pos[pos > 0]              # idle lanes ride along at pos 0
        assert meta["kv_bytes"] == int((live + 1).sum()) * per_token
    assert max(m["kv_bytes"] for m in metas) > 16 * per_token
