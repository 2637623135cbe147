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
