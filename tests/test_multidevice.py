"""Multi-device semantics (ring attention, compressed pod psum).

The main pytest process must keep seeing 1 device (the dry-run rule), so
these specs run in subprocesses with ``xla_force_host_platform_device_count``.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_ring_attention_matches_reference():
    """ring_sp plan: seq-sharded shard_map attention == dense oracle, and
    the compiled module moves KV only via collective-permute."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.ring_attention import make_ring_attention
from repro.kernels import ref
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
B, H, S, D = 2, 4, 64, 16
q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, D)) * 0.4
k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D)) * 0.4
v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D))
with mesh:
    for causal in (True, False):
        ring = make_ring_attention(mesh, causal=causal)
        out = jax.jit(ring)(q, k, v)
        want = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        txt = jax.jit(ring).lower(q, k, v).compile().as_text()
        assert txt.count("collective-permute(") > 0
        assert txt.count("all-gather(") == 0
print("RING_OK")
""")
    assert "RING_OK" in out


@pytest.mark.slow
def test_compressed_pod_psum():
    """int8 pod-axis gradient reduction == exact psum within int8 error."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum
mesh = jax.make_mesh((2,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = jax.random.normal(jax.random.PRNGKey(0), (2, 512))

def reduce_fn(x):
    return compressed_psum(x, "pod")

def exact_fn(x):
    return jax.lax.psum(x, "pod")

shard_map = jax.shard_map

with mesh:
    sm_c = jax.jit(shard_map(reduce_fn, mesh=mesh,
                             in_specs=P("pod", None),
                             out_specs=P("pod", None)))
    sm_e = jax.jit(shard_map(exact_fn, mesh=mesh,
                             in_specs=P("pod", None),
                             out_specs=P("pod", None)))
    approx = np.asarray(sm_c(g))
    exact = np.asarray(sm_e(g))
amax = np.abs(g).max()
assert np.abs(approx - exact).max() <= 2 * amax / 127.0 + 1e-6, \\
    np.abs(approx - exact).max()
print("PSUM_OK")
""", n=2)
    assert "PSUM_OK" in out
