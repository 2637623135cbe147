"""Ring sequence-parallel attention (shard_map + ppermute).

The `ring_sp` layout plan: Q/K/V are sharded over the *sequence* on the
``model`` axis; each device computes flash-style partial attention against
the KV block it currently holds, then passes the block around the ring —
overlapping the permute with the next chunk's compute on real hardware
(here: correctness + collective-volume accounting; the dry-run shows
``collective-permute`` ops of exactly one KV block per step instead of an
all-gather of the whole sequence).

This is the long-context-prefill alternative to ``tp`` when heads do not
divide the model axis or when S >> heads·d and KV residency dominates:
wire bytes per device = (n-1)/n × local KV vs a full KV all-gather, and
peak memory never exceeds one extra KV block.

Causal masking uses global positions derived from the ring step, so the
result is exactly ``attention_ref`` on the gathered sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def make_ring_attention(mesh: Mesh, axis_name: str = "model",
                        causal: bool = True, scale: float | None = None,
                        batch_axis: str | None = None):
    """Build the shard_map'd ring attention for a given mesh axis.

    ``batch_axis``: also shard the batch dim (e.g. "data") — without it the
    manual region replicates the batch across that axis.
    """
    n = mesh.shape[axis_name]
    perm = [(j, (j + 1) % n) for j in range(n)]
    if batch_axis is None and "data" in mesh.axis_names:
        batch_axis = "data"

    def body(q, k, v):
        b, h, s_loc, d = q.shape
        sc = float(scale if scale is not None else d ** -0.5)
        idx = jax.lax.axis_index(axis_name)
        q32 = q.astype(jnp.float32)
        # mark the carries device-varying (the scan produces per-shard
        # values; jax's vma type system requires matching carry types)
        axes = (axis_name,) if batch_axis is None \
            else (axis_name, batch_axis)
        mk = lambda x: jax.lax.pcast(x, axes, to="varying")
        m0 = mk(jnp.full((b, h, s_loc), -1e30, jnp.float32))
        l0 = mk(jnp.zeros((b, h, s_loc), jnp.float32))
        a0 = mk(jnp.zeros((b, h, s_loc, d), jnp.float32))

        def step(carry, i):
            m_prev, l_prev, acc, kc, vc = carry
            src = (idx - i) % n
            sco = jnp.einsum("bhqd,bhkd->bhqk", q32,
                             kc.astype(jnp.float32)) * sc
            if causal:
                qi = idx * s_loc + jnp.arange(s_loc)[:, None]
                kj = src * s_loc + jnp.arange(s_loc)[None, :]
                sco = jnp.where((qi >= kj)[None, None], sco, -1e30)
            m_cur = jnp.maximum(m_prev, sco.max(-1))
            p = jnp.exp(sco - m_cur[..., None])
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = l_prev * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            return (m_cur, l_cur, acc, kc, vc), None

        (m, l, acc, _, _), _ = jax.lax.scan(
            step, (m0, l0, a0, k, v), jnp.arange(n))
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).astype(q.dtype)

    spec = P(batch_axis, None, axis_name, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)


def ring_collective_bytes(b: int, h: int, s: int, d: int, n: int,
                          bytes_per_el: int = 2) -> int:
    """Analytic wire bytes per device: (n-1) permutes of one local KV."""
    return 2 * b * h * (s // n) * d * bytes_per_el * (n - 1)
