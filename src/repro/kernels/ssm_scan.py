"""Mamba-1 selective-scan Pallas kernel (chunked recurrence).

The SSM hot-spot of falcon-mamba-7b / zamba2-7b.  Recurrence per channel d
and state n::

    h[t] = exp(dt[t,d] * A[d,n]) * h[t-1] + dt[t,d] * B[t,n] * x[t,d]
    y[t,d] = sum_n C[t,n] * h[t,n] + D[d] * x[t,d]

TPU adaptation: the CUDA selective-scan kernel parallelises over threads
within a block and uses shared-memory warp scans.  On TPU the (d_inner x
d_state) state plane lives in a VMEM scratch accumulator, the time loop
walks a *chunk* of the sequence per grid step (grid innermost dim is
sequential — "arbitrary" semantics), and each step is a full-width VPU
op over the state plane.  Performance parameter (install-time AT):
``chunk`` — the sequence block per grid step, trading VMEM residency of
x/dt/B/C slices against grid overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref, h_ref, *,
                 chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)            # (Di, N)
    dskip = d_ref[...].astype(jnp.float32)        # (Di,)

    def step(t, h):
        xt = x_ref[0, t].astype(jnp.float32)      # (Di,)
        dtt = dt_ref[0, t].astype(jnp.float32)    # (Di,)
        bt = b_ref[0, t].astype(jnp.float32)      # (N,)
        ct = c_ref[0, t].astype(jnp.float32)      # (N,)
        da = jnp.exp(dtt[:, None] * a)            # (Di, N)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        y = (h * ct[None, :]).sum(axis=1) + dskip * xt
        o_ref[0, t] = y.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, d: jax.Array, *, chunk: int = 64,
                   interpret: bool = False) -> jax.Array:
    """x, dt: (B, L, Di); a: (Di, N); b, c: (B, L, N); d: (Di,) -> (B, L, Di).

    ``dt`` must already be positive (softplus applied by the caller).
    """
    bsz, l, di = x.shape
    n = a.shape[1]
    ch = min(chunk, l)
    p = (-l) % ch
    if p:
        pad3 = ((0, 0), (0, p), (0, 0))
        x, dt, b, c = (jnp.pad(t, pad3) for t in (x, dt, b, c))
    lp = x.shape[1]
    grid = (bsz, lp // ch)
    out = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=ch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ch, di), lambda bb, ic: (bb, ic, 0)),
            pl.BlockSpec((1, ch, di), lambda bb, ic: (bb, ic, 0)),
            pl.BlockSpec((di, n), lambda bb, ic: (0, 0)),
            pl.BlockSpec((1, ch, n), lambda bb, ic: (bb, ic, 0)),
            pl.BlockSpec((1, ch, n), lambda bb, ic: (bb, ic, 0)),
            pl.BlockSpec((di,), lambda bb, ic: (0,)),
        ],
        out_specs=pl.BlockSpec((1, ch, di), lambda bb, ic: (bb, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, lp, di), x.dtype),
        scratch_shapes=[pltpu.VMEM((di, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, b, c, d)
    return out[:, :l, :]


def ssm_vmem_bytes(chunk: int, d_inner: int, d_state: int,
                   bytes_per_el: int = 2) -> int:
    """Analytic VMEM footprint per grid step (CPU-side AT cost model)."""
    return (2 * chunk * d_inner + 2 * chunk * d_state) * bytes_per_el \
        + d_inner * d_state * (bytes_per_el + 4) \
        + chunk * d_inner * bytes_per_el
