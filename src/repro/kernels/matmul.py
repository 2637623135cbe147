"""Tiled MXU matmul Pallas kernel.

The framework's GEMM hot-spot.  Performance parameters (install-time AT):

* ``block_m``, ``block_n``, ``block_k`` — VMEM tile shape.  The MXU wants
  the contracting/lane dims in multiples of 128 and the sublane dim in
  multiples of 8, so the AT ``varied`` ranges are generated in
  hardware-aligned steps (see tuning/install.py), not 1..16 as in the
  paper's Fortran loops — this is the documented hardware adaptation of the
  paper's ``unroll`` PP.

Accumulation is fp32 in a VMEM scratch tile across the k grid dimension
(innermost), with an optional fused epilogue (bias add / gelu / silu /
residual) so XLA does not round-trip the tile through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _epilogue(x: jax.Array, kind: str) -> jax.Array:
    if kind == "none":
        return x
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "relu":
        return jnp.maximum(x, 0)
    raise ValueError(f"unknown epilogue {kind!r}")


def _mm_kernel(x_ref, y_ref, o_ref, acc_ref, *, epilogue: str,
               n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], epilogue).astype(o_ref.dtype)


def _mm_bias_kernel(x_ref, y_ref, b_ref, o_ref, acc_ref, *, epilogue: str,
                    n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...] + b_ref[...].astype(jnp.float32),
                               epilogue).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "epilogue", "interpret", "out_dtype"))
def matmul(x: jax.Array, y: jax.Array, bias: jax.Array | None = None, *,
           block_m: int = 128, block_n: int = 128, block_k: int = 128,
           epilogue: str = "none", interpret: bool = False,
           out_dtype=None) -> jax.Array:
    """``x @ y (+ bias)`` with explicit VMEM tiling.

    Shapes: x (M, K), y (K, N), bias (N,) optional.  M/N/K need not divide
    the block sizes — blocks are clamped and the operands zero-padded.
    """
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)

    def pad(a, mults):
        pads = [(0, (-s) % mult) for s, mult in zip(a.shape, mults)]
        if any(p for _, p in pads):
            return jnp.pad(a, pads)
        return a

    xp, yp = pad(x, (bm, bk)), pad(y, (bk, bn))
    mp, kp = xp.shape
    np_ = yp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
                pl.BlockSpec((bk, bn), lambda i, j, l: (l, j))]
    args = [xp, yp]
    if bias is not None:
        in_specs.append(pl.BlockSpec((bn,), lambda i, j, l: (j,)))
        args.append(pad(bias, (bn,)))
        kernel = functools.partial(_mm_bias_kernel, epilogue=epilogue,
                                   n_k=grid[2])
    else:
        kernel = functools.partial(_mm_kernel, epilogue=epilogue,
                                   n_k=grid[2])

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:m, :n]


def matmul_vmem_bytes(block_m: int, block_n: int, block_k: int,
                      bytes_per_el: int = 2) -> int:
    """Analytic VMEM footprint of one grid step (the install-time AT cost
    model used on CPU where wall-clock is meaningless)."""
    return (block_m * block_k + block_k * block_n) * bytes_per_el \
        + block_m * block_n * 4 + block_m * block_n * bytes_per_el
