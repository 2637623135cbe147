"""Plain reference of a dense llama-family decoder, in float32.

Follows the published description (as in Hugging Face ``LlamaForCausalLM``):
RMSNorm before attention and before the MLP, rotary embedding with the
halves of each head rotated (``rotate_half``), grouped-query attention
with a causal mask, a SwiGLU MLP ``down(silu(gate(x)) * up(x))``, a final
RMSNorm and an untied output head.  Every matmul runs at ``highest``
precision in float32; no kernel, cache or batching of the program is used,
and nothing of the program is imported.

It reads the weights the benchmark made, in the program's tree layout:
``embed`` (V, d), ``lm_head`` (d, V), ``final_norm_w`` (d,), and under
``layers`` stacked over depth ``norm1_w``, ``norm2_w``, ``attn/wq|wk|wv|wo``
and ``mlp/w_gate|w_up|w_down``, each matrix stored (in, out).

``quant`` (``"int8"`` or ``"fp8"``, float8 e4m3) is the lower-precision
control: every matmul (the projections, attention's two products and the
head) takes operands rounded to that type, weights scaled per output
channel, activations, queries, keys and attention weights per row and
values per column, with float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _fake_quant(x, axis, quant):
    """``x`` rounded to int8 or to float8 (e4m3), scaled by its largest
    magnitude along ``axis``; identity where ``quant`` is None."""
    if quant is None:
        return x
    top = 127.0 if quant == "int8" else 448.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(x, w, quant):
    w = w.astype(jnp.float32)
    return _fake_quant(x, -1, quant) @ _fake_quant(w, 0, quant)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x (S, n, Dh) at positions 0 .. S-1."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1))[:, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1))[:, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("conf", "quant"))
def _layer(x, layers, i, conf, quant):
    d, h, hkv, dh, eps, theta = conf
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), layers)
    s = x.shape[0]
    hn = _rms(x, p["norm1_w"], eps)
    q = _rope(_matmul(hn, p["attn"]["wq"], quant).reshape(s, h, dh), theta)
    k = _rope(_matmul(hn, p["attn"]["wk"], quant).reshape(s, hkv, dh), theta)
    v = _matmul(hn, p["attn"]["wv"], quant).reshape(s, hkv, dh)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    outs = []
    for a in range(0, s, QUERY_BLOCK):
        qb = q[a:a + QUERY_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", _fake_quant(qb, -1, quant),
                        _fake_quant(k, -1, quant)) / np.sqrt(dh)
        qpos = np.arange(a, a + qb.shape[0])[:, None]
        mask = jnp.asarray(np.arange(s)[None, :] <= qpos)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        pr = _fake_quant(jax.nn.softmax(sc, -1), -1, quant)
        outs.append(jnp.einsum("hqk,khd->qhd", pr, _fake_quant(v, 0, quant)))
    att = jnp.concatenate(outs, 0).reshape(s, h * dh)
    x = x + _matmul(att, p["attn"]["wo"], quant)
    hn = _rms(x, p["norm2_w"], eps)
    g = _matmul(hn, p["mlp"]["w_gate"], quant)
    u = _matmul(hn, p["mlp"]["w_up"], quant)
    return x + _matmul(jax.nn.silu(g) * u, p["mlp"]["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "quant"))
def _head(x, rows, norm_w, head, eps, vocab, quant):
    return _matmul(_rms(x[rows], norm_w, eps), head[:, :vocab], quant)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def logits(params, conf: dict, tokens, rows, pad_to: int,
           quant: str | None = None) -> np.ndarray:
    """Float32 logits (len(rows), vocab) of the causal forward pass over
    ``tokens``, at positions ``rows``.  ``tokens`` is padded to ``pad_to``
    positions (after the last real one, so no real position sees them)
    and ``rows`` to ``len(rows)`` rounded up to 128, so one cell compiles
    one program of each kind."""
    heads = conf["num_attention_heads"]
    dims = (conf["hidden_size"], heads, conf["num_key_value_heads"],
            conf.get("head_dim", conf["hidden_size"] // heads),
            float(conf["rms_norm_eps"]), float(conf["rope_theta"]))
    n_rows = len(rows)
    padded = list(rows) + [rows[-1]] * ((-n_rows) % 128)
    with jax.default_matmul_precision("highest"):
        padded_tokens = np.zeros((pad_to,), np.int32)
        padded_tokens[:len(tokens)] = tokens
        x = _embed(params["embed"], jnp.asarray(padded_tokens))
        for i in range(conf["num_hidden_layers"]):
            x = _layer(x, params["layers"], jnp.int32(i), dims, quant)
        out = _head(x, jnp.asarray(padded, jnp.int32),
                    params["final_norm_w"], params["lm_head"],
                    float(conf["rms_norm_eps"]), int(conf["vocab_size"]),
                    quant)
    return np.asarray(out)[:n_rows]
