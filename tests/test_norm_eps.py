"""The norm eps a configuration states reaches every norm of the paged
steps.

A dense toy stack at ``norm_eps=1e-6`` is fed embeddings whose mean
square is about 1e-6, where the eps decides how far each norm scales its
input.  Its prefill and decode logits agree with a float32 reference at
``highest`` precision taken at the same eps, and differ from that
reference taken at 1e-5.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models import build_model

PAGE = 8
PROMPT = 12
# the float32 program against the float32 reference: both run at
# ``highest``, so they part only by rounding and the order of sums
TOL = 1e-4


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x (S, H, Dh) at positions 0 .. S-1, the halves of each head
    rotated."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang)] * 2, -1)[:, None, :]
    sin = np.concatenate([np.sin(ang)] * 2, -1)[:, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _reference(params, cfg, tokens, eps):
    """Logits (S, vocab) of a plain causal llama forward over ``tokens``."""
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = len(tokens)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)]
        mask = np.tril(np.ones((s, s), bool))
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            xn = _rms(x, p["norm1_w"], eps)
            q = _rope((xn @ p["attn"]["wq"]).reshape(s, h, dh),
                      cfg.rope_theta)
            k = _rope((xn @ p["attn"]["wk"]).reshape(s, hkv, dh),
                      cfg.rope_theta)
            v = (xn @ p["attn"]["wv"]).reshape(s, hkv, dh)
            k = jnp.repeat(k, h // hkv, axis=1)
            v = jnp.repeat(v, h // hkv, axis=1)
            sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dh)
            pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
            att = jnp.einsum("hqk,khd->qhd", pr, v).reshape(s, h * dh)
            x = x + att @ p["attn"]["wo"]
            xn = _rms(x, p["norm2_w"], eps)
            m = p["mlp"]
            x = x + (jax.nn.silu(xn @ m["w_gate"]) * (xn @ m["w_up"])
                     ) @ m["w_down"]
        x = _rms(x, params["final_norm_w"], eps)
        return np.asarray(x @ params["lm_head"])[:, :cfg.vocab_size]


def _tiny_inputs():
    cfg = get_arch("deepseek-7b").reduced()
    assert cfg.norm_eps == 1e-6 and cfg.compute_dtype == "float32"
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # embedding rows N(0, 0.02^2) scaled to a mean square of about 1e-6;
    # norm gains away from 1 so each norm's weight is read too
    params["embed"] = params["embed"] * 0.05
    gains = jax.random.split(jax.random.PRNGKey(1), 3)
    layers = dict(params["layers"])
    for key, name in zip(gains, ("norm1_w", "norm2_w")):
        layers[name] = 1.0 + 0.1 * jax.random.normal(key, layers[name].shape)
    params["layers"] = layers
    params["final_norm_w"] = 1.0 + 0.1 * jax.random.normal(
        gains[2], params["final_norm_w"].shape)
    ms = float(jnp.mean(params["embed"] ** 2))
    assert 0.5e-6 < ms < 2e-6, ms
    tokens = list(np.random.default_rng(0).integers(1, cfg.vocab_size,
                                                    PROMPT + 1))
    return cfg, model, params, tokens


def _paged_logits(model, params, tokens):
    """Prefill logits at the prompt's last token, then decode logits of
    the one token after it, through the paged steps."""
    n_pages = 1 + -(-len(tokens) // PAGE)
    caches = model.init_paged_caches(n_pages, PAGE)
    table = jnp.arange(1, n_pages, dtype=jnp.int32)[None]
    prompt = jnp.asarray(tokens[:PROMPT], jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        pre, caches = jax.jit(model.paged_prefill_step)(
            params, caches, table, prompt, jnp.zeros((1,), jnp.int32),
            jnp.full((1,), PROMPT, jnp.int32),
            jnp.full((1,), PROMPT - 1, jnp.int32))
        dec, _ = jax.jit(model.paged_decode_step)(
            params, caches, table,
            jnp.asarray([[tokens[PROMPT]]], jnp.int32),
            jnp.full((1,), PROMPT, jnp.int32))
    vocab = model.cfg.vocab_size
    return np.asarray(pre)[0, :vocab], np.asarray(dec)[0, :vocab]


@pytest.mark.parametrize("step", ["paged_prefill_step", "paged_decode_step"])
def test_paged_steps_serve_the_stated_eps(step):
    cfg, model, params, tokens = _tiny_inputs()
    row = {"paged_prefill_step": (0, PROMPT - 1),
           "paged_decode_step": (1, PROMPT)}[step]
    got = _paged_logits(model, params, tokens)[row[0]]
    want = _reference(params, cfg, tokens, 1e-6)[row[1]]
    other = _reference(params, cfg, tokens, 1e-5)[row[1]]
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) <= TOL * scale
    # the eps the config does not state lies far outside the tolerance
    assert np.max(np.abs(got - other)) > 100 * TOL * scale


def test_configs_state_their_norm_eps():
    assert get_arch("deepseek-7b").norm_eps == 1e-6
    assert get_arch("deepseek-7b").reduced().norm_eps == 1e-6
    others = {name: cfg.norm_eps for name, cfg in ARCHS.items()
              if name != "deepseek-7b"}
    assert others and set(others.values()) == {1e-5}, others
