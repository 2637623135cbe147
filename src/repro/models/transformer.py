"""Decoder-family LM assembly: dense / MoE / SSM / hybrid / VLM.

Layers are stacked along a leading axis and driven by ``lax.scan`` so HLO
size is O(1) in depth (the 1000+-node posture: an 81-layer zamba2 lowers to
the same program size as a 2-layer smoke model).  Remat policy and
activation shardings come from the active
:class:`~repro.models.sharding_ctx.LayoutPlan`.

Modes: ``forward`` (train/prefill), ``prefill`` (forward + KV/SSM state
collection), ``decode_step`` (one token, scanned over per-layer caches).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (chunked_cross_entropy, dense_init, embed_init,
                     layer_norm, rms_norm)
from .sharding_ctx import (constrain, constrain_layer_params,
                           current_plan)

# --------------------------------------------------------------------------
# config adapters
# --------------------------------------------------------------------------


def attn_config(cfg: ArchConfig, window: bool = True) -> attn.AttnConfig:
    return attn.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        d_head=cfg.head_dim, rope_theta=cfg.rope_theta,
        window=cfg.window if window else None, causal=True,
        use_rope=cfg.use_rope, qkv_bias=cfg.qkv_bias)


def moe_config(cfg: ArchConfig) -> moe_mod.MoEConfig:
    return moe_mod.MoEConfig(
        d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
        top_k=cfg.top_k, n_shared_experts=cfg.n_shared_experts,
        capacity_factor=cfg.capacity_factor, group_size=cfg.moe_group_size)


def mamba1_config(cfg: ArchConfig) -> ssm_mod.Mamba1Config:
    return ssm_mod.Mamba1Config(cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state)


def mamba2_config(cfg: ArchConfig) -> ssm_mod.Mamba2Config:
    return ssm_mod.Mamba2Config(
        cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads,
        cfg.ssm_head_dim, chunk=cfg.ssm_chunk)


def _norm(cfg: ArchConfig, p: dict, x: jax.Array, name: str) -> jax.Array:
    if cfg.norm == "ln":
        return layer_norm(x, p[f"{name}_w"], p[f"{name}_b"],
                          eps=cfg.norm_eps)
    return rms_norm(x, p[f"{name}_w"], eps=cfg.norm_eps)


def _init_norm(cfg: ArchConfig, name: str, dtype) -> dict:
    p = {f"{name}_w": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "ln":
        p[f"{name}_b"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _mlp_init(key, cfg: ArchConfig, dtype) -> dict:
    from .layers import init_mlp
    return init_mlp(key, cfg.d_model, cfg.d_ff, gated=True, dtype=dtype)


def _mlp_apply(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    from .layers import apply_mlp
    return apply_mlp(p, x, cfg.act)


# --------------------------------------------------------------------------
# per-layer init / apply
# --------------------------------------------------------------------------


def init_layer(key, cfg: ArchConfig, kind: str, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {}
    if kind in ("attn_mlp", "attn_moe"):
        p.update(_init_norm(cfg, "norm1", dtype))
        p.update(_init_norm(cfg, "norm2", dtype))
        p["attn"] = attn.init_attention(ks[0], attn_config(cfg), dtype)
        if kind == "attn_mlp":
            p["mlp"] = _mlp_init(ks[1], cfg, dtype)
        else:
            p["moe"] = moe_mod.init_moe(ks[1], moe_config(cfg), dtype)
    elif kind == "mamba1":
        p.update(_init_norm(cfg, "norm1", dtype))
        p["ssm"] = ssm_mod.init_mamba1(ks[0], mamba1_config(cfg), dtype)
    elif kind == "mamba2":
        p.update(_init_norm(cfg, "norm1", dtype))
        p["ssm"] = ssm_mod.init_mamba2(ks[0], mamba2_config(cfg), dtype)
    else:
        raise ValueError(kind)
    return p


def apply_layer(p: dict, x: jax.Array, cfg: ArchConfig, kind: str):
    """Full-sequence layer application.  Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind in ("attn_mlp", "attn_moe"):
        h = attn.full(p["attn"], _norm(cfg, p, x, "norm1"), attn_config(cfg))
        x = x + h
        h2 = _norm(cfg, p, x, "norm2")
        if kind == "attn_mlp":
            x = x + _mlp_apply(p["mlp"], h2, cfg)
        else:
            out, aux = moe_mod.apply_moe(p["moe"], h2, moe_config(cfg))
            x = x + out
    elif kind == "mamba1":
        x = x + ssm_mod.apply_mamba1(p["ssm"], _norm(cfg, p, x, "norm1"),
                                     mamba1_config(cfg))
    elif kind == "mamba2":
        x = x + ssm_mod.apply_mamba2(p["ssm"], _norm(cfg, p, x, "norm1"),
                                     mamba2_config(cfg))
    else:
        raise ValueError(kind)
    return constrain(x, "hidden"), aux


# --------------------------------------------------------------------------
# model init
# --------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    kinds = cfg.layer_kinds()
    kind = kinds[0]            # decoder stacks are homogeneous per family
    ks = jax.random.split(key, 8)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg, kind, dtype))(layer_keys)
    p: dict = {
        "embed": embed_init(ks[1], cfg.padded_vocab, cfg.d_model, dtype),
        "layers": layers,
    }
    p.update(_init_norm(cfg, "final_norm", dtype))
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.padded_vocab,
                                  dtype)
    if cfg.attn_period > 0:    # zamba2 shared attention block
        p["shared_attn"] = {
            "attn": attn.init_attention(ks[3], attn_config(cfg), dtype),
            "mlp": _mlp_init(ks[4], cfg, dtype),
            **_init_norm(cfg, "norm1", dtype),
            **_init_norm(cfg, "norm2", dtype),
        }
    if cfg.frontend != "none":
        p["frontend_proj"] = dense_init(ks[5], cfg.d_model, cfg.d_model,
                                        dtype)
    return p


def _compute(x, cfg: ArchConfig):
    return x.astype(jnp.dtype(cfg.compute_dtype))


def _cast_tree(p, cfg: ArchConfig):
    dt = jnp.dtype(cfg.compute_dtype)
    return jax.tree.map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, p)


def _shared_attn_block(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = attn.full(p["attn"], _norm(cfg, p, x, "norm1"), attn_config(cfg))
    x = x + h
    x = x + _mlp_apply(p["mlp"], _norm(cfg, p, x, "norm2"), cfg)
    return x


def _remat_wrap(fn):
    plan = current_plan()
    policy = plan.remat if plan is not None else "none"
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)   # full


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------


def embed_tokens(params: dict, tokens: jax.Array, cfg: ArchConfig,
                 frontend_embeds: jax.Array | None = None) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    x = _compute(x, cfg)
    if frontend_embeds is not None:
        fe = _compute(frontend_embeds, cfg) @ _compute(
            params["frontend_proj"], cfg)
        x = jnp.concatenate([fe, x], axis=1)
    return constrain(x, "hidden")


def forward(params: dict, tokens: jax.Array, cfg: ArchConfig,
            frontend_embeds: jax.Array | None = None):
    """tokens (B, S) -> (hidden (B, S', d), aux_loss)."""
    x = embed_tokens(params, tokens, cfg, frontend_embeds)
    kind = cfg.layer_kinds()[0]
    shared = _cast_tree(params.get("shared_attn"), cfg) \
        if cfg.attn_period > 0 else None
    # cast the stacked weights ONCE, before the scan: the FSDP all-gather
    # then moves bf16 (2 bytes) instead of fp32 — half the wire bytes
    layers_c = _cast_tree(params["layers"], cfg)

    def body(carry, scanned):
        x, aux, idx = carry
        lp = constrain_layer_params(scanned)
        x, a = apply_layer(lp, x, cfg, kind)
        if shared is not None:
            x = jax.lax.cond(
                (idx + 1) % cfg.attn_period == 0,
                lambda v: _shared_attn_block(shared, v, cfg),
                lambda v: v, x)
        return (x, aux + a, idx + 1), None

    body = _remat_wrap(body)
    (x, aux, _), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0), jnp.int32(0)), layers_c)
    x = _norm(cfg, _cast_tree(
        {k: params[k] for k in params if k.startswith("final_norm")}, cfg),
        x, "final_norm")
    return x, aux


def lm_head_weight(params: dict, cfg: ArchConfig) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return w


def train_loss(params: dict, batch: dict, cfg: ArchConfig,
               n_loss_chunks: int | None = None) -> jax.Array:
    """batch: tokens (B, S), labels (B, S), optional loss_mask,
    frontend_embeds."""
    if n_loss_chunks is None:
        plan = current_plan()
        n_loss_chunks = plan.loss_chunks if plan is not None else 8
    hidden, aux = forward(params, batch["tokens"], cfg,
                          batch.get("frontend_embeds"))
    fs = hidden.shape[1] - batch["labels"].shape[1]
    if fs > 0:                   # vlm/audio prefix carries no loss
        hidden = hidden[:, fs:]
    b, s, d = hidden.shape
    hidden = constrain(hidden.reshape(b * s, d), "logits_hidden")
    labels = batch["labels"].reshape(-1)
    mask = batch.get("loss_mask")
    mask = mask.reshape(-1).astype(jnp.float32) if mask is not None else None
    w = _compute(lm_head_weight(params, cfg), cfg)
    loss = chunked_cross_entropy(hidden, w, labels, mask,
                                 n_chunks=n_loss_chunks)
    return loss + aux


# --------------------------------------------------------------------------
# decode: caches + one-token step
# --------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_len: int,
                dtype=None) -> Any:
    """Stacked per-layer decode state (KV in cfg.cache_dtype)."""
    dtype = jnp.dtype(cfg.cache_dtype) if dtype is None else dtype
    kind = cfg.layer_kinds()[0]
    l = cfg.n_layers

    def stack(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(
            a, (l,) + a.shape).copy(), tree)

    caches: dict = {}
    if kind in ("attn_mlp", "attn_moe"):
        k, v = attn.init_cache(batch, attn_config(cfg), max_len, dtype)
        caches["kv"] = (stack(k), stack(v))
    elif kind == "mamba1":
        caches["ssm"] = stack(ssm_mod.init_mamba1_state(
            batch, mamba1_config(cfg)))
    elif kind == "mamba2":
        caches["ssm"] = stack(ssm_mod.init_mamba2_state(
            batch, mamba2_config(cfg)))
    if cfg.attn_period > 0:
        napp = cfg.n_shared_attn_applications()
        k, v = attn.init_cache(batch, attn_config(cfg), max_len, dtype)
        caches["shared_kv"] = (
            jax.tree.map(lambda a: jnp.broadcast_to(
                a, (napp,) + a.shape).copy(), k),
            jax.tree.map(lambda a: jnp.broadcast_to(
                a, (napp,) + a.shape).copy(), v))
    return caches


def supports_paged_cache(cfg: ArchConfig) -> bool:
    """Paged KV applies to plain attention stacks: SSM state is O(1) and
    SWA ring buffers are already O(window), so neither benefits from
    paging; the zamba2 shared-attn block would need a second page space."""
    return (cfg.layer_kinds()[0] in ("attn_mlp", "attn_moe")
            and cfg.attn_period == 0 and cfg.window is None)


def init_paged_caches(cfg: ArchConfig, n_pages: int, page_size: int,
                      dtype=None, quantized: bool = False) -> Any:
    """Layer-stacked physical page pools: ``kv`` = (L, P, Hkv, psz, Dh) x2.

    Unlike :func:`init_caches` this allocates O(n_pages * page_size)
    tokens of KV *total*, not O(batch * max_len) — lanes borrow pages from
    the shared pool via their page tables.

    ``quantized=True`` stores the pools as int8 and adds a ``kv_scale``
    entry — (L, P, Hkv, psz) fp32 per-row scales for k and v.  The scale
    arrays keep the page axis at position 1 (after the layer stack), so
    every page-indexed treemap over the caches (COW copies, swap
    gather/scatter) applies to scales unchanged.
    """
    if not supports_paged_cache(cfg):
        raise ValueError(
            f"arch {cfg.name!r} does not support the paged KV cache "
            "(needs a plain attention stack: no SSM/SWA/shared-attn)")
    l = cfg.n_layers
    stack = lambda a: jnp.broadcast_to(a, (l,) + a.shape).copy()
    if quantized:
        k, v = attn.init_paged_pool(n_pages, attn_config(cfg), page_size,
                                    jnp.int8)
        ks, vs = attn.init_paged_scales(n_pages, attn_config(cfg), page_size)
        return {"kv": (stack(k), stack(v)),
                "kv_scale": (stack(ks), stack(vs))}
    dtype = jnp.dtype(cfg.cache_dtype) if dtype is None else dtype
    k, v = attn.init_paged_pool(n_pages, attn_config(cfg), page_size, dtype)
    return {"kv": (stack(k), stack(v))}


def _paged_out_caches(new_states: dict) -> dict:
    """Scan outputs -> cache dict (kv, plus kv_scale for int8 pools)."""
    out = {"kv": new_states["kv"]}
    if "kv_scale" in new_states:
        out["kv_scale"] = new_states["kv_scale"]
    return out


def paged_decode_step(params: dict, caches: Any, page_table: jax.Array,
                      token: jax.Array, pos: jax.Array, cfg: ArchConfig,
                      mesh=None):
    """One decode step over paged caches.

    token (B, 1) int32, pos (B,) int32, page_table (B, nblk) int32 shared
    by every layer (one logical->physical mapping per sequence; each layer
    has its own physical pool).  Returns (logits (B, V), caches).

    Named scopes, in the ops' ``op_name``: ``layers`` (the scan), inside
    it ``attn`` (with ``kv_write``, the pool scatter) and ``mlp``, then
    ``lm_head``; :func:`paged_prefill_step` names its ops alike.
    """
    x = jnp.take(params["embed"], token, axis=0)
    x = _compute(x, cfg)
    kind = cfg.layer_kinds()[0]
    acfg = attn_config(cfg)

    def body(carry, scanned):
        x, = carry
        lp = scanned["params"]
        kp, vp = scanned["kv"]
        scales = scanned.get("kv_scale")
        with jax.named_scope("attn"):
            h, kp, vp, scales = attn.paged_decode(
                lp["attn"], _norm(cfg, lp, x, "norm1"),
                kp, vp, page_table, pos, acfg, scales, mesh=mesh)
            x = x + h
        with jax.named_scope("mlp"):
            h2 = _norm(cfg, lp, x, "norm2")
            if kind == "attn_mlp":
                x = x + _mlp_apply(lp["mlp"], h2, cfg)
            else:
                out, _ = moe_mod.apply_moe(lp["moe"], h2, moe_config(cfg))
                x = x + out
        states = {"kv": (kp, vp)}
        if scales is not None:
            states["kv_scale"] = scales
        return (x,), states

    scanned_in = {"params": _cast_tree(params["layers"], cfg),
                  "kv": caches["kv"]}
    if "kv_scale" in caches:        # int8 pools: thread the scales too
        scanned_in["kv_scale"] = caches["kv_scale"]
    with jax.named_scope("layers"):
        (x,), new_states = jax.lax.scan(body, (x,), scanned_in)
    with jax.named_scope("lm_head"):
        x = _norm(cfg, _cast_tree(
            {k: params[k] for k in params if k.startswith("final_norm")},
            cfg), x, "final_norm")
        w = _compute(lm_head_weight(params, cfg), cfg)
        logits = (x[:, 0] @ w).astype(jnp.float32)
    return logits, _paged_out_caches(new_states)


def paged_prefill_step(params: dict, caches: Any, page_table: jax.Array,
                       tokens: jax.Array, start: jax.Array,
                       kv_len: jax.Array, logit_idx: jax.Array,
                       cfg: ArchConfig, mesh=None):
    """One prompt *chunk* of prefill over paged caches.

    tokens (B, C) int32 — a fixed-size chunk (pad the ragged tail; padded
    positions are masked by ``kv_len`` and their KV lands in the null
    page), start (B,) int32 — absolute position of the chunk's first
    token, kv_len (B,) = start + valid chunk length, page_table (B, nblk)
    shared by every layer.  ``logit_idx`` (B,) selects the chunk row whose
    logits are returned — the last valid prompt token on the final chunk
    (what seeds decode); earlier chunks' logits are discarded by the
    caller.  Returns (logits (B, V), caches).
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    x = _compute(x, cfg)
    kind = cfg.layer_kinds()[0]
    acfg = attn_config(cfg)

    def body(carry, scanned):
        x, = carry
        lp = scanned["params"]
        kp, vp = scanned["kv"]
        scales = scanned.get("kv_scale")
        with jax.named_scope("attn"):
            h, kp, vp, scales = attn.paged_prefill(
                lp["attn"], _norm(cfg, lp, x, "norm1"),
                kp, vp, page_table, start, kv_len, acfg, scales, mesh=mesh)
            x = x + h
        with jax.named_scope("mlp"):
            h2 = _norm(cfg, lp, x, "norm2")
            if kind == "attn_mlp":
                x = x + _mlp_apply(lp["mlp"], h2, cfg)
            else:
                out, _ = moe_mod.apply_moe(lp["moe"], h2, moe_config(cfg))
                x = x + out
        states = {"kv": (kp, vp)}
        if scales is not None:
            states["kv_scale"] = scales
        return (x,), states

    scanned_in = {"params": _cast_tree(params["layers"], cfg),
                  "kv": caches["kv"]}
    if "kv_scale" in caches:
        scanned_in["kv_scale"] = caches["kv_scale"]
    with jax.named_scope("layers"):
        (x,), new_states = jax.lax.scan(body, (x,), scanned_in)
    with jax.named_scope("lm_head"):
        x = _norm(cfg, _cast_tree(
            {k: params[k] for k in params if k.startswith("final_norm")},
            cfg), x, "final_norm")
        x_last = jnp.take_along_axis(
            x, logit_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        w = _compute(lm_head_weight(params, cfg), cfg)
        logits = (x_last @ w).astype(jnp.float32)
    return logits, _paged_out_caches(new_states)


def speculative_step(params: dict, caches: Any, page_table: jax.Array,
                     tokens: jax.Array, start: jax.Array,
                     kv_len: jax.Array, cfg: ArchConfig, mesh=None):
    """Speculative *verify* step: score every chunk position in one call.

    tokens (B, C) int32 — ``[last committed token, draft_1 .. draft_k]``
    at absolute positions ``start .. start + C - 1`` (rows at positions
    ``>= kv_len`` are padding; their KV routes to the null page), start /
    kv_len (B,) int32, page_table (B, nblk) shared by every layer.  The
    attention math is exactly chunked prefill (committed prefix + the
    chunk's causal triangle at absolute positions) dispatched through the
    ``verify``-tuned kernel entry; unlike :func:`paged_prefill_step` the
    *full* (B, C, V) logits come back — the accept/reject rule needs the
    target distribution at every drafted position, not just the last one.
    Returns (logits (B, C, V) float32, caches).
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    x = _compute(x, cfg)
    kind = cfg.layer_kinds()[0]
    acfg = attn_config(cfg)

    def body(carry, scanned):
        x, = carry
        lp = scanned["params"]
        kp, vp = scanned["kv"]
        scales = scanned.get("kv_scale")
        h, kp, vp, scales = attn.paged_verify(
            lp["attn"], _norm(cfg, lp, x, "norm1"),
            kp, vp, page_table, start, kv_len, acfg, scales, mesh=mesh)
        x = x + h
        h2 = _norm(cfg, lp, x, "norm2")
        if kind == "attn_mlp":
            x = x + _mlp_apply(lp["mlp"], h2, cfg)
        else:
            out, _ = moe_mod.apply_moe(lp["moe"], h2, moe_config(cfg))
            x = x + out
        states = {"kv": (kp, vp)}
        if scales is not None:
            states["kv_scale"] = scales
        return (x,), states

    scanned_in = {"params": _cast_tree(params["layers"], cfg),
                  "kv": caches["kv"]}
    if "kv_scale" in caches:
        scanned_in["kv_scale"] = caches["kv_scale"]
    (x,), new_states = jax.lax.scan(body, (x,), scanned_in)
    x = _norm(cfg, _cast_tree(
        {k: params[k] for k in params if k.startswith("final_norm")}, cfg),
        x, "final_norm")
    w = _compute(lm_head_weight(params, cfg), cfg)
    logits = (x @ w).astype(jnp.float32)
    return logits, _paged_out_caches(new_states)


def slice_draft_params(params: dict, cfg: ArchConfig,
                       draft_cfg: ArchConfig) -> dict:
    """Self-speculative draft parameters: the target's leading layers.

    Slices the layer-stacked leaves down to ``draft_cfg.n_layers`` and
    shares the embedding / final norm / head, so the draft is the target
    with its tail layers skipped (Draft&Verify-style self-speculation).
    Requires an identical width (``draft_config(width_frac=1.0)``) —
    a narrower draft has its own embedding geometry and must be trained
    (initialised) independently instead.
    """
    if (draft_cfg.d_model, draft_cfg.n_heads, draft_cfg.head_dim) != \
            (cfg.d_model, cfg.n_heads, cfg.head_dim):
        raise ValueError(
            "slice_draft_params needs a same-width draft config; "
            "width-reduced drafts take independently initialised params")
    if draft_cfg.n_layers > cfg.n_layers:
        raise ValueError("draft is deeper than the target")
    out = dict(params)
    out["layers"] = jax.tree.map(lambda a: a[:draft_cfg.n_layers],
                                 params["layers"])
    return out


def decode_step(params: dict, caches: Any, token: jax.Array,
                pos: jax.Array, cfg: ArchConfig):
    """token (B, 1) int32, pos (B,) int32 -> (logits (B, V), caches)."""
    x = jnp.take(params["embed"], token, axis=0)
    x = _compute(x, cfg)
    kind = cfg.layer_kinds()[0]
    shared = _cast_tree(params.get("shared_attn"), cfg) \
        if cfg.attn_period > 0 else None
    acfg = attn_config(cfg)

    shared_kv = caches.get("shared_kv")

    def body(carry, scanned):
        x, idx, skv = carry
        lp = scanned["params"]
        if kind in ("attn_mlp", "attn_moe"):
            ck, cv = scanned["kv"]
            h, ck, cv = attn.decode(lp["attn"], _norm(cfg, lp, x, "norm1"),
                                    ck, cv, pos, acfg)
            x = x + h
            h2 = _norm(cfg, lp, x, "norm2")
            if kind == "attn_mlp":
                x = x + _mlp_apply(lp["mlp"], h2, cfg)
            else:
                out, _ = moe_mod.apply_moe(lp["moe"], h2, moe_config(cfg))
                x = x + out
            new_state = {"kv": (ck, cv)}
        elif kind == "mamba1":
            h, st = ssm_mod.step_mamba1(lp["ssm"],
                                        _norm(cfg, lp, x, "norm1"),
                                        scanned["ssm"], mamba1_config(cfg))
            x = x + h
            new_state = {"ssm": st}
        else:
            h, st = ssm_mod.step_mamba2(lp["ssm"],
                                        _norm(cfg, lp, x, "norm1"),
                                        scanned["ssm"], mamba2_config(cfg))
            x = x + h
            new_state = {"ssm": st}
        if shared is not None:
            app_idx = (idx + 1) // cfg.attn_period - 1

            def apply_shared(operand):
                x, skv = operand
                k_all, v_all = skv
                ck = jax.tree.map(lambda a: a[app_idx], k_all)
                cv = jax.tree.map(lambda a: a[app_idx], v_all)
                h, ck, cv = attn.decode(shared["attn"],
                                        _norm(cfg, shared, x, "norm1"),
                                        ck, cv, pos, acfg)
                x = x + h
                x = x + _mlp_apply(shared["mlp"],
                                   _norm(cfg, shared, x, "norm2"), cfg)
                k_all = jax.tree.map(
                    lambda a, b: jax.lax.dynamic_update_index_in_dim(
                        a, b.astype(a.dtype), app_idx, 0), k_all, ck)
                v_all = jax.tree.map(
                    lambda a, b: jax.lax.dynamic_update_index_in_dim(
                        a, b.astype(a.dtype), app_idx, 0), v_all, cv)
                return x, (k_all, v_all)

            x, skv = jax.lax.cond(
                (idx + 1) % cfg.attn_period == 0, apply_shared,
                lambda op: op, (x, skv))
        return (x, idx + 1, skv), new_state

    scanned_in = {"params": _cast_tree(params["layers"], cfg)}
    for key in ("kv", "ssm"):
        if key in caches:
            scanned_in[key] = caches[key]
    (x, _, shared_kv), new_states = jax.lax.scan(
        body, (x, jnp.int32(0), shared_kv), scanned_in)
    x = _norm(cfg, _cast_tree(
        {k: params[k] for k in params if k.startswith("final_norm")}, cfg),
        x, "final_norm")
    w = _compute(lm_head_weight(params, cfg), cfg)
    logits = (x[:, 0] @ w).astype(jnp.float32)
    new_caches = dict(new_states)
    if shared_kv is not None:
        new_caches["shared_kv"] = shared_kv
    return logits, new_caches


def prefill(params: dict, tokens: jax.Array, cfg: ArchConfig,
            frontend_embeds: jax.Array | None = None,
            max_len: int | None = None, mesh=None):
    """Forward over the prompt; returns (last-token logits, caches).

    Attention layers collect KV for the whole prompt; SSM layers collect the
    final recurrent state.  Sliding-window archs keep only the last W keys
    (ring-buffer layout, slot = pos % W).  ``max_len`` sizes the returned
    KV caches (>= prompt length) so decode steps have room to append —
    without it the cache is exactly prompt-sized and the *next* token's KV
    would be dropped.  ``mesh`` routes long causal prompts through the
    ring sequence-parallel attention tail (see
    :func:`repro.kernels.ops.attention`).
    """
    x = embed_tokens(params, tokens, cfg, frontend_embeds)
    b, s, _ = x.shape
    kind = cfg.layer_kinds()[0]
    acfg = attn_config(cfg)
    shared = _cast_tree(params.get("shared_attn"), cfg) \
        if cfg.attn_period > 0 else None
    w = acfg.window
    max_len = max(max_len or s, s)
    cache_len = min(max_len, w) if w is not None else max_len
    cdt = jnp.dtype(cfg.cache_dtype)

    def kv_out(k, v):
        if s > cache_len:
            # ring-buffer layout: slot = pos % W must match decode's indexing
            start = s - cache_len
            k, v = k[:, :, -cache_len:], v[:, :, -cache_len:]
            shift = start % cache_len
            k = jnp.roll(k, shift, axis=2)
            v = jnp.roll(v, shift, axis=2)
        elif s < cache_len:   # room for decode appends (slot = pos [% W])
            pad = ((0, 0), (0, 0), (0, cache_len - s), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        return k.astype(cdt), v.astype(cdt)

    def body(carry, scanned):
        x, aux, idx = carry
        lp = scanned
        ys = {}
        if kind in ("attn_mlp", "attn_moe"):
            h, (k, v) = attn.full(lp["attn"], _norm(cfg, lp, x, "norm1"),
                                  acfg, return_cache=True, mesh=mesh)
            ys["kv"] = kv_out(k, v)
            x = x + h
            h2 = _norm(cfg, lp, x, "norm2")
            if kind == "attn_mlp":
                x = x + _mlp_apply(lp["mlp"], h2, cfg)
            else:
                out, a = moe_mod.apply_moe(lp["moe"], h2, moe_config(cfg))
                x = x + out
                aux = aux + a
        elif kind in ("mamba1", "mamba2"):
            mcfg = mamba1_config(cfg) if kind == "mamba1" \
                else mamba2_config(cfg)
            appf = ssm_mod.apply_mamba1 if kind == "mamba1" \
                else ssm_mod.apply_mamba2
            xin = _norm(cfg, lp, x, "norm1")
            # the chunked scan hands back the exact final recurrent state
            # (§Perf: this replaced an O(S)-sequential replay that cost
            # 32768 tiny psums per layer at prefill_32k)
            h, st = appf(lp["ssm"], xin, mcfg, return_state=True)
            x = x + h
            ys["ssm"] = st
        if shared is not None:
            def app(v):
                xin = _norm(cfg, shared, v, "norm1")
                h, (k, vv) = attn.full(shared["attn"], xin, acfg,
                                       return_cache=True)
                v = v + h
                v = v + _mlp_apply(shared["mlp"],
                                   _norm(cfg, shared, v, "norm2"), cfg)
                return v, kv_out(k, vv)

            def noapp(v):
                zk = jnp.zeros((b, acfg.n_kv_heads, cache_len,
                                acfg.d_head), cdt)
                return v, (zk, zk)

            is_app = (idx + 1) % cfg.attn_period == 0
            x, skv = jax.lax.cond(is_app, app, noapp, x)
            ys["shared_kv_all"] = skv
            ys["is_app"] = is_app.astype(jnp.float32)
        return (x, aux, idx + 1), ys

    (x, aux, _), states = jax.lax.scan(
        body, (x, jnp.float32(0.0), jnp.int32(0)),
        _cast_tree(params["layers"], cfg))

    caches = dict(states) if states else {}
    if shared is not None:
        # compact (L, ...) zero-padded shared KV down to (n_apps, ...)
        is_app = caches.pop("is_app")
        kv_all = caches.pop("shared_kv_all")
        napp = cfg.n_shared_attn_applications()
        idxs = jnp.cumsum(is_app.astype(jnp.int32)) - 1
        sel = jnp.zeros((napp, cfg.n_layers), jnp.float32)
        sel = sel.at[idxs, jnp.arange(cfg.n_layers)].set(is_app)
        caches["shared_kv"] = (
            jnp.einsum("al,l...->a...", sel,
                       kv_all[0].astype(jnp.float32)).astype(cdt),
            jnp.einsum("al,l...->a...", sel,
                       kv_all[1].astype(jnp.float32)).astype(cdt))
    x = _norm(cfg, _cast_tree(
        {k: params[k] for k in params if k.startswith("final_norm")}, cfg),
        x, "final_norm")
    wv = _compute(lm_head_weight(params, cfg), cfg)
    logits = (x[:, -1] @ wv).astype(jnp.float32)
    return logits, caches



