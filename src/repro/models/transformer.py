"""Architecture assembly: every family, scan-over-layers, three modes.

All stacks use ``jax.lax.scan`` over layer-stacked parameters so the HLO
stays one-layer-sized regardless of depth (essential for 512-device dry-run
compiles and the standard MaxText-style structure XLA pipelines well).
Heterogeneous stacks (gemma3 local/global, griffin rec/rec/attn, vision
cross groups, deepseek first-dense) are expressed as grouped scans or
per-layer flag arrays — never unrolled.

Modes: ``train`` (logits, no cache), ``prefill`` (logits + built cache),
``decode`` (one token in, cache updated in place).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention, common, mla, moe, rglru, ssm
from repro.models.common import (P, apply_norm, embed_tokens, embedding_init,
                                 logits_from_hidden, mlp_apply, mlp_init,
                                 norm_init, split_tree, stack_axes,
                                 vmap_stack)

BIG_WINDOW = 1 << 30


def _current_mesh():
    """The mesh in effect, or None outside one: the abstract mesh when set,
    else the physical mesh a ``with Mesh(...):`` block pushes."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and mesh.axis_names:
        return mesh
    from jax._src import mesh as _mesh_internal
    phys = _mesh_internal.thread_resources.env.physical_mesh
    if phys is not None and phys.axis_names:
        return phys
    return None


def constrain(x, axes):
    """with_sharding_constraint by logical axes — no-op outside a mesh
    context (smoke tests), divisibility-aware inside one. This pins the
    activation layout at the embedding/logits boundary; SPMD propagation
    can otherwise pick a replicated layout for whole forward passes (it
    resolves ties arbitrarily — observed on MLA archs)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    from repro.runtime import sharding as shd
    spec = shd.spec_for(axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _policy(remat: str):
    if remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    return None


def _maybe_remat(fn, cfg, mode):
    if mode == "train" and cfg.remat != "none":
        return jax.checkpoint(fn, policy=_policy(cfg.remat))
    return fn


# ---------------------------------------------------------------------------
# Layer inits
# ---------------------------------------------------------------------------

def _attn_init(cfg, key, kv_input_dim=None):
    return attention.init(key, cfg.d_model, cfg.n_heads, cfg.n_kv,
                          cfg.head_dim_, qkv_bias=cfg.qkv_bias,
                          dtype=cfg.params_dtype, kv_input_dim=kv_input_dim)


def decoder_layer_init(cfg, key, use_moe: bool, d_ff: Optional[int] = None):
    ks = jax.random.split(key, 2)
    p = dict(ln1=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype))
    if cfg.ssm:
        p["mixer"] = ssm.block_init(
            ks[0], cfg.d_model, d_inner=cfg.d_inner,
            head_dim=cfg.ssm_head_dim, n_groups=cfg.ssm_groups,
            d_state=cfg.ssm_state, dtype=cfg.params_dtype)
        return p
    if cfg.mla:
        p["attn"] = mla.init(ks[0], cfg.d_model, cfg.n_heads,
                             q_lora=cfg.q_lora, kv_lora=cfg.kv_lora,
                             d_nope=cfg.d_nope, d_rope=cfg.d_rope,
                             d_v=cfg.d_v, dtype=cfg.params_dtype)
    else:
        p["attn"] = _attn_init(cfg, ks[0])
    p["ln2"] = norm_init(cfg.d_model, cfg.norm, cfg.params_dtype)
    if use_moe:
        p["mlp"] = moe.init(ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts,
                            n_shared=cfg.n_shared, dtype=cfg.params_dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, d_ff or cfg.d_ff,
                            cfg.params_dtype)
    return p


def rec_layer_init(cfg, key):
    ks = jax.random.split(key, 2)
    return dict(ln1=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype),
                mixer=rglru.block_init(ks[0], cfg.d_model,
                                       lru_width=cfg.lru_width,
                                       dtype=cfg.params_dtype),
                ln2=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype),
                mlp=mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.params_dtype,
                             gate="gelu"))


def cross_layer_init(cfg, key):
    """Gated cross-attention layer (llama-3.2-vision style)."""
    ks = jax.random.split(key, 2)
    return dict(ln1=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype),
                cross=_attn_init(cfg, ks[0]),
                gate_attn=common.zeros_init((1,), ("scalar",),
                                            cfg.params_dtype),
                ln2=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype),
                mlp=mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.params_dtype),
                gate_mlp=common.zeros_init((1,), ("scalar",),
                                           cfg.params_dtype))


def encdec_dec_layer_init(cfg, key):
    ks = jax.random.split(key, 3)
    return dict(ln1=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype),
                self=_attn_init(cfg, ks[0]),
                ln2=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype),
                cross=_attn_init(cfg, ks[1]),
                ln3=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype),
                mlp=mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.params_dtype))


# ---------------------------------------------------------------------------
# Layer applies
# ---------------------------------------------------------------------------

def _gemma3_layer_args(cfg, flag):
    """Per-layer (window, theta) from the is_global flag (traced-safe)."""
    window = jnp.where(flag > 0, jnp.int32(BIG_WINDOW),
                       jnp.int32(max(cfg.window, 1)))
    theta = jnp.where(flag > 0,
                      jnp.float32(cfg.rope_theta_global or cfg.rope_theta),
                      jnp.float32(cfg.rope_theta))
    return window, theta


def decoder_layer_apply(cfg, p, x, positions, flag, mode, cache, decode_pos,
                        use_moe: bool):
    h = apply_norm(x, p["ln1"], cfg.norm)
    if cfg.ssm:
        mix, new_cache = ssm.block_apply(h, p["mixer"], cfg, mode=mode,
                                         cache=cache, chunk=cfg.ssd_chunk)
        return x + mix, new_cache
    if cfg.mla:
        mix, new_cache = mla.apply(
            h, p["attn"], n_heads=cfg.n_heads, q_lora=cfg.q_lora,
            kv_lora=cfg.kv_lora, d_nope=cfg.d_nope, d_rope=cfg.d_rope,
            d_v=cfg.d_v, positions=positions, block_kv=cfg.block_kv,
            cache=cache if mode == "decode" else None, decode_pos=decode_pos)
        if mode == "prefill":
            # MLA prefill cache = the compressed latents, recomputed cheaply.
            c_kv, k_rope = mla._latent(h, p["attn"], cfg.kv_lora, positions)
            new_cache = (c_kv, k_rope)
    else:
        if cfg.family == "gemma3":
            window, theta = _gemma3_layer_args(cfg, flag)
            kind = "sliding"
        else:
            window, theta, kind = cfg.window, cfg.rope_theta, \
                ("sliding" if cfg.window else "causal")
        mix, kv = attention.apply(
            h, p["attn"], n_kv=cfg.n_kv, n_heads=cfg.n_heads,
            positions=positions, kind=kind, window=window, rope_theta=theta,
            block_kv=cfg.block_kv, softmax_scale=cfg.softmax_scale,
            cache=cache if mode == "decode" else None, decode_pos=decode_pos)
        if mode == "prefill" and kv is None:
            k, v = attention.project_kv(h, p["attn"], theta, positions)
            kv = (k, v)
        new_cache = kv if mode != "train" else None
    x = x + mix
    h2 = apply_norm(x, p["ln2"], cfg.norm)
    if use_moe:
        y = moe.apply(h2, p["mlp"], top_k=cfg.top_k, n_experts=cfg.n_experts,
                      capacity_factor=cfg.moe_capacity_factor)
    else:
        y = mlp_apply(h2, p["mlp"])
    return x + y, new_cache


def rec_layer_apply(cfg, p, x, mode, cache):
    h = apply_norm(x, p["ln1"], cfg.norm)
    mix, new_cache = rglru.block_apply(h, p["mixer"], mode=mode, cache=cache)
    x = x + mix
    h2 = apply_norm(x, p["ln2"], cfg.norm)
    return x + mlp_apply(h2, p["mlp"], gate="gelu"), new_cache


def attn_layer_apply(cfg, p, x, positions, mode, cache, decode_pos):
    """Griffin local-attention layer (MQA, sliding window)."""
    h = apply_norm(x, p["ln1"], cfg.norm)
    mix, kv = attention.apply(
        h, p["attn"], n_kv=cfg.n_kv, n_heads=cfg.n_heads,
        positions=positions, kind="sliding", window=cfg.window,
        rope_theta=cfg.rope_theta, block_kv=cfg.block_kv,
        cache=cache if mode == "decode" else None, decode_pos=decode_pos)
    if mode == "prefill" and kv is None:
        kv = attention.project_kv(h, p["attn"], cfg.rope_theta, positions)
    x = x + mix
    h2 = apply_norm(x, p["ln2"], cfg.norm)
    return x + mlp_apply(h2, p["mlp"], gate="gelu"), \
        (kv if mode != "train" else None)


def cross_layer_apply(cfg, p, x, img_kv, mode, positions):
    """Gated cross-attention to static image/encoder KV (never updates it)."""
    h = apply_norm(x, p["ln1"], cfg.norm)
    if mode == "decode":
        mix, _ = attention.apply(
            h, p["cross"], n_kv=cfg.n_kv, n_heads=cfg.n_heads,
            positions=positions, kind="full", rope_theta=None,
            cache=img_kv, decode_pos=0)
    else:
        k, v = img_kv
        kv_pos = jax.lax.broadcasted_iota(jnp.int32, (k.shape[1],), 0)
        q = attention.project_q(h, p["cross"], None, positions)
        B, Sq = q.shape[:2]
        q = q.reshape(B, Sq, cfg.n_kv, cfg.n_heads // cfg.n_kv, -1)
        o = attention.blocked_attention(q, k, v, positions, kv_pos,
                                        kind="full", block_kv=cfg.block_kv)
        mix = attention.project_out(o.reshape(B, Sq, cfg.n_heads, -1),
                                    p["cross"])
    x = x + jnp.tanh(p["gate_attn"].astype(x.dtype)) * mix
    h2 = apply_norm(x, p["ln2"], cfg.norm)
    return x + jnp.tanh(p["gate_mlp"].astype(x.dtype)) * mlp_apply(h2,
                                                                   p["mlp"])


# ---------------------------------------------------------------------------
# Stack runners
# ---------------------------------------------------------------------------

def _scan_stack(cfg, stacked, x, flags, caches, mode, layer_fn):
    """Generic scan over a homogeneous stack. ``layer_fn(x, lp, flag, cache)``
    → (x, cache_out). caches=None in train mode."""
    def body(carry, inp):
        if caches is None:
            lp, fl = inp
            y, c = layer_fn(carry, lp, fl, None)
        else:
            lp, fl, cache = inp
            y, c = layer_fn(carry, lp, fl, cache)
        return y, c

    body = _maybe_remat(body, cfg, mode)
    xs = (stacked, flags) if caches is None else (stacked, flags, caches)
    return jax.lax.scan(body, x, xs)


# ---------------------------------------------------------------------------
# Family assemblies
# ---------------------------------------------------------------------------

def init(cfg, key):
    """Full parameter tree (P leaves)."""
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = dict(
        embed=embedding_init(ks[0], cfg.padded_vocab, cfg.d_model,
                             cfg.params_dtype, tied=cfg.tie_embeddings),
        final_norm=norm_init(cfg.d_model, cfg.norm, cfg.params_dtype))

    if cfg.family in ("decoder", "gemma3"):
        use_moe = cfg.n_experts > 0
        if cfg.first_dense:
            params["dense_layers"] = vmap_stack(
                lambda k: decoder_layer_init(cfg, k, False,
                                             d_ff=cfg.dense_d_ff),
                ks[1], cfg.first_dense)
        params["layers"] = vmap_stack(
            lambda k: decoder_layer_init(cfg, k, use_moe), ks[2],
            cfg.n_layers - cfg.first_dense)

    elif cfg.family == "griffin":
        n_groups, rem = divmod(cfg.n_layers, 3)

        def group_init(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return dict(rec1=rec_layer_init(cfg, k1),
                        rec2=rec_layer_init(cfg, k2),
                        attn=dict(ln1=norm_init(cfg.d_model, cfg.norm,
                                                cfg.params_dtype),
                                  attn=_attn_init(cfg, k3),
                                  ln2=norm_init(cfg.d_model, cfg.norm,
                                                cfg.params_dtype),
                                  mlp=mlp_init(jax.random.fold_in(k3, 1),
                                               cfg.d_model, cfg.d_ff,
                                               cfg.params_dtype,
                                               gate="gelu")))
        params["groups"] = vmap_stack(group_init, ks[1], n_groups)
        if rem:
            params["tail"] = vmap_stack(lambda k: rec_layer_init(cfg, k),
                                        ks[2], rem)

    elif cfg.family == "vision":
        per = cfg.cross_every
        n_groups = cfg.n_layers // per

        def group_init(k):
            k1, k2 = jax.random.split(k)
            return dict(cross=cross_layer_init(cfg, k1),
                        selfs=vmap_stack(
                            lambda kk: decoder_layer_init(cfg, kk, False),
                            k2, per - 1))
        params["groups"] = vmap_stack(group_init, ks[1], n_groups)

    elif cfg.family == "encdec":
        params["enc_layers"] = vmap_stack(
            lambda k: dict(ln1=norm_init(cfg.d_model, cfg.norm,
                                         cfg.params_dtype),
                           attn=_attn_init(cfg, k),
                           ln2=norm_init(cfg.d_model, cfg.norm,
                                         cfg.params_dtype),
                           mlp=mlp_init(jax.random.fold_in(k, 1), cfg.d_model,
                                        cfg.d_ff, cfg.params_dtype)),
            ks[1], cfg.enc_layers)
        params["enc_norm"] = norm_init(cfg.d_model, cfg.norm,
                                       cfg.params_dtype)
        params["layers"] = vmap_stack(lambda k: encdec_dec_layer_init(cfg, k),
                                      ks[2], cfg.n_layers)
    else:
        raise ValueError(cfg.family)
    return params


def _encode(cfg, params, frames):
    """Bidirectional encoder over stub frame embeddings [B, S_src, d]."""
    x = frames.astype(cfg.compute_dtype)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def layer(xc, lp, fl, cache):
        h = apply_norm(xc, lp["ln1"], cfg.norm)
        mix, _ = attention.apply(h, lp["attn"], n_kv=cfg.n_kv,
                                 n_heads=cfg.n_heads, positions=positions,
                                 kind="full", rope_theta=cfg.rope_theta,
                                 block_kv=cfg.block_kv)
        xc = xc + mix
        h2 = apply_norm(xc, lp["ln2"], cfg.norm)
        return xc + mlp_apply(h2, lp["mlp"]), None

    flags = jnp.zeros(cfg.enc_layers)
    x, _ = _scan_stack(cfg, params["enc_layers"], x, flags, None, "train",
                       layer)
    return apply_norm(x, params["enc_norm"], cfg.norm)


def apply(cfg, params, batch, mode, cache=None, decode_pos=None):
    """Returns (logits, new_cache). batch: tokens [B,S] (+frames/patches)."""
    dtype = cfg.compute_dtype
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(tokens, params["embed"], dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    if cfg.embed_scale:
        x = x * np.sqrt(cfg.d_model).astype(dtype)
    if mode == "decode":
        positions = jnp.full((1,), decode_pos, jnp.int32)
    else:
        positions = jnp.arange(S, dtype=jnp.int32)

    new_cache = None
    if cfg.family in ("decoder", "gemma3"):
        use_moe = cfg.n_experts > 0
        n_rest = cfg.n_layers - cfg.first_dense
        if cfg.family == "gemma3":
            idx = np.arange(n_rest)
            flags = jnp.asarray((idx % cfg.attn_every) == cfg.attn_every - 1,
                                jnp.float32)
        else:
            flags = jnp.zeros(n_rest)
        c_dense, c_rest = (cache if cache is not None else (None, None))
        if cfg.first_dense:
            fl0 = jnp.zeros(cfg.first_dense)
            x, c_dense = _scan_stack(
                cfg, params["dense_layers"], x, fl0, c_dense, mode,
                lambda xc, lp, fl, cc: decoder_layer_apply(
                    cfg, lp, xc, positions, fl, mode, cc, decode_pos, False))
        x, c_rest = _scan_stack(
            cfg, params["layers"], x, flags, c_rest, mode,
            lambda xc, lp, fl, cc: decoder_layer_apply(
                cfg, lp, xc, positions, fl, mode, cc, decode_pos, use_moe))
        if mode != "train":
            new_cache = (c_dense, c_rest)

    elif cfg.family == "griffin":
        def group_apply(xc, gp, fl, gc):
            gc = gc or {}
            c1 = gc.get("rec1") if gc else None
            xc, o1 = rec_layer_apply(cfg, gp["rec1"], xc, mode, c1)
            c2 = gc.get("rec2") if gc else None
            xc, o2 = rec_layer_apply(cfg, gp["rec2"], xc, mode, c2)
            ca = gc.get("attn") if gc else None
            xc, oa = attn_layer_apply(cfg, gp["attn"], xc, positions, mode,
                                      ca, decode_pos)
            out = dict(rec1=o1, rec2=o2, attn=oa) if mode != "train" else None
            return xc, out

        gcache, tcache = (cache if cache is not None else (None, None))
        n_groups = cfg.n_layers // 3
        x, gout = _scan_stack(cfg, params["groups"], x,
                              jnp.zeros(n_groups), gcache, mode, group_apply)
        tout = None
        if "tail" in params:
            rem = cfg.n_layers - 3 * n_groups
            x, tout = _scan_stack(
                cfg, params["tail"], x, jnp.zeros(rem), tcache, mode,
                lambda xc, lp, fl, cc: rec_layer_apply(cfg, lp, xc, mode, cc))
        if mode != "train":
            new_cache = (gout, tout)

    elif cfg.family == "vision":
        patches = batch.get("patches")
        per = cfg.cross_every
        n_groups = cfg.n_layers // per

        def group_apply(xc, gp, fl, gc):
            if mode == "decode":
                img_kv = gc["img"]
            else:
                k, v = attention.project_kv(
                    patches.astype(dtype), gp["cross"]["cross"], None,
                    jnp.arange(patches.shape[1], dtype=jnp.int32) * 0)
                img_kv = (k, v)
            xc = cross_layer_apply(cfg, gp["cross"], xc, img_kv, mode,
                                   positions)
            sc = gc["selfs"] if gc else None
            xc, souts = _scan_stack(
                cfg, gp["selfs"], xc, jnp.zeros(per - 1), sc, mode,
                lambda xx, lp, f2, cc: decoder_layer_apply(
                    cfg, lp, xx, positions, f2, mode, cc, decode_pos, False))
            out = (dict(img=img_kv, selfs=souts) if mode != "train" else None)
            return xc, out

        x, gout = _scan_stack(cfg, params["groups"], x, jnp.zeros(n_groups),
                              cache, mode, group_apply)
        if mode != "train":
            new_cache = gout

    elif cfg.family == "encdec":
        if mode == "decode":
            memory = None
        else:
            memory = _encode(cfg, params, batch["frames"])
        mem_pos = (jnp.arange(memory.shape[1], dtype=jnp.int32)
                   if memory is not None else None)

        def dec_layer(xc, lp, fl, cc):
            c_self = cc["self"] if cc else None
            h = apply_norm(xc, lp["ln1"], cfg.norm)
            mix, kv = attention.apply(
                h, lp["self"], n_kv=cfg.n_kv, n_heads=cfg.n_heads,
                positions=positions, kind="causal",
                rope_theta=cfg.rope_theta, block_kv=cfg.block_kv,
                cache=c_self if mode == "decode" else None,
                decode_pos=decode_pos)
            if mode == "prefill" and kv is None:
                kv = attention.project_kv(h, lp["self"], cfg.rope_theta,
                                          positions)
            xc = xc + mix
            h2 = apply_norm(xc, lp["ln2"], cfg.norm)
            if mode == "decode":
                xmix, _ = attention.apply(
                    h2, lp["cross"], n_kv=cfg.n_kv, n_heads=cfg.n_heads,
                    positions=positions, kind="full", rope_theta=None,
                    cache=cc["cross"], decode_pos=0)
                cross_kv = cc["cross"]
            else:
                ck, cv = attention.project_kv(memory, lp["cross"], None,
                                              mem_pos)
                q = attention.project_q(h2, lp["cross"], None, positions)
                Bq, Sq = q.shape[:2]
                q = q.reshape(Bq, Sq, cfg.n_kv, cfg.n_heads // cfg.n_kv, -1)
                o = attention.blocked_attention(q, ck, cv, positions, mem_pos,
                                                kind="full",
                                                block_kv=cfg.block_kv)
                xmix = attention.project_out(
                    o.reshape(Bq, Sq, cfg.n_heads, -1), lp["cross"])
                cross_kv = (ck, cv)
            xc = xc + xmix
            h3 = apply_norm(xc, lp["ln3"], cfg.norm)
            xc = xc + mlp_apply(h3, lp["mlp"])
            out = (dict(self=kv, cross=cross_kv) if mode != "train" else None)
            return xc, out

        x, couts = _scan_stack(cfg, params["layers"], x,
                               jnp.zeros(cfg.n_layers), cache, mode,
                               dec_layer)
        if mode != "train":
            new_cache = couts
    else:
        raise ValueError(cfg.family)

    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = logits_from_hidden(x, params["embed"], cfg.vocab, dtype)
    logits = constrain(logits, ("act_batch", "act_seq", "act_vocab"))
    return logits, new_cache
