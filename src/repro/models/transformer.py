"""Decoder-only LM (dense + MoE) with train / prefill / decode paths.

Distribution: GSPMD (pjit) with Megatron-style tensor parallelism over the
``model`` mesh axis and batch data-parallelism over (``pod``, ``data``);
optional FSDP shards params over the dp axes too (kimi-k2 needs it). The
MoE FFN is an explicit ``shard_map`` island: expert-parallel when
n_experts % model_size == 0 (kimi-k2: 384/16), expert-tensor-parallel
otherwise (mixtral: 8 experts < 16 shards → shard d_ff). Layers run under
``lax.scan`` with stacked params (compile-time O(1) in depth) + remat.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.configs.base import LMConfig
from repro.models.layers import decode_attention, flash_attention, rms_norm, rope


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def dp_axis_names(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def wsc(x, mesh, spec: P):
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec)
    )


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axis_names(mesh):
        s *= mesh.shape[a]
    return s


def model_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_lm(rng: jax.Array, cfg: LMConfig) -> Dict[str, Any]:
    pdt = jnp.dtype(cfg.param_dtype)
    d, hd, hq, kv, l = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    keys = jax.random.split(rng, 16)

    def nrm(key, shape, scale=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(pdt)

    layers: Dict[str, jax.Array] = {
        "wq": nrm(keys[0], (l, d, hq * hd)),
        "wk": nrm(keys[1], (l, d, kv * hd)),
        "wv": nrm(keys[2], (l, d, kv * hd)),
        "wo": nrm(keys[3], (l, hq * hd, d), 0.02 / math.sqrt(2 * l)),
        "ln1": jnp.ones((l, d), pdt),
        "ln2": jnp.ones((l, d), pdt),
    }
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((l, hq * hd), pdt)
        layers["bk"] = jnp.zeros((l, kv * hd), pdt)
        layers["bv"] = jnp.zeros((l, kv * hd), pdt)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((l, hd), pdt)
        layers["k_norm"] = jnp.ones((l, hd), pdt)
    if cfg.moe is None:
        layers["wi"] = nrm(keys[4], (l, d, cfg.d_ff))
        layers["wg"] = nrm(keys[5], (l, d, cfg.d_ff))
        layers["wo_ff"] = nrm(keys[6], (l, cfg.d_ff, d), 0.02 / math.sqrt(2 * l))
    else:
        e = cfg.moe.n_experts
        layers["router"] = nrm(keys[7], (l, d, e))
        layers["ewi"] = nrm(keys[8], (l, e, d, cfg.d_ff))
        layers["ewg"] = nrm(keys[9], (l, e, d, cfg.d_ff))
        layers["ewo"] = nrm(keys[10], (l, e, cfg.d_ff, d), 0.02 / math.sqrt(2 * l))
        if cfg.moe.n_shared:
            s = cfg.moe.n_shared
            layers["swi"] = nrm(keys[11], (l, d, s * cfg.d_ff))
            layers["swg"] = nrm(keys[12], (l, d, s * cfg.d_ff))
            layers["swo"] = nrm(keys[13], (l, s * cfg.d_ff, d), 0.02 / math.sqrt(2 * l))

    return {
        "embed": nrm(keys[14], (cfg.vocab, d)),
        "unembed": nrm(keys[15], (d, cfg.vocab)),
        "final_norm": jnp.ones((d,), pdt),
        "layers": layers,
    }


def lm_param_specs(cfg: LMConfig, mesh) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_lm`` output."""
    dp = dp_axis_names(mesh)
    fs = dp if cfg.fsdp else None  # FSDP: shard the big dim over dp too
    m = "model"

    layers: Dict[str, P] = {
        "wq": P(None, fs, m),
        "wk": P(None, fs, m),
        "wv": P(None, fs, m),
        "wo": P(None, m, fs),
        "ln1": P(None, None),
        "ln2": P(None, None),
    }
    if cfg.qkv_bias:
        layers.update(bq=P(None, m), bk=P(None, m), bv=P(None, m))
    if cfg.qk_norm:
        layers.update(q_norm=P(None, None), k_norm=P(None, None))
    if cfg.moe is None:
        layers.update(
            wi=P(None, fs, m), wg=P(None, fs, m), wo_ff=P(None, m, fs)
        )
    else:
        ep = cfg.moe.n_experts % model_size(mesh) == 0 and cfg.moe.n_experts >= model_size(mesh)
        if ep:
            layers.update(
                router=P(None, None, None),
                ewi=P(None, m, fs, None),
                ewg=P(None, m, fs, None),
                ewo=P(None, m, None, fs),
            )
        else:
            layers.update(
                router=P(None, None, None),
                ewi=P(None, None, fs, m),
                ewg=P(None, None, fs, m),
                ewo=P(None, None, m, fs),
            )
        if cfg.moe.n_shared:
            layers.update(swi=P(None, fs, m), swg=P(None, fs, m), swo=P(None, m, fs))

    return {
        "embed": P(m, fs),
        "unembed": P(fs, m),
        "final_norm": P(None),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _dense_ffn(x, wi, wg, wo):
    dt = x.dtype
    h = jax.nn.silu(x @ wg.astype(dt)) * (x @ wi.astype(dt))
    return h @ wo.astype(dt)


def moe_block(x: jax.Array, lp: Dict[str, jax.Array], cfg: LMConfig, mesh) -> jax.Array:
    """Expert FFN as a shard_map island (see module docstring)."""
    moe = cfg.moe
    dp = dp_axis_names(mesh)
    dsz, msz = dp_size(mesh), model_size(mesh)
    b, s, d = x.shape
    shard_batch = dsz > 1 and b % dsz == 0
    b_loc = b // dsz if shard_batch else b
    t_loc = b_loc * s
    e = moe.n_experts
    ep = e % msz == 0 and e >= msz
    cap = int(t_loc * moe.top_k / e * moe.capacity_factor + 0.999)
    cap = min(t_loc, max(8, -(-cap // 8) * 8))

    x_spec = P(dp, None, None) if shard_batch else P(None, None, None)
    fs = dp if (cfg.fsdp and dp) else None  # FSDP: expert weights stay
    # dp-sharded INTO the shard_map and are all-gathered per expert inside
    # the expert loop (streaming FSDP) — otherwise the replication implied
    # by the in_specs makes GSPMD materialize every layer's full expert
    # weights outside the layer scan (>150 GiB for kimi-k2).
    if ep:
        especs = (P("model", fs, None), P("model", fs, None), P("model", None, fs))
    else:
        especs = (P(None, fs, "model"), P(None, fs, "model"), P(None, "model", fs))

    def local_fn(x_loc, router_w, wi, wg, wo):
        dt = x_loc.dtype
        xl = x_loc.reshape(-1, d)  # [t_loc, d]
        # Router matmul in the compute dtype; only the [t, E] logits are
        # upcast. Upcasting xl itself creates a full-activation f32 copy
        # that AD saves per layer (107 GiB for kimi-k2 — see EXPERIMENTS
        # §Perf iteration log).
        logits = (xl @ router_w.astype(dt)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gval, gidx = jax.lax.top_k(probs, moe.top_k)
        gval = gval / jnp.maximum(gval.sum(-1, keepdims=True), 1e-9)
        e_loc = wi.shape[0]
        e0 = jax.lax.axis_index("model") * e_loc if ep else 0

        def expert_step(out, ew):
            wi_e, wg_e, wo_e, e_rel = ew
            if fs is not None:
                # cast BEFORE the gather: the FSDP weight all-gather is the
                # dominant collective for MoE decode — f32 wire format would
                # double it (§Perf: kimi-k2 decode 258 GB/dev → 129 GB/dev)
                wi_e = jax.lax.all_gather(wi_e.astype(dt), fs, axis=0, tiled=True)
                wg_e = jax.lax.all_gather(wg_e.astype(dt), fs, axis=0, tiled=True)
                wo_e = jax.lax.all_gather(wo_e.astype(dt), fs, axis=1, tiled=True)
            e_glob = e0 + e_rel
            gate_e = jnp.sum(jnp.where(gidx == e_glob, gval, 0.0), axis=-1)  # [t]
            topv, topi = jax.lax.top_k(gate_e, cap)
            xe = xl[topi]
            h = jax.nn.silu(xe @ wg_e.astype(dt)) * (xe @ wi_e.astype(dt))
            ye = (h @ wo_e.astype(dt)) * topv[:, None].astype(dt)
            return out.at[topi].add(ye), None

        out0 = jnp.zeros_like(xl)
        out, _ = jax.lax.scan(
            expert_step,
            out0,
            (wi, wg, wo, jnp.arange(wi.shape[0], dtype=jnp.int32)),
        )
        out = jax.lax.psum(out, "model")
        return out.reshape(x_loc.shape)

    out = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, P(None, None)) + especs,
        out_specs=x_spec,
        check_vma=False,
    )(x, lp["router"], lp["ewi"], lp["ewg"], lp["ewo"])

    if moe.n_shared:
        out = out + _dense_ffn(x, lp["swi"], lp["swg"], lp["swo"])
    return out


def _qkv(x, lp, cfg: LMConfig, positions):
    b = x.shape[0]
    s = x.shape[1]
    hd, hq, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    g = hq // kvh
    dt = x.dtype
    q = x @ lp["wq"].astype(dt)
    k = x @ lp["wk"].astype(dt)
    v = x @ lp["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + lp["bq"].astype(dt)
        k = k + lp["bk"].astype(dt)
        v = v + lp["bv"].astype(dt)
    q = q.reshape(b, s, kvh * g, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = q.reshape(b, s, kvh, g, hd)
    return q, k, v


def attention_block(x, lp, cfg: LMConfig, positions, triangle_skip=False):
    b, s, _ = x.shape
    q, k, v = _qkv(x, lp, cfg, positions)
    o = flash_attention(
        q,
        k,
        v,
        causal=True,
        window=cfg.sliding_window,
        q_chunk=cfg.attn_q_chunk,
        kv_chunk=cfg.attn_kv_chunk,
        triangle_skip=triangle_skip,
    )
    o = o.reshape(b, s, cfg.n_heads * cfg.hd)
    return o @ lp["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# forward paths
# ---------------------------------------------------------------------------

def _ffn(x, lp, cfg: LMConfig, mesh):
    if cfg.moe is None:
        return _dense_ffn(x, lp["wi"], lp["wg"], lp["wo_ff"])
    return moe_block(x, lp, cfg, mesh)


def _layer_specs(cfg: LMConfig, mesh):
    """Per-layer weight specs (stacked specs minus the leading L dim)."""
    return {
        k: P(*v[1:]) for k, v in lm_param_specs(cfg, mesh)["layers"].items()
    }


def _constrain_layer(lp, cfg: LMConfig, mesh):
    """Re-pin the scan body's sliced weights to their sharded layout.

    Without this, GSPMD hoists the FSDP all-gather of the *whole stacked*
    parameter tree out of the layer scan — materializing every layer's
    full weights on every device (for kimi-k2 that is >150 GiB of temp).
    Constraining inside the body forces the gather to happen per layer.
    """
    if not cfg.fsdp:
        return lp
    specs = _layer_specs(cfg, mesh)
    return {k: wsc(v, mesh, specs[k]) for k, v in lp.items()}


def lm_forward(params, tokens, cfg: LMConfig, mesh, *, triangle_skip=False):
    """Shared trunk: tokens [B, S] → final hidden states [B, S, d]."""
    dp = dp_axis_names(mesh)
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0, mode='clip').astype(dt)
    x = wsc(x, mesh, P(dp, None, None))
    positions = jnp.arange(tokens.shape[1])

    def layer(x, lp):
        # Barrier: without it XLA hoists the rematted bf16→f32 convert of
        # the saved activation out of the backward loop, materializing the
        # whole [L, B, S, d] stack in f32 (2× remat memory; 107 GiB for
        # kimi-k2). The barrier pins the convert inside the loop body.
        x = jax.lax.optimization_barrier(x)
        lp = _constrain_layer(lp, cfg, mesh)
        h = attention_block(
            rms_norm(x, lp["ln1"], cfg.norm_eps), lp, cfg, positions,
            triangle_skip=triangle_skip,
        )
        x = x + h
        h2 = _ffn(rms_norm(x, lp["ln2"], cfg.norm_eps), lp, cfg, mesh)
        x = x + h2
        x = wsc(x, mesh, P(dp, None, None))
        return x, None

    # prevent_cse=False: scan already isolates iterations; the default
    # barriers make XLA keep an extra f32 copy of the saved activation
    # stack (2× remat memory for free).
    body = jax.checkpoint(layer, prevent_cse=False) if cfg.remat else layer
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def lm_loss(params, tokens, labels, cfg: LMConfig, mesh) -> jax.Array:
    x = lm_forward(params, tokens, cfg, mesh)
    return softmax_xent(x, params["unembed"], labels, cfg)


def softmax_xent(x, unembed, labels, cfg: LMConfig) -> jax.Array:
    """Token-mean cross entropy; optional vocab-chunked logsumexp (perf
    knob: avoids the [B, S, V] f32 logit buffer)."""
    b, s, d = x.shape
    v = unembed.shape[1]
    if cfg.vocab_chunk is None:
        logits = (x @ unembed.astype(x.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - ll)
    vc = cfg.vocab_chunk
    assert v % vc == 0
    nchunks = v // vc
    un = unembed.reshape(d, nchunks, vc)

    def chunk(carry, inp):
        m, ssum, ll = carry
        ci, w = inp
        lg = (x @ w.astype(x.dtype)).astype(jnp.float32)  # [B, S, vc]
        m_new = jnp.maximum(m, lg.max(-1))
        ssum = ssum * jnp.exp(m - m_new) + jnp.exp(lg - m_new[..., None]).sum(-1)
        rel = labels - ci * vc
        inside = (rel >= 0) & (rel < vc)
        lab = jnp.take_along_axis(lg, jnp.clip(rel, 0, vc - 1)[..., None], axis=-1)[..., 0]
        ll = jnp.where(inside, lab, ll)
        return (m_new, ssum, ll), None

    init = (
        jnp.full((b, s), -jnp.inf, jnp.float32),
        jnp.zeros((b, s), jnp.float32),
        jnp.zeros((b, s), jnp.float32),
    )
    (m, ssum, ll), _ = jax.lax.scan(
        chunk, init, (jnp.arange(nchunks), un.transpose(1, 0, 2))
    )
    lse = m + jnp.log(ssum)
    return jnp.mean(lse - ll)


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------

def cache_shape(cfg: LMConfig, batch: int, cache_len: int):
    t = cache_len if cfg.sliding_window is None else min(cache_len, cfg.sliding_window)
    shp = (cfg.n_layers, batch, t, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jax.ShapeDtypeStruct(shp, jnp.dtype(cfg.dtype)),
        "v": jax.ShapeDtypeStruct(shp, jnp.dtype(cfg.dtype)),
    }


def cache_specs(cfg: LMConfig, mesh, batch: int):
    dp = dp_axis_names(mesh)
    if batch % max(dp_size(mesh), 1) == 0 and dp_size(mesh) > 1:
        spec = P(None, dp, "model", None, None)
    else:
        # tiny-batch long-context: shard the sequence dim over everything
        spec = P(None, None, (dp + ("model",)) if dp else "model", None, None)
    return {"k": spec, "v": spec}


def lm_prefill(params, tokens, cfg: LMConfig, mesh):
    """tokens [B, S] → (last-token logits [B, V], cache)."""
    dp = dp_axis_names(mesh)
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0, mode='clip').astype(dt)
    positions = jnp.arange(tokens.shape[1])

    def layer(x, lp):
        lp = _constrain_layer(lp, cfg, mesh)
        xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
        b, s, _ = xn.shape
        q, k, v = _qkv(xn, lp, cfg, positions)
        o = flash_attention(
            q, k, v,
            causal=True,
            window=cfg.sliding_window,
            q_chunk=cfg.attn_q_chunk,
            kv_chunk=cfg.attn_kv_chunk,
        )
        o = o.reshape(b, s, cfg.n_heads * cfg.hd) @ lp["wo"].astype(x.dtype)
        x = x + o
        x = x + _ffn(rms_norm(x, lp["ln2"], cfg.norm_eps), lp, cfg, mesh)
        if cfg.sliding_window is not None and s > cfg.sliding_window:
            # Rolling layout: token p lives at slot p % W, matching
            # lm_decode_step's write index so decode can continue the cache.
            w = cfg.sliding_window
            k = jnp.roll(k[:, -w:], shift=s % w, axis=1)
            v = jnp.roll(v[:, -w:], shift=s % w, axis=1)
        return x, {"k": k, "v": v}

    x, cache = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = (x @ params["unembed"].astype(x.dtype)).astype(jnp.float32)
    return logits[:, 0], cache


def lm_decode_step(params, token, cache, pos, cfg: LMConfig, mesh):
    """token [B] int32; cache {'k','v': [L, B, T, KV, hd]}; pos scalar index
    of the new token. Returns (logits [B, V], new cache)."""
    dt = jnp.dtype(cfg.dtype)
    b = token.shape[0]
    x = jnp.take(params["embed"], token[:, None], axis=0, mode='clip').astype(dt)  # [B,1,d]
    t_cache = cache["k"].shape[2]
    write_idx = pos % t_cache if cfg.sliding_window is not None else pos
    positions = pos[None] if jnp.ndim(pos) == 0 else pos

    def layer(x, lp_cache):
        lp, kc, vc = lp_cache
        lp = _constrain_layer(lp, cfg, mesh)
        xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(xn, lp, cfg, jnp.reshape(positions, (1,)))
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, write_idx, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, write_idx, 0, 0))
        mask_pos = jnp.minimum(pos, t_cache - 1)
        o = decode_attention(q[:, 0], kc, vc, mask_pos)
        o = o.reshape(b, 1, cfg.n_heads * cfg.hd) @ lp["wo"].astype(x.dtype)
        x = x + o
        x = x + _ffn(rms_norm(x, lp["ln2"], cfg.norm_eps), lp, cfg, mesh)
        return x, {"k": kc, "v": vc}

    x, new_cache = jax.lax.scan(layer, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["unembed"].astype(x.dtype)).astype(jnp.float32)
    return logits[:, 0], new_cache
