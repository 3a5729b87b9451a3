"""Core NN layers: norms, RoPE/M-RoPE, chunked-online-softmax attention
(global + sliding window, GQA/MQA, softcap, qk-norm), gated MLPs, MoE with
capacity-based expert-parallel dispatch, embeddings.

Memory discipline: training attention never materializes (S x S); it scans
over query chunks with an online softmax (flash-style in jnp, O(C*S) live).
Sliding-window blocks slice a static (C + W) KV strip -> O(S*W) FLOPs, which
is what makes gemma2/gemma3/recurrentgemma long-context cells viable.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ----------------------------------------------------------------------------
# init helpers
# ----------------------------------------------------------------------------

def dense_init(rng, shape, dtype, scale=None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    s = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(rng, shape, jnp.float32) * s).astype(dtype)


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def rmsnorm(x, gamma, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return ((1.0 + gamma.astype(jnp.float32)) * out).astype(x.dtype)


def layernorm(x, gamma, beta, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (gamma.astype(jnp.float32) * out + beta.astype(jnp.float32)).astype(x.dtype)


def apply_norm(params, x, kind):
    if kind == "rmsnorm":
        return rmsnorm(x, params["g"])
    return layernorm(x, params["g"], params["b"])


def init_norm(rng, d, kind, dtype):
    if kind == "rmsnorm":
        return {"g": jnp.zeros((d,), dtype)}
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


# ----------------------------------------------------------------------------
# RoPE (+ partial + M-RoPE)
# ----------------------------------------------------------------------------

def rope_freqs(head_dim, rope_pct, base):
    rot = int(head_dim * rope_pct) // 2 * 2
    inv = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, *, rope_pct=1.0, base=10_000.0,
               mrope_sections=None):
    """x: (..., S, H, hd); positions: (..., S) int or (3, ..., S) for M-RoPE."""
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, rope_pct, base)
    if rot == 0:
        return x
    if mrope_sections is not None:
        # qwen2-vl: the rot/2 frequency slots are split into sections, each
        # driven by its own position stream (temporal/height/width).
        secs = mrope_sections
        assert sum(secs) == rot // 2, (secs, rot)
        pos_parts = []
        for i, s in enumerate(secs):
            pos_parts.append(jnp.broadcast_to(positions[i][..., None],
                                              positions[i].shape + (s,)))
        pos = jnp.concatenate(pos_parts, axis=-1)          # (..., S, rot/2)
        theta = pos.astype(jnp.float32) * inv              # (..., S, rot/2)
    else:
        theta = positions[..., None].astype(jnp.float32) * inv
    cos = jnp.cos(theta)[..., None, :]                     # (..., S, 1, rot/2)
    sin = jnp.sin(theta)[..., None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ----------------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------------

def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


def pick_chunk(S, want):
    """Largest divisor of S that is <= want (graceful for odd lengths)."""
    c = min(want, S)
    while S % c:
        c -= 1
    return c


def _attn_scores(q, k, softcap, scale):
    # q: (B, C, KV, G, hd)  k: (B, T, KV, hd) -> (B, KV, G, C, T)
    s = jnp.einsum("bckgh,btkh->bkgct", q, k,
                   preferred_element_type=jnp.float32) * scale
    return _softcap(s, softcap)


def chunked_attention(q, k, v, positions, positions_k=None, *, causal=True,
                      window=None, softcap=None, q_chunk=512, scale=None):
    """Causal (optionally sliding-window) or bidirectional attention with an
    online softmax over query chunks. q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd),
    positions: (B,Sq) int32 (query positions; key positions default to the
    same -- pass positions_k for cross attention). Returns (B,Sq,H,hd)."""
    B, S, H, hd = q.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    C = pick_chunk(S, q_chunk)
    nch = S // C
    qg = q.reshape(B, S, KV, G, hd)
    if positions_k is None:
        positions_k = positions

    if not causal:
        def chunk(ci):
            qc = jax.lax.dynamic_slice_in_dim(qg, ci * C, C, axis=1)
            s = _attn_scores(qc, k, softcap, scale)        # (B,KV,G,C,Sk)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bkgct,btkh->bckgh", p.astype(v.dtype), v)

        out = jax.lax.map(jax.checkpoint(chunk), jnp.arange(nch))
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, KV, G, hd)
        return out.reshape(B, S, H, hd)

    if window is not None and window < S:
        W = min(window, S)
        Wpad = ((W + C - 1) // C) * C          # static strip length multiple of C
        T = C + Wpad

        def chunk(ci):
            qs = ci * C
            qc = jax.lax.dynamic_slice_in_dim(qg, qs, C, axis=1)
            pq = jax.lax.dynamic_slice_in_dim(positions, qs, C, axis=1)
            ks = jnp.maximum(qs - Wpad, 0)
            # static-size KV strip; left-pad region masked out below
            kc = jax.lax.dynamic_slice_in_dim(k, ks, min(T, S), axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, ks, min(T, S), axis=1)
            pk = jax.lax.dynamic_slice_in_dim(positions, ks, min(T, S), axis=1)
            s = _attn_scores(qc, kc, softcap, scale)       # (B,KV,G,C,T)
            dp = pq[:, None, None, :, None] - pk[:, None, None, None, :]
            m = (dp >= 0) & (dp < W)
            s = jnp.where(m, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bkgct,btkh->bckgh", p.astype(v.dtype), vc)

        out = jax.lax.map(jax.checkpoint(chunk), jnp.arange(nch))  # (nch,B,C,KV,G,hd)
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, KV, G, hd)
        return out.reshape(B, S, H, hd)

    # global causal: chunk queries, full keys, masked
    def chunk(ci):
        qs = ci * C
        qc = jax.lax.dynamic_slice_in_dim(qg, qs, C, axis=1)
        pq = jax.lax.dynamic_slice_in_dim(positions, qs, C, axis=1)
        s = _attn_scores(qc, k, softcap, scale)            # (B,KV,G,C,Sk)
        m = pq[:, None, None, :, None] >= positions_k[:, None, None, None, :]
        s = jnp.where(m, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgct,btkh->bckgh", p.astype(v.dtype), v)

    out = jax.lax.map(jax.checkpoint(chunk), jnp.arange(nch))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, KV, G, hd)
    return out.reshape(B, S, H, hd)


def decode_attention(q, kcache, vcache, pos, *, window=None, softcap=None,
                     scale=None):
    """Single-token attention against a cache. q: (B,1,H,hd);
    k/vcache: (B,S,KV,hd); pos: scalar/ (B,) current position (last valid).
    Windowed blocks only score the last `window` slots (O(W) not O(S))."""
    B, S, KV, hd = kcache.shape
    H = q.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, 1, KV, G, hd)
    if window is not None and window < S:
        start = jnp.clip(pos - (window - 1), 0, S - window)
        kc = jax.lax.dynamic_slice_in_dim(kcache, start, window, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vcache, start, window, axis=1)
        idx = start + jnp.arange(window)
    else:
        kc, vc = kcache, vcache
        idx = jnp.arange(S)
    s = _attn_scores(qg, kc, softcap, scale)               # (B,KV,G,1,T)
    m = idx[None, None, None, None, :] <= pos
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,btkh->bckgh", p.astype(vc.dtype), vc)
    return out.reshape(B, 1, H, hd)


def decode_attention_ring(q, kcache, vcache, pos, *, window, softcap=None,
                          scale=None):
    """Decode attention over a ring-buffer cache of `window` slots (slot j
    holds the latest position p_j = j + W*floor((pos-j)/W) <= pos; negative
    p_j means the slot hasn't been written yet)."""
    B, W, KV, hd = kcache.shape
    H = q.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, 1, KV, G, hd)
    j = jnp.arange(W)
    p_j = j + W * ((pos - j) // W)
    s = _attn_scores(qg, kcache, softcap, scale)           # (B,KV,G,1,W)
    m = (p_j >= 0) & (p_j <= pos)
    s = jnp.where(m[None, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,btkh->bckgh", p.astype(vcache.dtype), vcache)
    return out.reshape(B, 1, H, hd)


def init_attn(rng, cfg, dtype):
    r = jax.random.split(rng, 5)
    d, H, KVh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p = {
        "wq": dense_init(r[0], (d, H * hd), dtype),
        "wk": dense_init(r[1], (d, KVh * hd), dtype),
        "wv": dense_init(r[2], (d, KVh * hd), dtype),
        "wo": dense_init(r[3], (H * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KVh * hd,), dtype)
        p["bv"] = jnp.zeros((KVh * hd,), dtype)
    if cfg.qk_norm:
        p["qnorm"] = {"g": jnp.zeros((hd,), dtype)}
        p["knorm"] = {"g": jnp.zeros((hd,), dtype)}
    return p


def attn_qkv(params, x, cfg, positions, rope_base, cross_kv=None):
    B, S, d = x.shape
    H, KVh, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(B, S, H, hd)
    src = x if cross_kv is None else cross_kv
    Sk = src.shape[1]
    k = src @ params["wk"]
    v = src @ params["wv"]
    if "bk" in params:
        k, v = k + params["bk"], v + params["bv"]
    k = k.reshape(B, Sk, KVh, hd)
    v = v.reshape(B, Sk, KVh, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["qnorm"]["g"])
        k = rmsnorm(k, params["knorm"]["g"])
    if rope_base is not None and cross_kv is None:
        ap = functools.partial(apply_rope, rope_pct=cfg.rope_pct,
                               base=rope_base,
                               mrope_sections=cfg.mrope_sections)
        q, k = ap(q, positions), ap(k, positions)
    return q, k, v


# ----------------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------------

def init_mlp(rng, d, dff, kind, dtype):
    r = jax.random.split(rng, 3)
    if kind in ("geglu", "swiglu"):
        return {"wi": dense_init(r[0], (d, dff), dtype),
                "wg": dense_init(r[1], (d, dff), dtype),
                "wo": dense_init(r[2], (dff, d), dtype)}
    return {"wi": dense_init(r[0], (d, dff), dtype),
            "wo": dense_init(r[2], (dff, d), dtype)}


def mlp_forward(params, x, kind):
    if kind == "geglu":
        h = jax.nn.gelu(x @ params["wg"]) * (x @ params["wi"])
    elif kind == "swiglu":
        h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
    else:  # gelu
        h = jax.nn.gelu(x @ params["wi"])
    return h @ params["wo"]


# ----------------------------------------------------------------------------
# MoE: top-1 router + capacity dispatch (expert-parallel over `model` axis)
# ----------------------------------------------------------------------------

# Launcher-installed MoE dispatch context. Two modes:
#  * portable (mesh=None): grouped-dispatch pure-jnp path, groups = G
#    independently-capacitated dispatch groups (G=1 in unit tests),
#  * production (mesh set): explicit shard_map expert parallelism -- tokens
#    stay on their DP shard, each model rank owns E/|tp| experts, weights are
#    manually FSDP-gathered inside, outputs psum over `tp`. GSPMD's generic
#    scatter partitioning replicates token tensors (measured 2e12 B/step of
#    junk collectives on llama4-scout); the manual path makes every dispatch
#    op shard-local. EXPERIMENTS.md section Perf, iterations A2/A3.
MOE_CTX = {"groups": 1, "spec": None, "mesh": None, "dp": None,
           "tp": "model", "fsdp": None, "gather_weights": True}


def set_moe_ctx(groups=1, spec=None, mesh=None, dp=None, tp="model",
                fsdp=None, gather_weights=True):
    """gather_weights=True: FSDP just-in-time all-gather (training/prefill --
    amortized over many tokens). False: weights stay resident 2-D sharded and
    expert matmuls psum partial activations over the fsdp axes (decode --
    activations are 1 token, streaming 100s of GB of weights per step would
    dominate; EXPERIMENTS.md section Perf, iteration B2)."""
    MOE_CTX.update(groups=groups, spec=spec, mesh=mesh, dp=dp, tp=tp,
                   fsdp=fsdp, gather_weights=gather_weights)


def init_moe(rng, cfg, dff, dtype):
    r = jax.random.split(rng, 5)
    E, d = cfg.n_experts, cfg.d_model
    p = {
        "router": dense_init(r[0], (d, E), dtype, scale=0.02),
        "wi": dense_init(r[1], (E, d, dff), dtype),
        "wg": dense_init(r[2], (E, d, dff), dtype),
        "wo": dense_init(r[3], (E, dff, d), dtype),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(r[4], d, dff, "swiglu", dtype)
    return p


def moe_forward(params, x, cfg, dff):
    """Top-1 capacity-dropped MoE, GShard-style grouped dispatch but
    sort/scatter-based (no (T,E,C) one-hot einsum -> HLO FLOPs ~= useful).

    Tokens are split into G = MOE_CTX["groups"] dispatch groups, each with
    its own capacity C = ceil(T/G * cf / E). In production the launcher sets
    G = number of DP shards, so scatter/gather are shard-local and each
    device computes exactly its tokens' expert FLOPs (capacity computed
    globally would make every data rank compute *all* tokens routed to its
    experts -- a 16x redundancy we measured before grouping; EXPERIMENTS.md
    section Perf, iteration A2). Expert weights (E, d, ff) live E-sharded on
    `model` and are all-gathered over the fsdp axes at use (launch hook).
    """
    if MOE_CTX["mesh"] is not None:
        return _moe_forward_shardmap(params, x, cfg, dff)
    B, S, d = x.shape
    E = cfg.n_experts
    T = B * S
    G = MOE_CTX["groups"] if T % max(MOE_CTX["groups"], 1) == 0 else 1
    Tg = T // G
    xt = x.reshape(G, Tg, d)
    logits = (xt @ params["router"]).astype(jnp.float32)   # (G, Tg, E)
    prob = jax.nn.softmax(logits, axis=-1)
    eid = jnp.argmax(prob, axis=-1)                        # (G, Tg) top-1
    onehot = jax.nn.one_hot(eid, E, dtype=jnp.int32)       # (G, Tg, E)
    gate = jnp.sum(prob * onehot, axis=-1)                 # (G, Tg)

    C = max(1, int(math.ceil(Tg * cfg.capacity_factor / E)))
    pos_in_e = jnp.cumsum(onehot, axis=1) - 1              # (G, Tg, E)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)              # (G, Tg)
    keep = pos < C
    drop_idx = jnp.where(keep, eid, E)                     # OOB -> dropped
    posw = jnp.where(keep, pos, 0)
    gidx = jnp.broadcast_to(jnp.arange(G)[:, None], (G, Tg))
    buf = jnp.zeros((G, E, C, d), x.dtype)
    buf = buf.at[gidx, drop_idx, posw].set(xt, mode="drop")
    if MOE_CTX["spec"] is not None:
        buf = jax.lax.with_sharding_constraint(buf, MOE_CTX["spec"])
    # grouped expert FFN: g sharded over dp, e over model -> local matmuls
    h = jnp.einsum("gecd,edf->gecf", buf, params["wg"])
    h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", buf, params["wi"])
    out_e = jnp.einsum("gecf,efd->gecd", h, params["wo"])  # (G, E, C, d)
    got = out_e.at[gidx, drop_idx, posw].get(
        mode="fill", fill_value=0)                         # (G, Tg, d)
    out = got * (gate * keep).astype(x.dtype)[..., None]
    if "shared" in params:
        out = out + mlp_forward(params["shared"], xt, "swiglu")
    # router aux loss (load balance), returned for the trainer
    me = jnp.mean(prob, axis=(0, 1))
    ce = jnp.mean(onehot.astype(jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(me * ce)
    return out.reshape(B, S, d), aux


def _moe_forward_shardmap(params, x, cfg, dff):
    """Explicit-EP MoE: shard_map over the full mesh; see MOE_CTX docs."""

    mesh = MOE_CTX["mesh"]
    tp = MOE_CTX["tp"]
    dp = MOE_CTX["dp"]
    fsdp = MOE_CTX["fsdp"]
    dp_axes = (dp,) if isinstance(dp, str) else tuple(dp or ())
    fsdp_axes = (fsdp,) if isinstance(fsdp, str) else tuple(fsdp or ())
    B, S, d = x.shape
    E = cfg.n_experts
    ntp = mesh.shape[tp]
    E_loc = E // ntp
    assert E % ntp == 0, (E, ntp)

    gather_w = MOE_CTX["gather_weights"] or not fsdp_axes
    nfs = 1
    for a in fsdp_axes:
        nfs *= mesh.shape[a]

    def body(wi, wg, wo, router, xl):
        # wi/wg/wo: (E_loc, d/|fsdp|, ff) etc.; xl: (B_loc, S, d)
        if fsdp_axes and gather_w:
            wi = jax.lax.all_gather(wi, fsdp_axes, axis=1, tiled=True)
            wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, fsdp_axes, axis=1, tiled=True)
        if fsdp_axes:
            router = jax.lax.all_gather(router, fsdp_axes, axis=0, tiled=True)
        Bl, Sl, _ = xl.shape
        if not gather_w and fsdp_axes:
            # resident weights: the fsdp axes slice the contraction dims, so
            # every fsdp rank must see the SAME tokens before partial-summing
            # -- gather the (decode-tiny) token batch instead of the weights
            xl = jax.lax.all_gather(xl, fsdp_axes, axis=0, tiled=True)
            Bl = xl.shape[0]
        T = Bl * Sl
        xt = xl.reshape(T, d)
        logits = (xt @ router).astype(jnp.float32)          # (T, E)
        prob = jax.nn.softmax(logits, axis=-1)
        eid = jnp.argmax(prob, axis=-1)
        onehot = jax.nn.one_hot(eid, E, dtype=jnp.int32)
        gate = jnp.sum(prob * onehot, axis=-1)
        C = max(1, int(math.ceil(T * cfg.capacity_factor / E)))
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
        keep = pos < C
        rank = jax.lax.axis_index(tp)
        base = rank * E_loc
        mine = (eid >= base) & (eid < base + E_loc) & keep
        lid = jnp.where(mine, eid - base, E_loc)            # OOB -> dropped
        posw = jnp.where(mine, pos, 0)
        buf = jnp.zeros((E_loc, C, d), xl.dtype)
        buf = buf.at[lid, posw].set(xt, mode="drop")        # fully local
        if gather_w:
            h = jnp.einsum("ecd,edf->ecf", buf, wg)
            h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, wi)
            out_e = jnp.einsum("ecf,efd->ecd", h, wo)
        else:
            # resident 2-D weights: contract the local d/ff slice, psum the
            # (tiny, decode-sized) partial activations over fsdp
            fr = jnp.zeros((), jnp.int32)
            for a in fsdp_axes:
                fr = fr * mesh.shape[a] + jax.lax.axis_index(a)
            d_loc = wi.shape[1]
            buf_d = jax.lax.dynamic_slice_in_dim(buf, fr * d_loc, d_loc,
                                                 axis=2)
            h = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf_d, wg),
                             fsdp_axes)
            h = jax.nn.silu(h) * jax.lax.psum(
                jnp.einsum("ecd,edf->ecf", buf_d, wi), fsdp_axes)
            ff_loc = wo.shape[1]
            h_f = jax.lax.dynamic_slice_in_dim(h, fr * ff_loc, ff_loc,
                                               axis=2)
            out_e = jnp.einsum("ecf,efd->ecd", h_f, wo)     # partial over ff
        got = out_e.at[lid, posw].get(mode="fill", fill_value=0)
        out = got * (gate * mine).astype(xl.dtype)[:, None]
        # combine experts (+ fsdp partials in resident mode)
        out = jax.lax.psum(out, (tp,) + (() if gather_w else fsdp_axes))
        if not gather_w and fsdp_axes:
            # take back this shard's slice of the gathered batch
            fr2 = jnp.zeros((), jnp.int32)
            for a in fsdp_axes:
                fr2 = fr2 * mesh.shape[a] + jax.lax.axis_index(a)
            Bl_own = Bl // nfs
            out = jax.lax.dynamic_slice_in_dim(
                out.reshape(Bl, Sl, d), fr2 * Bl_own, Bl_own, axis=0)
        else:
            out = out.reshape(Bl, Sl, d)
        me = jnp.mean(prob, axis=0)
        ce = jnp.mean(onehot.astype(jnp.float32), axis=0)
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp_axes + (tp,)) if dp_axes else aux
        return out, aux

    P_ = P
    w_spec = P_(tp, fsdp, None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(w_spec, w_spec, w_spec, P_(fsdp, None),
                  P_(dp, None, None)),
        out_specs=(P_(dp, None, None), P_()),
        check_vma=False,
    )(params["wi"], params["wg"], params["wo"], params["router"], x)
    if "shared" in params:   # shared expert: plain TP outside the shard_map
        out = out + mlp_forward(params["shared"],
                                x.reshape(B * S, d), "swiglu").reshape(B, S, d)
    return out, aux


# ----------------------------------------------------------------------------
# embeddings / head
# ----------------------------------------------------------------------------

def init_embed(rng, cfg, dtype):
    p = {"tok": dense_init(rng, (cfg.vocab, cfg.d_model), dtype, scale=0.02)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(jax.random.fold_in(rng, 1),
                               (cfg.d_model, cfg.vocab), dtype, scale=0.02)
    return p


def embed_tokens(params, tokens, cfg):
    x = jnp.take(params["tok"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(params, x, cfg):
    w = params["tok"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    return _softcap(logits, cfg.final_softcap)
