"""Shared layers: param-def machinery, RMSNorm, RoPE variants, GQA attention
(full / sliding-window / softcapped; einsum and memory-chunked paths; KV-cache
decode), and gated MLPs with the optional pSRAM (photonic-offload) projection
path.

Param-def pattern: every block exposes ``defs(cfg)`` returning a pytree of
``{"shape": ..., "axes": (logical names...)}`` leaves. ``init_params`` builds
arrays from defs; ``specs_of`` extracts the logical-spec pytree consumed by
dist.sharding; ``stack_defs`` adds the scanned-layers leading axis.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.photonic_layer import maybe_psram_matmul
from repro.dist.sharding import hint
from .config import ArchConfig


# ---------------------------------------------------------------------------
# param defs
# ---------------------------------------------------------------------------

def ddef(shape, axes, init="normal", scale=None, dtype=None):
    assert len(shape) == len(axes)
    return {"shape": tuple(shape), "axes": tuple(axes), "init": init,
            "scale": scale, "dtype": dtype}


def _is_def(x):
    return isinstance(x, dict) and set(x) == {"shape", "axes", "init", "scale", "dtype"}


def wdef(cfg, shape, axes):
    """Projection-weight def: int8 words + per-column scale when the pSRAM
    stored-weight path is on (weights stationary in the array), else a plain
    dense def."""
    if cfg.psram_projections and cfg.psram_stored_int8:
        scale_shape = (1,) * (len(shape) - 1) + (shape[-1],)
        scale_axes = (None,) * (len(shape) - 1) + (axes[-1],)
        return {
            "q": ddef(shape, axes, init="qnormal", dtype="int8"),
            "scale": ddef(scale_shape, scale_axes, init="qscale", dtype="float32"),
        }
    return ddef(shape, axes)


def is_quantized(w) -> bool:
    return isinstance(w, dict) and set(w) == {"q", "scale"} and not _is_def(w)


def stack_defs(defs, n: int):
    return jax.tree.map(
        lambda d: {**d, "shape": (n, *d["shape"]), "axes": ("layers", *d["axes"])},
        defs,
        is_leaf=_is_def,
    )


def specs_of(defs):
    return jax.tree.map(lambda d: d["axes"], defs, is_leaf=_is_def)


def shapes_of(defs, dtype):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d["shape"], jnp.dtype(d["dtype"] or dtype)),
        defs, is_leaf=_is_def,
    )


@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal_leaf(key, shape, scale, dtype):
    # one jitted draw per leaf: the f32 normals fuse into the cast, so a
    # bf16 leaf never has a full-size f32 twin on the device (eagerly, a
    # layer-stacked MLP leaf of an 8B model would double the peak)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


@partial(jax.jit, static_argnames=("shape",))
def _qnormal_leaf(key, shape):
    from repro.core.quantization import quantize_symmetric

    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    w = jax.random.normal(key, shape) / math.sqrt(fan_in)
    q, _ = quantize_symmetric(w, axis=tuple(range(len(shape) - 1)))
    return q


def init_params(key, defs, dtype=jnp.float32):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))

    def one(k, d):
        dt = jnp.dtype(d["dtype"] or dtype)
        if d["init"] == "zeros":
            return jnp.zeros(d["shape"], dt)
        if d["init"] == "ones":
            return jnp.ones(d["shape"], dt)
        if d["init"] == "qnormal":  # pre-programmed array words
            return _qnormal_leaf(k, d["shape"])
        if d["init"] == "qscale":
            # matches qnormal: scale ~= max|w| / 127 per output column
            fan_in = d["shape"][-1]
            return jnp.full(d["shape"], 4.0 / math.sqrt(max(fan_in, 2)) / 127.0, dt)
        fan_in = d["shape"][-2] if len(d["shape"]) >= 2 else d["shape"][-1]
        scale = d["scale"] if d["scale"] is not None else 1.0 / math.sqrt(fan_in)
        return _normal_leaf(k, d["shape"], float(scale), dt)

    return jax.tree.unflatten(treedef, [one(k, d) for k, d in zip(keys, leaves)])


# ---------------------------------------------------------------------------
# norm
# ---------------------------------------------------------------------------

def rmsnorm_defs(d):
    return {"w": ddef((d,), ("embed",), init="ones")}


def rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["w"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _rot_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x, pos, cfg: ArchConfig):
    """x: (B, S, H, hd); pos: (B, S) int32, or (3, B, S) for M-RoPE."""
    if cfg.rope == "none":
        return x
    hd = x.shape[-1]
    rot = int(hd * cfg.rope_partial_frac) if cfg.rope == "partial" else hd
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    inv = cfg.rope_theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)  # (rot/2,)
    if cfg.rope == "mrope":
        # sections split the frequency axis across t/h/w position streams
        sec = jnp.cumsum(jnp.array((0,) + tuple(cfg.mrope_sections)))
        freq_idx = jnp.arange(rot // 2)
        stream = jnp.searchsorted(sec[1:], freq_idx, side="right")  # (rot/2,) in {0,1,2}
        # angles[b, s, i] = pos[stream[i], b, s] * inv[i]
        angles = jnp.einsum("tbs,t i->bsi",
                            pos.astype(jnp.float32),
                            jax.nn.one_hot(stream, 3, dtype=jnp.float32).T * inv[None, :])
    else:
        angles = pos.astype(jnp.float32)[..., None] * inv  # (B, S, rot/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.concatenate([cos, cos], axis=-1).astype(x.dtype)  # (B, S, 1, rot)
    sin = jnp.concatenate([sin, sin], axis=-1).astype(x.dtype)
    y = x_rot * cos + _rot_half(x_rot) * sin
    return jnp.concatenate([y, x_pass], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention_defs(cfg: ArchConfig):
    d = cfg.d_model
    return {
        "wq": wdef(cfg, (d, cfg.q_dim), ("embed", "qdim")),
        "wk": wdef(cfg, (d, cfg.kv_dim), ("embed", "kvdim")),
        "wv": wdef(cfg, (d, cfg.kv_dim), ("embed", "kvdim")),
        "wo": wdef(cfg, (cfg.q_dim, d), ("qdim", "embed")),
    }


def _proj(x, w, cfg: ArchConfig):
    if is_quantized(w):  # stored-int8 array words (weights stationary)
        from repro.core.photonic_layer import psram_linear
        return psram_linear(x, w, adc_bits=cfg.adc_bits).astype(x.dtype)
    return maybe_psram_matmul(x, w, cfg.psram_projections, cfg.adc_bits)


def _mask_bias(q_pos, k_pos, causal, window):
    """(..., Sq, Sk) additive bias from position grids."""
    ok = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        ok &= q_pos >= k_pos
    if window:
        ok &= (q_pos - k_pos) < window
    return jnp.where(ok, 0.0, -1e30)


def _sdpa(q, k, v, bias, cfg: ArchConfig):
    """Grouped-query attention core. q:(B,Sq,H,hd) k/v:(B,Sk,Hkv,hd)."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5
    qg = q.reshape(b, sq, hkv, rep, hd)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, k).astype(jnp.float32) * scale
    if cfg.attn_softcap > 0:
        logits = jnp.tanh(logits / cfg.attn_softcap) * cfg.attn_softcap
    logits = logits + bias  # bias broadcasts over (b, hkv, rep)
    if cfg.attn_probs_bf16:
        # flash-style: f32 max/sum statistics, bf16 weights
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        e = jnp.exp((logits - m)).astype(jnp.bfloat16)
        p = e / jnp.maximum(jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True), 1e-30).astype(jnp.bfloat16)
    else:
        p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrqs,bskd->bqkrd", p.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd)


def _sdpa_chunked(q, k, v, cfg: ArchConfig, causal, window, q0: int = 0):
    """Memory-bounded attention: scan over q chunks (exact softmax)."""
    b, s, h, hd = q.shape
    cq = min(cfg.attn_chunk, s)
    assert s % cq == 0
    n = s // cq
    k_pos = jnp.arange(k.shape[1])[None, :]

    def step(_, qc_i):
        qc, i = qc_i
        q_pos = (q0 + i * cq + jnp.arange(cq))[:, None]
        bias = _mask_bias(q_pos, k_pos, causal, window)  # (cq, Sk)
        return None, _sdpa(qc, k, v, bias, cfg)

    qs = q.reshape(b, n, cq, h, hd).transpose(1, 0, 2, 3, 4)
    _, out = jax.lax.scan(step, None, (qs, jnp.arange(n)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def attention_fwd(
    p, x, cfg: ArchConfig, pos, *, layer_local: bool = False,
    kv_override=None, causal: bool = True,
):
    """Full-sequence attention (train / prefill). Returns (y, (k, v))."""
    b, s, d = x.shape
    q = _proj(x, p["wq"], cfg).reshape(b, s, cfg.n_heads, cfg.head_dim)
    if kv_override is None:
        k = _proj(x, p["wk"], cfg).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = _proj(x, p["wv"], cfg).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        rope_pos = pos
        k = apply_rope(k, rope_pos, cfg)
    else:  # cross attention: kv precomputed from the encoder
        k, v = kv_override
    q = apply_rope(q, pos, cfg)
    q, k, v = (hint(t, ("batch", "seq", "kv_heads" if t is not q else "heads", None))
               for t in (q, k, v))
    window = cfg.sliding_window if layer_local else 0
    if cfg.attention_impl == "chunked" and s > cfg.attn_chunk:
        out = _sdpa_chunked(q, k, v, cfg, causal, window)
    else:
        qp = jnp.arange(s)[:, None]
        kp = jnp.arange(k.shape[1])[None, :]
        bias = _mask_bias(qp, kp, causal, window)
        out = _sdpa(q, k, v, bias, cfg)
    out = hint(out, ("batch", "seq", "heads", None))
    y = _proj(out.reshape(b, s, cfg.q_dim), p["wo"], cfg)
    return y, (k, v)


def _new_kv(p, x, cfg: ArchConfig, cache_pos):
    """Project + rope the decode token's q/k/v (shared by both decode paths).

    ``cache_pos`` is a scalar (whole batch at one position) or a ``(b,)``
    vector (continuous batching: every row decodes at its own length).
    """
    b = x.shape[0]
    q = _proj(x, p["wq"], cfg).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    pos = jnp.broadcast_to(
        jnp.asarray(cache_pos, jnp.int32).reshape(-1, 1), (b, 1))
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, b, 1))
    q = apply_rope(q, pos, cfg)
    kn = _proj(x, p["wk"], cfg).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    kn = apply_rope(kn, pos, cfg)
    vn = _proj(x, p["wv"], cfg).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    return kn, vn, q


def attention_decode_append(
    p, x, cfg: ArchConfig, k_old, v_old, cache_pos, *, layer_local: bool = False,
    precomputed=None,
):
    """Decode against a *stale* cache slice plus the explicit new token.

    k_old/v_old hold positions < cache_pos (position cache_pos may be stale);
    the new token's kn/vn enter via a logit-level concat (tiny) instead of a
    KV-level concat/update (full-cache copy). This lets the caller dynamic-
    slice the carried cache BEFORE the in-place dynamic-update-slice, the
    read-then-write order XLA aliases without copying.
    """
    b = x.shape[0]
    kn, vn, q = precomputed if precomputed is not None else _new_kv(p, x, cfg, cache_pos)
    s_k = k_old.shape[1]
    hkv, rep = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    hd = cfg.head_dim
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5
    qg = q.reshape(b, 1, hkv, rep, hd)
    lg_h = jnp.einsum("bqkrd,bskd->bkrqs", qg, k_old).astype(jnp.float32) * scale
    lg_n = jnp.einsum("bqkrd,bskd->bkrqs", qg, kn).astype(jnp.float32) * scale
    if cfg.attn_softcap > 0:
        lg_h = jnp.tanh(lg_h / cfg.attn_softcap) * cfg.attn_softcap
        lg_n = jnp.tanh(lg_n / cfg.attn_softcap) * cfg.attn_softcap
    k_pos = jnp.arange(s_k)[None, :]
    # cache_pos: scalar -> (1, 1); per-row -> (b, 1). Strict: slot
    # cache_pos is stale in k_old either way.
    cp = jnp.asarray(cache_pos, jnp.int32).reshape(-1, 1)
    valid = k_pos < cp
    if layer_local and cfg.sliding_window:
        valid &= (cp - k_pos) < cfg.sliding_window
    lg_h = lg_h + jnp.where(valid[:, None, None, None, :], 0.0, -1e30)
    # flash-style two-block combine — concatenating the history logits with
    # the new token's (S -> S+1) breaks the seq sharding and makes GSPMD
    # fully rematerialize V (measured: +0.8s collective on dbrx decode)
    m_h = jnp.max(lg_h, axis=-1, keepdims=True)
    e_h = jnp.exp(lg_h - m_h)
    s_h = jnp.sum(e_h, axis=-1, keepdims=True)
    o_h = jnp.einsum("bkrqs,bskd->bqkrd", e_h.astype(v_old.dtype), v_old)
    m = jnp.maximum(m_h, lg_n)
    alpha = jnp.exp(m_h - m)                              # (b,kv,rep,1,1)
    beta = jnp.exp(lg_n - m)
    aw = jnp.transpose(alpha, (0, 3, 1, 2, 4))            # -> (b,1,kv,rep,1)
    bw = jnp.transpose(beta, (0, 3, 1, 2, 4))
    denom = s_h * alpha + beta
    dw = jnp.transpose(denom, (0, 3, 1, 2, 4))
    out = (o_h * aw + bw * vn[:, :, :, None, :].astype(o_h.dtype)) / dw
    y = _proj(out.reshape(b, 1, cfg.q_dim).astype(x.dtype), p["wo"], cfg)
    return y


def attention_decode(
    p, x, cfg: ArchConfig, cache, cache_pos, *, layer_local: bool = False,
    cross: bool = False, precomputed_q=None, skip_kv_write: bool = False,
):
    """One-token decode against a (B, S, Hkv, hd) KV cache.

    cache: {"k": ..., "v": ...}; cache_pos: scalar int32 — write position.
    For cross attention the cache is the (static) encoder KV; no write.
    Returns (y, new_cache).
    """
    b, one, d = x.shape
    if cross:
        q = _proj(x, p["wq"], cfg).reshape(b, 1, cfg.n_heads, cfg.head_dim)
        # cross attention is non-rotary (matches encdec forward)
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        if precomputed_q is not None:
            q = precomputed_q
            kn = vn = None
        else:
            kn, vn, q = _new_kv(p, x, cfg, cache_pos)
        if skip_kv_write:  # caller already wrote the token into the cache
            k, v = cache["k"], cache["v"]
            new_cache = cache
        else:
            k = jax.lax.dynamic_update_slice(
                cache["k"], kn.astype(cache["k"].dtype), (0, cache_pos, 0, 0))
            v = jax.lax.dynamic_update_slice(
                cache["v"], vn.astype(cache["v"].dtype), (0, cache_pos, 0, 0))
            new_cache = {"k": k, "v": v}
    s_k = k.shape[1]
    k_pos = jnp.arange(s_k)[None, :]
    valid = k_pos <= cache_pos if not cross else jnp.ones_like(k_pos, bool)
    if layer_local and cfg.sliding_window:
        valid &= (cache_pos - k_pos) < cfg.sliding_window
    bias = jnp.where(valid, 0.0, -1e30)  # (1, Sk) broadcast
    k = hint(k, ("batch", "seq_kv", "kv_heads", None))
    v = hint(v, ("batch", "seq_kv", "kv_heads", None))
    out = _sdpa(q, k, v, bias, cfg)
    y = _proj(out.reshape(b, 1, cfg.q_dim), p["wo"], cfg)
    return y, new_cache


def attention_cache_defs(cfg: ArchConfig, batch: int, seq: int):
    shape = (batch, seq, cfg.n_kv_heads, cfg.head_dim)
    axes = ("batch", "seq_kv", "kv_heads", None)
    return {"k": ddef(shape, axes, init="zeros"), "v": ddef(shape, axes, init="zeros")}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ArchConfig, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {
            "wi": wdef(cfg, (d, ff), ("embed", "ff")),
            "wg": wdef(cfg, (d, ff), ("embed", "ff")),
            "wo": wdef(cfg, (ff, d), ("ff", "embed")),
        }
    return {"wi": wdef(cfg, (d, ff), ("embed", "ff")),
            "wo": wdef(cfg, (ff, d), ("ff", "embed"))}


def mlp_fwd(p, x, cfg: ArchConfig):
    h = _proj(x, p["wi"], cfg)
    if cfg.act == "swiglu":
        h = jax.nn.silu(_proj(x, p["wg"], cfg)) * h
    elif cfg.act == "geglu":
        h = jax.nn.gelu(_proj(x, p["wg"], cfg)) * h
    else:
        h = jax.nn.gelu(h)
    h = hint(h, ("batch", "seq", "ff"))
    return _proj(h, p["wo"], cfg)
