"""Plain reference of a llama-style decoder, float32, in ``jax.numpy``.

Imports nothing of the program. It follows the published description of
the architecture (Llama as in Hugging Face's ``LlamaForCausalLM``, which
granite-8b-code-base uses): token embedding; per layer RMSNorm, q/k/v
projections, rotary embedding on q and k (the "rotate half" pairing of
dimension i with i + d/2), grouped-query causal attention scaled by
``head_dim ** -0.5``, output projection, residual; RMSNorm, SwiGLU
feed-forward ``(silu(x W_g) * x W_i) W_o``, residual; final RMSNorm and
the unembedding (the embedding's transpose where the configuration ties
them). Projections have no biases; the configuration file says so.

The weights are the ones the benchmark drew (``weights.py``), read by the
names of the program's parameter tree, upcast to float32 one layer at a
time. Every matmul runs at ``highest`` precision. Sequences run one at a
time, padded to a multiple of ``BUCKET`` tokens at the end (causality
keeps the padding out of every position that is read), attention in
blocks of ``Q_BLOCK`` queries, so that the chip holds it beside the
weights.

``quant="fp8"`` is the control: every projection and the unembedding with
float8 (e4m3) weights, scaled per output column, and float8 activations,
scaled per token: the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BUCKET = 512
Q_BLOCK = 512
HEAD_ROWS = 256


def _fp8(x, axis):
    """Round to float8 e4m3 after scaling the largest magnitude along
    ``axis`` to the format's largest finite value, 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (S, H, hd); the rotate-half pairing."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@partial(jax.jit, static_argnames=("dims", "quant"))
def layer(x, p, dims, quant):
    """One decoder layer over one sequence ``x`` (S, D), float32."""
    n_heads, n_kv, hd, eps, theta = dims
    s = x.shape[0]
    pos = jnp.arange(s)
    h = rmsnorm(x, p["pre_norm"], eps)
    q = rope(linear(h, p["wq"], quant).reshape(s, n_heads, hd), pos, theta)
    k = rope(linear(h, p["wk"], quant).reshape(s, n_kv, hd), pos, theta)
    v = linear(h, p["wv"], quant).reshape(s, n_kv, hd)
    rep = n_heads // n_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)

    def block(args):
        qb, q0 = args
        logits = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        qp = (q0 + jnp.arange(Q_BLOCK))[:, None]
        logits = jnp.where(qp >= pos[None, :], logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HI)

    nb = s // Q_BLOCK
    att = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, n_heads, hd),
                              jnp.arange(nb) * Q_BLOCK))
    att = att.reshape(s, n_heads * hd)
    x = x + linear(att, p["wo"], quant)
    h = rmsnorm(x, p["mlp_norm"], eps)
    ff = jax.nn.silu(linear(h, p["wg"], quant)) * linear(h, p["wi"], quant)
    return x + linear(ff, p["w2"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, norm_w, w, eps, quant):
    return linear(rmsnorm(x, norm_w, eps), w, quant)


def _layer_weights(params, i):
    b = params["blocks"]["layer0"]
    f32 = lambda a: jnp.asarray(a[i], jnp.float32)  # noqa: E731
    return {"pre_norm": f32(b["pre_norm"]["w"]),
            "wq": f32(b["mixer"]["wq"]), "wk": f32(b["mixer"]["wk"]),
            "wv": f32(b["mixer"]["wv"]), "wo": f32(b["mixer"]["wo"]),
            "mlp_norm": f32(b["mlp_norm"]["w"]),
            "wi": f32(b["mlp"]["wi"]), "wg": f32(b["mlp"]["wg"]),
            "w2": f32(b["mlp"]["wo"])}


def logits_at(params, model: dict, seqs, positions, quants=(None,)):
    """Logits of each sequence at its listed positions.

    ``seqs``: token-id arrays; ``positions``: per sequence, the positions
    whose next-token logits are wanted; ``model``: the configuration file's
    numbers. Returns, per entry of ``quants``, a list of (n_pos, V) float32
    numpy arrays, one per sequence. The layers run once per precision."""
    dims = (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], float(model["rms_norm_eps"]),
            float(model["rope_theta"]))
    n_layers = model["num_hidden_layers"]
    emb = params["embed"]
    out = []
    for quant in quants:
        xs = []
        for t in seqs:
            pad = -len(t) % BUCKET
            ids = jnp.asarray(np.concatenate([t, np.zeros(pad, t.dtype)]))
            xs.append(jnp.asarray(emb[ids], jnp.float32))
        for i in range(n_layers):
            p = _layer_weights(params, i)
            xs = [layer(x, p, dims, quant) for x in xs]
            del p
        norm_w = jnp.asarray(params["final_norm"]["w"], jnp.float32)
        w = jnp.asarray(emb.T if model["tie_word_embeddings"]
                        else params["head"], jnp.float32)
        res = []
        for x, pos in zip(xs, positions):
            n = len(pos)            # padded so that few shapes compile
            pos = np.concatenate([pos, np.zeros(-n % HEAD_ROWS, pos.dtype)])
            sel = x[jnp.asarray(pos)]
            res.append(np.asarray(head(sel, norm_w, w, dims[3], quant))[:n])
        del w, xs
        out.append(res)
    return out
