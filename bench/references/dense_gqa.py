"""Plain reference: a dense GQA transformer, its linears digital or CIM.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, with no
kernel, cache or batching: pre-norm blocks (RMSNorm, rotary attention with
grouped key/value heads, SwiGLU), final RMSNorm and a head tied to the
embedding. The configuration's ``serving.cim_mode`` says how a block's
linears run:

* ``off``: a plain matrix product, as the configuration states it;
* ``sim``: the macro's arithmetic as the paper states it, without its
  readout noise. The activation row is quantized to ``in`` bits against a
  reference of ``act_clip_sigmas`` times its RMS, round to nearest,
  clipped to +-(2^(in-1) - 1); the weight matrix to ``w`` bits, symmetric,
  against its largest magnitude; the integer dot product is exact, and the
  result is scaled back. (The served program draws the noise in its kernel
  and fits the activation reference once per call over the whole batch:
  no reference that runs one request at a time computes that, which is
  why no benchmark cell serves ``sim`` yet.)

``low=True`` computes the same model with every tensor that the
configuration keeps in bfloat16 (weights, activations, attention
probabilities) rounded to float8 (e4m3): the control that the comparison
must tell apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QBLOCK = 512


def _lo(x, low: bool):
    if not low:
        return x
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _macro(x, w, bits, clip):
    """x (T, K) float32, w (K, N) float32 -> (T, N): the noise-free macro."""
    qx = 2 ** (bits[0] - 1) - 1
    qw = 2 ** (bits[1] - 1) - 1
    xs = clip * jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-16) / qx
    xs = jnp.maximum(xs, 1e-12)
    xq = jnp.clip(jnp.round(x / xs), -qx, qx)
    ws = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8) / qw
    wq = jnp.clip(jnp.round(w / ws), -qw, qw)
    return (xq @ wq) * xs * ws


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, low):
    """Causal GQA over query blocks: q (T, H, D), k/v (T, KV, D)."""
    t, h, d = q.shape
    kv = k.shape[1]
    g = h // kv
    qb = min(QBLOCK, t)
    nb = t // qb
    qs = q.reshape(nb, qb, kv, g, d)
    kpos = jnp.arange(t)

    def block(args):
        i, qblk = args
        s = jnp.einsum("qkgd,tkd->kgqt", qblk, k) / jnp.sqrt(jnp.float32(d))
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = _lo(jax.nn.softmax(s, -1), low)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (jnp.arange(nb), qs))
    return out.reshape(t, h * d)


@functools.partial(jax.jit, static_argnames=("dims", "low"))
def _layer(h, blocks, layer, dims, low):
    (n_heads, n_kv, hd, eps, theta, clip, attn_bits, mlp_bits) = dims
    at = lambda x: jax.lax.dynamic_index_in_dim(x, layer, 0, False).astype(
        jnp.float32)
    a, m = blocks["attn"], blocks["mlp"]
    t = h.shape[0]
    pos = jnp.arange(t)
    x = _lo(_rmsnorm(h, at(blocks["n1"]["g"]), eps), low)

    def proj(p, bits, x):
        w = _lo(at(p["w"]), low)
        y = x @ w if bits is None else _macro(x, w, bits, clip)
        if "b" in p:
            y = y + at(p["b"])
        return _lo(y, low)

    q = proj(a["q"], attn_bits, x).reshape(t, n_heads, hd)
    k = proj(a["k"], attn_bits, x).reshape(t, n_kv, hd)
    v = proj(a["v"], attn_bits, x).reshape(t, n_kv, hd)
    q = _lo(_rope(q, pos, theta), low)
    k = _lo(_rope(k, pos, theta), low)
    o = _lo(_attention(q, k, v, low), low)
    h = _lo(h + proj(a["o"], attn_bits, o), low)
    x = _lo(_rmsnorm(h, at(blocks["n2"]["g"]), eps), low)
    gate = proj(m["gate"], mlp_bits, x)
    up = proj(m["up"], mlp_bits, x)
    act = _lo(jax.nn.silu(gate) * up, low)
    return _lo(h + proj(m["down"], mlp_bits, act), low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(h, g, emb, eps, low):
    x = _lo(_rmsnorm(h, g.astype(jnp.float32), eps), low)
    return x @ _lo(emb.astype(jnp.float32), low).T


def _bucket(t: int) -> int:
    b = 256
    while b < t:
        b *= 2
    return b


def logits(weights, cfg: dict, tokens: np.ndarray, first: int,
           low: bool = False) -> np.ndarray:
    """Logits (float32) of positions ``first .. len(tokens)-1`` of one
    sequence. The sequence is right-padded to a power-of-two bucket; causal
    attention and per-token activation references keep the pad out of
    every real position."""
    serving = cfg["serving"]
    bits = {"attn": None, "mlp": None}
    if serving["cim_mode"] == "sim":
        bits = {c: (b["in"], b["w"])
                for c, b in serving["macro_bits"].items()}
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]), float(serving["act_clip_sigmas"]),
            bits["attn"], bits["mlp"])
    t = len(tokens)
    tb = _bucket(t)
    ids = np.zeros(tb, np.int32)
    ids[:t] = tokens
    with jax.default_matmul_precision("highest"):
        emb = weights["embed"]["e"]
        h = _lo(jnp.take(emb, jnp.asarray(ids), 0).astype(jnp.float32), low)
        for layer in range(cfg["num_hidden_layers"]):
            h = _layer(h, weights["blocks"], layer, dims, low)
        n = t - first
        nb = min(_bucket(n), tb)
        start = min(first, tb - nb)
        rows = jax.lax.dynamic_slice_in_dim(h, jnp.int32(start), nb, 0)
        out = _head(rows, weights["final_norm"]["g"], emb,
                    float(cfg["rms_norm_eps"]), low)
        return np.asarray(out)[first - start:first - start + n]
