"""Plain reference: DeepSeek-V2's MLA + MoE decoder, one device's share of
its experts.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, with no
kernel, cache or batching, written from the published equations
(arXiv:2405.04434 §2, the ``deepseek_v2`` model code's config keys):

* pre-norm blocks (RMSNorm), then the final RMSNorm and an untied head;
* attention, MLA: the query ``q = W_q x`` (``q_lora_rank`` null) or
  ``W_uq RMSNorm(W_dq x)``, split per head into ``qk_nope_head_dim``
  channels and ``qk_rope_head_dim`` rotary ones; the latent
  ``c = RMSNorm(W_dkv x)`` of ``kv_lora_rank`` channels with one shared
  rotary key; per-head keys ``[W_uk c, k_rope]`` and values ``W_uv c``;
  causal softmax of ``q·k`` times the softmax scale; the output projection;
* rotary embedding with YaRN (``rope_scaling``): frequencies blended
  between the original and the ``factor``-interpolated ones over the ramp
  from ``floor(c(beta_fast))`` to ``ceil(c(beta_slow))`` pairs, with
  ``c(r) = d·ln(L/(2πr)) / (2 ln θ)``, cos/sin times
  ``mscale(mscale)/mscale(mscale_all_dim)``, and the softmax scale
  ``(nope+rope)^-1/2 · mscale(mscale_all_dim)^2``, where
  ``mscale(m) = 0.1·m·ln(factor) + 1``;
* the first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``; the others: a softmax router over all
  ``n_routed_experts_published`` experts keeping each token's top
  ``num_experts_per_tok`` gates (renormalised only if ``norm_topk_prob``,
  times ``routed_scaling_factor``), the part of the routed sum that the
  held experts give (``n_routed_experts`` of them from
  ``held_first_expert``: the same share the program holds, each computed
  densely over every token and weighted by its gate, zero where the token
  did not pick it), plus the shared experts, one SwiGLU of
  ``n_shared_experts · moe_intermediate_size``.

Departures from the published model: rotary pairs are the two halves of
the rotary channels (rotate-half), where the published code pairs
interleaved channels; on weights drawn at random that is a fixed
permutation of the rotary channels of ``W_q`` and ``W_dkv``. The other
devices' experts are left out, in the program and here alike (the
deployment the configuration states).

``low=True`` computes the same model with every tensor that the
configuration keeps in bfloat16 (weights, activations, attention
probabilities) rounded to float8 (e4m3): the control that the comparison
must tell apart.
"""

from __future__ import annotations

import functools
import math

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


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(cfg: dict):
    """(inverse frequencies, cos/sin factor, softmax scale) of the rotary
    channels, from the configuration's published keys."""
    d = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    freqs = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5
    sc = cfg.get("rope_scaling")
    if not sc:
        return freqs, 1.0, scale
    f = float(sc["factor"])

    def corr(rot):
        return (d * math.log(sc["original_max_position_embeddings"]
                             / (rot * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(corr(sc["beta_fast"])), 0)
    high = min(math.ceil(corr(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    freqs = freqs / f * (1 - keep) + freqs * keep
    cs = _mscale(f, sc["mscale"]) / _mscale(f, sc["mscale_all_dim"])
    if sc["mscale_all_dim"]:
        scale *= _mscale(f, sc["mscale_all_dim"]) ** 2
    return freqs, cs, scale


def _rope(x, pos, freqs, cs):
    """x (T, ..., R): rotate-half pairs at positions ``pos``."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, scale, low):
    """Causal attention over query blocks: q, k (T, H, Dk); v (T, H, Dv)."""
    t, h, dk = q.shape
    qb = min(QBLOCK, t)
    nb = t // qb
    qs = q.reshape(nb, qb, h, dk)
    kpos = jnp.arange(t)

    def block(args):
        i, qblk = args
        s = jnp.einsum("qhd,thd->hqt", qblk, k) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = _lo(jax.nn.softmax(s, -1), low)
        return jnp.einsum("hqt,thd->qhd", p, v)

    out = jax.lax.map(block, (jnp.arange(nb), qs))
    return out.reshape(t, -1)


def _swiglu(p, x, at, low):
    gate = x @ _lo(at(p["gate"]["w"]), low)
    up = x @ _lo(at(p["up"]["w"]), low)
    act = _lo(jax.nn.silu(_lo(gate, low)) * _lo(up, low), low)
    return _lo(act @ _lo(at(p["down"]["w"]), low), low)


def _mla(a, x, at, dims, low):
    (h, nope, rope, vd, lora, eps, freqs, cs, scale) = dims
    t = x.shape[0]
    pos = jnp.arange(t)
    w = lambda name: _lo(at(a[name]["w"]), low)
    if "q" in a:
        q = x @ w("q")
    else:
        cq = _lo(_rmsnorm(x @ w("dq"), at(a["q_norm"]["g"]), eps), low)
        q = cq @ w("uq")
    q = _lo(q, low).reshape(t, h, nope + rope)
    kv = _lo(x @ w("dkv"), low)
    c = _lo(_rmsnorm(kv[:, :lora], at(a["kv_norm"]["g"]), eps), low)
    k_rope = _lo(_rope(kv[:, lora:], pos, freqs, cs), low)     # (T, R)
    q_rope = _lo(_rope(q[..., nope:], pos, freqs, cs), low)
    k_nope = _lo(c @ w("uk"), low).reshape(t, h, nope)
    v = _lo(c @ w("uv"), low).reshape(t, h, vd)
    qf = jnp.concatenate([q[..., :nope], q_rope], -1)
    kf = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (t, h, rope))], -1)
    o = _lo(_attention(qf, kf, v, scale, low), low)
    return _lo(o @ w("o"), low)


def _experts(m, x, at, moe, low):
    """The held experts' part of the routed sum, and the shared experts."""
    (n_all, held, first, top_k, norm, routed_scale) = moe
    logits = x @ at(m["router"]["w"])
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
    if norm:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * routed_scale
    y = jnp.zeros_like(x)
    for e in range(held):
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)     # (T,)
        we = lambda name: _lo(at(m[name])[e], low)
        act = _lo(jax.nn.silu(_lo(x @ we("w_gate"), low))
                  * _lo(x @ we("w_up"), low), low)
        y = y + g[:, None] * _lo(act @ we("w_down"), low)
    return _lo(y, low) + _swiglu(m["shared"], x, at, low)


@functools.partial(jax.jit, static_argnames=("dims", "moe", "low"))
def _layer(h, blocks, layer, dims, moe, low):
    eps = dims[5]
    at = lambda x: jax.lax.dynamic_index_in_dim(x, layer, 0, False).astype(
        jnp.float32)
    x = _lo(_rmsnorm(h, at(blocks["n1"]["g"]), eps), low)
    h = _lo(h + _mla(blocks["attn"], x, at, dims, low), low)
    x = _lo(_rmsnorm(h, at(blocks["n2"]["g"]), eps), low)
    if moe is None:
        return _lo(h + _swiglu(blocks["mlp"], x, at, low), low)
    return _lo(h + _experts(blocks["moe"], x, at, moe, low), low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(h, g, head, eps, low):
    x = _lo(_rmsnorm(h, g.astype(jnp.float32), eps), low)
    return x @ _lo(head.astype(jnp.float32), low).T


def _bucket(t: int) -> int:
    b = 256
    while b < t:
        b *= 2
    return b


def logits(weights, cfg: dict, tokens: np.ndarray, first: int,
           low: bool = False) -> np.ndarray:
    """Logits (float32) of positions ``first .. len(tokens)-1`` of one
    sequence. The sequence is right-padded to a power-of-two bucket; causal
    attention keeps the pad out of every real position."""
    freqs, cs, scale = yarn(cfg)
    dims = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            float(cfg["rms_norm_eps"]), tuple(float(f) for f in freqs),
            float(cs), float(scale))
    moe = (cfg["n_routed_experts_published"], cfg["n_routed_experts"],
           cfg["held_first_expert"], cfg["num_experts_per_tok"],
           bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]))
    n_lead = cfg["first_k_dense_replace"]
    t = len(tokens)
    tb = _bucket(t)
    ids = np.zeros(tb, np.int32)
    ids[:t] = tokens
    with jax.default_matmul_precision("highest"):
        h = _lo(jnp.take(weights["embed"]["e"], jnp.asarray(ids), 0)
                .astype(jnp.float32), low)
        for layer in range(cfg["num_hidden_layers"]):
            if layer < n_lead:
                h = _layer(h, weights["lead_blocks"], layer, dims, None, low)
            else:
                h = _layer(h, weights["blocks"], layer - n_lead, dims, moe,
                           low)
        n = t - first
        nb = min(_bucket(n), tb)
        start = min(first, tb - nb)
        rows = jax.lax.dynamic_slice_in_dim(h, jnp.int32(start), nb, 0)
        out = _head(rows, weights["final_norm"]["g"], weights["head"]["e"],
                    float(cfg["rms_norm_eps"]), low)
        return np.asarray(out)[first - start:first - start + n]
