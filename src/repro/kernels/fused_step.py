"""Per-layer decode megakernel: norm + QKV + rope + attention + O + SwiGLU
as ONE Pallas program (DESIGN.md §15).

The decode step of a dense transformer layer is seven skinny matmuls and an
attention sweep, each a separate XLA op whose (B, d)-sized activations
round-trip HBM between stages; with the CIM macro doing the MACs nearly for
free (the paper's 818-TOPS/W operating point), that handoff tail *is* the
step cost. This kernel keeps the whole layer's activations VMEM-resident:

  * grid ``(kv_blocks,)``, one program per layer, all B slot rows jointly
    resident. The batch must stay whole because the sim-mode activation
    scale is batch-global (``layers._act_scale`` takes the rms over every
    element of the projection input) — a per-row grid would change the
    quantization and break bit-identity with the unfused path.
  * prologue (block 0): rmsnorm1, the three QKV projections, rope at
    position ``lens[b]-1``, and the cache-write image of the current
    token's K/V (the int8 path replicates ``attention._kv_quant`` exactly
    and emits the int8 rows + scales for the caller's cache write).
  * sweep: the length-aware online-softmax attention of
    ``kernels/decode_attention.py`` against the *stale* cache blocks, with
    the current token's K/V substituted in-register at ``lens[b]-1`` —
    bit-identical to writing the cache first and attending to it, without
    serialising on the HBM write. KV index maps clamp at the batch-max
    live block, so dead-tail DMA is elided batch-wide.
  * epilogue (last block): O projection, residual, rmsnorm2, SwiGLU,
    second residual — the attention output never leaves VMEM.

Projections run in two modes, selected statically:

  * ``mode="off"``: plain f32 dots (ideal digital).
  * ``mode="sim"`` with deployed planes: the in-kernel replica of
    ``ops.cim_matmul_deployed`` — per-projection rms act-scale, round/clip
    quantization, K-tiled int32 dots over the int8 plane, per-tile Threefry
    readout noise on global (row, col) counters (``core.prng.tile_gaussian``
    — the same stream as ``cim_matmul_fused_pallas``/``cim_matmul_fused_ref``,
    so fused == unfused holds token for token against the
    ``cim.use_kernel=True`` engine), and the ``x_scale * w_scale`` dequant
    epilogue. The 7 per-projection noise seeds arrive via SMEM in the same
    ``ctx.next_key()`` order the unfused layer draws them
    (q, k, v, o, gate, up, down).

Routed from ``transformer._dense_block`` via ``cfg.fuse_layer`` (see
``_use_fused_layer`` for the exact eligibility contract); the per-layer step
is still driven by the existing ``lax.scan`` over stacked planes, so the
whole L-layer decode tower is L megakernel launches inside one program.
Validated token-for-token against the unfused engine in
tests/test_megakernel.py; CPU callers get ``interpret=True`` automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.core.cim import output_noise_std_int_per_tile
from repro.core.prng import seed_from_key, tile_gaussian
from repro.kernels.decode_attention import NEG_INF, _pick_block_k

# projection order == the unfused layer's dense-call (and next_key) order
_ROLES = ("attn_qkv", "attn_qkv", "attn_qkv", "attn_out",
          "mlp_in", "mlp_in", "mlp_out")


# scoped VMEM a pallas_call may use on TPU v5e unless it raises the limit
# itself (this kernel does not)
VMEM_LIMIT_BYTES = 16 * 2**20


def vmem_bytes(cfg, max_slots: int, max_len: int, sim: bool) -> int:
    """VMEM the megakernel needs for one layer, from the config's widths.

    Counts what ``fused_dense_layer`` keeps resident: the seven projection
    planes as whole-array blocks (int8 in sim, the weight dtype in off),
    double-buffered by the Pallas pipeline, plus the f32/int32 copy of the
    widest plane each projection widens in-kernel, plus the double-buffered
    K/V cache blocks of the attention sweep.
    """
    d, f, h, kv, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    planes = [d * h * hd, d * kv * hd, d * kv * hd, h * hd * d,
              d * f, d * f, f * d]
    w_bytes = 1 if sim else jnp.dtype(cfg.dtype).itemsize
    kv_bytes = 1 if cfg.kv_cache_int8 else jnp.dtype(cfg.dtype).itemsize
    bk = _pick_block_k(max_len, 128)
    return (2 * sum(planes) * w_bytes + 4 * max(planes)
            + 2 * 2 * max_slots * bk * kv * hd * kv_bytes)


def check_fused_layer(cfg, max_slots: int, max_len: int, sim: bool) -> None:
    """Raise ValueError where the megakernel cannot serve this config.

    The kernel runs on float32 activations only (``_use_fused_layer``
    routes any other dtype to the per-layer path, which would turn
    ``fuse_layer`` into a silent no-op), and its resident working set must
    fit the scoped VMEM limit; the seven whole-array weight blocks do not
    stream over a grid axis.
    """
    if jnp.dtype(cfg.dtype) != jnp.float32:
        raise ValueError(
            f"fuse_layer needs float32 activations; {cfg.name} runs "
            f"{cfg.dtype}, which the dense megakernel does not take")
    need = vmem_bytes(cfg, max_slots, max_len, sim)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"fuse_layer does not fit {cfg.name}: the dense megakernel "
            f"keeps {need / 2**20:.1f} MiB of one layer resident in VMEM "
            f"(whole-array projection weights, double-buffered), over the "
            f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB scoped limit; serve with "
            f"fuse_layer=False")


def _rms(xf: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    y = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return y * g


def _kernel(lens_ref, lmax_ref, *refs, b: int, d: int, h: int, kv: int, hd: int,
            f: int, grp: int, bk: int, n_kb: int, sim: bool, int8: bool,
            qkv_bias: bool, eps: float, theta: float, scale: float,
            clip_k: float, qmaxes, sigmas, macro_rows: int):
    it = iter(refs)
    x_ref, g1_ref, g2_ref = next(it), next(it), next(it)
    w_refs = [next(it) for _ in range(7)]
    b_refs = [next(it) for _ in range(3)] if qkv_bias else [None] * 3
    kc_ref, vc_ref = next(it), next(it)
    ks_ref, vs_ref = (next(it), next(it)) if int8 else (None, None)
    wsc_ref, seed_ref = (next(it), next(it)) if sim else (None, None)
    xo_ref, ko_ref, vo_ref = next(it), next(it), next(it)
    kso_ref, vso_ref = (next(it), next(it)) if int8 else (None, None)
    q_s, kcur_s, vcur_s, m_s, l_s, acc_s = it

    kb = pl.program_id(0)

    def _proj(hx, idx, xs):
        """One projection: plain f32 dot (off) or the in-kernel
        ``cim_matmul_deployed`` replica (sim). hx: (b, K) f32."""
        w_ref = w_refs[idx]
        if not sim:
            y = jnp.dot(hx, w_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        else:
            kdim, n = w_ref.shape
            xq = jnp.clip(jnp.round(hx / xs), -qmaxes[idx],
                          qmaxes[idx]).astype(jnp.int32)
            wi = w_ref[...].astype(jnp.int32)
            sigma = sigmas[idx]
            if sigma > 0.0:
                s0 = seed_ref[idx, 0].astype(jnp.uint32)
                s1 = seed_ref[idx, 1].astype(jnp.uint32)
                zeros = jnp.zeros((b, n), jnp.uint32)
                r_ids = jax.lax.broadcasted_iota(jnp.uint32, (b, n), 0) + zeros
                c_ids = jax.lax.broadcasted_iota(jnp.uint32, (b, n), 1) + zeros
            y = jnp.zeros((b, n), jnp.float32)
            for ti in range(-(-kdim // macro_rows)):
                sl = slice(ti * macro_rows, min((ti + 1) * macro_rows, kdim))
                s = jnp.dot(xq[:, sl], wi[sl, :],
                            preferred_element_type=jnp.int32
                            ).astype(jnp.float32)
                if sigma > 0.0:
                    s = s + sigma * tile_gaussian(s0, s1, jnp.uint32(ti),
                                                  r_ids, c_ids)
                y = y + s
            y = y * (xs * wsc_ref[idx])
        if idx < 3 and qkv_bias:
            y = y + b_refs[idx][...].astype(jnp.float32)
        return y

    def _xs(hx, idx):
        if not sim:
            return None
        rms = jnp.sqrt(jnp.mean(jnp.square(hx))) + 1e-8
        return clip_k * rms / qmaxes[idx]

    @pl.when(kb == 0)
    def _prologue():
        xf = x_ref[...].astype(jnp.float32)                     # (B, d)
        h1 = _rms(xf, g1_ref[...].astype(jnp.float32), eps)
        xs = _xs(h1, 0)
        q = _proj(h1, 0, xs).reshape(b, h, hd)
        k = _proj(h1, 1, xs).reshape(b, kv, hd)
        v = _proj(h1, 2, xs).reshape(b, kv, hd)
        # rope at the query position lens[b]-1 (== cache len before write);
        # SMEM holds scalars only, so the (B,) vector is assembled per row
        rows = jax.lax.broadcasted_iota(jnp.int32, (b,), 0)
        pos = jnp.zeros((b,), jnp.int32)
        for bi in range(b):
            pos = jnp.where(rows == bi, lens_ref[bi] - 1, pos)
        pos = pos.astype(jnp.float32)                           # (B,)
        expnt = (jax.lax.broadcasted_iota(jnp.int32, (hd // 2,), 0)
                 .astype(jnp.float32) * 2.0) / hd
        freqs = 1.0 / (theta ** expnt)
        ang = pos[:, None] * freqs[None, :]                     # (B, hd/2)
        cos = jnp.cos(ang)[:, None, :]
        sin = jnp.sin(ang)[:, None, :]

        def rope(x3):
            x1, x2 = x3[..., :hd // 2], x3[..., hd // 2:]
            return jnp.concatenate(
                [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)

        q = rope(q)
        k = rope(k)
        q_s[...] = q
        if int8:
            for val, qo, so, cur in ((k, ko_ref, kso_ref, kcur_s),
                                     (v, vo_ref, vso_ref, vcur_s)):
                sc = jnp.maximum(
                    jnp.max(jnp.abs(val), axis=-1, keepdims=True) / 127.0,
                    1e-8)
                qv = jnp.clip(jnp.round(val / sc), -127, 127)
                qo[...] = qv.astype(jnp.int8)
                so[...] = sc
                cur[...] = qv * sc     # == what the attention sweep reads back
        else:
            ko_ref[...] = k.astype(ko_ref.dtype)
            vo_ref[...] = v.astype(vo_ref.dtype)
            kcur_s[...] = k
            vcur_s[...] = v
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(kb * bk < lmax_ref[0])
    def _sweep():
        kj = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
        for bi in range(b):
            n_live = lens_ref[bi]
            # rows whose live range ends before this block still execute —
            # all-invalid masking makes the update an exact no-op
            # (alpha = exp(0) = 1, p = exp(NEG_INF - finite m) = 0)
            valid = kj < n_live
            cur = (kj == n_live - 1)[:, None]                   # (bk, 1)
            for hk in range(kv):
                kblk = kc_ref[bi, :, hk * hd:(hk + 1) * hd]
                vblk = vc_ref[bi, :, hk * hd:(hk + 1) * hd]
                if int8:
                    kblk = kblk.astype(jnp.float32) * ks_ref[bi, :, hk, :]
                    vblk = vblk.astype(jnp.float32) * vs_ref[bi, :, hk, :]
                # current token: the cache block is stale (written by the
                # caller after this kernel); substitute the freshly
                # computed row so the sweep sees the post-write cache
                kblk = jnp.where(cur, kcur_s[bi, hk][None, :], kblk)
                vblk = jnp.where(cur, vcur_s[bi, hk][None, :], vblk)
                qg = q_s[bi, hk * grp:(hk + 1) * grp, :]        # (G, hd)
                s = jnp.dot(qg, kblk.T,
                            preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid[None, :], s, NEG_INF)
                m_prev = m_s[bi, hk]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new[:, None])
                l_s[bi, hk] = l_s[bi, hk] * alpha + jnp.sum(p, axis=-1)
                acc_s[bi, hk] = acc_s[bi, hk] * alpha[:, None] + jnp.dot(
                    p, vblk, preferred_element_type=jnp.float32)
                m_s[bi, hk] = m_new

    @pl.when(kb == n_kb - 1)
    def _epilogue():
        denom = jnp.maximum(l_s[...], 1e-30)[..., None]         # (B, KV, G, 1)
        attn = (acc_s[...] / denom).reshape(b, h * hd)
        o = _proj(attn, 3, _xs(attn, 3))
        x1 = x_ref[...].astype(jnp.float32) + o
        h2 = _rms(x1, g2_ref[...].astype(jnp.float32), eps)
        xs = _xs(h2, 4)
        g = _proj(h2, 4, xs)
        u = _proj(h2, 5, xs)
        hm = jax.nn.silu(g) * u
        dn = _proj(hm, 6, _xs(hm, 6))
        xo_ref[...] = (x1 + dn).astype(xo_ref.dtype)


def fused_dense_layer(ctx, p, x, cache):
    """One dense transformer layer's decode step as a single Pallas program.

    x: (B, 1, d); cache: the layer's slot cache ({k, v[, ks, vs], len},
    K/V lane-dense ``(B, T, KV·hd)``).
    Returns (x_out (B, 1, d), new_cache) with the same cache-write semantics
    as the unfused ``transformer._dense_block`` (``write_rows`` at the old
    length, ``len + 1``). Eligibility is the caller's job
    (``transformer._use_fused_layer``).
    """
    from repro.models.attention import write_rows

    cfg = ctx.cfg
    b, s, d = x.shape
    assert s == 1, "fused_dense_layer is decode-only"
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    f = cfg.d_ff
    grp = h // kv
    start = cache["len"]
    lens = (start + 1).astype(jnp.int32)
    int8 = "ks" in cache
    sim = ctx.mode == "sim"
    qkv_bias = "b" in p["attn"]["q"]
    t = cache["k"].shape[1]
    bk = _pick_block_k(t, 128)
    n_kb = t // bk
    interpret = jax.default_backend() != "tpu"

    leaves = [p["attn"]["q"], p["attn"]["k"], p["attn"]["v"], p["attn"]["o"],
              p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"]]
    kdims = (d, d, d, h * hd, d, d, f)
    operands = [x[:, 0], p["n1"]["g"].reshape(1, d), p["n2"]["g"].reshape(1, d)]
    if sim:
        specs = [ctx.spec_for(r) for r in _ROLES]
        macro_rows = specs[0].macro_rows
        sigmas = tuple(output_noise_std_int_per_tile(sp, kd)
                       for sp, kd in zip(specs, kdims))
        qmaxes = tuple(quant.qmax(sp.in_bits) for sp in specs)
        operands += [lf[f"wq{sp.w_bits}"] for lf, sp in zip(leaves, specs)]
        wscales = jnp.stack([
            jnp.asarray(lf[f"ws{sp.w_bits}"], jnp.float32).reshape(())
            for lf, sp in zip(leaves, specs)])
        # same ctx.next_key() order as the unfused layer's dense calls
        seeds = jnp.stack([seed_from_key(ctx.next_key()) for _ in range(7)])
    else:
        macro_rows = 1024
        sigmas = (0.0,) * 7
        qmaxes = (0,) * 7
        operands += [lf["w"] for lf in leaves]
        wscales = seeds = None
    if qkv_bias:
        operands += [p["attn"][nm]["b"].reshape(1, -1) for nm in ("q", "k", "v")]
    operands += [cache["k"], cache["v"]]
    if int8:
        operands += [cache["ks"], cache["vs"]]
    if sim:
        operands += [wscales, seeds]

    def const(i, lens_pref, lmax_pref):
        return (0,) * 2

    def kv_map(i, lens_pref, lmax_pref):
        last = jnp.maximum((lmax_pref[0] - 1) // bk, 0)
        return (0, jnp.minimum(i, last), 0)

    def scale_map(i, lens_pref, lmax_pref):
        return kv_map(i, lens_pref, lmax_pref) + (0,)

    in_specs = [pl.BlockSpec(op.shape, const) for op in operands[:3]]
    in_specs += [pl.BlockSpec(wv.shape, const) for wv in operands[3:10]]
    if qkv_bias:
        in_specs += [pl.BlockSpec((1, bb.shape[1]), const)
                     for bb in operands[10:13]]
    in_specs += [pl.BlockSpec((b, bk, kv * hd), kv_map)] * 2
    if int8:
        in_specs += [pl.BlockSpec((b, bk, kv, 1), scale_map)] * 2
    if sim:
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2

    kdt = cache["k"].dtype
    out_shape = [jax.ShapeDtypeStruct((b, d), x.dtype),
                 jax.ShapeDtypeStruct((b, kv, hd), kdt),
                 jax.ShapeDtypeStruct((b, kv, hd), kdt)]
    out_specs = [pl.BlockSpec((b, d), const),
                 pl.BlockSpec((b, kv, hd), lambda i, lp, lm: (0, 0, 0)),
                 pl.BlockSpec((b, kv, hd), lambda i, lp, lm: (0, 0, 0))]
    if int8:
        out_shape += [jax.ShapeDtypeStruct((b, kv, 1), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((b, kv, 1), lambda i, lp, lm: (0, 0, 0))] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_kb,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((b, h, hd), jnp.float32),        # roped q
            pltpu.VMEM((b, kv, hd), jnp.float32),       # current k (dequant)
            pltpu.VMEM((b, kv, hd), jnp.float32),       # current v (dequant)
            pltpu.VMEM((b, kv, grp), jnp.float32),      # running max
            pltpu.VMEM((b, kv, grp), jnp.float32),      # denominator
            pltpu.VMEM((b, kv, grp, hd), jnp.float32),  # accumulator
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _kernel, b=b, d=d, h=h, kv=kv, hd=hd, f=f, grp=grp, bk=bk,
            n_kb=n_kb, sim=sim, int8=int8, qkv_bias=qkv_bias,
            eps=cfg.norm_eps, theta=cfg.rope_theta, scale=1.0 / (hd ** 0.5),
            clip_k=cfg.cim.act_clip_sigmas, qmaxes=qmaxes, sigmas=sigmas,
            macro_rows=macro_rows),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lens, jnp.max(lens).reshape(1), *operands)

    if int8:
        x_new, kq, vq, kscale, vscale = outs
        rows = {"k": kq.reshape(b, 1, kv * hd), "v": vq.reshape(b, 1, kv * hd),
                "ks": kscale[:, None], "vs": vscale[:, None]}
    else:
        x_new, k_cur, v_cur = outs
        rows = {"k": k_cur.reshape(b, 1, kv * hd),
                "v": v_cur.reshape(b, 1, kv * hd)}
    new_cache = {n: write_rows(cache, n, u, start) for n, u in rows.items()}
    new_cache["len"] = start + 1
    return x_new[:, None], new_cache
