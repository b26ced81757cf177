"""Mixture-of-Experts block: top-k router + capacity dispatch, EP-sharded.

Three dispatch regimes (selected automatically; §Perf deepseek-v2 log):
  * single device / tiny batches — one-group argsort+scatter (O(T log T),
    no (T, E) one-hot materialisation);
  * on-mesh, >=256 tokens/DP-group — tokens reshaped to a dp-aligned leading
    group dim; with shard_map each model rank scatters only its own experts'
    rows locally (zero dispatch collectives) and the combine is one TP-style
    psum — the minimal EP communication;
  * decode-size batches on-mesh — single-group fallback (grouped dispatch
    would force FSDP expert-weight gathers that dwarf the tiny activations).

Expert weights carry the 'experts' logical axis (sharded over the mesh
'model' axis); the router runs digital f32 per SAC (role 'router').
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Ctx, Params, _init_dense, dense, init_swiglu, swiglu
from repro.distributed.sharding import shard


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    d, f = cfg.d_model, cfg.d_ff
    kr, ke, ks = jax.random.split(key, 3)
    # the router scores every expert; the banks hold only this device's
    pr, ar = _init_dense(kr, d, m.n_experts, ("embed", None), dtype=jnp.float32)
    lim = 1.0 / jnp.sqrt(d)
    kw1, kw2, kw3 = jax.random.split(ke, 3)
    e = m.held
    p = {
        "router": pr,
        "w_gate": jax.random.uniform(kw1, (e, d, f), dtype, -lim, lim),
        "w_up": jax.random.uniform(kw2, (e, d, f), dtype, -lim, lim),
        "w_down": jax.random.uniform(kw3, (e, f, d), dtype, -lim, lim),
    }
    a = {
        "router": ar,
        "w_gate": ("experts", "embed", "mlp"),
        "w_up": ("experts", "embed", "mlp"),
        "w_down": ("experts", "mlp", "embed"),
    }
    if m.n_shared:
        psh, ash = init_swiglu(ks, d, m.n_shared * f, dtype)
        p["shared"], a["shared"] = psh, ash
    return p, a


def _dispatch_indices(flat_e: jnp.ndarray, n_experts: int, capacity: int):
    """Position of each assignment within its expert + keep mask."""
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e)                     # stable
    sorted_e = flat_e[order]
    run_start = jnp.searchsorted(sorted_e, jnp.arange(n_experts), side="left")
    pos_sorted = jnp.arange(tk) - run_start[sorted_e]
    pos = jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < capacity
    return pos, keep


def _dp_axes():
    """(mesh, dp_axes tuple, dp_degree, model_degree) from active rules."""
    from repro.distributed.sharding import get_rules

    rules = get_rules()
    if rules is None:
        return None, (), 1, 1
    ax = rules.activation.get("batch")
    axes = () if ax is None else ((ax,) if isinstance(ax, str) else tuple(ax))
    n = 1
    for a in axes:
        n *= rules.mesh.shape[a]
    m = rules.mesh.shape.get("model", 1)
    return rules.mesh, axes, n, m


def _dp_degree() -> int:
    return _dp_axes()[2]


def _gates(m, probs: jnp.ndarray):
    """Top-k gates and experts of router probabilities (..., E): the
    gates renormalised to sum to 1 where ``norm_topk``, then scaled by
    ``routed_scale``."""
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True)
                                 + 1e-9)
    if m.routed_scale != 1.0:
        gate_vals = gate_vals * m.routed_scale
    return gate_vals, expert_idx


def moe_block(ctx: Ctx, p: Params, x: jnp.ndarray,
              dropless: bool = False) -> jnp.ndarray:
    """x: (B, S, d) -> (B, S, d).

    ``dropless=True`` (serving) keeps every token's every routed expert,
    so a token's output cannot depend on how many other tokens share its
    fixed-shape program (chunked prefill must be token-for-token equal to
    whole-prompt prefill, DESIGN.md §15). With digital experts it runs
    ``held_experts``: a grouped matmul over the rows routed to this
    device's experts. Otherwise (and in training, which keeps the classic
    capacity-factor buffer) the capacity path below serves it, its buffer
    sized at the worst case ``tl * top_k`` when dropless.

    Dispatch is *local per DP shard*: tokens are reshaped to a leading
    (dp_degree,)-group dim that aligns 1:1 with the DP mesh axes, and the
    sort/scatter/gather run vmapped along it — XLA partitions batched index
    ops trivially on a sharded leading dim, so dispatch costs zero
    collectives. A naive global scatter instead makes GSPMD replicate the
    (E, C, d) buffer across DP: ~17 TB/device of all-gather per step on
    deepseek-v2 train_4k (EXPERIMENTS.md §Perf iteration 1-2). The combine
    is a local gather + the usual TP reduction of the block output.
    """
    cfg = ctx.cfg
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    if dropless and ctx.spec_for("moe_expert") is None:
        y = held_experts(ctx, p, x.reshape(t, d)).reshape(b, s, d)
        if m.n_shared:
            y = y + swiglu(ctx, p["shared"], x)
        return y
    if m.held != m.n_experts:
        raise ValueError(
            f"a layer holding {m.held} of {m.n_experts} experts serves "
            "through held_experts alone (dropless, digital experts)")
    groups = _dp_degree()
    # grouped/shard_map dispatch pays off at training/prefill token counts;
    # at decode-size batches it forces XLA to gather FSDP expert weights
    # (26 GB/step on deepseek-v2 decode) — single-group dispatch with its
    # tiny capacity buffer is the right regime there.
    if t % groups or (t // groups) < max(256, m.top_k):
        groups = 1
    tl = t // groups                                  # tokens per dp group

    xg = x.reshape(groups, tl, d)
    xg = shard(xg, "batch", None, "embed")

    # router (digital, f32)
    logits = dense(ctx, p["router"], xg.astype(jnp.float32), "router")
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = _gates(m, probs)                    # (G, tl, k)

    if dropless:
        capacity = tl * m.top_k
    else:
        capacity = max(int(tl * m.top_k / m.n_experts * m.capacity_factor),
                       m.top_k)
    flat_e = expert_idx.reshape(groups, tl * m.top_k)
    pos, keep = jax.vmap(
        lambda fe: _dispatch_indices(fe, m.n_experts, capacity))(flat_e)

    tok_of_assign = jnp.repeat(jnp.arange(tl), m.top_k)
    e_idx = jnp.where(keep, flat_e, 0)
    pos_idx = jnp.where(keep, pos, 0)
    gates_flat = gate_vals.reshape(groups, tl * m.top_k)

    mesh, dp_ax, dp_n, model_n = _dp_axes()
    use_smap = (mesh is not None and groups == dp_n and dp_n > 1
                and "model" in mesh.shape and m.n_experts % model_n == 0)

    if use_smap:
        # shard_map EP dispatch/combine (EXPERIMENTS.md §Perf deepseek-v2
        # iteration 4): activations are dp-sharded and model-replicated, so
        # every model rank already holds its dp-group's tokens — it scatters
        # *only its own experts'* rows locally (zero dispatch collectives;
        # the pjit scatter instead makes GSPMD all-reduce the expert buffer
        # across 'model': ~3.9 TB/device/step). The combine is one TP-style
        # psum of the block output — the minimal EP communication.
        ex = _smap_dispatch(mesh, dp_ax, x.dtype, xg, e_idx, pos_idx, keep,
                            tok_of_assign, m.n_experts // model_n, capacity, d)
    else:
        def scatter_one(xt_g, e_g, pos_g, keep_g):
            buf = jnp.zeros((m.n_experts, capacity, d), x.dtype)
            upd = xt_g[tok_of_assign] * keep_g[:, None].astype(x.dtype)
            return buf.at[e_g, pos_g].add(upd)

        ex = jax.vmap(scatter_one)(xg, e_idx, pos_idx, keep)
        ex = shard(ex, "batch", "experts", None, "embed")

    # expert FFN (SwiGLU), batched einsum; experts sharded over 'model' (EP)
    def ffn(ex_in):
        g = _expert_dense(ctx, ex_in, p, "w_gate")
        u = _expert_dense(ctx, ex_in, p, "w_up")
        h = jax.nn.silu(g) * u
        h = shard(h, "batch", "experts", None, "mlp")
        return _expert_dense(ctx, h, p, "w_down")

    out = ffn(ex)

    if use_smap:
        y = _smap_combine(mesh, dp_ax, x.dtype, out, e_idx, pos_idx, keep,
                          gates_flat, tok_of_assign,
                          m.n_experts // model_n, capacity, tl, d)
    else:
        out = shard(out, "batch", "experts", None, "embed")

        def combine_one(out_g, e_g, pos_g, gates_g, keep_g):
            y_assign = out_g[e_g, pos_g] * (gates_g.reshape(-1, 1)
                                            * keep_g[:, None]).astype(x.dtype)
            return jnp.zeros((tl, d), x.dtype).at[tok_of_assign].add(y_assign)

        y = jax.vmap(combine_one)(out, e_idx, pos_idx, gates_flat, keep)
    y = y.reshape(b, s, d)

    if m.n_shared:
        y = y + swiglu(ctx, p["shared"], x.reshape(b, s, d)).reshape(b, s, d)
    return y


def _tile(dim: int) -> int:
    """A grouped-matmul tile along a weight dimension: the whole dimension
    up to 1536, else its largest multiple-of-128 divisor up to 512."""
    if dim <= 1536:
        return dim
    return max(t for t in range(128, 513, 128) if dim % t == 0)


def expert_tile_rows(t: int, m) -> int:
    """Rows per tile of the grouped matmul for ``t`` routed tokens: about
    four times the rows an expert expects (``t·top_k/n_experts``), a power
    of two in [16, 128]. Larger tiles hold a group in fewer tiles, so
    fewer tiles reread its weights; smaller ones compute fewer pad rows."""
    tm = 16
    while tm < 128 and tm < 4 * t * m.top_k / m.n_experts:
        tm *= 2
    return tm


def _gmm(x, w, sizes, tm):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    k, n = w.shape[1:]
    return gmm(x, w.astype(x.dtype), sizes, preferred_element_type=jnp.float32,
               tiling=(tm, _tile(k), _tile(n)),
               interpret=jax.default_backend() != "tpu")


def held_experts(ctx: Ctx, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The held experts' part of the routed sum for tokens x: (T, d).

    The router scores all ``n_experts`` and keeps each token's top-k, as
    published; only assignments to the held experts
    ``[held_first, held_first + held)`` are computed here, and what the
    other devices' experts add is not (expert parallelism without its
    exchange). Held assignments are sorted by expert into one row buffer
    and run through a grouped matmul (megablox ``gmm``), which visits only
    the row tiles that hold routed rows: the work follows the rows
    actually routed here plus each group's tile padding, whatever the
    buffer's static size (``T·min(top_k, held)`` rows, the most that can
    be routed here). Appends ``(routed rows per held expert, rows the
    matmul computed, held experts that had rows)`` to ``ctx.moe_log``
    where the caller collects them.
    """
    m = ctx.cfg.moe
    t, d = x.shape
    h, k = m.held, m.top_k
    with jax.named_scope("moe.route"):
        # full float32: a near-tie of the k-th and (k+1)-th expert must
        # not turn on the matmul's bf16 passes
        with jax.default_matmul_precision("highest"):
            logits = dense(ctx, p["router"], x.astype(jnp.float32), "router")
        gate_vals, expert_idx = _gates(m, jax.nn.softmax(logits, axis=-1))
        rel = expert_idx.reshape(-1) - m.held_first        # (T·k,)
        held = (rel >= 0) & (rel < h)
        group = jnp.where(held, rel, h)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=h + 1)[:h].astype(jnp.int32)
        tm = expert_tile_rows(t, m)
        rows = -(-t * min(k, h) // tm) * tm
        order = jnp.pad(order, (0, max(0, rows - t * k)))[:rows]
        src = order // k                                   # token of each row
        total = jnp.sum(sizes)
        live = jnp.arange(rows) < total
        gate = jnp.where(live, gate_vals.reshape(-1)[order], 0.0)
    with jax.named_scope("moe.experts"):
        xs = x[src]
        g = _gmm(xs, p["w_gate"], sizes, tm)
        u = _gmm(xs, p["w_up"], sizes, tm)
        hid = (jax.nn.silu(g) * u).astype(x.dtype)
        out = _gmm(hid, p["w_down"], sizes, tm)
        # rows past the routed ones are never written by the matmul
        out = jnp.where(live[:, None], out, 0.0) * gate[:, None]
        y = jnp.zeros((t, d), jnp.float32).at[src].add(out).astype(x.dtype)
    if ctx.moe_log is not None:
        ends = jnp.cumsum(sizes)
        tiles = jnp.where(sizes > 0, -(-ends // tm) - (ends - sizes) // tm, 0)
        ctx.moe_log.append((sizes, jnp.sum(tiles) * tm,
                            jnp.sum(sizes > 0).astype(jnp.int32)))
    return y


def _smap_dispatch(mesh, dp_ax, dtype, xg, e_idx, pos_idx, keep,
                   tok_of_assign, e_local, capacity, d):
    """Per-model-rank local scatter: (G, tl, d) -> (G, E, C, d) EP-sharded."""
    from jax.sharding import PartitionSpec as P

    def body(xg_l, e_l, pos_l, keep_l):
        mi = jax.lax.axis_index("model")
        e_rel = e_l - mi * e_local
        ok = keep_l & (e_rel >= 0) & (e_rel < e_local)

        def one(xt_g, e_g, pos_g, ok_g):
            buf = jnp.zeros((e_local, capacity, d), dtype)
            upd = xt_g[tok_of_assign] * ok_g[:, None].astype(dtype)
            return buf.at[jnp.where(ok_g, e_g, 0), jnp.where(ok_g, pos_g, 0)
                          ].add(upd)

        return jax.vmap(one)(xg_l, e_rel, pos_l, ok)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_ax, None, None), P(dp_ax, None), P(dp_ax, None),
                  P(dp_ax, None)),
        out_specs=P(dp_ax, "model", None, None),
    )(xg, e_idx, pos_idx, keep)


def _smap_combine(mesh, dp_ax, dtype, out, e_idx, pos_idx, keep, gates,
                  tok_of_assign, e_local, capacity, tl, d):
    """Masked local gather + psum('model'): (G, E, C, d) -> (G, tl, d)."""
    from jax.sharding import PartitionSpec as P

    def body(out_l, e_l, pos_l, keep_l, gat_l):
        mi = jax.lax.axis_index("model")
        e_rel = e_l - mi * e_local
        ok = keep_l & (e_rel >= 0) & (e_rel < e_local)

        def one(out_g, e_g, pos_g, ok_g, g_g):
            vals = out_g[jnp.where(ok_g, e_g, 0), jnp.where(ok_g, pos_g, 0)]
            w = (g_g * ok_g).astype(dtype)[:, None]
            return jnp.zeros((tl, d), dtype).at[tok_of_assign].add(vals * w)

        y = jax.vmap(one)(out_l, e_rel, pos_l, ok, gat_l)
        return jax.lax.psum(y, "model")

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_ax, "model", None, None), P(dp_ax, None),
                  P(dp_ax, None), P(dp_ax, None), P(dp_ax, None)),
        out_specs=P(dp_ax, None, None),
    )(out, e_idx, pos_idx, keep, gates)


def _expert_dense(ctx: Ctx, x: jnp.ndarray, p: Params,
                  name: str) -> jnp.ndarray:
    """(G, E, C, a) x (E, a, b) -> (G, E, C, b) through the CIM model.

    ``p[name]`` is the expert bank; a deployed per-tensor plane
    ``p[f"{name}_q{w_bits}"]``/``_s{w_bits}`` (``core.deploy`` — the key
    fingerprints the deployed bit-width) lets sim mode skip the whole-bank
    abs-max/quantize per call, bit-identically.
    """
    w = p[name]
    spec = ctx.spec_for("moe_expert")
    if spec is None:
        return jnp.einsum("geca,eab->gecb", x, w.astype(x.dtype))
    # behavioural CIM on the batched expert matmuls: exact int path is an
    # einsum; the readout error is injected output-side (same statistics).
    from repro.core import quant
    from repro.core.cim import output_noise_std_int

    if ctx.mode == "qat":
        xs = quant.abs_max_scale(x.astype(jnp.float32), spec.in_bits)
        ws = quant.abs_max_scale(w.astype(jnp.float32), spec.w_bits)
        xf = quant.fake_quant(x.astype(jnp.float32), xs, spec.in_bits)
        wf = quant.fake_quant(w.astype(jnp.float32), ws, spec.w_bits)
        y = jnp.einsum("geca,eab->gecb", xf, wf)
    else:
        wq = p.get(f"{name}_q{spec.w_bits}")
        ws = p.get(f"{name}_s{spec.w_bits}")
        if ctx.deployed and wq is None:
            raise ValueError(
                "deployed sim-mode expert FFN has no pre-quantized weight "
                f"plane for '{name}' at w_bits={spec.w_bits} — run "
                "core.deploy.deploy() with the serving policy")
        xq, xs, wq_i, ws = quant.quantize_operands(
            x.astype(jnp.float32), None if wq is not None else w.astype(jnp.float32),
            spec.in_bits, spec.w_bits, w_scale=ws, wq=wq)
        y = jnp.einsum("geca,eab->gecb", xq.astype(jnp.float32),
                       wq_i.astype(jnp.float32))
        y = y * xs * ws
    key = ctx.next_key()
    if key is not None:
        sigma = output_noise_std_int(spec, x.shape[-1], include_static=ctx.mode != "qat")
        y = y + (sigma * xs * ws) * jax.random.normal(key, y.shape, jnp.float32)
    return y.astype(x.dtype)
