"""Operations and bytes of DeepSeek-V2's MLA + MoE kernels and tokens.

The costs of ``bench/flops.py`` for a configuration with a ``program``
block of the ``moe`` family with MLA (``bench/configs/deepseek-v2-*``),
its sizes read from the file's published keys (``sizes``). Counted from
shapes with no tile padding, a multiply-add as two bf16 operations: the
least work the mathematics needs, so that a share of a peak computed from
these numbers cannot pass 100% unless the device time leaves out part of
the work.

Attention is counted in its two forms where they differ. Per query, key
and head, the *absorbed* form (W_uk folded into the query, scores and
values taken from the latent: what the kernels run) costs
``2(L + R) + 2L`` and reads only the latent cache; the *up-projected*
form costs ``2(nope + rope) + 2v`` once each key's per-head K/V exist,
which costs ``2L·H·(nope + v)`` per key. A kernel's least work is the
smaller of the two; a token's counts the up-projected form, the model's
own arithmetic.
"""

from __future__ import annotations

from typing import Iterable

from bench.flops import Cost


def sizes(config: dict) -> dict:
    """The sizes the costs take, by short name, from the file's keys."""
    return {"n_layers": config["num_hidden_layers"],
            "n_lead": config["first_k_dense_replace"],
            "d": config["hidden_size"], "h": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "v": config["v_head_dim"],
            "lat": config["kv_lora_rank"], "q_lora": config["q_lora_rank"],
            "f_dense": config["intermediate_size"],
            "f_expert": config["moe_intermediate_size"],
            "experts": config["n_routed_experts_published"],
            "held": config["n_routed_experts"],
            "shared": config["n_shared_experts"],
            "vocab": config["vocab_size"]}


def _absorbed(z: dict) -> float:
    """Operations per (query, key, head) of the absorbed form."""
    return 2.0 * (z["lat"] + z["rope"]) + 2.0 * z["lat"]


def _pairwise(z: dict) -> float:
    """Operations per (query, key, head) once K/V are up-projected."""
    return 2.0 * (z["nope"] + z["rope"]) + 2.0 * z["v"]


def mla_decode_call(lens: Iterable[int], z: dict, kv_bytes: int = 2,
                    q_bytes: int = 2) -> Cost:
    """One latent-cache decode attention call (one layer) over rows whose
    caches hold ``lens`` keys: absorbed scores and weighted sum for every
    head, each live latent row (``L + R`` channels) read once."""
    lens = list(lens)
    live = float(sum(lens))
    qo = len(lens) * z["h"] * (2 * z["lat"] + z["rope"]) * q_bytes
    return Cost(bf16_flops=z["h"] * _absorbed(z) * live,
                bytes=live * (z["lat"] + z["rope"]) * kv_bytes + qo)


def mla_prefill_call(s: int, start: int, z: dict, kv_bytes: int = 2,
                     q_bytes: int = 2) -> Cost:
    """One causal chunk of ``s`` queries at ``start .. start+s-1`` (one
    layer) against the latent rows before and among them: the smaller of
    the two forms' operations, each latent row read once."""
    pairs = s * start + s * (s + 1) / 2.0
    keys = start + s
    absorbed = z["h"] * _absorbed(z) * pairs
    up = (z["h"] * _pairwise(z) * pairs
          + keys * 2.0 * z["lat"] * z["h"] * (z["nope"] + z["v"]))
    qo = s * z["h"] * (2 * z["lat"] + z["rope"]) * q_bytes
    return Cost(bf16_flops=min(absorbed, up),
                bytes=keys * (z["lat"] + z["rope"]) * kv_bytes + qo)


def expert_call(routed: float, groups: float, z: dict,
                w_bytes: int = 2, x_bytes: int = 2) -> Cost:
    """The held experts' grouped SwiGLU matmuls over ``routed`` rows, where
    ``groups`` (layer, expert) pairs had rows: each such expert's three
    weight matrices read once, each row's input read and output written
    once."""
    d, f = z["d"], z["f_expert"]
    return Cost(bf16_flops=routed * 3 * 2.0 * d * f,
                bytes=groups * 3.0 * d * f * w_bytes
                + routed * 2.0 * d * x_bytes)


def _linears(z: dict) -> float:
    """Operations of one token's attention linears, one layer."""
    d, h = z["d"], z["h"]
    qh = h * (z["nope"] + z["rope"])
    q = d * qh if z["q_lora"] is None else d * z["q_lora"] + z["q_lora"] * qh
    kv = d * (z["lat"] + z["rope"]) + z["lat"] * h * (z["nope"] + z["v"])
    return 2.0 * (q + kv + h * z["v"] * d)


def _ffn(z: dict) -> float:
    """Operations of one token's dense and shared-expert FFNs and router,
    all layers (the routed experts are counted by rows, ``expert_call``)."""
    d = z["d"]
    lead = z["n_lead"]
    moe = z["n_layers"] - lead
    return (lead * 6.0 * d * z["f_dense"]
            + moe * (6.0 * d * z["f_expert"] * z["shared"]
                     + 2.0 * d * z["experts"]))


def token_cost(z: dict, context: int, logits: bool) -> Cost:
    """Work one token needs outside the routed experts: every layer's
    attention linears and attention over ``context`` keys (itself
    included), the dense and shared FFNs and the router, and the logits
    head when its logits are used."""
    attn = z["h"] * _pairwise(z) * context
    head = 2.0 * z["d"] * z["vocab"] if logits else 0.0
    return Cost(bf16_flops=(_linears(z) + attn) * z["n_layers"] + _ffn(z)
                + head)


def chunk_cost(z: dict, start: int, valid: int, final: bool) -> Cost:
    """Work the ``valid`` real tokens of one prefill chunk need outside
    the routed experts; only the prompt's last token needs the head."""
    pairs = valid * start + valid * (valid + 1) / 2.0
    attn = z["h"] * _pairwise(z) * pairs
    head = 2.0 * z["d"] * z["vocab"] if final else 0.0
    return Cost(bf16_flops=(_linears(z) * valid + attn) * z["n_layers"]
                + _ffn(z) * valid + head)


def expert_time_s(summary, z: dict) -> float:
    """Device seconds of the held experts' grouped matmul: the ``gmm``
    calls, and the operations that stage their weights. A custom call takes
    its operands as buffers, so XLA slices each layer's expert weights
    (``bf16[held, d, f]`` and ``bf16[held, f, d]``) out of the stacked ones
    into a buffer of their own, in VMEM where they fit (v5e): the HBM read
    of the weights is that staging operation's, and ``gmm`` reads VMEM."""
    shapes = {f"bf16[{z['held']},{z['d']},{z['f_expert']}]",
              f"bf16[{z['held']},{z['f_expert']},{z['d']}]"}
    return sum(o.end - o.start for ops in summary.ops.values() for o in ops
               if o.name == "gmm" or o.result in shapes) * 1e-9


def experts_drained(sp) -> dict:
    """Totals of the ``engine.drain.experts`` spans (``bench/spans.py``'s
    ``Spans``) in the window: rows routed to the held experts, the grouped
    matmul's padding rows, and the (layer, expert) pairs that had rows.
    ``None`` where the window has none."""
    got = [s.stats for s in sp.spans if s.name == "engine.drain.experts"]
    if not got:
        return None
    return {k: sum(int(g.get(k, 0)) for g in got)
            for k in ("routed_rows", "expert_pad_rows", "expert_groups")}
