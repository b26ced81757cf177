import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step with optimizer
update / prefill_step / serve_step), constructs ShapeDtypeStruct inputs from
``input_specs`` with NamedShardings from the logical-axis rules, and runs
``jax.jit(...).lower().compile()`` on the production mesh. Success proves the
distribution config is coherent; the compiled artifact yields

  * memory_analysis()  — per-device bytes (proves it fits),
  * cost_analysis()    — per-device FLOPs/bytes for §Roofline,
  * collective traffic — parsed from the partitioned HLO text,

all recorded as JSON under experiments/dryrun/ for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-130m --shape long_500k
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ShapeConfig, get_shape
from repro.configs.registry import ASSIGNED, get_config
from repro.distributed.sharding import (ShardingRules, default_rules, dp_axes,
                                        tp_axis, use_rules)
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf_mod
from repro.models.layers import Ctx
from repro.models.model import build, input_specs, param_specs
from repro.training import optimizer as opt_mod
from repro.training.trainer import make_train_step

# roofline hardware constants (given): TPU v5e-class chip
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # B/s per chip
ICI_BW = 50e9              # B/s per link

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", re.I)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
          "s8": 1, "u8": 1, "pred": 1}
_MULT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
         "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES.get(dt.split("[")[0], 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device collective traffic from the partitioned HLO (result shapes
    x op-specific ring multipliers; all-reduce counts 2x for reduce+broadcast
    phases). The module is the per-device SPMD program, so no /chips."""
    per_op: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(2).lower()
        result = m.group(1)
        b = _shape_bytes(result) * _MULT[op]
        per_op[op] = per_op.get(op, 0.0) + b
    per_op["total"] = sum(v for k, v in per_op.items())
    return per_op


# --------------------------------------------------------------------------
# sharding trees for inputs
# --------------------------------------------------------------------------


def _gqa_cache_axes(cfg):
    lanes = ("kv_heads", cfg.hd)     # (KV·D) minor dim, whole heads
    return {"k": ("layers", "batch", "seq", lanes),
            "v": ("layers", "batch", "seq", lanes),
            "ks": ("layers", "batch", "seq", "kv_heads", None),
            "vs": ("layers", "batch", "seq", "kv_heads", None),
            "len": ("layers", "batch")}


def cache_axes(cfg) -> Any:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return _gqa_cache_axes(cfg)
    if fam == "moe":
        if cfg.mla is not None:
            return {"ckv": ("layers", "batch", "seq", None),
                    "krope": ("layers", "batch", None, "seq"),
                    "len": ("layers", "batch")}
        return _gqa_cache_axes(cfg)
    if fam == "ssm":
        return {"conv": ("layers", "batch", None, "mlp"),
                "state": ("layers", "batch", "heads", None, None)}
    if fam == "hybrid":
        return {
            "mamba": {"conv": ("layers", "layers", "batch", None, "mlp"),
                      "state": ("layers", "layers", "batch", "heads", None, None)},
            "attn": _gqa_cache_axes(cfg),
        }
    if fam == "encdec":
        return {
            "self": _gqa_cache_axes(cfg),
            "cross": {"k": ("layers", "batch", "frames", "kv_heads", "head_dim"),
                      "v": ("layers", "batch", "frames", "kv_heads", "head_dim")},
        }
    raise ValueError(fam)


def batch_axes(cfg, shape: ShapeConfig) -> Dict[str, Any]:
    ax: Dict[str, Any] = {}
    specs = input_specs(cfg, shape)
    for k in specs:
        if k == "tokens" or k == "labels":
            ax[k] = ("batch", "seq")
        elif k == "patch_embeds":
            ax[k] = ("batch", "seq", "embed")
        elif k == "frames":
            ax[k] = ("batch", "frames", "embed")
        elif k == "images":
            ax[k] = ("batch", None, None, None)
        elif k == "caches":
            ax[k] = cache_axes(cfg)
    return ax


def _sharding_tree(rules: ShardingRules, spec_tree: Any, axes_tree: Any) -> Any:
    def one(spec, names):
        if names is None:
            return NamedSharding(rules.mesh, P())
        return NamedSharding(rules.mesh,
                             rules.activation_spec(names, spec.shape))

    def rec(spec, names):
        if spec is None:  # e.g. whisper prefill: cross-KV built by the step
            return None
        if isinstance(spec, dict):
            return {k: rec(spec[k], (names or {}).get(k) if isinstance(names, dict)
                           else None) for k in spec}
        return one(spec, names)

    return rec(spec_tree, axes_tree)


def param_sharding_tree(rules: ShardingRules, pspecs: Any, paxes: Any) -> Any:
    def rec(spec, names):
        if isinstance(spec, dict):
            return {k: rec(spec[k], names[k]) for k in spec}
        return NamedSharding(rules.mesh, rules.param_spec(names, spec.shape))

    return rec(pspecs, paxes)


# --------------------------------------------------------------------------
# cell lowering
# --------------------------------------------------------------------------


def _lower_cell(cfg, shape: ShapeConfig, mesh, rules: ShardingRules):
    """Build + lower + compile the step fn of one cell; return (compiled, s)."""
    pspecs, paxes = param_specs(cfg)
    pshard = param_sharding_tree(rules, pspecs, paxes)
    ispecs = input_specs(cfg, shape)
    ishard = _sharding_tree(rules, ispecs, batch_axes(cfg, shape))
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    rep = NamedSharding(mesh, P())

    t0 = time.time()
    with use_rules(rules):
        if shape.kind == "train":
            opt_cfg = opt_mod.OptConfig()
            step = make_train_step(cfg, opt_cfg)
            ospecs = jax.eval_shape(opt_mod.init_opt_state, pspecs)
            oshard = {"m": pshard, "v": pshard, "master": pshard, "step": rep}
            fn = jax.jit(step,
                         in_shardings=(pshard, oshard, ishard, rep),
                         donate_argnums=(0, 1))
            lowered = fn.lower(pspecs, ospecs, ispecs, key_spec)
        elif shape.kind == "prefill":
            def prefill_step(params, batch, key):
                caches = batch.pop("caches")
                ctx = Ctx.make(cfg, key, mode="sim" if cfg.cim.mode != "off" else "off")
                logits, caches = tf_mod.forward(params, batch, cfg, ctx, caches)
                return logits[:, -1], caches

            fn = jax.jit(prefill_step, in_shardings=(pshard, ishard, rep))
            lowered = fn.lower(pspecs, ispecs, key_spec)
        else:  # decode
            def serve_step(params, tokens, caches, key):
                ctx = Ctx.make(cfg, key, mode="sim" if cfg.cim.mode != "off" else "off")
                logits, caches = tf_mod.forward(
                    params, {"tokens": tokens}, cfg, ctx, caches)
                return logits[:, -1], caches

            fn = jax.jit(serve_step,
                         in_shardings=(pshard, ishard["tokens"], ishard["caches"], rep),
                         donate_argnums=(2,))
            lowered = fn.lower(pspecs, ispecs["tokens"], ispecs["caches"], key_spec)

        compiled = lowered.compile()
    return compiled, time.time() - t0


def _analyze(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # per-device list on some jaxlib versions
        cost = cost[0] if cost else {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collective": coll}


def _depth_variant(cfg, n_scan: int):
    """Same arch with n_scan *unrolled* layers (XLA cost_analysis counts
    while-loop bodies once, so the extrapolation variants must not scan)."""
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, n_layers=n_scan * cfg.attn_period,
                                   scan_layers=False)
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, n_layers=n_scan, n_enc_layers=n_scan,
                                   scan_layers=False)
    return dataclasses.replace(cfg, n_layers=n_scan, scan_layers=False)


def _scan_depth(cfg) -> int:
    return cfg.n_layers // cfg.attn_period if cfg.family == "hybrid" else cfg.n_layers


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "experiments/dryrun",
             seq_shard_long: bool = True,
             serve_fsdp: bool = True,
             overrides: Optional[Dict[str, Any]] = None,
             rules_fn=None) -> Dict[str, Any]:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    tag = f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}"

    if shape_name == "long_500k" and not cfg.subquadratic:
        return {"cell": tag, "status": "skipped",
                "reason": "full-attention arch at 500k ctx (DESIGN.md §6)"}

    long_ctx = shape_name == "long_500k"
    if rules_fn is None:
        # Replicated-param + seq-sharded-KV serving (§Perf cell C) pays off
        # when the KV cache/attention dominates and the weights fit HBM
        # after TP: dense-family decode. It *hurts* MoE (expert params >>
        # cache; replication doesn't fit), SSM (O(1) state, batch=1 work
        # just gets duplicated) and long_500k (already seq-sharded) —
        # measured in EXPERIMENTS §Roofline-optimized notes.
        # canonical axis roles resolved through distributed.sharding — the
        # same helpers the deploy-time plane sharding uses, so a dryrun spec
        # and a live deploy spec can never disagree on axis names.
        tp = tp_axis(mesh)
        dp = dp_axes(mesh)
        model_deg = mesh.shape.get(tp, 1) if tp else 1
        params_rep_bytes = cfg.param_count() * 2 / model_deg
        replicate_ok = (
            shape.kind == "decode" and not long_ctx and not serve_fsdp
            and cfg.family in ("dense", "vlm", "hybrid", "encdec")
            and params_rep_bytes <= 12e9
        )
        fsdp = not replicate_ok
        seq_axis = None
        if long_ctx and seq_shard_long and dp:
            seq_axis = dp[-1]
        elif replicate_ok:
            seq_axis = tp
        rules = default_rules(mesh, fsdp_params=fsdp, seq_axis=seq_axis)
    else:
        rules = rules_fn(mesh, cfg, shape)

    # full-depth compile: the runnability proof + memory analysis
    compiled, lower_s = _lower_cell(cfg, shape, mesh, rules)
    mem = compiled.memory_analysis()
    full = _analyze(compiled)

    # XLA cost_analysis counts while-loop (scan) bodies ONCE — correct by
    # two-point depth extrapolation: cost(L) = cost(1) + (L-1) * delta.
    L = _scan_depth(cfg)
    a1 = _analyze(_lower_cell(_depth_variant(cfg, 1), shape, mesh, rules)[0])
    a2 = _analyze(_lower_cell(_depth_variant(cfg, 2), shape, mesh, rules)[0])

    def corrected(key):
        if key == "collective":
            d = {k: a1["collective"].get(k, 0.0)
                 + (L - 1) * (a2["collective"].get(k, 0.0) - a1["collective"].get(k, 0.0))
                 for k in set(a1["collective"]) | set(a2["collective"])}
            return d
        return a1[key] + (L - 1) * (a2[key] - a1[key])

    flops = corrected("flops")
    bytes_acc = corrected("bytes_accessed")
    coll = corrected("collective")
    terms = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll.get("total", 0.0) / ICI_BW,
    }
    dominant = max(terms, key=terms.get)

    result = {
        "cell": tag,
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": list(mesh.shape.values()),
        "chips": int(mesh.devices.size),
        "compile_s": round(lower_s, 1),
        "scan_depth": L,
        "per_device": {
            "flops": flops,
            "bytes_accessed": bytes_acc,
            "collective_bytes": coll,
            "raw_module": full,
            "memory_analysis": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            },
        },
        "roofline": {**terms, "dominant": dominant},
        "param_count": cfg.param_count(),
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="beyond-paper serving layout (replicated params + "
                         "seq-sharded KV for decode) — §Perf defaults")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else args.arch.split(",")
    shapes = [s.name for s in SHAPES] if args.shape == "all" else args.shape.split(",")
    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'pod2' if mp else 'pod1'}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip existing] {tag}")
                    continue
                try:
                    r = run_cell(arch, shape, mp, args.out,
                                 serve_fsdp=not args.optimized)
                    if r["status"] == "ok":
                        ra = r["roofline"]
                        print(f"[ok]   {tag:55s} compile={r['compile_s']:7.1f}s "
                              f"dom={ra['dominant']:13s} "
                              f"c={ra['compute_s']:.3e} m={ra['memory_s']:.3e} "
                              f"x={ra['collective_s']:.3e}")
                    else:
                        print(f"[SKIP] {tag:55s} {r['reason']}")
                        with open(path, "w") as f:
                            json.dump(r, f, indent=1)
                except Exception as e:
                    failures += 1
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                    traceback.print_exc()
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
