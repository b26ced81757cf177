"""Logical-axis sharding: one rules table maps model-space names to mesh axes.

Models annotate activations with ``shard(x, 'batch', 'seq', 'embed')`` and
parameters carry logical-axis tuples built at init; the launcher installs a
``ShardingRules`` for the active mesh and everything resolves through it.

Default rules (DESIGN.md §7):
  * batch    -> ('pod', 'data')   data parallel over pods x data axis
  * heads/kv_heads/mlp/experts/vocab -> 'model'   tensor/expert parallel
  * embed    -> ('pod', 'data') on *parameters* (ZeRO/FSDP; XLA re-gathers
    per layer under scan) — applied via param rules, not activation rules
  * seq      -> None (replicated) normally; 'data' for long-context SP

Axes whose size does not divide the mesh axis resolve to None (replicated) —
e.g. qwen2's 14 heads on a 16-way model axis.

Canonical mesh-axis naming (PR 10): every mesh in the repo — production,
debug, dryrun, replica bench — draws its axis names from ``MESH_AXES`` and
resolves its roles through ``dp_axes`` / ``tp_axis`` / ``pp_axis``. The
dryrun helpers used to hardcode single-host names in three places, which
let a deploy-time spec and a dryrun spec disagree on the same config; now
one table drives both (``ShardingRules._resolve`` consults only
``mesh.shape``, so a devices-free ``VirtualMesh`` runs the *identical*
resolution for configs too big to materialize — that is how the big-config
sharding plans are dryrun-verified without 256 devices).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the one canonical axis vocabulary, outermost first: 'pod' = pipeline /
# cross-pod DCN, 'data' = data parallel (+ FSDP), 'model' = tensor/expert
# parallel. make_production_mesh/make_debug_mesh, the dryrun, the sharded
# deploy and the replica bench all build meshes from these names.
MESH_AXES = ("pod", "data", "model")


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """Axis-name -> size for a Mesh OR a VirtualMesh (anything with a
    ``.shape`` mapping)."""
    return dict(mesh.shape)


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes present on this mesh, canonical order."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def tp_axis(mesh) -> Optional[str]:
    """The tensor/expert-parallel axis, or None (pure-DP mesh)."""
    return "model" if "model" in mesh.shape else None


def pp_axis(mesh) -> Optional[str]:
    """The pipeline axis, or None (single-pod mesh)."""
    return "pod" if "pod" in mesh.shape else None


@dataclasses.dataclass(frozen=True)
class VirtualMesh:
    """Shape-only mesh stand-in: resolves specs without any devices.

    ``ShardingRules._resolve`` consumes only ``mesh.shape``, so a
    VirtualMesh drives the exact same logical-axis -> PartitionSpec
    computation as a live mesh of the same shape — the dryrun-verification
    path for configs whose parameters (deepseek_v2_236b, zamba2_7b) cannot
    be materialized on the test host. ``axis_sizes`` keys must come from
    ``MESH_AXES``.
    """

    axis_sizes: Tuple[Tuple[str, int], ...]

    @staticmethod
    def make(**sizes: int) -> "VirtualMesh":
        bad = [a for a in sizes if a not in MESH_AXES]
        if bad:
            raise ValueError(
                f"unknown mesh axes {bad}: the canonical vocabulary is "
                f"{MESH_AXES} (distributed.sharding)")
        ordered = tuple((a, int(sizes[a])) for a in MESH_AXES if a in sizes)
        return VirtualMesh(axis_sizes=ordered)

    @property
    def shape(self) -> Dict[str, int]:
        return dict(self.axis_sizes)

    @property
    def devices(self):  # parity with Mesh for size accounting
        import numpy as _np
        n = 1
        for _, s in self.axis_sizes:
            n *= s
        return _np.empty((n,), object)


AxisVal = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    activation: Dict[str, AxisVal]
    param: Dict[str, AxisVal]

    # lower = assigned first. 'seq'/'qseq' resolve last so they only take a
    # mesh axis left free by heads/experts (e.g. GQA caches with kv_heads <
    # model-degree shard their seq dim instead — §Perf cell C iteration 2).
    PRIORITY = {"seq": 9, "qseq": 8, "frames": 9}

    def _resolve(self, table: Dict[str, AxisVal], names, shape) -> P:
        # a name given as (name, unit) is a dim merged from ``name`` and a
        # minor dim of ``unit`` (e.g. the (KV·D) cache lanes): it shards in
        # whole units, so divisibility is checked on dim // unit
        names = [n if isinstance(n, tuple) else (n, 1) for n in names]
        order = sorted(range(len(shape)),
                       key=lambda i: self.PRIORITY.get(names[i][0] or "", 1))
        spec = [None] * len(shape)
        used = set()
        for i in order:
            name, unit = names[i]
            dim = shape[i] // unit
            ax = table.get(name)
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            if any(a in used for a in axes):
                continue  # an axis can appear only once in a spec
            size = 1
            for a in axes:
                size *= self.mesh.shape[a]
            if dim % size != 0:
                continue  # non-divisible -> replicate (e.g. 14 heads)
            used.update(axes)
            spec[i] = axes[0] if len(axes) == 1 else axes
        return P(*spec)

    def activation_spec(self, names, shape) -> P:
        return self._resolve(self.activation, names, shape)

    def param_spec(self, names, shape) -> P:
        return self._resolve(self.param, names, shape)

    def param_sharding(self, names, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.param_spec(names, shape))


def default_rules(mesh: Mesh, *, seq_sharded: bool = False,
                  fsdp_params: bool = True,
                  seq_axis: AxisVal = None) -> ShardingRules:
    dp: AxisVal = dp_axes(mesh)
    if len(dp) == 1:
        dp = dp[0]
    if seq_axis is None and seq_sharded and "data" in mesh.shape:
        seq_axis = "data"
    act = {
        "batch": dp,
        "seq": seq_axis,
        # query-seq of attention scores: takes 'model' only when the head
        # dims can't (resolver priority) -> context-parallel attention for
        # archs like qwen2 (14 heads on a 16-way axis). §Perf cell B iter 2.
        "qseq": "model",
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "vocab": "model",
        "state": None,
        "frames": None,
    }
    par = {
        # ZeRO/FSDP: parameters sharded over the DP axes on their largest
        # replicated dim; re-gathered per layer (scan keeps it per-layer).
        "embed": dp if fsdp_params else None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "vocab": "model",
        "layers": None,
        "state": None,
        "conv": None,
        "classes": None,
        "patch": None,
    }
    return ShardingRules(mesh=mesh, activation=act, param=par)


_STATE = threading.local()


def set_rules(rules: Optional[ShardingRules]) -> None:
    _STATE.rules = rules


def get_rules() -> Optional[ShardingRules]:
    return getattr(_STATE, "rules", None)


class use_rules:
    """Context manager installing sharding rules for model tracing."""

    def __init__(self, rules: Optional[ShardingRules]):
        self.rules = rules

    def __enter__(self):
        self.prev = get_rules()
        set_rules(self.rules)
        return self.rules

    def __exit__(self, *exc):
        set_rules(self.prev)


def shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Constrain activation sharding by logical dim names (no-op w/o rules)."""
    rules = get_rules()
    if rules is None:
        return x
    spec = rules.activation_spec(names, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
