"""Pipeline parallelism: GPipe-style stage loop over the 'pod' axis.

Maps the multi-pod mesh's 'pod' axis to pipeline stages: the layer stack is
split into n_pod contiguous stages, microbatches stream through with
``jax.lax.ppermute`` hand-offs inside a shard_map, and the standard GPipe
schedule (n_micro + n_stages - 1 ticks) overlaps stage compute with the ICI
transfer of activations. DP×TP sharding *within* a stage composes with the
remaining ('data', 'model') axes untouched.

This is the optional training topology (DESIGN.md §7): DP×TP×EP is the
deployment default at 512 chips; PP becomes attractive when layer-parallel
memory (or cross-pod DCN bandwidth) dominates — e.g. >1T-param dense stacks.

The implementation is deliberately schedule-transparent: ``pipeline_apply``
takes any per-stage function, so tests validate it against the sequential
stack on a fake 4-device mesh (tests/test_pipeline.py).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import pp_axis


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    axis: Optional[str] = None,
    n_micro: int = 4,
) -> jnp.ndarray:
    """Run x through n_stage stages living on mesh[axis] (GPipe schedule).

    Args:
      stage_fn: (params_for_stage, microbatch) -> microbatch output; the
        same computation on every stage (layers stacked per stage).
      stage_params: pytree with leading dim n_stages, sharded over `axis`.
      x: (batch, ...) global input; batch % n_micro == 0.
      mesh/axis: the pipeline axis (stages = mesh.shape[axis]); None
        resolves the canonical pipeline axis via ``pp_axis(mesh)``.
      n_micro: microbatches in flight.

    Returns: (batch, ...) output of the full stack.
    """
    if axis is None:
        axis = pp_axis(mesh)
        if axis is None:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has no pipeline axis "
                f"(canonical name 'pod'); pass axis= explicitly")
    n_stage = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro
    n_ticks = n_micro + n_stage - 1

    def body(params_l, x_l):
        # params_l: this stage's params (leading dim 1); x_l: full batch
        # (replicated over `axis`) — each stage computes only when its
        # microbatch has arrived: tick t processes micro (t - stage_id).
        params_l = jax.tree.map(lambda t: t[0], params_l)
        stage = jax.lax.axis_index(axis)
        micros = x_l.reshape((n_micro, mb) + x_l.shape[1:])

        def tick(carry, t):
            buf, outs = carry      # buf: microbatch flowing into this stage
            my_micro = t - stage
            take_new = (stage == 0) & (my_micro >= 0) & (my_micro < n_micro)
            inp = jnp.where(
                take_new,
                micros[jnp.clip(my_micro, 0, n_micro - 1)],
                buf)
            active = (my_micro >= 0) & (my_micro < n_micro)
            out = jnp.where(active, stage_fn(params_l, inp), inp)
            # hand off to the next stage (ring permute; last->0 unused)
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stage) for i in range(n_stage)])
            done_micro = t - (n_stage - 1)
            is_done = (stage == n_stage - 1) & (done_micro >= 0) & (done_micro < n_micro)
            outs = jnp.where(
                is_done,
                outs.at[jnp.clip(done_micro, 0, n_micro - 1)].set(out),
                outs)
            return (nxt, outs), None

        # the carries become device-varying after the first ppermute; mark
        # the initial values accordingly (shard_map vma semantics)
        buf0 = jax.lax.pcast(jnp.zeros((mb,) + x_l.shape[1:], x_l.dtype),
                             (axis,), to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(micros), (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        # only the last stage holds real outputs; zero elsewhere -> psum
        outs = jnp.where(stage == n_stage - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
        return outs.reshape((b,) + x_l.shape[1:])

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
    )(stage_params, x)
