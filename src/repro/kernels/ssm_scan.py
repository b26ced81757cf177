"""Fused mamba2 selective-scan decode-step Pallas TPU kernel.

One decode token advances a mamba2 block through four dependent stages —
rolling depthwise conv, SiLU, per-head SSM state recurrence, readout — that
the einsum path (``models/ssm.py`` decode branch) runs as separate XLA ops
with the (B, H, P, N) state round-tripping HBM between them. This kernel
fuses the whole step into one program per row so the state is read once,
updated in VMEM, and written once:

  * grid ``(B,)``, one program per slot row; every operand block is the
    row's own slice (constant index maps for the shared conv weight / decay
    / skip parameters), so there is no dead work to skip — decode cost for
    an SSM block is O(state), independent of context length by
    construction.
  * conv window advance happens in-kernel: the (conv_width-1) cached rows
    and the current in-projection slice are concatenated, reduced against
    the depthwise weight, and the shifted window is emitted alongside the
    new state — the caller stores both, nothing is recomputed.
  * the recurrence ``state = state * exp(dt*A) + dt * (x outer B)`` and the
    readout ``y = state . C + D*x`` are elementwise/broadcast VPU work on
    the VMEM-resident state; no MXU involvement, no intermediate HBM
    tensors.

Matches the einsum decode branch term for term (post-softplus ``dt1`` is
computed by the caller, which owns the in/out projections). Validated
against ``ref.ssm_decode_step_ref`` and the einsum branch in interpret mode
(tests/test_megakernel.py); CPU callers get ``interpret=True`` automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(conv_ref, xbc_ref, w_ref, b_ref, dt_ref, a_ref, dsk_ref,
            state_ref, y_ref, co_ref, so_ref, *, d_inner: int, ngroups: int,
            d_state: int, nheads: int, headdim: int, conv_width: int):
    win = conv_width - 1
    conv_win = jnp.concatenate(
        [conv_ref[0].astype(jnp.float32), xbc_ref[0].astype(jnp.float32)],
        axis=0)                                               # (w, cd)
    w = w_ref[...].astype(jnp.float32)
    conv = jnp.sum(conv_win * w, axis=0) + b_ref[0].astype(jnp.float32)
    xbc_c = jax.nn.silu(conv)                                 # (cd,)
    xs = xbc_c[:d_inner]
    bv = xbc_c[d_inner:d_inner + ngroups * d_state]
    cv = xbc_c[d_inner + ngroups * d_state:]
    xh = xs.reshape(nheads, headdim)                          # (H, P)
    bm = bv.reshape(ngroups, d_state)[0]                      # (N,)
    cm = cv.reshape(ngroups, d_state)[0]
    dt1 = dt_ref[0].astype(jnp.float32)                       # (H,)
    da = jnp.exp(dt1 * a_ref[0].astype(jnp.float32))
    upd = (dt1[:, None, None] * xh[:, :, None]) * bm[None, None, :]
    state = state_ref[0] * da[:, None, None] + upd            # (H, P, N)
    y = (jnp.sum(state * cm[None, None, :], axis=-1)
         + dsk_ref[0].astype(jnp.float32)[:, None] * xh)      # (H, P)
    y_ref[0] = y.reshape(d_inner)
    co_ref[0] = conv_win[1:].astype(co_ref.dtype).reshape(win, -1)
    so_ref[0] = state


@functools.partial(jax.jit, static_argnames=("d_inner", "ngroups", "d_state",
                                             "interpret"))
def ssm_decode_step(
    conv_cache: jnp.ndarray,
    xbc: jnp.ndarray,
    conv_w: jnp.ndarray,
    conv_b: jnp.ndarray,
    dt1: jnp.ndarray,
    a: jnp.ndarray,
    d: jnp.ndarray,
    state: jnp.ndarray,
    d_inner: int,
    ngroups: int,
    d_state: int,
    interpret: bool | None = None,
):
    """One fused mamba2 decode step (conv + SSM recurrence + readout).

    Args:
      conv_cache: (B, conv_width-1, conv_dim) rolling conv window.
      xbc:        (B, 1, conv_dim) current in-projection x/B/C slice.
      conv_w:     (conv_width, conv_dim) depthwise conv weight.
      conv_b:     (conv_dim,) conv bias.
      dt1:        (B, nheads) step sizes, softplus already applied.
      a:          (nheads,) negative decay rate (-exp(A_log)).
      d:          (nheads,) skip gain.
      state:      (B, nheads, headdim, d_state) float32 SSM state.

    Returns:
      (y, new_conv, new_state): y (B, d_inner) float32 pre-gated-norm
      output; new_conv (B, conv_width-1, conv_dim) in conv_cache.dtype;
      new_state (B, nheads, headdim, d_state) float32.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, win, conv_dim = conv_cache.shape
    conv_width = win + 1
    nheads = a.shape[0]
    headdim = d_inner // nheads

    def row2(bi):
        return (bi, 0)

    def row3(bi):
        return (bi, 0, 0)

    def row4(bi):
        return (bi, 0, 0, 0)

    def whole2(bi):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, win, conv_dim), row3),           # conv window
            pl.BlockSpec((1, 1, conv_dim), row3),             # xbc
            pl.BlockSpec((conv_width, conv_dim), whole2),     # conv_w
            pl.BlockSpec((1, conv_dim), whole2),              # conv_b
            pl.BlockSpec((1, nheads), row2),                  # dt1
            pl.BlockSpec((1, nheads), whole2),                # A
            pl.BlockSpec((1, nheads), whole2),                # D
            pl.BlockSpec((1, nheads, headdim, d_state), row4),  # state
        ],
        out_specs=[
            pl.BlockSpec((1, d_inner), row2),                 # y
            pl.BlockSpec((1, win, conv_dim), row3),           # new conv
            pl.BlockSpec((1, nheads, headdim, d_state), row4),  # new state
        ],
    )
    y, new_conv, new_state = pl.pallas_call(
        functools.partial(_kernel, d_inner=d_inner, ngroups=ngroups,
                          d_state=d_state, nheads=nheads, headdim=headdim,
                          conv_width=conv_width),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, d_inner), jnp.float32),
            jax.ShapeDtypeStruct((b, win, conv_dim), conv_cache.dtype),
            jax.ShapeDtypeStruct((b, nheads, headdim, d_state), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(conv_cache, xbc, conv_w, conv_b.reshape(1, -1), dt1,
      a.reshape(1, -1), d.reshape(1, -1), state.astype(jnp.float32))
    return y, new_conv, new_state
