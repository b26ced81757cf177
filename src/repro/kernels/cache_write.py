"""In-place column write into a positions-minor cache stack (Pallas TPU).

A decode step adds one position per cache row. For a leaf stored with its
positions minor, ``(L, R, W, T)`` — the MLA rope keys, cached ``(R, T)``
per row (``attention.init_mla_cache``) — that position is one column of
``W`` values in the lane dimension. XLA writes such a column scatter by
relayouting the operand so the positions are no longer minor, and for a
stack small enough to stage on chip (a leading dense layer's one-layer
group) it does: it copies the whole stack there and back. This kernel
writes the column where it is instead: grid ``(B,)``, each step reads
the ``(W, 128)`` tile of its row that holds its position, sets the
column and writes the tile back into the aliased stack. No other tile is
read or written.

A position at or past ``T`` is dropped (its row's tile is written back
unchanged), as ``mode="drop"`` drops it in a scatter. Rows are distinct
(each batch row owns its cache row). CPU callers get ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _kernel(layer_ref, rows_ref, pos_ref, col_ref, in_ref, o_ref, *, lanes,
            t):
    b = pl.program_id(0)
    pos = pos_ref[b]
    base = jnp.minimum(pos // lanes, t // lanes - 1) * lanes
    hit = (jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) == pos - base)
    o_ref[...] = jnp.where(hit & (pos < t), col_ref[...], in_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_columns(stack: jnp.ndarray, cols: jnp.ndarray, layer, rows,
                  pos: jnp.ndarray, interpret: bool | None = None):
    """``stack`` (L, R, W, T) with ``cols[b]`` (W,) written at
    ``[layer, rows[b], :, pos[b]]``, in place.

    Args:
      stack: the positions-minor cache stack; T a multiple of 128 or < 128.
      cols:  (B, W) the new column of each batch row.
      layer: the stack's layer (a traced scalar).
      rows:  (B,) int32 distinct cache rows, or None for ``0..B-1``.
      pos:   (B,) int32 position of each row's column.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_layers, n_rows, w, t = stack.shape
    b = cols.shape[0]
    lanes = min(_LANES, t)
    if t % lanes:
        raise ValueError(f"positions {t} are not whole {lanes}-lane tiles")
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    rows = (jnp.arange(b, dtype=jnp.int32) if rows is None
            else jnp.asarray(rows, jnp.int32).reshape(b))
    pos = jnp.asarray(pos, jnp.int32).reshape(b)

    def tile(bi, layer_ref, rows_ref, pos_ref):
        kb = jnp.minimum(pos_ref[bi] // lanes, t // lanes - 1)
        return (layer_ref[0], rows_ref[bi], 0, kb)

    block = pl.BlockSpec((None, None, w, lanes), tile)
    return pl.pallas_call(
        functools.partial(_kernel, lanes=lanes, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, w, 1), lambda bi, *_: (bi, 0, 0)),
                      block],
            out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        # operand 4 (after the three scalar-prefetch operands and cols)
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="cache_column_write",
    )(layer, rows, pos, cols.astype(stack.dtype)[:, :, None], stack)
