"""Random weights for a configuration, drawn on the device from the seed.

The tree has the layout of the program's own ``init`` for the
configuration, of any family (``layout``: paths, shapes and dtypes, no
values); every value is drawn here, in one jitted call, in the dtype the
model is served in. The plain reference redraws the same tree from the
same seed, so it uses nothing the program computed.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from bench.family import model_config


def layout(config: dict) -> dict:
    """ShapeDtypeStruct tree of the configuration's parameters, as the
    program's ``init`` builds it: per-layer leaves stacked on a leading
    layer axis. Every leaf is in the file's ``torch_dtype`` or the one its
    ``leaf_dtypes`` names for it; the program does not choose the dtype
    that both sides are drawn in."""
    from repro.models.model import build

    init = build(model_config(config)).init
    tree = jax.eval_shape(lambda k: init(k)[0], jax.random.PRNGKey(0))
    stated = config.get("leaf_dtypes", {})
    for p, s in jax.tree_util.tree_flatten_with_path(tree)[0]:
        path = jax.tree_util.keystr(p)
        want = jnp.dtype(stated.get(path, config["torch_dtype"]))
        if s.dtype != want:
            raise ValueError(f"the program's init makes {path} {s.dtype}; "
                             f"the configuration states {want}")
    return tree


def _stream(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def _draw(key, path: str, shape, dtype):
    leaf = path.rsplit("[", 1)[-1].strip("]'\"")
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("w", "w_gate", "w_up", "w_down"):
        # (..., d_in, d_out) linear; the experts' are stacked per expert
        v = z / jnp.sqrt(jnp.float32(shape[-2]))
    elif leaf == "b":
        v = 0.1 * z
    elif leaf == "g":                     # norm gain
        v = 1.0 + 0.1 * z
    elif leaf == "e":                     # embedding (and tied head)
        v = 0.02 * z
    else:
        raise ValueError(f"no rule to draw weight {path}")
    return v.astype(dtype)


def make(config: dict, seed32: int):
    """The parameter tree for ``seed32``, drawn on the default device in
    one call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout(config))
    specs = [(jax.tree_util.keystr(p), s.shape, s.dtype) for p, s in leaves]

    def init(key):
        return [_draw(jax.random.fold_in(key, _stream(path)), path, shape,
                      dtype) for path, shape, dtype in specs]

    vals = jax.jit(init)(jax.random.PRNGKey(seed32))
    return jax.tree_util.tree_unflatten(treedef, vals)
