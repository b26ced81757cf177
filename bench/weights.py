"""Random weights for a configuration, drawn on the device from the seed.

The tree has the layout the system under test takes for a dense GQA
model (``layout``; a test checks it against the program's own ``init``);
every value is drawn here, in one jitted call, in the dtype the model is
served in. The plain reference redraws the same tree from the same seed,
so it uses nothing the program computed.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def layout(config: dict) -> dict:
    """ShapeDtypeStruct tree of a dense GQA configuration's parameters:
    per-layer leaves stacked on a leading layer axis."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    f = config["intermediate_size"]
    dt = jnp.dtype(config["torch_dtype"])

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    def lin(k, m, bias=False):
        p = {"w": leaf(n, k, m)}
        if bias:
            p["b"] = leaf(n, m)
        return p

    bias = config["attention_bias"]
    return {
        "blocks": {
            "attn": {"q": lin(d, q, bias), "k": lin(d, kv, bias),
                     "v": lin(d, kv, bias), "o": lin(q, d)},
            "mlp": {"gate": lin(d, f), "up": lin(d, f), "down": lin(f, d)},
            "n1": {"g": leaf(n, d)}, "n2": {"g": leaf(n, d)},
        },
        "embed": {"e": leaf(config["vocab_size"], d)},
        "final_norm": {"g": leaf(d)},
    }


def _stream(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def _draw(key, path: str, shape, dtype):
    leaf = path.rsplit("[", 1)[-1].strip("]'\"")
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf == "w":                       # (..., d_in, d_out) linear
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
