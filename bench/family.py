"""The program's ``ModelConfig`` for a configuration file, of any family.

A file keeps its published keys at the top level (what ``reduced``
names). A dense GQA file is read from them alone (``DENSE``). A file of
another family carries a ``program`` block, the keywords of
``ModelConfig`` with each nested object made the dataclass its field's
type names, and a ``published`` map from each of the block's fields
(``moe.n_experts``) to the top-level key that states the same size
(``n_routed_experts``): the two must agree, so the file runs the widths
it publishes. A field with no published counterpart is named in
``unpublished``. How the model is served comes from ``serving``.
"""

from __future__ import annotations

import dataclasses
import functools
import typing

# ModelConfig field -> published key, for a file without ``program``
DENSE = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
         "n_heads": "num_attention_heads",
         "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
         "d_ff": "intermediate_size", "vocab_size": "vocab_size",
         "qkv_bias": "attention_bias", "rope_theta": "rope_theta",
         "norm_eps": "rms_norm_eps", "tie_embeddings": "tie_word_embeddings",
         "dtype": "torch_dtype"}


def published(config: dict) -> dict:
    """The file's map from ``ModelConfig`` fields to published keys."""
    return config["published"] if "program" in config else DENSE


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file; raises
    ``ValueError`` where a ``program`` block departs from its published
    keys or holds a field that neither they nor ``unpublished`` name."""
    from repro.configs.base import CIMModelConfig, ModelConfig

    s = config["serving"]
    program = config.get("program") or dict(
        {f: config[k] for f, k in DENSE.items()}, family="dense")
    mcfg = _dataclass(ModelConfig, dict(
        program, name=config["name"], attn_impl=s["attn_impl"],
        kv_cache_int8=s["kv_cache_dtype"] == "int8",
        cim=CIMModelConfig(mode=s["cim_mode"], policy=s["cim_policy"],
                           act_clip_sigmas=s["act_clip_sigmas"],
                           use_kernel=s["cim_use_kernel"])))
    if "program" in config:
        _check_published(config, mcfg)
    return mcfg


def _dataclass(cls, values: dict):
    """``cls(**values)``, with each dict among the values made the
    dataclass that its field's type names (``Optional[MoEConfig]``:
    ``MoEConfig``)."""
    hints = typing.get_type_hints(cls)
    kw = {}
    for k, v in values.items():
        if isinstance(v, dict):
            t = next(a for a in (hints[k], *typing.get_args(hints[k]))
                     if dataclasses.is_dataclass(a))
            v = _dataclass(t, v)
        kw[k] = v
    return cls(**kw)


def _fields(block: dict, prefix: str = ""):
    """The block's fields as dotted names (``mla.kv_lora``)."""
    for k, v in block.items():
        if isinstance(v, dict):
            yield from _fields(v, f"{prefix}{k}.")
        else:
            yield prefix + k


def _check_published(config: dict, mcfg) -> None:
    pub = config["published"]
    loose = (set(_fields(config["program"])) - {"family"} - set(pub)
             - set(config.get("unpublished", ())))
    if loose:
        raise ValueError(f"program fields with no published key and not "
                         f"in unpublished: {sorted(loose)}")
    for field, key in pub.items():
        got = functools.reduce(getattr, field.split("."), mcfg)
        if got != config[key]:
            raise ValueError(f"program {field} = {got!r}, but the published "
                             f"{key} = {config[key]!r}")
