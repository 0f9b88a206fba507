"""A configuration file (``bench/configs/<name>.json``) as the program runs it.

The file holds the published ``config.json`` keys at its top level, with the
chip's share applied and each changed key listed in ``reduced``, beside
``source``, ``deployment``, ``assumed`` and ``registry_base``: the program's
registry entry of the same model. ``register`` replaces that entry's sizes by
the file's and registers the result under the file's own name, so the
program's ``Trainer``, ``Server`` and plane workers run it by name.
"""
from __future__ import annotations

import dataclasses

# the reference's and the weights' view: published keys only
ARCH_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "vocab_size", "rope_theta", "rms_norm_eps", "tie_word_embeddings")

# published key -> the program's ArchConfig field
PROGRAM_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
                  "num_attention_heads": "num_heads",
                  "num_key_value_heads": "num_kv_heads",
                  "head_dim": "head_dim", "intermediate_size": "d_ff",
                  "vocab_size": "vocab_size", "rope_theta": "rope_theta",
                  "rms_norm_eps": "norm_eps",
                  "tie_word_embeddings": "tie_embeddings",
                  "torch_dtype": "dtype",
                  "max_position_embeddings": "max_context"}


def arch(config: dict) -> dict:
    out = {k: config[k] for k in ARCH_KEYS}
    out["rope_theta"] = float(out["rope_theta"])
    out["rms_norm_eps"] = float(out["rms_norm_eps"])
    return out


def program_name(name: str) -> str:
    """The registry name the program runs the configuration under; the
    registry's own entries (``qwen3-0.6b`` ...) keep theirs."""
    return "bench." + name


def register(name: str, config: dict):
    """Register the configuration with the program's registry as
    ``program_name(name)`` (once per process) and return the program's
    ``ArchConfig``."""
    from repro.configs import base as configs
    # loads the repo's own entries first: the registry loads them only
    # while it is empty
    base = configs.get(config["registry_base"])
    name = program_name(name)
    if name in configs.names():
        return configs.get(name)
    fields = {PROGRAM_FIELDS[k]: config[k] for k in PROGRAM_FIELDS}
    fields["rope_theta"] = float(fields["rope_theta"])
    fields["norm_eps"] = float(fields["norm_eps"])
    cfg = dataclasses.replace(base, name=name, family="dense", qk_norm=True,
                              **fields)
    return configs.register(cfg)
