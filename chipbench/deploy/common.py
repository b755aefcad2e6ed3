"""Pieces every deployment shares: the model configuration from a
configuration file, and keys and seeds drawn from `--seed`."""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict

import numpy as np

#: configuration-file keys (Hugging Face `config.json` names) and the
#: program's `ArchConfig` fields they set
MODEL_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}

#: streams of randomness drawn from one `--seed`
WEIGHTS, DATA, SAMPLE = 1, 2, 3


def arch_config(cfg: Dict[str, Any]):
    """The program's `ArchConfig` for the file's `arch`, with every size
    the file states (head size is hidden size over heads, as the source
    defines it)."""
    from repro.configs import get_config
    fields = {f: cfg[k] for k, f in MODEL_KEYS.items() if k in cfg}
    fields["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    return dataclasses.replace(get_config(cfg["arch"]), **fields)


def child_seed(seed: int, stream: int) -> int:
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def jax_key(seed: int, stream: int):
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def store_of(deployment: Dict[str, Any], root: str):
    from repro.core import FileStore
    if deployment["store"] != "file":
        raise ValueError(f"unknown store {deployment['store']!r}")
    os.makedirs(root, exist_ok=True)
    return FileStore(root, fsync=deployment["fsync"])


def policy_of(deployment: Dict[str, Any]):
    from repro.core import LGA
    if deployment["policy"] != "LGA":
        raise ValueError(f"unknown podding policy {deployment['policy']!r}")
    return LGA()
