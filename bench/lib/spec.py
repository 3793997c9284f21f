"""A configuration file, read into the sizes the yardstick needs.

The file keeps the published ``config.json`` keys of its source (with the
cuts listed under ``reduced``); this module maps them onto one plain record
that the reference, the FLOP counts and the harness share.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    d_model: int
    n_layers: int
    d_ff: int
    vocab_size: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    dtype: str                       # weights and activations as served


def load_doc(path) -> dict:
    return json.loads(Path(path).read_text())


def model_spec(doc: dict) -> ModelSpec:
    d = int(doc["hidden_size"])
    heads = int(doc["num_attention_heads"])
    return ModelSpec(
        name=doc["name"],
        d_model=d,
        n_layers=int(doc["num_hidden_layers"]),
        d_ff=int(doc["intermediate_size"]),
        vocab_size=int(doc["vocab_size"]),
        n_heads=heads,
        n_kv_heads=int(doc["num_key_value_heads"]),
        head_dim=int(doc.get("head_dim", d // heads)),
        qkv_bias=bool(doc.get("attention_bias", False)),
        rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        tie_embeddings=bool(doc.get("tie_word_embeddings", False)),
        dtype=doc.get("torch_dtype", "bfloat16"),
    )
