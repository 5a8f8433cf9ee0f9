"""Sizes of the dense decoders the benchmark serves (GLM-4, Mistral).

A configuration file holds the keys of the model's own published
``config.json`` (with the values changed that its ``reduced`` list in
BENCHMARK.json names), a ``serving`` group for the engine, and notes.
Its ``model_type`` names the family module that reads those keys,
``families/<model_type>.py`` (harness.load_family); the dense families
return a ``Dims``. Another family returns sizes of its own: the harness
and traffic.py read only ``n_layers``, ``d_model``, ``vocab`` and
``max_ctx`` of them.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    max_ctx: int
