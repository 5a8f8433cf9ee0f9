"""Seeded random weights, made on the device in the type they are
served in (bf16), and the map from them onto the program's parameter
tree.

Each decoder layer's weights are a pure function of (seed, layer): the
set-up makes all layers of any family in one jitted call (``make``, from
the family's ``make_layer`` and ``make_outer``), and the plain reference
makes one layer at a time from the same functions after the program is
gone, so it takes nothing the program holds. Projections have
std 1/sqrt(fan_in); the embedding std 0.02; RMSNorm weights are stored
as offsets from 1 (``gamma = 1 + w``, std 0.1) and the qkv biases have
std 0.02, so that every parameter moves the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dims import Dims

DTYPE = jnp.bfloat16


def root_key(seed: int):
    """A key from any whole-number seed (larger than 32 bits too)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(DTYPE)


def _layer(key, d: Dims):
    ks = jax.random.split(key, 12)
    dm, h, kv, hd, ff = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.d_ff
    w = {
        "ln1": _normal(ks[0], (dm,), 0.1),
        "wq": _normal(ks[1], (dm, h, hd), dm ** -0.5),
        "wk": _normal(ks[2], (dm, kv, hd), dm ** -0.5),
        "wv": _normal(ks[3], (dm, kv, hd), dm ** -0.5),
        "wo": _normal(ks[4], (h, hd, dm), (h * hd) ** -0.5),
        "ln2": _normal(ks[5], (dm,), 0.1),
        "wg": _normal(ks[6], (dm, ff), dm ** -0.5),
        "wu": _normal(ks[7], (dm, ff), dm ** -0.5),
        "wd": _normal(ks[8], (ff, dm), ff ** -0.5),
    }
    if d.qkv_bias:
        w["bq"] = _normal(ks[9], (h, hd), 0.02)
        w["bk"] = _normal(ks[10], (kv, hd), 0.02)
        w["bv"] = _normal(ks[11], (kv, hd), 0.02)
    return w


def _layer_key(seed_key, i):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


def _outer(seed_key, d: Dims):
    ks = jax.random.split(jax.random.fold_in(seed_key, 2), 3)
    return {"embed": _normal(ks[0], (d.vocab, d.d_model), 0.02),
            "final_norm": _normal(ks[1], (d.d_model,), 0.1),
            "head": _normal(ks[2], (d.d_model, d.vocab), d.d_model ** -0.5)}


def make(fam, seed: int, d):
    """Every weight of the family ``fam`` (harness.load_family) in one
    jitted call: {"layers": {kind: leaves [layers of that kind, ...]}}
    plus what ``fam.make_outer`` gives ("embed", "final_norm", "head")."""
    kinds = fam.layer_kinds(d)
    rows = {k: np.asarray([i for i, x in enumerate(kinds) if x == k],
                          np.int32) for k in dict.fromkeys(kinds)}

    def build(key):
        layers = {k: jax.lax.map(
            functools.partial(fam.make_layer, key, d=d, kind=k), ix)
            for k, ix in rows.items()}
        return {"layers": layers, **fam.make_outer(key, d)}

    return jax.jit(build)(root_key(seed))


def make_layer(key, i, d: Dims):
    """(seed key, i) -> the dense layer i's weights."""
    return _layer(_layer_key(key, i), d)


def make_outer(key, d: Dims):
    """(seed key) -> the embedding, final norm and head."""
    return _outer(key, d)


def to_program(w, d: Dims):
    """The program's parameter tree (repro.models: one scanned period of
    one layer kind, leaves stacked [n_layers, ...])."""
    L = w["layers"]["dense"]
    mixer = {k: L[k] for k in ("wq", "wk", "wv", "wo")}
    if d.qkv_bias:
        mixer.update(bq=L["bq"], bk=L["bk"], bv=L["bv"])
    layer = {"ln1": L["ln1"], "mixer": mixer, "ln2": L["ln2"],
             "ffn": {"dense": {"wg": L["wg"], "wu": L["wu"], "wd": L["wd"]}}}
    return {"embed": w["embed"], "stack": [layer],
            "final_norm": w["final_norm"], "head": w["head"]}
