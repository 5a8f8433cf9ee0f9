"""A toy hybrid family, added to a copy of the benchmark as new files
only: periods of Mamba-2 layers and NoPE GQA attention layers (as
``layer_types`` lists them), each layer followed by a SwiGLU MLP, read
from the key names of Hugging Face's granitemoehybrid config.

The reference Mamba-2 mixer is the plain recurrence of Dao and Gu,
"Transformers are SSMs" (arXiv:2405.21060), section 7: from the pre-norm
input h, z = h Wz and dt = h Wdt; x, B, C = SiLU(causal depthwise conv
of [h Wx, h WB, h WC] plus bias); dt = softplus(dt + dt_bias) and
A = -exp(A_log) per head; then, one position after another,

    state_t = exp(dt_t A) state_{t-1} + dt_t x_t B_t^T   [heads, P, N]
    y_t     = state_t C_t + D x_t

and the output (RMSNorm(y * SiLU(z)) over d_inner) Wout. float32, every
contraction at HIGHEST precision; it imports nothing of the program.
Departures from the published block, all of them the program's layout:
the input projection is held as five matrices, not one; the conv's
weights as [width, channels]; the gated norm's weight, like every norm
here, as an offset from 1; one group of B and C, as configured.

The attention layers are the dense layer of reference.py without RoPE.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

import reference as R
import weights as W

HI = jax.lax.Precision.HIGHEST
KINDS = {"mamba": "mamba", "attention": "attn"}


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    max_ctx: int
    kinds: tuple                 # "mamba" | "attn", one per layer
    d_state: int
    ssm_head: int
    expand: int
    conv: int
    chunk: int
    qkv_bias: bool = False
    rope_theta: float = 0.0      # NoPE: never read

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head

    @property
    def period(self) -> int:
        return self.n_layers // self.kinds.count("attn")


def dims(c: dict) -> Dims:
    kinds = tuple(KINDS[t] for t in c["layer_types"])
    d = Dims(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        norm_eps=c["rms_norm_eps"], max_ctx=c["max_position_embeddings"],
        kinds=kinds, d_state=c["mamba_d_state"], ssm_head=c["mamba_d_head"],
        expand=c["mamba_expand"], conv=c["mamba_d_conv"],
        chunk=c["mamba_chunk_size"])
    p, first = d.period, kinds.index("attn")
    if (len(kinds) != d.n_layers or kinds != tuple(
            "attn" if i % p == first else "mamba" for i in range(d.n_layers))
            or c["mamba_n_groups"] != 1 or d.ssm_heads != c["mamba_n_heads"]
            or c["position_embedding_type"] != "nope"
            or c["tie_word_embeddings"]):
        raise ValueError("expected periods of Mamba-2 layers and one NoPE "
                         "attention layer, one group, an untied head")
    return d


def arch(d: Dims, name: str):
    from repro.configs.base import ArchConfig, SSMConfig
    return ArchConfig(
        name=name, family="hybrid", n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, head_dim=d.head_dim,
        d_ff=d.d_ff, vocab_size=d.vocab, use_rope=False,
        norm_eps=d.norm_eps, attn_every=d.period,
        attn_offset=d.kinds.index("attn"),
        ssm=SSMConfig(d_state=d.d_state, head_dim=d.ssm_head,
                      expand=d.expand, chunk=d.chunk, conv_dim=d.conv))


# -------------------------------------------------------------- weights
def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(W.DTYPE)


def _mamba(key, d: Dims):
    """One Mamba-2 layer and its MLP. A_log, dt_bias and D are float32,
    as the program holds them; softplus(dt_bias) spans [0.001, 0.1]."""
    ks = jax.random.split(key, 16)
    dm, di, n, nh, ff = d.d_model, d.d_inner, d.d_state, d.ssm_heads, d.d_ff
    dt = jnp.exp(jax.random.uniform(ks[8], (nh,)) * math.log(100.0)
                 + math.log(0.001))
    return {
        "ln1": _normal(ks[0], (dm,), 0.1),
        "wx": _normal(ks[1], (dm, di), dm ** -0.5),
        "wz": _normal(ks[2], (dm, di), dm ** -0.5),
        "wB": _normal(ks[3], (dm, n), dm ** -0.5),
        "wC": _normal(ks[4], (dm, n), dm ** -0.5),
        "wdt": _normal(ks[5], (dm, nh), dm ** -0.5),
        "conv_w": _normal(ks[6], (d.conv, di + 2 * n), d.conv ** -0.5),
        "conv_b": _normal(ks[7], (di + 2 * n,), 0.1),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[9], (nh,), minval=1.0,
                                            maxval=16.0)),
        "D": 1.0 + 0.1 * jax.random.normal(ks[10], (nh,)),
        "norm": _normal(ks[11], (di,), 0.1),
        "wout": _normal(ks[12], (di, dm), di ** -0.5),
        "ln2": _normal(ks[13], (dm,), 0.1),
        "wg": _normal(ks[14], (dm, ff), dm ** -0.5),
        "wu": _normal(ks[15], (dm, ff), dm ** -0.5),
        "wd": _normal(jax.random.fold_in(key, 16), (ff, dm), ff ** -0.5),
    }


def layer_kinds(d: Dims):
    return list(d.kinds)


def make_layer(key, i, d: Dims, kind: str):
    if kind == "attn":       # the dense layer's weights, without biases
        return W.make_layer(key, i, d)
    return _mamba(jax.random.fold_in(jax.random.fold_in(key, 3), i), d)


make_outer = W.make_outer


def to_program(w, d: Dims):
    """One scanned period: position j holds the layers i = j (mod
    period), stacked [n_periods, ...], from weights.make's
    {"layers": {"mamba": [n_mamba, ...], "attn": [n_attn, ...]}, ...}."""
    p = d.period
    stack = []
    for j in range(p):
        kind = d.kinds[j]
        rows = [r for r, i in enumerate(
            i for i, x in enumerate(d.kinds) if x == kind) if i % p == j]
        L = jax.tree.map(lambda a: a[jnp.asarray(rows)], w["layers"][kind])
        if kind == "attn":
            mixer = {k: L[k] for k in ("wq", "wk", "wv", "wo")}
        else:
            mixer = {k: L[k] for k in ("wx", "wz", "wB", "wC", "wdt",
                                       "conv_w", "conv_b", "A_log", "D",
                                       "dt_bias", "norm")}
            mixer["wo"] = L["wout"]
        stack.append({"ln1": L["ln1"], "mixer": mixer, "ln2": L["ln2"],
                      "ffn": {"dense": {k: L[k] for k in ("wg", "wu",
                                                          "wd")}}})
    return {"embed": w["embed"], "stack": stack,
            "final_norm": w["final_norm"], "head": w["head"]}


# ------------------------------------------------------------ reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _mixer(x, w, d: Dims, act):
    """x plus the pre-norm Mamba-2 mixer over the sequence x [S, d]."""
    S, di, n = x.shape[0], d.d_inner, d.d_state
    h = act(_rms(x, w["ln1"], d.norm_eps))
    z = jnp.dot(h, w["wz"], precision=HI)
    dt = jnp.dot(h, w["wdt"], precision=HI)
    xbc = jnp.concatenate([jnp.dot(h, w[k], precision=HI)
                           for k in ("wx", "wB", "wC")], -1)
    pad = jnp.pad(xbc, ((d.conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[k:k + S] * w["conv_w"][k]
                          for k in range(d.conv)) + w["conv_b"])
    xs, B, C = act(xbc[:, :di]), act(xbc[:, di:di + n]), act(xbc[:, di + n:])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])

    def step(state, t):              # state [heads, P, N]
        x_t, dt_t, b_t, c_t = t
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        y = jnp.einsum("hpn,n->hp", state, c_t, precision=HI) \
            + w["D"][:, None] * x_t
        return state, y

    state0 = jnp.zeros((d.ssm_heads, d.ssm_head, n), jnp.float32)
    _, y = jax.lax.scan(step, state0, (
        xs.reshape(S, d.ssm_heads, d.ssm_head), dt, B, C))
    y = _rms(y.reshape(S, di) * jax.nn.silu(z), w["norm"], d.norm_eps)
    return x + jnp.dot(act(y), w["wout"], precision=HI)


def forward_layer(x, w, pos, d: Dims, kind: str, fp8: bool, q_block: int):
    act = R.row_act(fp8)
    if kind == "attn":
        w = R.f32_weights(w, fp8, cols=("wq", "wk", "wv", "wg", "wu", "wd"),
                          heads=("wo",))
        x = R.attention(x, w, pos, d, act, q_block, rope=False)
    else:
        w = R.f32_weights(w, fp8, cols=("wx", "wz", "wB", "wC", "wdt",
                                        "wout", "wg", "wu", "wd"))
        x = _mixer(x, w, d, act)
    return R.swiglu(x, w, d, act)


embed, head = R.embed, R.head


# --------------------------------------------------------------- counts
def _counts(d: Dims):
    """(FLOPs per token outside attention's context and the head,
    attention layers, attention FLOPs per layer per key)."""
    di, n, nh = d.d_inner, d.d_state, d.ssm_heads
    n_attn = d.kinds.count("attn")
    attn = 2 * (d.d_model * (d.n_heads + 2 * d.n_kv_heads) * d.head_dim
                + d.n_heads * d.head_dim * d.d_model)
    # projections, conv taps, and the state's update (2) and read (2)
    mamba = 2 * (d.d_model * (2 * di + 2 * n + nh) + di * d.d_model) \
        + 2 * d.conv * (di + 2 * n) + 4 * nh * d.ssm_head * n
    per_token = n_attn * attn + (d.n_layers - n_attn) * mamba \
        + d.n_layers * 6 * d.d_model * d.d_ff
    return per_token, n_attn, 4 * d.n_heads * d.head_dim


def prefill_flops(d: Dims, p: int) -> int:
    per_token, n_attn, per_key = _counts(d)
    return p * per_token + n_attn * per_key * (p * (p + 1) // 2) \
        + 2 * d.d_model * d.vocab


def decode_run_flops(d: Dims, first_keys: int, n: int) -> int:
    if n <= 0:
        return 0
    per_token, n_attn, per_key = _counts(d)
    keys = n * first_keys + n * (n - 1) // 2
    return n * (per_token + 2 * d.d_model * d.vocab) \
        + n_attn * per_key * keys


def paged_attn_run(d: Dims, first_keys: int, n: int):
    """(flops, bytes) of paged attention over ``n`` decode tokens: the
    attention layers only, as flops.paged_attn_run counts one layer."""
    if n <= 0:
        return 0, 0
    _, n_attn, per_key = _counts(d)
    keys = n * first_keys + n * (n - 1) // 2
    kv = 2 * keys * d.n_kv_heads * d.head_dim * 2
    qo = n * 2 * d.n_heads * d.head_dim * 2
    return n_attn * per_key * keys, n_attn * (kv + qo)
