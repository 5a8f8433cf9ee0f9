"""IBM Granite 4.0-H (Hugging Face ``granitemoehybrid``) behind the family
interface (harness.load_family): its sizes, the program's architecture,
seeded weights, the plain float32 reference and the counts.

A decoder layer is a pre-norm mixer, Mamba-2 or NoPE GQA attention as
``layer_types`` says, then a pre-norm mixture of experts in every layer:

    x = x + residual_multiplier * mixer(RMSNorm(x))
    h = RMSNorm(x)
    x = x + residual_multiplier * (sum_{e in top_k} g_e SwiGLU_e(h)
                                   + SwiGLU_shared(h))

with the router's logits h Wr over all ``published.num_local_experts``
experts, their top ``num_experts_per_tok`` picked and g the softmax over
the picked logits. The embedding is multiplied by
``embedding_multiplier``, attention scores are q.k times
``attention_multiplier`` (not 1/sqrt(head_dim)), the head is the
embedding (tied) and its logits are divided by ``logits_scaling``.

The Mamba-2 mixer is the plain recurrence of Dao and Gu, "Transformers
are SSMs" (arXiv:2405.21060), section 7: from the pre-norm input h,
z = h Wz and dt = h Wdt; x, B, C = SiLU(causal depthwise conv of
[h Wx, h WB, h WC] plus bias); dt = softplus(dt + dt_bias) and
A = -exp(A_log) per head; then, one position after another,

    state_t = exp(dt_t A) state_{t-1} + dt_t x_t B_t^T   [heads, P, N]
    y_t     = state_t C_t + D x_t

and the output RMSNorm(y * SiLU(z)) Wout, the gated norm over all of
d_inner (one group).

The chip's share (the configuration's ``deployment``): this chip holds
``num_local_experts`` of each layer's experts, those from ``HELD_OFFSET``
on, and computes their part of the routed sum; the part of the experts
held on other chips is left out, here and in the program alike. The
router keeps its published width. Expert e's weights are a function of
(seed, layer, e), so every share of a layer is cut from one model.

The reference is float32 with every contraction at HIGHEST precision,
no kernels, no cache and no batching, and imports nothing of the
program. Departures from the published block, all of them the
program's layout: the Mamba input projection is held as five matrices,
not one; the conv's weights as [width, channels]; each expert's input
projection as two matrices (gate, up), not one of twice the width;
every norm weight as an offset from 1.
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
HELD_OFFSET = 0          # the first of the deployment's expert shares
BF16, F32 = 2, 4


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    norm_eps: float
    max_ctx: int
    kinds: tuple                 # "mamba" | "attn", one per layer
    d_state: int
    ssm_head: int
    expand: int
    conv: int
    chunk: int
    n_experts: int               # the router's width (published)
    n_held: int                  # experts held on this chip
    top_k: int
    d_ff: int                    # one expert's width
    shared_ff: int
    emb_mult: float
    res_mult: float
    attn_mult: float
    logits_scaling: float

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head

    @property
    def period(self) -> int:
        return self.n_layers // self.kinds.count("attn")

    @property
    def held_hits(self) -> float:
        """Routed experts per token that land on this chip, expected
        over uniform routing: top_k * n_held / n_experts."""
        return self.top_k * self.n_held / self.n_experts


def dims(c: dict) -> Dims:
    """The published keys; ``layer_types`` is the published list, of
    which the first ``num_hidden_layers`` are run."""
    n = c["num_hidden_layers"]
    kinds = tuple(KINDS[t] for t in c["layer_types"][:n])
    d = Dims(
        n_layers=n, d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        vocab=c["vocab_size"], norm_eps=c["rms_norm_eps"],
        max_ctx=c["max_position_embeddings"], kinds=kinds,
        d_state=c["mamba_d_state"], ssm_head=c["mamba_d_head"],
        expand=c["mamba_expand"], conv=c["mamba_d_conv"],
        chunk=c["mamba_chunk_size"],
        n_experts=c["published"]["num_local_experts"],
        n_held=c["num_local_experts"], top_k=c["num_experts_per_tok"],
        d_ff=c["intermediate_size"],
        shared_ff=c["shared_intermediate_size"],
        emb_mult=float(c["embedding_multiplier"]),
        res_mult=float(c["residual_multiplier"]),
        attn_mult=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]))
    p, first = d.period, kinds.index("attn")
    if (kinds != tuple("attn" if i % p == first else "mamba"
                       for i in range(n))
            or c["mamba_n_groups"] != 1 or d.ssm_heads != c["mamba_n_heads"]
            or c["position_embedding_type"] != "nope"
            or not c["tie_word_embeddings"] or c["attention_bias"]
            or c["mamba_proj_bias"] or not c["mamba_conv_bias"]
            or not 0 < HELD_OFFSET + d.n_held <= d.n_experts):
        raise ValueError("expected periods of Mamba-2 layers and one NoPE "
                         "attention layer without biases, one group, a tied "
                         "head, and the held experts within the router's")
    return d


def arch(d: Dims, name: str):
    from repro.configs.base import ArchConfig, MoEConfig, SSMConfig
    return ArchConfig(
        name=name, family="hybrid", n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, head_dim=d.head_dim,
        d_ff=0, vocab_size=d.vocab, use_rope=False, tie_embeddings=True,
        norm_eps=d.norm_eps, attn_every=d.period,
        attn_offset=d.kinds.index("attn"),
        ssm=SSMConfig(d_state=d.d_state, head_dim=d.ssm_head,
                      expand=d.expand, chunk=d.chunk, conv_dim=d.conv),
        moe=MoEConfig(n_experts=d.n_experts, top_k=d.top_k, d_ff=d.d_ff,
                      n_held=d.n_held, held_offset=HELD_OFFSET,
                      shared_d_ff=d.shared_ff),
        embedding_multiplier=d.emb_mult, residual_multiplier=d.res_mult,
        logits_scaling=d.logits_scaling, attention_multiplier=d.attn_mult)


# -------------------------------------------------------------- weights
def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(W.DTYPE)


def _experts(key, d: Dims):
    """The router (over every expert), the held experts, each a function
    of its global index, and the shared expert."""
    ks = jax.random.split(key, 5)
    dm, ff, sf = d.d_model, d.d_ff, d.shared_ff

    def expert(e):
        k = jax.random.split(jax.random.fold_in(ks[1], e), 3)
        return (_normal(k[0], (dm, ff), dm ** -0.5),
                _normal(k[1], (dm, ff), dm ** -0.5),
                _normal(k[2], (ff, dm), ff ** -0.5))

    eg, eu, ed = jax.vmap(expert)(HELD_OFFSET + jnp.arange(d.n_held))
    return {"ln2": _normal(ks[0], (dm,), 0.1),
            "router": _normal(ks[2], (dm, d.n_experts), dm ** -0.5),
            "ewg": eg, "ewu": eu, "ewd": ed,
            "swg": _normal(ks[3], (dm, sf), dm ** -0.5),
            "swu": _normal(jax.random.fold_in(ks[3], 1), (dm, sf), dm ** -0.5),
            "swd": _normal(ks[4], (sf, dm), sf ** -0.5)}


def _mamba(key, d: Dims):
    """One Mamba-2 mixer. A_log, dt_bias and D are float32, as the
    program holds them; softplus(dt_bias) spans [0.001, 0.1]."""
    ks = jax.random.split(key, 13)
    dm, di, n, nh = d.d_model, d.d_inner, d.d_state, d.ssm_heads
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
    }


def _attention(key, d: Dims):
    ks = jax.random.split(key, 5)
    dm, h, kv, hd = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim
    return {"ln1": _normal(ks[0], (dm,), 0.1),
            "wq": _normal(ks[1], (dm, h, hd), dm ** -0.5),
            "wk": _normal(ks[2], (dm, kv, hd), dm ** -0.5),
            "wv": _normal(ks[3], (dm, kv, hd), dm ** -0.5),
            "wo": _normal(ks[4], (h, hd, dm), (h * hd) ** -0.5)}


def layer_kinds(d: Dims):
    return list(d.kinds)


def make_layer(key, i, d: Dims, kind: str):
    """(seed key, i) -> layer i's weights: its mixer and its experts."""
    k = jax.random.fold_in(jax.random.fold_in(key, 5), i)
    mixer = _mamba(k, d) if kind == "mamba" else _attention(k, d)
    return {**mixer, **_experts(jax.random.fold_in(k, 1), d)}


def make_outer(key, d: Dims):
    """The embedding (also the head) and the final norm. The embedding's
    std is 1 / (4 embedding_multiplier sqrt(d_model)): in the tied head
    a token's own embedding then adds a quarter of the residual's RMS,
    in standard deviations of the logits, to its own logit, and does not
    outweigh what the layers add (at std 0.02 it would add about fifteen
    at d_model 4096, and nearly every greedy token would repeat its
    input, whatever the layers computed)."""
    ks = jax.random.split(jax.random.fold_in(key, 2), 2)
    std = 0.25 / (d.emb_mult * d.d_model ** 0.5)
    return {"embed": _normal(ks[0], (d.vocab, d.d_model), std),
            "final_norm": _normal(ks[1], (d.d_model,), 0.1)}


def to_program(w, d: Dims):
    """One scanned period: position j holds the layers i = j (mod
    period), stacked [n_periods, ...], from weights.make's
    {"layers": {"mamba": [n_mamba, ...], "attn": [n_attn, ...]}, ...};
    the router in float32, as the program holds it."""
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
        moe = {"router": L["router"].astype(jnp.float32),
               "wg": L["ewg"], "wu": L["ewu"], "wd": L["ewd"],
               "shared": {"wg": L["swg"], "wu": L["swu"], "wd": L["swd"]}}
        stack.append({"ln1": L["ln1"], "mixer": mixer, "ln2": L["ln2"],
                      "ffn": {"moe": moe}})
    return {"embed": w["embed"], "stack": stack,
            "final_norm": w["final_norm"]}


# ------------------------------------------------------------ reference
def _mixer_mamba(h, w, d: Dims, act):
    """The Mamba-2 mixer over the sequence h [S, d] (pre-normed)."""
    S, di, n = h.shape[0], d.d_inner, d.d_state
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
    y = R._rms(y.reshape(S, di) * jax.nn.silu(z), w["norm"], d.norm_eps)
    return jnp.dot(act(y), w["wout"], precision=HI)


def _mixer_attention(h, w, d: Dims, act, q_block: int):
    """Causal NoPE GQA attention over h [S, d] (pre-normed), scores
    q.k * attention_multiplier."""
    q = act(jnp.einsum("sd,dhk->shk", h, w["wq"], precision=HI))
    k = act(jnp.einsum("sd,dhk->shk", h, w["wk"], precision=HI))
    v = act(jnp.einsum("sd,dhk->shk", h, w["wv"], precision=HI))
    S, g = h.shape[0], d.n_heads // d.n_kv_heads
    qg = q.reshape(S, d.n_kv_heads, g, d.head_dim) * d.attn_mult

    def block(i):   # queries [i*qb, (i+1)*qb) against every key
        qb = jax.lax.dynamic_slice_in_dim(qg, i * q_block, q_block, 0)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI)
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // q_block))
    o = act(o.reshape(S, d.n_heads, d.head_dim))
    return jnp.einsum("shk,hkd->sd", o, w["wo"], precision=HI)


def moe(h, w, d: Dims, act):
    """The held experts' part of the routed sum plus the shared expert,
    over h [S, d] (pre-normed): every held expert runs every position
    densely, weighted by its gate (zero where it was not picked)."""
    logits = jnp.dot(h, w["router"], precision=HI)             # [S, E]
    top, idx = jax.lax.top_k(logits, d.top_k)
    g = jax.nn.softmax(top, axis=-1)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(h.shape[0])[:, None], idx].set(g)
    gates = gates[:, HELD_OFFSET:HELD_OFFSET + d.n_held]      # [S, held]
    a = jax.nn.silu(jnp.einsum("sd,edf->sef", h, w["ewg"], precision=HI)) \
        * jnp.einsum("sd,edf->sef", h, w["ewu"], precision=HI)
    y = jnp.einsum("sef,efd,se->sd", act(a), w["ewd"], gates, precision=HI)
    s = jax.nn.silu(jnp.dot(h, w["swg"], precision=HI)) \
        * jnp.dot(h, w["swu"], precision=HI)
    return y + jnp.dot(act(s), w["swd"], precision=HI)


def forward_layer(x, w, pos, d: Dims, kind: str, fp8: bool, q_block: int):
    """One decoder layer over the whole sequence x [S, d_model]."""
    act = R.row_act(fp8)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    if fp8:   # every matmul weight per output channel (input axes)
        cols = {"wx", "wz", "wB", "wC", "wdt", "wout", "wq", "wk", "wv",
                "router", "swg", "swu", "swd"}
        w = {k: (R._q8(v, 0) if k in cols
                 else R._q8(v, (0, 1)) if k == "wo"
                 else R._q8(v, 1) if k in ("ewg", "ewu", "ewd") else v)
             for k, v in w.items()}
    h = act(R._rms(x, w["ln1"], d.norm_eps))
    y = (_mixer_mamba(h, w, d, act) if kind == "mamba"
         else _mixer_attention(h, w, d, act, q_block))
    x = x + d.res_mult * y
    h = act(R._rms(x, w["ln2"], d.norm_eps))
    return x + d.res_mult * moe(h, w, d, act)


def embed(outer, tokens, d: Dims):
    return outer["embed"][tokens].astype(jnp.float32) * d.emb_mult


def head(x, outer, fp8, d: Dims):
    """Final RMSNorm, the tied head, logits / logits_scaling."""
    h = R._rms(x, outer["final_norm"].astype(jnp.float32), d.norm_eps)
    w = outer["embed"].astype(jnp.float32)                     # [V, d]
    if fp8:
        h, w = R._q8(h, -1), R._q8(w, 1)
    return jnp.einsum("sd,vd->sv", h, w, precision=HI) / d.logits_scaling


# --------------------------------------------------------------- counts
def _counts(d: Dims):
    """(FLOPs per token outside attention's context and the head,
    attention layers, attention FLOPs per layer per key). Routed experts
    count at ``held_hits`` per token: the chip's expected share."""
    di, n, nh = d.d_inner, d.d_state, d.ssm_heads
    n_attn = d.kinds.count("attn")
    attn = 2 * (d.d_model * (d.n_heads + 2 * d.n_kv_heads) * d.head_dim
                + d.n_heads * d.head_dim * d.d_model)
    # projections, conv taps, and the state's update (2) and read (2)
    mamba = 2 * (d.d_model * (2 * di + 2 * n + nh) + di * d.d_model) \
        + 2 * d.conv * (di + 2 * n) + 4 * nh * d.ssm_head * n
    ffn = 2 * d.d_model * d.n_experts \
        + d.held_hits * 6 * d.d_model * d.d_ff + 6 * d.d_model * d.shared_ff
    per_token = n_attn * attn + (d.n_layers - n_attn) * mamba \
        + d.n_layers * ffn
    return per_token, n_attn, 4 * d.n_heads * d.head_dim


def prefill_flops(d: Dims, p: int) -> float:
    per_token, n_attn, per_key = _counts(d)
    return p * per_token + n_attn * per_key * (p * (p + 1) // 2) \
        + 2 * d.d_model * d.vocab


def decode_run_flops(d: Dims, first_keys: int, n: int) -> float:
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
    kv = 2 * keys * d.n_kv_heads * d.head_dim * BF16
    qo = n * 2 * d.n_heads * d.head_dim * BF16
    return n_attn * per_key * keys, n_attn * (kv + qo)


def ssm_step_call(d: Dims, lanes: int):
    """(flops, bytes) of one ``_ssm_step_kernel`` call over ``lanes``
    lanes of one layer: every lane's f32 state read and written once,
    its x, dt, B and C read and y written (the conv state is not the
    kernel's); decay, rank-1 update and read-out are 5 FLOPs a state
    element."""
    state = d.ssm_heads * d.ssm_head * d.d_state
    vecs = 2 * d.ssm_heads * d.ssm_head + d.ssm_heads + 2 * d.d_state
    return lanes * 5 * state, lanes * (2 * state + vecs) * F32


def moe_gmm_call(d: Dims, rows: int, n_out: int):
    """(flops, bytes) of one expert grouped-matmul call whose result is
    [rows, n_out]: the gate or up projection (n_out = d_ff, input
    d_model) or the down projection (n_out = d_model, input d_ff). Its
    rows are a bucket of tokens x min(top_k, n_held) assignments; the
    routed ones count at ``held_hits`` per token, and every held
    expert's weights are read once."""
    k_in = d.d_model if n_out == d.d_ff else d.d_ff
    tokens = rows / min(d.top_k, d.n_held)
    hits = tokens * d.held_hits
    return (2 * hits * k_in * n_out,
            (d.n_held * k_in * n_out + hits * (k_in + n_out)) * BF16)
