"""The shared runner of the plain reference forward, and the dense
decoder layer (GLM-4 and Mistral as configured): RMSNorm, GQA attention
with RoPE and optional qkv bias, SwiGLU MLP, untied head. float32
throughout, every matmul at HIGHEST precision, no kernels, no cache, no
batching. It imports nothing of the program and regenerates its weights
from the seed one layer at a time, so it fits beside nothing.

``Reference`` takes from the configuration's family module
(``families/<model_type>.py``, harness.load_family) the kind of each
layer, that layer's weights and the forward of its kind, the outer
weights, the embedding and the head; it owns the padding, the chunked
head, the gaps and the fp8 control. The dense families point at the
layer below (dense.py); ``attention`` and ``swiglu`` are its halves, for
families whose layers share them.

Departures from the published models, kept because the served program
makes them too and the reference follows the configuration as run:
GLM-4 publishes rotary embeddings on half of each head (interleaved
pairs); the program rotates the whole head in rotate-half order, and so
does this reference. RMSNorm weights are held as offsets from 1.

``mode="fp8"`` is the control: every matmul operand (weights per output
channel, activations per row, q/k/v per head row) is rounded through
float8_e4m3 with an absmax scale, the step below the bf16 the
configuration serves in. It must fail the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
from dims import Dims

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round x through float8_e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head; x [S, n, hd]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def f32_weights(w, fp8: bool, cols=(), heads=()):
    """w in float32; with ``fp8`` the weights named in ``cols`` rounded
    through fp8 per output channel (scale over the input axis 0), those
    in ``heads`` over their (head, head_dim) input axes."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    if fp8:
        w = {k: (_q8(v, 0) if k in cols else _q8(v, (0, 1)) if k in heads
                 else v)
             for k, v in w.items()}
    return w


def row_act(fp8: bool):
    """The rounding of an activation that enters a matmul: per row
    through fp8 in the control, none otherwise."""
    return (lambda a: _q8(a, -1)) if fp8 else (lambda a: a)


def attention(x, w, pos, d, act, q_block: int, rope: bool = True):
    """x plus the pre-norm (``ln1``) GQA attention sublayer over the
    whole sequence x [S, d_model], causal, with RoPE unless ``rope`` is
    False (NoPE)."""
    h = act(_rms(x, w["ln1"], d.norm_eps))
    q = jnp.einsum("sd,dhk->shk", h, w["wq"], precision=HI)
    k = jnp.einsum("sd,dhk->shk", h, w["wk"], precision=HI)
    v = jnp.einsum("sd,dhk->shk", h, w["wv"], precision=HI)
    if d.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if rope:
        q = _rope(q, pos, d.rope_theta)
        k = _rope(k, pos, d.rope_theta)
    q, k, v = act(q), act(k), act(v)
    S, g = x.shape[0], d.n_heads // d.n_kv_heads
    qg = q.reshape(S, d.n_kv_heads, g, d.head_dim) / np.sqrt(d.head_dim)

    def block(i):   # queries [i*qb, (i+1)*qb) against every key
        qb = jax.lax.dynamic_slice_in_dim(qg, i * q_block, q_block, 0)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI)
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // q_block))
    o = act(o.reshape(S, d.n_heads, d.head_dim))
    return x + jnp.einsum("shk,hkd->sd", o, w["wo"], precision=HI)


def swiglu(x, w, d, act):
    """x plus the pre-norm (``ln2``) SwiGLU MLP sublayer."""
    h = act(_rms(x, w["ln2"], d.norm_eps))
    a = jax.nn.silu(jnp.dot(h, w["wg"], precision=HI)) \
        * jnp.dot(h, w["wu"], precision=HI)
    return x + jnp.dot(act(a), w["wd"], precision=HI)


def _layer_fwd(x, w, pos, d: Dims, fp8: bool, q_block: int):
    """One dense decoder layer over the whole sequence x [S, d_model]."""
    w = f32_weights(w, fp8, cols=("wq", "wk", "wv", "wg", "wu", "wd"),
                    heads=("wo",))
    act = row_act(fp8)
    return swiglu(attention(x, w, pos, d, act, q_block), w, d, act)


class Reference:
    """Teacher-forced logits of the plain forward, one layer at a time.

    Sequences are padded to a power of two (at least ``min_len``) and the
    head runs over ``chunk`` positions at a time, so a run compiles a few
    fixed shapes that the persistent compile cache keeps."""

    def __init__(self, fam, d, seed: int, *, q_block: int = 256,
                 min_len: int = 1024, chunk: int = 512):
        self.key = W.root_key(seed)
        self.min_len, self.chunk = min_len, chunk
        self.kinds = fam.layer_kinds(d)
        self._layer_w = {k: jax.jit(functools.partial(
            fam.make_layer, d=d, kind=k)) for k in set(self.kinds)}
        self.outer = jax.jit(functools.partial(fam.make_outer, d=d))(
            self.key)
        self._embed = jax.jit(functools.partial(fam.embed, d=d))
        self._fwd = {(k, m): jax.jit(functools.partial(
            fam.forward_layer, d=d, kind=k, fp8=(m == "fp8"),
            q_block=q_block))
            for k in set(self.kinds) for m in ("f32", "fp8")}
        self._gaps = jax.jit(functools.partial(
            _chunk_gaps, head=functools.partial(fam.head, d=d)),
            static_argnums=(5,))

    def hidden(self, tokens, mode: str = "f32"):
        """Final-layer activations [S_pad, d_model] of tokens [S]."""
        S = len(tokens)
        n = max(self.min_len, 1 << (S - 1).bit_length())
        toks = np.zeros(n, np.int32)
        toks[:S] = tokens                 # causal: pad keys never reach
        x = self._embed(self.outer, jnp.asarray(toks))
        pos = jnp.arange(n)
        for i, kind in enumerate(self.kinds):
            x = self._fwd[kind, mode](x, self._layer_w[kind](self.key, i),
                                      pos)
        return x

    def gaps(self, prompt, served, control: bool = False):
        """Per served token: the gap by which its reference logit lies
        below the reference's best. With ``control`` also the gaps of
        the tokens the fp8 forward puts first at the same positions.
        Returns numpy arrays {"program": [n], ("fp8": [n])}."""
        n, P = len(served), len(prompt)
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        # logits at positions P-1 .. P+n-2 predict served[0 .. n-1]
        want = np.zeros(-(-n // self.chunk) * self.chunk, np.int32)
        want[:n] = served
        hf = self.hidden(seq)
        hc = self.hidden(seq, "fp8") if control else None
        out = {"program": [], "fp8": []}
        for a in range(0, len(want), self.chunk):
            g = self._gaps(hf, hf if hc is None else hc, self.outer,
                           jnp.asarray(want[a:a + self.chunk]),
                           np.int32(P - 1 + a), control)
            out["program"].append(np.asarray(g[0]))
            if control:
                out["fp8"].append(np.asarray(g[1]))
        res = {"program": np.concatenate(out["program"])[:n]}
        if control:
            res["fp8"] = np.concatenate(out["fp8"])[:n]
        return res


def embed(outer, tokens, d: Dims):
    """The dense families' embedding rows, in float32."""
    return outer["embed"][tokens].astype(jnp.float32)


def head(x, outer, fp8, d: Dims):
    """The dense families' final RMSNorm and untied head."""
    h = _rms(x, outer["final_norm"].astype(jnp.float32), d.norm_eps)
    w = outer["head"].astype(jnp.float32)
    if fp8:
        h, w = _q8(h, -1), _q8(w, 0)
    return jnp.dot(h, w, precision=HI)


def _chunk_gaps(hf, hc, outer, served, lo, control, head):
    """Gaps at positions [lo, lo + len(served)): the served tokens', and
    (control) those of the fp8 forward's first choices, both measured in
    reference logits. Rows past the activations read zero padding."""
    n = served.shape[0]

    def rows_of(h):
        h = jnp.pad(h, ((0, n), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(h, lo, n, 0)

    hf, hc = rows_of(hf), rows_of(hc)
    ref = head(hf, outer, False)
    best = ref.max(-1)
    rows = jnp.arange(ref.shape[0])
    g = best - ref[rows, served]
    if not control:
        return g, g
    t = head(hc, outer, True).argmax(-1)
    return g, best - ref[rows, t]
