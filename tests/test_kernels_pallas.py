"""Per-kernel Pallas validation (interpret mode on CPU): sweep shapes and
dtypes, assert_allclose against the pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fmmu.types import small_geometry
from repro.kernels import ref
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import mamba_scan as ms
from repro.kernels import ssm_step
from repro.kernels import fmmu_lookup as fl


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("sq,skv,h,kv,d", [
    (128, 128, 4, 4, 32),
    (128, 128, 4, 2, 64),     # GQA
    (64, 192, 2, 1, 32),      # cross-length (right-aligned causal)
    (256, 256, 2, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(sq, skv, h, kv, d, dtype):
    k = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(k, 1), (2, sq, h, d), dtype)
    kk = jax.random.normal(jax.random.fold_in(k, 2), (2, skv, kv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(k, 3), (2, skv, kv, d), dtype)
    out = fa.flash_attention(q, kk, v, causal=True, q_block=64, kv_block=64,
                             interpret=True)
    want = ref.attention_naive(q, kk, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=TOL[dtype],
                               rtol=TOL[dtype])


@pytest.mark.parametrize("kwargs", [
    dict(window=64), dict(softcap=30.0), dict(window=96, softcap=20.0),
    dict(causal=False, bidirectional=True),
])
def test_flash_attention_variants(kwargs):
    k = jax.random.key(1)
    q = jax.random.normal(jax.random.fold_in(k, 1), (1, 256, 4, 64))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(k, 3), (1, 256, 2, 64))
    kwargs.setdefault("causal", True)
    out = fa.flash_attention(q, kk, v, q_block=64, kv_block=64,
                             interpret=True, **kwargs)
    want = ref.attention_naive(q, kk, v, **kwargs)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


def test_flash_attention_unaligned_seq():
    """Sequence not a block multiple -> padded, result identical."""
    k = jax.random.key(2)
    q = jax.random.normal(jax.random.fold_in(k, 1), (1, 100, 2, 32))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (1, 100, 2, 32))
    v = jax.random.normal(jax.random.fold_in(k, 3), (1, 100, 2, 32))
    out = fa.flash_attention(q, kk, v, q_block=64, kv_block=64,
                             interpret=True)
    want = ref.attention_naive(q, kk, v)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


# ----------------------------------------------------------------------
PAGED_SHAPES = [
    (2, 4, 4, 32, 16, 8),
    (3, 8, 2, 64, 8, 6),      # GQA
    (1, 4, 1, 128, 32, 4),
    (5, 32, 16, 128, 16, 16),  # several blocks a lane: _paged_ctx's edges
]
STACKED_CASES = [
    pytest.param(*shape, jnp.float32, which,
                 id="-".join(map(str, (which, *shape))))
    for shape in PAGED_SHAPES for which in ("first", "middle", "last")] + [
    pytest.param(*PAGED_SHAPES[-1], jnp.bfloat16, "last",
                 id="-".join(map(str, ("last", *PAGED_SHAPES[-1],
                                       "bfloat16"))))]


def _paged_ctx(b, page, maxp, kvd, dtype):
    """Contexts of the b lanes: spread over the bucket, or with five
    lanes the kernel's edges — a dead lane (0), 1, exactly one block,
    one past a block boundary, and the full bucket."""
    if b != 5:
        return jnp.asarray([(maxp * page * (i + 1)) // (b + 1) + 1
                            for i in range(b)], jnp.int32)
    ppb = pa.pages_per_block(page, kvd, jnp.dtype(dtype).itemsize, maxp)
    assert 2 * ppb <= maxp, "the bucket must hold two blocks"
    return jnp.asarray([0, 1, ppb * page, ppb * page + 1, maxp * page],
                       jnp.int32)


def _assert_dead_lanes(ctx, out, m, l):
    """A lane with no context reads nothing: output 0, l 0, m -inf
    (what the flash-decoding combine weighs as no keys at all). The
    references attend the masked bucket uniformly there instead."""
    dead = np.asarray(ctx) == 0
    assert (np.asarray(out, np.float32)[dead] == 0).all()
    assert (np.asarray(l)[dead] == 0).all()
    assert (np.asarray(m)[dead] == pa.NEG_INF).all()
    return ~dead


@pytest.mark.parametrize("b,h,kv,d,page,maxp", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_shapes(b, h, kv, d, page, maxp, dtype):
    k = jax.random.key(3)
    nb = b * maxp + 4
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, h, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(k, 2), (nb, page, kv, d), dtype)
    vp = jax.random.normal(jax.random.fold_in(k, 3), (nb, page, kv, d), dtype)
    table = jax.random.permutation(
        jax.random.fold_in(k, 4), jnp.arange(nb))[:b * maxp].reshape(b, maxp)
    ctx = _paged_ctx(b, page, maxp, kv * d, dtype)
    out, (m, l) = pa.paged_attention(q, kp.reshape(nb, page, kv * d),
                                     vp.reshape(nb, page, kv * d), table,
                                     ctx, return_stats=True, interpret=True)
    want, (wm, wl) = ref.paged_attention_naive(q, kp, vp, table, ctx,
                                               return_stats=True)
    live = _assert_dead_lanes(ctx, out, m, l)
    np.testing.assert_allclose(out.astype(np.float32)[live],
                               want.astype(np.float32)[live],
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(m[live], wm[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(l[live], wl[live], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("b,h,kv,d,page,maxp,dtype,which", STACKED_CASES)
def test_paged_attention_stacked_pool(b, h, kv, d, page, maxp, dtype,
                                      which):
    """The whole stack's pool [L, NB, P, KV*D] with a layer index (a
    scalar-prefetch operand of the kernel, a direct pool[layer, table]
    gather in the jnp lowerings) reads exactly that layer: the kernel
    matches both references given the same index, and each path gives
    bit for bit what it gives on that layer's pool alone."""
    from repro.kernels import ops
    n_layers = 3
    li = {"first": 0, "middle": 1, "last": n_layers - 1}[which]
    k = jax.random.key(5)
    nb = b * maxp + 4
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, h, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(k, 2),
                           (n_layers, nb, page, kv * d), dtype)
    vp = jax.random.normal(jax.random.fold_in(k, 3),
                           (n_layers, nb, page, kv * d), dtype)
    table = jax.random.permutation(
        jax.random.fold_in(k, 4), jnp.arange(nb))[:b * maxp].reshape(b, maxp)
    ctx = _paged_ctx(b, page, maxp, kv * d, dtype)
    got, alone = {}, {}
    for impl in ("pallas_interpret", "blocked", "naive"):
        got[impl], _ = jax.jit(lambda q, kp, vp, t, c, li: ops.paged_attention(
            q, kp, vp, t, c, layer=li, return_stats=True,
            impl=impl))(q, kp, vp, table, ctx, jnp.int32(li))
        alone[impl], _ = ops.paged_attention(
            q, kp[li], vp[li], table, ctx, return_stats=True, impl=impl)
        np.testing.assert_array_equal(got[impl], alone[impl])
    want = ref.paged_attention_naive(
        q, kp[li].reshape(nb, page, kv, d), vp[li].reshape(nb, page, kv, d),
        table, ctx)
    tol = 2e-5 if dtype == jnp.float32 else TOL[dtype]
    live = np.asarray(ctx) > 0
    for impl in got:
        np.testing.assert_allclose(np.asarray(got[impl], np.float32)[live],
                                   np.asarray(want, np.float32)[live],
                                   atol=tol, rtol=tol)
    # another layer's pages give another answer: the index is not ignored
    other = ops.paged_attention(q, kp[li - 1], vp[li - 1], table, ctx,
                                impl="naive")
    assert not np.allclose(np.asarray(got["pallas_interpret"], np.float32),
                           np.asarray(other, np.float32), atol=1e-3)


@pytest.mark.parametrize("kwargs", [
    dict(softcap=25.0),
    # windows whose lower pages are dead; at 150 the live pages of the
    # longest lane span two blocks
    dict(window=40), dict(window=40, softcap=25.0), dict(window=150),
], ids=["softcap", "window", "window-softcap", "window-two-blocks"])
def test_paged_attention_softcap(kwargs):
    k = jax.random.key(4)
    b, h, kv, d, page, maxp = 3, 8, 8, 128, 16, 16    # ppb 8 in f32
    nb = b * maxp
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, h, d))
    kp = jax.random.normal(jax.random.fold_in(k, 2), (nb, page, kv, d))
    vp = jax.random.normal(jax.random.fold_in(k, 3), (nb, page, kv, d))
    table = jnp.arange(nb).reshape(b, maxp)
    ctx = jnp.array([17, 30, 250])
    out = pa.paged_attention(q, kp.reshape(nb, page, kv * d),
                             vp.reshape(nb, page, kv * d), table, ctx,
                             interpret=True, **kwargs)
    want = ref.paged_attention_naive(q, kp, vp, table, ctx, **kwargs)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype,window", [(jnp.float32, 0),
                                          (jnp.bfloat16, 40)])
def test_paged_attention_never_reads_dead_pages(window, dtype):
    """Dead pages are never copied, and rows past ctx never count: every
    table entry past a lane's live pages (and below its window) names a
    block outside the pool, which the interpreter's bounds check
    refuses to copy; the rows past ctx in each lane's last live page
    are NaN. The output is finite and equal, bit for bit, to the call
    on the clean table and pool. Lane 0 is dead: no entry is valid."""
    k = jax.random.key(13)
    b, h, kv, d, page, maxp = PAGED_SHAPES[-1]
    nb = b * maxp
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, h, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(k, 2), (nb, page, kv * d),
                           dtype)
    vp = jax.random.normal(jax.random.fold_in(k, 3), (nb, page, kv * d),
                           dtype)
    table = np.arange(nb, dtype=np.int32).reshape(b, maxp)
    ctx = np.array(_paged_ctx(b, page, maxp, kv * d, dtype))
    ctx[1] = 200                       # a page and a half past a window
    live_hi = -(-ctx // page)
    live_lo = np.maximum(ctx - window, 0) // page if window else 0 * ctx
    j = np.arange(maxp)[None, :]
    dead = (j >= live_hi[:, None]) | (j < live_lo[:, None])
    poisoned = np.where(dead, nb + 1000 + j, table).astype(np.int32)
    kpn, vpn = np.array(kp), np.array(vp)
    for lane in range(b):
        if ctx[lane] % page:
            last = table[lane, live_hi[lane] - 1]
            kpn[last, ctx[lane] % page:] = np.nan
            vpn[last, ctx[lane] % page:] = np.nan
    run = lambda t, kk, vv: pa.paged_attention(
        q, jnp.asarray(kk), jnp.asarray(vv), jnp.asarray(t),
        jnp.asarray(ctx), window=window, return_stats=True, interpret=True)
    clean, (cm, cl) = run(table, kp, vp)
    got, (m, l) = run(poisoned, kpn, vpn)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    np.testing.assert_array_equal(m, cm)
    np.testing.assert_array_equal(l, cl)
    _assert_dead_lanes(ctx, got, m, l)


@pytest.mark.parametrize("kernel", ["flash", "paged"])
def test_masked_calls_never_fall_back_from_pallas(kernel):
    """The Pallas kernels take no segment/page masks: asking for Pallas
    with a mask raises rather than quietly running the blocked jnp
    lowering, which on a TPU would hide the device path."""
    from repro.kernels import ops
    if kernel == "flash":
        x = jnp.zeros((1, 64, 2, 32))
        segs = jnp.ones((1, 64), jnp.int32)
        call = lambda impl: ops.flash_attention(
            x, x, x, segment_ids=(segs, segs), impl=impl)
    else:
        q = jnp.zeros((2, 4, 32))
        pool = jnp.zeros((8, 8, 2 * 32))
        table = jnp.arange(8).reshape(2, 4)
        call = lambda impl: ops.paged_attention(
            q, pool, pool, table, jnp.array([5, 9]),
            page_mask=jnp.ones((2, 4), bool), impl=impl)
    with pytest.raises(NotImplementedError):
        call("pallas_interpret")
    assert np.isfinite(np.asarray(call("blocked"))).all()


# ----------------------------------------------------------------------
@pytest.mark.parametrize("bt,s,h,p,n,chunk", [
    (2, 128, 2, 16, 8, 32),
    (1, 256, 4, 64, 128, 64),   # production-ish head
    (2, 96, 2, 32, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_shapes(bt, s, h, p, n, chunk, dtype):
    k = jax.random.key(5)
    x = jax.random.normal(jax.random.fold_in(k, 1), (bt, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 2),
                                           (bt, s, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 3), (h,)))
    B = jax.random.normal(jax.random.fold_in(k, 4), (bt, s, n), dtype)
    C = jax.random.normal(jax.random.fold_in(k, 5), (bt, s, n), dtype)
    D = jnp.ones((h,))
    y, fin = ms.mamba_chunk_scan(x, dt, A, B, C, D, chunk=chunk,
                                 interpret=True)
    yw, fw = ref.mamba_chunk_scan_naive(x, dt, A, B, C, D, chunk=chunk)
    tol = 5e-3 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(y.astype(np.float32), yw.astype(np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(fin, fw, atol=tol, rtol=tol)


def test_mamba_scan_initial_state():
    k = jax.random.key(6)
    bt, s, h, p, n, chunk = 1, 64, 2, 8, 4, 16
    x = jax.random.normal(jax.random.fold_in(k, 1), (bt, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 2), (bt, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 3), (h,)))
    B = jax.random.normal(jax.random.fold_in(k, 4), (bt, s, n))
    C = jax.random.normal(jax.random.fold_in(k, 5), (bt, s, n))
    D = jnp.zeros((h,))
    s0 = jax.random.normal(jax.random.fold_in(k, 7), (bt, h, p, n))
    y, fin = ms.mamba_chunk_scan(x, dt, A, B, C, D, chunk=chunk,
                                 initial_state=s0, interpret=True)
    yw, fw = ref.mamba_chunk_scan_naive(x, dt, A, B, C, D, chunk=chunk,
                                        initial_state=s0)
    np.testing.assert_allclose(y, yw, atol=5e-3, rtol=5e-3)
    np.testing.assert_allclose(fin, fw, atol=5e-3, rtol=5e-3)


def _ssm_step_args(L, bt, h, p, n):
    k = jax.random.key(11)
    f = lambda i, shape: jax.random.normal(jax.random.fold_in(k, i), shape)
    return (f(0, (L, bt, h, p, n)), f(1, (bt, h, p)).astype(jnp.bfloat16),
            jax.nn.softplus(f(2, (bt, h))) * 0.1, -jnp.exp(f(3, (h,))),
            f(4, (bt, n)).astype(jnp.bfloat16),
            f(5, (bt, n)).astype(jnp.bfloat16), 1.0 + 0.1 * f(6, (h,)))


@pytest.mark.parametrize("bt,h,p,n", [
    (2, 8, 16, 32),
    (2, 128, 64, 128),          # granite-4.0-h-small's heads
])
@pytest.mark.parametrize("layer", [None, 0, 2])
def test_ssm_step_kernel_matches_ref(bt, h, p, n, layer):
    """_ssm_step_kernel (interpret) against its ref lowering, on one
    layer's state and on a stack of three with a layer index; the other
    layers of the stack come back untouched. f32 state; the kernel sums
    y over N in another order than the einsum: 1e-5."""
    st, x, dt, A, B, C, D = _ssm_step_args(3, bt, h, p, n)
    if layer is None:
        st = st[1]
    y, got = ssm_step.ssm_step(st, x, dt, A, B, C, D, layer=layer,
                               interpret=True)
    yw, want = ref.mamba_decode_step(st, x, dt, A, B, C, D, layer=layer)
    assert got.shape == st.shape and y.dtype == x.dtype
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y.astype(np.float32), yw.astype(np.float32),
                               atol=1e-2, rtol=1e-2)   # both rounded to bf16
    if layer is not None:
        others = [i for i in range(3) if i != layer]
        np.testing.assert_array_equal(np.asarray(got)[others],
                                      np.asarray(st)[others])
        assert not np.allclose(got[layer], st[layer])


@pytest.mark.parametrize("kernel", ["paged", "flash"])
def test_attention_kernels_take_a_scale(kernel):
    """A q.k multiplier other than 1/sqrt(D) (granite: 1/128 at D=128)
    reaches _pa_kernel and _fa_kernel and their ref lowerings alike; the
    default is 1/sqrt(D). f32: 2e-5, as the other kernel tests."""
    k = jax.random.key(12)
    d, scale = 128, 1.0 / 128
    f = lambda i, shape: jax.random.normal(jax.random.fold_in(k, i), shape)
    if kernel == "paged":
        b, h, kv, page, maxp = 2, 4, 2, 8, 4
        nb = b * maxp
        q = f(1, (b, h, d))
        kp, vp = f(2, (nb, page, kv, d)), f(3, (nb, page, kv, d))
        table = jnp.arange(nb, dtype=jnp.int32).reshape(b, maxp)
        ctx = jnp.asarray([13, 29], jnp.int32)
        run = lambda sc: pa.paged_attention(
            q, kp.reshape(nb, page, kv * d), vp.reshape(nb, page, kv * d),
            table, ctx, scale=sc, interpret=True)
        want = lambda sc: ref.paged_attention_naive(q, kp, vp, table, ctx,
                                                    scale=sc)
    else:
        q, kk, v = f(1, (1, 64, 4, d)), f(2, (1, 64, 2, d)), f(3, (1, 64, 2, d))
        run = lambda sc: fa.flash_attention(q, kk, v, causal=True, scale=sc,
                                            q_block=32, kv_block=32,
                                            interpret=True)
        want = lambda sc: ref.attention_naive(q, kk, v, causal=True,
                                              scale=sc)
    got = run(scale)
    np.testing.assert_allclose(got, want(scale), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(run(None), want(1.0 / np.sqrt(d)),
                               atol=2e-5, rtol=2e-5)
    assert not np.allclose(got, run(None), atol=1e-3)


# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_sets,n_ways,e,bq", [
    (8, 2, 4, 64), (16, 4, 8, 256), (4, 1, 4, 33)])
def test_fmmu_lookup_vs_ref(n_sets, n_ways, e, bq):
    k = jax.random.key(7)
    tags = jax.random.randint(jax.random.fold_in(k, 1),
                              (n_sets, n_ways), 0, 64)
    # force tag-set consistency: tags in set s must be ≡ s (mod n_sets)
    tags = tags * n_sets + jnp.arange(n_sets)[:, None]
    valid = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.7,
                                 (n_sets, n_ways))
    data = jax.random.randint(jax.random.fold_in(k, 3),
                              (n_sets, n_ways, e), -1, 1 << 26)
    dlpns = jax.random.randint(jax.random.fold_in(k, 4), (bq,), -2,
                               64 * n_sets * e)
    got = fl.fmmu_lookup(tags, valid, data, dlpns, entries_per_block=e,
                         block_size=32, interpret=True)
    want = ref.fmmu_lookup_ref(tags, valid, data, dlpns,
                               entries_per_block=e)
    np.testing.assert_array_equal(got[0], want[0])  # hit
    np.testing.assert_array_equal(got[1], want[1])  # dppn
    np.testing.assert_array_equal(got[2], want[2])  # set
    # way only meaningful on hits
    np.testing.assert_array_equal(np.where(got[0], got[3], 0),
                                  np.where(want[0], want[3], 0))


def test_ops_dispatch_pallas_interpret():
    """ops.py dispatch: pallas_interpret path matches blocked path."""
    from repro.kernels import ops
    k = jax.random.key(8)
    q = jax.random.normal(jax.random.fold_in(k, 1), (1, 128, 2, 32))
    kk = jax.random.normal(jax.random.fold_in(k, 2), (1, 128, 2, 32))
    v = jax.random.normal(jax.random.fold_in(k, 3), (1, 128, 2, 32))
    a = ops.flash_attention(q, kk, v, impl="pallas_interpret")
    b = ops.flash_attention(q, kk, v, impl="blocked")
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_sets,n_ways,e,bq,np_sz", [
    (8, 2, 4, 64, 256), (16, 4, 8, 300, 5000), (4, 1, 4, 33, 100)])
def test_fmmu_translate_vs_ref(n_sets, n_ways, e, bq, np_sz):
    """Fused translate kernel (probe + backing fallback + ref touch)
    matches the reference lowering bit-for-bit, including the streamed
    backing gather crossing chunk boundaries and the [S,W] ref output."""
    from repro.kernels import fmmu_translate as ft
    k = jax.random.key(11)
    tags = jax.random.randint(jax.random.fold_in(k, 1),
                              (n_sets, n_ways), 0, 64)
    tags = tags * n_sets + jnp.arange(n_sets)[:, None]
    valid = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.7,
                                 (n_sets, n_ways))
    refb = jax.random.bernoulli(jax.random.fold_in(k, 6), 0.3,
                                (n_sets, n_ways))
    # value range deliberately crosses 2^24: host-tier block ids are
    # tagged at 1<<24 and above, so value gathers must stay bit-exact
    # past f32's exact-integer range
    data = jax.random.randint(jax.random.fold_in(k, 3),
                              (n_sets, n_ways, e), -1, 1 << 26)
    backing = jax.random.randint(jax.random.fold_in(k, 5), (np_sz,),
                                 -1, 1 << 26)
    # upper range deliberately exceeds NP: out-of-contract dlpns must
    # clip to backing[NP-1] identically on every impl path
    dlpns = jax.random.randint(jax.random.fold_in(k, 4), (bq,), -2,
                               np_sz + 3)
    touch = jax.random.bernoulli(jax.random.fold_in(k, 8), 0.6, (bq,))
    got = ft.fmmu_translate(tags, valid, refb, data, backing, dlpns,
                            touch, entries_per_block=e, block_size=32,
                            backing_chunk=96, interpret=True)
    want = ref.fmmu_translate_ref(tags, valid, refb, data, backing,
                                  dlpns, touch, entries_per_block=e)
    np.testing.assert_array_equal(got[0], want[0])  # hit
    np.testing.assert_array_equal(got[1], want[1])  # out dppn
    np.testing.assert_array_equal(got[2], want[2])  # set
    np.testing.assert_array_equal(np.where(got[0], got[3], 0),
                                  np.where(want[0], want[3], 0))
    np.testing.assert_array_equal(got[4], want[4])  # ref bits


def test_fmmu_translate_partial_last_chunk():
    """ISSUE-3 chunk-grid edge: n_backing NOT a multiple of
    backing_chunk — misses whose dlpn lands in the final partial chunk
    (and right at the chunk seam) must gather their backing value from
    the padded tile bit-exactly, interpret-vs-ref."""
    from repro.kernels import fmmu_translate as ft
    n_sets, n_ways, e = 4, 2, 4
    np_sz, chunk = 130, 64            # 130 = 64 + 64 + 2: last tile 2/64
    k = jax.random.key(3)
    tags = jnp.full((n_sets, n_ways), -1)
    valid = jnp.zeros((n_sets, n_ways), bool)    # empty cache: all miss
    refb = jnp.zeros((n_sets, n_ways), bool)
    data = jnp.full((n_sets, n_ways, e), -1)
    backing = jax.random.randint(k, (np_sz,), -1, 1 << 26)
    # seam and tail coverage: last entry of tile 0, first of tile 1,
    # the two real entries of the partial tile 2, plus interior points
    dlpns = jnp.array([63, 64, 127, 128, 129, 0, 65, 120], jnp.int32)
    touch = jnp.ones(dlpns.shape, bool)
    got = ft.fmmu_translate(tags, valid, refb, data, backing, dlpns,
                            touch, entries_per_block=e, block_size=8,
                            backing_chunk=chunk, interpret=True)
    want = ref.fmmu_translate_ref(tags, valid, refb, data, backing,
                                  dlpns, touch, entries_per_block=e)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[1],
                                  backing[jnp.clip(dlpns, 0, np_sz - 1)])


def test_fmmu_translate_all_dlpns_beyond_np_clip():
    """ISSUE-3 chunk-grid edge: every dlpn >= NP — the out-of-contract
    clip must serve backing[NP-1] on every lane (not the pad region,
    not a silent no-match), identically on interpret and ref paths."""
    from repro.kernels import fmmu_translate as ft
    n_sets, n_ways, e = 4, 2, 4
    np_sz = 100                       # padded to 192 with chunk 96
    k = jax.random.key(4)
    tags = jnp.full((n_sets, n_ways), -1)
    valid = jnp.zeros((n_sets, n_ways), bool)
    refb = jnp.zeros((n_sets, n_ways), bool)
    data = jnp.full((n_sets, n_ways, e), -1)
    backing = jax.random.randint(k, (np_sz,), -1, 1 << 26)
    dlpns = jnp.array([100, 101, 150, 191, 192, 1000], jnp.int32)
    touch = jnp.ones(dlpns.shape, bool)
    got = ft.fmmu_translate(tags, valid, refb, data, backing, dlpns,
                            touch, entries_per_block=e, block_size=8,
                            backing_chunk=96, interpret=True)
    want = ref.fmmu_translate_ref(tags, valid, refb, data, backing,
                                  dlpns, touch, entries_per_block=e)
    np.testing.assert_array_equal(got[1], want[1])
    assert (np.asarray(got[1]) == int(backing[np_sz - 1])).all()
