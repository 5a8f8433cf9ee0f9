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
@pytest.mark.parametrize("b,h,kv,d,page,maxp", [
    (2, 4, 4, 32, 16, 8),
    (3, 8, 2, 64, 8, 6),      # GQA
    (1, 4, 1, 128, 32, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_shapes(b, h, kv, d, page, maxp, dtype):
    k = jax.random.key(3)
    nb = b * maxp + 4
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, h, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(k, 2), (nb, page, kv, d), dtype)
    vp = jax.random.normal(jax.random.fold_in(k, 3), (nb, page, kv, d), dtype)
    table = jax.random.permutation(
        jax.random.fold_in(k, 4), jnp.arange(nb))[:b * maxp].reshape(b, maxp)
    ctx = jnp.asarray([(maxp * page * (i + 1)) // (b + 1) + 1
                       for i in range(b)], jnp.int32)
    out, (m, l) = pa.paged_attention(q, kp.reshape(nb, page, kv * d),
                                     vp.reshape(nb, page, kv * d), table,
                                     ctx, return_stats=True, interpret=True)
    want, (wm, wl) = ref.paged_attention_naive(q, kp, vp, table, ctx,
                                               return_stats=True)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(m, wm, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(l, wl, atol=1e-4, rtol=1e-4)


def test_paged_attention_softcap():
    k = jax.random.key(4)
    b, h, kv, d, page, maxp = 2, 4, 2, 32, 8, 4
    nb = b * maxp
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, h, d))
    kp = jax.random.normal(jax.random.fold_in(k, 2), (nb, page, kv, d))
    vp = jax.random.normal(jax.random.fold_in(k, 3), (nb, page, kv, d))
    table = jnp.arange(nb).reshape(b, maxp)
    ctx = jnp.array([17, 30])
    out = pa.paged_attention(q, kp.reshape(nb, page, kv * d),
                             vp.reshape(nb, page, kv * d), table, ctx,
                             softcap=25.0, interpret=True)
    want = ref.paged_attention_naive(q, kp, vp, table, ctx, softcap=25.0)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


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
