"""Batched (vectorized) FMMU engine: dict semantics, MSHR-merge dedup,
CondUpdate races, property tests, and the fused translate pipeline
(single-probe invariant, fused-vs-unfused bit-identity, mixed-op edge
cases)."""
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from fmmu_lockstep import batch_lockstep
from repro.core.fmmu import batch as B
from repro.core.fmmu.types import (COND_UPDATE, LOOKUP, NIL, UPDATE,
                                   small_geometry)


@pytest.fixture(scope="module")
def setup():
    g = small_geometry()
    return g, B.make_jitted(g)


def test_batch_semantics(setup):
    g, fns = setup
    stt = B.init_batch_state(g)
    rng = random.Random(0)
    n_pages = g.n_tvpns * g.entries_per_tp
    shadow = {}
    for _ in range(150):
        bq = 16
        dlpns = rng.sample(range(n_pages), bq)
        op = rng.choice(["lookup", "update", "cond"])
        if op == "update":
            dppns = [rng.randrange(10 ** 6) for _ in range(bq)]
            stt = fns["update"](stt, jnp.array(dlpns), jnp.array(dppns))
            shadow.update(zip(dlpns, dppns))
        elif op == "lookup":
            stt, out = fns["lookup"](stt, jnp.array(dlpns))
            for a, o in zip(dlpns, np.asarray(out)):
                assert o == shadow.get(a, NIL)
        else:
            olds = [shadow.get(a, NIL) if rng.random() < 0.5
                    else rng.randrange(10 ** 6) for a in dlpns]
            news = [rng.randrange(10 ** 6) for _ in range(bq)]
            stt, ok = fns["cond_update"](stt, jnp.array(dlpns),
                                         jnp.array(news), jnp.array(olds))
            for a, n, o, k in zip(dlpns, news, olds, np.asarray(ok)):
                assert bool(k) == (shadow.get(a, NIL) == o)
                if shadow.get(a, NIL) == o:
                    shadow[a] = n


def test_batch_miss_dedup_is_mshr_merge(setup):
    """All misses to one cache block produce exactly ONE backing fill —
    the vectorized equivalent of in-cache MSHR merging."""
    g, fns = setup
    stt = B.init_batch_state(g)
    # populate backing
    dl = jnp.arange(g.cmt_entries)
    stt = fns["update"](stt, dl, dl * 10)
    stt = B.init_batch_state(g)._replace(backing=stt.backing)  # cold cache
    fills_before = int(stt.stats[2])
    # 8 lookups, all within one block
    reps = jnp.array([0, 1, 2, 3, 0, 1, 2, 3])[: g.cmt_entries]
    stt, out = fns["lookup"](stt, reps)
    assert int(stt.stats[2]) - fills_before == 1
    np.testing.assert_array_equal(np.asarray(out), np.asarray(reps) * 10)


def test_batch_inactive_slots(setup):
    g, fns = setup
    stt = B.init_batch_state(g)
    stt = fns["update"](stt, jnp.array([3, -1, 5]), jnp.array([30, 99, 50]))
    stt, out = fns["lookup"](stt, jnp.array([3, -1, 5]))
    assert list(np.asarray(out)) == [30, NIL, 50]


def test_batch_capacity_eviction(setup):
    """More distinct blocks than the cache holds: values still correct
    (served from backing), cache does not corrupt."""
    g, fns = setup
    stt = B.init_batch_state(g)
    n_pages = g.n_tvpns * g.entries_per_tp
    dl = jnp.arange(0, n_pages, g.cmt_entries)  # one per block, all blocks
    stt = fns["update"](stt, dl, dl + 1)
    stt, out = fns["lookup"](stt, dl)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dl) + 1)


# pinned regression cases: same-set eviction churn across update/lookup rounds
# (the PR-2 incremental-table seed), and a re-written dlpn read back
# through a cold cache (the PR-4 swap CondUpdate shape)
@example([(True, [0, 1, 2, 3], 100), (False, [3, 2, 1, 0], 0),
          (True, [0, 64], 7), (False, [64, 0], 0),
          (True, [0, 4, 8, 12, 16], 55), (False, [16, 0, 8], 0)])
@example([(False, [127], 0), (True, [127], 5), (False, [127], 0),
          (True, [127], 9), (False, [127], 0)])
@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.booleans(),
                          st.lists(st.integers(0, 127), min_size=1,
                                   max_size=8, unique=True),
                          st.integers(0, 999)),
                min_size=1, max_size=25))
def test_batch_property(ops):
    g = small_geometry()
    fns = B.make_jitted(g)
    stt = B.init_batch_state(g)
    shadow = {}
    for is_update, dlpns, base in ops:
        arr = jnp.array(dlpns)
        if is_update:
            vals = jnp.array([base + i for i in range(len(dlpns))])
            stt = fns["update"](stt, arr, vals)
            shadow.update({d: base + i for i, d in enumerate(dlpns)})
        else:
            stt, out = fns["lookup"](stt, arr)
            for d, o in zip(dlpns, np.asarray(out)):
                assert o == shadow.get(d, NIL)


# ======================================================================
# Fused translate pipeline
# ======================================================================
def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eq in jaxpr.eqns:
        for v in eq.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for x in vs:
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    yield from _iter_jaxprs(sub)


def _count_sorts(closed_jaxpr):
    return sum(1 for j in _iter_jaxprs(closed_jaxpr.jaxpr)
               for eq in j.eqns if eq.primitive.name == "sort")


def test_single_probe_single_insert_per_batch():
    """The single-probe invariant: every batch entry point traces exactly
    ONE CMT probe and ONE insert pass (one sort) — in particular the
    CondUpdate/GC path, which used to probe twice and insert twice."""
    g = small_geometry()
    stt = B.init_batch_state(g)
    dl = jnp.arange(8, dtype=jnp.int32)
    dp = jnp.ones(8, jnp.int32)
    old = jnp.zeros(8, jnp.int32)
    mixed = jnp.array([0, 1, 2, 0, 1, 2, 0, 1], jnp.int32)
    ms = B.init_serving_state(g)
    cases = [
        (functools.partial(B.cond_update_batch, g), (stt, dl, dp, old)),
        (functools.partial(B.lookup_batch, g), (stt, dl)),
        (functools.partial(B.update_batch, g), (stt, dl, dp)),
        (functools.partial(B.translate_batch, g), (stt, mixed, dl, dp, old)),
        # the serving wrapper's incremental-table scatter must add no
        # probe and no sort
        (functools.partial(B.translate_serving, g),
         (ms, mixed, dl, dp, old)),
    ]
    for fn, args in cases:
        p0, i0 = B.PROBE_TRACES[0], B.INSERT_TRACES[0]
        jaxpr = jax.make_jaxpr(fn)(*args)
        assert B.PROBE_TRACES[0] - p0 == 1, fn
        assert B.INSERT_TRACES[0] - i0 == 1, fn
        assert _count_sorts(jaxpr) == 1, fn
    # contrast: the unfused GC path probes twice, inserts twice, and
    # pays two full sorts per insert
    p0, i0 = B.PROBE_TRACES[0], B.INSERT_TRACES[0]
    jaxpr = jax.make_jaxpr(
        functools.partial(B.cond_update_batch_unfused, g))(stt, dl, dp, old)
    assert B.PROBE_TRACES[0] - p0 == 2
    assert B.INSERT_TRACES[0] - i0 == 2
    assert _count_sorts(jaxpr) == 4


def test_translate_mixed_lockstep_vs_unfused_and_shadow():
    """Mixed-op batches: fused path is bit-identical (full state pytree
    + outputs) to the unfused three-call sequence, and both follow
    dict semantics."""
    for seed in range(2):
        res = batch_lockstep(seed, n_batches=40)
        assert res.startswith("OK"), res


def test_translate_overflow_and_duplicate_blocks_lockstep():
    """Unconstrained batches: duplicate blocks in one batch (MSHR
    merge), >W distinct new blocks per set (no-allocate overflow),
    duplicate read dlpns — dict semantics and write-through coherence
    hold."""
    for seed in range(2):
        res = batch_lockstep(seed, n_batches=40, overflow=True)
        assert res.startswith("OK"), res
    res = batch_lockstep(11, n_batches=25, overflow=True,
                         geom_kw=dict(cmt_sets=2, cmt_ways=1))
    assert res.startswith("OK"), res


def test_translate_duplicate_block_one_batch_single_fill(setup):
    """All lanes of a mixed batch inside ONE cache block: exactly one
    backing fill (MSHR merge across op kinds)."""
    g, fns = setup
    stt = B.init_batch_state(g)
    base = jnp.arange(g.cmt_entries, dtype=jnp.int32)
    stt = fns["update"](stt, base, base * 7)
    stt = B.init_batch_state(g)._replace(backing=stt.backing)  # cold cache
    e = g.cmt_entries
    opc = jnp.array([LOOKUP, UPDATE, COND_UPDATE, LOOKUP][:e], jnp.int32)
    dl = jnp.arange(len(opc), dtype=jnp.int32)          # one block
    dp = jnp.full((len(opc),), 999, jnp.int32)
    old = dl * 7                                        # cond lane matches
    stt, out, ok = fns["translate"](stt, opc, dl, dp, old)
    assert int(stt.stats[2]) == 1
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dl) * 7)
    assert bool(ok[2])
    # write-allocate pulled the post-write contents (state is donated:
    # snapshot the miss counter before handing stt to the next call)
    miss_before = int(stt.stats[1])
    stt2, out2 = fns["lookup"](stt, dl)
    assert int(stt2.stats[1]) == miss_before            # all hits now
    want = np.asarray(dl) * 7
    want[1] = 999                                       # UPDATE lane
    want[2] = 999                                       # applied COND lane
    np.testing.assert_array_equal(np.asarray(out2), want)


def test_translate_set_overflow_serves_uncached(setup):
    """>W distinct blocks into one set in ONE mixed batch: surplus is
    served from backing (values still correct), at most W fills land."""
    g, _ = setup
    g2 = small_geometry(cmt_sets=2, cmt_ways=2)
    fns = B.make_jitted(g2)
    stt = B.init_batch_state(g2)
    e = g2.cmt_entries
    # 5 distinct blocks, all congruent mod 2 -> same set
    blocks = np.arange(0, 10, 2)
    dl = jnp.asarray(blocks * e, jnp.int32)
    dp = jnp.asarray(blocks * 100, jnp.int32)
    stt = fns["update"](stt, dl, dp)                    # write-allocate
    assert int(stt.stats[2]) <= g2.cmt_ways
    stt, out = fns["lookup"](stt, dl)
    np.testing.assert_array_equal(np.asarray(out), blocks * 100)


def test_serving_table_coherent_with_map(setup):
    """ServingMapState.table is maintained by the same fused call that
    commits each write: after any mixed-op churn it equals the mapping
    a full lookup of every DLPN would return (shadow-dict oracle)."""
    g, fns = setup
    ms = B.init_serving_state(g)
    n_pages = g.n_tvpns * g.entries_per_tp
    rng = random.Random(3)
    shadow = {}
    for _ in range(60):
        bq = 12
        dlpns = rng.sample(range(n_pages), bq)
        kinds = [rng.choice([LOOKUP, UPDATE, COND_UPDATE])
                 for _ in range(bq)]
        news = [rng.randrange(10 ** 6) for _ in range(bq)]
        olds = [shadow.get(a, NIL) if rng.random() < 0.5
                else rng.randrange(10 ** 6) for a in dlpns]
        ms, _, ok = fns["serve"](ms, jnp.array(kinds), jnp.array(dlpns),
                                 jnp.array(news), jnp.array(olds))
        for a, k, n, o, applied in zip(dlpns, kinds, news, olds,
                                       np.asarray(ok)):
            if k == UPDATE or (k == COND_UPDATE and applied):
                shadow[a] = n
    table = np.asarray(ms.table)
    want = np.full(n_pages, NIL, np.int32)
    for a, v in shadow.items():
        want[a] = v
    np.testing.assert_array_equal(table, want)


def test_make_jitted_donation_chain(setup):
    """Donated state: chained steady-state use (always rebinding the
    returned state) stays correct through every entry point."""
    g, fns = setup
    stt = B.init_batch_state(g)
    dl = jnp.arange(6, dtype=jnp.int32)
    stt = fns["update"](stt, dl, dl + 50)
    stt, out = fns["lookup"](stt, dl)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dl) + 50)
    stt, ok = fns["cond_update"](stt, dl, dl + 90, dl + 50)
    assert np.asarray(ok).all()
    opc = jnp.zeros(6, jnp.int32)
    stt, out, _ = fns["translate"](stt, opc, dl, opc, opc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dl) + 90)


# ---------------------------------------------------------------------
# Device-resident free-list allocator (ISSUE 3): pure state transitions
# ---------------------------------------------------------------------
def test_allocator_alloc_free_oob_flag(setup):
    from repro.core.fmmu.types import HOST_BASE
    g, _ = setup
    ms = B.init_serving_state(g, n_device_blocks=4, n_host_blocks=2)
    # init mirrors BlockPool: first pop is block 0, then 1, 2, ...
    ms, blk, ok = B.alloc_serving(ms, jnp.array([True, False, True]))
    assert list(np.asarray(blk)) == [0, -1, 1]
    assert list(np.asarray(ok)) == [True, False, True]
    assert int(ms.free_n) == 2 and not bool(ms.oob)
    # over-allocation: earlier lanes succeed, later lanes fail, the
    # sticky OutOfBlocks FLAG raises instead of a Python exception
    ms, blk, ok = B.alloc_serving(ms, jnp.array([True, True, True]))
    assert list(np.asarray(blk)) == [2, 3, -1]
    assert list(np.asarray(ok)) == [True, True, False]
    assert int(ms.free_n) == 0 and bool(ms.oob)
    # free routes tiers by HOST_BASE and pushes in lane order (the
    # host block below models one the host tier handed out: free may
    # only return blocks that were actually popped)
    ms = ms._replace(host_n=jnp.int32(1))    # host popped HOST_BASE+0
    ms = B.free_serving(ms, jnp.array([1, -1, HOST_BASE, 3]))
    assert int(ms.free_n) == 2
    assert list(np.asarray(ms.free_stack[:2])) == [1, 3]
    assert int(ms.host_n) == 2
    assert int(ms.host_stack[1]) == HOST_BASE
    # resync from the (authoritative) host pool clears the flag
    ms = B.set_allocator(ms, jnp.arange(3, -1, -1, dtype=jnp.int32),
                         jnp.int32(4), ms.host_stack, ms.host_n)
    assert int(ms.free_n) == 4 and not bool(ms.oob)
    ms, blk, ok = B.alloc_serving(ms, jnp.array([True]))
    assert int(blk[0]) == 0


def test_serving_grow_allocates_and_commits(setup):
    """serving_grow = one pop + one fused map commit: the new mapping
    lands in the backing map AND the incremental table, the allocator
    advances, and failed lanes leave every structure untouched."""
    g, _ = setup
    ms = B.init_serving_state(g, n_device_blocks=2)
    grow = jnp.array([True, False, True])
    dl = jnp.array([5, -1, 9], jnp.int32)
    ms, blocks, ok = B.serving_grow(g, ms, grow, dl)
    assert list(np.asarray(blocks)) == [0, -1, 1]
    assert int(ms.table[5]) == 0 and int(ms.table[9]) == 1
    assert int(ms.fmmu.backing[5]) == 0 and int(ms.fmmu.backing[9]) == 1
    assert int(ms.free_n) == 0
    # pool dry: nothing commits, oob raised
    ms2, blocks2, ok2 = B.serving_grow(g, ms, jnp.array([False, True, False]),
                                       jnp.array([-1, 17, -1], jnp.int32))
    assert not bool(ok2[1]) and bool(ms2.oob)
    assert int(ms2.table[17]) == NIL and int(ms2.fmmu.backing[17]) == NIL


def test_allocator_transitions_inside_jit_donated(setup):
    """alloc/free/grow are pure pytree transitions usable under jit
    with donation (the macro-step contract)."""
    g, _ = setup
    ms = B.init_serving_state(g, n_device_blocks=8)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def roundtrip(ms, want, dl):
        ms, blocks, ok = B.alloc_serving(ms, want)
        ms = B.free_serving(ms, jnp.where(ok, blocks, NIL))
        ms, _, _ = B.serving_grow(g, ms, want, dl)
        return ms

    ms = roundtrip(ms, jnp.array([True, True]), jnp.array([3, 4], jnp.int32))
    assert int(ms.free_n) == 6
    assert int(ms.table[3]) >= 0 and int(ms.table[4]) >= 0
