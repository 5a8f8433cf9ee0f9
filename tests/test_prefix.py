"""Copy-on-write prefix sharing (ISSUE 10): radix tree unit behavior
(page groups, exact-prefix verification, LRU pruning), the refcount
lane vs a numpy oracle under admit/share/COW/free/GC churn, the
satellite-3 refcount-invariant property test, SHARE/COW journal replay
bit-identity, recovery pin release, the sharing-off jaxpr- and
journal-byte-identity guarantees, and engine-level output
bit-identity with sharing on."""
import collections
import random
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import journal as jl
from repro.core.fmmu import batch as B
from repro.core.fmmu.types import UPDATE, small_geometry
from repro.paging import kv_manager as KM
from repro.paging.kv_manager import KVPageManager
from repro.paging.pool import BlockPool

from hypothesis import example, given, settings, strategies as st

pytestmark = pytest.mark.prefix

CHANNELS = (1, 2, 4)
PAGE = 2            # tokens per page for the synthetic prompts below

# three fixed prefixes (2 pages each at PAGE=2) over a tiny vocab —
# the property test's admissions draw from these so prefixes collide
PREFIXES = [(1, 2, 3, 4), (1, 2, 9, 9), (5, 6, 7, 8)]


def _kvm(C, n_dev=32, n_host=8, max_pages=8):
    return KVPageManager(n_slots=6, max_pages=max_pages,
                         n_device_blocks=n_dev, n_host_blocks=n_host,
                         channels=C, track_live=True, track_refs=True)


def _map_counts(kvm) -> collections.Counter:
    """Device-tier mapping multiset recomputed from host seq_pages —
    the ground truth for both the _ref dict and the refcnt lane."""
    return collections.Counter(
        b for ps in kvm.seq_pages.values() for b in ps
        if not BlockPool.is_host(b))


def _check_invariants(kvm, ctx=""):
    """Satellite 3's invariants, asserted wholesale:
    - every tracked block's refcount equals its number of mapping
      dlpns (zero only while the tree pins it);
    - every device block is in EXACTLY one of {free, mapped-or-pinned,
      retired};
    - the device refcnt lane mirrors the mapping counts bit-for-bit
      (so COW/free/GC never leave a dangling or phantom ref)."""
    cnt = _map_counts(kvm)
    for b, n in kvm._ref.items():
        assert n == cnt.get(b, 0), (ctx, "ref", b, n, cnt.get(b, 0))
        if n == 0:
            assert b in kvm._pinned, (ctx, "zero-ref unpinned", b)
    for b in kvm._pinned:
        assert b in kvm._ref, (ctx, "pin untracked", b)
    free = {b for ch in kvm.pool._free_dev_ch for b in ch}
    retired = {b for b in kvm.pool._retired if not BlockPool.is_host(b)}
    held = set(cnt) | set(kvm._pinned)
    for b in range(kvm.pool.n_device):
        where = (b in free) + (b in held) + (b in retired)
        assert where == 1, (ctx, "partition", b,
                            b in free, b in held, b in retired)
    want = np.zeros(kvm.pool.n_device, np.int64)
    for b, n in cnt.items():
        want[b] = n
    np.testing.assert_array_equal(kvm.refcounts(), want, err_msg=str(ctx))


def _admit_shared(kvm, slot, tokens):
    """The engine's admission dance at manager level: match, map the
    hit as shared leading pages, register the full prompt path."""
    groups = KVPageManager.page_groups(tokens, PAGE)
    m = kvm.match_prefix(groups)
    kvm.new_seq(slot, len(groups), shared=m)
    kvm.register_prefix(slot, groups)
    return len(m)


# ---------------------------------------------------------------------
# radix tree units
# ---------------------------------------------------------------------
def test_page_groups_and_path_keys():
    """Groups split page-granular with a shareable partial tail; path
    keys chain over the WHOLE prefix (same tail after different heads
    gets different keys)."""
    g = KVPageManager.page_groups([1, 2, 3, 4, 5], 2)
    assert g == [(1, 2), (3, 4), (5,)]
    ka = KVPageManager._path_keys([(1, 2), (3, 4)])
    kb = KVPageManager._path_keys([(9, 9), (3, 4)])
    assert [d for d, _ in ka] == [1, 2]
    assert ka[0] != kb[0] and ka[1] != kb[1]   # chained, not per-page


@pytest.mark.parametrize("C", CHANNELS)
def test_match_register_roundtrip(C):
    """A registered prompt path matches in full; a shorter prompt
    matches its prefix; a diverging prompt matches only the common
    part. Registration is idempotent (first writer wins)."""
    kvm = _kvm(C)
    toks = [1, 2, 3, 4, 5, 6]
    groups = KVPageManager.page_groups(toks, PAGE)
    kvm.new_seq(0, len(groups))
    assert kvm.match_prefix(groups) == []
    n = kvm.register_prefix(0, groups)
    assert n == 3
    assert kvm.register_prefix(0, groups) == 0         # idempotent
    assert kvm.match_prefix(groups) == kvm.seq_pages[0]
    assert kvm.match_prefix(groups[:2]) == kvm.seq_pages[0][:2]
    div = KVPageManager.page_groups([1, 2, 3, 4, 7, 7], PAGE)
    assert kvm.match_prefix(div) == kvm.seq_pages[0][:2]
    _check_invariants(kvm)


def test_match_rejects_hash_collision():
    """A node whose stored exact prefix disagrees with the probe (a
    crc32 collision, simulated white-box) degrades to a MISS at that
    depth — sharing the wrong KV is never possible."""
    kvm = _kvm(1)
    groups = KVPageManager.page_groups([1, 2, 3, 4], PAGE)
    kvm.new_seq(0, 2)
    kvm.register_prefix(0, groups)
    keys = KVPageManager._path_keys(groups)
    b, _ = kvm._nodes[keys[1]]
    kvm._nodes[keys[1]] = (b, ((1, 2), (8, 8)))        # forged prefix
    assert kvm.match_prefix(groups) == kvm.seq_pages[0][:1]


def test_lru_prune_bounds_tree_and_frees_orphans():
    """Eviction walks least-recently-matched first; an unpinned block
    with no mappers goes straight back to the pool, one still mapped
    lingers until its refs drain through the free gate."""
    kvm = _kvm(1)
    a = KVPageManager.page_groups([1, 2, 3, 4], PAGE)
    c = KVPageManager.page_groups([5, 6, 7, 8], PAGE)
    kvm.new_seq(0, 2)
    kvm.register_prefix(0, a)
    kvm.new_seq(1, 2)
    kvm.register_prefix(1, c)
    kvm.match_prefix(a)                  # LRU-touch path a
    free0 = kvm.pool.free_device
    kvm.free_seq(1)                      # c's blocks now pinned-at-0
    assert kvm.pool.free_device == free0  # tree still holds them
    kvm.prefix_max_nodes = 2
    kvm._prune_nodes()                   # evicts c's nodes (cold)
    assert kvm.match_prefix(a) == kvm.seq_pages[0]     # hot path kept
    assert kvm.match_prefix(c) == []
    assert kvm.pool.free_device == free0 + 2           # orphans freed
    _check_invariants(kvm)


# ---------------------------------------------------------------------
# shared admission + refcount lane vs oracle
# ---------------------------------------------------------------------
@pytest.mark.parametrize("C", CHANNELS)
def test_shared_admission_refcounts_match_oracle(C):
    """B admissions of a common prefix map ONE physical block per
    shared page; shared pages program nothing; host _ref and the
    device refcnt lane both equal the mapping count."""
    kvm = _kvm(C)
    common = [1, 2, 3, 4]
    _admit_shared(kvm, 0, common + [10, 11])           # leader
    writes0 = kvm.host_writes
    for i, slot in enumerate((1, 2, 3)):
        hit = _admit_shared(kvm, slot, common + [20 + i, 30 + i])
        assert hit == 2
    assert kvm.host_writes - writes0 == 3      # only the unique tails
    lead = kvm.seq_pages[0][:2]
    for slot in (1, 2, 3):
        assert kvm.seq_pages[slot][:2] == lead         # one block, B maps
    assert kvm._ref[lead[0]] == 4 and kvm._ref[lead[1]] == 4
    _check_invariants(kvm)


@pytest.mark.parametrize("C", CHANNELS)
def test_free_seq_refcount_gate(C):
    """free_seq returns a share-managed block only at zero mapping
    refs and no pin — freeing one mapper leaves the other's pages
    intact; freeing the last mapper of an UNPINNED shared block (a COW
    destination is plain, but a matched block stays pinned) keeps it
    out of the pool until the tree lets go."""
    kvm = _kvm(C)
    common = [1, 2, 3, 4]
    _admit_shared(kvm, 0, common + [10, 11])
    _admit_shared(kvm, 1, common + [20, 21])
    shared = kvm.seq_pages[0][:2]
    free0 = kvm.pool.free_device
    kvm.free_seq(1)
    # slot 1's tail block was pinned by ITS registration: tree-held
    assert kvm.pool.free_device == free0
    assert kvm._ref[shared[0]] == 1
    kvm.free_seq(0)
    # every block of both slots is now pinned-at-zero: pool unchanged
    assert kvm.pool.free_device == free0
    assert all(kvm._ref[b] == 0 for b in shared)
    _check_invariants(kvm)
    kvm.prefix_max_nodes = 0
    kvm._prune_nodes()
    assert kvm.pool.free_device == kvm.pool.n_device   # all home
    assert not kvm._ref and not kvm._pinned
    _check_invariants(kvm)


# ---------------------------------------------------------------------
# copy-on-write relocation
# ---------------------------------------------------------------------
@pytest.mark.parametrize("C", CHANNELS)
def test_cow_relocates_and_drops_ref(C):
    """First divergent write: every shared page at/after the write
    frontier relocates to a private block (KV rows copied
    bit-identically), the shared block's ref drops, and the OTHER
    mapper still reads the original data."""
    kvm = _kvm(C)
    common = [1, 2, 3, 4]
    _admit_shared(kvm, 0, common + [10, 11])
    _admit_shared(kvm, 1, common + [20, 21])
    width = kvm.pool.n_device + kvm.pool.n_host + 1
    pools = [jnp.arange(width * 4.0).reshape(width, 4)]
    rows0 = np.asarray(pools[0])
    shared = list(kvm.seq_pages[0][:2])
    pools, n = kvm.cow_writes({1: 1}, pools, block_axis=0)
    # frontier page 1: pages 1 (shared) and 2 (own pin) relocate;
    # page 0 stays shared below the frontier
    assert n == 2
    assert kvm.seq_pages[1][0] == shared[0]
    assert kvm.seq_pages[1][1] != shared[1]
    assert kvm.seq_pages[0] [:2] == shared             # leader intact
    assert kvm._ref[shared[1]] == 1
    assert kvm.cow_moves == n
    rows = np.asarray(pools[0])
    for old, new in zip(shared[1:] , kvm.seq_pages[1][1:2]):
        np.testing.assert_array_equal(rows[new], rows0[old])
    np.testing.assert_array_equal(rows[shared[1]], rows0[shared[1]])
    _check_invariants(kvm)
    # the relocated pages left the COW trigger set: a second boundary
    # scan at the same frontier finds nothing
    _, n2 = kvm.cow_writes({1: 1})
    assert n2 == 0


def test_cow_stale_lane_skipped():
    """A page remapped BEHIND the host's back (racing commit) fails
    the CondUpdate guard: the lane is skipped, its unused destination
    returns to the free list, and the mapping is left alone — the GC
    walk's stale-lane discipline, verbatim."""
    kvm = _kvm(1)
    common = [1, 2, 3, 4]
    _admit_shared(kvm, 0, common + [10, 11])
    _admit_shared(kvm, 1, common + [20, 21])
    old = kvm.seq_pages[1][1]
    # remap slot 1 page 1 via a raw fused UPDATE the host dicts never
    # see: the _shared entry now points at a dead mapping
    dl = 1 * kvm.max_pages + 1
    kvm._xlate(UPDATE, [dl], [31])
    free0 = kvm.pool.free_device
    moves0 = kvm.cow_moves
    _, n = kvm.cow_writes({1: 1})
    # page 1's lane failed its guard; page 2 (own pin) still moved
    assert kvm.seq_pages[1][1] == old     # host view untouched
    assert kvm._ref[old] == 2             # ref NOT dropped
    assert n == 1 and kvm.cow_moves - moves0 == 1
    assert kvm.pool.free_device == free0 - 1   # only page 2's dest


# ---------------------------------------------------------------------
# satellite 3: refcount invariants under random interleavings
# ---------------------------------------------------------------------
def _churn(seed: int, C: int, steps: int = 40):
    kvm = _kvm(C)
    rng = random.Random(seed)
    tail = iter(range(100, 100 + 4 * steps))
    for step in range(steps):
        op = rng.random()
        free_slots = [s for s in range(kvm.n_slots)
                      if s not in kvm.seq_pages]
        try:
            if op < 0.35 and free_slots:
                pre = list(PREFIXES[rng.randrange(3)])
                toks = pre + [next(tail), next(tail)]
                _admit_shared(kvm, rng.choice(free_slots), toks)
            elif op < 0.55 and kvm.seq_pages:
                kvm.free_seq(rng.choice(list(kvm.seq_pages)))
            elif op < 0.75 and kvm._shared:
                slot = rng.choice(list(kvm._shared))
                kvm.cow_writes({slot: rng.randrange(kvm.max_pages)})
            elif op < 0.85:
                kvm.gc_collect(block_pages=4, budget=8)
            else:
                kvm.prefix_max_nodes = rng.randrange(4)
                kvm._prune_nodes()
                kvm.prefix_max_nodes = 4096
        except KM.OutOfBlocks:
            pass
        _check_invariants(kvm, (seed, C, step))


@example(seed=0, C=1)
@example(seed=1, C=2)
@example(seed=2, C=4)
@example(seed=77, C=1)
@example(seed=1234, C=4)
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), C=st.sampled_from(CHANNELS))
def test_refcount_invariants_property(seed, C):
    """Random admit / shared-admit / diverge-COW / free / GC / prune
    interleavings: every device block is in exactly one of
    free/mapped/retired, every refcount equals its mapper count, and
    COW never leaves a dangling reference (checked after EVERY op)."""
    _churn(seed, C)


# ---------------------------------------------------------------------
# crash consistency: SHARE/COW records replay bit-identically
# ---------------------------------------------------------------------
@pytest.mark.parametrize("C", CHANNELS)
def test_share_cow_journal_replay_bit_identity(C):
    """Leader registration, shared admission, and a COW divergence all
    journal; replay + restore rebuilds seq_pages, pool state, the
    device table, the refcnt lane, and the _ref dict bit-identically
    (all pins still carry mappers here, so recovery's pin release is
    a no-op)."""
    def fresh():
        return _kvm(C)
    with tempfile.TemporaryDirectory() as d:
        kvm = fresh()
        j = jl.Journal(d)
        kvm.journal = j
        j.snapshot(kvm.snapshot_state())
        common = [1, 2, 3, 4]
        _admit_shared(kvm, 0, common + [10, 11])       # leader + pins
        groups = KVPageManager.page_groups(common + [20, 21], PAGE)
        m = kvm.match_prefix(groups)
        assert len(m) == 2
        kvm.new_seq(1, len(groups), shared=m)          # SHARE record
        kvm.cow_writes({1: 0})                         # COW record
        kvm.new_seq(2, 2)                              # plain traffic
        rec = jl.replay(d)
        k2 = fresh()
        k2.restore_mapping(rec)
        assert {s: list(p) for s, p in kvm.seq_pages.items()} == \
               {s: list(p) for s, p in k2.seq_pages.items()}
        assert kvm.pool.state_dict() == k2.pool.state_dict()
        assert kvm._ref == k2._ref
        np.testing.assert_array_equal(np.asarray(kvm.block_tables()),
                                      np.asarray(k2.block_tables()))
        np.testing.assert_array_equal(kvm.refcounts(), k2.refcounts())
        _check_invariants(kvm)
        j.close()


def test_recovery_releases_orphan_pins():
    """The radix tree is volatile: after a crash, recovered pins with
    no surviving mapper return to the pool (deterministic sorted
    order) and the restored manager carries no sharing state — the
    cache rebuilds from post-recovery traffic."""
    with tempfile.TemporaryDirectory() as d:
        kvm = _kvm(1)
        j = jl.Journal(d)
        kvm.journal = j
        j.snapshot(kvm.snapshot_state())
        _admit_shared(kvm, 0, [1, 2, 3, 4, 10, 11])
        kvm.free_seq(0)             # 3 blocks pinned-at-zero, live
        assert kvm.pool.free_device == kvm.pool.n_device - 3
        rec = jl.replay(d)
        assert rec.ref == {b: 0 for b in rec.pinned} and len(rec.pinned) == 3
        k2 = _kvm(1)
        k2.restore_mapping(rec)
        assert k2.pool.free_device == k2.pool.n_device  # pins released
        assert not k2._ref and not k2._pinned
        _check_invariants(k2)
        j.close()


@pytest.mark.parametrize("C", (1, 2))
def test_sharing_off_journal_byte_identity(C):
    """With sharing never engaged, a track_refs=True manager's journal
    stream is BYTE-identical to a track_refs=False manager's — k=0
    admission emits the exact historical NEW_SEQ record."""
    import os

    def drive(kvm):
        kvm.new_seq(0, 3)
        kvm.extend_seq(0, 1)
        kvm.new_seq(1, 2)
        kvm.free_seq(0)

    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db:
        ka = _kvm(C)
        kb = KVPageManager(n_slots=6, max_pages=8, n_device_blocks=32,
                           n_host_blocks=8, channels=C,
                           track_live=True, track_refs=False)
        for kvm, d in ((ka, da), (kb, db)):
            kvm.journal = jl.Journal(d)
            kvm.journal.snapshot(kvm.snapshot_state())
            drive(kvm)
            kvm.journal.close()
        for name in ("journal.log", "oob.log"):
            with open(os.path.join(da, name), "rb") as fa, \
                    open(os.path.join(db, name), "rb") as fb_:
                assert fa.read() == fb_.read(), name


# ---------------------------------------------------------------------
# sharing-off jaxpr identity: refcnt is an ABSENT pytree leaf
# ---------------------------------------------------------------------
def _prims(closed):
    return collections.Counter(e.primitive.name
                               for jx in _iter(closed.jaxpr)
                               for e in jx.eqns)


def _iter(jaxpr):
    yield jaxpr
    for eq in jaxpr.eqns:
        for v in eq.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for x in vs:
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    yield from _iter(sub)


def test_sharing_off_jaxpr_identical_and_on_adds_no_probe():
    """track_refs=False leaves refcnt=None — an absent pytree leaf —
    so the traced fused translate is STRING-IDENTICAL to the pre-
    sharing (PR 9) graph. Arming the lane adds only elementwise +
    scatter ops riding the existing write mask: no sort, no gather."""
    import functools
    g = small_geometry()
    dl = jnp.arange(8, dtype=jnp.int32)
    dp = jnp.ones(8, jnp.int32)
    old = jnp.zeros(8, jnp.int32)
    kinds = jnp.array([0, 1, 2, 0, 1, 2, 0, 1], jnp.int32)
    fn = functools.partial(B.translate_serving, g)
    ms_pr9 = B.init_serving_state(g, n_device_blocks=8, track_live=True)
    ms_off = B.init_serving_state(g, n_device_blocks=8, track_live=True,
                                  track_refs=False)
    ms_on = B.init_serving_state(g, n_device_blocks=8, track_live=True,
                                 track_refs=True)
    assert ms_off.refcnt is None and ms_on.refcnt is not None
    jx_pr9 = jax.make_jaxpr(fn)(ms_pr9, kinds, dl, dp, old)
    jx_off = jax.make_jaxpr(fn)(ms_off, kinds, dl, dp, old)
    jx_on = jax.make_jaxpr(fn)(ms_on, kinds, dl, dp, old)
    assert str(jx_off) == str(jx_pr9)       # the off path CANNOT regress
    off, on = _prims(jx_off), _prims(jx_on)
    assert not (off - on), (off - on)
    extra = on - off
    assert "sort" not in extra, extra
    assert "gather" not in extra, extra


def test_manager_refs_off_carries_no_lane():
    kvm = KVPageManager(n_slots=4, max_pages=8, n_device_blocks=16,
                        n_host_blocks=0, channels=1)
    assert kvm.state.refcnt is None
    assert kvm.match_prefix([(1, 2)]) == []
    assert kvm.register_prefix(0, [(1, 2)]) == 0
    assert not kvm.has_shared()


# ---------------------------------------------------------------------
# engine end to end: sharing changes footprint, never outputs
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def _model():
    from repro.configs import get_arch, smoke_config
    from repro.models import Runtime, build_model
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                 remat="none", page_size=8, capacity_factor=100.0)
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, rt)
    return m, m.init(jax.random.key(0))


@pytest.mark.slow
def test_engine_prefix_sharing_outputs_bit_identical(_model):
    """4 requests with a 16-token common prefix: sharing on must emit
    bit-identical tokens to sharing off, prefill ONCE (the leader),
    admit the followers on shared pages, and COW each diverging tail —
    across the single-step and macro decode paths."""
    from repro.serving.config import PrefixConfig, ServeConfig
    from repro.serving.engine import ServeEngine
    m, params = _model

    def run(prefix, macro_k=0, channels=1):
        sc = ServeConfig(n_slots=8, max_ctx=64, macro_k=macro_k,
                         channels=channels,
                         prefix=PrefixConfig(min_tokens=8)
                         if prefix else None)
        e = ServeEngine(m, params, config=sc)
        rids = [e.submit(list(t), max_new=4) for t in prompts]
        out = e.run()
        return e, [out[r] for r in rids]

    common = list(range(1, 17))
    prompts = [common + [100 + i] * 4 for i in range(4)]
    e0, o0 = run(False)
    assert e0.kvm.state.refcnt is None          # off path truly inert
    assert e0.metrics["shared_admits"] == 0
    assert e0.metrics["cow_moves"] == 0
    e1, o1 = run(True)
    assert o1 == o0
    assert e1.metrics["shared_admits"] == 3
    assert e1.metrics["shared_pages"] == 6
    assert e1.metrics["cow_moves"] > 0
    assert e1.metrics["prefills"] == 1          # leader only
    e2, o2 = run(True, macro_k=4)
    assert o2 == o0
    assert e2.metrics["shared_admits"] == 3
