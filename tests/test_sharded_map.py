"""Channel-sharded FMMU map (ISSUE 5).

The paper's headline scalability claim — translation stays off the
critical path up to a 32-channel, 8-way SSD — rests on partitioning the
map state per channel. These tests pin the serving adaptation of that
partitioning to the single-device oracle:

  * property sweep: sharded ``translate_sharded`` vs the single-device
    serving path on identical random mixed LOOKUP/UPDATE/COND_UPDATE
    batches (duplicate/overflow keys included) — outputs, ok masks and
    the materialized table bit-identical, plus shadow-dict semantics
    (tests/fmmu_lockstep.sharded_lockstep);
  * shard_map lowering == vmap lowering bit-identically (in-process
    when the session has >= C devices — CI's tier1-sharded lane runs
    with XLA_FLAGS=--xla_force_host_platform_device_count=8 — and via
    an 8-virtual-device subprocess otherwise);
  * per-channel allocator stacks mirror the per-channel BlockPool free
    lists exactly; channel-dry raises per-channel OutOfBlocks / oob;
  * KVPageManager churn (new/extend/free/swap/precommit) against the
    retranslation oracle, the host-numpy swap oracle, and the mirror;
  * ServeEngine(channels=N): sharded K-step macro scan vs K single
    steps vs the unsharded engine — tokens bit-identical, per-channel
    pool free lists equal in non-retiring scans — plus the macro
    counter contract and zero fallbacks under per-channel pressure.

Every test here carries the ``sharded`` marker: CI's tier1-sharded lane
selects them under an 8-device host platform so the mesh lowering runs
for real; the normal lanes run them too (vmap lowering).
"""
import os
import random
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from fmmu_lockstep import sharded_geometries, sharded_lockstep
from repro.core.fmmu import batch as B
from repro.core.fmmu.types import (HOST_BASE, LOOKUP, NIL, UPDATE,
                                   small_geometry)
from repro.paging import kv_manager as KM
from repro.paging.kv_manager import KVPageManager
from repro.paging.pool import BlockPool, OutOfBlocks

pytestmark = pytest.mark.sharded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- core oracle
def test_sharded_lockstep_channels():
    """Sharded translate vs single-device oracle, C in {1, 2, 4, 8}:
    outputs, ok masks, and the materialized table bit-identical under
    random mixed batches with duplicate/overflow keys."""
    for C in (1, 2, 4, 8):
        res = sharded_lockstep(3, C, n_batches=20)
        assert res.startswith("OK"), f"C={C}: {res}"


def test_sharded_lockstep_degenerate_geometry():
    """1-way 2-set per-channel CMT (maximal eviction churn) and a
    channel count that does not divide the page space evenly."""
    res = sharded_lockstep(4, 4, n_batches=12,
                           geom_kw=dict(cmt_ways=1, cmt_sets=2))
    assert res.startswith("OK"), res
    res = sharded_lockstep(5, 3, n_batches=12)   # 128 pages % 3 != 0
    assert res.startswith("OK"), res


# pinned regression seeds: the seed/channel pairs that first exercised
# duplicate-block MSHR merges landing in different channels and a COND
# losing its race in a non-owner batch position
@example(11, 2)
@example(23, 8)
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 6), st.sampled_from([1, 2, 4, 8]))
def test_sharded_lockstep_property(seed, channels):
    res = sharded_lockstep(seed, channels, n_batches=12)
    assert res.startswith("OK"), f"C={channels} seed={seed}: {res}"


@pytest.mark.slow
def test_sharded_lockstep_long_interleaving():
    """Long mixed-op interleavings across every channel count — the
    oracle-hardening sweep's endurance case."""
    for C in (2, 4, 8):
        res = sharded_lockstep(7, C, n_batches=60)
        assert res.startswith("OK"), f"C={C}: {res}"


# ------------------------------------------------- shard_map == vmap
def _drive_pair(fj, vj, msS, msV, n_pages, seed, iters=10):
    rng = random.Random(seed)
    nprng = np.random.RandomState(seed)
    for it in range(iters):
        Bq = 16
        dl = np.asarray([rng.randrange(n_pages) if rng.random() < .9
                         else -1 for _ in range(Bq)], np.int32)
        opc = nprng.randint(0, 3, Bq).astype(np.int32)
        seen = set()
        for i in range(Bq):
            if opc[i] != LOOKUP and dl[i] in seen:
                dl[i] = -1
            seen.add(int(dl[i]))
        dp = nprng.randint(0, 10 ** 6, Bq).astype(np.int32)
        old = nprng.randint(0, 10 ** 6, Bq).astype(np.int32)
        msS, outS, okS = fj(msS, opc, dl, dp, old)
        msV, outV, okV = vj(msV, opc, dl, dp, old)
        np.testing.assert_array_equal(np.asarray(outS),
                                      np.asarray(outV), f"iter {it}")
        np.testing.assert_array_equal(np.asarray(okS),
                                      np.asarray(okV), f"iter {it}")
    for fld, a, b in zip(msV._fields, msV, msS):
        if fld == "fmmu":
            for f2, x, y in zip(a._fields, a, b):
                np.testing.assert_array_equal(np.asarray(x),
                                              np.asarray(y), f2)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          fld)


def test_shard_map_lowering_equals_vmap_inprocess():
    """With >= 2 devices in-process (the tier1-sharded CI lane forces
    8), the shard_map lowering over the channel mesh must be
    bit-identical to the portable vmap lowering — state pytree
    included."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.parallel.sharding import channel_mesh, shard_map
    C = jax.device_count()
    if C < 2:
        pytest.skip("needs >= 2 devices (tier1-sharded lane has 8)")
    C = 1 << (C.bit_length() - 1)       # largest pow2 <= device count
    _, gC = sharded_geometries(C)
    n_pages = 128
    msV = B.init_sharded_state(gC, C, n_device_blocks=16,
                               n_host_blocks=8, n_lanes=2)
    mesh = channel_mesh(C)
    msS = jax.device_put(msV, NamedSharding(mesh, P("channel")))
    fj = jax.jit(shard_map(
        B.make_sharded_shard_body(gC, C), mesh=mesh,
        in_specs=(P("channel"), P(), P(), P(), P()),
        out_specs=(P("channel"), P(), P())), donate_argnums=(0,))
    vj = jax.jit(functools.partial(B.translate_sharded, gC, C),
                 donate_argnums=(0,))
    _drive_pair(fj, vj, msS, msV, n_pages, seed=1)


@pytest.mark.slow
def test_shard_map_lowering_equals_vmap_subprocess():
    """Same bit-identity proven on a real 8-device host platform via a
    subprocess (the default test session sees 1 CPU device)."""
    prog = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = \
        "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, os.path.join(%r, "src"))
    sys.path.insert(0, os.path.join(%r, "tests"))
    import jax
    assert jax.device_count() == 8
    from test_sharded_map import (
        test_shard_map_lowering_equals_vmap_inprocess as t)
    t()
    print("SHARDED_OK")
    """ % (ROOT, ROOT))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("PYTEST_CURRENT_TEST", None)
    proc = subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_OK" in proc.stdout


def test_parallel_ctx_channel_axis():
    """ParallelCtx grows a 'channel' logical axis (ISSUE-5): specs
    naming it resolve onto the mesh's channel axis, ch_size reports
    its extent, and contexts without one replicate it (pre-ISSUE-5
    behavior preserved)."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import channel_ctx, trivial_ctx
    ctx = channel_ctx(1)            # 1 device suffices: mesh (1,1,1)
    assert ctx.ch_size == 1
    assert ctx.resolve(P("channel"), shape=(4,)) == P("channel")
    assert ctx.resolve(P(None, "channel"), shape=(3, 4)) \
        == P(None, "channel")
    sh = ctx.sharding(P("channel"), shape=(4,))
    assert sh.spec == P("channel")
    # no channel axis configured -> 'channel' replicates
    assert trivial_ctx().resolve(P("channel"), shape=(4,)) == P()
    assert trivial_ctx().ch_size == 1


# ------------------------------------------------- allocator sharding
def test_sharded_allocator_stacks_mirror_pool():
    """init_sharded_state stripes both tiers by block id mod C in
    per-channel BlockPool pop order (first pop of channel c = block c),
    bit-equal to BlockPool's per-channel free lists."""
    C = 4
    _, gC = sharded_geometries(C)
    ms = B.init_sharded_state(gC, C, n_device_blocks=10, n_host_blocks=6,
                              n_lanes=2)
    pool = BlockPool(10, 6, n_channels=C)
    for c in range(C):
        n = int(ms.free_n[c])
        assert n == pool.free_device_ch(c)
        np.testing.assert_array_equal(
            np.asarray(ms.free_stack[c, :n]),
            np.asarray(pool._free_dev_ch[c], np.int32))
        h = int(ms.host_n[c])
        assert h == pool.free_host_ch(c)
        np.testing.assert_array_equal(
            np.asarray(ms.host_stack[c, :h]),
            np.asarray(pool._free_host_ch[c], np.int32))


def test_grow_sharded_pops_owner_channel_and_flags_dry_channel():
    """grow_sharded pops each lane's block from the OWNER channel of
    its dlpn; a dry channel fails only its own lanes and raises only
    its own oob flag (per-channel pool pressure)."""
    C = 2
    _, gC = sharded_geometries(C)
    # channel 0 owns {0, 2}, channel 1 owns {1, 3}
    ms = B.init_sharded_state(gC, C, n_device_blocks=4)
    grow = jnp.array([True, True, True])
    dl = jnp.array([0, 1, 2], jnp.int32)     # owners: 0, 1, 0
    ms, blocks, ok = B.grow_sharded(gC, C, ms, grow, dl)
    assert list(np.asarray(blocks)) == [0, 1, 2]
    assert list(np.asarray(ok)) == [True] * 3
    assert not bool(np.asarray(ms.oob).any())
    # channel 0 is now dry; channel 1 still holds block 3
    ms, blocks, ok = B.grow_sharded(gC, C, ms, jnp.array([True, True]),
                                    jnp.array([4, 3], jnp.int32))
    assert list(np.asarray(blocks)) == [-1, 3]   # dlpn 4 -> ch 0: dry
    assert list(np.asarray(ok)) == [False, True]
    assert list(np.asarray(ms.oob)) == [True, False]
    # the committed mappings landed in the owning shards' tables
    tbl = np.asarray(B.dense_table(ms, C, 8))
    assert list(tbl[:5]) == [0, 1, 2, 3, NIL]


def test_pool_alloc_for_per_channel_out_of_blocks():
    pool = BlockPool(4, 0, n_channels=2)
    assert pool.alloc_for([0, 1, 0]) == [0, 1, 2]
    with pytest.raises(OutOfBlocks):
        pool.alloc_for([0])                  # channel 0 dry
    assert pool.free_device == 1             # pre-check popped nothing
    assert pool.alloc_for([1]) == [3]
    pool.free([2, 3])
    assert pool._free_dev_ch[0] == [2] and pool._free_dev_ch[1] == [3]


# ------------------------------------------------- KVPageManager churn
def _oracle_apply_swap(shadow, kvm, pre_pages, post_pages):
    row = lambda b: (kvm.pool.host_row(b) if BlockPool.is_host(b)
                     else b)
    src = [row(a) for a, b in zip(pre_pages, post_pages) if a != b]
    dst = [row(b) for a, b in zip(pre_pages, post_pages) if a != b]
    shadow[dst] = shadow[src]


@pytest.mark.parametrize("channels", [2, 4])
def test_kvm_sharded_churn_vs_oracles(channels):
    """new/extend/free/swap/precommit churn on a channel-sharded
    KVPageManager: pool bytes vs the host-numpy swap oracle, table vs
    the sharded retranslation oracle, per-channel allocator mirror
    exact, channel-lane counters sum to the routed lanes."""
    kvm = KVPageManager(n_slots=4, max_pages=6, n_device_blocks=16,
                        n_host_blocks=10, channels=channels)
    pool = jnp.arange((16 + 10 + 1) * 3.0).reshape(27, 3)
    shadow = np.array(pool)
    rng = random.Random(5)
    live = set()
    for step in range(80):
        ops = ["new"] if len(live) < 4 else []
        if live:
            ops += ["extend", "free", "swap_out", "swap_in", "pre"]
        op = rng.choice(ops)
        try:
            if op == "new":
                s = rng.choice([x for x in range(4) if x not in live])
                kvm.new_seq(s, rng.randint(1, 3))
                live.add(s)
            elif op == "extend":
                s = rng.choice(sorted(live))
                room = max(0, 6 - len(kvm.seq_pages[s]))
                if room:
                    kvm.extend_seq(s, rng.randint(1, room))
            elif op == "pre":
                # the sharded macro boundary's growth pre-commit
                slots = [s for s in sorted(live) if kvm.is_resident(s)
                         and len(kvm.seq_pages[s]) <= 4]
                if slots:
                    kvm.precommit_growth(slots + slots[:1])
            elif op == "free":
                s = rng.choice(sorted(live))
                kvm.free_seq(s)
                live.discard(s)
            else:
                s = rng.choice(sorted(live))
                pre = list(kvm.seq_pages[s])
                fn = kvm.swap_out if op == "swap_out" else kvm.swap_in
                [pool], _ = fn(s, [pool], check=rng.random() < 0.5)
                _oracle_apply_swap(shadow, kvm, pre, kvm.seq_pages[s])
        except OutOfBlocks:
            pass
        np.testing.assert_array_equal(np.asarray(pool), shadow,
                                      f"step {step}: pool diverged")
        if step % 16 == 15:
            np.testing.assert_array_equal(
                np.asarray(kvm.block_tables()),
                np.asarray(kvm.retranslate_tables()), f"step {step}")
            kvm.sync_allocator()
            st_ = kvm.state
            for c in range(channels):
                n = int(st_.free_n[c])
                assert n == kvm.pool.free_device_ch(c), (step, c)
                np.testing.assert_array_equal(
                    np.asarray(st_.free_stack[c, :n]),
                    np.asarray(kvm.pool._free_dev_ch[c], np.int32))
    assert kvm.channel_lanes.sum() > 0
    assert (kvm.channel_lanes > 0).all(), \
        "some channel never serviced a lane: routing is broken"


def test_kvm_sharded_swap_pending_lane_all_channels():
    """The swap_pending residency lane is replicated per channel and
    flips in the same fused call on every shard."""
    kvm = KVPageManager(n_slots=3, max_pages=4, n_device_blocks=8,
                        n_host_blocks=8, channels=2)
    pool = jnp.zeros((8 + 8 + 1, 2))
    kvm.new_seq(0, 2)
    [pool], _ = kvm.swap_out(0, [pool])
    lanes = np.asarray(kvm.state.swap_pending)
    assert lanes.shape == (2, 3)
    assert lanes[:, 0].all() and not lanes[:, 1:].any()
    assert not kvm.is_resident(0)
    [pool], _ = kvm.swap_in(0, [pool])
    assert not np.asarray(kvm.state.swap_pending).any()


# ------------------------------------------------- engine cross-tests
RTT = None
_MODEL = None


def _tiny_model():
    global RTT, _MODEL
    if _MODEL is None:
        from repro.configs import get_arch, smoke_config
        from repro.models import Runtime, build_model
        RTT = Runtime(compute_dtype=jnp.float32,
                      param_dtype=jnp.float32, remat="none",
                      page_size=8, capacity_factor=100.0)
        cfg = smoke_config(get_arch("llama3.2-1b"))
        m = build_model(cfg, RTT)
        params = m.init(jax.random.key(0))
        _MODEL = (m, params)
    return _MODEL


def _pool_state_ch(eng):
    return ([list(ch) for ch in eng.kvm.pool._free_dev_ch],
            [list(ch) for ch in eng.kvm.pool._free_host_ch],
            {s: list(p) for s, p in eng.kvm.seq_pages.items()})


def test_sharded_engine_tokens_match_unsharded():
    """channels=2 single-step AND macro tokens bit-identical to the
    channels=1 engine (retirement mid-scan included)."""
    from repro.serving.engine import ServeEngine
    m, params = _tiny_model()
    t1, t2 = list(range(1, 8)), list(range(50, 73))

    def run(channels, macro_k):
        eng = ServeEngine(m, params, n_slots=2, max_ctx=64,
                          macro_k=macro_k, channels=channels)
        r1 = eng.submit(t1, max_new=10)
        r2 = eng.submit(t2, max_new=7)      # retires mid-scan at K=4
        done = eng.run()
        return done[r1], done[r2], eng

    ref = run(1, 0)
    sh_ss = run(2, 0)
    sh_mk = run(2, 4)
    assert ref[:2] == sh_ss[:2] == sh_mk[:2]
    assert sh_mk[2].metrics["macro_steps"] > 0
    assert sh_mk[2].metrics["macro_fallbacks"] == 0
    np.testing.assert_array_equal(
        np.asarray(ref[2].kvm.block_tables()),
        np.asarray(sh_ss[2].kvm.block_tables()))


def test_sharded_macro_equals_single_steps_bitwise():
    """Non-retiring scans: channels=2 K-step macro == K single steps —
    tokens, block tables, seq_pages AND per-channel pool free lists
    (the pre-committed growth pops in the same step-major order the
    single-step path pops)."""
    from repro.serving.engine import ServeEngine
    m, params = _tiny_model()
    t1, t2 = list(range(1, 8)), list(range(30, 53))

    def run(macro_k):
        eng = ServeEngine(m, params, n_slots=2, max_ctx=64,
                          macro_k=macro_k, channels=2)
        r1 = eng.submit(t1, max_new=8)     # multiples of K: retirement
        r2 = eng.submit(t2, max_new=8)     # only at boundaries
        done = eng.run()
        return (done[r1], done[r2]), eng

    outs_s, eng_s = run(0)
    outs_m, eng_m = run(4)
    assert eng_m.metrics["macro_steps"] > 0
    assert outs_s == outs_m
    assert _pool_state_ch(eng_s) == _pool_state_ch(eng_m)
    np.testing.assert_array_equal(np.asarray(eng_s.kvm.block_tables()),
                                  np.asarray(eng_m.kvm.block_tables()))
    # per-channel allocator mirror agrees after the lazy sync
    eng_m.kvm.sync_allocator()
    st_ = eng_m.kvm.state
    for c in range(2):
        n = int(st_.free_n[c])
        assert n == eng_m.kvm.pool.free_device_ch(c)
        np.testing.assert_array_equal(
            np.asarray(st_.free_stack[c, :n]),
            np.asarray(eng_m.kvm.pool._free_dev_ch[c], np.int32))


def test_sharded_macro_counter_contract():
    """Per K tokens in sharded steady state: exactly 1 macro dispatch +
    1 host sync, at most 1 fused sharded map call (growth boundaries
    only), 0 allocator syncs, 0 full-map retranslations, no translate
    re-trace — and the routed lanes split ~1/N per channel."""
    from repro.serving import engine as E
    from repro.serving.engine import ServeEngine
    m, params = _tiny_model()
    K = 8
    eng = ServeEngine(m, params, n_slots=2, max_ctx=256, macro_k=K,
                      channels=2)
    eng.min_page_bucket = 32
    eng.submit(list(range(1, 9)), max_new=10 ** 6)
    eng.submit(list(range(20, 28)), max_new=10 ** 6)
    done: dict = {}
    eng.step(done)
    for _ in range(3):                 # settle: trace the scan variants
        eng.step(done)
    for _ in range(6):
        d0, s0 = E.MACRO_DISPATCHES[0], E.HOST_SYNCS[0]
        x0, f0, a0 = (KM.XLATE_CALLS[0], KM.FULL_TABLE_CALLS[0],
                      KM.ALLOC_SYNCS[0])
        p0 = B.PROBE_TRACES[0]
        n0 = eng.metrics["decode_steps"]
        eng.step(done)
        assert eng.metrics["decode_steps"] - n0 == K
        assert E.MACRO_DISPATCHES[0] - d0 == 1
        assert E.HOST_SYNCS[0] - s0 == 1
        assert KM.XLATE_CALLS[0] - x0 <= 1
        assert KM.FULL_TABLE_CALLS[0] - f0 == 0
        assert KM.ALLOC_SYNCS[0] - a0 == 0
        assert B.PROBE_TRACES[0] - p0 == 0, "sharded path re-traced"
    assert eng.metrics["macro_fallbacks"] == 0
    lanes = eng.kvm.channel_lanes
    assert lanes.sum() > 0
    # 1/N routing: with page-striped dlpns both channels carry work
    assert lanes.min() >= lanes.sum() // 4, lanes


@pytest.mark.slow
def test_sharded_oversubscribed_zero_fallbacks():
    """ISSUE-5 acceptance: ~2x oversubscription on a channels=2 engine
    (per-channel pools absorb the pressure) keeps every decode round on
    the fused sharded macro path — zero fallbacks, swap traffic
    nonzero, outputs bit-identical to uncontended solo runs."""
    from repro.serving.engine import ServeEngine
    m, params = _tiny_model()
    eng = ServeEngine(m, params, n_slots=4, max_ctx=64,
                      n_device_blocks=10, n_host_blocks=24, macro_k=4,
                      swap_patience=2, channels=2)
    prompts = [list(range(1 + 20 * i, 9 + 20 * i)) for i in range(4)]
    rids = [eng.submit(p, max_new=24) for p in prompts]
    done: dict = {}
    while eng.step(done):
        pass
    assert set(done) == set(rids)
    assert eng.metrics["macro_fallbacks"] == 0, \
        "per-channel pressure dropped the sharded engine off the " \
        "macro path"
    assert eng.metrics["swaps_out"] > 0 and eng.metrics["swaps_in"] > 0
    for p, rid in zip(prompts, rids):
        solo = ServeEngine(m, params, n_slots=1, max_ctx=64,
                           channels=2)
        rs = solo.submit(list(p), max_new=24)
        assert solo.run()[rs] == done[rid], rid
