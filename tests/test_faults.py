"""Fault-injection plane + recovery machinery (ISSUE 6).

Covers, bottom-up: plan determinism (replay-from-seed is the chaos
harness's only reproduction handle), BlockPool retirement + the typed
``PoolExhausted`` channel attribution, the pre-mutation guarantees of
injected swap/alloc failures, bad-block retirement re-driving writes
through the fused CondUpdate path, the zero-cost-when-disabled claim
(jaxpr + counter identity), the engine's retry/backoff/quarantine
state machine (hypothesis property with pinned regression examples),
the K-token detection latency of the in-graph oob flag, and the
satellite-6 same-boundary reservation release. The randomized
end-to-end sweeps live in tests/chaos/."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.core import faults as flt
from repro.core.faults import FaultPlan, FaultPlane, SwapFault, make_plan
from repro.models import Runtime, build_model
from repro.paging.kv_manager import KVPageManager
from repro.paging.pool import BlockPool, OutOfBlocks, PoolExhausted
from repro.serving.engine import ServeEngine
from hypothesis import example, given, settings, strategies as st

pytestmark = pytest.mark.faults

RT = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
             remat="none", page_size=8, capacity_factor=100.0)


@pytest.fixture(scope="module")
def tiny():
    """Minimal model (the serve-bench idiom): these tests exercise the
    fault/recovery plane, not the transformer — compute is kept as
    close to zero as the engine allows."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    cfg = dataclasses.replace(cfg, name="faults-tiny",
                              n_layers=cfg.period, d_model=32, n_heads=2,
                              n_kv_heads=1, head_dim=16, d_ff=64,
                              vocab_size=128)
    m = build_model(cfg, RT)
    return m, m.init(jax.random.key(0))


def _plan(horizon=32, channels=1, **axes):
    """FaultPlan with EXPLICIT schedule bits (unit tests want exact
    fault positions, not probabilities)."""

    def sched(key):
        out = np.zeros(horizon, bool)
        for i in axes.get(key, ()):
            out[i] = True
        return out

    stall = axes.get("stall")
    return FaultPlan(
        seed=0, swap_fail=sched("swap"), program_fail=sched("program"),
        alloc_fail=sched("alloc"),
        stall=(np.ones(channels) if stall is None
               else np.asarray(stall, np.float64)))


# ---------------------------------------------------------------- plan
def test_plan_determinism_and_replay():
    a = make_plan(1234, channels=2, swap_fail_p=0.2, program_fail_p=0.1,
                  alloc_fail_p=0.05, stall=[4.0, 1.0], horizon=512)
    b = make_plan(1234, channels=2, swap_fail_p=0.2, program_fail_p=0.1,
                  alloc_fail_p=0.05, stall=[4.0, 1.0], horizon=512)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    c = make_plan(1235, swap_fail_p=0.2, horizon=512)
    assert not np.array_equal(a.swap_fail, c.swap_fail)
    # rates land near p (the hash is uniform enough for scheduling)
    assert 0.1 < a.swap_fail.mean() < 0.3
    assert a.stall.shape == (2,)
    plane = FaultPlane(a)
    assert "seed=1234" in plane.describe()
    # the consumer walks the schedule with wraparound, counting fires
    fired = sum(plane.swap_fails() for _ in range(1024))
    assert fired == plane.counts()["swap"] == 2 * int(a.swap_fail.sum())


def test_plan_validation():
    with pytest.raises(AssertionError):
        make_plan(0, channels=2, stall=[1.0])          # shape mismatch
    with pytest.raises(AssertionError):
        make_plan(0, stall=[0.5])                      # < 1 not a stall


# ---------------------------------------------------------------- pool
def test_pool_retirement_permanently_removes_blocks():
    pool = BlockPool(8, 0, n_channels=2)
    blocks = pool.alloc_for([0, 0, 1])
    pool.retire(blocks[:2])                            # both channel 0
    assert pool.stats.retired == 2
    assert pool.retired_ch == [2, 0]
    assert all(pool.is_retired(b) for b in blocks[:2])
    free0 = pool.free_device
    pool.free(blocks)               # retired blocks never re-enter
    assert pool.free_device == free0 + 1
    with pytest.raises(AssertionError):
        pool.retire([blocks[0]])                       # never twice


def test_pool_exhausted_typed_channel_attribution():
    pool = BlockPool(4, 0, n_channels=2)               # 2 blocks/channel
    pool.alloc_for([0, 0])                             # drain channel 0
    with pytest.raises(PoolExhausted) as ei:
        pool.alloc_for([0])
    assert ei.value.channel == 0 and not ei.value.transient
    assert isinstance(ei.value, OutOfBlocks)           # old handlers work
    assert pool.exhausted_ch == [1, 0]
    # aggregate (channel-agnostic) shortage attributes the emptiest
    with pytest.raises(PoolExhausted) as ei:
        pool.alloc(3)
    assert ei.value.channel == 0
    assert pool.exhausted_ch == [2, 0]


# ------------------------------------------------- kvm injection points
def test_swap_fault_raises_before_any_mutation():
    kvm = KVPageManager(2, 4, 8, 8,
                        faults=FaultPlane(_plan(swap=[0])))
    kvm.new_seq(0, 2)
    pools = [jnp.arange(17.0)[:, None] * jnp.ones((1, 3))]
    pages0 = list(kvm.seq_pages[0])
    free0 = (kvm.pool.free_device, kvm.pool.free_host)
    with pytest.raises(SwapFault) as ei:
        kvm.swap_out(0, pools, check=False)
    assert (ei.value.slot, ei.value.n_blocks) == (0, 2)
    # pure retry contract: map, pools, page lists, free lists untouched
    assert kvm.seq_pages[0] == pages0
    assert (kvm.pool.free_device, kvm.pool.free_host) == free0
    assert kvm.is_resident(0)
    assert kvm.faults.counts()["swap"] == 1
    # schedule entry 1 is clean: the identical retry succeeds
    pools, moved = kvm.swap_out(0, pools, check=True)
    assert moved == 2 and not kvm.is_resident(0)
    st = kvm.hit_stats()
    assert st["swap_faults"] == 1 and st["swaps_out"] == 2


def test_alloc_fault_is_transient_and_pre_pop():
    kvm = KVPageManager(2, 4, 8, 0,
                        faults=FaultPlane(_plan(alloc=[0])))
    free0 = kvm.pool.free_device
    with pytest.raises(PoolExhausted) as ei:
        kvm.new_seq(0, 2)
    assert ei.value.transient and ei.value.channel == 0
    assert kvm.pool.free_device == free0               # nothing popped
    assert 0 not in kvm.seq_pages
    assert kvm.pool.exhausted_ch[0] == 1
    kvm.new_seq(0, 2)                                  # retry clean
    assert len(kvm.seq_pages[0]) == 2


def test_program_fault_retires_and_redrives_same_channel():
    kvm = KVPageManager(2, 4, 8, 0,
                        faults=FaultPlane(_plan(program=[0])))
    blocks = kvm.new_seq(0, 2)
    bad_stats = kvm.hit_stats()
    # schedule: program 0 (the first freshly mapped block) failed; its
    # replacement (consult 2) succeeded. CondUpdate re-drove the map.
    assert bad_stats["retired_blocks"] == 1
    assert bad_stats["program_faults"] == 1
    retired = [b for b in range(8) if kvm.pool.is_retired(b)]
    assert len(retired) == 1
    assert retired[0] not in blocks
    assert kvm.pool.channel_of(retired[0]) == \
        kvm.pool.channel_of(blocks[0])
    # the map agrees with the page list (the re-drive committed)
    tables = np.asarray(kvm.block_tables())
    np.testing.assert_array_equal(tables[0, :2], blocks)
    # retirement shrinks capacity permanently: 8 - 2 held - 1 retired
    assert kvm.pool.free_device == 5


def test_program_fault_redrive_chain_is_bounded():
    """Every program fails (p=1 schedule): the re-drive chain retires
    at most _MAX_REDRIVE candidates, keeps the last one regardless,
    and a dry channel defers retirement instead of deadlocking."""
    from repro.paging.kv_manager import _MAX_REDRIVE
    kvm = KVPageManager(1, 4, 16, 0,
                        faults=FaultPlane(_plan(
                            horizon=1, program=[0])))   # wraps: all True
    kvm.new_seq(0, 1)
    assert len(kvm.seq_pages[0]) == 1
    assert kvm.hit_stats()["retired_blocks"] == _MAX_REDRIVE
    # mapped block is the chain's last candidate, kept despite its
    # schedule failure (bounded recovery)
    assert not kvm.pool.is_retired(kvm.seq_pages[0][0])


def test_retire_bad_blocks_moves_rows_when_data_programmed():
    """The reconcile-path variant: data already lives in the bad block,
    so retirement must move rows old->new inside the fused CondUpdate
    jit (a bad block is just another relocation)."""
    kvm = KVPageManager(2, 4, 8, 0)
    blocks = kvm.new_seq(0, 2)
    pool = jnp.arange(8.0)[:, None] * jnp.ones((1, 3))
    victim = blocks[0]
    want = np.asarray(pool)[victim].copy()   # pool donates into the jit
    kvm.faults = FaultPlane(_plan())            # no schedule needed
    (moved,), n = kvm.retire_bad_blocks([(0, victim)], pools=[pool],
                                        block_axis=0)
    assert n == 1 and kvm.pool.is_retired(victim)
    new = kvm.seq_pages[0][0]
    assert new != victim
    np.testing.assert_array_equal(np.asarray(moved)[new], want)
    np.testing.assert_array_equal(
        np.asarray(kvm.block_tables())[0, :2], kvm.seq_pages[0])


# ------------------------------------------- disabled plane: zero cost
def test_disabled_plane_jaxpr_identical():
    """Attaching a plane must not change any traced graph: the plane
    is consumed at host commit points only. Asserted, not assumed —
    the fused serve and swap jaxprs are string-identical with and
    without a plane."""
    plain = KVPageManager(2, 4, 8, 8)
    faulty = KVPageManager(2, 4, 8, 8,
                           faults=FaultPlane(make_plan(
                               7, swap_fail_p=0.5, program_fail_p=0.5,
                               alloc_fail_p=0.5, stall=[4.0])))
    opc = np.zeros(4, np.int32)
    dl = np.arange(4, dtype=np.int32)
    args = (opc, dl, dl, dl)

    def serve_jaxpr(k):
        return str(jax.make_jaxpr(
            lambda s: k.fns["serve"](s, *args))(k.state))

    assert serve_jaxpr(plain) == serve_jaxpr(faulty)

    pools = [jnp.zeros((17, 2))]
    lanes = (dl, dl, dl, dl, dl, np.int32(0), True)

    def swap_jaxpr(k):
        fn = k._swap_fn(4, 0, 1)
        return str(jax.make_jaxpr(
            lambda s, p: fn(s, p, *lanes))(k.state, pools))

    assert swap_jaxpr(plain) == swap_jaxpr(faulty)


def test_zero_probability_plan_is_counter_identical(tiny):
    """A plan with all-zero probabilities must be bit-and-counter
    identical to no plan at all: same outputs, same engine metrics,
    zero fired faults — the hot path pays nothing when faults are
    'on but quiet'."""
    m, params = tiny
    eng = ServeEngine(m, params, n_slots=4, max_ctx=64,
                      n_device_blocks=10, n_host_blocks=24, macro_k=4,
                      swap_patience=2)

    def run():
        rids = [eng.submit(list(range(1 + 7 * i, 9 + 7 * i)),
                           max_new=16) for i in range(4)]
        done = eng.run()
        return [done[r] for r in rids], dict(eng.metrics)

    out_none, met_none = run()
    eng.reset(FaultPlane(make_plan(99)))       # p=0 on every axis
    out_zero, met_zero = run()
    assert out_none == out_zero
    assert met_none == met_zero
    assert eng.faults.counts() == {"swap": 0, "program": 0, "alloc": 0,
                                   "crash": 0}
    st = eng.kvm.hit_stats()
    assert st["swap_faults"] == st["program_faults"] == \
        st["alloc_faults"] == 0
    assert st["retired_blocks"] == 0


# --------------------------------- engine retry/backoff/quarantine FSM
def _stub_engine(max_retries=3, cap=8, watchdog=4):
    """The scheduler-side recovery state machine on a stub: the methods
    under test (_note_swap_fault/_backed_off/_quarantine/_release_slot/
    _watchdog) touch only host bookkeeping, so no model is needed."""
    from repro.serving.engine import Request
    e = types.SimpleNamespace()
    e.metrics = {"swap_faults": 0, "quarantines": 0,
                 "watchdog_quarantines": 0, "requeues": 0}
    e._swap_fails, e._retry_at, e._progress = {}, {}, {}
    e._pending_since, e._resident_since = {}, {}
    e.active, e.queue = {}, __import__("collections").deque()
    e.ctx_lens = np.zeros(4, np.int32)
    e._boundary = 0
    e.max_swap_retries, e.swap_backoff_cap = max_retries, cap
    e.watchdog_rounds = watchdog
    e.kvm = types.SimpleNamespace(freed=[])
    e.kvm.free_seq = e.kvm.freed.append
    e.journal = None          # quarantine journals when attached (PR 7)
    for name in ("_note_swap_fault", "_backed_off", "_quarantine",
                 "_release_slot", "_watchdog"):
        setattr(e, name, types.MethodType(getattr(ServeEngine, name), e))
    req = Request(rid=0, tokens=[1, 2], max_new=4, out=[9], slot=1)
    e.active[0] = req
    return e, req


@example(fails=3, retries=3, cap=8)     # quarantine exactly at the cap
@example(fails=2, retries=3, cap=8)     # backoff only, no quarantine
@example(fails=6, retries=7, cap=4)     # backoff saturates at the cap
@example(fails=1, retries=1, cap=8)     # immediate quarantine
@settings(max_examples=50, deadline=None)
@given(fails=st.integers(1, 12), retries=st.integers(1, 8),
       cap=st.integers(1, 32))
def test_retry_backoff_quarantine_property(fails, retries, cap):
    """For any failure run: backoff is exactly min(2^n, cap) boundaries
    after the n-th consecutive failure, the window gates _backed_off,
    quarantine fires exactly when n reaches max_swap_retries — freeing
    pages ONCE, requeuing the request at the admission front with
    output reset — and per-slot state is fully cleared."""
    e, req = _stub_engine(max_retries=retries, cap=cap)
    for n in range(1, fails + 1):
        if 0 not in e.active:
            break                       # already quarantined
        e._note_swap_fault(1)
        if n >= retries:
            assert 0 not in e.active, "quarantine late"
            break
        assert e._retry_at[1] - e._boundary == min(2 ** n, cap)
        assert e._backed_off(1)
        e._boundary += min(2 ** n, cap)
        assert not e._backed_off(1)     # window exactly closed
    quarantined = fails >= retries
    assert e.metrics["quarantines"] == int(quarantined)
    assert e.metrics["swap_faults"] == min(fails, retries)
    if quarantined:
        assert e.kvm.freed == [1]       # pages freed exactly once
        assert list(e.queue)[0] is req  # admission FRONT
        assert req.slot == -1 and req.out == []
        for d in (e._swap_fails, e._retry_at, e._progress):
            assert 1 not in d           # slot state fully released
        assert e.ctx_lens[1] == 0


def test_watchdog_quarantines_stalled_lane_only():
    e, req = _stub_engine(watchdog=3)
    from repro.serving.engine import Request
    live = Request(rid=1, tokens=[1], max_new=4, out=[], slot=2)
    e.active[1] = live
    for _ in range(6):
        e._boundary += 1
        e._watchdog()
        live.out.append(7)              # lane 2 makes progress; 1 not
    assert 0 not in e.active, "stalled lane not quarantined"
    assert 1 in e.active, "progressing lane wrongly quarantined"
    assert e.metrics["watchdog_quarantines"] == 1
    assert list(e.queue) == [req]


def test_free_eff_degrades_stalled_channels_only():
    e = types.SimpleNamespace(channels=2)
    e.kvm = types.SimpleNamespace(
        free_device_vec=lambda: np.asarray([12, 9], np.int64))
    e._free_eff = types.MethodType(ServeEngine._free_eff, e)
    e._stall_shrink = types.MethodType(ServeEngine._stall_shrink, e)
    e.faults = None
    np.testing.assert_array_equal(e._free_eff(), [12, 9])
    e.faults = FaultPlane(_plan(channels=2, stall=[4.0, 1.0]))
    np.testing.assert_array_equal(e._free_eff(), [3, 9])


# ----------------------------------------------- engine-level recovery
def test_swap_retry_then_success_end_to_end(tiny):
    """One injected swap failure under oversubscription: the engine
    backs the slot off, retries after the window, and the outputs stay
    bit-identical to the fault-free run (retry is pure)."""
    m, params = tiny
    eng = ServeEngine(m, params, n_slots=4, max_ctx=64,
                      n_device_blocks=10, n_host_blocks=24, macro_k=4,
                      swap_patience=2)
    prompts = [list(range(1 + 7 * i, 9 + 7 * i)) for i in range(4)]

    def run():
        rids = [eng.submit(list(p), max_new=16) for p in prompts]
        done = eng.run()
        return [done[r] for r in rids]

    ref = run()
    eng.reset(FaultPlane(_plan(horizon=64, swap=[0, 3])))
    got = run()
    assert got == ref
    assert eng.metrics["swap_faults"] >= 1
    assert eng.metrics["quarantines"] == 0     # retries sufficed


def test_quarantine_releases_reservation_same_boundary(tiny):
    """Satellite 6 regression: when a preemption victim's swap-out
    fails terminally (retries exhausted -> quarantine), its freed pages
    must satisfy the blocked allocation in the SAME scheduling round —
    the engine neither raises OutOfBlocks nor deadlocks, the
    quarantined request restarts from the admission front, and every
    output matches the fault-free run."""
    m, params = tiny
    eng = ServeEngine(m, params, n_slots=2, max_ctx=64,
                      n_device_blocks=2, n_host_blocks=4,
                      fault_plane=FaultPlane(_plan(horizon=64, swap=[0])),
                      max_swap_retries=1)     # first failure quarantines
    t1, t2 = list(range(1, 9)), list(range(30, 38))
    r1 = eng.submit(t1, max_new=6)
    r2 = eng.submit(t2, max_new=6)
    done = eng.run()
    assert set(done) == {r1, r2}
    assert eng.metrics["quarantines"] >= 1
    assert eng.metrics["requeues"] >= 1
    for toks, rid in [(t1, r1), (t2, r2)]:
        solo = ServeEngine(m, params, n_slots=1, max_ctx=64)
        rs = solo.submit(list(toks), max_new=6)
        assert solo.run()[rs] == done[rid], rid


def test_transient_alloc_fault_does_not_trip_livelock_raise(tiny):
    """The _grow_pages livelock guard must distinguish injected
    transient exhaustion (schedule advances -> retry is progress) from
    genuine dry-pool pressure (same state recurs -> raise)."""
    m, params = tiny
    eng = ServeEngine(
        m, params, n_slots=1, max_ctx=64, n_device_blocks=4,
        n_host_blocks=0,
        fault_plane=FaultPlane(_plan(horizon=64, alloc=[1, 2, 5])))
    rid = eng.submit(list(range(1, 9)), max_new=12)
    done = eng.run()
    assert rid in done
    assert eng.kvm.hit_stats()["alloc_faults"] >= 1
    solo = ServeEngine(m, params, n_slots=1, max_ctx=64)
    rs = solo.submit(list(range(1, 9)), max_new=12)
    assert solo.run()[rs] == done[rid]


def test_macro_program_fault_relocates_written_rows(tiny):
    """C=1 macro path: a program fault on a block the scan already
    WROTE must relocate both the mapping and the KV rows (the
    retire-with-pools path) — tokens stay bit-identical to the
    fault-free run, which would fail if the rows were dropped."""
    m, params = tiny
    eng = ServeEngine(m, params, n_slots=2, max_ctx=64, macro_k=4)
    prompts = [list(range(1, 8)), list(range(40, 47))]

    def run():
        rids = [eng.submit(list(p), max_new=16) for p in prompts]
        done = eng.run()
        return [done[r] for r in rids]

    ref = run()
    eng.reset(FaultPlane(_plan(horizon=64, program=[2, 3, 7])))
    got = run()
    assert got == ref
    st = eng.kvm.hit_stats()
    assert st["retired_blocks"] >= 1
    assert st["program_faults"] >= 1


# ------------------------------------------------- detection latency
def test_oob_detection_latency_is_at_most_k_tokens(tiny):
    """The in-graph allocation-failure flag is written at the failing
    scan step but OBSERVABLE only at the next host sync — up to K
    tokens later (the documented detection latency; stickiness makes
    the deferred read lossless). Forcing the macro path onto a dry
    pool: the host's typed per-channel exhaustion count is zero before
    the boundary and folded exactly at it."""
    m, params = tiny
    eng = ServeEngine(m, params, n_slots=1, max_ctx=64,
                      n_device_blocks=1, n_host_blocks=0, macro_k=4)
    # bypass the proactive eligibility check so the scan really runs
    # its allocator dry (the reactive path under test)
    eng._macro_eligible = lambda: True
    eng.submit(list(range(1, 9)), max_new=3)   # budget < K: full mode
    done: dict = {}
    assert eng.kvm.hit_stats()["pool_exhausted"] == [0]
    eng.step(done)                             # scan: growth fails in-graph
    st = eng.kvm.hit_stats()
    assert st["pool_exhausted"][0] >= 1, \
        "boundary never folded the sticky oob flag"
    # the failing lane paused in-scan: nothing was emitted into the
    # scratch block's shadow (full-mode NIL masking)
    assert eng.metrics["generated"] <= 1       # prefill token only
    # the resync acknowledges + clears the flag lane
    eng.kvm.sync_allocator()
    assert not bool(np.asarray(eng.kvm.state.oob))


def test_sharded_oob_lane_folds_per_channel():
    """C>1 silent-flag regression (satellite a): each channel's sticky
    flag folds into its own typed exhaustion count at sync — before
    the fix the C>1 engine cleared the lane without ever reading it."""
    kvm = KVPageManager(2, 4, 8, 0, channels=2, use_mesh=False)
    kvm.new_seq(0, 2)
    kvm.state = kvm.state._replace(
        oob=jnp.asarray([True, False]))        # channel 0 ran dry
    kvm._alloc_dirty = True
    kvm.sync_allocator()
    assert kvm.pool.exhausted_ch == [1, 0]
    np.testing.assert_array_equal(np.asarray(kvm.state.oob),
                                  [False, False])
