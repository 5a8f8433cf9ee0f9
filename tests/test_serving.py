"""End-to-end serving correctness: prefill + paged decode must equal the
teacher-forced full forward, for every architecture family (paged GQA,
local/global+softcap, SSM states, hybrid, MoE, cross-attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.models import Runtime, build_model
from repro.serving.config import ServeConfig
from repro.serving.engine import ServeEngine

RT = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
             remat="none", page_size=8, capacity_factor=100.0)

ARCHS = ["llama3.2-1b", "gemma2-9b", "glm4-9b", "qwen2-72b",
         "jamba-1.5-large-398b", "mamba2-1.3b", "dbrx-132b",
         "arctic-480b", "seamless-m4t-large-v2", "llava-next-mistral-7b"]


def _teacher_logits(m, params, req_batch, upto):
    """Full-forward logits at position upto-1 (teacher forcing)."""
    batch = {k: v for k, v in req_batch.items()}
    batch["tokens"] = req_batch["tokens"][:, :upto]
    logits, _ = jax.jit(m.prefill)(params, batch)
    return logits


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_teacher_forcing(arch):
    cfg = smoke_config(get_arch(arch))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    key = jax.random.key(1)
    L, n_new = 21, 4
    toks = np.asarray(
        jax.random.randint(key, (L + n_new,), 0, cfg.vocab_size))
    extra = {}
    if cfg.prefix_len:
        extra["prefix_emb"] = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 1), (cfg.prefix_len, cfg.d_model),
            jnp.float32)
    if cfg.n_enc_layers:
        extra["src_emb"] = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 2), (32, cfg.d_model), jnp.float32)

    eng = ServeEngine(m, params, n_slots=2, max_ctx=64)
    rid = eng.submit(list(toks[:L]), max_new=n_new, **extra)

    # engine greedy decode
    done = eng.run()
    got = done[rid]

    # teacher-forced reference: at each step, feed ground-truth prefix
    # where "ground truth" is the engine's own greedy choice
    full = list(toks[:L]) + got
    req_batch = {"tokens": jnp.asarray(full)[None]}
    if "prefix_emb" in extra:
        req_batch["prefix_emb"] = extra["prefix_emb"][None]
    if "src_emb" in extra:
        req_batch["src_emb"] = extra["src_emb"][None]
        req_batch["src_valid"] = jnp.ones((1, 32), jnp.int32)
    for t in range(n_new):
        ref_logits = _teacher_logits(m, params, req_batch, L + t)
        want = int(jnp.argmax(ref_logits[0]))
        assert got[t] == want, (
            f"{arch}: step {t}: engine={got[t]} teacher={want}")


def test_two_concurrent_requests_isolated():
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    t1 = list(range(1, 12))
    t2 = list(range(50, 73))
    # solo runs
    e1 = ServeEngine(m, params, n_slots=2, max_ctx=64)
    r1 = e1.submit(t1, max_new=4)
    solo1 = e1.run()[r1]
    e2 = ServeEngine(m, params, n_slots=2, max_ctx=64)
    r2 = e2.submit(t2, max_new=4)
    solo2 = e2.run()[r2]
    # batched together
    e = ServeEngine(m, params, n_slots=2, max_ctx=64)
    rr1 = e.submit(t1, max_new=4)
    rr2 = e.submit(t2, max_new=4)
    both = e.run()
    assert both[rr1] == solo1
    assert both[rr2] == solo2


def test_preemption_swap_roundtrip():
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    # tiny device pool: 6 blocks of 8 tokens; host overflow available
    eng = ServeEngine(m, params, n_slots=2, max_ctx=48,
                      n_device_blocks=6, n_host_blocks=8)
    r1 = eng.submit(list(range(1, 25)), max_new=4)   # 24 toks -> 4 pages
    r2 = eng.submit(list(range(30, 50)), max_new=4)  # 20 toks -> 3 pages
    done = eng.run()
    assert set(done) == {r1, r2}
    assert eng.metrics["preemptions"] >= 1
    # compare r1 against solo run (no preemption)
    solo = ServeEngine(m, params, n_slots=1, max_ctx=48)
    rs = solo.submit(list(range(1, 25)), max_new=4)
    assert solo.run()[rs] == done[r1]


def test_growth_pause_resume_without_host_tier():
    """On-demand growth under a tight pool with NO host tier: a slot
    whose page growth fails must PAUSE (not decode into the scratch
    block) and resume once blocks free up, with outputs identical to
    uncontended solo runs."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    # pool of 3 pages, page_size 8: both prompts take 1 page each; at
    # ctx 8 both want a second page -> only one can grow, the other
    # pauses until r1 finishes and frees its blocks
    eng = ServeEngine(m, params, n_slots=2, max_ctx=64,
                      n_device_blocks=3, n_host_blocks=0)
    t1, t2 = list(range(1, 9)), list(range(30, 38))
    r1 = eng.submit(t1, max_new=6)
    r2 = eng.submit(t2, max_new=12)
    done = eng.run()
    assert set(done) == {r1, r2}
    for toks, max_new, rid in [(t1, 6, r1), (t2, 12, r2)]:
        solo = ServeEngine(m, params, n_slots=1, max_ctx=64)
        rs = solo.submit(list(toks), max_new=max_new)
        assert solo.run()[rs] == done[rid], rid


def test_growth_livelock_raises_out_of_blocks():
    """If every resident needs pages and nothing can be grown or
    preempted, the engine must raise (pausing everyone would spin
    forever) rather than silently corrupt KV in the scratch block."""
    from repro.paging.pool import OutOfBlocks

    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, n_slots=1, max_ctx=64,
                      n_device_blocks=2, n_host_blocks=0)
    eng.submit(list(range(1, 9)), max_new=40)   # needs 6 pages, pool=2
    with pytest.raises(OutOfBlocks):
        eng.run()


def test_fmmu_map_hit_stats_progress():
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, n_slots=2, max_ctx=32)
    rid = eng.submit(list(range(1, 17)), max_new=4)
    eng.run()
    st = eng.kvm.hit_stats()
    # the incremental table means the hot path performs zero lookups:
    # only UPDATE lanes ran, so the probe counters must NOT have moved
    assert st["updates"] > 0
    assert st["hits"] + st["misses"] == 0
    # the probe path itself is still live (oracle retranslation uses it)
    eng.kvm.retranslate_tables()
    st = eng.kvm.hit_stats()
    assert st["hits"] + st["misses"] > 0


def _pool_state(eng):
    return (list(eng.kvm.pool._free_dev), list(eng.kvm.pool._free_host),
            {s: list(p) for s, p in eng.kvm.seq_pages.items()})


@pytest.mark.slow
def test_macro_step_equivalence_bitwise():
    """ISSUE-3 equivalence: K-step fused decode produces bit-identical
    tokens, block tables, and pool state to K single steps — including
    slots crossing page boundaries mid-macro-step (7-token prompts,
    page 8: the crossing lands inside a scan) and a slot finishing
    mid-scan (max_new=7 with K=4 retires at scan step 3). Marked slow:
    CI fast lane skips it; the full lane and local tier-1 run it."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    t1, t2 = list(range(1, 8)), list(range(50, 73))

    def run(macro_k):
        eng = ServeEngine(m, params, n_slots=2, max_ctx=64,
                          macro_k=macro_k)
        r1 = eng.submit(t1, max_new=10)     # budget > K: simple variant
        r2 = eng.submit(t2, max_new=7)      # finishes mid-scan: full
        done = eng.run()
        return done[r1], done[r2], eng

    a1, a2, es = run(0)
    b1, b2, em = run(4)
    assert em.metrics["macro_steps"] > 0
    assert (a1, a2) == (b1, b2)
    assert _pool_state(es) == _pool_state(em)
    np.testing.assert_array_equal(np.asarray(es.kvm.block_tables()),
                                  np.asarray(em.kvm.block_tables()))
    # device allocator mirror agrees with the host pool once the
    # (lazily deferred) sync of the final host-side frees runs
    em.kvm.sync_allocator()
    st = em.kvm.state
    assert int(st.free_n) == em.kvm.pool.free_device
    np.testing.assert_array_equal(
        np.asarray(st.free_stack[:int(st.free_n)]),
        np.asarray(em.kvm.pool._free_dev, np.int32))


@pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma2-9b"])
def test_macro_step_writes_only_its_tokens_rows(arch):
    """One K-step macro scan changes the KV pools at exactly the rows its
    live lanes wrote: for each attention layer l (period p, attention
    index a: l = p * n_attn + a, gemma2 holding two per period) and each
    token the scan decodes at position t of slot s, row (l, block of t
    in s's table, t % page) of both pools; nothing else. A token written
    into the wrong layer, or a layer written back whole, shows here."""
    cfg = smoke_config(get_arch(arch))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    K = 4
    eng = ServeEngine(m, params, config=ServeConfig(
        n_slots=2, max_ctx=64, macro_k=K))
    eng.submit(list(range(1, 8)), max_new=40)      # crosses a page
    eng.submit(list(range(50, 62)), max_new=40)
    done: dict = {}
    eng.step(done)                       # admission, prefill, a scan
    before = {k: np.asarray(eng.caches[k]) for k in ("pool_k", "pool_v")}
    ctx0 = eng.ctx_lens.copy()
    n0, s0 = eng.metrics["decode_steps"], eng.metrics["macro_steps"]
    eng.step(done)
    assert eng.metrics["macro_steps"] - s0 == 1
    assert eng.metrics["decode_steps"] - n0 == K
    assert list(eng.ctx_lens - ctx0) == [K, K]
    table = np.asarray(eng.kvm.block_tables())
    n_per, n_attn = before["pool_k"].shape[:2]
    want = {(p, a, int(table[s, t // eng.page]), t % eng.page)
            for p in range(n_per) for a in range(n_attn)
            for s in range(2) for t in range(ctx0[s], ctx0[s] + K)}
    for k, old in before.items():
        new = np.asarray(eng.caches[k])
        assert new.shape == old.shape
        changed = np.nonzero((new != old).any(axis=-1))
        assert set(zip(*map(lambda a: a.tolist(), changed))) == want, k


def test_macro_pool_dry_engages_single_step_fallback():
    """ISSUE-3: when the device pool cannot cover a worst-case K-step
    growth, the engine must fall back to single-step mode (whose
    preempt/pause machinery needs the host) BEFORE the in-graph
    allocator can run dry — pause semantics preserved, outputs equal
    the uncontended solo runs, and the macro path reports fallbacks."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    t1, t2 = list(range(1, 9)), list(range(30, 38))

    eng = ServeEngine(m, params, n_slots=2, max_ctx=64,
                      n_device_blocks=3, n_host_blocks=0, macro_k=4)
    r1 = eng.submit(t1, max_new=6)
    r2 = eng.submit(t2, max_new=12)
    done = eng.run()
    assert set(done) == {r1, r2}
    assert eng.metrics["macro_fallbacks"] > 0
    assert not bool(np.asarray(eng.kvm.state.oob)), \
        "in-graph allocator ran dry: proactive check failed"
    for toks, max_new, rid in [(t1, 6, r1), (t2, 12, r2)]:
        solo = ServeEngine(m, params, n_slots=1, max_ctx=64)
        rs = solo.submit(list(toks), max_new=max_new)
        assert solo.run()[rs] == done[rid], rid


def test_macro_steady_state_one_dispatch_one_sync_per_k_steps():
    """ISSUE-3 acceptance: steady-state fused decode performs exactly
    ONE host dispatch and ONE device->host sync per K steps, zero host
    -side fused map calls, zero full-map retranslations, zero
    allocator re-syncs, and no re-tracing of the translate pipeline."""
    from repro.core.fmmu import batch as B
    from repro.paging import kv_manager as KM
    from repro.serving import engine as E

    K = 8
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, n_slots=2, max_ctx=256, macro_k=K)
    eng.min_page_bucket = 32       # pin: a bucket crossing re-traces
    eng.submit(list(range(1, 9)), max_new=10 ** 6)
    eng.submit(list(range(20, 28)), max_new=10 ** 6)
    done: dict = {}
    eng.step(done)                     # admission + prefill + 1st macro
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
        assert KM.XLATE_CALLS[0] - x0 == 0
        assert KM.FULL_TABLE_CALLS[0] - f0 == 0
        assert KM.ALLOC_SYNCS[0] - a0 == 0
        assert B.PROBE_TRACES[0] - p0 == 0, "macro scan re-traced"
    assert eng.metrics["macro_fallbacks"] == 0


def test_oversubscribed_zero_fallbacks_counter_enforced():
    """ISSUE-4 acceptance: under ~2x oversubscription (4 live
    sequences vs a device pool sized for ~2, host tier holding the
    overflow) the non-blocking swap pipeline keeps EVERY decode round
    on the fused macro path — zero single-step fallbacks, asserted
    from counters, not timings — while swap traffic is nonzero and
    every output is bit-identical to an uncontended solo run."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    # each seq: 8-token prompt + 24 new = 4 pages; 4 seqs = 16 pages
    # of working set vs 10 device blocks (~2x); host absorbs the rest
    eng = ServeEngine(m, params, n_slots=4, max_ctx=64,
                      n_device_blocks=10, n_host_blocks=24, macro_k=4,
                      swap_patience=2)
    prompts = [list(range(1 + 20 * i, 9 + 20 * i)) for i in range(4)]
    rids = [eng.submit(p, max_new=24) for p in prompts]
    done: dict = {}
    swapped_slots = set()
    while eng.step(done):
        for r in eng.active.values():
            if not eng.kvm.is_resident(r.slot):
                swapped_slots.add(r.slot)
    assert set(done) == set(rids)
    assert eng.metrics["macro_fallbacks"] == 0, \
        "oversubscription dropped the engine out of the macro path"
    assert eng.metrics["swaps_out"] > 0 and eng.metrics["swaps_in"] > 0
    assert len(swapped_slots) >= 2, "rotation never swapped anyone"
    st = eng.kvm.hit_stats()
    assert st["swaps_out"] > 0 and st["swaps_in"] > 0
    # a swap-pending slot that resumed must be bit-identical to a solo
    # run that never swapped (the pipeline moved its KV bytes exactly)
    for p, rid in zip(prompts, rids):
        solo = ServeEngine(m, params, n_slots=1, max_ctx=64)
        rs = solo.submit(list(p), max_new=24)
        assert solo.run()[rs] == done[rid], rid


def test_nonblocking_false_restores_fallback_behavior():
    """The PR-3 baseline knob: with nonblocking_swap=False the same
    oversubscribed workload must fall back to single-step mode (the
    behavior serve_bench's oversub_fallback mode times) and still
    produce identical outputs — the pipelines differ in scheduling,
    never in results."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))

    def run(nonblocking):
        eng = ServeEngine(m, params, n_slots=4, max_ctx=64,
                          n_device_blocks=10, n_host_blocks=24,
                          macro_k=4, swap_patience=2,
                          nonblocking_swap=nonblocking)
        rids = [eng.submit(list(range(1 + 20 * i, 9 + 20 * i)),
                           max_new=24) for i in range(4)]
        done = eng.run()
        return [done[r] for r in rids], eng

    outs_nb, eng_nb = run(True)
    outs_fb, eng_fb = run(False)
    assert outs_nb == outs_fb
    assert eng_nb.metrics["macro_fallbacks"] == 0
    assert eng_fb.metrics["macro_fallbacks"] > 0, \
        "PR-3 baseline should have fallen back under pressure"


def test_chunked_admission_token_budget():
    """Continuous-batching admission: a prompt longer than the
    per-round token budget is chunk-prefilled (first chunk through the
    prefill kernel, remainder streamed through the decode path as
    forced lanes) and the outputs are identical to unbudgeted
    admission — on both the single-step and macro paths."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    long_p = [int(t) for t in np.asarray(jax.random.randint(
        jax.random.key(3), (30,), 1, cfg.vocab_size))]
    short_p = list(range(40, 48))

    def run(admit_tokens, macro_k):
        eng = ServeEngine(m, params, n_slots=2, max_ctx=64,
                          macro_k=macro_k, admit_tokens=admit_tokens)
        r1 = eng.submit(list(long_p), max_new=6)
        r2 = eng.submit(list(short_p), max_new=6)
        d = eng.run()
        return d[r1], d[r2], eng

    ref1, ref2, eng0 = run(None, 0)
    assert eng0.metrics["chunked_prefills"] == 0
    for admit, mk in [(12, 0), (12, 4), (5, 4)]:
        b1, b2, eng = run(admit, mk)
        assert (b1, b2) == (ref1, ref2), (admit, mk)
        assert eng.metrics["chunked_prefills"] >= 1, (admit, mk)
        if mk:
            assert eng.metrics["macro_fallbacks"] == 0, \
                "chunked admission must ride the macro path"


def test_steady_state_decode_zero_full_map_translations():
    """ISSUE-2 trace-count assertion: a steady-state decode step performs
    ZERO full-map retranslations and at most ONE fused map call (the
    batched page-growth `_xlate`; zero on non-boundary steps), and does
    not re-trace the translate pipeline."""
    from repro.core.fmmu import batch as B
    from repro.paging import kv_manager as KM

    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, n_slots=2, max_ctx=64)
    eng.submit(list(range(1, 9)), max_new=40)
    eng.submit(list(range(20, 28)), max_new=40)
    done: dict = {}
    eng.step(done)                      # admission + prefill + 1st step
    for _ in range(3):                  # settle: trace the decode shapes
        eng.step(done)
    boundary_seen = False
    for _ in range(10):
        f0, x0, p0 = (KM.FULL_TABLE_CALLS[0], KM.XLATE_CALLS[0],
                      B.PROBE_TRACES[0])
        pre = {r.slot: len(eng.kvm.seq_pages[r.slot])
               for r in eng.active.values()}
        eng.step(done)
        grew = any(len(eng.kvm.seq_pages.get(s, [])) != n
                   for s, n in pre.items())
        assert KM.FULL_TABLE_CALLS[0] - f0 == 0
        assert KM.XLATE_CALLS[0] - x0 == (1 if grew else 0)
        boundary_seen = boundary_seen or grew
        if not grew:                    # steady state: nothing re-traced
            assert B.PROBE_TRACES[0] - p0 == 0
    assert boundary_seen, "bench window never crossed a page boundary"
    assert eng.metrics["decode_steps"] >= 14


@pytest.mark.parametrize("macro_k", [1, 4])
def test_admission_costs_one_host_sync(macro_k):
    """Every blocking readback on the step path is one HOST_SYNCS bump:
    a step that admits one request pays its prefill's first-token
    readback plus the decode readback (macro scan or single step), and
    a steady step pays the decode readback alone. The engine's own
    ``host_syncs`` count moves with the registry cell."""
    from repro.serving import engine as E
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, config=ServeConfig(
        n_slots=2, max_ctx=64, macro_k=macro_k))
    done: dict = {}
    for n_admit in (1, 0, 1, 0):
        if n_admit:
            eng.submit(list(range(1, 9)), max_new=32)
        s0, e0, p0 = (E.HOST_SYNCS[0], eng.metrics["host_syncs"],
                      eng.metrics["prefills"])
        eng.step(done)
        assert eng.metrics["prefills"] - p0 == n_admit
        assert E.HOST_SYNCS[0] - s0 == n_admit + 1
        assert eng.metrics["host_syncs"] - e0 == n_admit + 1


@pytest.mark.parametrize("macro_k", [1, 4])
def test_paged_attention_page_counters(macro_k):
    """Each decode dispatch adds its resident lanes' pages at scan end
    to ``pa_live_pages`` and lanes x its page bucket to
    ``pa_bucket_pages``: the pages the paged-attention kernel copies
    against those a grid of one page a step over the bucket copied."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, config=ServeConfig(
        n_slots=3, max_ctx=128, macro_k=macro_k))
    buckets = []
    for name in ("_decode", "_macro", "_macro_simple"):
        fn = getattr(eng, name)
        if fn is not None:       # the page bucket is every call's last
            setattr(eng, name, lambda *a, fn=fn: (buckets.append(a[-1]),
                                                  fn(*a))[1])
    eng.submit(list(range(1, 20)), max_new=10 ** 6)
    eng.submit(list(range(30, 34)), max_new=10 ** 6)
    want_live = want_bucket = 0
    done: dict = {}
    for _ in range(8):
        n = len(buckets)
        eng.step(done)
        held = [len(eng.kvm.seq_pages[r.slot]) for r in eng.active.values()]
        assert len(buckets) == n + 1
        want_live += sum(held)
        want_bucket += len(held) * buckets[-1]
    assert eng.metrics["pa_live_pages"] == want_live
    assert eng.metrics["pa_bucket_pages"] == want_bucket
    assert 0 < want_live < want_bucket


def _serve_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of every ``serve.*`` host span in
    the profile written under ``trace_dir``, in start order."""
    import glob
    import os
    from jax.profiler import ProfileData
    out = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        out.append((ev.name, ev.start_ns, ev.end_ns,
                                    {k: str(v) for k, v in ev.stats}))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_step_span_tree(tmp_path):
    """Under the profiler (CPU here) each step() is one serve.step
    annotation; admission nests serve.admit > serve.prefill (carrying
    the request id and its token count) > serve.sync, and every macro
    step dispatches its scan before it reads the tokens back."""
    cfg = smoke_config(get_arch("llama3.2-1b"))
    m = build_model(cfg, RT)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, config=ServeConfig(
        n_slots=2, max_ctx=64, macro_k=4))
    done: dict = {}
    eng.submit(list(range(1, 9)), max_new=32)
    eng.step(done)                     # compile outside the trace
    rid = eng.submit(list(range(20, 31)), max_new=32)
    n_steps = 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(n_steps):
            eng.step(done)
    finally:
        jax.profiler.stop_trace()
    spans = _serve_spans(str(tmp_path))

    def inside(name, outer):
        return [s for s in spans if s[0] == name
                and outer[1] <= s[1] and s[2] <= outer[2]]

    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == n_steps
    assert [int(s[3]["step_num"]) for s in steps] == [1, 2, 3]
    (admit,) = [a for s in steps for a in inside("serve.admit", s)
                if inside("serve.prefill", a)]
    (prefill,) = inside("serve.prefill", admit)
    assert prefill[3]["rid"] == str(rid)
    assert prefill[3]["tokens"] == "11"
    assert len(inside("serve.sync", prefill)) == 1
    for s in steps:
        (dispatch,) = inside("serve.dispatch", s)
        syncs = [x for x in inside("serve.sync", s) if x[1] >= dispatch[2]]
        assert len(syncs) == 1
        assert inside("serve.book", s) and inside("serve.map", s)
