"""Decode-throughput benchmark for the serving map path.

Measures steady-state decode steps/sec of `ServeEngine` at
n_slots=16, max_pages=64 (the ISSUE-2 reference point) across three
interleaved groups: the six historical modes below, plus the ISSUE-5
``channel_scaling`` sweep (the fused macro engine with the FMMU map
sharded across N in {1,2,4,8} channels). The sweep's ``cpu_bound``
flag records the lowering that actually ran (``kvm.mesh is None``) —
today always true, since the serving engine pins the vmap lowering
until model/map mesh co-residency lands (ROADMAP) — and in that
regime the per-channel routed-lane counters carry the
1/N-translate-work claim instead of wall clock. Core modes:

  * ``fused_macro``  — the live path: K-step fused decode macro-steps
    (K=8, ONE donated jit runs attention + sampling + page-boundary
    detection + device-side block allocation + map commit for K
    tokens, one host dispatch and one device->host sync per K steps)
    plus this PR's graph optimizations (live-page bucketing,
    single-chunk paged attention);
  * ``single_step``  — the live single-step path (same graph
    optimizations, no macro fusion): isolates the macro-step
    contribution;
  * ``incremental``  — the PR-2 incremental baseline restored
    faithfully (single-step, full-width tables, 8-page attention
    chunks): the ISSUE-3 acceptance reference;
  * ``rebuild_legacy`` — pre-PR-2: rebuilds the full table by
    re-translating every DLPN each step and masks it on host;
  * ``oversub_fused`` / ``oversub_fallback`` — the ISSUE-4 pair: the
    same engine under ~2x OVERSUBSCRIPTION (16 requests whose working
    set is about twice the device pool; a host tier holds the
    overflow), measured as CONTINUOUS-BATCHING COMPLETION rounds: each
    interleaved round submits a fresh batch of 16 finite requests and
    times delivered tokens/sec from the first decode step until every
    request completes. Completion rounds make fairness part of the
    metric — a scheduler cannot win by starving the swapped-out
    sequences, the failure mode an open-ended steps/sec window hides.
    ``oversub_fused`` is the non-blocking swap pipeline: swap-pending
    slots are masked scan lanes, the boundary scheduler rotates
    residency, and the measured rounds perform ZERO single-step
    fallbacks (counter-asserted). ``oversub_fallback`` is the PR-3
    behavior restored faithfully: ``nonblocking_swap=False`` (any
    non-resident slot drops every round to single-step mode) AND the
    PR-3 swap data movement (eager un-donated jnp row moves that
    functionally copy the pools, a separate fused map call, and a
    blocking guard readback per swap — ``_patch_pr3_swap``).
    Acceptance: oversub_fused >= 1.3x oversub_fallback tokens/sec.

All modes run in-process because this box's 2-core timings are too
noisy to compare across runs; per-window dispersion (median/min/IQR
over ``--repeats`` consecutive windows) is recorded so the noise is
visible in the artifact. In ``--quick`` (CI smoke) mode, speedup
shortfalls against the targets and regressions against the committed
BENCH_serve.json are REPORTED as warnings, not failures — the runner
is too noisy for a hard gate.

Emits CSV rows (shared benchmark format) and writes ``BENCH_serve.json``
(repo root or $REPRO_BENCH_OUT) so CI can archive the perf trajectory.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import SCALE, emit

N_SLOTS = 16
MAX_PAGES = 64
MACRO_K = 8
WARM_STEPS = 3
# oversubscription workload (ISSUE 4): 80-token prompts = 10 pages per
# sequence at admission, 16 sequences = 160 pages of working set vs a
# 76-block device pool (~2.1x, deepening to ~3x as contexts grow to
# prompt+max_new); the host tier absorbs the overflow
OVERSUB_PROMPT = 80
OVERSUB_MAX_NEW = 48
OVERSUB_DEV = 76
OVERSUB_HOST = 640
# channel-scaling sweep (ISSUE 5): the fused macro engine with the map
# state sharded across N channels; measured with the same interleaved
# windows as the main decode group, in its own group (its engines are
# only comparable to each other)
CHANNEL_SWEEP = (1, 2, 4, 8)
# fault-injection degraded mode (ISSUE 6): the oversubscribed fused
# engine, channel-sharded, with ONE channel browned out 4x and a 1%
# injected swap-failure rate — measured as the same completion rounds
# as the oversub pair against an identical healthy engine. The
# deterministic plan regenerates from the seed (core/faults.make_plan)
FAULT_CHANNELS = 4
FAULT_STALL = (4.0, 1.0, 1.0, 1.0)
FAULT_SWAP_P = 0.01
FAULT_SEED = 2026
# crash/recovery measurement (ISSUE 7): a journaled channel-sharded
# oversubscribed engine killed at a deterministic commit point, then
# recovered from the journal directory. MTTR = power cut -> first
# RESUMED token (replay + map restore + re-admission + prefill), swept
# over the snapshot interval: tighter snapshots replay fewer records
# at recovery but pay more snapshot writes while healthy — the
# committed sweep records both sides of that tradeoff.
RECOVERY_CHANNELS = 2
RECOVERY_SEED = 2027
RECOVERY_CRASH_AT = 80
RECOVERY_SNAPSHOT_SWEEP = (1, 4, 16)
# GC victim-eviction walk (ISSUE 9): the oversubscribed fused engine
# with the boundary GC walk + CTP prefetch on vs off, measured as the
# same completion rounds as the oversub pair. The gc section records
# the write-amplification axis (host writes vs flash programs) and
# the reclaim counters; acceptance is gc_on retaining >= 0.9x gc_off
# delivered tokens/sec while actually reclaiming victims.
GC_WATERMARK = 6
GC_BUDGET = 8
GC_BLOCK_PAGES = 4
# prefix sharing (ISSUE 10): B requests with a common 80-token prompt
# prefix (10 full pages) + a short unique tail. The shared engine must
# admit the followers on the leader's physical pages (ONE prefill for
# the whole batch), COW each diverging tail, and emit tokens
# bit-identical to the sharing-off control. Acceptance: prefill-FLOP
# proxy (prompt tokens through prefill + forced lanes) and distinct
# device pages after admission both <= 1/4 of the unshared baseline
PREFIX_B = 8
PREFIX_COMMON = 80
PREFIX_TAIL = 4
PREFIX_MAX_NEW = 4
PREFIX_RATIO_TARGET = 0.25
# in-run speedup targets (ISSUE 3: fused >= 1.5x incremental;
# ISSUE 4: non-blocking swap >= 1.3x the fall-back-on-pressure PR-3
# behavior under 2x oversubscription; ISSUE 6: the degraded engine
# retains >= 60% of the healthy fused engine's delivered tokens/sec;
# ISSUE 9: the GC-enabled engine retains >= 90% of the GC-off
# engine's delivered tokens/sec under the same oversubscription)
TARGETS = {"fused_macro_vs_incremental": 1.5,
           "incremental_vs_rebuild": 1.5,
           "oversub_fused_vs_fallback": 1.3,
           "degraded_retention": 0.6,
           "gc_retention": 0.9}


def _build_engine(mode: str):
    import dataclasses

    import jax

    from repro.configs import get_arch, smoke_config
    from repro.models import Runtime, build_model
    from repro.serving.config import GCConfig, PrefixConfig, ServeConfig
    from repro.serving.engine import ServeEngine

    # the PR-2-faithful baselines pin the pre-ISSUE-3 decode graph:
    # 8-page attention chunks (no auto-widening) and full-width tables
    pr2 = mode in ("incremental", "rebuild_legacy")
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                 remat="none", page_size=8, capacity_factor=100.0,
                 paged_chunk=8 if pr2 else None)
    # minimal model: this benchmark isolates the serving *map* path
    # (the paper's FTL-exec-time claim), so model compute is kept as
    # close to zero as the engine allows — with the full smoke config
    # the transformer forward drowns the map delta on this 2-core box
    cfg = smoke_config(get_arch("llama3.2-1b"))
    cfg = dataclasses.replace(cfg, name="serve-bench-tiny",
                              n_layers=cfg.period, d_model=32, n_heads=2,
                              n_kv_heads=1, head_dim=16, d_ff=64,
                              vocab_size=128)
    m = build_model(cfg, rt)
    params = m.init(jax.random.key(0))
    max_ctx = MAX_PAGES * rt.page_size
    if mode in ("oversub_fused", "oversub_fallback"):
        # identical engine + workload; the differences are exactly the
        # ISSUE-4 tentpole: masked swap-pending scan lanes + boundary
        # scheduler vs per-round single-step fallback, and the fused
        # donated swap jit vs PR-3's eager copy-per-swap data movement
        eng = ServeEngine(m, params, config=ServeConfig(
            n_slots=N_SLOTS, max_ctx=max_ctx,
            n_device_blocks=OVERSUB_DEV, n_host_blocks=OVERSUB_HOST,
            macro_k=MACRO_K, swap_patience=4,
            nonblocking_swap=(mode == "oversub_fused")))
        if mode == "oversub_fused":
            # pin the swap-lane pad so the fused swap fn compiles ONCE
            # per direction (during warm-up) instead of re-tracing at
            # every pow2 cap crossing as sequences grow — a mid-round
            # XLA compile would poison that round's sample
            eng.kvm.swap_pad = MAX_PAGES
        else:
            _patch_pr3_swap(eng)
        return eng
    if mode.startswith("channels_"):
        # ISSUE-5 sweep: the fused macro engine with the map sharded
        # across N channels (N=1 is the unsharded tentpole baseline,
        # rebuilt per mode so the windows interleave fairly)
        return ServeEngine(m, params, config=ServeConfig(
            n_slots=N_SLOTS, max_ctx=max_ctx, macro_k=MACRO_K,
            channels=int(mode.rsplit("_", 1)[1])))
    if mode.startswith("faults_"):
        # ISSUE-6 pair: identical channel-sharded oversubscribed fused
        # engines; the degraded one carries the fault plane (brownout
        # on channel 0 + injected swap failures) — the delta measured
        # is exactly the cost of degradation plus recovery
        from repro.core.faults import FaultPlane, make_plan
        plane = None
        if mode == "faults_degraded":
            plane = FaultPlane(make_plan(
                FAULT_SEED, channels=FAULT_CHANNELS,
                swap_fail_p=FAULT_SWAP_P, stall=list(FAULT_STALL)))
        eng = ServeEngine(m, params, config=ServeConfig(
            n_slots=N_SLOTS, max_ctx=max_ctx,
            n_device_blocks=OVERSUB_DEV, n_host_blocks=OVERSUB_HOST,
            macro_k=MACRO_K, swap_patience=4,
            channels=FAULT_CHANNELS), fault_plane=plane)
        eng.kvm.swap_pad = MAX_PAGES
        return eng
    if mode in ("gc_off", "gc_on"):
        # ISSUE-9 pair: identical oversubscribed fused engines; the
        # gc_on one adds the boundary victim walk + CTP prefetch. The
        # delta measured is exactly the GC tax (relocations ride the
        # same fused CondUpdate path decode uses), and the reclaim /
        # write-amp counters prove the walk did real work
        gc = GCConfig(watermark=GC_WATERMARK,
                      pages_per_boundary=GC_BUDGET,
                      block_pages=GC_BLOCK_PAGES,
                      prefetch=True) if mode == "gc_on" else None
        eng = ServeEngine(m, params, config=ServeConfig(
            n_slots=N_SLOTS, max_ctx=max_ctx,
            n_device_blocks=OVERSUB_DEV, n_host_blocks=OVERSUB_HOST,
            macro_k=MACRO_K, swap_patience=4, gc=gc))
        eng.kvm.swap_pad = MAX_PAGES
        return eng
    if mode in ("prefix_on", "prefix_off"):
        # ISSUE-10 pair: identical single-step engines; the on one arms
        # the radix prefix cache + refcnt lane. No oversubscription —
        # the section measures the prompt-work and footprint deltas,
        # and swaps would blur the page accounting. Single-step (not
        # macro) so the per-step peak-footprint probe actually observes
        # the mapped working set (a K=8 macro drains the whole short
        # workload inside one step call); the macro path's sharing
        # bit-identity is pinned by tests/test_prefix.py instead
        eng = ServeEngine(m, params, config=ServeConfig(
            n_slots=PREFIX_B, max_ctx=max_ctx, macro_k=0,
            prefix=(PrefixConfig(min_tokens=16)
                    if mode == "prefix_on" else None)))
        return eng
    if mode == "recovery":
        # ISSUE-7: the journaled engine for the crash/recover sweep —
        # oversubscribed + channel-sharded so the journal carries every
        # record kind (swaps included); the caller attaches the journal
        # and the crash plan per sweep point
        eng = ServeEngine(m, params, config=ServeConfig(
            n_slots=N_SLOTS, max_ctx=max_ctx,
            n_device_blocks=OVERSUB_DEV, n_host_blocks=OVERSUB_HOST,
            macro_k=MACRO_K, swap_patience=4,
            channels=RECOVERY_CHANNELS))
        eng.kvm.swap_pad = MAX_PAGES
        return eng
    eng = ServeEngine(m, params, config=ServeConfig(
        n_slots=N_SLOTS, max_ctx=max_ctx,
        macro_k=MACRO_K if mode == "fused_macro" else 0))
    if pr2:
        eng.min_page_bucket = MAX_PAGES    # PR 2 had no page bucketing
    if mode == "rebuild_legacy":
        _patch_legacy(eng)
    return eng


def _patch_legacy(eng):
    """Pre-PR serving map behaviour, restored for an in-run baseline:

    * admission preallocates prompt+max_new pages up front (so decode
      never grows the map — the old engine's steady state);
    * every decode step rebuilds the full [n_slots, max_pages] table by
      re-translating every DLPN through the FMMU (`retranslate_tables`,
      the churn-test oracle) and masks paused/invalid rows on host via
      numpy before shipping the table back to device;
    * the decode jit takes the host-masked table directly and does NOT
      donate the KV caches (the pre-PR jit functionally copied the
      whole pool every step)."""
    import types

    import jax

    from repro.paging.pool import OutOfBlocks

    def _legacy_decode_fn(self, params, tokens, caches, ctx_lens, tables,
                          src_valid=None):
        logits, caches = self.m.decode_step(
            params, tokens, caches, ctx_lens=ctx_lens, block_table=tables,
            src_valid=src_valid)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    def _legacy_admit(self):
        free = self._free_slots()
        while self.queue and free:
            req = self.queue[0]
            slot = free[0]
            n_pages = -(-(len(req.tokens) + req.max_new) // self.page)
            n_pages = min(n_pages, self.max_pages)
            try:
                self.kvm.new_seq(slot, n_pages)
            except OutOfBlocks:
                if not self._preempt(exclude=slot):
                    return
                continue
            self.queue.popleft()
            free.pop(0)
            req.slot = slot
            self.active[req.rid] = req
            self._do_prefill(req)

    def _legacy_decode_step(self, done):
        self._ensure_resident()
        residents = [r for r in self.active.values()
                     if self.kvm.is_resident(r.slot)]
        if not residents:
            return
        resident_slots = {r.slot for r in residents}
        tokens = np.zeros(self.n_slots, np.int32)
        for r in residents:
            tokens[r.slot] = r.out[-1] if r.out else r.tokens[-1]
        tables = np.array(self.kvm.retranslate_tables())
        step_ctx = np.asarray(self.ctx_lens, np.int64).copy()
        for slot in range(self.n_slots):
            if slot not in resident_slots:
                tables[slot, :] = self.scratch_block
                step_ctx[slot] = 0
        tables = np.where((tables < 0) | (tables >= self.scratch_block),
                          self.scratch_block, tables)
        next_tok, self.caches = self._legacy_decode(
            self.params, jnp.asarray(tokens), self.caches,
            jnp.asarray(step_ctx, jnp.int32),
            jnp.asarray(tables, jnp.int32), None)
        self._finish_step(residents, np.asarray(next_tok), done)

    eng._admit = types.MethodType(_legacy_admit, eng)
    eng._decode_step = types.MethodType(_legacy_decode_step, eng)
    eng._legacy_decode = jax.jit(types.MethodType(_legacy_decode_fn, eng))


def _patch_pr3_swap(eng):
    """Restore the PR-3 swap data movement faithfully (the baseline
    the ISSUE-4 acceptance compares against): eager, un-jitted,
    un-donated jnp row moves — every swap functionally copies the
    whole KV pools — with a separate fused map call and a BLOCKING
    guard-mask readback per swap. Verbatim from the pre-ISSUE-4
    KVPageManager (git 39799ba)."""
    import types

    import numpy as np

    from repro.core.fmmu.types import COND_UPDATE, HOST_BASE
    from repro.paging.kv_manager import _move_rows
    from repro.paging.pool import BlockPool

    def swap_out(self, slot, pools, block_axis=0, check=True):
        blocks = self.seq_pages[slot]
        dev = [b for b in blocks if not BlockPool.is_host(b)]
        if not dev:
            return pools, 0
        host = self.pool.alloc(len(dev), host=True)
        self._alloc_dirty = True
        dl = [slot * self.max_pages + i for i, b in enumerate(blocks)
              if not BlockPool.is_host(b)]
        _, ok = self._xlate(COND_UPDATE, dl, host, dev)
        assert np.asarray(ok).all(), "swap_out raced"
        src = jnp.asarray(dev, jnp.int32)
        dst = jnp.asarray([self.pool.n_device + (h - HOST_BASE)
                           for h in host], jnp.int32)
        pools = [_move_rows(p, src, dst, block_axis) for p in pools]
        self.pool.free(dev)
        self.seq_pages[slot] = [
            host[dev.index(b)] if b in dev else b for b in blocks]
        self._host_pages[slot] = sum(
            BlockPool.is_host(b) for b in self.seq_pages[slot])
        self.pool.stats.swaps_out += len(dev)
        return pools, len(dev)

    def swap_in(self, slot, pools, block_axis=0, check=True):
        blocks = self.seq_pages[slot]
        hostb = [b for b in blocks if BlockPool.is_host(b)]
        if not hostb:
            return pools, 0
        dev = self.pool.alloc(len(hostb))
        self._alloc_dirty = True
        dl = [slot * self.max_pages + i for i, b in enumerate(blocks)
              if BlockPool.is_host(b)]
        _, ok = self._xlate(COND_UPDATE, dl, dev, hostb)
        assert np.asarray(ok).all()
        src = jnp.asarray([self.pool.n_device + (h - HOST_BASE)
                           for h in hostb], jnp.int32)
        dst = jnp.asarray(dev, jnp.int32)
        pools = [_move_rows(p, src, dst, block_axis) for p in pools]
        self.pool.free(hostb)
        self.seq_pages[slot] = [
            dev[hostb.index(b)] if b in hostb else b for b in blocks]
        self._host_pages[slot] = sum(
            BlockPool.is_host(b) for b in self.seq_pages[slot])
        self.pool.stats.swaps_in += len(hostb)
        return pools, len(hostb)

    eng.kvm.swap_out = types.MethodType(swap_out, eng.kvm)
    eng.kvm.swap_in = types.MethodType(swap_in, eng.kvm)


def _run_decode(modes, n_steps: int, repeats: int, prompt_len: int = 8):
    """One serving run per mode, windows INTERLEAVED across modes: for
    each of `repeats` rounds, every mode times one window of n_steps
    decode steps (counted via engine metrics, so a fused macro-step
    contributes K). Interleaving matters on this 2-core virtualized
    box: CPU steal drifts on multi-second scales, so consecutive
    same-mode windows correlate and back-to-back mode blocks skew the
    ratio; round-robin windows see the same noise. Context grows
    slowly across windows (8 tokens/page) but every mode walks the
    identical schedule, so windows stay comparable. Returns
    ({mode: [steps/sec per window]},
     {mode: [generated tokens/sec per window]}, {mode: engine})."""
    engines, dones, fb0 = {}, {}, {}
    # decode jits are specialized on the live-page bucket; pin the
    # bucket that covers the whole timed range so no window eats a
    # mid-run re-trace (a bucket crossing costs seconds of XLA compile,
    # which would make that window's sample garbage)
    end_ctx = prompt_len + 1 + (1 + WARM_STEPS) * MACRO_K \
        + repeats * n_steps + MACRO_K
    bucket = 4
    while bucket * 8 < end_ctx + 8:
        bucket *= 2
    for mode in modes:
        eng = _build_engine(mode)
        eng.min_page_bucket = max(eng.min_page_bucket,
                                  min(bucket, MAX_PAGES))
        for i in range(N_SLOTS):
            eng.submit(list(range(1 + i, 1 + i + prompt_len)),
                       max_new=10 ** 9)
        done = {}
        eng.step(done)                   # admits + prefills + first step
        for _ in range(WARM_STEPS):
            eng.step(done)
        engines[mode], dones[mode] = eng, done
        # the zero-fallback claim is STEADY-STATE: admission under an
        # oversubscribed pool may legitimately fall back while slots
        # are first preempted to fit; snapshot after warm-up
        fb0[mode] = eng.metrics["macro_fallbacks"]
    sps = {mode: [] for mode in modes}
    tps = {mode: [] for mode in modes}
    for rep in range(repeats):
        # rotate the order each round: the mode that follows the heavy
        # legacy window inherits its cache damage, so a fixed order
        # biases one mode systematically
        order = modes[rep % len(modes):] + modes[:rep % len(modes)]
        for mode in order:
            eng, done = engines[mode], dones[mode]
            s0 = eng.metrics["decode_steps"]
            g0 = eng.metrics["generated"]
            t0 = time.perf_counter()
            while eng.metrics["decode_steps"] - s0 < n_steps:
                eng.step(done)
            dt = time.perf_counter() - t0
            sps[mode].append((eng.metrics["decode_steps"] - s0) / dt)
            tps[mode].append((eng.metrics["generated"] - g0) / dt)
    for mode, eng in engines.items():
        assert len(eng.active) == N_SLOTS, "sequences finished mid-bench"
        assert int(max(eng.ctx_lens)) < MAX_PAGES * eng.page, "ctx overflow"
        if mode == "fused_macro" or mode.startswith("channels_"):
            assert eng.metrics["macro_steps"] > 0, "fused mode never fused"
            assert eng.metrics["macro_fallbacks"] == fb0[mode], \
                f"{mode}: single-step fallback during steady state"
    return sps, tps, engines


def _run_oversub(modes, repeats: int):
    """ISSUE-4 measurement: interleaved CONTINUOUS-BATCHING COMPLETION
    rounds under ~2x oversubscription. Each round submits a fresh
    batch of N_SLOTS finite requests, performs one engine step
    (admissions + prefills + first decode — identical work in both
    modes, excluded from the window), then times delivered tokens/sec
    until every request completes. Completion rounds bake fairness
    into the metric: the swapped-out sequences must finish, so a
    scheduler cannot look fast by starving them. Round 0 per mode is
    an unmeasured warm-up (XLA compiles for the scan variants and the
    pinned swap jit). Returns ({mode: [steps/s]}, {mode: [tokens/s]},
    {mode: engine})."""
    engines, fb_warm = {}, {}

    def one_round(eng):
        for i in range(N_SLOTS):
            eng.submit(list(range(1 + i, 1 + i + OVERSUB_PROMPT)),
                       max_new=OVERSUB_MAX_NEW)
        done: dict = {}
        eng.step(done)          # admissions + prefills + first step
        s0, g0 = eng.metrics["decode_steps"], eng.metrics["generated"]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        assert not eng.active and not eng.queue, "round did not drain"
        return ((eng.metrics["decode_steps"] - s0) / dt,
                (eng.metrics["generated"] - g0) / dt)

    for mode in modes:
        eng = _build_engine(mode)
        # pin the live-page bucket covering prompt+max_new so no round
        # eats a bucket-crossing re-trace
        need = -(-(OVERSUB_PROMPT + OVERSUB_MAX_NEW) // 8)
        eng.min_page_bucket = 1 << (need - 1).bit_length()
        one_round(eng)                       # warm-up, unmeasured
        engines[mode] = eng
        # the zero-fallback claim is for the measured rounds; warm-up
        # includes first-ever admissions while jits are still cold
        fb_warm[mode] = eng.metrics["macro_fallbacks"]
    sps = {mode: [] for mode in modes}
    tps = {mode: [] for mode in modes}
    for rep in range(repeats):
        order = list(modes)[rep % len(modes):] \
            + list(modes)[:rep % len(modes)]
        for mode in order:
            s, t = one_round(engines[mode])
            sps[mode].append(s)
            tps[mode].append(t)
    for mode, eng in engines.items():
        assert eng.metrics["swaps_out"] > 0, \
            f"{mode}: never swapped — pool not oversubscribed"
        if mode == "oversub_fused":
            assert eng.metrics["macro_steps"] > 0
            assert eng.metrics["macro_fallbacks"] == fb_warm[mode], \
                "fused mode fell back to single-step during a " \
                "measured round"
        else:
            assert eng.metrics["macro_fallbacks"] > fb_warm[mode], \
                "fallback baseline stayed fused: no pressure applied"
    return sps, tps, engines


def _run_faults(repeats: int):
    """ISSUE-6 measurement: graceful degradation under an adverse
    fault schedule. Two identical channel-sharded oversubscribed fused
    engines run interleaved completion rounds (same protocol as
    ``_run_oversub``); the degraded one carries a deterministic fault
    plane — one channel browned out 4x (its advertised free-block
    budget shrinks, pushing residency/growth to healthy channels) and
    a 1% injected swap-failure rate (retried with backoff; persistent
    failers quarantine and restart). Throughput is DELIVERED
    tokens/sec — tokens in completed outputs, not raw generation — so
    a quarantined request's regenerated prefix cannot pad the degraded
    number. Acceptance: the degraded engine retains >= 60% of healthy
    throughput (TARGETS['degraded_retention'])."""
    modes = ("faults_healthy", "faults_degraded")
    engines = {}

    def one_round(eng):
        for i in range(N_SLOTS):
            eng.submit(list(range(1 + i, 1 + i + OVERSUB_PROMPT)),
                       max_new=OVERSUB_MAX_NEW)
        done: dict = {}
        eng.step(done)          # admissions + prefills + first step
        t0 = time.perf_counter()
        done.update(eng.run())
        dt = time.perf_counter() - t0
        assert not eng.active and not eng.queue, "round did not drain"
        # the handful of pre-window tokens (prefill + first step) is
        # identical across modes, so the retention ratio is unbiased
        return sum(len(v) for v in done.values()) / dt

    for mode in modes:
        eng = _build_engine(mode)
        need = -(-(OVERSUB_PROMPT + OVERSUB_MAX_NEW) // 8)
        eng.min_page_bucket = 1 << (need - 1).bit_length()
        one_round(eng)                       # warm-up, unmeasured
        engines[mode] = eng
    tps = {mode: [] for mode in modes}
    for rep in range(repeats):
        order = list(modes)[rep % len(modes):] \
            + list(modes)[:rep % len(modes)]
        for mode in order:
            tps[mode].append(one_round(engines[mode]))
    deg = engines["faults_degraded"]
    assert deg.metrics["swap_faults"] > 0, \
        "degraded mode never fired an injected swap failure"
    assert engines["faults_healthy"].metrics["swap_faults"] == 0
    return tps, engines


def _run_gc(repeats: int):
    """ISSUE-9 measurement: the write-amplification axis of the GC
    victim-eviction walk. Two identical oversubscribed fused engines
    run interleaved completion rounds (same protocol as
    ``_run_oversub``); the gc_on one adds the budgeted boundary walk
    (watermark-triggered victim selection from the fused-path live
    counts, relocations through the same single-probe CondUpdate
    commit decode uses) plus the CTP map-segment prefetch. Delivered
    tokens/sec gives the retention headline; the hit_stats
    write-amplification fields (host_writes vs flash_programs) and
    the reclaim counters prove the walk did real work. Acceptance:
    gc_on retains >= 90% of gc_off throughput
    (TARGETS['gc_retention']) while gc_moves/victims stay non-zero,
    and the gc_off control never relocates a page."""
    modes = ("gc_off", "gc_on")
    engines = {}

    def one_round(eng):
        for i in range(N_SLOTS):
            eng.submit(list(range(1 + i, 1 + i + OVERSUB_PROMPT)),
                       max_new=OVERSUB_MAX_NEW)
        done: dict = {}
        eng.step(done)          # admissions + prefills + first step
        g0 = eng.metrics["generated"]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        assert not eng.active and not eng.queue, "round did not drain"
        return (eng.metrics["generated"] - g0) / dt

    for mode in modes:
        eng = _build_engine(mode)
        need = -(-(OVERSUB_PROMPT + OVERSUB_MAX_NEW) // 8)
        eng.min_page_bucket = 1 << (need - 1).bit_length()
        one_round(eng)                       # warm-up, unmeasured
        engines[mode] = eng
    tps = {mode: [] for mode in modes}
    for rep in range(repeats):
        order = list(modes)[rep % len(modes):] \
            + list(modes)[:rep % len(modes)]
        for mode in order:
            tps[mode].append(one_round(engines[mode]))
    on, off = engines["gc_on"], engines["gc_off"]
    assert on.metrics["gc_moves"] > 0, \
        "gc_on never relocated a live page (walk found no work)"
    assert on.metrics["gc_victims"] > 0, \
        "gc_on never reclaimed a victim block"
    assert off.metrics["gc_moves"] == 0, \
        "gc_off control relocated pages (GC not actually disabled)"
    return tps, engines


def _run_prefix():
    """ISSUE-10 measurement: copy-on-write prefix sharing.

    Three runs of the same B-request batch (80 common prompt tokens +
    a unique 4-token tail each):

      * ``prefix_off`` — the control: every request prefills its whole
        prompt and owns every page;
      * ``prefix_on``  — the leader prefills once, followers admit on
        the leader's physical pages and stream only their tails;
      * forced divergence — ``prefix_on`` again with IDENTICAL
        80-token prompts, so every follower's first forced write lands
        INSIDE a shared page and must relocate copy-on-write.

    The prefill-FLOP proxy is prompt tokens through the prefill path
    plus forced pending-prompt lanes (engine ``prefill_tokens``);
    device pages are the distinct blocks mapped after the admission
    step. Acceptance: both ratios <= PREFIX_RATIO_TARGET and outputs
    bit-identical to the control; the off engine must stay inert
    (no refcnt lane, zero shared admissions)."""
    common = list(range(1, 1 + PREFIX_COMMON))
    tailed = [common + [100 + i] * PREFIX_TAIL for i in range(PREFIX_B)]
    flat = [list(common) for _ in range(PREFIX_B)]

    def one(mode, prompts):
        eng = _build_engine(mode)
        done: dict = {}
        rids = [eng.submit(list(t), max_new=PREFIX_MAX_NEW)
                for t in prompts]
        pages, t0 = 0, time.perf_counter()
        alive = True
        while alive:           # step-at-a-time so the PEAK distinct
            alive = eng.step(done)     # mapped-block footprint is seen
            pages = max(pages, len({b for ps in
                                    eng.kvm.seq_pages.values()
                                    for b in ps}))
        dt = time.perf_counter() - t0
        assert not eng.active and not eng.queue, \
            "prefix bench: round did not drain"
        return eng, [done[r] for r in rids], pages, dt

    off, out_off, pages_off, _ = one("prefix_off", tailed)
    assert off.kvm.state.refcnt is None, \
        "prefix_off control armed the refcnt lane"
    assert off.metrics["shared_admits"] == 0 \
        and off.metrics["cow_moves"] == 0, \
        "prefix_off control shared pages (sharing not actually off)"
    on, out_on, pages_on, _ = one("prefix_on", tailed)
    assert out_on == out_off, \
        "prefix sharing changed emitted tokens (must be bit-identical)"
    assert on.metrics["shared_admits"] == PREFIX_B - 1, \
        f"expected {PREFIX_B - 1} shared admissions, " \
        f"got {on.metrics['shared_admits']}"
    assert on.metrics["cow_moves"] > 0, \
        "prefix_on run never diverged copy-on-write"
    flop_ratio = on.metrics["prefill_tokens"] \
        / max(1, off.metrics["prefill_tokens"])
    page_ratio = pages_on / max(1, pages_off)
    assert flop_ratio <= PREFIX_RATIO_TARGET, \
        f"prefill-FLOP ratio {flop_ratio:.3f} above " \
        f"{PREFIX_RATIO_TARGET} target"
    assert page_ratio <= PREFIX_RATIO_TARGET, \
        f"device-page ratio {page_ratio:.3f} above " \
        f"{PREFIX_RATIO_TARGET} target"
    # forced divergence: identical prompts share ALL pages (the skip
    # caps at len-1), so the one forced token per follower writes into
    # a shared page and must COW first — control run with the same
    # prompts proves relocation never changes tokens
    offd, out_offd, _, _ = one("prefix_off", flat)
    ond, out_ond, _, _ = one("prefix_on", flat)
    assert out_ond == out_offd, \
        "forced-divergence outputs differ from the unshared control"
    assert ond.metrics["cow_moves"] >= PREFIX_B - 1, \
        "forced divergence produced no COW relocations"
    return {
        "batch": PREFIX_B,
        "common_tokens": PREFIX_COMMON,
        "tail_tokens": PREFIX_TAIL,
        "max_new": PREFIX_MAX_NEW,
        "prefill_tokens": {"prefix_off": off.metrics["prefill_tokens"],
                           "prefix_on": on.metrics["prefill_tokens"]},
        "prefill_flop_ratio": round(flop_ratio, 4),
        "device_pages": {"prefix_off": pages_off,
                         "prefix_on": pages_on},
        "device_page_ratio": round(page_ratio, 4),
        "shared_admits": on.metrics["shared_admits"],
        "shared_pages": on.metrics["shared_pages"],
        "cow_moves": on.metrics["cow_moves"],
        "outputs_bit_identical": out_on == out_off,
        "off_inert": True,
        "forced_divergence": {
            "cow_moves": ond.metrics["cow_moves"],
            "outputs_bit_identical": out_ond == out_offd,
        },
    }


def _run_recovery():
    """ISSUE-7 measurement: bounded MTTR after a sudden power-off.

    One journaled engine, reused across the snapshot-interval sweep
    (reset keeps the compiled jits, so recovery timings measure the
    SPOR path, not XLA compiles — a warm-up crash/recover cycle runs
    first for the same reason). Per sweep point: run the
    oversubscribed workload under a deterministic plan that kills the
    process at the same commit point, recover, and time

      * ``recover_s``  — replay + map restore + journal re-arm,
      * ``mttr_s``     — power cut to the first RESUMED token
                         (recover_s + re-admission + prefill).

    Replayed-record counts expose the snapshot tradeoff: a tighter
    interval replays fewer records at the same crash point."""
    import tempfile

    from repro.core import faults as flt
    from repro.core.faults import FaultPlane, make_plan

    eng = _build_engine("recovery")
    need = -(-(OVERSUB_PROMPT + OVERSUB_MAX_NEW) // 8)
    eng.min_page_bucket = 1 << (need - 1).bit_length()

    def crash_recover(snap_every):
        with tempfile.TemporaryDirectory() as d:
            plan = make_plan(RECOVERY_SEED, channels=RECOVERY_CHANNELS,
                             crash_at=RECOVERY_CRASH_AT)
            eng.reset(FaultPlane(plan))
            eng.attach_journal(d, snapshot_every=snap_every)
            t_crash = None
            try:
                for i in range(N_SLOTS):
                    eng.submit(list(range(1 + i,
                                          1 + i + OVERSUB_PROMPT)),
                               max_new=OVERSUB_MAX_NEW)
                eng.run()
            except flt.Crash:
                t_crash = time.perf_counter()
            assert t_crash is not None, \
                "recovery bench: scheduled power cut never fired"
            durable = eng.recover(d, fault_plane=None)
            info = dict(eng.last_recovery)
            # first resumed token: admission + prefill + one decode
            g0 = eng.metrics["generated"]
            done: dict = {}
            while eng.step(done) and eng.metrics["generated"] == g0:
                pass
            assert eng.metrics["generated"] > g0, \
                "recovery bench: no token after recovery"
            info["mttr_s"] = time.perf_counter() - t_crash
            done.update(eng.run())
            assert not eng.active and not eng.queue, \
                "recovery bench: recovered run did not drain"
            assert len(set(durable) | set(done)) == N_SLOTS, \
                "recovery bench: lost requests across the crash"
            assert eng.journal_lane_check(), \
                "recovery bench: journal/device lane divergence"
            eng.reset(None)       # close the journal before the dir goes
            return info

    crash_recover(RECOVERY_SNAPSHOT_SWEEP[0])     # warm-up, unmeasured
    sweep = {}
    for snap_every in RECOVERY_SNAPSHOT_SWEEP:
        info = crash_recover(snap_every)
        sweep[f"snap{snap_every}"] = {
            "snapshot_every": snap_every,
            "mttr_s": round(info["mttr_s"], 4),
            "recover_s": round(info["recover_s"], 4),
            "replayed_records": int(info["replayed"]),
            "snapshot_seq": int(info["snap_seq"]),
            "last_seq": int(info["last_seq"]),
            "torn": bool(info["torn"]),
            "oob_scan": bool(info["oob_scan"]),
            "requeued": int(info["requeued"]),
        }
    return sweep


def _dispersion(sps):
    qs = statistics.quantiles(sps, n=4) if len(sps) >= 2 else [sps[0]] * 3
    return {"median": round(statistics.median(sps), 2),
            "min": round(min(sps), 2),
            "iqr": round(qs[2] - qs[0], 2),
            "windows": [round(s, 2) for s in sps]}


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    repeats = 8        # multiple of the mode count: every mode sees
    if "--repeats" in sys.argv:   # every rotation position equally
        repeats = int(sys.argv[sys.argv.index("--repeats") + 1])
    quick = "--quick" in sys.argv
    n_steps = max(MACRO_K, int(24 * SCALE) // MACRO_K * MACRO_K)
    results, windows = {}, {}
    all_sps, _, _ = _run_decode(("fused_macro", "single_step",
                                 "incremental", "rebuild_legacy"),
                                n_steps, repeats)
    # ISSUE-4 group: same engine pair under ~2x oversubscription,
    # measured as continuous-batching COMPLETION rounds (its own
    # interleaved rounds — the workload differs, so its windows are
    # only comparable to each other). The acceptance ratio is computed
    # from delivered TOKENS/sec: scheduling rounds decode only
    # resident lanes, so steps/sec is not apples-to-apples here.
    over_sps, over_tps, over_eng = _run_oversub(
        ("oversub_fused", "oversub_fallback"), repeats)
    all_sps.update(over_sps)
    # ISSUE-6 group: graceful degradation under faults (its own
    # interleaved completion rounds; delivered tokens/sec)
    fault_tps, fault_eng = _run_faults(repeats)
    # ISSUE-9 group: GC walk on/off under the same oversubscription
    # (its own interleaved completion rounds; delivered tokens/sec)
    gc_tps, gc_eng = _run_gc(repeats)
    # ISSUE-10 group: copy-on-write prefix sharing — the section
    # asserts bit-identical outputs and the <= 1/4 prompt-work and
    # footprint ratios internally; the artifact records the evidence
    shared_prefix = _run_prefix()
    emit("serve_prefix_flop_ratio", 0.0,
         f"x{shared_prefix['prefill_flop_ratio']:.3f}"
         f"_pages_x{shared_prefix['device_page_ratio']:.3f}"
         f"_cow={shared_prefix['cow_moves']}")
    # ISSUE-7 group: crash -> recover MTTR across snapshot intervals
    recovery_sweep = _run_recovery()
    for name, r in recovery_sweep.items():
        emit(f"serve_recovery_mttr_{name}", r["mttr_s"] * 1e6,
             f"mttr_s={r['mttr_s']:.3f}_recover_s={r['recover_s']:.3f}"
             f"_replayed={r['replayed_records']}")
    # ISSUE-5 group: the fused macro engine across channel counts (its
    # own interleaved group — the engines are only comparable to each
    # other). On a host with fewer devices than channels the sharded
    # map lowers to vmap on ONE device (`cpu_bound` below): the sweep
    # then measures sharding overhead rather than channel parallelism,
    # and the 1/N-translate-work claim is carried by the per-channel
    # routed-lane counters instead of wall clock (EXPERIMENTS.md
    # §Channel-scaling). With >= 8 devices (tier1-sharded lane /
    # real hardware) the same engines run the shard_map lowering.
    import jax

    ch_modes = tuple(f"channels_{n}" for n in CHANNEL_SWEEP)
    ch_sps, _, ch_eng = _run_decode(ch_modes, n_steps, repeats)
    ch_disp = {f"n{n}": _dispersion(ch_sps[f"channels_{n}"])
               for n in CHANNEL_SWEEP}
    for name, d in ch_disp.items():
        emit(f"serve_decode_channels_{name}", 1e6 / d["median"],
             f"steps_per_sec={d['median']:.2f}"
             f"_min={d['min']:.2f}_iqr={d['iqr']:.2f}")
    # cpu_bound reflects the lowering that actually RAN, not the device
    # count: ServeEngine pins the vmap lowering until model/map mesh
    # co-residency lands (DESIGN.md trade-offs; ROADMAP multi-host
    # item), so today this is true even on an 8-device host — the
    # wall-clock acceptance gate only arms once kvm.mesh is real
    mesh_used = all(ch_eng[f"channels_{n}"].kvm.mesh is not None
                    for n in CHANNEL_SWEEP if n > 1)
    channel_scaling = {
        "channels": list(CHANNEL_SWEEP),
        "device_count": jax.device_count(),
        "cpu_bound": not mesh_used,
        "steps_per_sec": {k: d["median"] for k, d in ch_disp.items()},
        "dispersion": ch_disp,
        "speedup_n8_vs_n1": round(statistics.median(
            x / y for x, y in zip(ch_sps[f"channels_{max(CHANNEL_SWEEP)}"],
                                  ch_sps["channels_1"])), 2),
        # routed active lanes per channel, accumulated over every fused
        # map call of the run: each channel must carry ~1/N of the
        # translate work regardless of the lowering
        "per_channel_lanes": {
            f"n{n}": [int(x)
                      for x in ch_eng[f"channels_{n}"].kvm.channel_lanes]
            for n in CHANNEL_SWEEP if n > 1},
    }
    emit("serve_decode_channel_speedup_n8_vs_n1", 0.0,
         f"x{channel_scaling['speedup_n8_vs_n1']:.2f}"
         + ("_cpu_bound" if channel_scaling["cpu_bound"] else ""))
    for name, lanes in channel_scaling["per_channel_lanes"].items():
        # 1/N guard is an UPPER bound on skew (no channel carries more
        # than 2x its fair share): a lower bound on the minimum would
        # be wrong for short windows — page p routes to channel
        # p mod C (max_pages divides by C), so a run that has not yet
        # grown into page C-1 leaves that channel legitimately idle
        tot = max(1, sum(lanes))
        assert max(lanes) * len(lanes) <= 2 * tot, \
            f"channel routing skewed: {name} lanes {lanes}"
    for mode, sps in all_sps.items():
        windows[mode] = _dispersion(sps)
        results[mode] = windows[mode]["median"]
        emit(f"serve_decode_{mode}", 1e6 / results[mode],
             f"steps_per_sec={results[mode]:.2f}"
             f"_min={windows[mode]['min']:.2f}"
             f"_iqr={windows[mode]['iqr']:.2f}")
    # speedups as the MEDIAN OF PER-ROUND RATIOS, not the ratio of
    # medians: this box's CPU-steal bursts last seconds, so whole
    # windows get hit; windows of the same round are adjacent in time
    # and see correlated noise, making their ratio far more stable
    def med_ratio(a, b):
        return round(statistics.median(
            x / y for x, y in zip(all_sps[a], all_sps[b])), 2)

    speedups = {
        # ISSUE-3 acceptance headline: live fused path vs the PR 2
        # incremental baseline
        "fused_macro_vs_incremental":
            med_ratio("fused_macro", "incremental"),
        # macro fusion isolated from this PR's graph optimizations
        "fused_macro_vs_single_step":
            med_ratio("fused_macro", "single_step"),
        "single_step_vs_incremental":
            med_ratio("single_step", "incremental"),
        "incremental_vs_rebuild":
            med_ratio("incremental", "rebuild_legacy"),
        # ISSUE-4 acceptance headline: non-blocking swap pipeline vs
        # the PR-3 fall-back-on-pressure behavior, 2x oversubscribed —
        # measured in delivered tokens/sec (see _run_decode docstring)
        "oversub_fused_vs_fallback": round(statistics.median(
            x / y for x, y in zip(over_tps["oversub_fused"],
                                  over_tps["oversub_fallback"])), 2),
    }
    over_tokens = {mode: _dispersion(w) for mode, w in over_tps.items()}
    for mode, d in over_tokens.items():
        emit(f"serve_decode_{mode}_tokens", 1e6 / max(d["median"], 1e-9),
             f"tokens_per_sec={d['median']:.2f}"
             f"_min={d['min']:.2f}_iqr={d['iqr']:.2f}")
    # ISSUE-6 headline: median of per-round delivered-throughput ratios
    # (same correlated-noise rationale as the other speedups)
    retention = round(statistics.median(
        x / y for x, y in zip(fault_tps["faults_degraded"],
                              fault_tps["faults_healthy"])), 2)
    fault_tokens = {m: _dispersion(w) for m, w in fault_tps.items()}
    for mode, d in fault_tokens.items():
        emit(f"serve_decode_{mode}_tokens", 1e6 / max(d["median"], 1e-9),
             f"tokens_per_sec={d['median']:.2f}"
             f"_min={d['min']:.2f}_iqr={d['iqr']:.2f}")
    emit("serve_decode_degraded_retention", 0.0, f"x{retention:.2f}")
    # ISSUE-9 headline pair: GC retention (median of per-round
    # delivered-throughput ratios) and the write-amplification axis
    gc_retention = round(statistics.median(
        x / y for x, y in zip(gc_tps["gc_on"], gc_tps["gc_off"])), 2)
    gc_tokens = {m: _dispersion(w) for m, w in gc_tps.items()}
    gc_stats = {m: eng.kvm.hit_stats() for m, eng in gc_eng.items()}
    for mode, d in gc_tokens.items():
        emit(f"serve_decode_{mode}_tokens", 1e6 / max(d["median"], 1e-9),
             f"tokens_per_sec={d['median']:.2f}"
             f"_min={d['min']:.2f}_iqr={d['iqr']:.2f}")
    emit("serve_decode_gc_retention", 0.0, f"x{gc_retention:.2f}")
    emit("serve_gc_write_amp", 0.0,
         f"x{gc_stats['gc_on']['write_amp']:.3f}"
         f"_moves={gc_stats['gc_on']['gc_moves']}"
         f"_victims={sum(gc_stats['gc_on']['victims_ch'])}")
    for name, x in speedups.items():
        emit(f"serve_decode_speedup_{name}", 0.0, f"x{x:.2f}")

    path = os.environ.get("REPRO_BENCH_OUT", "BENCH_serve.json")
    # regression smoke: compare against targets and the committed
    # trajectory, but only WARN — the 2-core CI runner swings 2-3x
    # between runs, so a hard gate would be pure noise
    warnings = []
    for name, target in TARGETS.items():
        if name == "degraded_retention":
            got = retention
        elif name == "gc_retention":
            got = gc_retention
        else:
            got = speedups[name]
        if got < target:
            warnings.append(f"speedup {name} x{got:.2f} "
                            f"below x{target:.2f} target")
    # ISSUE-5 acceptance: >= 1.5x at N=8 on a real 8-device mesh; on a
    # CPU-bound host the lane counters above carry the claim instead
    if not channel_scaling["cpu_bound"] \
            and channel_scaling["speedup_n8_vs_n1"] < 1.5:
        warnings.append(
            f"channel scaling x{channel_scaling['speedup_n8_vs_n1']:.2f}"
            " below x1.50 target on an 8-device mesh")
    try:
        with open(path) as f:
            prev = json.load(f).get("steps_per_sec", {})
        for mode, now in results.items():
            old = prev.get(mode)
            if old and now < 0.6 * old:
                warnings.append(f"{mode} {now:.0f} steps/s vs "
                                f"{old:.0f} committed (>40% drop)")
    except (OSError, ValueError):
        pass
    for w in warnings:
        print(f"# WARNING: possible regression: {w}", flush=True)
    if warnings and quick:
        print("# (smoke mode: reported, not failed)", flush=True)

    out = {
        "bench": "serve_decode",
        "n_slots": N_SLOTS,
        "max_pages": MAX_PAGES,
        "macro_k": MACRO_K,
        "steps_timed": n_steps,
        "repeats": repeats,
        "steps_per_sec": results,
        "dispersion": windows,
        "speedups": speedups,
        # ISSUE-5: channel-scaling sweep of the sharded fused engine
        "channel_scaling": channel_scaling,
        # ISSUE-4: the zero-fallback claim is recorded from counters
        # so the trajectory artifact is assertable, not inferential
        "oversubscription": {
            "prompt_len": OVERSUB_PROMPT,
            "max_new": OVERSUB_MAX_NEW,
            "n_device_blocks": OVERSUB_DEV,
            "n_host_blocks": OVERSUB_HOST,
            # delivered-token throughput: the rate the acceptance
            # ratio uses (a scheduling round decodes only resident
            # lanes, so the shared steps/sec table under-specifies
            # this pair)
            "tokens_per_sec": {m: d["median"]
                               for m, d in over_tokens.items()},
            "tokens_dispersion": over_tokens,
            "modes": {
                mode: {
                    "macro_steps": eng.metrics["macro_steps"],
                    "macro_fallbacks": eng.metrics["macro_fallbacks"],
                    "swaps_out": eng.metrics["swaps_out"],
                    "swaps_in": eng.metrics["swaps_in"],
                    "pool": {
                        "swaps_out_blocks": eng.kvm.pool.stats.swaps_out,
                        "swaps_in_blocks": eng.kvm.pool.stats.swaps_in,
                    },
                } for mode, eng in over_eng.items()
            },
        },
        # ISSUE-6: graceful degradation under a deterministic fault
        # plan — retention is the acceptance headline, the recovery
        # counters prove the degraded run actually exercised the plane
        "fault_injection": {
            "channels": FAULT_CHANNELS,
            "stall": list(FAULT_STALL),
            "swap_fail_p": FAULT_SWAP_P,
            "seed": FAULT_SEED,
            "retention_degraded_vs_healthy": retention,
            "tokens_per_sec": {m: d["median"]
                               for m, d in fault_tokens.items()},
            "tokens_dispersion": fault_tokens,
            "modes": {
                mode: {
                    "swap_faults": eng.metrics["swap_faults"],
                    "quarantines": eng.metrics["quarantines"],
                    "watchdog_quarantines":
                        eng.metrics["watchdog_quarantines"],
                    "requeues": eng.metrics["requeues"],
                    "retired_blocks":
                        eng.kvm.hit_stats()["retired_blocks"],
                    "program_faults":
                        eng.kvm.hit_stats()["program_faults"],
                } for mode, eng in fault_eng.items()
            },
        },
        # ISSUE-9: the GC victim-eviction walk's write-amplification
        # axis — host writes vs flash programs (fused-path commits +
        # swap-ins + GC relocations), reclaim counters, and the CTP
        # prefetch hit accounting; retention is the acceptance headline
        "gc": {
            "watermark": GC_WATERMARK,
            "pages_per_boundary": GC_BUDGET,
            "block_pages": GC_BLOCK_PAGES,
            "retention_gc_on_vs_off": gc_retention,
            "tokens_per_sec": {m: d["median"]
                               for m, d in gc_tokens.items()},
            "tokens_dispersion": gc_tokens,
            "modes": {
                mode: {
                    "gc_walks": eng.metrics["gc_walks"],
                    "gc_moves": eng.metrics["gc_moves"],
                    "gc_victims": eng.metrics["gc_victims"],
                    "host_writes": gc_stats[mode]["host_writes"],
                    "flash_programs": gc_stats[mode]["flash_programs"],
                    "write_amp": round(gc_stats[mode]["write_amp"], 4),
                    "victims_per_channel":
                        list(gc_stats[mode]["victims_ch"]),
                    "prefetch_hits": gc_stats[mode]["prefetch_hits"],
                    "prefetch_misses": gc_stats[mode]["prefetch_misses"],
                } for mode, eng in gc_eng.items()
            },
        },
        # ISSUE-10: copy-on-write prefix sharing — prompt-work and
        # footprint ratios vs the sharing-off control, the COW
        # evidence, and the bit-identity / inertness proofs
        "shared_prefix": shared_prefix,
        # ISSUE-7: sudden-power-off recovery — MTTR per snapshot
        # interval (same deterministic crash point throughout, so the
        # replayed-record counts are the interval tradeoff, not noise)
        "recovery": {
            "channels": RECOVERY_CHANNELS,
            "seed": RECOVERY_SEED,
            "crash_at": RECOVERY_CRASH_AT,
            "snapshot_sweep": recovery_sweep,
            "mttr_s": {name: r["mttr_s"]
                       for name, r in recovery_sweep.items()},
        },
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
