"""Serving engine: continuous batching over a fixed slot grid, with the
FMMU page manager owning logical->physical KV translation.

Prefill writes each request's KV into pool blocks named by the FMMU
block table; decode steps run the whole slot batch through
Model.decode_step against the **device-resident incremental block
table** (a member of the FMMU state pytree, kept coherent by the same
fused call that commits each map write — see DESIGN.md). The decode
hot loop performs zero full-map retranslations and at most one fused
map call per step: page growth for all slots crossing a page boundary
is batched into ONE allocation + ONE ``_xlate``, and paused/invalid
slot masking happens inside the decode jit (no host table roundtrip;
the only per-step host sync is the next-token transfer). Pool
exhaustion preempts the longest victim sequence to the host tier
(swap_out, CondUpdate-guarded) — the serving analogue of the paper's
GC path.

K-step fused decode macro-steps (DESIGN.md "Macro-step decode")
---------------------------------------------------------------
With ``macro_k >= 2`` the steady-state inner loop leaves the host
entirely: ONE donated jit runs a ``lax.scan`` of K decode steps —
attention + greedy sampling + page-boundary detection + device-side
block allocation (the ServingMapState free stack) + fused map commit
per step — and the host performs exactly one dispatch and one
device->host sync (tokens + allocation log) per K tokens. The host
pool stays authoritative at macro-step boundaries only: admission,
swap, preemption and the reconciliation of allocator deltas
(``KVPageManager.reconcile_macro``) happen between scans, and the
engine falls back to the single-step path only when the decoding
lanes' worst-case growth cannot be made to fit the device pool even
by swapping (proactive check; the in-graph ``oob`` flag is the
reactive backstop) — e.g. with no host tier configured. Slots
that finish mid-scan (EOS / max_new budget) are retired *inside* the
scan with single-step pause semantics — masked to the scratch block,
context frozen, no further growth — and freed by the host at the
boundary, so a K-step scan is bit-identical to K single steps.

Non-blocking host-tier swap pipeline (DESIGN.md, ISSUE 4)
---------------------------------------------------------
The paper's FMMU services outstanding requests while a map-cache miss
is handled; the serving analogue is a slot whose KV pages live in the
host tier. With ``nonblocking_swap`` (the default) such slots no
longer drop the engine out of the fused macro path: they are
**swap-pending lanes** — masked inside the scan from the
``ServingMapState.swap_pending`` residency lane exactly like paused
slots — while every other slot keeps decoding. A boundary scheduler
(``_swap_schedule``) plans tier moves between macro-steps: it swaps
out victims until the residents' worst-case K-step growth fits the
free pool, swaps waiting slots back in FIFO, and rotates by aging
(``swap_patience``) so sustained 2x oversubscription runs steady-state
with ZERO single-step fallbacks (counter-enforced). Swap data
movement itself is one donated jitted gather/scatter per swap with the
CondUpdate map commits riding the single-probe fused translate
(``KVPageManager.swap_out/swap_in``, ``check=False``: the host never
blocks on a swap). ``nonblocking_swap=False`` restores the PR-3
fall-back-on-pressure behavior (the serve_bench baseline).

Channel-sharded map (DESIGN.md "Channel-sharded map pipeline", ISSUE 5)
-----------------------------------------------------------------------
``ServeEngine(channels=N)`` shards the FMMU map state across N
channels by the static hash ``dlpn mod N`` (KVPageManager above). The
macro path then PRE-COMMITS each scan's worst-case growth at the
boundary — one channel-aware pool allocation in the scan's own
step-major pop order plus ONE fused sharded map dispatch — and runs a
pure-decode K-step scan (``_macro_sharded_fn``) against the table
materialized from the channel shards once per dispatch. Eligibility
and the swap scheduler's reserve arithmetic compare need against free
blocks PER CHANNEL (a dry channel is real pressure even while others
hold blocks). ``channels=1`` (default) is the unsharded path above,
bit-identical.

Continuous-batching admission rides the same boundaries: ``_admit``
spends at most ``admit_tokens`` prompt tokens per scheduling round;
a longer prompt is chunk-prefilled — its first chunk goes through the
prefill kernel and the remainder streams through the decode scans as
**forced lanes** (the scan consumes the known prompt token instead of
the sampled one and the boundary prediction is discarded), so
admission never stalls the decode batch.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import journal as jl
from repro.core.counters import COUNTERS
from repro.core.faults import FaultPlane, SwapFault
from repro.core.fmmu import batch as fb
from repro.core.fmmu.types import NIL
from repro.models import transformer
from repro.models.common import Runtime
from repro.models.model import Model, _src_len
from repro.paging.kv_manager import KVPageManager
from repro.paging.pool import OutOfBlocks
from repro.serving.config import (DurabilityConfig, FaultPolicy,
                                  GCConfig, ServeConfig)

# Host-cost counters (the XLATE_CALLS pattern): one MACRO_DISPATCHES
# bump per macro-step jit call, one HOST_SYNCS bump per blocking
# device->host readback. tests/test_serving.py asserts steady-state
# macro decode costs exactly one of each per K steps. The names alias
# registry cells (core/counters.py): same list objects, also visible
# to COUNTERS.snapshot()/delta().
MACRO_DISPATCHES = COUNTERS.cell("engine.macro_dispatches")
HOST_SYNCS = COUNTERS.cell("engine.host_syncs")


@dataclasses.dataclass
class Request:
    rid: int
    tokens: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    src_emb: Optional[jnp.ndarray] = None
    prefix_emb: Optional[jnp.ndarray] = None
    # chunked admission: prompt tokens not yet fed to the model — they
    # stream through the decode path as forced lanes (predictions over
    # this range are discarded; the true next token is known)
    pending_prompt: List[int] = dataclasses.field(default_factory=list)


class ServeEngine:
    def __init__(self, model: Model, params, *,
                 config: Optional[ServeConfig] = None,
                 fault_plane: Optional[FaultPlane] = None,
                 **legacy):
        # typed-config constructor (ISSUE 9 API redesign): the primary
        # form is ServeEngine(model, params, config=ServeConfig(...));
        # the historical flat keyword set still works through ONE
        # deprecation shim and builds the identical config value
        # (bit-equivalence unit-tested in tests/test_gc.py). The fault
        # PLANE stays a runtime argument on both forms — it is a
        # stateful schedule, not configuration.
        if config is not None and legacy:
            raise TypeError(
                "pass config=ServeConfig(...) OR legacy keyword "
                f"arguments, not both (got {sorted(legacy)})")
        if config is None:
            warnings.warn(
                "keyword-style ServeEngine construction is deprecated; "
                "pass config=ServeConfig(...)",
                DeprecationWarning, stacklevel=2)
            config = ServeConfig.from_legacy(**legacy)
        self.config = config
        n_slots = config.n_slots
        max_ctx = config.max_ctx
        n_device_blocks = config.n_device_blocks
        n_host_blocks = config.n_host_blocks
        eos_id = config.eos_id
        macro_k = config.macro_k
        nonblocking_swap = config.nonblocking_swap
        admit_tokens = config.admit_tokens
        swap_patience = config.swap_patience
        channels = config.channels
        use_mesh = config.use_mesh
        max_swap_retries = config.faults.max_swap_retries
        swap_backoff_cap = config.faults.swap_backoff_cap
        watchdog_rounds = config.faults.watchdog_rounds
        journal_path = config.durability.journal_path
        snapshot_every = config.durability.snapshot_every
        self.m = model
        self.cfg = model.cfg
        self.rt = model.rt
        self.params = params
        self.n_slots = n_slots
        self.page = self.rt.page_size
        self.max_pages = -(-max_ctx // self.page)
        n_dev = n_device_blocks or (n_slots * self.max_pages)
        # ISSUE-5: channels > 1 shards the FMMU map state (CMT, backing,
        # incremental table, free-list allocator, swap lanes) across an
        # N-channel mesh by the static hash owner(dlpn) = dlpn mod N;
        # the decode scans consume the table materialized from the
        # shards at macro-step boundaries. channels=1 (default) is the
        # unsharded pre-ISSUE-5 path, bit-identical.
        self.channels = int(channels)
        # the engine pins the portable vmap lowering for its map manager
        # even when >= C devices are visible (use_mesh=None): the model
        # jits carry single-device sharding constraints, and feeding
        # them mesh-committed tables/caches trips jax's incompatible-
        # device check. Model-and-map co-residency on one mesh is the
        # ROADMAP "real multi-host channel mesh" item; the shard_map
        # lowering itself is pinned bit-identical to vmap at the map
        # level (tests/test_sharded_map.py), so nothing is lost in
        # results. An explicit use_mesh=True is forwarded for setups
        # whose model is already mesh-sharded.
        # the GC plane (ISSUE 9 tentpole): config.gc arms the map's
        # live lane (per-block live-page counts maintained INSIDE the
        # fused translate commits) and the boundary victim walk below.
        # gc=None keeps live=None — an absent pytree leaf, so every
        # traced graph is bit-identical to the pre-GC engine
        # (jaxpr-identity asserted in tests/test_gc.py).
        self.gc = config.gc
        # the prefix-sharing plane (ISSUE 10 tentpole): config.prefix
        # arms the map's refcnt lane (per-block mapping counts, the
        # live lane's twin) plus the radix admission path and the COW
        # frontier scan below. prefix=None keeps refcnt=None — an
        # absent pytree leaf, so every traced graph is bit-identical
        # to the pre-sharing engine (tests/test_prefix.py).
        self.prefix = config.prefix
        self.kvm = KVPageManager(n_slots, self.max_pages, n_dev,
                                 n_host_blocks, channels=self.channels,
                                 use_mesh=bool(use_mesh),
                                 faults=fault_plane,
                                 track_live=self.gc is not None,
                                 track_refs=self.prefix is not None)
        if self.prefix is not None:
            self.kvm.prefix_max_nodes = self.prefix.max_nodes
        # sharing only applies to pure paged-attention state: a mamba
        # layer's recurrent state is per-slot and position-dependent,
        # so a skipped prefill cannot be reconstructed from shared KV
        # pages (requests with prefix/src embeddings are gated per
        # request in _share_ok for the same reason)
        self._share_model_ok = not any(
            self.cfg.layer_kind(j) == "mamba"
            for j in range(self.cfg.period))
        src_len = _src_len(self.cfg, max_ctx)
        # +1 scratch block: unmapped table entries (inactive slots) write
        # their garbage KV there instead of corrupting block 0
        self.scratch_block = n_dev + n_host_blocks
        self.caches = transformer.init_decode_caches(
            self.cfg, self.rt, n_slots, self.max_pages,
            n_dev + n_host_blocks + 1, self.rt.compute_dtype,
            src_len=src_len)
        # int32 end-to-end: the decode jit consumes these every step and
        # an int64 numpy array would pay a device-side convert per call
        self.ctx_lens = np.zeros(n_slots, np.int32)
        self.src_cap = src_len
        self.src_lens = np.zeros(n_slots, np.int32)
        self.active: Dict[int, Request] = {}
        self.eos_id = eos_id
        self.queue: Deque[Request] = deque()
        self._rid = 0
        # caches (arg 2) are DONATED: the KV pool is updated in place
        # instead of functionally copied every step. Callers always
        # rebind self.caches from the return (same contract as the
        # donated FMMU state pytree). The live-page bucket (arg 7) is
        # STATIC: the block table is sliced to the smallest power-of-2
        # page count covering every mapped page before attention runs,
        # so decode work scales with actual context, not max_ctx; each
        # bucket traces once (<= log2(max_pages) compilations per run).
        self._decode = jax.jit(self._decode_fn, donate_argnums=(2,),
                               static_argnums=(7,))
        self._prefill = jax.jit(self._prefill_fn, donate_argnums=(2,))
        # K-step fused macro-steps: state pytree (arg 1) and caches
        # (arg 2) both DONATED — the whole inner loop mutates in place.
        # Two static specializations (cached separately, never
        # re-traced): `simple` drops the retirement machinery for the
        # common steady state where no slot can finish mid-scan
        # (eos_id < 0 and every budget >= K); `full` keeps EOS/budget
        # retirement with pause semantics.
        self.macro_k = int(macro_k)
        self._macro = self._macro_simple = None
        self._macro_sh = self._macro_sh_simple = None
        if self.macro_k >= 2:
            if self.channels == 1:
                self._macro = jax.jit(self._macro_fn,
                                      donate_argnums=(1, 2),
                                      static_argnums=(10,))
                self._macro_simple = jax.jit(
                    functools.partial(self._macro_fn, simple=True),
                    donate_argnums=(1, 2), static_argnums=(10,))
            else:
                # channel-sharded scans: growth is pre-committed at the
                # boundary, so the scan takes no map state — only the
                # caches donate and the sharded table materializes once
                # inside the jit (static arg 9 = live-page bucket)
                self._macro_sh = jax.jit(self._macro_sharded_fn,
                                         donate_argnums=(1,),
                                         static_argnums=(9,))
                self._macro_sh_simple = jax.jit(
                    functools.partial(self._macro_sharded_fn,
                                      simple=True),
                    donate_argnums=(1,), static_argnums=(9,))
        self._macro_on = self.macro_k >= 2
        self.min_page_bucket = 4
        # non-blocking swap pipeline + continuous-batching admission
        # (module docstring): swap-pending slots are masked scan lanes,
        # the boundary scheduler rotates residency by aging, and
        # admission spends at most admit_tokens prompt tokens per round
        # (None = admit whole prompts, the pre-ISSUE-4 behavior)
        self.nonblocking_swap = bool(nonblocking_swap)
        if admit_tokens is not None and admit_tokens <= 0:
            raise ValueError(
                f"admit_tokens={admit_tokens}: a non-positive budget "
                "would never admit anything (pass None for unlimited)")
        self.admit_tokens = admit_tokens
        self.swap_patience = int(swap_patience)
        self._boundary = 0
        self._pending_since: Dict[int, int] = {}
        self._resident_since: Dict[int, int] = {}
        # fault plane + recovery machinery (ISSUE 6, core/faults.py):
        # swap failures retry with capped exponential backoff and a
        # per-slot counter — a persistent failer is QUARANTINED (pages
        # freed, request requeued at the admission front, reservation
        # released the same boundary); a macro-boundary watchdog
        # force-quarantines any lane with no token progress for
        # watchdog_rounds boundaries (None: 8*patience with a plane,
        # off without — a healthy engine cannot strand a lane)
        self.faults = fault_plane
        self.max_swap_retries = int(max_swap_retries)
        self.swap_backoff_cap = int(swap_backoff_cap)
        if watchdog_rounds is None:
            watchdog_rounds = (8 * max(1, self.swap_patience)
                               if fault_plane is not None else 0)
        self.watchdog_rounds = int(watchdog_rounds)
        self._swap_fails: Dict[int, int] = {}     # slot -> consecutive
        self._retry_at: Dict[int, int] = {}       # slot -> boundary gate
        self._progress: Dict[int, tuple] = {}     # slot -> (out, pend, bd)
        self.metrics = {"prefills": 0, "prefill_tokens": 0,
                        "decode_steps": 0, "preemptions": 0,
                        "generated": 0, "macro_steps": 0,
                        "macro_fallbacks": 0, "swaps_out": 0,
                        "swaps_in": 0, "chunked_prefills": 0,
                        "swap_faults": 0, "quarantines": 0,
                        "watchdog_quarantines": 0, "requeues": 0,
                        "recoveries": 0, "gc_walks": 0, "gc_moves": 0,
                        "gc_victims": 0, "shared_admits": 0,
                        "shared_pages": 0, "cow_moves": 0}
        # crash-consistency journal (ISSUE 7, core/journal.py): when
        # attached, every host commit point appends a sequence-numbered
        # record and every `snapshot_every`-th macro boundary writes a
        # full atomic state snapshot. Detached (default) the engine is
        # byte-for-byte the PR-6 engine — the hooks are `is not None`
        # guards on host code, so the traced graphs cannot differ
        # (jaxpr-identity asserted in tests/test_journal.py).
        self.journal: Optional["jl.Journal"] = None
        self.snapshot_every = int(snapshot_every)
        self._finished: Dict[int, List[int]] = {}
        self._ever_admitted: set = set()
        self._lane_base = 0
        self.last_recovery: Optional[dict] = None
        if journal_path:
            self.attach_journal(journal_path)

    # ------------------------------------------------------------- API
    def submit(self, tokens: List[int], max_new: int = 16, *,
               src_emb=None, prefix_emb=None) -> int:
        rid = self._rid
        self._rid += 1
        self.queue.append(Request(rid, list(tokens), max_new,
                                  src_emb=src_emb, prefix_emb=prefix_emb))
        if self.journal is not None:
            assert src_emb is None and prefix_emb is None, \
                "journaled serving persists token prompts only"
            self.journal.append(jl.SUBMIT,
                                {"rid": rid,
                                 "tokens": [int(t) for t in tokens],
                                 "max_new": int(max_new), "lanes": 0})
        return rid

    def run(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        done: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            if not self.step(done):
                break
        return done

    def reset(self, fault_plane: Optional[FaultPlane] = None):
        """Fresh serving state on the SAME compiled jits: the decode /
        prefill / macro / swap closures are bound methods whose traces
        are per-instance, so a new ServeEngine recompiles everything —
        this instead reinitializes map, pool, caches and bookkeeping
        (optionally installing a new fault plane) and keeps every
        compiled function. The chaos harness (tests/chaos/) replays
        hundreds of fault schedules per engine through this."""
        self.kvm.reset(faults=fault_plane)
        self.faults = fault_plane
        self.caches = transformer.init_decode_caches(
            self.cfg, self.rt, self.n_slots, self.max_pages,
            self.scratch_block + 1, self.rt.compute_dtype,
            src_len=self.src_cap)
        self.ctx_lens[:] = 0
        self.src_lens[:] = 0
        self.active = {}
        self.queue = deque()
        self._rid = 0
        self._boundary = 0
        self._pending_since = {}
        self._resident_since = {}
        self._swap_fails = {}
        self._retry_at = {}
        self._progress = {}
        if self.journal is not None:
            self.journal.close()
        self.journal = None        # kvm.reset detached its hook already
        self._finished = {}
        self._ever_admitted = set()
        for k in self.metrics:
            self.metrics[k] = 0

    # -------------------------------------- crash consistency (ISSUE 7)
    def attach_journal(self, path: str,
                       snapshot_every: Optional[int] = None,
                       resume: bool = False) -> "jl.Journal":
        """Arm crash-consistent journaling at `path`: every host commit
        point appends a record, every snapshot_every-th boundary writes
        an atomic snapshot, and the fault plane's crash axis (if any)
        is consumed per append. Writes the base snapshot immediately —
        recovery always has a floor to replay from."""
        if snapshot_every is not None:
            self.snapshot_every = int(snapshot_every)
        self.journal = jl.Journal(path, faults=self.faults,
                                  resume=resume)
        self.kvm.journal = self.journal
        # lane-integrity baseline: device commit lanes vs journaled
        # lanes advance in lockstep from here (journal_lane_check)
        self._lane_base = self._device_lanes()
        self.journal.lanes_base = self.journal.commit_lanes
        self._write_snapshot()
        return self.journal

    def _journal_finish(self, r: Request):
        """FINISH precedes the slot's FREE in the journal: a crash
        between the two leaves an orphan mapping that replay's cleanup
        pass re-frees (the request is durably done either way)."""
        if self.journal is None:
            return
        out = [int(t) for t in r.out[:r.max_new]]
        self._finished[r.rid] = out
        self.journal.append(jl.FINISH,
                            {"rid": r.rid, "out": out, "lanes": 0})

    def _device_lanes(self) -> int:
        """Total committed map-write lanes on device (the ISSUE-7
        commit_seq lane, summed over channel shards). A readback —
        diagnostics and tests only, never the hot path."""
        return int(np.asarray(jax.device_get(
            fb.commit_seq_vec(self.kvm.state))).sum())

    def journal_lane_check(self) -> bool:
        """Integrity cross-check at a quiesced boundary: the device's
        commit_seq lane and the journal's cumulative record lanes must
        have advanced identically since attach. (Between a macro scan
        and its reconcile record the two legitimately diverge — call
        this after ``step`` returns, not mid-dispatch.)"""
        if self.journal is None:
            return True
        return (self._device_lanes() - self._lane_base
                == self.journal.commit_lanes - self.journal.lanes_base)

    def _write_snapshot(self) -> str:
        """One atomic full-state snapshot: the manager's host truth
        (page lists + pool allocator incl. free-list order) plus the
        engine's request/admission state. Host bookkeeping only — no
        device arrays, no KV data (volatile by design: in-flight
        requests restart via the quarantine discipline)."""
        st = self.kvm.snapshot_state()
        st["queue"] = [r.rid for r in self.queue]
        st["ever_admitted"] = sorted(self._ever_admitted)
        st["active"] = [[r.rid, r.slot] for r in self.active.values()]
        st["done"] = {int(r): o for r, o in self._finished.items()}
        st["submits"] = {
            r.rid: [[int(t) for t in r.tokens], int(r.max_new)]
            for r in list(self.queue) + list(self.active.values())}
        st["rid"] = self._rid
        st["boundary"] = self._boundary
        return self.journal.snapshot(st)

    def recover(self, path: str,
                fault_plane: Optional[FaultPlane] = None,
                snapshot_every: Optional[int] = None
                ) -> Dict[int, List[int]]:
        """Sudden-power-off recovery: rebuild this engine from the
        journal directory at `path` (latest snapshot + record replay +
        OOB reverse-map scan for a torn tail — core/journal.py), then
        restart every in-flight request with the quarantine discipline
        — pages freed, output reset, requeued at its admission
        position — and re-arm the journal with a fresh base snapshot.

        Requeue ordering (satellite 2): the recovered admission deque
        is [crash-time front-requeued quarantined requests] +
        [in-flight requests, admission order] + [never-admitted
        arrivals, FIFO]. Quarantined requests were deliberately pushed
        AHEAD of the admission point before the crash, so recovery
        must not reorder them behind the recovered in-flight ones; the
        crash-time queue can only be (requeued..., pristine...) —
        appendleft builds the front, append the back — so the split
        point is the first never-admitted rid.

        Returns the durably finished outputs (rid -> tokens); resumed
        decode is bit-identical to an uncrashed run (greedy
        determinism). ``last_recovery`` carries MTTR inputs: replayed
        record count, torn/oob_scan flags, and wall recovery time."""
        t0 = time.perf_counter()
        rec = jl.replay(path)
        n_recov = self.metrics.get("recoveries", 0)
        self.reset(fault_plane)
        self.kvm.restore_mapping(rec)
        # in-flight restart (KV was volatile): free surviving pages —
        # journal detached, so these frees are folded into the fresh
        # base snapshot rather than logged — and rebuild Requests
        requeued: List[Request] = []
        for rid, slot in rec.active.items():
            if slot in self.kvm.seq_pages:
                self.kvm.free_seq(slot)
            toks, mx = rec.submits[rid]
            requeued.append(Request(rid, list(toks), int(mx)))
        qreqs = []
        for rid in rec.queue:
            toks, mx = rec.submits[rid]
            qreqs.append(Request(rid, list(toks), int(mx)))
        k = 0
        while k < len(qreqs) and qreqs[k].rid in rec.ever_admitted:
            k += 1
        self.queue = deque(qreqs[:k] + requeued + qreqs[k:])
        self._rid = int(rec.rid)
        self._boundary = int(rec.boundary)
        self._finished = {int(r): list(o) for r, o in rec.done.items()}
        self._ever_admitted = (set(rec.ever_admitted)
                               | set(rec.active.keys()))
        self.metrics["requeues"] += len(requeued)
        self.metrics["recoveries"] = n_recov + 1
        # re-arm: truncate the torn tail, continue the sequence, seal
        # with a fresh snapshot — a second crash replays from here
        self.attach_journal(path, snapshot_every=snapshot_every,
                            resume=True)
        self.last_recovery = {
            "snap_seq": int(rec.snap_seq),
            "last_seq": int(rec.last_seq),
            "replayed": int(rec.replayed),
            "torn": bool(rec.torn), "oob_scan": bool(rec.oob_scan),
            "requeued": len(requeued),
            "recover_s": time.perf_counter() - t0}
        return {int(r): list(o) for r, o in rec.done.items()}

    # ------------------------------------------------------------- steps
    def step(self, done: Dict[int, List[int]]) -> bool:
        """One scheduling round: admissions (budgeted), boundary swap
        planning, then either ONE fused K-step macro-step (swap-pending
        slots masked as paused lanes) or one single decode step."""
        self._admit()
        if not self.active:
            return bool(self.queue)
        # one scheduling round = one boundary (the aging/backoff/
        # watchdog clock); counted here so fallback rounds age too
        self._boundary += 1
        if self.watchdog_rounds:
            self._watchdog()
            if not self.active:
                return bool(self.queue)
        if self._macro_on and self.nonblocking_swap:
            self._swap_schedule()
        # COW frontier (ISSUE 10): shared pages the coming writes
        # would touch go private here, before any decode dispatch
        # (and before the macro paths' allocator sync — the copies'
        # destination pops must reach the device mirror)
        if self.prefix is not None:
            self._cow_boundary()
        if self._macro_eligible():
            self._macro_decode_step(done)
        else:
            if self._macro_on:
                self.metrics["macro_fallbacks"] += 1
            self._decode_step(done)
        # GC watermark policy (ISSUE 9 tentpole): when any channel's
        # free device blocks fall below the watermark, run ONE budgeted
        # victim walk at this boundary — never inside the decode path
        if self.gc is not None:
            self._gc_boundary()
        # macro-boundary snapshot cadence (ISSUE 7): every
        # snapshot_every-th scheduling round seals the journal with a
        # full atomic state snapshot, bounding replay length (MTTR)
        if self.journal is not None and self.snapshot_every \
                and self._boundary % self.snapshot_every == 0:
            self._write_snapshot()
        return bool(self.active or self.queue)

    def _free_slots(self) -> List[int]:
        used = {r.slot for r in self.active.values()}
        return [s for s in range(self.n_slots) if s not in used]

    def _admit(self):
        """Continuous-batching admission: admit + prefill queued
        requests under a per-round token budget (``admit_tokens``). A
        prompt longer than the remaining budget is CHUNK-prefilled:
        its first chunk goes through the prefill kernel now and the
        remainder streams through the decode scans as forced lanes, so
        one long prompt cannot stall the decode batch for a round."""
        if not self.queue:
            return
        budget = self.admit_tokens
        free = self._free_slots()
        while self.queue and free:
            req = self.queue[0]
            slot = free[0]
            chunk = len(req.tokens)
            if budget is not None:
                if budget <= 0:
                    return                  # token budget spent this round
                chunk = min(chunk, budget)
            # prefix sharing (ISSUE 10): walk the radix tree over the
            # prompt's page groups; any cached prefix maps this slot's
            # leading dlpns at the SHARED blocks and skips their
            # prefill entirely (zero FLOPs, zero programs, zero budget)
            groups = shared_blocks = None
            if self._share_ok(req):
                groups = self.kvm.page_groups(req.tokens, self.page)
                shared_blocks = self.kvm.match_prefix(groups)
            # on-demand allocation: admission reserves only the chunk
            # (+prefix) pages that prefill actually writes; decode grows
            # the mapping page-by-page (batched, one fused map call per
            # step) instead of parking max_new worth of blocks up front
            n_prefix = (req.prefix_emb.shape[0]
                        if req.prefix_emb is not None else 0)
            if shared_blocks:
                n_pages = len(shared_blocks)
            else:
                n_pages = -(-(chunk + n_prefix) // self.page)
                n_pages = max(1, min(n_pages, self.max_pages))
            try:
                self.kvm.new_seq(slot, n_pages, shared=shared_blocks)
            except OutOfBlocks:
                if not self._preempt(exclude=slot):
                    return
                continue
            self.queue.popleft()
            free.pop(0)
            req.slot = slot
            self.active[req.rid] = req
            self._ever_admitted.add(req.rid)
            self._resident_since[slot] = self._boundary
            if self.journal is not None:
                self.journal.append(
                    jl.ADMIT, {"rid": req.rid, "slot": int(slot),
                               "lanes": 0})
            if shared_blocks:
                # the cached prefix IS the context: start the slot at
                # n_skip and stream the (always >= 1) remaining prompt
                # tokens through the decode scans as forced lanes —
                # the chunked-prefill machinery, so outputs stay
                # bit-identical to an unshared admission. Keeping the
                # final token out of the skip even when the whole
                # prompt is cached makes the last forced step produce
                # the first output logits; its page is relocated
                # copy-on-write before the write lands (_cow_boundary).
                n_skip = min(sum(len(g) for g in
                                 groups[:len(shared_blocks)]),
                             len(req.tokens) - 1)
                self.ctx_lens[slot] = n_skip
                req.pending_prompt = list(req.tokens[n_skip:])
                self.metrics["shared_admits"] += 1
                self.metrics["shared_pages"] += len(shared_blocks)
            else:
                self._do_prefill(req, chunk)
                if budget is not None:
                    budget -= chunk

    # ------------------------------------- prefix sharing (ISSUE 10)
    def _share_ok(self, req: Request) -> bool:
        """Prefix sharing applies to plain token prompts on attention
        -only state long enough to be worth the tree walk; prefix/src
        embeddings carry per-slot state the shared pages don't hold."""
        return (self.prefix is not None and self._share_model_ok
                and req.prefix_emb is None and req.src_emb is None
                and len(req.tokens) >= self.prefix.min_tokens)

    def _register_prompt(self, req: Request):
        """Pin a fully-prefilled prompt's pages into the radix tree
        (idempotent — register_prefix skips cached keys) so later
        admissions can map them. Called at every prompt-completion
        site: full prefill, single-step drain, macro-scan drain."""
        if self._share_ok(req):
            self.kvm.register_prefix(
                req.slot, self.kvm.page_groups(req.tokens, self.page))

    def _cow_boundary(self):
        """Relocate diverging shared pages BEFORE this round's decode
        writes land (ISSUE 10): every resident lane's write-frontier
        page and beyond must be private by the time the scan commits
        KV there. One batched CondUpdate + fused KV row copy — the GC
        walk's machinery and stale-lane discipline. On exhaustion,
        preempt one victim to the host tier and retry once (the copy
        itself cannot be deferred: the write is about to commit)."""
        kvm = self.kvm
        if not kvm.has_shared():
            return
        fronts = {r.slot: int(self.ctx_lens[r.slot]) // self.page
                  for r in self.active.values()
                  if kvm.is_resident(r.slot) and kvm.has_shared(r.slot)}
        if not fronts:
            return
        pools = [self.caches["pool_k"], self.caches["pool_v"]]
        try:
            pools, n = kvm.cow_writes(fronts, pools, block_axis=2)
        except OutOfBlocks:
            if not self._preempt(exclude=-1):
                raise
            pools, n = kvm.cow_writes(fronts, pools, block_axis=2)
        self.caches["pool_k"], self.caches["pool_v"] = pools
        self.metrics["cow_moves"] += n

    def _preempt(self, exclude: int) -> bool:
        """Swap the longest active sequence that still holds device
        pages out to the host tier (an already-swapped victim would
        move nothing). False when no such victim exists or the host
        tier itself cannot take the blocks."""
        if self.kvm.pool.n_host == 0:
            return False
        victims = [r for r in self.active.values() if r.slot != exclude]
        for victim in sorted(victims, key=lambda r: self.ctx_lens[r.slot],
                             reverse=True):
            if self._swap_out_slot(victim.slot, check=True):
                self.metrics["preemptions"] += 1
                return True
            if victim.rid not in self.active:
                # the failed swap quarantined the victim (retries
                # exhausted): its pages are free right now, which is
                # all the caller needed (satellite-6 same-boundary
                # release)
                return True
        return False

    def _ensure_resident(self):
        """Swap in any host-tier pages of active sequences (before decode).
        Sequences that cannot come back yet PAUSE (they are excluded from
        the decode batch) until device blocks free up. Tier predicate:
        KVPageManager.is_resident (BlockPool.is_host underneath)."""
        if self.kvm.pool.n_host == 0:
            return    # no host tier: nothing can ever be swapped out
        for r in sorted(self.active.values(),
                        key=lambda r: len(self.kvm.seq_pages.get(r.slot, []))):
            if not self.kvm.is_resident(r.slot) \
                    and not self._backed_off(r.slot):
                # a False return = stays swapped & paused; retried next
                # round (same OutOfBlocks semantics as before the dedup)
                self._swap_in_slot(r.slot, check=True)

    # --------------------------------------------- boundary swap planner
    def _growth_need(self, slot: int) -> int:
        """Total worst-case device blocks `slot` can pop during one
        K-step scan (sum of ``_growth_need_ch`` — the one home of the
        growth arithmetic the scan body and the reconcile replay
        mirror)."""
        return int(self._growth_need_ch(slot).sum())

    def _growth_need_ch(self, slot: int) -> np.ndarray:
        """Worst-case K-step growth of `slot` per owner channel
        ([total] at channels=1): page p pops from channel
        (slot * max_pages + p) mod C, so the reserve checks must fit
        per channel, not in aggregate. Same page-boundary arithmetic
        as the scan body and the reconcile replay (mirror
        protocol)."""
        C = self.channels
        have = len(self.kvm.seq_pages[slot])
        target = min(self.max_pages,
                     -(-(int(self.ctx_lens[slot]) + self.macro_k)
                       // self.page))
        out = np.zeros(C, np.int64)
        base = slot * self.max_pages
        for p in range(have, target):
            out[(base + p) % C] += 1
        return out

    def _swap_out_slot(self, slot: int, check: bool = False) -> bool:
        """Move one slot's device pages to the host tier through the
        fused swap jit; the ONE home for the engine's swap-out protocol
        (pool pack + caches rebind + counters + residency stamps),
        shared by the boundary scheduler (check=False: no readback,
        the non-blocking mode) and the single-step preempt path
        (check=True, the PR-3-faithful blocking guard). The slot
        becomes a swap-pending lane — masked in the next scans — until
        it is swapped back in."""
        kvm = self.kvm
        if kvm.n_device_pages(slot) == 0:
            return False
        pools = [self.caches["pool_k"], self.caches["pool_v"]]
        try:
            pools, moved = kvm.swap_out(slot, pools, block_axis=2,
                                        check=check)
        except SwapFault:
            self._note_swap_fault(slot)   # backoff, maybe quarantine
            return False
        except OutOfBlocks:
            return False               # host tier full: nothing moved
        self.caches["pool_k"], self.caches["pool_v"] = pools
        if not moved:
            return False
        self._swap_fails.pop(slot, None)
        self._retry_at.pop(slot, None)
        self._progress.pop(slot, None)
        self.metrics["swaps_out"] += 1
        self._pending_since[slot] = self._boundary
        return True

    def _swap_in_slot(self, slot: int, check: bool = False) -> bool:
        """Swap-out's dual: same single home, same check semantics."""
        kvm = self.kvm
        pools = [self.caches["pool_k"], self.caches["pool_v"]]
        try:
            pools, moved = kvm.swap_in(slot, pools, block_axis=2,
                                       check=check)
        except SwapFault:
            self._note_swap_fault(slot)
            return False
        except OutOfBlocks:
            return False
        self.caches["pool_k"], self.caches["pool_v"] = pools
        if not moved:
            return False
        self._swap_fails.pop(slot, None)
        self._retry_at.pop(slot, None)
        self._progress.pop(slot, None)
        self.metrics["swaps_in"] += 1
        self._resident_since[slot] = self._boundary
        self._pending_since.pop(slot, None)
        return True

    # --------------------------------------- fault recovery (ISSUE 6)
    def _note_swap_fault(self, slot: int):
        """An injected swap failure left state untouched (SwapFault
        raises pre-mutation): back the slot off for min(2^fails,
        swap_backoff_cap) boundaries — capped exponential — and
        QUARANTINE it once max_swap_retries consecutive attempts have
        failed (a wedged slot must not pin its reservation forever)."""
        self.metrics["swap_faults"] += 1
        n = self._swap_fails.get(slot, 0) + 1
        self._swap_fails[slot] = n
        if n >= self.max_swap_retries:
            self._quarantine(slot, "swap retries exhausted")
        else:
            self._retry_at[slot] = self._boundary + min(
                1 << n, self.swap_backoff_cap)

    def _backed_off(self, slot: int) -> bool:
        """True while `slot`'s swap backoff window is open: the
        scheduler neither retries its swap nor picks it as a victim
        (both directions share the per-slot failure counter)."""
        return self._retry_at.get(slot, 0) > self._boundary

    def _quarantine(self, slot: int, reason: str):
        """Remove a failing slot from service: free its pages (both
        tiers), requeue its request at the ADMISSION FRONT with output
        reset (greedy decode is deterministic and per-slot independent,
        so the restarted request's tokens are bit-identical to an
        uninterrupted run — the chaos harness asserts this), and clear
        every per-slot scheduler stamp. The slot's reserved worst-case
        growth is released the moment this returns — the same boundary
        (satellite 6), not at retirement."""
        req = next((r for r in self.active.values() if r.slot == slot),
                   None)
        if req is None:
            return
        self.kvm.free_seq(slot)
        del self.active[req.rid]
        self._release_slot(slot)
        req.slot = -1
        req.out = []
        req.pending_prompt = []
        self.queue.appendleft(req)
        if self.journal is not None:
            self.journal.append(jl.QUAR, {"rid": req.rid, "lanes": 0})
        self.metrics["quarantines"] += 1
        self.metrics["requeues"] += 1
        if "watchdog" in reason:
            self.metrics["watchdog_quarantines"] += 1

    def _release_slot(self, slot: int):
        """Per-slot scheduler-state cleanup shared by retirement and
        quarantine: a reused slot must not inherit its predecessor's
        backoff window, watchdog stamp or residency ages."""
        self.ctx_lens[slot] = 0
        for d in (self._pending_since, self._resident_since,
                  self._swap_fails, self._retry_at, self._progress):
            d.pop(slot, None)

    def _watchdog(self):
        """Macro-boundary watchdog: force-quarantine any lane with no
        progress for ``watchdog_rounds`` boundaries — the backstop that
        catches a lane stuck behind a pathologically browned-out
        channel or an unlucky fault schedule, so the rest of the batch
        keeps its throughput. Progress is token progress (generated
        output or consumed prompt chunk) OR a completed tier move (the
        swap paths clear the stamp): a host-resident lane rotating
        through the normal oversubscription cycle is WAITING, not
        wedged, and must not be restarted — only a lane that neither
        decodes nor moves for the whole window is."""
        for r in list(self.active.values()):
            s = r.slot
            cur = (len(r.out), len(r.pending_prompt))
            last = self._progress.get(s)
            if last is None or (last[0], last[1]) != cur:
                self._progress[s] = (cur[0], cur[1], self._boundary)
            elif self._boundary - last[2] >= self.watchdog_rounds:
                self._quarantine(s, "watchdog: no token progress")

    def _stall_shrink(self, free: np.ndarray) -> np.ndarray:
        """Apply the fault plane's per-channel stall multipliers to a
        free-block vector: a browned-out channel advertises 1/stall of
        its blocks. Identity without a plane."""
        if self.faults is not None:
            st = self.faults.stall_vec(self.channels)
            if (st > 1.0).any():
                free = (free / np.maximum(st, 1.0)).astype(np.int64)
        return free

    def _free_eff(self) -> np.ndarray:
        """Per-channel free device blocks as advertised to the boundary
        planners (_macro_eligible + _swap_schedule), shrunk by the
        fault plane's stall multipliers: a browned-out channel offers
        1/stall of its free blocks, so residency/growth shrink THERE
        while healthy channels keep full budget — graceful degradation
        through the existing per-channel eligibility vectors rather
        than a new scheduler. Identical to kvm.free_device_vec()
        without a plane. The single-step fallback path deliberately
        ignores stall (it allocates against the real pool), so a
        brownout can never livelock the engine — it only slows it."""
        return self._stall_shrink(self.kvm.free_device_vec())

    # ----------------------------------------- GC boundary walk (ISSUE 9)
    def _gc_boundary(self):
        """Watermark-triggered victim eviction (the paper's GCM): when
        some channel's free device blocks drop below ``gc.watermark``,
        run one budgeted walk — pick each pressured channel's
        fragmented erase block with the fewest live pages (from the
        counts the fused commits already maintain), relocate its live
        pages as ONE batched CondUpdate + KV row move, and free the
        whole victim. Budgeted (``gc.pages_per_boundary``) so GC can
        never stall decode; journaled as a host commit so a crash
        mid-walk recovers bit-identically."""
        gc = self.gc
        if bool((self.kvm.free_device_vec() >= gc.watermark).all()):
            return
        pools = [self.caches["pool_k"], self.caches["pool_v"]]
        pools, moved, victims = self.kvm.gc_collect(
            pools, block_axis=2, block_pages=gc.block_pages,
            budget=gc.pages_per_boundary)
        self.caches["pool_k"], self.caches["pool_v"] = pools
        self.metrics["gc_walks"] += 1
        self.metrics["gc_moves"] += moved
        self.metrics["gc_victims"] += victims

    def _swap_schedule(self):
        """Boundary swap planner (DESIGN.md "Non-blocking host-tier
        swap pipeline"): runs between macro-steps and keeps the fused
        scan eligible — swap-pending slots become masked lanes instead
        of dropping the engine to single-step mode. Three passes:

          1. reserve — swap out victims (longest context first, like
             ``_preempt``) until the residents' worst-case K-step
             growth fits the free device pool;
          2. resume — swap waiting slots back in, FIFO by the boundary
             they were swapped out, while they fit beside the reserve;
          3. aging — a slot pending longer than ``swap_patience``
             boundaries evicts the longest-resident slots until it
             fits: starvation-free rotation under sustained
             oversubscription.

        Every move is the fused donated swap with ``check=False`` —
        the host dispatches it and keeps scheduling; nothing blocks
        until the next token readback."""
        kvm = self.kvm
        if kvm.pool.n_host == 0 or not self.active:
            return
        slots = {r.slot for r in self.active.values()}
        residents = [s for s in slots if kvm.is_resident(s)]
        pending = sorted((s for s in slots if not kvm.is_resident(s)),
                         key=lambda s: self._pending_since.get(s, 0))
        moved_now: set = set()

        # all quantities are per-channel vectors ([total] at channels=1,
        # where every comparison reduces to the old scalar one): a
        # reserve that fits in aggregate can still dry out one channel
        def growth_total(slots):
            return sum((self._growth_need_ch(s) for s in slots),
                       np.zeros(self.channels, np.int64))

        def live():     # quarantine (mid-pass) shrinks the active set
            return {r.slot for r in self.active.values()}

        def can_resume(s):
            # a swap-in pays its one-time cost (the lane's host pages)
            # in REAL free blocks; only the ongoing growth reserve is
            # judged by the stall-shrunk budget. Dividing the whole
            # budget would count each host page `stall` times over and
            # let a strong brownout wall off re-admission entirely —
            # starving big lanes into watchdog restarts. The brownout
            # should shrink residency and growth, not re-admission.
            hp = kvm.host_pages_vec(s)
            fr = kvm.free_device_vec()
            if (hp > fr).any():
                return False
            return bool((self._stall_shrink(fr - hp)
                         >= total + self._growth_need_ch(s)).all())

        # stall-degraded budget: a browned-out channel advertises fewer
        # free blocks, so the reserve swaps residency away from it and
        # admission/growth shrink there (graceful degradation)
        free = self._free_eff
        # 1. reserve: the scan must never run any channel's pool dry.
        # Backed-off slots are not victims (their swap just failed);
        # a failed swap-out that QUARANTINED its victim freed the pages
        # outright, which serves the reserve just as well.
        total = growth_total(residents)
        while (total > free()).any() and len(residents) > 1:
            cands = [s for s in residents if not self._backed_off(s)]
            if not cands:
                break
            victim = max(cands, key=lambda s: int(self.ctx_lens[s]))
            if not self._swap_out_slot(victim):
                if victim not in live():
                    residents.remove(victim)
                    total = growth_total(residents)
                    continue
                if self._backed_off(victim):
                    continue    # SwapFault: excluded next iteration
                break           # host tier full: no pass can progress
            moved_now.add(victim)
            residents.remove(victim)
            pending.append(victim)
            total = growth_total(residents)
        # 2. resume FIFO while the reserve still holds
        for s in list(pending):
            if s in moved_now or self._backed_off(s):
                continue               # no ping-pong within one boundary
            if can_resume(s):
                if self._swap_in_slot(s):
                    moved_now.add(s)
                    pending.remove(s)
                    residents.append(s)
                    total += self._growth_need_ch(s)
                elif s not in live():
                    pending.remove(s)  # failed swap-in quarantined it
        # 3. aging rotation: the oldest pending slot forces its way in
        rest = [s for s in pending
                if s not in moved_now and not self._backed_off(s)
                and s in live()]
        if rest:
            oldest = rest[0]
            waited = self._boundary - self._pending_since.get(
                oldest, self._boundary)
            if waited >= self.swap_patience:
                while not can_resume(oldest) and len(residents) > 1:
                    cands = [s for s in residents if s not in moved_now
                             and not self._backed_off(s)]
                    if not cands:
                        break
                    victim = min(cands, key=lambda s:
                                 self._resident_since.get(s, 0))
                    if not self._swap_out_slot(victim):
                        if victim not in live():
                            residents.remove(victim)
                            total = growth_total(residents)
                            continue
                        break
                    residents.remove(victim)
                    total = growth_total(residents)
                if can_resume(oldest):
                    self._swap_in_slot(oldest)

    # ------------------------------------------------------------- prefill
    def _prefill_fn(self, params, batch, caches, table_row, slot):
        logits, cols = self.m.prefill(params, batch)
        caches = _scatter_prefill(self.cfg, self.rt, caches, cols,
                                  table_row, slot)
        return logits, caches

    def _do_prefill(self, req: Request, n_chunk: Optional[int] = None):
        """Prefill the first ``n_chunk`` prompt tokens (default: all).
        A partial chunk leaves the rest on ``req.pending_prompt`` to
        stream through the decode path as forced tokens; its boundary
        prediction is discarded (the true next token is known)."""
        n_chunk = len(req.tokens) if n_chunk is None else n_chunk
        self.metrics["prefill_tokens"] += n_chunk
        toks = jnp.asarray(req.tokens[:n_chunk], jnp.int32)[None]
        batch = {"tokens": toks}
        if req.prefix_emb is not None:
            batch["prefix_emb"] = req.prefix_emb[None]
        if req.src_emb is not None:
            batch["src_emb"] = req.src_emb[None]
            batch["src_valid"] = jnp.ones(req.src_emb.shape[:1], jnp.int32)[None]
        row = self.kvm.block_tables()[req.slot]   # device slice, no sync
        logits, self.caches = self._prefill(self.params, batch, self.caches,
                                            row, req.slot)
        n_ctx = n_chunk + (req.prefix_emb.shape[0]
                           if req.prefix_emb is not None else 0)
        self.ctx_lens[req.slot] = n_ctx
        if req.src_emb is not None:
            self.src_lens[req.slot] = req.src_emb.shape[0]
        if n_chunk < len(req.tokens):
            req.pending_prompt = list(req.tokens[n_chunk:])
            self.metrics["chunked_prefills"] += 1
        else:
            self._register_prompt(req)
            tok = int(jnp.argmax(logits[0]))
            req.out.append(tok)
            self.metrics["generated"] += 1
        self.metrics["prefills"] += 1

    # ------------------------------------------------------------- decode
    def _page_bucket(self, n_need: int) -> int:
        """Smallest power-of-2 page count >= n_need (>= min_page_bucket,
        <= max_pages): the static live-page width attention runs over.
        Raise ``min_page_bucket`` to pre-pin the bucket for an expected
        context length — every bucket crossing re-traces the decode
        jits, so latency-sensitive runs pay compilation up front."""
        p = self.min_page_bucket
        while p < n_need and p < self.max_pages:
            p *= 2
        return min(p, self.max_pages)

    def _table_grid(self, table, pages):
        """Flat (or [C, L] channel-sharded) incremental table ->
        [n_slots, <=pages] global grid: ``fb.interleave_table`` (the
        one home of the shard-interleave layout — under a mesh the
        transpose IS the boundary all-gather of the tentpole) plus the
        live-page bucket slice. Every decode path (_decode_fn,
        _macro_fn, _macro_sharded_fn) must read the table through here
        or bit-identity across paths breaks."""
        n = self.n_slots * self.max_pages    # table is geometry-padded
        grid = fb.interleave_table(table, n).reshape(self.n_slots,
                                                     self.max_pages)
        return grid[:, :pages or self.max_pages]

    def _mask_tables(self, grid, live):
        """Mask dead lanes to the scratch block (their garbage KV write
        lands there) and clamp out-of-range entries (NIL / host-tier
        tags) — the ONE shared clamp; see _table_grid."""
        t = jnp.where(live[:, None], grid, self.scratch_block)
        return jnp.where((t < 0) | (t >= self.scratch_block),
                         self.scratch_block, t)

    def _decode_fn(self, params, tokens, caches, ctx_lens, table,
                   resident_mask, src_valid=None, pages=None):
        """Single-fused serving map step: the flat device-resident table
        is reshaped and sliced to the live-page bucket (attention never
        touches pages beyond any mapped context), paused/inactive slots
        are masked to the scratch block with zeroed ctx, and
        out-of-range entries (NIL / host-tier tags) are clamped — all
        inside the decode jit, so no table bytes cross the host."""
        tables = self._mask_tables(self._table_grid(table, pages),
                                   resident_mask)
        ctx = jnp.where(resident_mask, ctx_lens, 0)
        logits, caches = self.m.decode_step(
            params, tokens, caches, ctx_lens=ctx, block_table=tables,
            src_valid=src_valid)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    def _grow_pages(self, residents) -> List[Request]:
        """Allocate pages for every resident crossing a page boundary:
        one batched allocation + one fused map call on the fast path.
        Returns the residents that may decode this step: preemption on
        the OutOfBlocks slow path may swap some out mid-step, and a
        slot whose growth failed outright PAUSES (decoding it with the
        new page unmapped would silently write its KV into the shared
        scratch block); it retries every step until blocks free up."""
        wants: Dict[int, int] = {}
        for r in residents:
            need = -(-int(self.ctx_lens[r.slot] + 1) // self.page)
            have = len(self.kvm.seq_pages[r.slot])
            if need > have and have < self.max_pages:
                wants[r.slot] = need - have
        if not wants:
            return residents
        try:
            self.kvm.extend_seqs(wants)
            return residents
        except OutOfBlocks:
            pass
        # slow path: grow slot-by-slot, preempting victims to host
        failed = set()
        transient = False
        for slot, n in wants.items():
            if slot not in self.kvm.seq_pages \
                    or not self.kvm.is_resident(slot):
                # became a preemption victim this step — or was
                # QUARANTINED mid-loop (a failed preempt swap can
                # quarantine any slot, including this one): its pages
                # are already freed and the request requeued
                continue
            try:
                self.kvm.extend_seq(slot, n)
            except OutOfBlocks as e:
                transient |= getattr(e, "transient", False)
                if not self._preempt(exclude=slot):
                    failed.add(slot)
                    continue
                try:
                    self.kvm.extend_seq(slot, n)
                except OutOfBlocks as e:
                    transient |= getattr(e, "transient", False)
                    failed.add(slot)
        if len(failed) == len(residents) and not transient:
            # nothing extended, nothing swapped: the same state recurs
            # next step, so pausing would livelock instead of degrade.
            # An INJECTED transient exhaustion is exempt — its schedule
            # advances every consult, so retrying next step is progress,
            # not the same state (PoolExhausted.transient, ISSUE 6)
            raise OutOfBlocks(
                f"pool exhausted: all {len(residents)} resident "
                "sequences need pages and none can be grown or "
                "preempted (no host tier / no victim)")
        # r.rid in active: a request quarantined during the loop holds a
        # freed slot — decoding it would write KV through a NIL mapping
        return [r for r in residents
                if r.slot not in failed and r.rid in self.active
                and self.kvm.is_resident(r.slot)]

    def _decode_step(self, done: Dict[int, List[int]]):
        self._ensure_resident()
        residents = [r for r in self.active.values()
                     if self.kvm.is_resident(r.slot)]
        if not residents:
            return
        residents = self._grow_pages(residents)
        if not residents:
            return
        tokens = np.zeros(self.n_slots, np.int32)
        resident_mask = np.zeros(self.n_slots, bool)
        for r in residents:
            tokens[r.slot] = (r.pending_prompt[0] if r.pending_prompt
                              else r.out[-1] if r.out else r.tokens[-1])
            resident_mask[r.slot] = True
        src_valid = self._src_valid()
        # numpy args go straight to the jit (its shard_args transfer is
        # cheaper than an explicit device_put per array); the only
        # per-step host sync is the next_tok readback
        pages = self._page_bucket(max(
            len(self.kvm.seq_pages[r.slot]) for r in residents))
        next_tok, self.caches = self._decode(
            self.params, tokens, self.caches, self.ctx_lens,
            self.kvm.state.table, resident_mask, src_valid, pages)
        self._finish_step(residents, np.asarray(next_tok), done)

    # ------------------------------------------------------ macro-steps
    def _macro_fn(self, params, ms, caches, cur_tok, ctx_lens, n_pages,
                  alive, budget, forced, src_valid=None, pages=None,
                  simple=False):
        """K fused decode steps under ONE jit (lax.scan): per step, page
        -boundary detection -> device-side block alloc + fused map
        commit (fb.serving_grow) -> masked decode -> greedy sample ->
        retire slots that hit EOS or their max_new budget. Lane masking
        matches _decode_fn exactly (scratch block, zeroed ctx, zeroed
        token) so a scan step is bit-identical to a single step.

        The alloc + translate commit runs under a lax.cond that only
        fires on steps where some lane crosses a page boundary — steady
        steps pay a bare decode plus a few fused elementwise ops, which
        is what makes K-step fusion pay on a CPU where per-op overhead
        dominates tiny graphs.

        ``simple`` (static) additionally drops the per-step retirement
        machinery: the caller guarantees no lane can finish mid-scan
        (eos_id < 0 and every budget covers the scan's emitted
        tokens), so the live set is the input ``alive`` for the whole
        scan and the masked block table only changes on growth steps
        (it rides the carry between refreshes).

        ``forced`` = (fmask [K,S], ftok [K,S], emit [K,S]): chunked
        admission streams the un-prefilled remainder of a prompt
        through the scan — where fmask, the step consumes ftok (the
        known prompt token) instead of the carried sample, and only
        steps with emit count against the max_new budget / EOS
        retirement (predictions inside the prompt are discarded by the
        host). ``forced=None`` (a separate trace, like simple/full) is
        the steady state — no lane mid-prompt — and adds ZERO ops and
        ZERO transfers to the scan: the macro hot path pays nothing
        for the admission machinery.

        The input ``alive`` mask is intersected with the device's own
        ``ms.swap_pending`` residency lane: a slot whose pages sit in
        (or are moving to) the host tier is a paused lane for the
        whole scan — every other slot keeps decoding, which is what
        makes swaps overlap decode instead of gating it.

        Returns (ms, caches, toks [K,S], oob). In full mode toks is
        NIL on lanes that emitted nothing (retired/paused); in simple
        mode dead-lane columns are garbage and the host masks them
        with its own alive vector. Either way the host replays the
        deterministic allocation sequence from the validity mask (the
        allocator mirror makes device pops predictable, so no
        allocation log needs to leave the device)."""
        g = self.kvm.geom
        page = self.page
        i32 = jnp.int32
        slots = jnp.arange(self.n_slots, dtype=i32)

        def mask_tables(ms, live):
            # shared grid + clamp (bucket slice is static): attention
            # work scales with actual context, exactly like _decode_fn
            return self._mask_tables(self._table_grid(ms.table, pages),
                                     live)

        def grow_commit(ms, npg, grow):
            # pop from the device free stack + commit dlpn->block in
            # one fused translate (single-probe invariant kept)
            dl_new = slots * self.max_pages + npg
            ms, _, ok = fb.serving_grow(g, ms, grow, dl_new)
            return ms, ok

        if simple:
            # n_pages/budget repurposed: the host precomputes the whole
            # growth schedule (it already replays the identical
            # arithmetic at the boundary) — n_pages is (grow_sched
            # [K,S] bool, grow_any [K] bool, dl_sched [K,S] int32) and
            # the scan body needs zero boundary-detection ops
            grow_sched, grow_any, dl_sched = n_pages
            xs = (grow_sched, grow_any, dl_sched)
            if forced is not None:
                xs += forced[:2]            # (fmask, ftok); emit unused
            # swap-pending slots are paused lanes for the whole scan
            alive0 = alive & ~ms.swap_pending

            def body(carry, xs):
                ms, caches, tok, ctx, tables = carry
                if forced is None:
                    gs, ga, dl = xs
                else:
                    gs, ga, dl, fm, ft = xs
                    tok = jnp.where(fm & alive0, ft, tok)

                def do_grow(ms):
                    # no lane can fail here (the host's worst-case
                    # eligibility check covers the scan), but if one
                    # does, ms.oob is raised and the host recovers
                    ms, _, _ = fb.serving_grow(g, ms, gs, dl)
                    return ms, mask_tables(ms, alive0)

                ms, tables = jax.lax.cond(
                    ga, do_grow, lambda ms: (ms, tables), ms)
                logits, caches = self.m.decode_step(
                    params, tok, caches,
                    ctx_lens=jnp.where(alive0, ctx, 0),
                    block_table=tables, src_valid=src_valid)
                nxt = jnp.argmax(logits, axis=-1).astype(i32)
                return (ms, caches, jnp.where(alive0, nxt, 0),
                        ctx + alive0.astype(i32), tables), nxt

            carry = (ms, caches, jnp.where(alive0, cur_tok, 0), ctx_lens,
                     mask_tables(ms, alive0))
            carry, toks = jax.lax.scan(body, carry, xs,
                                       length=self.macro_k)
            return carry[0], carry[1], toks, carry[0].oob

        alive = alive & ~ms.swap_pending

        def body(carry, xs):
            ms, caches, tok, ctx, npg, alive, bud = carry
            if forced is None:
                em = True
            else:
                fm, ft, em = xs
                tok = jnp.where(fm & alive, ft, tok)
            need = (ctx + page) // page          # ceil((ctx+1)/page)
            grow = alive & (need > npg) & (npg < self.max_pages)

            def do_grow(args):
                ms, npg = args
                ms, ok = grow_commit(ms, npg, grow)
                # a lane that wanted a block and failed PAUSES (it must
                # not decode into the shared scratch block); the sticky
                # oob flag sends the host to the single-step fallback
                live = alive & ~(grow & ~ok)
                return ms, npg + ok.astype(i32), live

            def no_grow(args):
                ms, npg = args
                return ms, npg, alive

            ms, npg, live = jax.lax.cond(grow.any(), do_grow, no_grow,
                                         (ms, npg))
            # decode against the incremental table, masked exactly like
            # _decode_fn (scratch block, zeroed ctx, zeroed token)
            logits, caches = self.m.decode_step(
                params, jnp.where(live, tok, 0), caches,
                ctx_lens=jnp.where(live, ctx, 0),
                block_table=mask_tables(ms, live), src_valid=src_valid)
            nxt = jnp.argmax(logits, axis=-1).astype(i32)
            # advance + retire finished lanes (EOS / budget) with pause
            # semantics: frozen ctx, no growth, no tokens. Only steps
            # that EMIT (prediction past the prompt) spend budget or
            # can retire — forced prompt steps never finish a lane.
            tok = jnp.where(live, nxt, tok)
            ctx = ctx + live.astype(i32)
            emitted = live & em
            bud = bud - emitted.astype(i32)
            fin = emitted & ((nxt == self.eos_id) | (bud <= 0))
            alive = alive & ~fin
            return (ms, caches, tok, ctx, npg, alive, bud), \
                jnp.where(live, nxt, NIL)

        carry = (ms, caches, cur_tok, ctx_lens, n_pages, alive, budget)
        carry, toks = jax.lax.scan(body, carry, forced,
                                   length=self.macro_k)
        ms, caches = carry[0], carry[1]
        return ms, caches, toks, ms.oob

    def _macro_eligible(self) -> bool:
        """Macro-steps run only when the scan provably cannot need the
        host mid-flight: the device pool covers the worst-case K-step
        growth of every decoding lane (so the in-graph allocator
        cannot run dry — pool exhaustion falls back to the single-step
        path, whose preempt/pause machinery needs the host). Finishing
        mid-scan is fine (handled in-graph). Under ``nonblocking_swap``
        a non-resident slot is NOT a fallback: it is a swap-pending
        lane, masked in the scan while everyone else decodes (the
        boundary scheduler already reserved growth headroom for the
        residents); pre-ISSUE-4 behavior required every slot
        resident."""
        if not self._macro_on or not self.active:
            return False
        need = np.zeros(self.channels, np.int64)
        n_res = 0
        for r in self.active.values():
            if not self.kvm.is_resident(r.slot):
                if not self.nonblocking_swap:
                    return False
                continue        # swap-pending lane: masked, not a fallback
            n_res += 1
            need += self._growth_need_ch(r.slot)
        # per-channel fit: a dry channel is real pool pressure even
        # while other channels still hold blocks (channels=1 reduces to
        # the old total comparison). _free_eff folds in the fault
        # plane's brownout multipliers — a stalled channel's shrunken
        # budget pushes growth pressure to the swap scheduler instead
        return n_res > 0 and bool((need <= self._free_eff()).all())

    def _src_valid(self):
        if not self.cfg.n_enc_layers:
            return None
        return (np.arange(self.src_cap)[None, :]
                < self.src_lens[:, None]).astype(np.int32)

    def _macro_lanes(self, residents, K: int):
        """Lane arrays for one K-step scan (shared by the unsharded and
        channel-sharded macro steps): tokens/alive/budget/pages plus
        the forced-lane schedule for chunk-prefilled prompts."""
        tokens = np.zeros(self.n_slots, np.int32)
        alive = np.zeros(self.n_slots, bool)
        budget = np.zeros(self.n_slots, np.int32)
        npages = np.zeros(self.n_slots, np.int32)
        pend = np.zeros(self.n_slots, np.int32)
        fmask = np.zeros((K, self.n_slots), bool)
        ftok = np.zeros((K, self.n_slots), np.int32)
        emit = np.ones((K, self.n_slots), bool)
        slot2req: Dict[int, Request] = {}
        for r in residents:
            s = r.slot
            tokens[s] = (r.pending_prompt[0] if r.pending_prompt
                         else r.out[-1] if r.out else r.tokens[-1])
            alive[s] = True
            budget[s] = r.max_new - len(r.out)
            npages[s] = len(self.kvm.seq_pages[s])
            slot2req[s] = r
            # forced lanes: steps [0, P) consume known prompt tokens;
            # predictions before step P-1 are inside the prompt and
            # neither emit nor spend budget
            p = len(r.pending_prompt)
            pend[s] = p
            if p:
                chunk = r.pending_prompt[:K]
                fmask[:len(chunk), s] = True
                ftok[:len(chunk), s] = chunk
                emit[:min(p - 1, K), s] = False
        return (tokens, alive, budget, npages, pend, fmask, ftok, emit,
                slot2req)

    def _growth_walk(self, live_of_step, npages, ctx):
        """The mirror-protocol page-boundary walk: which slots pop a
        block at each of the K scan steps. ONE home for the arithmetic
        (`need = (ctx + page) // page; grow = live & (need > npg) &
        (npg < max_pages)`) — the C=1 simple scheduler, the full-mode
        reconcile replay, and the sharded pre-commit must pop
        bit-identically or the host/device allocator mirror breaks.
        ``live_of_step(k)`` -> [S] bool mask of lanes decoding at step
        k. Returns (grow [K,S] bool, dl [K,S] int32 — each slot's next
        unmapped dlpn at that step, npg_end [S])."""
        K, S = self.macro_k, self.n_slots
        grow = np.zeros((K, S), bool)
        dl = np.zeros((K, S), np.int32)
        base = np.arange(S, dtype=np.int32) * self.max_pages
        npg = npages.copy()
        ctx = ctx.copy()
        for k in range(K):
            live = live_of_step(k)
            need = (ctx + self.page) // self.page
            grow[k] = live & (need > npg) & (npg < self.max_pages)
            dl[k] = base + npg
            npg += grow[k]
            ctx += live
        return grow, dl, npg

    def _macro_book_simple(self, residents, toks, pend, K: int,
                           done: Dict[int, List[int]]):
        """Boundary bookkeeping for a simple-mode scan: every alive
        lane ran all K steps and none can have finished mid-scan (the
        budget covered the emitted tokens; budget == emitted retires
        here at the boundary). A forced lane discards predictions
        inside its prompt: its outputs start at scan step P-1."""
        self.metrics["decode_steps"] += K
        for r in residents:
            s = r.slot
            p = int(pend[s])
            if p:
                # forced lanes are prompt work riding the decode path:
                # count them into the prefill-FLOP proxy
                self.metrics["prefill_tokens"] += min(p, K)
                del r.pending_prompt[:min(p, K)]
                if not r.pending_prompt:
                    self._register_prompt(r)   # drained mid-scan
                outs = ([int(t) for t in toks[p - 1:, s]]
                        if p <= K else [])
            else:
                outs = [int(t) for t in toks[:, s]]
            r.out.extend(outs)
            self.metrics["generated"] += len(outs)
            self.ctx_lens[s] += K
            if len(r.out) >= r.max_new:
                done[r.rid] = r.out[:r.max_new]
                self._journal_finish(r)
                self.kvm.free_seq(s)
                self._release_slot(s)
                del self.active[r.rid]

    def _macro_book_full(self, valid, toks, slot2req,
                         done: Dict[int, List[int]]):
        """Boundary bookkeeping for a full-mode scan: replay the
        emitted tokens step by step (NIL lanes emitted nothing)."""
        for k in range(valid.shape[0]):
            if not valid[k].any():
                break                  # everyone retired: steps k.. idle
            stepped = [slot2req[s] for s in range(self.n_slots)
                       if valid[k, s]]
            self._finish_step(stepped, toks[k], done)

    def _macro_decode_step(self, done: Dict[int, List[int]]):
        """Launch one K-step fused scan, then do the boundary work:
        ONE host sync (token matrix + oob flag), allocator-delta
        replay, token bookkeeping, frees."""
        if self.channels > 1:
            return self._macro_decode_step_sharded(done)
        self.kvm.sync_allocator()      # no-op unless the pool mutated
        # swap-pending slots stay active but are NOT in the batch: they
        # are masked lanes until the boundary scheduler resumes them
        residents = [r for r in self.active.values()
                     if self.kvm.is_resident(r.slot)]
        K = self.macro_k
        (tokens, alive, budget, npages, pend, fmask, ftok, emit,
         slot2req) = self._macro_lanes(residents, K)
        # CTP (ISSUE 9): the boundary knows the next K-step growth
        # exactly (the same mirror-protocol walk the scheduler and the
        # reconcile replay run), so pull the backing-table segments
        # those dlpns live in into the CMT AHEAD of the scan's
        # in-graph UPDATE commits
        if self.gc is not None and self.gc.prefetch and residents:
            pgs, pdl, _ = self._growth_walk(lambda k: alive, npages,
                                            self.ctx_lens)
            if pgs.any():
                self.kvm.prefetch_segments(pdl[pgs])
        src_valid = self._src_valid()
        # the `simple` specialization applies when no lane can finish
        # mid-scan: without EOS the retirement machinery is dead weight
        # on every scan step. A forced lane only emits K - (P-1) tokens
        # during the scan, so its budget needs to cover just that.
        gen = K - np.maximum(pend - 1, 0)
        simple = self.eos_id < 0 and bool(
            (budget[alive] >= gen[alive]).all())
        if simple:
            # precompute the growth schedule the scan will follow (no
            # retirement ⟹ the live set is static ⟹ page crossings
            # are a pure function of ctx/pages the host already holds)
            grow_sched, dl_sched, npages = self._growth_walk(
                lambda k: alive, npages, self.ctx_lens)
            sched = (grow_sched, grow_sched.any(axis=1), dl_sched)
        # live-page bucket: worst-case pages any slot can hold by scan
        # end (exact post-schedule count in simple mode)
        if simple:
            pages = self._page_bucket(int(npages[alive].max()))
        else:
            end = np.minimum(
                self.max_pages,
                np.maximum(npages, (self.ctx_lens + self.macro_k
                                    + self.page - 1) // self.page))
            pages = self._page_bucket(int(end[alive].max()))
        MACRO_DISPATCHES[0] += 1
        # steady state (no lane mid-prompt) uses the forced=None trace:
        # the scan carries zero admission machinery
        forced = (fmask, ftok, emit) if pend.any() else None
        st, self.caches, toks, oob = (
            self._macro_simple(
                self.params, self.kvm.state, self.caches, tokens,
                self.ctx_lens, sched, alive, budget, forced, src_valid,
                pages)
            if simple else
            self._macro(
                self.params, self.kvm.state, self.caches, tokens,
                self.ctx_lens, npages, alive, budget, forced, src_valid,
                pages))
        self.kvm.state = st
        HOST_SYNCS[0] += 1
        toks, oob = jax.device_get((toks, oob))
        self.metrics["macro_steps"] += 1
        if simple:
            # np.nonzero on [K,S] is row-major == the scan's step-major
            # slot-ascending pop order
            grow_seq = [int(s) for s in np.nonzero(grow_sched)[1]]
        else:
            # NIL marks lanes that emitted nothing (retired/paused);
            # replay the scan's growth decisions (the same _growth_walk
            # arithmetic, gated on the scan's own live mask) to recover
            # the allocation sequence — the allocator mirror makes the
            # popped block ids predictable, so no log left the device
            valid = (toks >= 0) & alive[None, :]
            grew, _, npages = self._growth_walk(
                lambda k: valid[k], npages, self.ctx_lens)
            grow_seq = [int(s) for s in np.nonzero(grew)[1]]
        got = self.kvm.reconcile_macro(grow_seq)
        self._retire_macro_programs(grow_seq, got)
        if simple:
            self._macro_book_simple(residents, toks, pend, K, done)
        else:
            self._macro_book_full(valid, toks, slot2req, done)
        if oob:
            # the proactive check makes this unreachable without a
            # fault plane; fold the flag into the typed per-channel
            # exhaustion counts and mark the allocator dirty (the
            # re-sync clears the lane) — single-step mode recovers
            self.kvm.observe_exhaustion(flags=[oob])

    def _retire_macro_programs(self, grow_seq, got):
        """Program-fault check for in-scan growth (ISSUE 6): the scan
        already WROTE KV into the blocks it popped, so retiring a bad
        one must also move its rows — ``retire_bad_blocks(pools=...)``
        runs the CondUpdate relocation and the old->new row copy in one
        donated jit (a bad block is just another relocation, same as
        the swap pipeline). Plane consults follow device pop order
        (step-major, slot-ascending = grow_seq order), matching the
        order the pre-commit paths consult in."""
        kvm = self.kvm
        if not got or kvm.faults is None:
            return
        idx = {s: len(kvm.seq_pages[s]) - len(bs)
               for s, bs in got.items()}
        bad = []
        for s in grow_seq:
            j = idx[s]
            idx[s] = j + 1
            if kvm.faults.program_fails():
                bad.append((s * self.max_pages + j, kvm.seq_pages[s][j]))
        if not bad:
            return
        pools = [self.caches["pool_k"], self.caches["pool_v"]]
        pools, _ = kvm.retire_bad_blocks(bad, pools=pools, block_axis=2)
        self.caches["pool_k"], self.caches["pool_v"] = pools

    # -------------------------------------- channel-sharded macro-steps
    def _macro_sharded_fn(self, params, caches, table, cur_tok,
                          ctx_lens, alive, budget, forced,
                          src_valid=None, pages=None, simple=False):
        """K decode steps against a PRE-COMMITTED channel-sharded map
        (DESIGN.md "Channel-sharded map pipeline"): the boundary
        already popped every block the scan can need and committed the
        mappings through the sharded fused translate, so the scan
        consumes a read-only table — the [C, L] shard stack
        interleaves back to global dlpn order ONCE here (on a channel
        mesh that transpose lowers to the cross-channel all-gather;
        this is the tentpole's one boundary collective). Pages mapped
        ahead of a lane's current context are invisible to attention
        (it reads ctx_lens positions only), so a scan step stays
        bit-identical to a single step. Lane masking, forced lanes and
        EOS/budget retirement mirror ``_macro_fn`` exactly; there is
        no in-graph allocator and no oob flag — per-channel pool
        pressure was resolved by the eligibility check before
        dispatch."""
        i32 = jnp.int32
        tbl = self._table_grid(table, pages)    # interleave ONCE

        def mask_tables(live):
            return self._mask_tables(tbl, live)

        if simple:
            alive0 = alive
            tables = mask_tables(alive0)
            xs = forced[:2] if forced is not None else None

            def body(carry, xs):
                caches, tok, ctx = carry
                if forced is not None:
                    fm, ft = xs
                    tok = jnp.where(fm & alive0, ft, tok)
                logits, caches = self.m.decode_step(
                    params, tok, caches,
                    ctx_lens=jnp.where(alive0, ctx, 0),
                    block_table=tables, src_valid=src_valid)
                nxt = jnp.argmax(logits, axis=-1).astype(i32)
                return (caches, jnp.where(alive0, nxt, 0),
                        ctx + alive0.astype(i32)), nxt

            carry, toks = jax.lax.scan(
                body, (caches, jnp.where(alive0, cur_tok, 0), ctx_lens),
                xs, length=self.macro_k)
            return carry[0], toks

        def body(carry, xs):
            caches, tok, ctx, alive, bud = carry
            if forced is None:
                em = True
            else:
                fm, ft, em = xs
                tok = jnp.where(fm & alive, ft, tok)
            live = alive
            logits, caches = self.m.decode_step(
                params, jnp.where(live, tok, 0), caches,
                ctx_lens=jnp.where(live, ctx, 0),
                block_table=mask_tables(live), src_valid=src_valid)
            nxt = jnp.argmax(logits, axis=-1).astype(i32)
            tok = jnp.where(live, nxt, tok)
            ctx = ctx + live.astype(i32)
            emitted = live & em
            bud = bud - emitted.astype(i32)
            fin = emitted & ((nxt == self.eos_id) | (bud <= 0))
            alive = alive & ~fin
            return (caches, tok, ctx, alive, bud), \
                jnp.where(live, nxt, NIL)

        carry, toks = jax.lax.scan(
            body, (caches, cur_tok, ctx_lens, alive, budget), forced,
            length=self.macro_k)
        return carry[0], toks

    def _macro_decode_step_sharded(self, done: Dict[int, List[int]]):
        """Channel-sharded boundary step: commit the scan's WORST-CASE
        growth schedule ahead of time — one channel-aware pool
        allocation in the scan's pop order (step-major,
        slot-ascending: exactly what K single steps would pop) + ONE
        fused sharded map dispatch (``KVPageManager.precommit_growth``)
        — then run the pure-decode K-step scan and the usual token
        bookkeeping. Per K tokens: 1 MACRO_DISPATCHES, 1 HOST_SYNCS,
        at most 1 XLATE_CALLS (growth boundaries only), 0 ALLOC_SYNCS
        (the device free stacks are not consumed in-graph; they lazily
        mirror for tests). A lane that retires mid-scan (full mode)
        keeps its pre-committed pages until the slot frees — the pool
        order then differs from the single-step schedule, which is the
        one sharding-vs-single divergence (tokens never differ)."""
        residents = [r for r in self.active.values()
                     if self.kvm.is_resident(r.slot)]
        K = self.macro_k
        (tokens, alive, budget, npages, pend, fmask, ftok, emit,
         slot2req) = self._macro_lanes(residents, K)
        # worst-case growth schedule, no-retirement arithmetic — the
        # same _growth_walk the C=1 simple scheduler and the reconcile
        # replay use (mirror protocol, one home); the walk's own dl
        # schedule rides along so pre-commit maps exactly those pages
        grow_sched, dl_walk, npg = self._growth_walk(
            lambda k: alive, npages, self.ctx_lens)
        grow_seq = [int(s) for s in np.nonzero(grow_sched)[1]]
        # CTP (ISSUE 9): warm the CMT with the backing segments the
        # pre-commit's own UPDATE batch is about to touch — the walk's
        # dl schedule IS the exact dlpn set, no prediction needed
        if self.gc is not None and self.gc.prefetch \
                and grow_sched.any():
            self.kvm.prefetch_segments(dl_walk[grow_sched])
        try:
            self.kvm.precommit_growth(
                grow_seq, dlpns=[int(d) for d in dl_walk[grow_sched]])
        except OutOfBlocks:
            # precommit raises BEFORE any pop or map write, so nothing
            # needs unwinding: an injected transient exhaustion (or a
            # pool raced dry between eligibility and here) falls back
            # to one single step; the macro path retries next boundary
            self.metrics["macro_fallbacks"] += 1
            self._decode_step(done)
            return
        src_valid = self._src_valid()
        gen = K - np.maximum(pend - 1, 0)
        simple = self.eos_id < 0 and bool(
            (budget[alive] >= gen[alive]).all())
        pages = self._page_bucket(int(npg[alive].max()))
        MACRO_DISPATCHES[0] += 1
        forced = (fmask, ftok, emit) if pend.any() else None
        if simple:
            self.caches, toks = self._macro_sh_simple(
                self.params, self.caches, self.kvm.state.table, tokens,
                self.ctx_lens, alive, budget, forced, src_valid, pages)
        else:
            self.caches, toks = self._macro_sh(
                self.params, self.caches, self.kvm.state.table, tokens,
                self.ctx_lens, alive, budget, forced, src_valid, pages)
        HOST_SYNCS[0] += 1
        toks = jax.device_get(toks)
        self.metrics["macro_steps"] += 1
        if simple:
            self._macro_book_simple(residents, toks, pend, K, done)
        else:
            valid = (toks >= 0) & alive[None, :]
            self._macro_book_full(valid, toks, slot2req, done)

    def _finish_step(self, residents, next_tok: np.ndarray,
                     done: Dict[int, List[int]]):
        self.metrics["decode_steps"] += 1
        for r in list(residents):
            self.ctx_lens[r.slot] += 1
            if r.pending_prompt:
                # forced lane: the step consumed a known prompt token;
                # its prediction only counts once the prompt is done
                self.metrics["prefill_tokens"] += 1
                r.pending_prompt.pop(0)
                if r.pending_prompt:
                    continue
                self._register_prompt(r)   # prompt drained this step
            tok = int(next_tok[r.slot])
            r.out.append(tok)
            self.metrics["generated"] += 1
            if len(r.out) >= r.max_new or tok == self.eos_id:
                done[r.rid] = r.out[:r.max_new]
                self._journal_finish(r)
                self.kvm.free_seq(r.slot)
                self._release_slot(r.slot)
                del self.active[r.rid]


# ----------------------------------------------------------------------
def _scatter_prefill(cfg: ArchConfig, rt: Runtime, caches, cols, table_row,
                     slot):
    """Write one request's prefill caches (B=1) into the slot grid.
    cols: per-period list of dicts with leaves stacked [NP, ...]."""
    period = cfg.period
    attn_js = [j for j in range(period) if cfg.layer_kind(j) == "attn"]
    ssm_js = [j for j in range(period) if cfg.layer_kind(j) == "mamba"]
    a_of = {j: i for i, j in enumerate(attn_js)}
    s_of = {j: i for i, j in enumerate(ssm_js)}
    page = rt.page_size
    caches = dict(caches)
    for j in range(period):
        col = cols[j]
        if "kv" in col:
            k, v = col["kv"]                  # [NP, 1, S, KV, hd]
            np_, _, s, kvh, hd = k.shape
            npages = -(-s // page)
            pad = npages * page - s
            kp = jnp.pad(k[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
            vp = jnp.pad(v[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
            kp = kp.reshape(np_, npages, page, kvh * hd)
            vp = vp.reshape(np_, npages, page, kvh * hd)
            rows = table_row[:npages]
            ai = a_of[j]
            # scatter: pool [NP, A, NB, P, KV*hd]
            caches["pool_k"] = caches["pool_k"].at[:, ai, rows].set(
                kp.astype(caches["pool_k"].dtype), mode="drop")
            caches["pool_v"] = caches["pool_v"].at[:, ai, rows].set(
                vp.astype(caches["pool_v"].dtype), mode="drop")
        if "ssm" in col:
            conv, ssm_st = col["ssm"]         # [NP,1,k,C], [NP,1,nh,hd,N]
            si = s_of[j]
            caches["conv"] = caches["conv"].at[:, si, slot].set(
                conv[:, 0].astype(caches["conv"].dtype))
            caches["ssm"] = caches["ssm"].at[:, si, slot].set(ssm_st[:, 0])
        if "cross_kv" in col:
            ck, cv = col["cross_kv"]          # [NP,1,Ss,KV,hd]
            cap = caches["cross_k"].shape[3]
            pad = cap - ck.shape[2]
            ckp = jnp.pad(ck[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
            cvp = jnp.pad(cv[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
            caches["cross_k"] = caches["cross_k"].at[:, j, slot].set(
                ckp.astype(caches["cross_k"].dtype))
            caches["cross_v"] = caches["cross_v"].at[:, j, slot].set(
                cvp.astype(caches["cross_v"].dtype))
    return caches
