"""KV page manager: the FMMU integrated as the serving page-table engine.

Logical address: DLPN = slot * max_pages + logical_page (slot = batch
slot of a live sequence). Physical: tier-tagged block id in the KV pool.
The mapping lives in the batched FMMU (core/fmmu/batch): lookups build
the block tables consumed by the paged-attention kernels; updates back
new allocations; CondUpdate arbitrates swap/relocation races exactly as
the paper's GC path does (a relocation only commits if the mapping still
points at the old block).

Every map operation funnels through ONE fused entry point
(``_xlate`` -> ``translate_serving`` -> ``translate_batch``): a single
CMT probe and a single insert pass per call, mirroring the paper's
arbiter that multiplexes all request sources through one shared
pipeline. All jitted closures donate the FMMU state pytree, so
steady-state serving performs zero state copies.

The block table is a **device-resident member of the state pytree**,
maintained incrementally by the same fused call that commits each map
write (DESIGN.md "Device-resident incremental block table"):
``block_tables()`` is a zero-cost accessor — no translation, no state
mutation — and steady-state decode performs zero full-map
retranslations. The from-scratch path survives as
``retranslate_tables()`` (test oracle / legacy benchmark baseline
only). NOTE: because the state pytree is donated, arrays returned by
``block_tables()`` are invalidated by the next map op — re-fetch
instead of holding them across ``new_seq``/``extend``/``free``/swaps.

Data movement between tiers is ONE donated jitted call per swap
(``_swap``): the CondUpdate map commits ride the single-probe fused
translate, the pool rows move by gather/scatter, and the
``ServingMapState.swap_pending`` residency lane flips — state and both
KV pools are donated, so a swap mutates in place and the host never
blocks on it (the guard-mask readback is opt-in via ``check=True``;
the serving scheduler leaves it off and lets the equivalence tests own
correctness). Swap lane counts are padded to the next power of two so
the jit re-traces O(log max_pages) times, not once per distinct swap
size. DESIGN.md "Non-blocking host-tier swap pipeline".

ISSUE-5 channel sharding: ``channels=N`` partitions the whole map
state by the static hash ``channel(dlpn) = dlpn mod N`` — each channel
holds a complete 1/N-sized ServingMapState shard (CMT, backing, table
slice, the free stacks of the blocks it owns: block ``b`` belongs to
channel ``b mod N``) stacked on a leading [C] pytree axis, and every
fused entry above runs as ONE sharded translate (shard_map over a
'channel' mesh when >= C devices are visible, else a bit-identical
jax.vmap). The pool free lists stripe per channel the same way
(``BlockPool(n_channels=N)``), macro-scan growth is pre-committed at
the boundary (``precommit_growth``) so the scan needs no in-graph
allocator, and ``block_tables()`` interleaves the shards back to
global order (the boundary all-gather). DESIGN.md "Channel-sharded
map pipeline". ``channels=1`` (default) bypasses every sharded branch.

ISSUE-3 allocator mirror: the FMMU serving state carries a
device-resident free-list allocator (decode macro-steps allocate KV
blocks without leaving the jit). The host ``BlockPool`` stays
authoritative at macro-step boundaries: host-side mutations mark the
device stacks dirty (lazily re-pushed by ``sync_allocator``), and
device-side pops are replayed onto the pool by ``reconcile_macro`` —
both sides apply identical deltas in identical order, so steady-state
decode needs zero sync pushes (DESIGN.md "Device-resident block
allocator + K-step fused decode macro-steps").
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as flt
from repro.core import journal as jl
from repro.core.counters import COUNTERS
from repro.core.fmmu import batch as fb
from repro.core.fmmu.types import (COND_UPDATE, FMMUGeometry, LOOKUP,
                                   NIL, SWAP_IN, SWAP_OUT, UPDATE)
from repro.paging.pool import (HOST_BASE, BlockPool, OutOfBlocks,
                               PoolExhausted)

# Host-level call counters (the PROBE_TRACES pattern, at op granularity):
# bumped once per *invocation*, so tests can assert that a steady-state
# decode step performs zero full-map retranslations and at most one
# fused map call — and that a steady-state MACRO step performs zero of
# either plus zero allocator re-syncs. The names alias registry cells
# (core/counters.py): same list objects, also visible to
# COUNTERS.snapshot()/delta().
XLATE_CALLS = COUNTERS.cell("kvm.xlate_calls")
FULL_TABLE_CALLS = COUNTERS.cell("kvm.full_table_calls")
ALLOC_SYNCS = COUNTERS.cell("kvm.alloc_syncs")

def _ji(xs) -> List[int]:
    """Journal payloads are JSON: plain ints, not numpy scalars."""
    return [int(x) for x in xs]


@dataclasses.dataclass
class MapStats:
    """Typed ``KVPageManager.hit_stats()`` result (ISSUE 9): every
    historical dict key is a field, ``__getitem__`` keeps the legacy
    ``stats["hits"]`` call sites working verbatim, and ``as_dict()``
    feeds the bench schema. New GC/CTP axes: ``gc_moves`` (live pages
    relocated by the victim walk), ``victims_ch`` (erase blocks fully
    reclaimed, per channel), ``prefetch_hits``/``prefetch_misses`` (CTP
    probes that found the map segment already cached vs. pulled it —
    a prefetch MISS is the useful case), and the write-amplification
    axis: ``host_writes`` (fresh page programs commanded by the host:
    admission, decode growth, macro pre-commits), ``flash_programs``
    (host writes + swap-ins + GC relocations — every device-tier
    program), ``write_amp`` = flash_programs / host_writes (>= 1.0
    whenever anything was written)."""
    hits: int = 0
    misses: int = 0
    fills: int = 0
    updates: int = 0
    swaps_out: int = 0
    swaps_in: int = 0
    host_resident_slots: int = 0
    retired_blocks: int = 0
    retired_ch: List[int] = dataclasses.field(default_factory=list)
    pool_exhausted: List[int] = dataclasses.field(default_factory=list)
    swap_faults: int = 0
    program_faults: int = 0
    alloc_faults: int = 0
    gc_moves: int = 0
    victims_ch: List[int] = dataclasses.field(default_factory=list)
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    host_writes: int = 0
    flash_programs: int = 0
    write_amp: float = 1.0
    shared_maps: int = 0
    cow_moves: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __getitem__(self, key: str):
        if not any(f.name == key for f in dataclasses.fields(self)):
            raise KeyError(key)
        return getattr(self, key)

    def __contains__(self, key: str) -> bool:
        return any(f.name == key for f in dataclasses.fields(self))


# bad-block re-drive bound: a retirement chain retires at most this
# many consecutive schedule-failed replacement candidates before the
# last candidate is kept regardless (bounded recovery — no infinite
# retirement cascade can stall a boundary)
_MAX_REDRIVE = 4


def _move_rows(pool, src, dst, axis: int):
    """pool[..., dst, ...] = pool[..., src, ...] along `axis`."""
    taken = jnp.take(pool, src, axis=axis)
    pm = jnp.moveaxis(pool, axis, 0)
    pm = pm.at[dst].set(jnp.moveaxis(taken, axis, 0))
    return jnp.moveaxis(pm, 0, axis)


def _geometry(n_slots: int, max_pages: int,
              channels: int = 1) -> FMMUGeometry:
    """Map geometry sized for one channel's shard: with C channels each
    shard owns ceil(n_dlpns / C) logical pages, so its CMT and backing
    table are 1/C-sized — the paper's per-channel FMMU partitioning
    (translate work per channel scales as 1/N)."""
    n_dlpns = -(-n_slots * max_pages // channels)
    ept = max(64, min(4096, max_pages))
    return FMMUGeometry(
        cmt_sets=max(8, min(512, n_dlpns // 64)),
        cmt_ways=4,
        cmt_entries=8,
        ctp_sets=8, ctp_ways=4,
        entries_per_tp=ept,
        n_tvpns=-(-n_dlpns // ept),
        queue_cap=64,
    )


class KVPageManager:
    """Host-driven control plane; device-resident map + pools."""

    def __init__(self, n_slots: int, max_pages: int, n_device_blocks: int,
                 n_host_blocks: int = 0, channels: int = 1,
                 use_mesh: Optional[bool] = None,
                 faults: Optional["flt.FaultPlane"] = None,
                 track_live: bool = False,
                 track_refs: bool = False):
        self.n_slots = n_slots
        self.max_pages = max_pages
        self._n_dev = n_device_blocks
        self._n_host = n_host_blocks
        self.channels = C = int(channels)
        # GC live-page tracking (ISSUE 9): when enabled the map state
        # carries the optional ``live`` lane (maintained inside every
        # fused commit — core/fmmu/batch.translate_serving). Off by
        # default: the lane is a None pytree leaf and every traced
        # graph stays jaxpr-identical to the pre-GC path.
        self.track_live = bool(track_live)
        # Prefix-sharing refcount tracking (ISSUE 10): same optional-
        # leaf discipline as the live lane — off by default, and when
        # armed the ``refcnt`` lane rides the identical fused commits.
        # With C > 1, sharing requires max_pages % C == 0 so that the
        # SAME page index of different slots stripes to the same
        # channel (dlpn = slot*max_pages + page, channel = dlpn mod C):
        # a shared block and every dlpn mapping it then live in one
        # channel, preserving the pool/alloc channel invariant.
        self.track_refs = bool(track_refs)
        if self.track_refs and C > 1:
            assert max_pages % C == 0, \
                (f"prefix sharing with {C} channels needs "
                 f"max_pages % channels == 0 (got {max_pages})")
        self.geom = _geometry(n_slots, max_pages, C)
        self.fns = fb.make_jitted(self.geom)
        # fault-injection plane (ISSUE 6, core/faults.py): consulted at
        # host commit points only — swap dispatch (_swap), pool
        # allocation (_alloc_blocks), and fresh-block program commits
        # (new_seq / extend_seqs / precommit_growth). None (default)
        # costs nothing and, because the plane never enters a traced
        # graph, attaching one cannot change any jaxpr either.
        self.faults = faults
        # crash-consistency journal (ISSUE 7, core/journal.py): when
        # attached (ServeEngine.attach_journal), every host commit
        # point above appends a sequence-numbered record AFTER its op
        # succeeds — the same ``is not None`` host-only discipline as
        # the fault plane, so journaling-disabled stays jaxpr-identical
        self.journal: Optional["jl.Journal"] = None
        # ISSUE-5 channel sharding: with channels > 1 the map state is C
        # per-channel ServingMapState shards stacked on a leading axis
        # (each shard: 1/C-sized CMT + backing + table slice + the free
        # stacks of the blocks its channel owns). Requests route by the
        # static hash owner(dlpn) = dlpn mod C; every fused map call
        # goes through ONE sharded translate (each channel keeps the
        # single-probe/single-sort contract locally). The lowering is
        # shard_map over a 'channel' mesh axis when the process has >= C
        # devices (use_mesh=None auto-detects; CI's tier1-sharded lane
        # forces 8 host devices), else jax.vmap — both bit-identical.
        self.mesh = None
        if C > 1:
            if use_mesh is None:
                use_mesh = len(jax.devices()) >= C
            if use_mesh:
                from jax.sharding import PartitionSpec as P

                from repro.parallel.sharding import channel_mesh, shard_map
                self.mesh = channel_mesh(C)
                self._xlate_graph = shard_map(
                    fb.make_sharded_shard_body(self.geom, C),
                    mesh=self.mesh,
                    in_specs=(P("channel"), P(), P(), P(), P()),
                    out_specs=(P("channel"), P(), P()))
            else:
                self._xlate_graph = functools.partial(
                    fb.translate_sharded, self.geom, C)
            self._serve_sharded = jax.jit(self._xlate_graph,
                                          donate_argnums=(0,))
            # per-channel routed-lane counters: the 1/N-translate-work
            # claim is asserted from these, not inferred from timings
            self.channel_lanes = np.zeros(C, np.int64)
        else:
            self.channel_lanes = np.zeros(1, np.int64)
        self.state = self._fresh_state()
        self.pool = BlockPool(n_device_blocks, n_host_blocks,
                              n_channels=C)
        self.seq_pages: Dict[int, List[int]] = {}   # slot -> block ids
        # host-tier page count per slot, maintained by the swap ops so
        # the per-step residency predicate is O(1), not a page-list scan
        self._host_pages: Dict[int, int] = {}
        # device-allocator mirror protocol: the host BlockPool is
        # authoritative at macro-step boundaries; any host-side pool
        # mutation (admission alloc, free, swap) marks the device
        # stacks stale and sync_allocator() re-pushes them before the
        # next macro-step. Macro-step pops are reconciled the other way
        # (reconcile_macro replays them onto the pool) WITHOUT dirtying
        # — both sides applied the same delta, so the mirror holds and
        # steady-state decode needs zero sync pushes.
        self._alloc_dirty = False
        if C > 1:
            self._retrans_fn = jax.jit(
                functools.partial(self._retranslate_sharded, self.geom,
                                  C, n_slots, max_pages, self.mesh),
                donate_argnums=(0,))
            self._set_alloc = jax.jit(fb.set_allocator_sharded,
                                      donate_argnums=(0,))
        else:
            self._retrans_fn = jax.jit(
                functools.partial(self._retranslate, self.geom),
                static_argnums=(1, 2), donate_argnums=(0,))
            self._set_alloc = jax.jit(fb.set_allocator,
                                      donate_argnums=(0,))
        # fused swap jits, cached per (padded lane count, block axis,
        # pool count): state + pools donated, re-traced O(log) times.
        # swap_pad (optional) pins a fixed lane count instead of the
        # next-pow2 policy: every swap then shares ONE compiled fn per
        # direction (pad moves are idempotent row copies), trading a
        # little extra gather/scatter width for zero mid-run
        # recompiles — latency-sensitive runs and benchmarks pin it
        self._swap_jits: Dict[Tuple[int, int, int], object] = {}
        self.swap_pad: Optional[int] = None
        # GC / CTP / write-amplification accounting (ISSUE 9): plain
        # host counters surfaced through hit_stats() as MapStats.
        self.gc_moves = 0
        self.victims_ch = [0] * C
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self._pf_seen: set = set()
        self.host_writes = 0
        # Prefix sharing (ISSUE 10): host-side radix-path index +
        # authoritative refcounts, mirroring the device refcnt lane the
        # way BlockPool mirrors the device allocator.
        #   _nodes   (depth, rolling-hash) -> (block, exact prefix) —
        #            the radix tree in path-key form: node at depth i
        #            holds the device block carrying page i-1's KV
        #            computed under that exact token prefix. Insertion
        #            order doubles as the pruning order (LRU-touched on
        #            match via move_to_end).
        #   _pinned  block -> node key: blocks the tree holds a
        #            reference on (a pin is NOT a mapping ref — the
        #            device lane counts dlpn->block mappings only).
        #   _ref     block -> number of dlpns mapping it; present for
        #            exactly the share-managed blocks (registered in
        #            the tree at some point and not yet reclaimed).
        #            Free rule everywhere: a share-managed block
        #            returns to the pool only at zero mapping refs AND
        #            no pin.
        #   _shared  slot -> {page -> block}: this slot's pages mapped
        #            at blocks it must not write in place — the COW
        #            trigger set read by cow_writes().
        self._nodes: "collections.OrderedDict[Tuple[int, int], Tuple[int, tuple]]" \
            = collections.OrderedDict()
        self._pinned: Dict[int, Tuple[int, int]] = {}
        self._ref: Dict[int, int] = {}
        self._shared: Dict[int, Dict[int, int]] = {}
        self.prefix_max_nodes = 4096
        self.shared_maps = 0
        self.cow_moves = 0

    # ----------------------------------------------------------- helpers
    def _fresh_state(self):
        """Build (or rebuild) the device-resident map state pytree —
        the ONE home of the init-and-shard logic, shared by __init__
        and ``reset``."""
        if self.channels > 1:
            st = fb.init_sharded_state(
                self.geom, self.channels, self._n_dev, self._n_host,
                n_lanes=self.n_slots, track_live=self.track_live,
                track_refs=self.track_refs)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                st = jax.device_put(
                    st, NamedSharding(self.mesh, P("channel")))
            return st
        return fb.init_serving_state(self.geom, self._n_dev,
                                     self._n_host, n_lanes=self.n_slots,
                                     track_live=self.track_live,
                                     track_refs=self.track_refs)

    def reset(self, faults: Optional["flt.FaultPlane"] = None):
        """Reinitialize map state, pool and bookkeeping while KEEPING
        every compiled closure (_swap_jits, the serve/retranslate/
        set-alloc jits): jitted bound methods trace per *instance*, so
        a fresh manager would recompile the world — the chaos harness
        (tests/chaos/) replays hundreds of fault schedules against ONE
        manager via this. Optionally installs a new fault plane."""
        self.state = self._fresh_state()
        self.pool = BlockPool(self._n_dev, self._n_host,
                              n_channels=self.channels)
        self.seq_pages = {}
        self._host_pages = {}
        self._alloc_dirty = False
        self.channel_lanes[:] = 0
        self.faults = faults
        self.journal = None    # the engine re-attaches after recovery
        self.gc_moves = 0
        self.victims_ch = [0] * self.channels
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self._pf_seen = set()
        self.host_writes = 0
        self._nodes = collections.OrderedDict()
        self._pinned = {}
        self._ref = {}
        self._shared = {}
        self.shared_maps = 0
        self.cow_moves = 0

    def _dlpns(self, slot: int, pages: range) -> np.ndarray:
        return np.asarray([slot * self.max_pages + p for p in pages],
                          np.int32)

    def _xlate(self, kind: int, dlpns, dppns, olds=None):
        """Single fused map entry: one translate call (one probe, one
        insert, incremental table scatter — PER CHANNEL when sharded)
        services the whole op batch; state is donated and rebound."""
        XLATE_CALLS[0] += 1
        # numpy in, jit transfers: cheaper than explicit device_puts
        dl = np.asarray(dlpns, np.int32)
        opc = np.full(dl.shape, kind, np.int32)
        dp = np.asarray(dppns, np.int32)
        od = (np.zeros(dl.shape, np.int32) if olds is None
              else np.asarray(olds, np.int32))
        if self.channels > 1:
            self.channel_lanes += np.bincount(
                dl[dl >= 0] % self.channels, minlength=self.channels)
            self.state, out, ok = self._serve_sharded(self.state, opc,
                                                      dl, dp, od)
        else:
            self.channel_lanes[0] += int((dl >= 0).sum())
            self.state, out, ok = self.fns["serve"](self.state, opc, dl,
                                                    dp, od)
        return out, ok

    def _alloc_blocks(self, dlpns, *, host: bool = False):
        """Pool allocation for a batch of dlpns: channel-agnostic pops
        at channels=1 (the legacy path, bit-identical), per-owner-
        channel pops otherwise — page and backing block always share a
        channel, so each channel's device stack mirror stays exact."""
        if self.faults is not None and len(dlpns) \
                and self.faults.alloc_fails():
            # injected transient exhaustion: raised BEFORE any pop, so
            # the caller's retry sees an untouched pool. transient=True
            # tells the engine's livelock guard this is not terminal.
            c = int(dlpns[0]) % self.channels
            self.pool.note_exhausted(c)
            raise PoolExhausted(
                f"injected transient {'host' if host else 'device'} "
                f"allocator exhaustion ({len(dlpns)} blocks)",
                channel=c, transient=True)
        if self.channels == 1:
            return self.pool.alloc(len(dlpns), host=host)
        return self.pool.alloc_for(
            [int(d) % self.channels for d in dlpns], host=host)

    @staticmethod
    def _retranslate(geom, fmmu, n_slots, max_pages):
        """Translate every (slot, page) through the FMMU -> block table."""
        dl = jnp.arange(n_slots * max_pages, dtype=jnp.int32)
        fmmu, out = fb.lookup_batch(geom, fmmu, dl)
        return fmmu, out.reshape(n_slots, max_pages)

    @staticmethod
    def _retranslate_sharded(geom, C, n_slots, max_pages, mesh, fmmu):
        """Sharded retranslation oracle: every channel looks up all of
        its local dlpns, and the per-channel results interleave back to
        the global order (global dlpn d = local l * C + channel c).
        On a channel mesh the lookups run under shard_map: a Pallas
        kernel cannot be partitioned automatically."""

        def body(fm):
            L = geom.n_tvpns * geom.entries_per_tp
            return fb.lookup_batch(geom, fm,
                                   jnp.arange(L, dtype=jnp.int32))

        per_channel = jax.vmap(body)
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            from repro.parallel.sharding import shard_map
            per_channel = shard_map(per_channel, mesh=mesh,
                                    in_specs=P("channel"),
                                    out_specs=P("channel"))
        fmmu, outs = per_channel(fmmu)
        flat = fb.interleave_table(outs, n_slots * max_pages)
        return fmmu, flat.reshape(n_slots, max_pages)

    # ----------------------------------------------------------- API
    def new_seq(self, slot: int, n_pages: int,
                shared: Optional[Sequence[int]] = None) -> List[int]:
        """Admit a sequence into `slot` with `n_pages` logical pages.

        ``shared`` (ISSUE 10) maps the LEADING len(shared) pages at the
        given already-resident blocks instead of allocating: the fused
        UPDATE commits those dlpns at the shared dppns (bumping the
        device refcnt lane), the host refcounts advance in mirror, and
        only the remaining pages allocate + program fresh blocks —
        shared pages cost zero flash programs and zero prefill. Callers
        obtain `shared` from ``match_prefix`` and MUST NOT write shared
        pages in place (``cow_writes`` relocates first). With shared
        empty/None this is byte-for-byte the historical admission path
        (same journal record, same pool order)."""
        assert slot not in self.seq_pages, f"slot {slot} busy"
        shared = list(shared or [])
        k = len(shared)
        assert k <= n_pages, (k, n_pages)
        assert k == 0 or self.track_refs, \
            "shared admission needs track_refs=True (the refcnt lane)"
        dl = self._dlpns(slot, range(n_pages))
        fresh = list(self._alloc_blocks(dl[k:])) if n_pages > k else []
        blocks = shared + fresh
        self._alloc_dirty = True
        self.host_writes += len(fresh)   # shared pages program nothing
        self._xlate(UPDATE, dl, blocks)
        self.seq_pages[slot] = list(blocks)
        if k:
            for b in shared:
                self._ref[b] = self._ref.get(b, 0) + 1
            self._shared[slot] = {i: b for i, b in enumerate(shared)}
            self.shared_maps += k
        if self.journal is not None:
            if k:
                # SHARE admission: the leading blocks are references to
                # blocks some other slot (or the tree) already owns —
                # replay re-takes only the fresh tail from the free
                # lists and counts the shared refs (core/journal._apply).
                # The OOB frame carries ALL lanes' owner pairs — the
                # shared ones as metadata-only entries (they program no
                # data) — so a torn record stays SPOR-recoverable: the
                # reverse-map scan would otherwise see a page hole
                # below the first fresh page.
                self.journal.append(
                    jl.SHARE, {"slot": int(slot), "dl": _ji(dl),
                               "blocks": _ji(blocks), "n_shared": k,
                               "lanes": len(dl)},
                    programmed=zip(dl, blocks))
            else:
                self.journal.append(
                    jl.NEW_SEQ, {"slot": int(slot), "dl": _ji(dl),
                                 "blocks": _ji(blocks)},
                    programmed=zip(dl, blocks))
        # program-fault check AFTER the map commit, BEFORE any data is
        # written (prefill follows admission): a bad block here needs
        # only the CondUpdate re-drive, no row copy. Shared pages hold
        # long-since-verified data — only fresh programs consult the
        # plane.
        self._maybe_retire_programs(dl[k:], fresh)
        return list(self.seq_pages[slot])

    def extend_seq(self, slot: int, n_new: int) -> List[int]:
        return self.extend_seqs({slot: n_new}).get(slot, [])

    def extend_seqs(self, wants: Dict[int, int]) -> Dict[int, List[int]]:
        """Grow several sequences at once: ONE pool allocation and ONE
        fused map call for the whole step (the decode hot path). Raises
        OutOfBlocks before any state changes if the pool can't cover
        the full batch."""
        wants = {s: n for s, n in wants.items() if n > 0}
        if not wants:
            return {}
        dl: List[int] = []
        for slot, n in wants.items():           # validate BEFORE alloc:
            have = len(self.seq_pages[slot])    # KeyError leaks nothing
            dl.extend(slot * self.max_pages + p
                      for p in range(have, have + n))
        blocks = self._alloc_blocks(dl)
        self._alloc_dirty = True
        self.host_writes += len(blocks)
        got: Dict[int, List[int]] = {}
        i = 0
        for slot, n in wants.items():
            got[slot] = blocks[i:i + n]
            i += n
            self.seq_pages[slot].extend(got[slot])
        self._xlate(UPDATE, dl, blocks)
        if self.journal is not None:
            self.journal.append(
                jl.EXTEND, {"dl": _ji(dl), "blocks": _ji(blocks)},
                programmed=zip(dl, blocks))
        # growth blocks are programmed by the decode step that follows;
        # a schedule-failed program re-drives map-only (no data yet)
        if self._maybe_retire_programs(dl, blocks):
            got = {s: self.seq_pages[s][-n:] for s, n in wants.items()}
        return got

    def free_seq(self, slot: int):
        blocks = self.seq_pages.pop(slot)
        self._host_pages.pop(slot, None)
        self._shared.pop(slot, None)
        dl = self._dlpns(slot, range(len(blocks)))
        self._xlate(UPDATE, dl, np.full(len(blocks), NIL, np.int32))
        if self._ref:
            # refcount gate (ISSUE 10): share-managed blocks return to
            # the pool only at zero mapping refs and no tree pin —
            # per-block in lane order, so the free-list order matches
            # the unshared bulk free (and journal replay) exactly
            for b in blocks:
                self._unref(b)
        else:
            self.pool.free(blocks)
        # The CTP frontier filter assumes growth dlpns advance
        # monotonically — true within one sequence's life, false across
        # slot reuse: the next occupant re-grows through the SAME dlpn
        # range, and a key left in _pf_seen would silently skip its
        # segment fetches forever. Drop the freed slot's keys so a
        # reused slot re-prefetches. (When max_pages is not a multiple
        # of cmt_entries a segment can straddle two slots, so this may
        # also drop a neighbour's still-warm key — harmless: the set is
        # a hint, and the re-probe lands as a redundant hit.)
        ent = self.geom.cmt_entries
        C = self.channels
        for d in dl.tolist():
            self._pf_seen.discard((d % C, (d // C) // ent) if C > 1
                                  else (0, d // ent))
        self._alloc_dirty = True
        if self.journal is not None:
            # no OOB frame: a free programs nothing — a torn tail just
            # drops it cleanly (pages stay mapped until re-freed)
            self.journal.append(jl.FREE,
                                {"slot": int(slot), "blocks": _ji(blocks),
                                 "lanes": len(blocks)})

    def is_resident(self, slot: int) -> bool:
        """True when no page of `slot` lives in the host tier. One
        source of truth for the tier predicate: BlockPool.is_host
        (counted into _host_pages by the swap ops; alloc paths only
        ever add device blocks)."""
        return self._host_pages.get(slot, 0) == 0

    def n_device_pages(self, slot: int) -> int:
        """Device-tier pages held by `slot` (preemption victims must
        have at least one, or swapping them out moves nothing)."""
        return (len(self.seq_pages.get(slot, ()))
                - self._host_pages.get(slot, 0))

    def n_host_pages(self, slot: int) -> int:
        """Host-tier pages held by `slot`, O(1) (swap-maintained
        count). The serving scheduler's cost term is the per-channel
        ``host_pages_vec``; this total remains for host-side
        bookkeeping and diagnostics."""
        return self._host_pages.get(slot, 0)

    def block_tables(self) -> jnp.ndarray:
        """[n_slots, max_pages] int32 device view of the incremental
        table — zero-cost: no translation, no state mutation. NIL for
        unmapped; host-tier blocks appear tagged (callers must swap in
        before attention). With channels > 1 the per-channel shards
        interleave back to the global order (the boundary all-gather;
        a relayout, still no translation). The view is invalidated by
        the next map op (donated state); re-fetch, don't hold."""
        n = self.n_slots * self.max_pages    # table is geometry-padded
        return fb.dense_table(self.state, self.channels, n).reshape(
            self.n_slots, self.max_pages)

    def retranslate_tables(self) -> jnp.ndarray:
        """From-scratch full-map retranslation (the pre-incremental
        path): every DLPN through ``lookup_batch``. Kept ONLY as the
        churn-equivalence test oracle and the legacy serving-bench
        baseline; the serving hot path must use ``block_tables()``."""
        FULL_TABLE_CALLS[0] += 1
        if self.channels > 1:
            fmmu, tables = self._retrans_fn(self.state.fmmu)
        else:
            fmmu, tables = self._retrans_fn(self.state.fmmu,
                                            self.n_slots, self.max_pages)
        self.state = self.state._replace(fmmu=fmmu)
        return tables

    # ------------------------------------------- device allocator mirror
    def sync_allocator(self):
        """Re-push the host free lists into the device allocator stacks
        (and clear the OutOfBlocks flag). No-op unless a host-side pool
        mutation happened since the last sync — steady-state macro-step
        decode performs ZERO of these (ALLOC_SYNCS-counted)."""
        if not self._alloc_dirty:
            return
        ALLOC_SYNCS[0] += 1
        if self.channels > 1:
            # the re-push clears the sticky per-channel oob flag lane;
            # fold any set flags into the typed exhaustion counts FIRST
            # — the C>1 engine otherwise never reads the lane (the
            # ISSUE-6 "silent case"; the C=1 macro boundary passes its
            # already-synced flag to observe_exhaustion instead)
            self.observe_exhaustion()
        # refresh the residency lane in the same call: host-side frees
        # of swapped-out slots leave swap_pending stale until here, and
        # every such free also dirtied the pool
        resid = np.zeros(self.n_slots, bool)
        for s, c in self._host_pages.items():
            resid[s] = c > 0
        if self.channels > 1:
            C = self.channels
            dev = np.full(self.state.free_stack.shape, NIL, np.int32)
            host = np.full(self.state.host_stack.shape, NIL, np.int32)
            for c in range(C):
                dev[c, :self.pool.free_device_ch(c)] = \
                    self.pool._free_dev_ch[c]
                host[c, :self.pool.free_host_ch(c)] = \
                    self.pool._free_host_ch[c]
            self.state = self._set_alloc(
                self.state, dev,
                np.asarray([self.pool.free_device_ch(c)
                            for c in range(C)], np.int32),
                host,
                np.asarray([self.pool.free_host_ch(c)
                            for c in range(C)], np.int32), resid)
        else:
            dev = np.full(self.pool.n_device, NIL, np.int32)
            dev[:len(self.pool._free_dev)] = self.pool._free_dev
            host = np.full(self.pool.n_host, NIL, np.int32)
            host[:len(self.pool._free_host)] = self.pool._free_host
            self.state = self._set_alloc(
                self.state, dev, np.int32(len(self.pool._free_dev)),
                host, np.int32(len(self.pool._free_host)), resid)
        self._alloc_dirty = False

    def reconcile_macro(self, grow_seq: List[int]) -> Dict[int, List[int]]:
        """Replay a macro-step's device-side allocations onto the host
        pool and page lists. grow_seq is the slot sequence that popped
        blocks, in device pop order (step-major, slot-ascending within
        a step). Because the host stack is an exact mirror, popping the
        host free list in the same order yields the identical block
        ids — the device never has to ship an allocation log. The pool
        is NOT marked dirty: both sides applied the same delta, so the
        mirror still holds. Returns {slot: [new blocks]} in page
        order."""
        # the channel-sharded macro path never runs this replay: its
        # scans pop nothing device-side (growth is pre-committed by
        # precommit_growth), so replaying here would shrink the host
        # lists while the device stacks stand still — mirror broken
        assert self.channels == 1, \
            "reconcile_macro is the channels=1 replay; sharded macro " \
            "steps pre-commit growth via precommit_growth instead"
        got: Dict[int, List[int]] = {}
        if not grow_seq:
            return got
        blocks = self.pool.alloc(len(grow_seq))
        self.host_writes += len(blocks)
        dl: List[int] = []
        for slot, b in zip(grow_seq, blocks):
            self.seq_pages[slot].append(b)
            dl.append(slot * self.max_pages
                      + len(self.seq_pages[slot]) - 1)
            got.setdefault(slot, []).append(b)
        if self.journal is not None:
            # the scan already committed these lanes in-graph; this
            # record is their durability point (the macro boundary is
            # the commit point the crash axis can land on)
            self.journal.append(
                jl.RECONCILE, {"grow_seq": _ji(grow_seq), "dl": _ji(dl),
                               "blocks": _ji(blocks)},
                programmed=zip(dl, blocks))
        return got

    def _grow_dlpns(self, grow_seq: List[int]) -> List[int]:
        """Growth dlpns for a pop sequence: each entry is the slot's
        next unmapped page at that point in the sequence."""
        pages = {s: len(self.seq_pages[s]) for s in set(grow_seq)}
        dl = []
        for s in grow_seq:
            dl.append(s * self.max_pages + pages[s])
            pages[s] += 1
        return dl

    def precommit_growth(self, grow_seq: List[int],
                         dlpns: Optional[List[int]] = None
                         ) -> Dict[int, List[int]]:
        """Channel-sharded macro-step growth: commit a whole K-step
        growth schedule AHEAD of the scan — one channel-aware pool
        allocation in the scan's pop order (step-major, slot-ascending,
        identical to what K single steps would pop) plus ONE fused
        sharded map dispatch. The scan then decodes against the
        materialized post-growth table and needs no in-graph allocator
        at all: the cross-channel traffic stays at the macro boundary
        (DESIGN.md "Channel-sharded map pipeline").

        ``dlpns`` (aligned with grow_seq) is the dl schedule the
        caller's growth walk already produced — pass it so there is
        ONE derivation of which page each pop maps (the engine's
        ``_growth_walk``); when omitted, the schedule is re-derived
        from the page lists (test drivers)."""
        got: Dict[int, List[int]] = {}
        if not grow_seq:
            return got
        dl = (list(dlpns) if dlpns is not None
              else self._grow_dlpns(grow_seq))
        assert len(dl) == len(grow_seq)
        blocks = self._alloc_blocks(dl)
        self._alloc_dirty = True
        self.host_writes += len(blocks)
        counts: Dict[int, int] = {}
        for slot, b in zip(grow_seq, blocks):
            self.seq_pages[slot].append(b)
            got.setdefault(slot, []).append(b)
            counts[slot] = counts.get(slot, 0) + 1
        self._xlate(UPDATE, dl, blocks)
        if self.journal is not None:
            self.journal.append(
                jl.PRECOMMIT, {"grow_seq": _ji(grow_seq), "dl": _ji(dl),
                               "blocks": _ji(blocks)},
                programmed=zip(dl, blocks))
        # pre-committed growth blocks are programmed by the scan that
        # follows this boundary, so (like extend_seqs) a bad block here
        # re-drives map-only — the scan then writes the replacement
        if self._maybe_retire_programs(dl, blocks):
            got = {s: self.seq_pages[s][-n:] for s, n in counts.items()}
        return got

    # -------------------------------------- bad-block retirement (ISSUE 6)
    def _maybe_retire_programs(self, dl, blocks) -> int:
        """Consult the fault plane once per freshly programmed device
        block (allocation order); retire + re-drive any that failed.
        Map-only recovery — callers invoke this before the block's
        data is written. Returns the number of blocks retired."""
        f = self.faults
        if f is None:
            return 0
        bad = [(int(d), int(b)) for d, b in zip(dl, blocks)
               if not BlockPool.is_host(int(b)) and f.program_fails()]
        if not bad:
            return 0
        _, n = self.retire_bad_blocks(bad)
        return n

    def retire_bad_blocks(self, bad: List[Tuple[int, int]], pools=None,
                          block_axis: int = 0):
        """Bad-block retirement: for each (dlpn, block) whose program
        failed, pop a replacement from the SAME channel, commit
        dlpn -> replacement through the fused CondUpdate single-probe
        path (failure-is-just-another-relocation: the paper's GC
        discipline already arbitrates racing relocations, so a program
        failure needs no new invariants), and permanently retire the
        bad block from the pool. With ``pools`` the relocation also
        copies the KV rows old -> new inside the same donated jit (for
        blocks whose data was already programmed, e.g. in-scan macro
        growth reconciled at the boundary); without, only the map
        commits — detection preceded the data write. Replacement
        programs re-consult the plane: a bounded re-drive chain
        (_MAX_REDRIVE) retires runs of bad blocks. A dry channel
        defers retirement — the original block stays in service and
        data stays intact either way. Returns (pools, n_retired)."""
        f = self.faults
        done: List[Tuple[int, int, int]] = []    # (dlpn, old, new)
        popped: List[int] = []      # every replacement candidate popped
        retired: List[int] = []     # every block permanently retired
        for dlpn, old in bad:
            assert not BlockPool.is_host(old), \
                "program faults model device-tier block programs"
            c = self.pool.channel_of(old)
            chain = [old]
            new = None
            for i in range(_MAX_REDRIVE):
                try:
                    cand = self.pool.alloc_for([c])[0]
                except OutOfBlocks:
                    break
                popped.append(cand)
                chain.append(cand)
                if f is None or i == _MAX_REDRIVE - 1 \
                        or not f.program_fails():
                    new = cand
                    break
            if new is None:
                # dry channel: old block serves on, un-retired — but any
                # candidates we DID pop failed their programs and must
                # still be retired, or they leak out of all accounting
                # (not free, not mapped, not retired)
                dead = chain[1:]
                if dead:
                    self.pool.retire(dead)
                    retired.extend(dead)
                continue
            dead = [b for b in chain if b != new]
            self.pool.retire(dead)
            retired.extend(dead)
            done.append((dlpn, old, new))
        if popped:
            self._alloc_dirty = True    # pops/retires moved the pool
        if done:
            dl = [d for d, _, _ in done]
            olds = [o for _, o, _ in done]
            news = [n for _, _, n in done]
            if pools is None:
                self._xlate(COND_UPDATE, dl, news, olds)
            else:
                pools, _ = self._retire_move(dl, news, olds, pools,
                                             block_axis)
            for d, o, n in done:
                pages = self.seq_pages[d // self.max_pages]
                pages[pages.index(o)] = n
        if self.journal is not None and (done or popped):
            touched = sorted({d // self.max_pages for d, _, _ in done})
            self.journal.append(
                jl.RETIRE,
                {"done": [[int(d), int(o), int(n)] for d, o, n in done],
                 "popped": _ji(popped), "retired": _ji(retired),
                 "pages": {int(s): _ji(self.seq_pages[s])
                           for s in touched},
                 "lanes": len(done)},
                programmed=[(d, n) for d, _, n in done],
                retired=retired)
        return pools, len(done)

    def _retire_fn(self, cap: int, block_axis: int, n_pools: int):
        """Fused retirement-relocation jit (cached beside the swap
        jits): CondUpdate map commit + device-row copy old -> new in
        ONE donated call — the swap pipeline's shape minus the
        residency-lane flip (retirement never changes tier)."""
        key = ("retire", cap, block_axis, n_pools)
        fn = self._swap_jits.get(key)
        if fn is None:
            g = self.geom
            sharded = self.channels > 1

            def f(ms, pools, dl, newb, oldb, src, dst):
                opc = jnp.full((cap,), COND_UPDATE, jnp.int32)
                if sharded:
                    ms, _, ok = self._xlate_graph(ms, opc, dl, newb,
                                                  oldb)
                else:
                    ms, _, ok = fb.translate_serving(g, ms, opc, dl,
                                                     newb, oldb)
                pools = [_move_rows(p, src, dst, block_axis)
                         for p in pools]
                return ms, pools, ok

            fn = jax.jit(f, donate_argnums=(0, 1))
            self._swap_jits[key] = fn
        return fn

    def _retire_move(self, dl, news, olds, pools, block_axis):
        """Dispatch one fused CondUpdate relocation (lanes padded to
        the next power of two, exactly like ``_swap``). Device-tier
        rows are the block ids themselves. Shared by bad-block
        retirement and the GC victim walk (both are "just another
        relocation"). Returns (pools, ok[:n]) — the guard-mask
        readback, so GC can skip lanes whose mapping went stale
        mid-walk (the page died; its relocation must not apply)."""
        n = len(dl)
        cap = 1 << (n - 1).bit_length()
        pad = cap - n

        def arr(xs, fill):
            return np.asarray(list(xs) + [fill] * pad, np.int32)

        XLATE_CALLS[0] += 1
        if self.channels > 1:
            self.channel_lanes += np.bincount(
                np.asarray(dl) % self.channels,
                minlength=self.channels)
        else:
            self.channel_lanes[0] += n
        fn = self._retire_fn(cap, block_axis, len(pools))
        # pad map lanes are inactive (dl=-1); pad moves repeat lane 0's
        # (src, dst) pair — duplicate writes of an identical value
        self.state, pools, ok = fn(
            self.state, list(pools), arr(dl, -1), arr(news, 0),
            arr(olds, 0), arr(olds, olds[0]), arr(news, news[0]))
        return pools, np.asarray(ok)[:n]

    def observe_exhaustion(self, flags=None) -> np.ndarray:
        """Fold the sticky in-graph OutOfBlocks flag lane into the
        typed per-channel exhaustion counts (``pool.exhausted_ch`` /
        hit_stats "pool_exhausted"). ``flags`` (host values) avoids a
        device readback when the caller already synced them — the C=1
        macro boundary passes the scan's returned flag; ``None`` reads
        ``state.oob``. Detection latency: an in-graph allocation
        failure at scan step j only becomes observable here, at the
        next boundary/sync — up to K tokens after the fact (documented
        + asserted in tests/test_faults.py). Any set flag marks the
        allocator dirty so the next ``sync_allocator`` re-push clears
        the lane."""
        if flags is None:
            flags = jax.device_get(self.state.oob)
        flags = np.atleast_1d(np.asarray(flags))
        for c, hit in enumerate(flags):
            if hit:
                self.pool.note_exhausted(c % self.channels)
                self._alloc_dirty = True
        return flags

    # ------------------------------------------------- GC walk (ISSUE 9)
    def live_counts(self) -> np.ndarray:
        """Host view of the device-maintained per-block live-page
        counts ([n_device] int; channel shards summed). ONE readback
        per GC walk — the counts are maintained by the fused commits
        themselves, so the walk never probes or scans the map."""
        assert self.track_live and self.state.live is not None, \
            "GC needs track_live=True (the optional live lane)"
        return np.asarray(jax.device_get(fb.live_vec(self.state)))

    def _pick_victim(self, c: int, lv: np.ndarray,
                     block_pages: int) -> Optional[List[int]]:
        """The channel's GC victim: among its full erase blocks
        (pool.erase_blocks grouping), the FRAGMENTED one — some live
        pages, some dead — with the fewest live pages (ties to the
        lowest id). Blocks touching retirement never recycle; fully
        dead blocks are already reclaimed frame-by-frame; fully live
        blocks have nothing to gain. Returns the victim's frames or
        None."""
        best = None
        for frames in self.pool.erase_blocks(c, block_pages):
            if any(self.pool.is_retired(f) for f in frames):
                continue
            # share-managed frames are immovable (ISSUE 10): a shared
            # block is mapped by SEVERAL dlpns (and possibly pinned by
            # the radix tree), and the walk's one-CondUpdate-per-frame
            # relocation can only re-point one of them — freeing the
            # old frame would tear every other mapper. The erase block
            # re-qualifies once the refcount gate drains it.
            if self._ref and any(f in self._ref for f in frames):
                continue
            nlive = int(sum(int(lv[f]) for f in frames))
            if nlive == 0 or nlive >= len(frames):
                continue
            if best is None or nlive < best[0]:
                best = (nlive, frames)
        return None if best is None else best[1]

    def gc_collect(self, pools=None, block_axis: int = 0, *,
                   block_pages: int, budget: int
                   ) -> Tuple[Optional[List[jnp.ndarray]], int, int]:
        """One budgeted GC victim-eviction walk (the paper's GCM):
        per channel, pick the fragmented erase block with the fewest
        live pages (from the fused-commit-maintained counts — no map
        probe, no sort), relocate its live pages as ONE batched
        CondUpdate through the single-probe fused path (+ KV row moves
        when ``pools`` is given), and free the old frames — the whole
        victim erase block then sits on the channel's free stack.

        ``budget`` caps pages moved across the whole call (the
        boundary budget: GC never blocks decode for more than a
        bounded relocation batch); a victim that does not fit finishes
        on later walks. Destinations come from the channel's own free
        list, EXCLUDING the victim's frames (pool.alloc_gc) — net free
        count is unchanged (the modeled erase granularity lives in the
        grouping, not in the free list; DESIGN.md), but live data
        defragments into whole-block holes.

        Relocate-if-still-mapped: a lane whose CondUpdate guard fails
        means the page died mid-walk — it is skipped and its unused
        destination returns to the free list (``returned``). Applied
        moves are journaled as a GC host commit (crash mid-walk
        replays or drops them atomically). Returns
        (pools, pages_moved, victims_reclaimed)."""
        assert self.track_live, \
            "GC needs track_live=True (the optional live lane)"
        if budget <= 0:
            return pools, 0, 0
        lv = self.live_counts()
        mp = self.max_pages
        rev: Dict[int, int] = {}
        for s, pages in self.seq_pages.items():
            for i, b in enumerate(pages):
                if not BlockPool.is_host(b):
                    rev[b] = s * mp + i
        plan = []   # (channel, n_live_in_victim, take frames, news)
        left = int(budget)
        for c in range(self.channels):
            if left <= 0:
                break
            frames = self._pick_victim(c, lv, block_pages)
            if frames is None:
                continue
            live_frames = [f for f in frames if int(lv[f]) > 0]
            missing = [f for f in live_frames if f not in rev]
            assert not missing, \
                f"live counts name unmapped blocks {missing}"
            take = live_frames[:left]
            news = self.pool.alloc_gc(c, len(take), avoid=frames)
            take = take[:len(news)]    # opportunistic: fewer is fine
            if not take:
                continue
            left -= len(take)
            plan.append((c, len(live_frames), take, news))
        if not plan:
            return pools, 0, 0
        self._alloc_dirty = True
        dl = [rev[f] for _, _, take, _ in plan for f in take]
        olds = [f for _, _, take, _ in plan for f in take]
        news = [b for _, _, _, ns in plan for b in ns]
        if pools is None:
            # map-only walk (test drivers): pad like every fused dispatch
            n = len(dl)
            cap = 1 << (n - 1).bit_length()
            _, ok = self._xlate(COND_UPDATE, dl + [-1] * (cap - n),
                                news + [0] * (cap - n),
                                olds + [0] * (cap - n))
            okh = np.asarray(ok)[:n]
        else:
            pools, okh = self._retire_move(dl, news, olds, pools,
                                           block_axis)
        moves: List[Tuple[int, int, int]] = []
        returned: List[int] = []
        reclaimed = 0
        i = 0
        for c, n_live, take, ns in plan:
            whole = len(take) == n_live
            for f, nb in zip(take, ns):
                if bool(okh[i]):
                    d = rev[f]
                    self.seq_pages[d // mp][d % mp] = nb
                    moves.append((d, f, nb))
                else:
                    returned.append(nb)    # page died mid-walk: skip
                    whole = False
                i += 1
            if whole:
                self.victims_ch[c] += 1
                reclaimed += 1
        # free applied olds then skipped news, in lane order — journal
        # replay (core/journal._apply GC branch) mirrors this exactly
        self.pool.free([o for _, o, _ in moves] + returned)
        self.gc_moves += len(moves)
        if self.journal is not None:
            self.journal.append(
                jl.GC,
                {"moves": [[int(d), int(o), int(n)]
                           for d, o, n in moves],
                 "returned": _ji(returned), "lanes": len(moves)},
                programmed=[(d, n) for d, _, n in moves])
        return pools, len(moves), reclaimed

    # ----------------------------------- prefix sharing (ISSUE 10)
    def refcounts(self) -> np.ndarray:
        """Host view of the device-maintained per-block mapping
        reference counts ([n_device] int; channel shards summed) — the
        refcnt lane's ``live_counts`` twin, read back once per check.
        The host ``_ref`` dict stays authoritative for share-managed
        blocks; the lane exists so tests can assert the two mirrors
        never diverge (and the GC/COW paths never pay a readback)."""
        assert self.track_refs and self.state.refcnt is not None, \
            "prefix sharing needs track_refs=True (the refcnt lane)"
        return np.asarray(jax.device_get(fb.refcount_vec(self.state)))

    @staticmethod
    def page_groups(tokens, page_size: int) -> List[tuple]:
        """Split a prompt into page-granular token groups — the radix
        path alphabet. The last group may be partial (a prompt tail
        that only part-fills its page); it is still shareable, because
        two requests whose prompts agree through the partial page have
        bit-identical KV for it, and the first divergent WRITE into it
        relocates copy-on-write."""
        toks = [int(t) for t in tokens]
        return [tuple(toks[i:i + page_size])
                for i in range(0, len(toks), page_size)]

    @staticmethod
    def _path_keys(groups) -> List[Tuple[int, int]]:
        """Rolling-hash node keys for every prefix of the page-group
        path: key_i = (depth i+1, crc32 chained over groups[:i+1]).
        The chain makes the key a function of the WHOLE prefix, so one
        flat dict keyed by (depth, hash) IS the radix tree — matching
        a prompt is a walk down increasing depths. Nodes store the
        exact prefix too: a crc collision degrades to a miss, never to
        sharing the wrong KV."""
        keys = []
        h = 0
        for i, g in enumerate(groups):
            h = zlib.crc32(np.asarray(g, np.int64).tobytes(), h)
            keys.append((i + 1, h))
        return keys

    def match_prefix(self, groups) -> List[int]:
        """Walk the radix path for a prompt's page groups; return the
        blocks backing the LONGEST already-cached prefix (possibly
        empty). Every returned block carries this exact prefix's KV,
        already resident in the device tier — admission maps the new
        slot's leading dlpns at them (``new_seq(shared=...)``) and
        prefill skips those pages entirely. Matched nodes are
        LRU-touched so hot prefixes survive pruning."""
        if not self.track_refs:
            return []
        out: List[int] = []
        pref: List[tuple] = []
        for g, key in zip(groups, self._path_keys(groups)):
            node = self._nodes.get(key)
            if node is None:
                break
            block, exact = node
            pref.append(tuple(g))
            if exact != tuple(pref) or self.pool.is_retired(block) \
                    or BlockPool.is_host(block):
                break               # collision / retired: miss, never lie
            self._nodes.move_to_end(key)
            out.append(block)
        return out

    def register_prefix(self, slot: int, groups) -> int:
        """Pin the slot's (fully prefilled) prompt pages into the radix
        tree so later admissions can share them. A pin is a TREE
        reference: the block now outlives its owner slot and returns to
        the pool only when the tree lets go AND no slot maps it. The
        owner's pinned pages also join its COW trigger set — the tree's
        copy must never be written in place, not even by the slot that
        computed it. Returns the number of newly pinned pages."""
        if not self.track_refs or not groups:
            return 0
        pages = self.seq_pages.get(slot)
        if pages is None:
            return 0
        mine = self._shared.setdefault(slot, {})
        pinned: List[Tuple[int, int]] = []     # (page, block)
        for i, key in enumerate(self._path_keys(groups)):
            if i >= len(pages):
                break
            if key in self._nodes:             # cached already (first
                continue                       # writer wins)
            b = pages[i]
            if BlockPool.is_host(b) or self.pool.is_retired(b) \
                    or b in self._pinned:
                continue
            self._nodes[key] = (b, tuple(tuple(g) for g in groups[:i + 1]))
            self._pinned[b] = key
            if b not in self._ref:
                self._ref[b] = 1               # the owner's mapping
            mine[i] = b
            pinned.append((i, b))
        if not mine:
            self._shared.pop(slot, None)
        if pinned and self.journal is not None:
            # a pin moves no map state and programs nothing — pure
            # refcount bookkeeping, replayed for the free-gate
            self.journal.append(
                jl.SHARE, {"op": "pin", "slot": int(slot),
                           "pages": [int(p) for p, _ in pinned],
                           "blocks": [int(b) for _, b in pinned],
                           "lanes": 0})
        self._prune_nodes()
        return len(pinned)

    def _prune_nodes(self):
        """Bound the tree at ``prefix_max_nodes``: evict least-recently
        -matched nodes (OrderedDict order). Unpinning releases the tree
        reference; the block is reclaimed immediately if no slot still
        maps it, else it lingers as an ordinary shared block until its
        mappers drain through the refcount gate."""
        dropped: List[int] = []
        while len(self._nodes) > self.prefix_max_nodes:
            _, (b, _) = self._nodes.popitem(last=False)
            self._pinned.pop(b, None)
            if self._ref.get(b, 0) <= 0:
                self._ref.pop(b, None)
                self.pool.free([b])
                self._alloc_dirty = True
            dropped.append(b)
        if dropped and self.journal is not None:
            self.journal.append(
                jl.SHARE, {"op": "unpin", "blocks": _ji(dropped),
                           "lanes": 0})

    def _unref(self, b: int):
        """Drop one mapping reference. Share-managed blocks (in
        ``_ref``) hit the pool only at zero refs with no pin; everything
        else frees as before."""
        n = self._ref.get(b)
        if n is None:
            self.pool.free([b])
            return
        self._ref[b] = n - 1
        if n - 1 <= 0 and b not in self._pinned:
            del self._ref[b]
            self.pool.free([b])

    def has_shared(self, slot: Optional[int] = None) -> bool:
        """Any (or this slot's) pages mapped at blocks that must not be
        written in place — the cheap guard the engine checks before
        paying the per-step COW frontier scan."""
        if slot is None:
            return bool(self._shared)
        return bool(self._shared.get(slot))

    def cow_writes(self, fronts: Dict[int, int], pools=None,
                   block_axis: int = 0):
        """Copy-on-write relocation (ISSUE 10): for each slot, every
        shared page AT OR AFTER its write frontier (the page index its
        next token lands in) is about to diverge from the cached
        prefix, so relocate it BEFORE the write commits: allocate a
        private block in the page's own channel, CondUpdate the dlpn
        old -> new through the batched relocation path (+ KV row copy
        when ``pools`` is given — the same fused jit GC and retirement
        ride), and drop the mapping ref on the shared block. A lane
        whose guard fails means the page died mid-copy (freed or moved
        by a racing commit) — it is skipped and its destination
        returns, exactly the GC walk's stale-lane discipline. Raises
        OutOfBlocks before any state changes if the pool cannot cover
        the batch. Returns (pools, n_relocated)."""
        work: List[Tuple[int, int, int]] = []    # (slot, page, old)
        for slot, wpage in fronts.items():
            m = self._shared.get(slot)
            if not m:
                continue
            for p in sorted(k for k in m if k >= wpage):
                old = m[p]
                if self.seq_pages[slot][p] != old:
                    m.pop(p)     # already diverged elsewhere (GC/retire)
                    continue
                work.append((slot, p, old))
        if not work:
            return pools, 0
        dl = [s * self.max_pages + p for s, p, _ in work]
        news = list(self._alloc_blocks(dl))
        olds = [o for _, _, o in work]
        self._alloc_dirty = True
        if pools is None:
            n = len(dl)
            cap = 1 << (n - 1).bit_length()
            _, ok = self._xlate(COND_UPDATE, dl + [-1] * (cap - n),
                                news + [0] * (cap - n),
                                olds + [0] * (cap - n))
            okh = np.asarray(ok)[:n]
        else:
            pools, okh = self._retire_move(dl, news, olds, pools,
                                           block_axis)
        moves: List[Tuple[int, int, int, int]] = []
        returned: List[int] = []
        for (slot, page, old), nb, okl in zip(work, news, okh):
            if bool(okl):
                self.seq_pages[slot][page] = nb
                self._shared[slot].pop(page, None)
                if not self._shared[slot]:
                    del self._shared[slot]
                self._unref(old)
                moves.append((slot, page, old, nb))
            else:
                returned.append(nb)
        self.pool.free(returned)
        self.cow_moves += len(moves)
        if self.journal is not None and (moves or returned):
            self.journal.append(
                jl.COW,
                {"moves": [[int(s), int(p), int(o), int(nw)]
                           for s, p, o, nw in moves],
                 "returned": _ji(returned), "lanes": len(moves)},
                programmed=[(s * self.max_pages + p, nw)
                            for s, p, _, nw in moves])
        return pools, len(moves)

    # ------------------------------------------ CTP prefetch (ISSUE 9)
    def prefetch_segments(self, dlpns) -> int:
        """The paper's CTP, from pre-commit knowledge: the macro
        boundary already knows exactly which dlpns the next K-step
        growth will touch, so pull the backing-table segments (CMT
        cache blocks) they live in into the CMT AHEAD of the scan —
        one fused LOOKUP over one representative dlpn per distinct
        (channel, segment), padded like every dispatch. A LOOKUP of a
        still-unmapped dlpn is exactly a segment fetch: the insert
        pass caches the whole backing block, so the scan's UPDATE
        commits hit instead of missing. Accounting: a prefetch MISS
        did useful work (the segment was cold); a prefetch HIT was
        redundant. Returns the number of segments probed.

        The prefetcher tracks the scan FRONTIER: a segment is fetched
        the first time growth crosses into it and never re-probed
        (``_pf_seen``) — growth dlpns advance monotonically, so
        without the filter every boundary would re-dispatch a LOOKUP
        over the same already-cached segments, and that per-boundary
        dispatch tax is what the >= 0.9x GC-retention acceptance
        forbids. The set is a hint, not a guarantee: a CMT eviction
        can re-cool a seen segment, which the scan then pays as an
        ordinary miss."""
        dl = np.unique(np.asarray(dlpns, np.int32))
        dl = dl[dl >= 0]
        if dl.size == 0:
            return 0
        ent = self.geom.cmt_entries
        C = self.channels
        reps: List[int] = []
        for d in dl.tolist():
            key = ((d % C, (d // C) // ent) if C > 1
                   else (0, d // ent))
            if key not in self._pf_seen:
                self._pf_seen.add(key)
                reps.append(int(d))
        n = len(reps)
        if n == 0:
            return 0
        cap = 1 << (n - 1).bit_length()
        before = self._cmt_hit_miss()
        self._xlate(LOOKUP, reps + [-1] * (cap - n),
                    np.zeros(cap, np.int32))
        after = self._cmt_hit_miss()
        self.prefetch_hits += int(after[0] - before[0])
        self.prefetch_misses += int(after[1] - before[1])
        return n

    def _cmt_hit_miss(self) -> Tuple[int, int]:
        s = np.asarray(jax.device_get(self.state.fmmu.stats))
        if self.channels > 1:
            s = s.sum(axis=0)
        return int(s[0]), int(s[1])

    # ----------------------------------------------------------- swapping
    def _swap_fn(self, cap: int, block_axis: int, n_pools: int):
        """Build (or fetch) the fused swap jit for a padded lane count.
        ONE donated call per swap: CondUpdate commits through the
        single-probe fused translate, pool rows gather/scatter, and the
        swap_pending residency lane flips — no host roundtrip between
        the map write and the data it guards."""
        key = (cap, block_axis, n_pools)
        fn = self._swap_jits.get(key)
        if fn is None:
            g = self.geom
            sharded = self.channels > 1

            def f(ms, pools, dl, newb, oldb, src, dst, lane, pending):
                opc = jnp.full((cap,), COND_UPDATE, jnp.int32)
                if sharded:
                    # same fused shape, channel-sharded commit: each
                    # channel CondUpdates the swap lanes it owns (the
                    # shard_map/vmap graph composes under this jit)
                    ms, _, ok = self._xlate_graph(ms, opc, dl, newb,
                                                  oldb)
                    ms = fb.mark_swap_sharded(ms, lane, pending)
                else:
                    ms, _, ok = fb.translate_serving(g, ms, opc, dl,
                                                     newb, oldb)
                    ms = fb.mark_swap(ms, lane, pending)
                pools = [_move_rows(p, src, dst, block_axis)
                         for p in pools]
                return ms, pools, ok

            fn = jax.jit(f, donate_argnums=(0, 1))
            self._swap_jits[key] = fn
        return fn

    def _swap(self, direction: int, slot: int, pools, block_axis: int,
              check: bool) -> Tuple[List[jnp.ndarray], int]:
        """Shared body of swap_out/swap_in: host bookkeeping + one
        fused donated jit. Lane arrays are padded to the next power of
        two (pad lanes are inactive map ops and idempotent row moves),
        bounding re-traces at O(log max_pages) per (axis, pool-count)."""
        blocks = self.seq_pages[slot]
        out = direction == SWAP_OUT
        # share-managed blocks never change tier (ISSUE 10): other
        # slots (or the radix tree) still read them in the device
        # tier, so a swap-out moves only this slot's PRIVATE pages and
        # leaves the shared prefix resident — the slot comes back with
        # its shared mappings untouched. (Swap-in never sees shared
        # blocks: only device-tier blocks are ever shared.)
        moving = [b for b in blocks
                  if BlockPool.is_host(b) != out and b not in self._ref]
        if not moving:
            return pools, 0
        if self.faults is not None and self.faults.swap_fails():
            # injected BEFORE any mutation (allocs, map, pools, page
            # lists): the caller may retry the identical swap later —
            # the engine backs off exponentially and quarantines a
            # slot whose swap keeps failing
            raise flt.SwapFault(slot, direction, len(moving))
        dl = [slot * self.max_pages + i for i, b in enumerate(blocks)
              if BlockPool.is_host(b) != out and b not in self._ref]
        fresh = self._alloc_blocks(dl, host=out)
        self._alloc_dirty = True
        row = self.pool.host_row
        src = [row(b) if not out else b for b in moving]
        dst = [b if not out else row(b) for b in fresh]
        n = len(moving)
        cap = 1 << (n - 1).bit_length()
        if self.swap_pad:
            cap = max(cap, self.swap_pad)   # pinned: one fn per direction
        pad = cap - n

        def arr(xs, fill):
            return np.asarray(list(xs) + [fill] * pad, np.int32)

        XLATE_CALLS[0] += 1
        if self.channels > 1:
            self.channel_lanes += np.bincount(
                np.asarray(dl) % self.channels,
                minlength=self.channels)
        else:
            self.channel_lanes[0] += n
        fn = self._swap_fn(cap, block_axis, len(pools))
        # pad map lanes are inactive (dl=-1); pad moves repeat lane 0's
        # (src, dst) pair — duplicate writes of an identical value
        self.state, pools, ok = fn(
            self.state, list(pools), arr(dl, -1), arr(fresh, 0),
            arr(moving, 0), arr(src, src[0]), arr(dst, dst[0]),
            np.int32(slot), out)
        if check:
            assert np.asarray(ok)[:n].all(), \
                "swap raced with a concurrent relocation"
        self.pool.free(moving)
        self.seq_pages[slot] = [
            fresh[moving.index(b)] if b in moving else b for b in blocks]
        self._host_pages[slot] = sum(
            BlockPool.is_host(b) for b in self.seq_pages[slot])
        if out:
            self.pool.stats.swaps_out += n
        else:
            self.pool.stats.swaps_in += n
        if self.journal is not None:
            # the swap's commit point: a crash on this append is the
            # ISSUE-7 "mid-swap" case — the OOB frame (dl -> fresh)
            # either survives whole (reverse-map scan re-applies the
            # move, freeing the displaced blocks) or tears (the move
            # never reached flash; pre-swap state is the truth)
            self.journal.append(
                jl.SWAP,
                {"slot": int(slot), "out": bool(out), "moving": _ji(moving),
                 "fresh": _ji(fresh), "pages": _ji(self.seq_pages[slot]),
                 "hp": int(self._host_pages[slot])},
                programmed=zip(dl, fresh))
        return pools, n

    def swap_out(self, slot: int, pools: List[jnp.ndarray],
                 block_axis: int = 0, check: bool = True
                 ) -> Tuple[List[jnp.ndarray], int]:
        """Relocate all device blocks of `slot` to the host tier in ONE
        donated jitted call (CondUpdate-guarded map commit + pool-row
        gather/scatter + swap_pending lane set). pools: list of
        [NB_dev(+host), ...] tensors (k & v per layer group); the host
        region lives at rows [n_device:]. Returns (pools, n moved).
        ``check=False`` skips the guard-mask readback so the caller
        never blocks on the swap (the serving scheduler's mode)."""
        return self._swap(SWAP_OUT, slot, pools, block_axis, check)

    def swap_in(self, slot: int, pools: List[jnp.ndarray],
                block_axis: int = 0, check: bool = True
                ) -> Tuple[List[jnp.ndarray], int]:
        """Bring a swapped-out sequence back to device blocks (same
        fused non-blocking pipeline as swap_out; clears the lane)."""
        return self._swap(SWAP_IN, slot, pools, block_axis, check)

    def free_device_vec(self) -> np.ndarray:
        """Free device blocks per channel ([total] at channels=1): the
        engine's growth-reserve checks compare per channel, because a
        dry channel is real pool pressure even while others have
        blocks."""
        return np.asarray([self.pool.free_device_ch(c)
                           for c in range(self.channels)], np.int64)

    def host_pages_vec(self, slot: int) -> np.ndarray:
        """Host-tier pages of `slot` per owner channel — the per-
        channel device blocks its swap-in would consume."""
        out = np.zeros(self.channels, np.int64)
        for b in self.seq_pages.get(slot, ()):
            if BlockPool.is_host(b):
                out[self.pool.channel_of(b)] += 1
        return out

    # -------------------------------------- crash consistency (ISSUE 7)
    def journal_cfg(self) -> dict:
        """Geometry stamped into every snapshot: recovery refuses to
        restore into a differently-shaped manager."""
        return {"channels": self.channels, "n_device": self._n_dev,
                "n_host": self._n_host, "max_pages": self.max_pages,
                "n_slots": self.n_slots}

    def snapshot_state(self) -> dict:
        """The manager's share of a journal snapshot: page lists, the
        swap-maintained host-page counts, and the full pool allocator
        state (free-list ORDER included — the device-mirror contract
        makes order part of the state). All host data: the device map
        is a pure function of this (``restore_mapping`` re-derives it),
        so snapshots never serialize device arrays or KV pools."""
        d = {"cfg": self.journal_cfg(),
             "seq_pages": {int(s): _ji(p)
                           for s, p in self.seq_pages.items()},
             "host_pages": {int(s): int(n)
                            for s, n in self._host_pages.items()}}
        if self._ref or self._pinned:
            # prefix sharing (ISSUE 10): mapping refcounts and tree
            # pins are host truth the free-gate depends on. The tree's
            # CONTENT (token hashes) is deliberately not persisted —
            # the prefix cache is volatile; recovery releases pins and
            # rebuilds sharing from new traffic (restore_mapping).
            d["ref"] = {str(int(b)): int(n)
                        for b, n in self._ref.items()}
            d["pinned"] = sorted(int(b) for b in self._pinned)
        d.update(self.pool.state_dict())
        return d

    def restore_mapping(self, rec: "jl.Recovered") -> int:
        """Rebuild this manager from recovered host truth (call on a
        freshly ``reset`` manager): restore the pool + page lists, then
        re-derive the whole device map with ONE fused batched UPDATE
        (lanes padded to the next power of two — the usual re-trace
        bound) and one allocator re-push. The CMT refills warm, which
        SPOR always pays; dense_table / free stacks / residency lanes
        come back bit-identical to the pre-crash state because they are
        pure functions of what the journal persisted. Returns the
        number of mapped pages re-committed."""
        cfg = self.journal_cfg()
        assert rec.cfg == cfg, f"snapshot geometry {rec.cfg} != {cfg}"
        self.pool.load_state({
            "free_dev_ch": rec.free_dev_ch,
            "free_host_ch": rec.free_host_ch,
            "rr": rec.rr, "retired": sorted(rec.retired),
            "retired_ch": rec.retired_ch,
            "exhausted_ch": rec.exhausted_ch, "stats": rec.stats})
        self.seq_pages = {int(s): _ji(p)
                          for s, p in rec.seq_pages.items()}
        self._host_pages = {int(s): int(n)
                            for s, n in rec.host_pages.items()}
        # prefix sharing (ISSUE 10): mapping refcounts are durable
        # truth; the radix tree is a volatile cache. Restore the
        # refcounts, then RELEASE every recovered pin — a pinned block
        # no slot maps goes straight back to the pool (in sorted block
        # order, so recovery is deterministic), and sharing rebuilds
        # from post-recovery traffic.
        self._ref = {int(b): int(n) for b, n in rec.ref.items()}
        for b in sorted(int(x) for x in rec.pinned):
            if self._ref.get(b, 0) <= 0:
                self._ref.pop(b, None)
                self.pool.free([b])
        dl: List[int] = []
        blocks: List[int] = []
        for s in sorted(self.seq_pages):
            for i, b in enumerate(self.seq_pages[s]):
                dl.append(s * self.max_pages + i)
                blocks.append(b)
        n = len(dl)
        if n:
            cap = 1 << (n - 1).bit_length()
            dl += [-1] * (cap - n)
            blocks += [0] * (cap - n)
            self._xlate(UPDATE, dl, blocks)
        self._alloc_dirty = True
        self.sync_allocator()    # stacks + residency lanes in one push
        return n

    def hit_stats(self) -> "MapStats":
        s = np.asarray(self.state.fmmu.stats)
        if self.channels > 1:
            s = s.sum(axis=0)
        fired = self.faults.counts() if self.faults is not None else {}
        # write-amplification axis (ISSUE 9): every flash program is a
        # host-commanded write, a swap-in re-program, a GC relocation,
        # or a copy-on-write divergence copy (ISSUE 10). Retirement
        # re-drives are deliberately excluded — they are fault
        # recovery, not amplification policy.
        flash = (self.host_writes + self.pool.stats.swaps_in
                 + self.gc_moves + self.cow_moves)
        return MapStats(
            hits=int(s[0]), misses=int(s[1]),
            fills=int(s[2]), updates=int(s[3]),
            # swap/tier activity (ISSUE-4): the zero-fallback claim
            # is asserted from counters, not inferred from timings
            swaps_out=self.pool.stats.swaps_out,
            swaps_in=self.pool.stats.swaps_in,
            host_resident_slots=sum(
                1 for c in self._host_pages.values() if c > 0),
            # fault/recovery plane (ISSUE 6): retirement + typed
            # per-channel exhaustion attribution + fired-fault
            # counts (all zero without a plane)
            retired_blocks=self.pool.stats.retired,
            retired_ch=list(self.pool.retired_ch),
            pool_exhausted=list(self.pool.exhausted_ch),
            swap_faults=fired.get("swap", 0),
            program_faults=fired.get("program", 0),
            alloc_faults=fired.get("alloc", 0),
            # GC/CTP plane (ISSUE 9)
            gc_moves=self.gc_moves,
            victims_ch=list(self.victims_ch),
            prefetch_hits=self.prefetch_hits,
            prefetch_misses=self.prefetch_misses,
            host_writes=self.host_writes,
            flash_programs=flash,
            write_amp=(flash / self.host_writes
                       if self.host_writes else 1.0),
            # prefix-sharing plane (ISSUE 10)
            shared_maps=self.shared_maps,
            cow_moves=self.cow_moves)
