"""Semantic tests of the FMMU oracle (the executable spec), including
hypothesis property tests: any dependency-serialized trace must behave
like a sequential dict, survive arbitrary flash-response reordering, and
persist completely through flush_all."""
import random

import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core.fmmu.oracle import FMMUOracle
from repro.core.fmmu.types import (COND_UPDATE, LOOKUP, NIL, Request,
                                   UPDATE, small_geometry)


class Driver:
    """HIL-style dependency checker: serializes per-dlpn, reorders flash
    responses with the given rng."""

    def __init__(self, unit, rng):
        self.u = unit
        self.rng = rng
        self.resps = {}
        self.inflight = set()
        self.rid2dlpn = {}
        self.rid = 0
        self.trace = []

    def pump(self):
        self.u.run()
        r, f, p = self.u.drain_outputs()
        for resp in r:
            self.resps[resp.req_id] = resp
            self.inflight.discard(self.rid2dlpn[resp.req_id])
        f = list(f)
        self.rng.shuffle(f)
        for t, s, w in f:
            self.u.push_flash_response(t, s, w)
        return f

    def issue(self, kind, dlpn, dppn=NIL, old=NIL):
        spins = 0
        while dlpn in self.inflight:
            self.pump()
            spins += 1
            assert spins < 10_000, "driver livelock"
        self.u.push_request(Request(kind, dlpn, dppn=dppn, old_dppn=old,
                                    req_id=self.rid,
                                    src=1 if kind == COND_UPDATE else 0))
        self.trace.append((kind, dlpn, self.rid, dppn, old))
        self.inflight.add(dlpn)
        self.rid2dlpn[self.rid] = dlpn
        self.rid += 1
        if self.rng.random() < 0.3:
            self.pump()

    def finish(self):
        for _ in range(5000):
            f = self.pump()
            if not self.u.pending_work() and not f and not self.inflight:
                break
        assert not self.inflight, "responses lost"

    def replay_and_check(self):
        shadow = {}
        for kind, dlpn, rid, dppn, old in self.trace:
            if kind == UPDATE:
                shadow[dlpn] = dppn
            elif kind == COND_UPDATE:
                if shadow.get(dlpn, NIL) == old:
                    shadow[dlpn] = dppn
            else:
                assert self.resps[rid].dppn == shadow.get(dlpn, NIL), (
                    f"lookup rid={rid} dlpn={dlpn}")
        return shadow


def _random_trace(unit, seed, n_ops):
    rng = random.Random(seed)
    g = unit.g
    n_pages = g.n_tvpns * g.entries_per_tp
    d = Driver(unit, rng)
    shadow = {}
    for _ in range(n_ops):
        dlpn = rng.randrange(n_pages)
        kind = rng.choice([LOOKUP, UPDATE, UPDATE, COND_UPDATE])
        if kind == LOOKUP:
            d.issue(LOOKUP, dlpn)
        elif kind == UPDATE:
            v = rng.randrange(10 ** 6)
            d.issue(UPDATE, dlpn, dppn=v)
            shadow[dlpn] = v
        else:
            old = rng.choice([shadow.get(dlpn, NIL), rng.randrange(10 ** 6)])
            v = rng.randrange(10 ** 6)
            d.issue(COND_UPDATE, dlpn, dppn=v, old=old)
            if shadow.get(dlpn, NIL) == old:
                shadow[dlpn] = v
    d.finish()
    return d


@pytest.mark.parametrize("seed", range(4))
def test_oracle_sequential_semantics(seed):
    o = FMMUOracle(small_geometry())
    d = _random_trace(o, seed, 1500)
    shadow = d.replay_and_check()
    for dlpn, v in shadow.items():
        assert o.resolve(dlpn) == v


@pytest.mark.parametrize("seed", range(2))
def test_oracle_flush_all_persists(seed):
    o = FMMUOracle(small_geometry())
    d = _random_trace(o, seed + 10, 800)
    shadow = d.replay_and_check()
    o.flush_all()
    assert o.cmt_dirty == 0 and o.ctp_dirty == 0
    g = o.g
    for dlpn, v in shadow.items():
        tppn = o.gtd[dlpn // g.entries_per_tp]
        got = NIL if tppn == NIL else o.flash_tp[tppn][dlpn % g.entries_per_tp]
        assert got == v


def test_oracle_mshr_merging_reduces_flash_reads():
    """Many concurrent lookups of one translation page -> one flash read
    (the non-blocking MSHR-merge claim of §4.2)."""
    g = small_geometry()
    o = FMMUOracle(g)
    # prime: one update far away so the TP exists in flash
    o.push_request(Request(UPDATE, 0, dppn=7, req_id=0))
    o.run(auto_flash=True)
    o.flush_all()
    base_reads = o.stats["fc_reads"]
    # evict TP from CTP by touching other TVPNs
    for i in range(1, g.n_tvpns):
        o.push_request(Request(UPDATE, i * g.entries_per_tp, dppn=i,
                               req_id=100 + i))
    o.run(auto_flash=True)
    o.flush_all()
    mid_reads = o.stats["fc_reads"]
    # now issue a burst of lookups to the SAME cmt block without serving
    # flash: all must merge into one outstanding read
    for j in range(g.mshr_cap):
        o.push_request(Request(LOOKUP, j, req_id=1000 + j))
    o.run(auto_flash=False)     # flash is slow: responses pending
    _, fc, _ = o.drain_outputs()
    assert len(fc) == 1, f"expected one merged flash read, got {len(fc)}"
    assert o.stats["mshr_merge"] >= g.mshr_cap - 1
    for t, s, w in fc:
        o.push_flash_response(t, s, w)
    o.run()
    r, _, _ = o.drain_outputs()
    got = {resp.req_id: resp.dppn for resp in r}
    assert got[1000] == 7
    for j in range(1, g.mshr_cap):
        assert got[1000 + j] == NIL


def test_oracle_condupdate_race():
    """GC CondUpdate must lose when the host updated concurrently (§4.1)."""
    o = FMMUOracle(small_geometry())
    o.push_request(Request(UPDATE, 5, dppn=100, req_id=0))
    o.run(auto_flash=True)
    # host writes a newer version
    o.push_request(Request(UPDATE, 5, dppn=200, req_id=1))
    o.run(auto_flash=True)
    # GC finishes its copy of the old page and conditionally updates
    o.push_request(Request(COND_UPDATE, 5, dppn=300, old_dppn=100,
                           req_id=2, src=1))
    o.run(auto_flash=True)
    r, _, _ = o.drain_outputs()
    stale = [x for x in r if x.req_id == 2][0]
    assert stale.status == 1  # ST_STALE: update refused
    assert o.resolve(5) == 200


def test_oracle_flush_batches_same_tvpn():
    """Dirty blocks of one TVPN flush together via next-links: flushing
    after k updates inside one TP costs exactly one program."""
    g = small_geometry()
    o = FMMUOracle(g)
    for j in range(4):  # 4 updates, all within TVPN 0, different blocks
        o.push_request(Request(UPDATE, j * g.cmt_entries, dppn=j, req_id=j))
    o.run(auto_flash=True)
    o.flush_all()
    assert o.stats["programs"] == 1
    assert o.stats["flush_blocks"] == 4


# pinned regression cases: a CondUpdate racing an Update on one dlpn, and a
# full-block write/readback sweep that forces a flush + reload
@example([(1, 0, 5), (0, 0, 0), (2, 0, 9), (0, 0, 0), (1, 0, 3),
          (2, 0, 9), (0, 0, 0)], 1234)
@example([(1, j, j) for j in range(8)]
         + [(0, j, 0) for j in range(8)], 7)
@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2),
                          st.integers(0, 127),
                          st.integers(0, 999)),
                min_size=1, max_size=120),
       st.integers(0, 2 ** 30))
def test_oracle_property_random_programs(ops, flash_seed):
    """Property: any op sequence == dict semantics (hypothesis-driven)."""
    g = small_geometry()
    o = FMMUOracle(g)
    rng = random.Random(flash_seed)
    d = Driver(o, rng)
    shadow = {}
    for op, dlpn, val in ops:
        if op == 0:
            d.issue(LOOKUP, dlpn)
        elif op == 1:
            d.issue(UPDATE, dlpn, dppn=val)
            shadow[dlpn] = val
        else:
            old = shadow.get(dlpn, NIL) if val % 2 else val
            d.issue(COND_UPDATE, dlpn, dppn=val, old=old)
            if shadow.get(dlpn, NIL) == old:
                shadow[dlpn] = val
    d.finish()
    d.replay_and_check()
    for dlpn, v in shadow.items():
        assert o.resolve(dlpn) == v
