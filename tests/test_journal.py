"""Crash-consistent map journaling (ISSUE 7): frame-level torn-tail
detection, replay truncated at EVERY byte offset of the last record
(full replay or clean drop — never a corrupt map), the injected crash
axis's byte-exact tears, the device commit_seq lane vs journaled lanes,
and jaxpr-identity of the journaling-disabled path.

The exhaustive truncation test enumerates offsets deterministically;
the hypothesis property on top varies the traffic script and the cut
fraction, with the pinned @example seeds always replayed."""
import os
import random
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import example, given, settings, strategies as st
from repro.core import journal as jl
from repro.core.faults import Crash, FaultPlane, make_plan
from repro.core.fmmu import batch as fb
from repro.paging.kv_manager import KVPageManager

pytestmark = pytest.mark.recovery


# ------------------------------------------------------------- framing
def test_frame_roundtrip_and_valid_bytes(tmp_path):
    p = str(tmp_path / "log")
    blob = b"".join(jl._frame(i + 1, jl.NEW_SEQ, {"i": i})
                    for i in range(3))
    with open(p, "wb") as f:
        f.write(blob)
    frames, valid, torn = jl.read_frames(p)
    assert [s for s, _, _ in frames] == [1, 2, 3]
    assert [d["i"] for _, _, d in frames] == [0, 1, 2]
    assert valid == len(blob) and not torn


def test_read_frames_every_truncation_is_detected(tmp_path):
    """Cutting the 2-frame log at ANY interior byte offset yields the
    longest whole-frame prefix and torn=True — no parser state escapes
    a partial header, partial payload, or partial crc."""
    f1 = jl._frame(1, jl.EXTEND, {"dl": [5], "blocks": [2], "lanes": 1})
    f2 = jl._frame(2, jl.FREE, {"slot": 0, "blocks": [2], "lanes": 1})
    blob = f1 + f2
    p = str(tmp_path / "log")
    for cut in range(len(blob) + 1):
        with open(p, "wb") as f:
            f.write(blob[:cut])
        frames, valid, torn = jl.read_frames(p)
        want = (2 if cut == len(blob) else 1 if cut >= len(f1) else 0)
        assert len(frames) == want, cut
        assert valid == (len(f1) * want if want < 2 else len(blob))
        assert torn == (cut not in (0, len(f1), len(blob))), cut


def test_corrupt_interior_frame_stops_replay(tmp_path):
    f1 = jl._frame(1, jl.SUBMIT, {"rid": 0, "lanes": 0})
    f2 = jl._frame(2, jl.SUBMIT, {"rid": 1, "lanes": 0})
    blob = bytearray(f1 + f2)
    blob[len(f1) // 2] ^= 0xFF          # flip a byte inside frame 1
    p = str(tmp_path / "log")
    with open(p, "wb") as f:
        f.write(bytes(blob))
    frames, valid, torn = jl.read_frames(p)
    assert frames == [] and valid == 0 and torn


# ------------------------------------------- torn-tail replay property
def _traffic(kvm, rng):
    """A random but always-legal op script; every op is a journaled
    commit point. Growth is gated on per-channel headroom (and leaves
    room for the caller's final 2-page new_seq) so no script ever hits
    OutOfBlocks."""
    live = []
    for _ in range(rng.randrange(6, 11)):
        op = rng.random()
        free_slots = [s for s in range(kvm.n_slots) if s not in live]
        roomy = [s for s in live
                 if len(kvm.seq_pages[s]) + 2 <= kvm.max_pages]
        headroom = min(kvm.pool.free_device_ch(c)
                       for c in range(kvm.channels)) >= 4
        if op < 0.5 and free_slots and headroom:
            slot = free_slots[0]
            kvm.new_seq(slot, rng.randrange(1, 4))
            live.append(slot)
        elif op < 0.8 and roomy and headroom:
            kvm.extend_seqs({rng.choice(roomy): rng.randrange(1, 3)})
        elif live:
            kvm.free_seq(live.pop(rng.randrange(len(live))))


def _cut_dir(src: str, dst: str, o_base: int, r_base: int, cut: int,
             o_tail: int):
    """Clone the journal dir with the final commit's (oob + record)
    byte stream truncated after `cut` bytes — the exact layout
    ``Journal.append``'s crash path would leave behind."""
    if os.path.isdir(dst):
        shutil.rmtree(dst)
    os.makedirs(dst)
    for name in os.listdir(src):
        shutil.copy(os.path.join(src, name), os.path.join(dst, name))
    with open(os.path.join(dst, "oob.log"), "r+b") as f:
        f.truncate(o_base + min(cut, o_tail))
    with open(os.path.join(dst, "journal.log"), "r+b") as f:
        f.truncate(r_base + max(0, cut - o_tail))


def _torn_tail_case(seed: int, exhaustive: bool, frac: float = 0.0,
                    final: str = "new2"):
    """Drive journaled traffic, then truncate the LAST commit's bytes —
    at every offset (exhaustive) or at one seeded offset — and require:
    replay never corrupts the map (check() passes) and the recovered
    mapping is bit-exactly either the pre-commit or the post-commit
    oracle, with the flip happening exactly when the commit's OOB frame
    is complete (the SPOR contract: whole OOB = replayable, torn OOB =
    dropped cleanly).

    ``final`` picks the dangling commit: a 2-page new_seq ("new2", the
    default — fits any channel draw), a 3-page new_seq or 3-page slot
    extension ("new3" / "extend3" — always at channels=2, where the
    slot's pages stripe across channels, so the OOB scan must apply
    owners in page order, not channel order), a RETIRE with a
    program-fault chain ("retire" — the dangling frame carries
    bad-block marks for a schedule-failed replacement candidate the
    replayed shadow still holds free), or a mid-swap tear ("swap")."""
    rng = random.Random(seed)
    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "j")
        kvm = KVPageManager(
            n_slots=4, max_pages=8, n_device_blocks=24,
            n_host_blocks=8 if final == "swap" else 0,
            channels=rng.choice((1, 2)) if final == "new2" else 2)
        j = jl.Journal(src)
        kvm.journal = j
        j.snapshot(kvm.snapshot_state())
        _traffic(kvm, rng)
        if len(kvm.seq_pages) == kvm.n_slots:
            kvm.free_seq(min(kvm.seq_pages))
        victim = None
        if final != "new2":
            # the non-default finals need pool headroom (and a live
            # 3-page victim slot for extend/retire/swap); every top-up
            # op below is itself a journaled commit, so it lands
            # before the pre-commit oracle is taken
            while (min(kvm.pool.free_device_ch(c)
                       for c in range(kvm.channels)) < 6
                   and kvm.seq_pages):
                kvm.free_seq(min(kvm.seq_pages))
            if final != "new3":
                victim = next(s for s in range(4)
                              if s not in kvm.seq_pages)
                kvm.new_seq(victim, 3)
        m_before = jl.replay(src).mapping()
        o_base = os.path.getsize(os.path.join(src, "oob.log"))
        r_base = os.path.getsize(os.path.join(src, "journal.log"))
        # final commit: programs blocks, so it has an OOB frame and
        # exercises the reverse-map scan
        if final in ("new2", "new3"):
            slot = next(s for s in range(4) if s not in kvm.seq_pages)
            kvm.new_seq(slot, 2 if final == "new2" else 3)
        elif final == "extend3":
            kvm.extend_seq(victim, 3)
        elif final == "retire":
            # first replacement candidate fails its program too: the
            # chain retires {original, candidate} and keeps the second
            # candidate — the candidate is a block the replayed shadow
            # still thinks is free
            old = kvm.seq_pages[victim][0]
            kvm.faults = FaultPlane(make_plan(seed)._replace(
                program_fail=np.array([True] + [False] * 7)))
            kvm.retire_bad_blocks([(victim * kvm.max_pages, old)])
        else:
            assert final == "swap"
            width = kvm.pool.n_device + kvm.pool.n_host + 1
            kvm.swap_out(victim, [jnp.zeros((width, 2))])
        j.close()
        m_after = jl.replay(src).mapping()
        assert m_after != m_before
        o_tail = os.path.getsize(os.path.join(src, "oob.log")) - o_base
        r_tail = (os.path.getsize(os.path.join(src, "journal.log"))
                  - r_base)
        total = o_tail + r_tail
        cuts = (range(total + 1) if exhaustive
                else [max(0, min(total, int(round(frac * total))))])
        work = os.path.join(d, "cut")
        for cut in cuts:
            _cut_dir(src, work, o_base, r_base, cut, o_tail)
            rec = jl.replay(work)
            rec.check()                      # never a corrupt map
            got = rec.mapping()
            if cut >= o_tail:                # whole OOB frame landed
                assert got == m_after, (seed, cut)
                assert rec.oob_scan == (cut < total), (seed, cut)
            else:                            # commit never hit "flash"
                assert got == m_before, (seed, cut)
                assert not rec.oob_scan, (seed, cut)


def test_truncate_every_byte_offset_of_last_record():
    """The satellite's exhaustive case: every single byte offset of the
    final commit's on-disk bytes, two fixed traffic scripts."""
    for seed in (7, 23):
        _torn_tail_case(seed, exhaustive=True)


@pytest.mark.parametrize("final", ("new3", "extend3", "retire", "swap"))
def test_truncate_every_byte_offset_other_commit_kinds(final):
    """Review hardening: exhaustive byte-offset sweeps for the dangling
    commit kinds the default case cannot reach — multi-page allocs
    whose pages stripe across channels=2 (the OOB scan must apply
    owners in page order), a RETIRE program-fault chain (bad-block
    marks for a block the shadow still holds free), and a mid-swap
    tear."""
    _torn_tail_case(31, exhaustive=True, final=final)


@example(seed=3, frac=0.0, final="new2")
@example(seed=5, frac=0.5, final="new2")
@example(seed=11, frac=0.93, final="new2")
@example(seed=42, frac=1.0, final="new2")
@example(seed=17, frac=0.4, final="new3")
@example(seed=19, frac=0.55, final="extend3")
@example(seed=29, frac=0.5, final="retire")
@example(seed=37, frac=0.8, final="swap")
@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), frac=st.floats(0.0, 1.0),
       final=st.sampled_from(("new2", "new3", "extend3", "retire",
                              "swap")))
def test_torn_tail_property(seed, frac, final):
    """Property form: arbitrary traffic script x arbitrary cut point x
    dangling commit kind. The pinned examples are the regression
    seeds; with hypothesis installed the strategy explores beyond
    them."""
    _torn_tail_case(int(seed), exhaustive=False, frac=float(frac),
                    final=str(final))


def test_torn_tail_seeded_sweep():
    """Deterministic seeded breadth beside the property: 12 scripts x 4
    cut fractions."""
    for seed in range(12):
        for frac in (0.0, 0.33, 0.71, 1.0):
            _torn_tail_case(100 + seed, exhaustive=False, frac=frac)


def test_torn_tail_seeded_sweep_commit_kinds():
    """Deterministic seeded breadth over the non-default dangling
    commits."""
    for final in ("new3", "extend3", "retire", "swap"):
        for seed in (0, 1, 2):
            for frac in (0.0, 0.45, 0.77, 1.0):
                _torn_tail_case(140 + seed, exhaustive=False,
                                frac=frac, final=final)


# --------------------------------------------------- injected crashes
def test_crash_axis_tears_byte_exactly(tmp_path):
    """The fault plane's crash axis must persist round(tear * total)
    bytes of the commit's oob+record stream and kill the journal."""
    for tear, torn in ((0.0, True), (0.4, True), (1.0, False)):
        d = str(tmp_path / f"t{tear}")
        plan = make_plan(1, crash_at=0)
        plan = plan._replace(
            crash_tear=np.full_like(plan.crash_tear, tear))
        j = jl.Journal(d, faults=FaultPlane(plan))
        with pytest.raises(Crash) as ei:
            j.append(jl.NEW_SEQ,
                     {"slot": 0, "dl": [0, 1], "blocks": [4, 6]},
                     programmed=[(0, 4), (1, 6)])
        assert ei.value.torn == torn and j.dead
        oob = jl._frame(1, jl.OOB,
                        {"pairs": [[0, 4], [1, 6]], "retired": []})
        rec = jl._frame(1, jl.NEW_SEQ, {"slot": 0, "dl": [0, 1],
                                        "blocks": [4, 6], "lanes": 2})
        total = len(oob) + len(rec)
        cut = int(round(tear * total))
        got = (os.path.getsize(os.path.join(d, "oob.log"))
               + os.path.getsize(os.path.join(d, "journal.log")))
        assert got == cut, (tear, got, cut)
        with pytest.raises(AssertionError):
            j.append(jl.FREE, {"slot": 0, "blocks": [], "lanes": 0})


def test_resume_truncates_torn_tail_and_continues_seq(tmp_path):
    d = str(tmp_path / "j")
    j = jl.Journal(d)
    j.append(jl.SUBMIT, {"rid": 0, "tokens": [1], "max_new": 1,
                         "lanes": 0})
    j.append(jl.SUBMIT, {"rid": 1, "tokens": [2], "max_new": 1,
                         "lanes": 0})
    j.close()
    with open(os.path.join(d, "journal.log"), "ab") as f:
        f.write(b"\x13\x37torn")
    j2 = jl.Journal(d, resume=True)
    assert j2.seq == 2                   # tail dropped, sequence kept
    frames, _, torn = jl.read_frames(os.path.join(d, "journal.log"))
    assert len(frames) == 2 and not torn
    s = j2.append(jl.SUBMIT, {"rid": 2, "tokens": [3], "max_new": 1,
                              "lanes": 0})
    assert s == 3
    j2.close()


# -------------------------------------------------- commit_seq lane
def test_commit_seq_lane_matches_journaled_lanes():
    """The device-resident commit_seq lane (ISSUE 7's sequence lane in
    the fused map) and the journal's cumulative record lanes advance in
    lockstep across every commit kind — alloc, batched growth, free,
    swap, retirement."""
    import jax.numpy as jnp
    with tempfile.TemporaryDirectory() as d:
        for C in (1, 2):
            kvm = KVPageManager(n_slots=4, max_pages=6,
                                n_device_blocks=16, n_host_blocks=8,
                                channels=C)
            j = jl.Journal(os.path.join(d, f"c{C}"))
            kvm.journal = j

            def lanes():
                return int(np.asarray(jax.device_get(
                    fb.commit_seq_vec(kvm.state))).sum())

            base = lanes()
            kvm.new_seq(0, 3)
            kvm.new_seq(1, 2)
            kvm.extend_seqs({0: 2, 1: 1})
            kvm.retire_bad_blocks([(1 * kvm.max_pages,
                                    kvm.seq_pages[1][0])])
            width = kvm.pool.n_device + kvm.pool.n_host + 1
            pools = [jnp.zeros((width, 2))]
            pools, _ = kvm.swap_out(0, pools)
            pools, _ = kvm.swap_in(0, pools)
            kvm.free_seq(1)
            assert lanes() - base == j.commit_lanes, C
            assert j.commit_lanes > 0
            j.close()


# ------------------------------------------- disabled path: zero cost
def test_journaling_disabled_jaxpr_identical():
    """Journaling is host-side file I/O behind ``if journal is not
    None`` — the traced serve and swap graphs must be string-identical
    with and without a journal attached (same contract, and the same
    test shape, as the ISSUE-6 fault plane's)."""
    import jax.numpy as jnp
    with tempfile.TemporaryDirectory() as d:
        plain = KVPageManager(2, 4, 8, 8)
        logged = KVPageManager(2, 4, 8, 8)
        logged.journal = jl.Journal(d)
        opc = np.zeros(4, np.int32)
        dl = np.arange(4, dtype=np.int32)

        def serve_jaxpr(k):
            return str(jax.make_jaxpr(
                lambda s: k.fns["serve"](s, opc, dl, dl, dl))(k.state))

        assert serve_jaxpr(plain) == serve_jaxpr(logged)

        pools = [jnp.zeros((17, 2))]
        lanes = (dl, dl, dl, dl, dl, np.int32(0), True)

        def swap_jaxpr(k):
            fn = k._swap_fn(4, 0, 1)
            return str(jax.make_jaxpr(
                lambda s, p: fn(s, p, *lanes))(k.state, pools))

        assert swap_jaxpr(plain) == swap_jaxpr(logged)
        logged.journal.close()
