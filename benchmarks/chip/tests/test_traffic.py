"""The traffic generator: the same sizes and gaps for every seed, in a
seed's own order; the mix's prefill lengths only; large seeds."""
import os

import numpy as np
import pytest

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plan(mix, seed, **kw):
    m = traffic.load(BENCH, mix)
    args = dict(n_slots=16, max_ctx=4096, vocab=32000, seconds=45.0)
    args.update(kw)
    return traffic.make_plan(m, seed=seed, **args)


def _sizes(plan):
    return sorted((len(r.prompt), r.max_new) for r in plan.requests)


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 3 * 2**40 + 7])
def test_backlog_same_sizes_other_order(seed):
    a, b = _plan("decode_long", 0), _plan("decode_long", seed)
    assert _sizes(a) == _sizes(b)
    assert [len(r.prompt) for r in a.requests] != \
        [len(r.prompt) for r in b.requests]
    assert not a.open_loop and all(r.arrival is None for r in b.requests)
    assert {len(r.prompt) for r in b.requests} <= {512, 1024, 2048}
    assert all(256 <= r.max_new <= 4096 - len(r.prompt) for r in b.requests)
    # outputs and ends on the mix's 64-token grid
    assert all(r.max_new % 64 == 0 for r in b.requests + b.first_wave)


def test_first_wave_is_stationary_and_rounded():
    p = _plan("decode_long", 9)
    assert len(p.first_wave) == 16
    for r in p.first_wave:
        assert len(r.prompt) % 512 == 0 and len(r.prompt) >= 512
        assert 1 <= r.max_new and len(r.prompt) + r.max_new <= 4096
    assert sorted((len(r.prompt), r.max_new) for r in p.first_wave) == \
        sorted((len(r.prompt), r.max_new) for r in _plan(
            "decode_long", 10).first_wave)


def test_poisson_arrivals_are_fixed_sizes_dealt_by_seed():
    a = _plan("chat_open", 3, rate_per_s=4.0)
    b = _plan("chat_open", 4, rate_per_s=4.0)
    assert [r.arrival for r in a.requests] == [r.arrival for r in b.requests]
    assert sorted((len(r.prompt), r.max_new) for r in a.requests) == \
        sorted((len(r.prompt), r.max_new) for r in b.requests)
    assert [len(r.prompt) for r in a.requests] != \
        [len(r.prompt) for r in b.requests]
    ga = np.diff([0.0] + [r.arrival for r in a.requests])
    # rate x window arrivals: every seed's window holds the same set
    assert len(a.requests) == int(np.ceil(4.0 * 45))
    # the mean gap is the rate's
    assert ga.mean() == pytest.approx(1 / 4.0, rel=0.02)
    assert {len(r.prompt) for r in a.requests} == {256, 512, 1024, 2048}
    assert all(16 <= r.max_new <= 512 for r in a.requests)


def test_tokens_follow_the_seed():
    a, b = _plan("chat_open", 11, rate_per_s=2.0), \
        _plan("chat_open", 11, rate_per_s=2.0)
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))
    c = _plan("chat_open", 12, rate_per_s=2.0)
    assert not np.array_equal(a.requests[0].prompt, c.requests[0].prompt)
