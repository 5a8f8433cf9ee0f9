"""The one traffic generator: it reads a mix's parameters from
``traffic/<mix>.json`` and turns them, with the cell's serving sizes, its
rate and the run's seed, into a plan of requests.

Every seed gets the same set of sizes and the same arrival times, drawn
once from a fixed generator (sizes by stratified quantiles, gaps as
exponential quantiles in a fixed order); the seed deals the sizes to
the arrivals in its own order and draws the token ids. So every seed
offers the same amount of work, but not in the same order: which long
prefills fall inside the window, and when, differs from seed to seed,
and that is part of the spread between seeds.

Mix keys:
  arrivals        "backlog" (every request queued at the window's open,
                  so the slots never run dry) or "poisson" (open loop
                  at the cell's ``rate_per_s``)
  prompt_tokens   {"values": [...], "weights": [...]}: a fixed set of
                  lengths, since each distinct length compiles a prefill
  output_tokens   {"lognormal_median", "sigma", "min", "max", "round_to"};
                  "max" may be omitted (the context limit bounds it);
                  "round_to" rounds each length, and each request's end,
                  to a multiple (the map compiles one translate per page
                  count a finished request frees)
  n_requests      size of the backlog (backlog arrivals only); a
                  poisson mix has rate x window arrivals, so every seed's
                  window holds the same requests in another order
  first_wave      {"round_to": n}: fill every slot before the window
                  with requests drawn from the mix's stationary state
                  (backlog arrivals only)
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# the fixed generator behind every seed's set of sizes and gaps
SIZES_SEED = 20240607


@dataclasses.dataclass
class Req:
    prompt: np.ndarray           # token ids
    max_new: int
    arrival: Optional[float]     # seconds after the window opens; None: backlog


@dataclasses.dataclass
class Plan:
    first_wave: List[Req]
    requests: List[Req]
    open_loop: bool

    def prefill_lengths(self) -> List[int]:
        return sorted({len(r.prompt) for r in self.first_wave + self.requests})


def load(bench_dir: str, mix: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{mix}.json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _prompt_set(spec: dict, n: int) -> np.ndarray:
    """n prompt lengths in the mix's proportions (largest remainders)."""
    vals = np.asarray(spec["values"], np.int64)
    w = np.asarray(spec["weights"], np.float64)
    w = w / w.sum()
    counts = np.floor(w * n).astype(int)
    rest = np.argsort(-(w * n - counts), kind="stable")
    counts[rest[:n - counts.sum()]] += 1
    return np.repeat(vals, counts)


def _output_set(spec: dict, prompts: np.ndarray, max_ctx: int,
                rng: np.random.Generator) -> np.ndarray:
    """Lognormal output lengths at stratified quantiles, paired with the
    prompts in a fixed shuffle, clipped to [min, max] and the context."""
    n = len(prompts)
    z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    out = spec["lognormal_median"] * np.exp(spec["sigma"] * z)
    out = out[rng.permutation(n)]
    hi = np.full(n, max_ctx) - prompts
    if "max" in spec:
        hi = np.minimum(hi, spec["max"])
    step = int(spec.get("round_to", 1))
    out = np.round(out / step) * step
    return np.clip(out, spec["min"], hi).astype(np.int64)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)


def make_plan(mix: dict, *, n_slots: int, max_ctx: int, vocab: int,
              seed: int, seconds: float, rate_per_s: Optional[float] = None
              ) -> Plan:
    fixed = np.random.default_rng(SIZES_SEED)
    run = np.random.default_rng(int(seed))
    if mix["arrivals"] == "backlog":
        n = int(mix["n_requests"])
    elif mix["arrivals"] == "poisson":
        if not rate_per_s:
            raise ValueError("a poisson mix needs the cell's rate_per_s")
        n = int(math.ceil(rate_per_s * seconds))
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    prompts = _prompt_set(mix["prompt_tokens"], n)[fixed.permutation(n)]
    outs = _output_set(mix["output_tokens"], prompts, max_ctx, fixed)
    first: List[Req] = []
    fw = mix.get("first_wave")
    if fw:
        first = _first_wave(fw, prompts, outs, n_slots, max_ctx, fixed,
                            int(mix["output_tokens"].get("round_to", 1)))
    arrivals = [None] * n
    if mix["arrivals"] == "poisson":
        gaps = -np.log1p(-_quantiles(n)) / rate_per_s
        arrivals = np.cumsum(gaps[fixed.permutation(n)]).tolist()
    order = run.permutation(n)
    reqs = [Req(None, int(outs[i]), a) for i, a in zip(order, arrivals)]
    for r, i in zip(reqs, order):
        r.prompt = _tokens(run, int(prompts[i]), vocab)
    for r in first:
        r.prompt = _tokens(run, len(r.prompt), vocab)
    run.shuffle(first)
    return Plan(first, reqs, mix["arrivals"] == "poisson")


def _first_wave(fw: dict, prompts, outs, n_slots: int, max_ctx: int,
                fixed: np.random.Generator, out_step: int) -> List[Req]:
    """One request per slot in the backlog's stationary state: a slot is
    found inside request i with odds proportional to its output length,
    at a uniform point of it. Its context so far becomes the prompt,
    rounded down to ``round_to`` (so few prefill lengths compile), and
    the rest of its output its budget, rounded like every output."""
    step = int(fw["round_to"])
    p = outs / outs.sum()
    wave = []
    for i in fixed.choice(len(outs), n_slots, p=p):
        done = int(fixed.integers(0, outs[i]))
        ctx = max(step, (int(prompts[i]) + done) // step * step)
        left = int(round((int(outs[i]) - done) / out_step)) * out_step
        left = max(out_step, min(left, max_ctx - ctx))
        wave.append(Req(np.zeros(ctx, np.int32), left, None))
    return wave
