"""One run of one cell: build the served model from the seed, warm every
shape the cell's traffic reaches, measure a window of ``ServeEngine``
steps, then check what the window served against the plain reference.

Everything that belongs to one cell is found by name:
  BENCHMARK.json      the cell, its configuration's file, its metrics
  configs/<c>.json    sizes (the model's own config.json keys) + serving
  families/<t>.py     the model family its ``model_type`` names: sizes,
                      the program's architecture, seeded weights, the
                      reference's layers and the FLOP and byte counts
                      (load_family says what a family module supplies)
  traffic/<m>.json    the mix, read by traffic.py
  cells/<w>.json      the cell's rate and the limits of its comparison
  metrics/<n>.py      read(run) -> number or None, one file per metric

The harness drives only the engine's public surface: ``submit``,
``step``, ``active``, ``queue``, ``metrics``, ``kvm`` (``hit_stats``,
and ``new_seq``/``free_seq`` to compile the map's lane counts in
set-up), plus the profiler.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))

import traffic  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, a compile in the
    window, a broken cell definition)."""


# ---------------------------------------------------------------- specs
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: object               # the module families/<model_type>.py
    dims: object                 # family.dims(config)
    serving: dict
    mix: dict
    params: dict                 # cells/<name>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH_DIR


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = [w for w in bench["workloads"] if w["name"] == name]
    if not wl:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    wl = wl[0]
    entry = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "cells", f"{name}.json")) as f:
        params = json.load(f)
    family = load_family(config.get("model_type"), bench_dir)
    return Cell(
        name=name, chips=int(wl["chips"]), config=config, family=family,
        dims=family.dims(config), serving=config["serving"],
        mix=traffic.load(bench_dir, wl["traffic"]), params=params,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def load_family(model_type: str, bench_dir: str = BENCH_DIR):
    """The module ``families/<model_type>.py``. It supplies

      dims(config)            the family's sizes, read from the published
                              config.json keys; the harness and traffic.py
                              read ``n_layers``, ``d_model``, ``vocab`` and
                              ``max_ctx`` of them
      arch(d, name)           the program's ArchConfig (on one chip:
                              build_engine builds no mesh)
      layer_kinds(d)          the kind of each layer
      make_layer(key, i, d, kind)   layer i's weights (bf16) from the
                              seed's key (weights.root_key)
      make_outer(key, d)      the weights outside the layers: "embed",
                              "final_norm", "head"
      to_program(w, d)        the served weights, which weights.make
                              builds from those two in one jitted call
                              ({"layers": {kind: [layers of it, ...]},
                              **outer}), as the program's parameter tree
      forward_layer(x, w, pos, d, kind, fp8, q_block)   one layer of that
                              kind over the sequence x [S, d_model], plain
                              float32, fp8-rounded operands with ``fp8``
      embed(outer, tokens, d), head(x, outer, fp8, d)
                              the reference's embedding and logits
      prefill_flops(d, p), decode_run_flops(d, first_keys, n),
      paged_attn_run(d, first_keys, n)   what Driver._book counts

    The dense families take all but ``dims`` from dense.py."""
    where = os.path.join(bench_dir, "families")
    found = sorted(f[:-3] for f in os.listdir(where) if f.endswith(".py"))
    if model_type not in found:
        raise BenchError(f"model_type {model_type!r} has no family module "
                         f"in {where}; found: {found}")
    path = os.path.join(where, f"{model_type}.py")
    spec = importlib.util.spec_from_file_location(
        f"family_{model_type}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ observing
class CompileWatch:
    """Counts backend compiles (persistent-cache loads included) by
    function name, through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.names: List[str] = []
        self.seconds = 0.0

        def on(event, duration, **kw):
            if event == BACKEND_COMPILE_EVENT:
                self.names.append(str(kw.get("fun_name", "?")))
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on)
        self._on = on

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class ReqLog:
    prompt: np.ndarray
    arrival: Optional[float]        # absolute perf_counter time, open loop
    admitted: Optional[float] = None   # start of the step that admitted it
    first_token: Optional[float] = None
    seen: int = 0
    last: Optional[float] = None
    finished: Optional[float] = None
    out: Optional[list] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Run:
    """What one run observed; the metric readers take it whole."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    t_open: float = 0.0
    t_close: float = 0.0
    steps: List[tuple] = dataclasses.field(default_factory=list)
    gaps_s: List[float] = dataclasses.field(default_factory=list)
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    queue_wait_s: List[float] = dataclasses.field(default_factory=list)
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    tokens_out: int = 0
    prompt_tokens: int = 0          # prefilled in the window
    model_flops: float = 0.0        # prefill + decode in the window
    pa_flops: float = 0.0           # paged attention in the window
    pa_bytes: float = 0.0
    engine_delta: Dict[str, int] = dataclasses.field(default_factory=dict)
    compiles_in_window: List[str] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    trace: object = None            # xtrace.Reduced in a --trace 1 run
    peaks: Optional[dict] = None
    requests: Dict[int, ReqLog] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def pct(xs, q: float) -> float:
    """q-th percentile of all samples (linear interpolation)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


# ---------------------------------------------------------------- model
def build_engine(cell: Cell, seed: int):
    """The system under test: the program's model at the configuration's
    sizes, with the benchmark's seeded weights, behind ServeEngine's
    K-step macro path (channels=1; GC, prefix sharing, journal, faults
    and host tier off)."""
    import jax
    import jax.numpy as jnp

    from repro.models import Runtime, build_model
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServeEngine

    import weights

    if cell.chips != 1:
        raise BenchError(f"{cell.name} asks for {cell.chips} chips; "
                         "build_engine places the model and the map on "
                         "one chip only")
    fam, d, s = cell.family, cell.dims, cell.serving
    arch = fam.arch(d, cell.config.get("name", cell.name))
    rt = Runtime(compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                 page_size=int(s["page_size"]))
    model = build_model(arch, rt)
    params = fam.to_program(weights.make(fam, seed, d), d)
    want = model.param_shapes()
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        raise BenchError("the program's parameter tree no longer matches "
                         f"the {cell.config['model_type']} family's "
                         "to_program")
    eng = ServeEngine(model, params, config=ServeConfig(
        n_slots=int(s["n_slots"]), max_ctx=d.max_ctx,
        macro_k=int(s["macro_k"]), channels=1))
    return eng


# -------------------------------------------------------------- warm-up
def _bucket(n: int, lo: int, max_pages: int) -> int:
    p = lo
    while p < n and p < max_pages:
        p *= 2
    return min(p, max_pages)


def lone_scans(L: int, max_new: int, K: int, page: int, max_pages: int,
               lo: int) -> set:
    """(simple?, page bucket) of each scan a request of prompt L and
    budget max_new runs when it is alone: the engine's own arithmetic
    (ServeEngine._macro_decode_step, _growth_walk, _page_bucket)."""
    ctx, npg, out, seen = L, -(-L // page), 1, set()

    def grow(n):
        nonlocal npg
        for k in range(n):
            if (ctx + k + page) // page > npg and npg < max_pages:
                npg += 1

    while out < max_new:
        budget = max_new - out
        if budget >= K:
            grow(K)
            seen.add((True, _bucket(npg, lo, max_pages)))
            ctx, out = ctx + K, out + K
        else:
            end = min(max_pages, max(npg, (ctx + K + page - 1) // page))
            seen.add((False, _bucket(end, lo, max_pages)))
            grow(budget)
            ctx, out = ctx + budget, out + budget
    return seen


def warm_plan(lengths: List[int], K: int, page: int, max_pages: int,
              max_ctx: int, lo: int) -> List[tuple]:
    """Lone (prompt, max_new) requests whose scans cover both scan
    variants at every page bucket the cell's traffic can reach."""
    lmin = min(lengths)
    first = _bucket((lmin + page) // page, lo, max_pages)
    buckets, b = [], first
    while True:
        buckets.append(b)
        if b >= max_pages:
            break
        b = min(2 * b, max_pages)
    need = {(v, b) for v in (True, False) for b in buckets}
    plan, covered = [], set()
    for L in lengths:
        n = min(K + 2, max_ctx - L)
        got = lone_scans(L, n, K, page, max_pages, lo)
        if got - covered:
            plan.append((L, n, got))
            covered |= got
    for v, b in sorted(need - covered):
        if (v, b) in covered:
            continue
        for L in sorted(lengths, reverse=True):
            hit = None
            for n in range(K + 2, max_ctx - L + 1):
                s = lone_scans(L, n, K, page, max_pages, lo)
                if (v, b) in s:
                    hit = (L, n, s)
                    break
            if hit:
                plan.append(hit)
                covered |= hit[2]
                break
        else:
            raise BenchError(f"no warm-up request reaches scan {(v, b)}")
    return [(L, n) for L, n, _ in plan]


def lane_counts(plans, page: int, max_pages: int) -> List[int]:
    """Page counts the map's translate runs over for the plans' requests:
    ``new_seq`` maps ceil(P / page) pages, and ``free_seq`` unmaps what a
    finished request grew to (the engine's growth arithmetic: decoding at
    context c needs c // page + 1 pages, the last at c = P + n - 2)."""
    out = set()
    for plan in plans:
        for r in plan.first_wave + plan.requests:
            P, n = len(r.prompt), r.max_new
            first = -(-P // page)
            out.add(first)
            out.add(min(max_pages, max(first, (P + n - 2) // page + 1)
                        if n >= 2 else first))
    return sorted(out)


def warm_up(eng, plans, vocab: int, rng, phase=lambda what: None) -> None:
    """Compile, before the window, every program the window will run:
    the prefill of each prompt length, both K-step scan variants at each
    reachable page bucket, the slot-indexed host programs of every slot,
    and the map's translate at every lane count the plans' requests map
    or free (``new_seq``/``free_seq`` do not pad their lanes)."""
    K, page, S = eng.macro_k, eng.page, eng.n_slots
    max_pages, lo = eng.max_pages, eng.min_page_bucket
    max_ctx = max_pages * page
    lengths = sorted({n for p in plans for n in p.prefill_lengths()})
    done: dict = {}

    def serve(reqs):
        for L, n in reqs:
            eng.submit(rng.integers(0, vocab, L).tolist(), max_new=n)
        while eng.step(done):
            pass

    for L, n in warm_plan(lengths, K, page, max_pages, max_ctx, lo):
        serve([(L, n)])
    phase("prefills and scans warm")
    serve([(min(lengths), K + 2)] * S)       # every slot once
    phase("every slot warm")
    for n in lane_counts(plans, page, max_pages):
        eng.kvm.new_seq(0, n)
        eng.kvm.free_seq(0)
    phase("map lane counts warm")


# --------------------------------------------------------------- window
class Driver:
    """Steps the engine and books what each step made visible."""

    def __init__(self, eng, run: Run):
        self.eng = eng
        self.run = run
        self.done: Dict[int, List[int]] = {}
        self.fam, self.d = run.cell.family, run.cell.dims

    def submit(self, req: traffic.Req, arrival_abs=None) -> int:
        rid = self.eng.submit(req.prompt.tolist(), max_new=req.max_new)
        self.run.requests[rid] = ReqLog(req.prompt, arrival_abs)
        return rid

    def step(self, in_window: bool):
        import jax
        eng = self.eng
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step(self.done)
        t1 = time.perf_counter()
        if in_window:
            self.run.steps.append((t0, t1))
        with jax.profiler.TraceAnnotation("bench.book"):
            self._book(t0, t1, in_window)
        return t0, t1

    def _book(self, t0: float, t1: float, in_window: bool):
        run, fam, d = self.run, self.fam, self.d
        visible = {rid: (r.out, False) for rid, r in self.eng.active.items()}
        for rid, out in self.done.items():
            log = run.requests.get(rid)
            if log is not None and log.finished is None:
                visible[rid] = (out, True)
        for rid, (out, fin) in visible.items():
            log = run.requests.get(rid)
            if log is None:
                continue
            if log.admitted is None:
                log.admitted = t0
                if in_window and log.arrival is not None:
                    run.queue_wait_s.append(t0 - log.arrival)
            n = len(out)
            if fin:
                log.finished = t1 if in_window else -1.0
                log.out = list(out)
            if n <= log.seen:
                continue
            a, b = log.seen, n
            if in_window:
                run.tokens_out += b - a
                if a == 0:
                    run.prompt_tokens += log.prompt_len
                    run.model_flops += fam.prefill_flops(d, log.prompt_len)
                lo_ = max(a, 1)
                run.model_flops += fam.decode_run_flops(
                    d, log.prompt_len + lo_, b - lo_)
                f, by = fam.paged_attn_run(d, log.prompt_len + lo_, b - lo_)
                run.pa_flops += f
                run.pa_bytes += by
                if log.last is not None:
                    run.gaps_s.append(t1 - log.last)
            if a == 0:
                log.first_token = t1
                if in_window and log.arrival is not None:
                    run.ttft_s.append(t1 - log.arrival)
            log.seen, log.last = n, t1


def _engine_counts(eng) -> Dict[str, float]:
    out = {f"engine.{k}": v for k, v in eng.metrics.items()}
    stats = eng.kvm.hit_stats().as_dict()
    out.update({f"map.{k}": v for k, v in stats.items()
                if isinstance(v, (int, float))})
    return out


def run_cell(cell: Cell, seed: int, seconds: float, *, trace: bool,
             t_start: float, require_tpu: bool = True,
             control: bool = False, log=print) -> dict:
    """One run; returns the result line's object. ``require_tpu=False``
    is for tests of the harness on the CPU."""
    import jax

    import peaks as peaks_mod

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX platform is {devs[0].platform!r}")
        if len(devs) < cell.chips:
            raise BenchError(f"{cell.chips} chips asked for, {len(devs)} "
                             "found")
        peaks = peaks_mod.peaks_for(devs[0].device_kind)
    else:
        peaks = peaks_mod.PEAKS["TPU v5 lite"]
    watch = CompileWatch()
    run = Run(cell=cell, seed=seed, seconds=seconds, peaks=peaks)
    s = cell.serving
    plan = traffic.make_plan(
        cell.mix, n_slots=int(s["n_slots"]), max_ctx=cell.dims.max_ctx,
        vocab=cell.dims.vocab, seed=seed, seconds=seconds,
        rate_per_s=cell.params.get("rate_per_s"))

    # ---- set-up: weights, engine, every shape, the first wave
    def phase(what):
        log(f"set-up {time.perf_counter() - t_start:.3f} s: {what} "
            f"({len(watch.names)} compiles or cache loads, "
            f"{watch.seconds:.3f} s)")

    phase("process and JAX up")
    eng = build_engine(cell, seed)
    phase("weights made, engine built")
    warm_up(eng, [plan], cell.dims.vocab, np.random.default_rng(0), phase)
    drv = Driver(eng, run)
    for r in plan.first_wave:
        drv.submit(r)
    if plan.first_wave:
        drv.step(in_window=False)
    if not plan.open_loop:
        for r in plan.requests:
            drv.submit(r)
    phase("first wave prefilled")
    # set-up leaves a large heap of tracing garbage: collect it now and
    # freeze the survivors, so no full collection lands in the window
    gc.collect()
    gc.freeze()

    # ---- the measured window, then the open loop's drain
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    measure(eng, drv, run, plan, seconds, t_start, watch,
            stop_trace=trace)
    mem = devs[0].memory_stats() or {}
    mem_peak = int(mem.get("peak_bytes_in_use", 0))
    log("device memory after the window: " + json.dumps(mem))

    if trace:
        import xtrace
        run.trace = xtrace.reduce(xtrace.load_dir(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- correctness: the plain reference over a sample of what the
    # window finished, after the program's state is gone
    sample = _sample(run, seed, cell.params.get("sample", {}))
    gc.unfreeze()           # the engine's cycles must be collectable now
    del eng, drv
    gc.collect()
    log(f"program freed: {(devs[0].memory_stats() or {}).get('bytes_in_use')}"
        " bytes in use on the device before the reference")
    checks, served_gap = _compare(run, sample, seed, control, log)

    watch.close()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"], cell.bench_dir)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    _log_summary(run, log)
    if run.compiles_in_window:
        raise BenchError(f"{len(run.compiles_in_window)} compiles inside "
                         f"the window: {sorted(set(run.compiles_in_window))}")
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and run.failed == 0 and bool(sample)
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    if served_gap is not None:
        out["control"] = served_gap
    out["checks"] = checks
    return out


def measure(eng, drv: Driver, run: Run, plan: traffic.Plan, seconds: float,
            t_start: float, watch: CompileWatch, stop_trace: bool = False):
    """The measured window: arrivals on schedule (open loop) or the
    backlog, one step() after another until ``seconds`` have passed.
    Then, for an open loop, every request due in the window is stepped
    to its first token, with no later arrivals and no output counted."""
    import jax
    n_setup = len(watch.names)
    base = _engine_counts(eng)
    pending = list(plan.requests) if plan.open_loop else []
    run.t_open = t_open = time.perf_counter()
    run.setup_s = t_open - t_start
    end = t_open + seconds
    win = jax.profiler.TraceAnnotation("bench.window")
    win.__enter__()
    last = t_open
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if plan.open_loop:
            with jax.profiler.TraceAnnotation("bench.arrivals"):
                while pending and t_open + pending[0].arrival <= now:
                    r = pending.pop(0)
                    due = t_open + r.arrival
                    run.lateness_s.append(now - due)
                    drv.submit(r, arrival_abs=due)
            if not eng.active and not eng.queue:
                nxt = t_open + pending[0].arrival if pending else end
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
                continue
        elif not eng.active and not eng.queue:
            raise BenchError("the backlog ran dry inside the window: give "
                             "the mix more requests")
        _, last = drv.step(in_window=True)
    run.t_close = max(last, t_open)
    win.__exit__(None, None, None)
    # arrivals due while the last step ran are submitted first, and every
    # request due in the window is stepped to its first token before the
    # trace (clipped to the window) is written out
    with jax.profiler.TraceAnnotation("bench.arrivals"):
        now = time.perf_counter()
        while pending and t_open + pending[0].arrival <= run.t_close:
            r = pending.pop(0)
            run.lateness_s.append(now - (t_open + r.arrival))
            drv.submit(r, arrival_abs=t_open + r.arrival)
    run.compiles_in_window = watch.names[n_setup:]
    run.engine_delta = {k: v - base.get(k, 0)
                        for k, v in _engine_counts(eng).items()}
    due = [r for r in run.requests.values()
           if r.arrival is not None and r.arrival <= run.t_close]
    t_drain = time.perf_counter()
    while any(r.first_token is None for r in due) \
            and time.perf_counter() - t_drain < 120:
        _, t1 = drv.step(in_window=False)
        for r in due:
            if r.first_token == t1:
                run.ttft_s.append(t1 - r.arrival)
                run.queue_wait_s.append(r.admitted - r.arrival)
    if stop_trace:
        jax.profiler.stop_trace()
    run.attempted = len(due) if plan.open_loop else sum(
        1 for r in run.requests.values()
        if r.last is not None and r.last >= run.t_open)
    run.failed = sum(1 for r in due if r.first_token is None)


# ---------------------------------------------------------- correctness
def _sample(run: Run, seed: int, spec: dict) -> List[int]:
    """Requests the window finished, drawn from the seed, the longest
    first, until ``min_tokens`` served tokens or ``max_requests``."""
    min_tokens = int(spec.get("min_tokens", 512))
    max_req = int(spec.get("max_requests", 4))
    fin = [rid for rid, r in run.requests.items()
           if r.finished is not None and r.finished > 0 and r.out]
    if not fin:
        return []
    longest = max(fin, key=lambda rid: (run.requests[rid].prompt_len
                                         + len(run.requests[rid].out), -rid))
    rng = np.random.default_rng(int(seed) + 1)
    rest = [rid for rid in rng.permutation(sorted(fin)).tolist()
            if rid != longest]
    pick, served = [longest], len(run.requests[longest].out)
    for rid in rest:
        if served >= min_tokens or len(pick) >= max_req:
            break
        pick.append(rid)
        served += len(run.requests[rid].out)
    return pick


def _compare(run: Run, sample, seed, control: bool, log):
    """The widest gap by which a judged token's reference logit lies below
    the reference's best, over the sample. The judged tokens are those the
    window served; with ``control`` they are instead the fp8 reference's
    first choices at the same positions of the same prompts and served
    tokens, so the control goes through the same limit and must fail it.
    Returns the checks and, with ``control``, the served tokens' gap."""
    import reference
    lim = run.cell.params["limits"]["max_logit_gap"]
    if not sample:
        log("check: the window finished no request to compare")
        return {"max_logit_gap": {"value": float("inf"), "limit": lim}}, None
    ref = reference.Reference(run.cell.family, run.cell.dims, seed)
    served, ctrl, n_tok = 0.0, 0.0, 0
    t0 = time.perf_counter()
    for rid in sample:
        r = run.requests[rid]
        g = ref.gaps(r.prompt, r.out, control=control)
        served = max(served, float(g["program"].max()))
        n_tok += len(r.out)
        if control:
            ctrl = max(ctrl, float(g["fp8"].max()))
        log(f"check: request {rid} prompt {r.prompt_len} served "
            f"{len(r.out)}: max logit gap {float(g['program'].max())!r}"
            + (f", fp8 control {float(g['fp8'].max())!r}" if control else ""))
    log(f"check: reference over {len(sample)} requests, {n_tok} served "
        f"tokens, {time.perf_counter() - t0:.3f} s"
        + ("; judging the fp8 control's tokens" if control else ""))
    checks = {"max_logit_gap": {"value": ctrl if control else served,
                                "limit": lim}}
    return checks, ({"served_max_logit_gap": served} if control else None)


def _log_summary(run: Run, log):
    def q(xs, unit=1e3):
        if not xs:
            return "none"
        return (f"p50 {pct(xs, 50) * unit:.3f} p95 {pct(xs, 95) * unit:.3f} "
                f"max {max(xs) * unit:.3f} (n={len(xs)})")
    log(f"window {run.window_s:.3f} s, {len(run.steps)} steps, "
        f"{run.tokens_out} tokens out, {run.prompt_tokens} prompt tokens "
        f"prefilled")
    log(f"delivery gap ms: {q(run.gaps_s)}")
    log(f"ttft ms: {q(run.ttft_s)}")
    log(f"queue wait ms: {q(run.queue_wait_s)}")
    log(f"generator lateness ms: {q(run.lateness_s)}")
    log("engine/map counts in window: " + json.dumps(
        {k: v for k, v in run.engine_delta.items() if v}))
    log(f"setup_s {run.setup_s:.3f}; compiles in window: "
        f"{len(run.compiles_in_window)}")
