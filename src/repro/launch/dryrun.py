import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# a CPU-side tool: 512 virtual host devices stand in for the pods, and a
# TPU (if the machine has one) stays free for the process that serves
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST precede any jax import: jax locks its backends on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes (16x16 single-pod; 2x16x16 multi-pod) with
ShapeDtypeStruct stand-ins (no allocation), print memory_analysis /
cost_analysis, parse the collective schedule, and emit a JSON record
for EXPERIMENTS.md §Dry-run / §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k --mesh pod --out results.json
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_arch, get_shape, shape_applicable  # noqa: E402
from repro.launch import roofline as rl                          # noqa: E402
from repro.launch.mesh import production_ctx                     # noqa: E402
from repro.models import Runtime, build_model                    # noqa: E402
from repro.models import transformer                             # noqa: E402
from repro.training import optimizer as opt                      # noqa: E402
from repro.training.train_loop import TrainState, make_train_step  # noqa: E402


import dataclasses  # noqa: E402


def _sds_with_sharding(model, tree_shapes, specs):
    shardings = model.ctx.tree_shardings(specs, tree_shapes,
                                         fsdp=model.ctx.fsdp_params)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_shapes, shardings)


def build_step(model, shape, *, grad_accum: int = 1):
    """Returns (fn, example_args) ready for jax.jit(fn).lower(*args)."""
    cfg, rt, ctx = model.cfg, model.rt, model.ctx
    pspecs = model.specs()
    pshapes = model.param_shapes()
    params_sds = _sds_with_sharding(model, pshapes, pspecs)
    ins = model.input_specs(shape)

    def in_sds(name):
        s, spec = ins[name]
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=ctx.sharding(spec, s.shape))

    if shape.kind == "train":
        step_fn, _, _ = make_train_step(
            model, opt.AdamWConfig(), grad_accum=grad_accum)
        mu = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                           sharding=s.sharding), params_sds)
        state = TrainState(
            params=params_sds,
            opt_state=opt.OptState(
                step=jax.ShapeDtypeStruct((), jnp.int32), mu=mu,
                nu=jax.tree.map(lambda s: s, mu)))
        batch = {k: in_sds(k) for k in ins if k != "segment_ids"}
        return step_fn, (state, batch)

    if shape.kind == "prefill":
        batch = {k: in_sds(k) for k in ins}

        def prefill_step(params, batch):
            return model.prefill(params, batch)

        return prefill_step, (params_sds, batch)

    # decode
    tokens = in_sds("tokens")
    ctx_lens = in_sds("ctx_lens")
    table = in_sds("block_table")
    caches = {}
    for k in ins:
        if k.startswith("cache/"):
            s, spec = ins[k]
            caches[k.split("/", 1)[1]] = jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=ctx.sharding(spec, s.shape))
    src_valid = in_sds("src_valid") if "src_valid" in ins else None

    def serve_step(params, tokens, caches, ctx_lens, table, src_valid=None):
        return model.decode_step(params, tokens, caches, ctx_lens=ctx_lens,
                                 block_table=table, src_valid=src_valid)

    args = (params_sds, tokens, caches, ctx_lens, table)
    if src_valid is not None:
        args = args + (src_valid,)
    return serve_step, args


def _measure(cfg, shape, ctx, rt_kw, grad_accum):
    """Lower+compile, return (record, compiled artifacts)."""
    model = build_model(cfg, Runtime(**rt_kw), ctx)
    t0 = time.time()
    fn, args = build_step(model, shape, grad_accum=grad_accum)
    with ctx.mesh:
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = rl.collective_bytes(compiled.as_text())
    return {
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed",
                                          "transcendentals") if k in cost},
        "collectives": coll,
    }


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             *, grad_accum: int = 1, rt_overrides=None,
             fsdp: bool = False, dim_fallback: bool = False,
             extrapolate: bool = True) -> dict:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch_name, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "applicable": ok}
    if not ok:
        rec["skip_reason"] = why
        return rec
    ctx = production_ctx(multi_pod=multi_pod)
    if fsdp:
        ctx = dataclasses.replace(ctx, fsdp_params=True)
    if dim_fallback:
        ctx = dataclasses.replace(ctx, spec_dim_fallback=True)
    rt_kw = dict(compute_dtype=jnp.bfloat16, param_dtype=jnp.float32,
                 remat="dots", scan_layers=True)
    rt_kw.update(rt_overrides or {})
    # main compile: full depth, scanned layers (compact HLO, real memory)
    main = _measure(cfg, shape, ctx, rt_kw, grad_accum)
    rec.update(main)
    rec["ok"] = True
    rec["n_devices"] = ctx.n_devices
    cost, coll_total = dict(main["cost"]), main["collectives"]["total_bytes"]
    if extrapolate:
        # XLA's cost analysis counts a while-loop body ONCE; reconstruct
        # true depth costs from 1-period and 2-period unrolled compiles.
        period = cfg.period
        n_periods = cfg.n_layers // period
        if n_periods > 1:
            def depth_cfg(k):
                kw = {"n_layers": k * period}
                if cfg.n_enc_layers:
                    kw["n_enc_layers"] = max(1, cfg.n_enc_layers
                                             * k * period // cfg.n_layers)
                return dataclasses.replace(cfg, **kw)

            rt1 = dict(rt_kw, scan_layers=False)
            m1 = _measure(depth_cfg(1), shape, ctx, rt1, grad_accum)
            m2 = _measure(depth_cfg(2), shape, ctx, rt1, grad_accum)
            cost = rl.extrapolate(m1["cost"], m2["cost"], n_periods)
            cb1 = {"total": m1["collectives"]["total_bytes"]}
            cb2 = {"total": m2["collectives"]["total_bytes"]}
            coll_total = rl.extrapolate(cb1, cb2, n_periods)["total"]
            rec["cost_extrapolated"] = cost
            rec["collective_bytes_extrapolated"] = coll_total
            rec["depth_probe"] = {"p1": m1["cost"], "p2": m2["cost"],
                                  "p1_coll": cb1["total"],
                                  "p2_coll": cb2["total"]}
    mf = rl.model_flops(cfg, shape)
    roof = rl.analyze(cost, {"total_bytes": coll_total},
                      n_devices=ctx.n_devices, model_flops_global=mf)
    rec["roofline"] = roof.as_dict()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--kv-chunk", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--shard-kv-pages", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--compute-dtype", default=None)
    ap.add_argument("--capacity", type=float, default=None)
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--dim-fallback", action="store_true")
    ap.add_argument("--no-extrapolate", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    rt_overrides = {}
    if args.q_chunk:
        rt_overrides["q_chunk"] = args.q_chunk
    if args.kv_chunk:
        rt_overrides["kv_chunk"] = args.kv_chunk
    if args.page_size:
        rt_overrides["page_size"] = args.page_size
    if args.remat:
        rt_overrides["remat"] = args.remat
    if args.shard_kv_pages:
        rt_overrides["shard_kv_pool_pages"] = True
    if args.seq_shard:
        rt_overrides["seq_shard_acts"] = True
    if args.compute_dtype:
        rt_overrides["compute_dtype"] = getattr(jnp, args.compute_dtype)
    if args.capacity:
        rt_overrides["capacity_factor"] = args.capacity
    if args.param_dtype:
        rt_overrides["param_dtype"] = getattr(jnp, args.param_dtype)
    try:
        rec = run_cell(args.arch, args.shape, args.mesh == "multipod",
                       grad_accum=args.grad_accum,
                       rt_overrides=rt_overrides, fsdp=args.fsdp,
                       dim_fallback=args.dim_fallback,
                       extrapolate=not args.no_extrapolate)
    except Exception as e:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    print(json.dumps(rec, indent=2, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2, default=str)
    if not rec.get("ok", rec.get("applicable", False) is False):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
