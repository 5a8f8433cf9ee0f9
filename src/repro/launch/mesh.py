"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (jax locks the device count on first init)."""
from __future__ import annotations

from repro.parallel.sharding import ParallelCtx, make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def production_ctx(*, multi_pod: bool = False) -> ParallelCtx:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = ("pod", "data") if multi_pod else ("data",)
    return ParallelCtx(mesh=mesh, dp=dp)
