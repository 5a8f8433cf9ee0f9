"""Logical-axis sharding rules resolved onto a concrete mesh.

Model code emits *logical* PartitionSpecs using the names 'data' and
'model'. A ParallelCtx maps 'data' -> the (possibly compound) batch axes
(('pod','data') on the multi-pod mesh) and 'model' -> the tensor axis,
and replicates any dimension whose size does not divide its mesh extent
(e.g. arctic's 56 Q heads on a 16-way model axis) instead of relying on
implicit GSPMD padding — the decision is explicit and logged.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False,
              axis_names=None):
    """``jax.shard_map`` with replication checks off by default;
    ``axis_names`` (optional) limits the manual axes."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, **kw)


def channel_mesh(n_channels: int) -> Mesh:
    """1-D mesh over the FMMU channel axis (ISSUE-5 map sharding): one
    device per channel. CI's tier1-sharded lane provides 8 host-platform
    devices via XLA_FLAGS=--xla_force_host_platform_device_count=8; on
    real hardware the channels ride the accelerator mesh."""
    if len(jax.devices()) < n_channels:
        raise ValueError(
            f"channel_mesh({n_channels}): only {len(jax.devices())} "
            "devices visible (set --xla_force_host_platform_device_count"
            " or shard with the vmap lowering instead)")
    return make_mesh((n_channels,), ("channel",))


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: Mesh
    dp: Tuple[str, ...] = ("data",)   # batch axes, outermost first
    tp: str = "model"
    ch: Optional[str] = None   # FMMU channel axis (map-state sharding);
    #                            None = unsharded map (pre-ISSUE-5)
    fsdp_params: bool = False  # ZeRO-3/FSDP: also shard params over dp
    spec_dim_fallback: bool = False  # non-dividing dim: slide the axis to
    #                                  the next dividing dim (e.g. arctic's
    #                                  56 heads -> shard head_dim instead)

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp]))

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.tp])

    @property
    def ch_size(self) -> int:
        return int(self.mesh.shape[self.ch]) if self.ch else 1

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    def axis_size(self, logical) -> int:
        names = self._physical(logical)
        if names is None:
            return 1
        if isinstance(names, str):
            return int(self.mesh.shape[names])
        return int(np.prod([self.mesh.shape[a] for a in names]))

    def _physical(self, logical):
        if logical is None:
            return None
        if logical == "data":
            return self.dp if len(self.dp) > 1 else self.dp[0]
        if logical == "model":
            return self.tp
        if logical == "channel":
            return self.ch
        if isinstance(logical, (tuple, list)):
            out = []
            for l in logical:
                p = self._physical(l)
                if p is None:
                    continue
                out.extend(p if isinstance(p, tuple) else (p,))
            return tuple(out) if out else None
        return logical  # already a physical axis name

    def resolve(self, spec: P, shape: Optional[Tuple[int, ...]] = None,
                fsdp: bool = False) -> P:
        """Logical spec -> physical spec; non-dividing dims replicated."""
        phys = []
        carry = []   # axes displaced by non-dividing dims (fallback mode)
        for i, s in enumerate(spec):
            p = self._physical(s)
            if p is None and carry and shape is not None and i < len(shape):
                cand = carry[0]
                ext = (int(np.prod([self.mesh.shape[a] for a in cand]))
                       if isinstance(cand, tuple)
                       else int(self.mesh.shape[cand]))
                if shape[i] % ext == 0:
                    p = carry.pop(0)
            if p is not None and shape is not None and i < len(shape):
                ext = (int(np.prod([self.mesh.shape[a] for a in p]))
                       if isinstance(p, tuple) else int(self.mesh.shape[p]))
                if shape[i] % ext != 0:
                    if self.spec_dim_fallback:
                        carry.append(p)
                    p = None  # replicate: dimension does not divide
            phys.append(p)
        if fsdp and shape is not None and len(shape) >= 2:
            # ZeRO-3: shard the largest still-open dim over the data axes
            # (GSPMD inserts the just-in-time all-gathers)
            dp = self.dp if len(self.dp) > 1 else self.dp[0]
            best, best_n = -1, 0
            for i, n in enumerate(shape):
                cur = phys[i] if i < len(phys) else None
                if cur is None and n % self.dp_size == 0 and n > best_n:
                    best, best_n = i, n
            if best >= 0:
                while len(phys) <= best:
                    phys.append(None)
                phys[best] = dp
        while phys and phys[-1] is None:
            phys.pop()
        return P(*phys)

    def sharding(self, spec: P, shape: Optional[Tuple[int, ...]] = None,
                 fsdp: bool = False) -> NamedSharding:
        return NamedSharding(self.mesh, self.resolve(spec, shape, fsdp=fsdp))

    def constraint(self, x, spec: P):
        return jax.lax.with_sharding_constraint(
            x, self.sharding(spec, tuple(x.shape)))

    def tree_shardings(self, specs, shapes, fsdp: bool = False):
        """specs: pytree of logical P; shapes: matching pytree of
        array-likes or ShapeDtypeStructs. fsdp applies ZeRO-3 data-axis
        sharding on top (parameter trees only)."""
        return jax.tree.map(
            lambda s, a: self.sharding(s, tuple(a.shape), fsdp=fsdp),
            specs, shapes,
            is_leaf=lambda s: isinstance(s, P))


def trivial_ctx() -> ParallelCtx:
    """1x1 mesh for single-device tests; same axis names as production."""
    return ParallelCtx(mesh=make_mesh((1, 1), ("data", "model")))


def test_ctx(data: int = 2, model: int = 2) -> ParallelCtx:
    return ParallelCtx(mesh=make_mesh((data, model), ("data", "model")))


def channel_ctx(channels: int, data: int = 1,
                model: int = 1) -> ParallelCtx:
    """Mesh with an FMMU 'channel' axis alongside data/model: logical
    'channel' specs resolve onto it (map-state leaves carry a leading
    channel dim), everything else is unaffected."""
    return ParallelCtx(
        mesh=make_mesh((data, model, channels),
                       ("data", "model", "channel")),
        ch="channel")
