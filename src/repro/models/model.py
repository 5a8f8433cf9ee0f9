"""Top-level Model: embeddings, stacks, head, losses, prefill/decode.

``build_model(cfg, rt, ctx)`` returns a Model whose methods are pure
functions of (params, batch) — ready for jax.jit with shardings from
``Model.param_shardings()`` / ``Model.input_specs()``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import attention, common, transformer
from repro.models.common import Runtime
from repro.parallel.sharding import ParallelCtx


def _src_len(cfg: ArchConfig, seq_len: int) -> int:
    return max(128, seq_len // 4) if cfg.n_enc_layers else 0


def _prefix_len(cfg: ArchConfig, seq_len: int) -> int:
    return min(cfg.prefix_len, seq_len // 2) if cfg.prefix_len else 0


@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    rt: Runtime
    ctx: ParallelCtx

    # ------------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg, dt = self.cfg, self.rt.param_dtype
        ks = jax.random.split(key, 4)
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                        jnp.float32) * 0.02).astype(dt),
            "stack": transformer.init_stack(ks[1], cfg, dt,
                                            cross=bool(cfg.n_enc_layers)),
            "final_norm": common.init_rms_norm(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["head"] = common.init_dense(
                ks[2], cfg.d_model, cfg.vocab_size, dt)
        if cfg.n_enc_layers:
            enc_cfg = dataclasses.replace(
                cfg, n_layers=cfg.n_enc_layers, moe=None, attn_every=0,
                layer_pattern=())
            params["enc_stack"] = transformer.init_stack(ks[3], enc_cfg, dt)
            params["enc_norm"] = common.init_rms_norm(cfg.d_model, dt)
        return params

    def specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "embed": P("model", None),
            "stack": transformer.stack_specs(cfg, cross=bool(cfg.n_enc_layers)),
            "final_norm": P(None,),
        }
        if not cfg.tie_embeddings:
            specs["head"] = P(None, "model")
        if cfg.n_enc_layers:
            enc_cfg = dataclasses.replace(
                cfg, n_layers=cfg.n_enc_layers, moe=None, attn_every=0,
                layer_pattern=())
            specs["enc_stack"] = transformer.stack_specs(enc_cfg)
            specs["enc_norm"] = P(None,)
        return specs

    def param_shardings(self, params_or_shapes):
        return self.ctx.tree_shardings(self.specs(), params_or_shapes,
                                       fsdp=self.ctx.fsdp_params)

    def param_shapes(self, ) -> Dict[str, Any]:
        return jax.eval_shape(lambda k: self.init(k), jax.random.key(0))

    # ------------------------------------------------------------------
    def _embed(self, params, tokens):
        x = params["embed"][tokens].astype(self.rt.compute_dtype)
        if self.cfg.name.startswith("gemma"):
            x = x * jnp.asarray(math.sqrt(self.cfg.d_model), x.dtype)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(self.cfg.embedding_multiplier, x.dtype)
        return x

    def _fuse_inputs(self, params, batch):
        """tokens (+ prefix embeddings) -> x [B,S,d], positions [B,S]."""
        x = self._embed(params, batch["tokens"])
        if "prefix_emb" in batch:
            pre = batch["prefix_emb"].astype(self.rt.compute_dtype)
            x = jnp.concatenate([pre, x], axis=1)
        b, s = x.shape[:2]
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        spec = (P("data", "model", None) if self.rt.seq_shard_acts
                else P("data", None, None))
        return self.ctx.constraint(x, spec), positions

    def _encode(self, params, batch):
        if not self.cfg.n_enc_layers:
            return None, None
        enc_cfg = dataclasses.replace(
            self.cfg, n_layers=self.cfg.n_enc_layers, moe=None,
            attn_every=0, layer_pattern=())
        src = batch["src_emb"].astype(self.rt.compute_dtype)
        pos = jnp.broadcast_to(jnp.arange(src.shape[1])[None], src.shape[:2])
        enc_model = dataclasses.replace(self, cfg=enc_cfg)
        enc_out, _, _ = transformer.stack_forward(
            params["enc_stack"], src, enc_cfg, self.rt, self.ctx,
            positions=pos, bidirectional=True)
        enc_out = common.rms_norm(enc_out, params["enc_norm"], enc_cfg.norm_eps)
        return enc_out, batch.get("src_valid")

    def _logits(self, params, x):
        cd = self.rt.compute_dtype
        if self.cfg.tie_embeddings:
            logits = x @ common.cast(params["embed"], cd).T
        else:
            logits = x @ common.cast(params["head"], cd)
        logits = logits.astype(jnp.float32)
        if self.cfg.logits_scaling != 1.0:
            logits = logits / self.cfg.logits_scaling
        return common.softcap(logits, self.cfg.final_softcap)

    # ------------------------------------------------------------------
    def loss_fn(self, params, batch) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """Train loss. batch: tokens [B,S], labels [B,S] (-1 = masked),
        optional positions/segment_ids/prefix_emb/src_emb/src_valid."""
        x, positions = self._fuse_inputs(params, batch)
        enc_out, src_valid = self._encode(params, batch)
        x, aux, _ = transformer.stack_forward(
            params["stack"], x, self.cfg, self.rt, self.ctx,
            positions=positions, segment_ids=batch.get("segment_ids"),
            enc_out=enc_out, src_valid=src_valid)
        x = common.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        labels = batch["labels"]
        if "prefix_emb" in batch:   # loss only on the text tail
            x = x[:, -labels.shape[1]:]
        logits = self._logits(params, x)
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mask
        ntok = jnp.maximum(mask.sum(), 1.0)
        loss = nll.sum() / ntok
        metrics = {"nll": loss, "aux_loss": aux, "tokens": ntok}
        if self.rt.zloss:
            zl = self.rt.zloss * ((lse * mask) ** 2).sum() / ntok
            loss = loss + zl
            metrics["zloss"] = zl
        loss = loss + aux
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------------
    def prefill(self, params, batch):
        """Returns (last_logits [B,V], raw caches for the paging layer)."""
        x, positions = self._fuse_inputs(params, batch)
        enc_out, src_valid = self._encode(params, batch)
        x, _, caches = transformer.stack_forward(
            params["stack"], x, self.cfg, self.rt, self.ctx,
            positions=positions, enc_out=enc_out, src_valid=src_valid,
            collect_caches=True, dropless=True)
        x = common.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        last = x[:, -1]
        return self._logits(params, last), caches

    def decode_step(self, params, tokens, caches, *, ctx_lens, block_table,
                    src_valid=None):
        """tokens [B] -> (logits [B,V], updated caches)."""
        x = self._embed(params, tokens)
        x = self.ctx.constraint(x, P("data", None))
        x, caches = transformer.stack_decode(
            params["stack"], x, caches, self.cfg, self.rt, self.ctx,
            ctx_lens=ctx_lens, block_table=block_table, src_valid=src_valid)
        x = common.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        return self._logits(params, x), caches

    # ------------------------------------------------------------------
    def cache_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        specs = {}
        if cfg.n_attn_layers:
            if self.rt.shard_kv_pool_pages:
                # long-context lever: stripe pool blocks across the
                # combine axes (the flash-channel analogy)
                b = None  # decided by batch shardability at trace time
                shape_b = None
                pool = P(None, None, ("data", "model"), None, None)
            else:
                pool = P(None, None, "data", None, "model")
            specs["pool_k"] = pool
            specs["pool_v"] = pool
        if any(cfg.layer_kind(i) == "mamba" for i in range(cfg.n_layers)):
            specs["conv"] = P(None, None, "data", None, "model")
            specs["ssm"] = P(None, None, "data", "model", None, None)
        if cfg.n_enc_layers:
            specs["cross_k"] = P(None, None, "data", None, None, "model")
            specs["cross_v"] = P(None, None, "data", None, None, "model")
        return specs

    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStructs (+ logical PartitionSpecs) for one step."""
        cfg, rt = self.cfg, self.rt
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if shape.kind in ("train", "prefill"):
            pre = _prefix_len(cfg, s)
            s_text = s - pre
            out = {
                "tokens": (sds((b, s_text), i32), P("data", None)),
                "positions": (sds((b, s), i32), P("data", None)),
            }
            if shape.kind == "train":
                out["labels"] = (sds((b, s_text), i32), P("data", None))
            if pre:
                out["prefix_emb"] = (
                    sds((b, pre, cfg.d_model), rt.compute_dtype),
                    P("data", None, None))
            if cfg.n_enc_layers:
                sl = _src_len(cfg, s)
                out["src_emb"] = (sds((b, sl, cfg.d_model), rt.compute_dtype),
                                  P("data", None, None))
                out["src_valid"] = (sds((b, sl), i32), P("data", None))
            return out
        # decode: one new token against a cache of length s
        max_pages = -(-s // rt.page_size)
        n_blocks = b * max_pages
        out = {
            "tokens": (sds((b,), i32), P("data")),
            "ctx_lens": (sds((b,), i32), P("data")),
            "block_table": (sds((b, max_pages), i32), P("data", None)),
        }
        caches = jax.eval_shape(
            lambda: transformer.init_decode_caches(
                cfg, rt, b, max_pages, n_blocks, rt.compute_dtype,
                src_len=_src_len(cfg, s)))
        cspecs = self.cache_specs()
        for k, v in caches.items():
            out[f"cache/{k}"] = (v, cspecs[k])
        if cfg.n_enc_layers:
            out["src_valid"] = (sds((b, _src_len(cfg, s)), i32),
                                P("data", None))
        return out


def build_model(cfg: ArchConfig, rt: Optional[Runtime] = None,
                ctx: Optional[ParallelCtx] = None) -> Model:
    from repro.parallel.sharding import trivial_ctx
    return Model(cfg=cfg, rt=rt or Runtime(), ctx=ctx or trivial_ctx())
