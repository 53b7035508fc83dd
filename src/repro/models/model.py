"""Public model API: one object per architecture config.

All methods are pure functions of (params, batch) so they can be jitted,
lowered abstractly for the dry-run, or wrapped in shard_map-free smoke
tests identically.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import decode as D
from repro.models import params as P
from repro.models import stack
from repro.models.layers import cross_entropy


class Model:
    def __init__(self, cfg: ModelConfig, use_pallas: bool = False):
        self.cfg = cfg
        self.use_pallas = use_pallas

    # ---- parameters ----
    def param_specs(self):
        return stack.param_specs(self.cfg)

    def abstract_params(self):
        return P.abstract(self.param_specs())

    def init_params(self, key: jax.Array):
        return P.initialize(self.param_specs(), key)

    # ---- training ----
    def loss(self, params: dict, batch: dict, *, remat: str = "none",
             z_loss: float = 0.0):
        logits, metrics = stack.forward(self.cfg, params, batch, remat=remat,
                                        use_pallas=self.use_pallas)
        loss, aux = cross_entropy(logits, batch["labels"],
                                  self.cfg.vocab_size, z_loss)
        metrics.update(aux)
        if "moe_aux" in metrics:
            loss = loss + self.cfg.router_aux_weight * metrics["moe_aux"]
        return loss, metrics

    # ---- serving ----
    def prefill(self, params: dict, batch: dict, cache_len: int | None = None):
        return D.prefill(self.cfg, params, batch, cache_len)

    def reference_prefill(self, params: dict, tokens: jax.Array,
                          positions: jax.Array | None = None):
        """The reference served logits are held to: ``prefill``'s cache-free
        forward over ``tokens`` [B,S] computed in float32 at full matmul
        precision (a TPU runs float32 matmuls in bf16 passes otherwise),
        with the same bf16 weights.  Returns float32 logits at the last
        position [B,Vpad], or at ``positions`` [B,K] -> [B,K,Vpad].
        Dense/moe."""
        with jax.default_matmul_precision("highest"):
            logits, _ = D.prefill(self.cfg, params, {"tokens": tokens},
                                  dtype=jnp.float32, positions=positions)
        return logits

    def decode_step(self, params: dict, cache: dict, token: jax.Array,
                    pos: jax.Array):
        return D.decode_step(self.cfg, params, cache, token, pos)

    def decode_chunk(self, params: dict, cache: dict, tokens: jax.Array,
                     pos: jax.Array, n_new: jax.Array):
        return D.decode_chunk(self.cfg, params, cache, tokens, pos, n_new)

    def decode_greedy_step(self, params: dict, cache: dict, token: jax.Array,
                           pos: jax.Array):
        """One-token decode with argmax fused into the jitted program:
        returns (tokens [B] int32, logits [B,Vpad] f32, new cache).  The
        all-greedy serving fast path — only the selected token vector
        crosses to the host (the logits stay on the device unless a
        request asks for them), and none of the sampling pipeline
        (sort/softmax/cumsum) lowers."""
        logits, cache = D.decode_step(self.cfg, params, cache, token, pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache

    def decode_greedy_chunk(self, params: dict, cache: dict,
                            tokens: jax.Array, pos: jax.Array,
                            n_new: jax.Array):
        """Chunked decode with fused argmax (paged engine, all-greedy):
        returns (tokens [B] int32, logits [B,Vpad] f32, new cache).  The
        logits stay on the device unless a request asks for them."""
        logits, cache = D.decode_chunk(self.cfg, params, cache, tokens, pos,
                                       n_new)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache

    def decode_sample_step(self, params: dict, cache: dict, token: jax.Array,
                           pos: jax.Array, lane: dict):
        """One-token decode with sampling fused into the jitted program:
        returns (tokens [B] int32, logits, new cache).  ``lane`` is the
        per-slot sampling state (serve.api.LaneState.as_args()); greedy
        lanes (temperature 0) still get exact argmax."""
        logits, cache = D.decode_step(self.cfg, params, cache, token, pos)
        return D.sample_from_logits(logits, lane), logits, cache

    def decode_sample_chunk(self, params: dict, cache: dict,
                            tokens: jax.Array, pos: jax.Array,
                            n_new: jax.Array, lane: dict):
        """Chunked decode with fused sampling (the paged engine's step)."""
        logits, cache = D.decode_chunk(self.cfg, params, cache, tokens, pos,
                                       n_new)
        return D.sample_from_logits(logits, lane), logits, cache

    def decode_paged_chunk(self, params: dict, cache: dict,
                           tokens: jax.Array, pos: jax.Array,
                           n_new: jax.Array, page_table: jax.Array, *,
                           attention: str):
        return D.decode_paged_chunk(self.cfg, params, cache, tokens, pos,
                                    n_new, page_table, attention=attention)

    def decode_paged_greedy_chunk(self, params: dict, cache: dict,
                                  tokens: jax.Array, pos: jax.Array,
                                  n_new: jax.Array, page_table: jax.Array, *,
                                  attention: str):
        """Chunked decode over the paged KV pool with fused argmax — the
        kernel-enabled paged engine's all-greedy step.  KV reads and
        writes both go through the page table; no dense working cache.
        Returns (tokens, logits, cache) like :meth:`decode_greedy_chunk`,
        and where the step counts anything (a chip's share of experts;
        :func:`decode.decode_paged_chunk`) those counts fourth."""
        logits, cache, stats = D.decode_paged_chunk(
            self.cfg, params, cache, tokens, pos, n_new, page_table,
            attention=attention)
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache
        return out + (stats,) if stats else out

    def decode_paged_sample_chunk(self, params: dict, cache: dict,
                                  tokens: jax.Array, pos: jax.Array,
                                  n_new: jax.Array, page_table: jax.Array,
                                  lane: dict, *, attention: str):
        """Chunked paged decode with fused sampling; returns as
        :meth:`decode_paged_greedy_chunk`."""
        logits, cache, stats = D.decode_paged_chunk(
            self.cfg, params, cache, tokens, pos, n_new, page_table,
            attention=attention)
        out = D.sample_from_logits(logits, lane), logits, cache
        return out + (stats,) if stats else out

    def cache_specs(self, batch: int, seq_len: int):
        return D.cache_specs(self.cfg, batch, seq_len)

    def paged_cache_specs(self, num_blocks: int, block_size: int,
                          impl: str = "pallas"):
        return D.paged_cache_specs(self.cfg, num_blocks, block_size, impl)

    def abstract_cache(self, batch: int, seq_len: int):
        return P.abstract(self.cache_specs(batch, seq_len))

    def zero_cache(self, batch: int, seq_len: int):
        return P.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                          self.cache_specs(batch, seq_len))

    # ---- batch/input declaration (dry-run ShapeDtypeStructs) ----
    def input_specs(self, shape: ShapeConfig) -> dict[str, Any]:
        """Abstract inputs for one assignment cell.  Modality frontends are
        stubs per the assignment: precomputed frame/patch embeddings."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32, bf16 = jnp.int32, jnp.bfloat16

        def tok(n):
            return jax.ShapeDtypeStruct((b, n), i32)

        if shape.kind == "train":
            if cfg.family == "encdec":
                return {
                    "audio_embed": jax.ShapeDtypeStruct((b, s, cfg.d_model), bf16),
                    "tokens": tok(cfg.decoder_train_len),
                    "labels": tok(cfg.decoder_train_len),
                }
            batch: dict[str, Any] = {"tokens": tok(s), "labels": tok(s)}
            if cfg.family == "vlm":
                batch["image_embed"] = jax.ShapeDtypeStruct(
                    (b, cfg.n_image_tokens, cfg.d_model), bf16)
            return batch
        if shape.kind == "prefill":
            if cfg.family == "encdec":
                return {
                    "audio_embed": jax.ShapeDtypeStruct((b, s, cfg.d_model), bf16),
                    "tokens": tok(cfg.decoder_train_len),
                }
            batch = {"tokens": tok(s)}
            if cfg.family == "vlm":
                batch["image_embed"] = jax.ShapeDtypeStruct(
                    (b, cfg.n_image_tokens, cfg.d_model), bf16)
            return batch
        # decode: one token against a seq_len-deep cache
        return {
            "token": tok(1),
            "pos": jax.ShapeDtypeStruct((b,), i32),
        }

    def sample_batch(self, shape: ShapeConfig, key: jax.Array) -> dict[str, Any]:
        """Materialized random batch matching input_specs (smoke/real runs)."""
        specs = self.input_specs(shape)
        out = {}
        for name, sds in specs.items():
            key, sub = jax.random.split(key)
            if sds.dtype == jnp.int32 and name in ("tokens", "labels", "token"):
                out[name] = jax.random.randint(sub, sds.shape, 0,
                                               self.cfg.vocab_size, jnp.int32)
            elif name == "pos":
                out[name] = jnp.zeros(sds.shape, jnp.int32)
            else:
                out[name] = jax.random.normal(sub, sds.shape, jnp.float32
                                              ).astype(sds.dtype)
        return out


def build(cfg: ModelConfig, use_pallas: bool = False) -> Model:
    return Model(cfg, use_pallas)
