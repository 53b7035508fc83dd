"""Per-family block stacks: spec declaration + forward/prefill/decode.

Every family lowers through ``jax.lax.scan`` over stacked layer parameters so
the HLO stays one-layer-sized regardless of depth — essential both for
compile time on the 512-device dry-run and for XLA's collective scheduling
(one FSDP gather per scan step, overlappable).

Families:
  dense   — [attn + SwiGLU] × L
  moe     — [attn + MoE] × L
  ssm     — [Mamba2] × L
  hybrid  — ([Mamba2] × (attn_every-1) + shared-attn block) × groups  (zamba2)
  vlm     — ([gated cross-attn] + [self] × cross_every) × groups      (llama-3.2-v)
  encdec  — encoder [bidir attn + GELU MLP] × Le; decoder [self + cross + MLP] × Ld
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.models.attention import (attn_specs, decode_attention,
                                    full_attention, tp_size)
from repro.models.layers import (embed_specs, embed_tokens, gelu_mlp,
                                 gelu_mlp_specs, head_geom, logits_from,
                                 rmsnorm, rmsnorm_spec, sinusoidal_positions,
                                 swiglu, swiglu_specs)
from repro.models.moe import moe_ffn, moe_specs
from repro.models.ssm import (conv_channels, mamba_block, mamba_decode,
                              ssm_specs)
from repro.parallel.ctx import constrain


# ===================================================================== specs


def _sandwich(cfg: ModelConfig, n: int, specs: dict) -> dict:
    """The norms after each branch, before its residual add (afmoe)."""
    if cfg.sandwich_norm:
        specs["ln1_post"] = rmsnorm_spec(cfg.d_model, n)
        specs["ln2_post"] = rmsnorm_spec(cfg.d_model, n)
    return specs


def _dense_layer_specs(cfg: ModelConfig, n: int,
                       d_ff: int | None = None) -> dict:
    return _sandwich(cfg, n, {
        "ln1": rmsnorm_spec(cfg.d_model, n),
        "attn": attn_specs(cfg, n),
        "ln2": rmsnorm_spec(cfg.d_model, n),
        "mlp": swiglu_specs(cfg, n, d_ff),
    })


def _moe_layer_specs(cfg: ModelConfig, n: int) -> dict:
    return _sandwich(cfg, n, {
        "ln1": rmsnorm_spec(cfg.d_model, n),
        "attn": attn_specs(cfg, n),
        "ln2": rmsnorm_spec(cfg.d_model, n),
        "moe": moe_specs(cfg, n),
    })


def _ssm_layer_specs(cfg: ModelConfig, n: int) -> dict:
    return {"ln": rmsnorm_spec(cfg.d_model, n), "mamba": ssm_specs(cfg, n)}


def _shared_attn_specs(cfg: ModelConfig) -> dict:
    """zamba2's globally shared attention+MLP block (unstacked)."""
    return {
        "attn": attn_specs(cfg, None),
        "mlp": swiglu_specs(cfg, None),
        "ln_attn": rmsnorm_spec(cfg.d_model, None),
        "ln_mlp": rmsnorm_spec(cfg.d_model, None),
    }


def _cross_layer_specs(cfg: ModelConfig, n: int) -> dict:
    return {
        "ln": rmsnorm_spec(cfg.d_model, n),
        "attn": attn_specs(cfg, n),
        "gate_attn": P.ParamSpec((n, 1), ("layers", None), jnp.float32, "zeros"),
        "ln_mlp": rmsnorm_spec(cfg.d_model, n),
        "mlp": swiglu_specs(cfg, n),
        "gate_mlp": P.ParamSpec((n, 1), ("layers", None), jnp.float32, "zeros"),
    }


def _encdec_dec_layer_specs(cfg: ModelConfig, n: int) -> dict:
    return {
        "ln1": rmsnorm_spec(cfg.d_model, n),
        "self": attn_specs(cfg, n),
        "ln2": rmsnorm_spec(cfg.d_model, n),
        "cross": attn_specs(cfg, n),
        "ln3": rmsnorm_spec(cfg.d_model, n),
        "mlp": gelu_mlp_specs(cfg, n),
    }


def param_specs(cfg: ModelConfig) -> dict:
    fam = cfg.family
    specs: dict[str, Any] = {"embed": embed_specs(cfg),
                             "final_norm": rmsnorm_spec(cfg.d_model)}
    if fam == "dense":
        specs["layers"] = _dense_layer_specs(cfg, cfg.n_layers)
    elif fam == "moe":
        # leading dense layers, then the expert layers: two stacks
        specs["layers"] = _moe_layer_specs(
            cfg, cfg.n_layers - cfg.n_dense_layers)
        if cfg.n_dense_layers:
            specs["dense_layers"] = _dense_layer_specs(
                cfg, cfg.n_dense_layers, cfg.d_ff_dense)
    elif fam == "ssm":
        specs["layers"] = _ssm_layer_specs(cfg, cfg.n_layers)
    elif fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        n_mamba = groups * (cfg.attn_every - 1)
        specs["layers"] = _ssm_layer_specs(cfg, n_mamba)
        specs["shared"] = _shared_attn_specs(cfg)
        specs["site_norm"] = rmsnorm_spec(cfg.d_model, groups)
    elif fam == "vlm":
        groups = cfg.n_layers // cfg.cross_every
        specs["layers"] = _dense_layer_specs(cfg, cfg.n_layers)
        specs["cross"] = _cross_layer_specs(cfg, groups)
    elif fam == "encdec":
        specs["enc_layers"] = {
            "ln1": rmsnorm_spec(cfg.d_model, cfg.n_encoder_layers),
            "attn": attn_specs(cfg, cfg.n_encoder_layers),
            "ln2": rmsnorm_spec(cfg.d_model, cfg.n_encoder_layers),
            "mlp": gelu_mlp_specs(cfg, cfg.n_encoder_layers),
        }
        specs["enc_norm"] = rmsnorm_spec(cfg.d_model)
        specs["layers"] = _encdec_dec_layer_specs(cfg, cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam}")
    return specs


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    total = P.count(param_specs(cfg))
    if active_only and cfg.n_experts and cfg.top_k:
        expert = 3 * cfg.d_model * cfg.d_ff  # gate+up+down per expert
        held = cfg.held_experts
        total -= ((cfg.n_layers - cfg.n_dense_layers) * expert
                  * (held - min(cfg.top_k, held)))
    return total


def nonembedding_param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    n = param_count(cfg, active_only)
    emb = cfg.padded_vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return n - emb


# ================================================================= forward


def refuse_paged_only(cfg: ModelConfig, path: str) -> None:
    """Raise where ``path`` would compute another model than ``cfg``'s
    (``ModelConfig.paged_only``)."""
    if cfg.paged_only:
        raise ValueError(
            f"{path} does not compute {cfg.name}'s layer (windows, output "
            f"gates, sandwich norms, held or sigmoid-routed experts): "
            f"serve it through PagedServeEngine's paged step")


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(f"unknown remat mode {mode}")


from repro.models.layers import bf16_tangent as _bf16_tangent


def _res(x):
    """Residual-stream boundary: (1) sharding constraint — under the
    train/prefill rule sets this is Megatron sequence parallelism (saved
    per-layer activations shard over the model axis; GSPMD inserts the
    block-boundary all-gather/reduce-scatter); (2) cotangent dtype pin —
    without it the f32 cotangents from the loss head propagate through the
    whole backward residual chain, and XLA materializes an f32 copy of the
    entire saved-activation stack (measured: +2× activation memory and 2×
    collective payloads on deepseek-coder-33b)."""
    return _bf16_tangent(constrain(x, ("act_batch", "act_res", None)))


def _dense_block(cfg, p, x, pos0=0, use_pallas=False):
    h = x + full_attention(cfg, p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                           pos0=pos0, use_pallas=use_pallas)
    return _res(h + swiglu(p["mlp"], rmsnorm(p["ln2"], h, cfg.norm_eps)))


def _moe_block(cfg, p, x, pos0=0):
    h = x + full_attention(cfg, p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                           pos0=pos0)
    y, aux = moe_ffn(cfg, p["moe"], rmsnorm(p["ln2"], h, cfg.norm_eps))
    return _res(h + y), jnp.mean(aux)


def _ssm_block(cfg, p, x, use_pallas=False):
    from repro.models.ssm import mamba_block_cp
    return _res(x + mamba_block_cp(cfg, p["mamba"],
                                   rmsnorm(p["ln"], x, cfg.norm_eps),
                                   use_pallas=use_pallas))


def _shared_block(cfg, p, site_norm, x, pos0=0):
    h = x + full_attention(
        cfg, p["attn"],
        rmsnorm(site_norm, rmsnorm(p["ln_attn"], x, cfg.norm_eps), cfg.norm_eps),
        pos0=pos0)
    return _res(h + swiglu(p["mlp"], rmsnorm(p["ln_mlp"], h, cfg.norm_eps)))


def _cross_block(cfg, p, x, ctx_kv):
    # image/patch context is replicated, not sequence-sharded: no kv gather
    h = full_attention(cfg, p["attn"], rmsnorm(p["ln"], x, cfg.norm_eps),
                       kv_x=ctx_kv, causal=False, gather_kv=False)
    x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
    m = swiglu(p["mlp"], rmsnorm(p["ln_mlp"], x, cfg.norm_eps))
    return _res(x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * m)


def forward(cfg: ModelConfig, params: dict, batch: dict, *,
            remat: str = "none", use_pallas: bool = False) -> tuple[jax.Array, dict]:
    """Full-sequence forward -> (logits [B,S,Vpad], metrics)."""
    fam = cfg.family
    metrics: dict[str, jax.Array] = {}
    refuse_paged_only(cfg, "forward")

    if fam == "encdec":
        return _encdec_forward(cfg, params, batch, remat)

    x = _res(embed_tokens(params["embed"], batch["tokens"]))

    if fam in ("dense",):
        body = _remat(
            lambda x, p: (_dense_block(cfg, p, x, use_pallas=use_pallas),
                          None), remat)
        x, _ = jax.lax.scan(body, x, params["layers"])
    elif fam == "moe":
        def moe_body(x, p):
            y, aux = _moe_block(cfg, p, x)
            return y, aux
        body = _remat(moe_body, remat)
        x, auxes = jax.lax.scan(body, x, params["layers"])
        metrics["moe_aux"] = jnp.mean(auxes)
    elif fam == "ssm":
        body = _remat(lambda x, p: (_ssm_block(cfg, p, x, use_pallas), None), remat)
        x, _ = jax.lax.scan(body, x, params["layers"])
    elif fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        per = cfg.attn_every - 1
        shared = params["shared"]
        stacked = jax.tree.map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), params["layers"])

        def group_body(x, gp):
            layer_p, site_norm = gp
            inner = _remat(
                lambda x, p: (_ssm_block(cfg, p, x, use_pallas), None), remat)
            x, _ = jax.lax.scan(inner, x, layer_p)
            x = _remat(
                lambda x, sn: (_shared_block(cfg, shared, sn, x), None), remat
            )(x, site_norm)[0]
            return x, None

        x, _ = jax.lax.scan(group_body, x, (stacked, params["site_norm"]))
    elif fam == "vlm":
        groups = cfg.n_layers // cfg.cross_every
        per = cfg.cross_every
        img = constrain(batch["image_embed"], ("act_batch", None, None))
        stacked = jax.tree.map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), params["layers"])

        def group_body(x, gp):
            cross_p, layer_p = gp
            x = _remat(lambda x, cp: (_cross_block(cfg, cp, x, img), None),
                       remat)(x, cross_p)[0]
            inner = _remat(lambda x, p: (_dense_block(cfg, p, x), None), remat)
            x, _ = jax.lax.scan(inner, x, layer_p)
            return x, None

        x, _ = jax.lax.scan(group_body, x, (params["cross"], stacked))
    else:
        raise ValueError(fam)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_from(params["embed"], cfg, x), metrics


def _encoder(cfg: ModelConfig, params: dict, audio_embed: jax.Array,
             remat: str) -> jax.Array:
    x = audio_embed + sinusoidal_positions(audio_embed.shape[1], cfg.d_model)
    x = _res(x)

    def body(x, p):
        h = x + full_attention(cfg, p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                               causal=False)
        return _res(h + gelu_mlp(p["mlp"], rmsnorm(p["ln2"], h, cfg.norm_eps))), None

    x, _ = jax.lax.scan(_remat(body, remat), x, params["enc_layers"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _encdec_forward(cfg: ModelConfig, params: dict, batch: dict, remat: str):
    enc = _encoder(cfg, params, batch["audio_embed"], remat)
    x = embed_tokens(params["embed"], batch["tokens"])
    x = _res(x + sinusoidal_positions(x.shape[1], cfg.d_model))

    def body(x, p):
        h = x + full_attention(cfg, p["self"], rmsnorm(p["ln1"], x, cfg.norm_eps))
        h = h + full_attention(cfg, p["cross"], rmsnorm(p["ln2"], h, cfg.norm_eps),
                               kv_x=enc, causal=False)
        return _res(h + gelu_mlp(p["mlp"], rmsnorm(p["ln3"], h, cfg.norm_eps))), None

    x, _ = jax.lax.scan(_remat(body, remat), x, params["layers"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_from(params["embed"], cfg, x), {}
