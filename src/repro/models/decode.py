"""Prefill and single-token decode paths with per-family cache layouts.

Cache shapes depend on the bound mesh (kv heads zero-padded to the model-
axis width so the cache itself shards) — so ``cache_specs`` must be called
under a bound shard context, mirroring the paper's late host binding.

decode shapes from the assignment lower ``decode_step`` (one new token
against a seq_len-deep cache), not ``train_step``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.models.attention import (chunk_decode_attention, decode_attention,
                                    full_attention,
                                    paged_chunk_decode_attention, tp_size)
from repro.models.layers import (embed_tokens, gelu_mlp, head_geom,
                                 logits_from, rmsnorm, sinusoidal_positions,
                                 swiglu)
from repro.models.moe import moe_ffn, moe_serve
from repro.models.ssm import conv_channels, mamba_decode
from repro.models.stack import (_cross_block, _encoder, _res,
                                 refuse_paged_only)
from repro.parallel.ctx import constrain


def _kv_cache_spec(cfg: ModelConfig, layers: int, b: int, s: int) -> dict:
    geom = head_geom(cfg, tp_size())
    shape = (layers, b, s, geom.n_kv, geom.head_dim)
    if geom.n_kv % max(geom.tp, 1) == 0:
        # kv heads divide the model axis: shard the head dim (local scores)
        axes = ("layers", "cache_batch", "cache_seq", "cache_kv", None)
    else:
        # GQA with few kv heads: shard the SEQUENCE dim over the model axis
        # instead of padding heads — zero memory waste; softmax stats and
        # the [B,H,hd] partial-output reduce are the only collectives.
        axes = ("layers", "cache_batch", "cache_seq_tp", None, None)
    return {
        "k": P.ParamSpec(shape, axes, init="zeros"),
        "v": P.ParamSpec(shape, axes, init="zeros"),
    }


def _ssm_cache_spec(cfg: ModelConfig, layers: int, b: int) -> dict:
    cc = conv_channels(cfg)
    return {
        "conv": P.ParamSpec((layers, b, cfg.conv_width - 1, cc),
                            ("layers", "cache_batch", None, "act_inner"),
                            init="zeros"),
        "ssm": P.ParamSpec(
            (layers, b, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            ("layers", "cache_batch", "cache_kv", None, None),
            jnp.float32, init="zeros"),
    }


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> dict[str, Any]:
    """Cache ParamSpec tree for a decode step at the given geometry."""
    fam = cfg.family
    if fam in ("dense", "moe"):
        return {"self": _kv_cache_spec(cfg, cfg.n_layers, batch, seq_len)}
    if fam == "ssm":
        return {"ssm": _ssm_cache_spec(cfg, cfg.n_layers, batch)}
    if fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        n_mamba = groups * (cfg.attn_every - 1)
        return {
            "ssm": _ssm_cache_spec(cfg, n_mamba, batch),
            "self": _kv_cache_spec(cfg, groups, batch, seq_len),
        }
    if fam == "vlm":
        groups = cfg.n_layers // cfg.cross_every
        return {
            "self": _kv_cache_spec(cfg, cfg.n_layers, batch, seq_len),
            "cross": _kv_cache_spec(cfg, groups, batch, cfg.n_image_tokens),
        }
    if fam == "encdec":
        return {
            "self": _kv_cache_spec(cfg, cfg.n_layers, batch, seq_len),
            "cross": _kv_cache_spec(cfg, cfg.n_layers, batch,
                                    cfg.n_audio_frames),
        }
    raise ValueError(fam)


# ======================================================== compile watching


class CompileWatcher:
    """Cache-miss counter around a jitted step callable.

    Recompilation on the serving hot path is a pathway misconfiguration
    (shape polymorphism leaking into what must be a fixed-shape program):
    output stays token-identical while every new shape pays a full XLA
    compile.  The watcher keys each call by the argument tree's
    (shape, dtype) signature — a new key is a compile-cache miss — and
    cross-checks ``fn._cache_size()`` where the jit object exposes it, so
    same-shape recompiles (donation/layout churn) are counted too.

    ``on_compile(name, reason, signature)`` fires once per detected
    compile; engines wire it to their tracer.  Overhead per call is one
    tree flatten over a handful of arrays — noise next to a dispatched
    step.
    """

    def __init__(self, fn, name: str, on_compile=None):
        self.fn = fn
        self.name = name
        self.on_compile = on_compile
        self.calls = 0
        self.compiles = 0
        self._seen: set = set()
        self._base_cache: int | None = None
        self._first_arg_sig: tuple | None = None  # (arg ref, signature)

    @staticmethod
    def _leaf_sig(tree) -> tuple:
        return tuple(
            (tuple(x.shape), str(x.dtype))
            for x in jax.tree.leaves(tree)
            if hasattr(x, "shape") and hasattr(x, "dtype"))

    def _signature(self, args) -> tuple:
        """(shape, dtype) key of the argument tree.  The first argument
        is the params pytree — the same (large) object every call — so
        its sub-signature is computed once and reused by identity; the
        per-call cost is flattening only the small cache/token/pos args."""
        if not args:
            return ()
        first, rest = args[0], args[1:]
        if self._first_arg_sig is None or self._first_arg_sig[0] is not first:
            self._first_arg_sig = (first, self._leaf_sig(first))
        return self._first_arg_sig[1] + self._leaf_sig(rest)

    def _cache_size(self) -> int | None:
        probe = getattr(self.fn, "_cache_size", None)
        if not callable(probe):
            return None
        try:
            return probe()
        except Exception:  # noqa: BLE001 - diagnostic only, never fatal
            return None

    def _fire(self, reason: str, sig: tuple) -> None:
        self.compiles += 1
        if self.on_compile is not None:
            self.on_compile(self.name, reason, sig)

    def __call__(self, *args):
        if self.calls == 0:
            # baseline for a jit cache shared with other engines: growth
            # is judged relative to what was already compiled before us
            self._base_cache = self._cache_size()
        self.calls += 1
        sig = self._signature(args)
        if sig not in self._seen:
            self._seen.add(sig)
            self._fire("new-shapes", sig)
        out = self.fn(*args)
        n = self._cache_size()
        if (n is not None and self._base_cache is not None
                and n - self._base_cache > len(self._seen)):
            # more entries appeared than our shape keys explain: a
            # same-shape recompile (donation/layout churn)
            self._base_cache = n - len(self._seen)
            self._fire("cache-grew", sig)
        return out


# ================================================================= prefill


def _prefill_attn(cfg, p, x, pos0=0):
    """full attention that also emits (k, v) for the cache."""
    y, (k, v) = full_attention(cfg, p, x, pos0=pos0, return_kv=True)
    return y, k, v


def _cast(tree: Any, dtype) -> Any:
    return tree if dtype is None else jax.tree.map(
        lambda a: a.astype(dtype), tree)


def prefill(cfg: ModelConfig, params: dict, batch: dict,
            cache_len: int | None = None, *, dtype=None,
            positions: jax.Array | None = None):
    """Returns (last-position logits [B,Vpad], cache).  ``cache_len`` > prompt
    pre-allocates decode headroom (engine never reallocates mid-stream).
    ``positions`` [B,K] int32 returns the logits at those positions
    instead, [B,K,Vpad] (causal: padding after a position leaves its
    logits unchanged, so sequences of several lengths share one call).

    ``dtype`` (dense/moe) computes in that dtype instead of the weights'
    own: each layer's weights are cast inside the layer scan, so a
    float32 forward of a bf16 model holds one layer's float32 copy at a
    time (``Model.reference_prefill``)."""
    fam = cfg.family
    refuse_paged_only(cfg, "prefill")
    geom = head_geom(cfg, tp_size()) if cfg.n_heads else None
    tokens = batch["tokens"]
    bsz, s = tokens.shape
    if dtype is not None and fam not in ("dense", "moe"):
        raise ValueError(f"prefill dtype= supports dense/moe, got {fam}")

    if fam in ("dense", "moe"):
        x = _res(embed_tokens(_cast(params["embed"], dtype), tokens))

        def body(x, p):
            p = _cast(p, dtype)
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, k, v = _prefill_attn(cfg, p["attn"], h)
            x = x + a
            h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
            if fam == "moe":
                y, _ = moe_ffn(cfg, p["moe"], h2)
            else:
                y = swiglu(p["mlp"], h2)
            return _res(x + y), (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        cache = {"self": {"k": ks, "v": vs}}
    elif fam == "ssm":
        from repro.models.ssm import mamba_block_cp
        x = _res(embed_tokens(params["embed"], tokens))

        def body(x, p):
            h = rmsnorm(p["ln"], x, cfg.norm_eps)
            y, (conv_tail, ssm_state) = mamba_block_cp(
                cfg, p["mamba"], h, return_state=True)
            return _res(x + y), (conv_tail, ssm_state)

        x, (convs, ssms) = jax.lax.scan(body, x, params["layers"])
        cache = {"ssm": {"conv": convs, "ssm": ssms}}
    elif fam == "vlm":
        groups = cfg.n_layers // cfg.cross_every
        per = cfg.cross_every
        img = constrain(batch["image_embed"], ("act_batch", None, None))
        x = embed_tokens(params["embed"], tokens)
        stacked = jax.tree.map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), params["layers"])

        def group_body(x, gp):
            cross_p, layer_p = gp
            ck = (img @ cross_p["attn"]["wk"]).reshape(
                bsz, -1, geom.n_kv, geom.head_dim)
            cv = (img @ cross_p["attn"]["wv"]).reshape(
                bsz, -1, geom.n_kv, geom.head_dim)
            x = _cross_block(cfg, cross_p, x, img)

            def body(x, p):
                h = rmsnorm(p["ln1"], x, cfg.norm_eps)
                a, k, v = _prefill_attn(cfg, p["attn"], h)
                x = x + a
                return _res(x + swiglu(
                    p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps))), (k, v)

            x, (ks, vs) = jax.lax.scan(body, x, layer_p)
            return x, (ks, vs, ck, cv)

        x, (ks, vs, cks, cvs) = jax.lax.scan(
            group_body, x, (params["cross"], stacked))
        lks = ks.reshape((cfg.n_layers,) + ks.shape[2:])
        lvs = vs.reshape((cfg.n_layers,) + vs.shape[2:])
        cache = {"self": {"k": lks, "v": lvs}, "cross": {"k": cks, "v": cvs}}
    elif fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        per = cfg.attn_every - 1
        x = embed_tokens(params["embed"], tokens)
        shared = params["shared"]
        stacked = jax.tree.map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), params["layers"])

        def group_body(x, gp):
            from repro.models.ssm import mamba_block_cp
            layer_p, site_norm = gp

            def inner(x, p):
                y, st = mamba_block_cp(cfg, p["mamba"],
                                       rmsnorm(p["ln"], x, cfg.norm_eps),
                                       return_state=True)
                return _res(x + y), st

            x, (convs, ssms) = jax.lax.scan(inner, x, layer_p)
            h = rmsnorm(site_norm,
                        rmsnorm(shared["ln_attn"], x, cfg.norm_eps),
                        cfg.norm_eps)
            a, k, v = _prefill_attn(cfg, shared["attn"], h)
            x = x + a
            x = _res(x + swiglu(shared["mlp"],
                                rmsnorm(shared["ln_mlp"], x, cfg.norm_eps)))
            return x, (convs, ssms, k, v)

        x, (convs, ssms, ks, vs) = jax.lax.scan(
            group_body, x, (stacked, params["site_norm"]))
        cache = {
            "ssm": {
                "conv": convs.reshape((groups * per,) + convs.shape[2:]),
                "ssm": ssms.reshape((groups * per,) + ssms.shape[2:]),
            },
            "self": {"k": ks, "v": vs},
        }
    elif fam == "encdec":
        enc = _encoder(cfg, params, batch["audio_embed"], "none")
        x = embed_tokens(params["embed"], tokens)
        x = x + sinusoidal_positions(s, cfg.d_model)

        def body(x, p):
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, k, v = _prefill_attn(cfg, p["self"], h)
            x = x + a
            ck = (enc @ p["cross"]["wk"]).reshape(bsz, -1, geom.n_kv, geom.head_dim)
            cv = (enc @ p["cross"]["wv"]).reshape(bsz, -1, geom.n_kv, geom.head_dim)
            x = x + full_attention(cfg, p["cross"],
                                   rmsnorm(p["ln2"], x, cfg.norm_eps),
                                   kv_x=enc, causal=False)
            x = _res(x + gelu_mlp(p["mlp"], rmsnorm(p["ln3"], x, cfg.norm_eps)))
            return x, (k, v, ck, cv)

        x, (ks, vs, cks, cvs) = jax.lax.scan(body, x, params["layers"])
        cache = {"self": {"k": ks, "v": vs}, "cross": {"k": cks, "v": cvs}}
    else:
        raise ValueError(fam)

    if cache_len is not None and cache_len > s and "self" in cache:
        pad = cache_len - s
        cache["self"] = jax.tree.map(
            lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            cache["self"])

    x = (x[:, -1:, :] if positions is None
         else jnp.take_along_axis(x, positions[:, :, None], axis=1))
    x = rmsnorm(_cast(params["final_norm"], dtype), x, cfg.norm_eps)
    logits = logits_from(_cast(params["embed"], dtype), cfg, x)
    return (logits[:, 0] if positions is None else logits), cache


# ================================================================== decode


def _idx(cache_arr: jax.Array, i: jax.Array) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(cache_arr, i, 0, keepdims=False)


def _upd(cache_arr: jax.Array, new_layer: jax.Array, i: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(cache_arr, new_layer, i, 0)


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                token: jax.Array, pos: jax.Array):
    """One-token decode.  token [B,1] int32, pos [B] int32.
    Returns (logits [B,Vpad] fp32, new cache).

    Caches ride the scan CARRY and are updated in place with
    dynamic-update-slice: with the cache argument donated, XLA aliases the
    buffer and the step's temp memory stays O(one layer) — emitting updated
    layers as stacked scan outputs instead double-buffers the whole cache
    (measured +2× cache bytes on the 32k cells)."""
    fam = cfg.family
    refuse_paged_only(cfg, "decode_step")
    x = embed_tokens(params["embed"], token)

    if fam in ("dense", "moe"):
        def body(carry, xs):
            x, kc, vc = carry
            p, i = xs
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, k_l, v_l = decode_attention(cfg, p["attn"], h,
                                           _idx(kc, i), _idx(vc, i), pos)
            x = x + a
            h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
            if fam == "moe":
                y, _ = moe_ffn(cfg, p["moe"], h2)
            else:
                y = swiglu(p["mlp"], h2)
            return (x + y, _upd(kc, k_l, i), _upd(vc, v_l, i)), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["self"]["k"], cache["self"]["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        new_cache = {"self": {"k": ks, "v": vs}}
    elif fam == "ssm":
        def body(carry, xs):
            x, convs, ssms = carry
            p, i = xs
            h = rmsnorm(p["ln"], x, cfg.norm_eps)
            y, conv, ssm = mamba_decode(cfg, p["mamba"], h,
                                        _idx(convs, i), _idx(ssms, i))
            return (x + y, _upd(convs, conv, i), _upd(ssms, ssm, i)), None

        (x, convs, ssms), _ = jax.lax.scan(
            body, (x, cache["ssm"]["conv"], cache["ssm"]["ssm"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        new_cache = {"ssm": {"conv": convs, "ssm": ssms}}
    elif fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        per = cfg.attn_every - 1
        shared = params["shared"]
        stacked = jax.tree.map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), params["layers"])

        def group_body(carry, xs):
            x, convs, ssms, kc, vc = carry
            layer_p, site_norm, g = xs

            def inner(icarry, ys):
                x, convs, ssms = icarry
                p, j = ys
                li = g * per + j
                h = rmsnorm(p["ln"], x, cfg.norm_eps)
                y, conv, ssm = mamba_decode(cfg, p["mamba"], h,
                                            _idx(convs, li), _idx(ssms, li))
                return (x + y, _upd(convs, conv, li), _upd(ssms, ssm, li)), None

            (x, convs, ssms), _ = jax.lax.scan(
                inner, (x, convs, ssms), (layer_p, jnp.arange(per)))
            h = rmsnorm(site_norm, rmsnorm(shared["ln_attn"], x, cfg.norm_eps),
                        cfg.norm_eps)
            a, k_g, v_g = decode_attention(cfg, shared["attn"], h,
                                           _idx(kc, g), _idx(vc, g), pos)
            x = x + a
            x = x + swiglu(shared["mlp"],
                           rmsnorm(shared["ln_mlp"], x, cfg.norm_eps))
            return (x, convs, ssms, _upd(kc, k_g, g), _upd(vc, v_g, g)), None

        (x, convs, ssms, ks, vs), _ = jax.lax.scan(
            group_body,
            (x, cache["ssm"]["conv"], cache["ssm"]["ssm"],
             cache["self"]["k"], cache["self"]["v"]),
            (stacked, params["site_norm"], jnp.arange(groups)))
        new_cache = {"ssm": {"conv": convs, "ssm": ssms},
                     "self": {"k": ks, "v": vs}}
    elif fam == "vlm":
        groups = cfg.n_layers // cfg.cross_every
        per = cfg.cross_every
        stacked = jax.tree.map(
            lambda a: a.reshape((groups, per) + a.shape[1:]), params["layers"])

        def group_body(carry, xs):
            x, kc, vc = carry
            cross_p, layer_p, ck, cv, g = xs
            h = rmsnorm(cross_p["ln"], x, cfg.norm_eps)
            a, _, _ = decode_attention(cfg, cross_p["attn"], h, ck, cv, pos,
                                       update_cache=False)
            x = x + jnp.tanh(cross_p["gate_attn"]).astype(x.dtype) * a
            m = swiglu(cross_p["mlp"], rmsnorm(cross_p["ln_mlp"], x, cfg.norm_eps))
            x = x + jnp.tanh(cross_p["gate_mlp"]).astype(x.dtype) * m

            def inner(icarry, ys):
                x, kc, vc = icarry
                p, j = ys
                li = g * per + j
                h = rmsnorm(p["ln1"], x, cfg.norm_eps)
                a, k_l, v_l = decode_attention(cfg, p["attn"], h,
                                               _idx(kc, li), _idx(vc, li), pos)
                x = x + a
                x = x + swiglu(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps))
                return (x, _upd(kc, k_l, li), _upd(vc, v_l, li)), None

            (x, kc, vc), _ = jax.lax.scan(
                inner, (x, kc, vc), (layer_p, jnp.arange(per)))
            return (x, kc, vc), None

        (x, ks, vs), _ = jax.lax.scan(
            group_body, (x, cache["self"]["k"], cache["self"]["v"]),
            (params["cross"], stacked, cache["cross"]["k"],
             cache["cross"]["v"], jnp.arange(groups)))
        new_cache = {"self": {"k": ks, "v": vs}, "cross": cache["cross"]}
    elif fam == "encdec":
        x = x + sinusoidal_positions(1, cfg.d_model, offset=pos[:, None])[:, None, :]

        def body(carry, xs):
            x, kc, vc = carry
            p, ck, cv, i = xs
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, k_l, v_l = decode_attention(cfg, p["self"], h,
                                           _idx(kc, i), _idx(vc, i), pos)
            x = x + a
            h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
            a2, _, _ = decode_attention(cfg, p["cross"], h2, ck, cv, pos,
                                        update_cache=False)
            x = x + a2
            x = x + gelu_mlp(p["mlp"], rmsnorm(p["ln3"], x, cfg.norm_eps))
            return (x, _upd(kc, k_l, i), _upd(vc, v_l, i)), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["self"]["k"], cache["self"]["v"]),
            (params["layers"], cache["cross"]["k"], cache["cross"]["v"],
             jnp.arange(cfg.n_layers)))
        new_cache = {"self": {"k": ks, "v": vs}, "cross": cache["cross"]}
    else:
        raise ValueError(fam)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_from(params["embed"], cfg, x)[:, 0]
    return logits, new_cache


# ============================================================ chunked decode


def decode_chunk(cfg: ModelConfig, params: dict, cache: dict,
                 tokens: jax.Array, pos: jax.Array, n_new: jax.Array):
    """C-token decode against the cache: the paged engine's single step.

    tokens [B,C] int32, pos [B] int32 (first write position per lane),
    n_new [B] int32 in [0, C] (how many of the lane's tokens are real; 0
    marks an idle slot, 1 is a plain decode tick, >1 is a prefill chunk).
    Prefill lanes consume C prompt tokens per call while decode lanes
    advance one token in the same batched step — chunked prefill without a
    second jitted program or shape polymorphism.

    Returns (logits [B,Vpad] at each lane's last real position, new cache).
    Only attention-cache families (dense/moe) support the chunked path;
    other families serve through the contiguous engine.
    """
    fam = cfg.family
    if fam not in ("dense", "moe"):
        raise ValueError(f"decode_chunk supports dense/moe caches, got {fam}")
    refuse_paged_only(cfg, "decode_chunk")
    b = tokens.shape[0]
    x = embed_tokens(params["embed"], tokens)

    def body(carry, xs):
        x, kc, vc = carry
        p, i = xs
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, kc_i, vc_i = chunk_decode_attention(cfg, p["attn"], h,
                                               _idx(kc, i), _idx(vc, i),
                                               pos, n_new)
        x = x + a
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if fam == "moe":
            y, _ = moe_ffn(cfg, p["moe"], h2)
        else:
            y = swiglu(p["mlp"], h2)
        return (x + y, _upd(kc, kc_i, i), _upd(vc, vc_i, i)), None

    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["self"]["k"], cache["self"]["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))

    last = jnp.maximum(n_new, 1) - 1
    x_last = x[jnp.arange(b), last][:, None, :]
    x_last = rmsnorm(params["final_norm"], x_last, cfg.norm_eps)
    logits = logits_from(params["embed"], cfg, x_last)[:, 0]
    return logits, {"self": {"k": ks, "v": vs}}


# ======================================================= paged chunked decode


def paged_cache_specs(cfg: ModelConfig, num_blocks: int, block_size: int,
                      impl: str = "pallas") -> dict[str, Any]:
    """Cache ParamSpec tree for the paged decode step: the KV lives in a
    shared page pool ``(layers, num_blocks, kv, block_size, width)``
    instead of dense per-slot rows — head-major within a page, the layout
    the Pallas kernel copies page by page (``kernels.paged_attention``),
    each head as wide as the attention ``impl`` that reads the pool needs
    (``kernels.ops.paged_pool_width``; zero lanes past ``hd``).
    Dense/moe only (attention caches)."""
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged cache supports dense/moe, got {cfg.family}")
    from repro.kernels import ops as kops

    geom = head_geom(cfg, tp_size())
    shape = (cfg.n_layers, num_blocks, geom.n_kv, block_size,
             kops.paged_pool_width(geom.head_dim, impl))
    axes = ("layers", None, "cache_kv", None, None)
    return {"paged": {
        "k": P.ParamSpec(shape, axes, init="zeros"),
        "v": P.ParamSpec(shape, axes, init="zeros"),
    }}


def decode_paged_chunk(cfg: ModelConfig, params: dict, cache: dict,
                       tokens: jax.Array, pos: jax.Array, n_new: jax.Array,
                       page_table: jax.Array, *, attention: str):
    """C-token decode straight over the paged KV pool: the kernel-enabled
    serving engine's single step.

    Same contract as :func:`decode_chunk` (tokens [B,C], pos [B], n_new
    [B]; logits at each lane's last real position) except the cache is
    ``{"paged": {"k", "v"}}`` — the shared page pool — and ``page_table``
    [B, n_pages] int32 maps each lane's logical blocks to physical
    pages.  Fresh KV rows are written through the table and attention
    reads through it (``kernels.paged_attention``, by the ``attention``
    implementation: ``"pallas"`` or ``"reference"``): no dense per-slot
    working cache exists anywhere on this path.

    A ``moe`` configuration's expert layers are :func:`moe.moe_serve`
    (dropless, over the experts held here); its leading dense layers
    (``n_dense_layers``) are a second layer stack, scanned first over the
    same carried pool.  Each layer's window and RoPE flag ride the scan
    as data (``ModelConfig.layer_windows``).

    Returns (logits, cache, stats): ``stats`` is ``{}``, or where the
    chip holds a share of the routed experts (``experts_held``)
    ``{"experts": [rows routed to the held experts, the most one held
    expert took]}`` int32, each summed over the expert layers.
    """
    fam = cfg.family
    if fam not in ("dense", "moe"):
        raise ValueError(f"decode_paged_chunk supports dense/moe, got {fam}")
    b, c = tokens.shape
    x = embed_tokens(params["embed"], tokens)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(x.dtype)
    windows = cfg.layer_windows()
    n_pages = page_table.shape[1]
    no_window = n_pages * cache["paged"]["k"].shape[3] + c
    valid = jnp.arange(c)[None, :] < n_new[:, None]
    layers, stack = params["layers"], None
    if fam == "moe":
        # the experts of every layer stay out of the scan and are indexed
        # inside moe_serve's loop
        moe_p = dict(layers["moe"])
        stack = {k: moe_p.pop(k) for k in ("gate", "up", "down")}
        layers = dict(layers, moe=moe_p)

    def layer(x, kp, vp, p, i, w, r, ffn):
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        # the whole pool rides the carry and the layer is an index into
        # it: writes scatter in place, the kernel selects the layer
        a, kp, vp = paged_chunk_decode_attention(
            cfg, p["attn"], h, kp, vp, i, page_table, pos, n_new,
            attention=attention, window=w, use_rope=r)
        if cfg.sandwich_norm:
            a = rmsnorm(p["ln1_post"], a, cfg.norm_eps)
        x = x + a
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        y, st = ffn(p, h2, i)
        if cfg.sandwich_norm:
            y = rmsnorm(p["ln2_post"], y, cfg.norm_eps)
        return x + y, kp, vp, st

    def run(carry, ps, first: int, ffn):
        """Scan one layer stack, the pool's layers ``first`` on."""
        n = jax.tree.leaves(ps)[0].shape[0]
        xs = (ps, jnp.arange(first, first + n))
        if cfg.sliding_window:
            ws = windows[first:first + n]
            xs += (jnp.asarray([w or no_window for w in ws], jnp.int32),
                   jnp.asarray([bool(w) or not cfg.nope_global for w in ws]))

        def body(carry, xs):
            x, kp, vp = carry
            p, i, w, r = xs + (None,) * (4 - len(xs))
            x, kp, vp, st = layer(x, kp, vp, p, i, w, r, ffn)
            return (x, kp, vp), st

        return jax.lax.scan(body, carry, xs)

    def mlp(p, h, i):
        return swiglu(p["mlp"], h), None

    def experts(p, h, i):
        return moe_serve(cfg, p["moe"], h, valid, stack,
                         i - cfg.n_dense_layers)

    carry = (x, cache["paged"]["k"], cache["paged"]["v"])
    stats = {}
    if fam == "dense":
        carry, _ = run(carry, layers, 0, mlp)
    else:
        if cfg.n_dense_layers:
            carry, _ = run(carry, params["dense_layers"], 0, mlp)
        carry, st = run(carry, layers, cfg.n_dense_layers, experts)
        if cfg.experts_held:
            stats = {"experts": jnp.sum(st, axis=0)}
    x, ks, vs = carry

    last = jnp.maximum(n_new, 1) - 1
    x_last = x[jnp.arange(b), last][:, None, :]
    x_last = rmsnorm(params["final_norm"], x_last, cfg.norm_eps)
    logits = logits_from(params["embed"], cfg, x_last)[:, 0]
    return logits, {"paged": {"k": ks, "v": vs}}, stats


# ============================================================ fused sampling


def lane_keys(seed: jax.Array, rid: jax.Array, step: jax.Array) -> jax.Array:
    """Counter-based per-lane PRNG keys: ``key = fold(fold(PRNGKey(seed),
    rid), step)``.

    Purely a function of (seed, request_id, step) — no generator state —
    so the ``step``-th token of a request draws the same key on any
    engine, in any slot, under any schedule, and a preempted request
    recomputed from scratch resumes its stream exactly.  All inputs are
    ``[B]``; the derivation is vmapped so the jitted step stays one fixed
    shape.
    """
    def one(s, r, t):
        k = jax.random.fold_in(jax.random.PRNGKey(s), r)
        return jax.random.fold_in(k, t)

    return jax.vmap(one)(seed, rid, step)


def sample_from_logits(logits: jax.Array, lane: dict[str, jax.Array]
                       ) -> jax.Array:
    """Fused token selection: logits ``[B, V]`` -> tokens ``[B]`` int32.

    ``lane`` carries per-lane ``[B]`` arrays: ``rid``/``step``/``seed``
    (key derivation, see :func:`lane_keys`) and ``temperature``/
    ``top_k``/``top_p`` (filtering).  ``temperature <= 0`` selects exact
    greedy argmax for that lane (bit-identical to the pre-sampling
    engines); ``top_k <= 0`` means no k-limit.  Every op is fixed-shape
    in (B, V) regardless of the request mix — sampling introduces no
    shape polymorphism, hence no recompiles on the serving hot path.

    Filtering is rank-based on one descending sort: the top-k cut keeps
    logits >= the k-th largest, the nucleus cut keeps the smallest set of
    tokens whose exclusive cumulative probability stays under ``top_p``
    (the argmax token always survives both).  The surviving set is
    sampled via per-lane-keyed Gumbel argmax (``jax.random.categorical``).
    """
    b, v = logits.shape
    lg = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(lane["temperature"], 1e-6)[:, None]
    scaled = lg / temp
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_eff = jnp.where(lane["top_k"] > 0, lane["top_k"], v)
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(k_eff - 1, 0, v - 1)[:, None], axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    # top_p >= 1 means no nucleus cut at all: bypass the comparison so
    # float32 cumsum rounding can never mask extreme-tail tokens
    p_bound = jnp.where(lane["top_p"] >= 1.0, jnp.inf, lane["top_p"])
    keep = cum_excl < p_bound[:, None]            # row 0 always True
    p_floor = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                      keepdims=True)
    masked = jnp.where((scaled >= kth) & (scaled >= p_floor), scaled,
                       -jnp.inf)

    keys = lane_keys(lane["seed"], lane["rid"], lane["step"])
    sampled = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    return jnp.where(lane["temperature"] > 0.0, sampled, greedy_tok)
