"""Bring-up check on the TPU: serve phi3-mini-3.8b at its published widths
through the normal serving path and hold what it served to the repo's
references.

    python chip_smoke.py [--seed N]           # one chip
    python chip_smoke.py --four-chips         # cluster phase, four chips

One chip: builds phi3-mini-3.8b (32 layers, d_model 3072, vocab 32064;
random weights from ``--seed``) and serves 8 requests that share a
256-token prefix through ``repro.launch.serve`` (the CLI's own path:
paged engine, scheduler, prefix cache, Pallas paged-attention kernel).
It fails unless every request finished, the prefix cache hit, the
served step's compiled HLO holds the Pallas kernel, the kernel agrees
with the page-table reference on one layer of the served pool, and the
served logits agree with a float32 cache-free forward (``Model.
reference_prefill``) at every decoded position.

``--four-chips``: only the cluster path — 4 replicas, one per chip,
behind prefix-affinity routing, against one engine serving the same
requests (``compare_engines(cluster=...)`` on logits).

Everything runs in this one process (a chip belongs to one process).
The last line of stdout is one JSON object naming the device; it is
printed only when every check passed.  Off the TPU the script exits
non-zero before serving anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "phi3-mini-3.8b"

# Serving geometry.  A request is <= 272 prompt tokens (256 shared + 4-16
# own) plus 32 new ones: 19 pages of 16 rows, within max_len 320 (20).
SLOTS, MAX_LEN, MAX_NEW, CHUNK, BLOCK = 4, 320, 32, 32, 16
SHARED_PREFIX, N_REQUESTS = 256, 8
# 512 pages: 3.0 GiB of bf16 KV (384 KiB a token), 4.3 GB on the chip
# once head_dim 96 is padded to 128 lanes; with 7.6 GB of weights that
# stays under 13 GB of the 16.
POOL_PAGES = 512
# the cluster phase holds a replica pool next to one engine's pool on
# chip 0
CLUSTER_POOL_PAGES = 256

# ---- tolerances, each with its reason ----------------------------------
# Kernel vs page-table reference on the same pool.  Both accumulate in
# float32 and round the output to bf16 once (half an ulp: 2^-9 of the
# value); on the chip the probabilities may enter the PV product rounded
# to bf16 (another 2^-9 of the weighted V).  Every output is a convex
# combination of V rows, so the error is bounded by ~2^-8 of max|V|; the
# limit is twice that.  An fp8 path (2^-4) fails it.
KERNEL_TOL = 2.0 ** -7
# Served (bf16 weights and activations) vs the float32 reference, as the
# relative L2 error of each logits row.  bf16's unit roundoff is 2^-9;
# the 4-layer CPU tests measure 1.0-1.5e-2, and the error of a residual
# stack grows about as the square root of depth: sqrt(32/4) * 1.5e-2 =
# 4.2e-2 for 32 layers.  The limit is twice that.  Rounding the weights
# to fp8 measures 0.13-0.20 at 4 layers and fails.
SERVED_TOL = 8e-2
# Cluster vs one engine: the same compiled program on the same kind of
# chip; a row can differ only if batch composition changed its rounding.
# A misrouted or corrupted request moves logits by O(1) of their scale,
# so the limit is 1e-3: 1/80 of the served-vs-reference limit.
CLUSTER_TOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(ok: bool, msg: str) -> None:
    log(f"  [{'ok' if ok else 'FAIL'}] {msg}")
    if not ok:
        fail(msg)


def peak_bytes(dev) -> int:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use", -1)


def _compile_seconds():
    """Running total of backend compile time in this process."""
    import jax
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    total = [0.0]

    def on_event(event, seconds, **_):
        if event == BACKEND_COMPILE_EVENT:
            total[0] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return total


# ------------------------------------------------------------ one chip


def kernel_vs_reference(engine, seed: int) -> float:
    """The Pallas kernel against ``paged_attention_ref`` on the served
    pool's last layer (selected in the kernel's page copies),
    random queries at the model's width, ragged lanes.  Returns the
    largest error over valid rows, relative to max|V| on the pages read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import paged_attention

    view = engine.view
    layers, nb, kv, bs, _ = view.k.shape
    cfg = engine.model.cfg
    # the model's own query width: the kernel meets the pool's wider
    # heads (pad lanes) itself, as on the served path
    hd = cfg.resolved_head_dim
    g = cfg.n_heads // cfg.n_kv_heads
    rng = np.random.default_rng(seed)
    n_pages = view.max_pages
    # pages the serving run wrote first (the allocator hands out low ids)
    table = rng.permutation(SLOTS * n_pages).reshape(SLOTS, n_pages)
    n_new = rng.integers(1, CHUNK + 1, size=SLOTS)
    pos = np.array([rng.integers(0, n_pages * bs - n + 1) for n in n_new])
    q = jnp.asarray(rng.standard_normal((SLOTS, CHUNK, kv, g, hd)),
                    jnp.bfloat16)
    args = (q, view.k, view.v, jnp.asarray(table, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(n_new, jnp.int32))
    # the pool stays in its pinned layout: no relayout copy of it here
    pools = (None, view.format, view.format, None, None, None)
    layer = layers - 1

    def ref(q, k, v, pt, pos, n_new):
        v_max = jnp.max(jnp.abs(v[layer][pt.ravel()].astype(jnp.float32)))
        return paged_attention(q, k, v, pt, pos, n_new, layer=layer,
                               impl="reference"), v_max

    got = jax.jit(lambda *a: paged_attention(*a, layer=layer, impl="pallas"),
                  in_shardings=pools)(*args)
    with jax.default_matmul_precision("highest"):
        want, v_max = jax.jit(ref, in_shardings=pools)(*args)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = max(float(np.max(np.abs(got[b, :n] - want[b, :n])))
              for b, n in enumerate(n_new))
    return err / max(float(v_max), 1e-30)


def served_vs_reference(model, params, done) -> dict:
    """Every served logits row against the float32 cache-free forward
    over the same tokens, in one batched call (sequences right-padded:
    attention is causal)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.verify import rel_l2

    done = sorted(done, key=lambda r: r.rid)
    feeds = [r.prompt[-(MAX_LEN - r.max_new):] for r in done]
    seqs = [f + r.out[:-1] for f, r in zip(feeds, done)]
    width = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    k = min(len(r.out) for r in done)
    positions = np.array([[len(f) - 1 + t for t in range(k)] for f in feeds],
                         np.int32)
    ref = jax.jit(model.reference_prefill)(params, jnp.asarray(tokens),
                                           jnp.asarray(positions))
    ref = np.asarray(ref)[:, :, :model.cfg.vocab_size]
    served = np.stack([np.stack(r.logits[:k]) for r in done])
    return {
        "first": rel_l2(served[:, 0], ref[:, 0]),      # last prompt position
        "last": rel_l2(served[:, -1], ref[:, -1]),     # after decoding
        "all": rel_l2(served, ref),                    # every row
        "rows": int(served.shape[0] * served.shape[1]),
    }


def one_chip(seed: int, compile_s) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import run_serve
    from repro.serve.engine import _chunk_fn_for

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    run = run_serve(ARCH, n_requests=N_REQUESTS, slots=SLOTS,
                    max_len=MAX_LEN, max_new=MAX_NEW, seed=seed,
                    block_size=BLOCK, chunk=CHUNK, num_blocks=POOL_PAGES,
                    shared_prefix=SHARED_PREFIX, keep_logits=True)
    wall = time.perf_counter() - t0
    out, model, params, eng = run.out, run.model, run.params, run.engine
    cfg = model.cfg
    rep = eng.report()
    log(f"config: {cfg.name}  layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim="
        f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size}  "
        f"params={cfg.param_count()}")
    log(f"pool: {rep['pages']} pages x {BLOCK} rows, "
        f"{eng.view.nbytes} bytes logical (k+v)")
    log(f"served: {out['served']}/{N_REQUESTS} requests, "
        f"{out['tokens_out']} tokens, {out['decode_steps']} steps, "
        f"prefix_hit_rate={out['prefix_hit_rate']}, "
        f"attention={rep['attention']}")
    log(f"one run's wall time (not a metric), build + serve: {wall:.3f} s; "
        f"compile so far {compile_s[0]:.3f} s")
    log(f"peak_bytes_in_use after serving: {peak_bytes(dev)}")
    check(cfg.n_layers == 32 and cfg.d_model == 3072
          and cfg.vocab_size == 32064, "phi3-mini-3.8b at published widths")
    check(all(r.finished for r in run.done) and out["served"] == N_REQUESTS,
          "every request finished")
    check(out["prefix_hit_rate"] > 0, "prefix cache hit (prefix_hit_rate > 0)")
    check(rep["attention"] == "pallas", "engine reports the Pallas kernel")
    check(out["audit"]["gate_ok"],
          f"audit gate clean (findings: {out['audit']['findings']})")

    # the served step itself: compile (persistent-cache read) and look
    step = _chunk_fn_for(model, False, "pallas", eng.view.format)
    i32 = jnp.int32
    compiled = step.lower(
        params, eng.view.cache(), jnp.zeros((SLOTS, CHUNK), i32),
        jnp.zeros((SLOTS,), i32), jnp.zeros((SLOTS,), i32),
        jnp.asarray(eng.view.page_table)).compile()
    mem = compiled.memory_analysis()
    log(f"step program: temp {mem.temp_size_in_bytes} bytes, arguments "
        f"{mem.argument_size_in_bytes} bytes (weights + pool as laid out "
        f"on the chip)")
    check("tpu_custom_call" in compiled.as_text(),
          "served step's compiled HLO holds the Pallas kernel "
          "(tpu_custom_call)")
    check(mem.temp_size_in_bytes < eng.view.nbytes // cfg.n_layers,
          "pool updated in place (step temporaries < one layer's pool)")

    err = kernel_vs_reference(eng, seed)
    check(err <= KERNEL_TOL, f"kernel vs paged_attention_ref on layer "
          f"{cfg.n_layers - 1}: max err / max|V| = {err:.6e} "
          f"<= {KERNEL_TOL:.6e}")

    # free the pool before the float32 reference
    run.engine = eng = None
    del compiled, step
    gc.collect()
    agree = served_vs_reference(model, params, run.done)
    log(f"served vs float32 reference over {agree['rows']} logits rows "
        f"(relative L2 per row, limit {SERVED_TOL}):")
    check(agree["first"] <= SERVED_TOL,
          f"last prompt position: {agree['first']:.6e}")
    check(agree["last"] <= SERVED_TOL,
          f"after decoding {MAX_NEW - 1} tokens: {agree['last']:.6e}")
    check(agree["all"] <= SERVED_TOL, f"every row: {agree['all']:.6e}")
    log(f"peak_bytes_in_use: {peak_bytes(dev)}; compile total "
        f"{compile_s[0]:.3f} s")


# ---------------------------------------------------------- four chips


def four_chips(seed: int, compile_s) -> None:
    import jax
    import numpy as np

    from repro.core.registry import resolve_arch
    from repro.models import build
    from repro.serve import Request
    from repro.serve.engine import compare_engines

    devs = jax.devices()
    check(len(devs) >= 4, f"four chips visible ({len(devs)})")
    cfg = resolve_arch(ARCH)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, size=SHARED_PREFIX).tolist()
    prompts = [prefix + rng.integers(0, cfg.vocab_size,
                                     size=int(rng.integers(4, 17))).tolist()
               for _ in range(N_REQUESTS)]

    def make():
        return [Request(rid=i, prompt=list(p), max_new=MAX_NEW)
                for i, p in enumerate(prompts)]

    t0 = time.perf_counter()
    report = compare_engines(
        model, params, make, slots=SLOTS, max_len=MAX_LEN,
        block_size=BLOCK, chunk=CHUNK,
        engine_kwargs={"paged": {"num_blocks": CLUSTER_POOL_PAGES}},
        cluster={"replicas": 4, "routing": "affinity",
                 "num_blocks": CLUSTER_POOL_PAGES},
        logits_tol=CLUSTER_TOL)
    wall = time.perf_counter() - t0
    log(f"config: {cfg.name} x 4 replicas (one per chip) vs one engine; "
        f"one run's wall time (not a metric): {wall:.3f} s; compile "
        f"{compile_s[0]:.3f} s")
    for d in devs[:4]:
        log(f"  device {d.id} ({d.device_kind}): peak_bytes_in_use "
            f"{peak_bytes(d)}")
    for v in report.verdicts:
        log(f"  verdict {v.kind}: {v.detail}")
    check(all(peak_bytes(d) > 7e9 for d in devs[:4]),
          "every chip held a replica's weights (peak > 7 GB each)")
    check(report.ok and any(v.kind == "logits" for v in report.verdicts),
          f"cluster logits agree with one engine (limit {CLUSTER_TOL})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cluster phase on four chips")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))
    from repro.core.bootstrap import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found (JAX platform {dev.platform!r}); this check "
             f"runs only on the chip")
    log(f"device: {dev.device_kind} x {len(jax.devices())} "
        f"(jax {jax.__version__}); compile cache {cache_dir}")
    compile_s = _compile_seconds()
    if args.four_chips:
        four_chips(args.seed, compile_s)
    else:
        one_chip(args.seed, compile_s)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
