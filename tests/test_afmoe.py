"""Trinity-Mini's layer (afmoe) in the program, at a tiny size on the CPU,
against the benchmark's plain reference (``bench/references/afmoe.py``):
served logits through ``PagedServeEngine``, the chip's share of the
experts, dropless routing, the windowed paged kernel, and the paths
that refuse the configuration."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program  # noqa: E402
from bench.families import afmoe as family  # noqa: E402
from bench.references import afmoe as reference  # noqa: E402
from bench.tests.afmoe_tiny import CONFIG  # noqa: E402
from repro.audit.trace import Tracer  # noqa: E402
from repro.configs.archs import TRINITY_MINI  # noqa: E402
from repro.configs.base import reduced  # noqa: E402
from repro.kernels.paged_attention import (block_pages,  # noqa: E402
                                           first_page, kernel_blocks,
                                           last_page,
                                           paged_attention_pallas,
                                           paged_attention_ref,
                                           walk_blocks)
from repro.models import moe  # noqa: E402
from repro.serve import PagedServeEngine, Request  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration's model and the seed's weights, laid out
    by the benchmark's family from the reference's draw."""
    model = program.build_model(family.model_config(CONFIG))
    params = program.make_params(model, family, reference, CONFIG, SEED)
    return model, params


def _serve(model, params, prompts, max_new, tracer=None):
    eng = PagedServeEngine(model, params, slots=3, max_len=96, block_size=8,
                           chunk=8, tracer=tracer)
    reqs = [Request(rid=i, prompt=p, max_new=max_new, keep_logits=True)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.drain()
    return reqs


def test_served_logits_agree_with_the_reference(tiny):
    """Chunked prefill (chunks crossing the window's edge) then decode
    through the page pool: at every served position the reference left
    decided (its route clear of a tie), the served logits lie within
    the bfloat16 band of the reference's float32 forward.  The band, for
    logits of standard deviation about 1.15: the widest of a position's
    500 differences reads 0.03-0.09 where no route flipped (median 0.06,
    so 0.08), and up to 0.62 where rounding flipped a route whose
    margin lay just over the reference's tie (so 1.0)."""
    model, params = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 500, n).tolist() for n in (21, 37, 46, 30)]
    reqs = _serve(model, params, prompts, max_new=24)
    tokens = [r.prompt + r.out[:-1] for r in reqs]
    positions = [[len(r.prompt) - 1 + t for t in range(len(r.out))]
                 for r in reqs]
    exact = reference.logits(CONFIG, SEED, tokens, positions)
    diffs, decided = [], 0
    for r, ref in zip(reqs, exact):
        served = np.stack(r.logits)
        ok = ~np.isnan(ref).all(axis=1)
        decided += int(ok.sum())
        diffs.append(np.abs(served[ok] - ref[ok]).max(axis=1))
    diffs = np.concatenate(diffs)
    assert decided > len(diffs) // 2 and decided >= 60
    assert max(len(t) for t in tokens) > 4 * CONFIG["sliding_window"]
    assert np.median(diffs) < 0.08, np.median(diffs)
    assert diffs.max() < 1.0, diffs.max()


def _layer_params(cfg, offset, held):
    """An expert layer's reference weights at a share, as the program's
    ``moe`` tree, in float32."""
    share = dict(cfg, num_experts=held,
                 expert_parallel=dict(cfg["expert_parallel"],
                                      expert_offset=offset))
    p = reference.draw_layer(share, jax.random.key(7), 3)
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    tree = {"router": p["router"], "bias": p["expert_bias"],
            "gate": p["expert_gate"], "up": p["expert_up"],
            "down": p["expert_down"],
            "shared": {"gate": p["shared_gate"], "up": p["shared_up"],
                       "down": p["shared_down"]}}
    return share, p, tree


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips of EP 4 each hold 4 of the 16 experts: the program's
    layer on each share, less the shared expert every chip computes
    alike, summed, and the shared expert once, is the reference's uncut
    layer."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 64)), jnp.float32)
    valid = jnp.ones((2, 8), bool)
    uncut, p_all, _ = _layer_params(CONFIG, 0, 16)
    want, _ = reference._experts(uncut, p_all, x.reshape(16, 64))
    parts, shared = [], None
    for offset in (0, 4, 8, 12):
        share, _, tree = _layer_params(CONFIG, offset, 4)
        mc = family.model_config(share)
        y, _ = moe.moe_serve(mc, tree, x, valid)
        shared = moe.swiglu(tree["shared"], x)
        parts.append(y - shared)
    got = (sum(parts) + shared).reshape(16, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _crowded(tree, expert):
    """The layer with a selection bias that makes every row choose
    ``expert`` among its top-k."""
    return dict(tree, bias=tree["bias"].at[expert].set(100.0))


def test_routing_is_dropless_and_a_row_stands_alone():
    """Every row picks one held expert (more rows than a tile of the
    grouped loop): each row's output is what it is when served alone,
    bit for bit, and the held experts compute exactly the (fed row,
    chosen held expert) pairs of the reference's routing; padding rows
    are routed nowhere."""
    share, p, tree = _layer_params(CONFIG, 4, 4)
    mc = family.model_config(share)
    tree = _crowded(tree, 5)
    rng = np.random.default_rng(1)
    b, c = 4, 48
    x = jnp.asarray(rng.standard_normal((b, c, 64)), jnp.bfloat16)
    # the program's dtypes: bfloat16 weights, the router and bias float32
    tree = {k: v if k in ("router", "bias") else jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), v) for k, v in tree.items()}
    n_new = jnp.asarray([48, 48, 0, 40])      # 136 fed rows > moe.TILE
    valid = jnp.arange(c)[None, :] < n_new[:, None]
    y, stats = moe.moe_serve(mc, tree, x, valid)
    # the reference's routing of every fed row
    xf = np.asarray(x, np.float32).reshape(b * c, 64)
    scores = jax.nn.sigmoid(xf @ np.asarray(tree["router"]))
    _, top_i = jax.lax.top_k(scores + tree["bias"], mc.top_k)
    held = np.asarray((top_i >= 4) & (top_i < 8))
    fed = np.asarray(valid).reshape(-1)
    assert held[fed].any(axis=1).all()             # all pick expert 5
    per_expert = [int(((np.asarray(top_i) == e) & fed[:, None]).sum())
                  for e in range(4, 8)]
    assert stats.tolist() == [int(held[fed].sum()), max(per_expert)]
    assert max(per_expert) > moe.TILE
    for row in (0, 47, 48 + 16, 3 * 48 + 32):
        alone = jnp.zeros((b, c), bool).reshape(-1).at[row].set(True)
        y1, s1 = moe.moe_serve(mc, tree, x, alone.reshape(b, c))
        np.testing.assert_array_equal(
            np.asarray(y1).reshape(-1, 64)[row],
            np.asarray(y).reshape(-1, 64)[row])
        assert int(s1[0]) == int(held[row].sum())


def test_padding_rows_take_no_expert():
    share, _, tree = _layer_params(CONFIG, 4, 4)
    mc = family.model_config(share)
    x = jnp.ones((2, 8, 64), jnp.float32)
    _, stats = moe.moe_serve(mc, _crowded(tree, 6), x,
                             jnp.zeros((2, 8), bool))
    assert stats.tolist() == [0, 0]


# ------------------------------------------------------- windowed kernel


def _case(b, c, kv, g, hd, bs, n_pages, pos, n_new, seed):
    rng = np.random.default_rng(seed)
    nb = b * n_pages + 3
    q = jnp.asarray(rng.standard_normal((b, c, kv, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, kv, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, kv, bs, hd)), jnp.float32)
    pt = jnp.asarray(rng.permutation(nb)[:b * n_pages]
                     .reshape(b, n_pages).astype(np.int32))
    return q, kp, vp, pt, jnp.asarray(pos, jnp.int32), jnp.asarray(
        n_new, jnp.int32)


@pytest.mark.kernel
@pytest.mark.parametrize("window", [5, 16, 24, 1000])
def test_windowed_kernel_matches_the_reference(window):
    """G = 4, chunks that start before the window's edge and end past
    it, a decode row far past it, an idle lane and a lane inside its
    first window; a window past every context is no window."""
    b, c, kv, g, hd, bs, n_pages = 5, 8, 2, 4, 32, 8, 8
    pos, n_new = [12, 40, 55, 0, 3], [8, 8, 1, 0, 5]
    args = _case(b, c, kv, g, hd, bs, n_pages, pos, n_new, seed=window)
    out = paged_attention_pallas(*args, window=window, interpret=True)
    ref = paged_attention_ref(*args, window=window)
    for i in range(b):
        n = n_new[i]
        np.testing.assert_allclose(np.asarray(out)[i, :n],
                                   np.asarray(ref)[i, :n],
                                   rtol=2e-5, atol=2e-5)
    if window == 1000:
        whole = paged_attention_ref(*args)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(whole),
                                   rtol=1e-6, atol=1e-6)
    else:
        whole = paged_attention_ref(*args)
        assert not np.allclose(np.asarray(ref)[1, :8],
                               np.asarray(whole)[1, :8])


def test_windowed_walk_names_no_page_before_the_window():
    """A windowed layer's walk starts at the window's first page: its
    blocks copy each lane's pages from that page to its last, in order,
    and none before it."""
    bs, n_pages, window, pages = 16, 12, 40, 3
    pos = np.array([100, 30, 150])
    nn = np.array([1, 32, 8])
    firsts = first_page(pos, window, bs)
    lasts = last_page(pos, nn, bs, n_pages)
    assert firsts.tolist() == [3, 0, 6]
    for b in range(3):
        first, last = int(firsts[b]), int(lasts[b])
        n = int(walk_blocks(pos[b], nn[b], window, bs, pages, n_pages))
        copied = [int(p) for i in range(n)
                  for p in block_pages(first, i, pages) if p <= last]
        assert copied == list(range(first, last + 1))


# -------------------------------------------------------- engine and paths


def test_step_events_count_the_window_and_the_experts(tiny):
    """A traced engine's ``step`` events carry the windowed walk's pages
    (from ``work`` and the window), the kernel's compute blocks over
    every layer (each windowed layer's from the window's first page) and
    the rows the held experts took."""
    model, params = tiny
    tr = Tracer()
    rng = np.random.default_rng(4)
    _serve(model, params, [rng.integers(0, 500, 30).tolist()
                           for _ in range(2)], max_new=6, tracer=tr)
    events = [e.data for e in tr.events("step")]
    assert events
    windowed = sum(1 for w in model.cfg.layer_windows() if w)
    kv, hd = model.cfg.n_kv_heads, model.cfg.resolved_head_dim
    # the CPU engine's pool is as wide as the reference reads it: hd
    _, pages = kernel_blocks((3, 8, kv, model.cfg.n_heads // kv, hd),
                             (1, 1, kv, 8, hd), 2, 2, 12)
    for ev in events:
        walk = sum(int(last_page(p, n, 8, 12)) - int(first_page(p, 16, 8))
                   + 1 for p, n in ev["work"])
        assert ev["window_pages"] == windowed * walk
        assert ev["attn_blocks"] == sum(
            int(walk_blocks(p, n, w or None, 8, pages, 12))
            for w in model.cfg.layer_windows() for p, n in ev["work"])
        fed = sum(n for _, n in ev["work"])
        # every expert layer sends each fed row to 0..8 held experts
        assert 0 <= ev["expert_rows"] <= 6 * fed * 4
        assert ev["expert_rows_max"] <= ev["expert_rows"]
    assert sum(ev["expert_rows"] for ev in events) > 0


def test_other_paths_refuse_the_configuration():
    from repro.core.registry import resolve_arch
    from repro.models import build
    from repro.models import stack
    from repro.serve.engine import ServeEngine

    assert resolve_arch("trinity-mini") is TRINITY_MINI
    assert resolve_arch("trinity_mini") is TRINITY_MINI
    cfg = reduced(TRINITY_MINI)
    assert cfg.paged_only and cfg.n_dense_layers == 1
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="paged"):
        model.prefill(params, {"tokens": tokens})
    with pytest.raises(ValueError, match="paged"):
        model.decode_chunk(params, {}, tokens, jnp.zeros((1,), jnp.int32),
                           jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="paged"):
        model.decode_step(params, {}, tokens[:, :1],
                          jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="paged"):
        stack.forward(cfg, params, {"tokens": tokens})
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params, slots=1, max_len=16)
    with pytest.raises(ValueError, match="paged"):
        PagedServeEngine(model, params, slots=1, max_len=16, kernel="gather")
    # a dense configuration is none of these
    assert not dataclasses.replace(cfg, family="dense", n_experts=0,
                                   router="softmax", n_shared_experts=0,
                                   experts_held=0, n_dense_layers=0,
                                   sliding_window=0, attn_gate=False,
                                   sandwich_norm=False,
                                   embed_scale=False).paged_only
