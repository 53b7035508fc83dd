"""Serving engines: contiguous slot caches (oracle) and the paged path.

Both engines implement the ``serve.api.Engine`` protocol — ``submit /
step / drain / cancel / report`` — so every caller (launchers, examples,
benchmarks, the audit pipeline) speaks one request-lifecycle contract and
the seed's two incompatible ``run()`` shapes survive only as the
``api.run_requests`` compatibility shim.

``ServeEngine`` is the seed contiguous engine: a fixed-shape jitted
decode step over B slots, serial per-token prefill at admission.  It is
kept as the *dual-environment oracle* — the paged engine's correctness
proof is a ``compare_engines`` verdict (core.verify.DualEnvHarness) that
the two produce identical token streams, greedy AND sampled (counter-
based per-request PRNG keys make sampled streams engine-independent).

``PagedServeEngine`` is the production path: a refcounted block allocator
+ hash-chained prefix cache (serve.paging) so overlapping prompts reuse KV
pages instead of recomputing them, chunked prefill so a long prompt
consumes C tokens per step in the same batched call that advances
decoding lanes by one, and a priority scheduler (serve.scheduler) with
preemption-on-OOM.  Preempted work parks its written KV pages on a host
swap tier (serve.paging.HostSwapPool) and readmission swaps them back in
— recompute-on-readmit survives as the costed fallback (and the audited
``swap=False`` misconfiguration).  Its default KV pathway
(``kernel="paged"``) keeps the cache *in the page pool on device* and
attends it through the per-slot page table (``decode_paged_chunk`` →
``kernels.paged_attention``); the dense per-slot working cache survives
only as the audited ``kernel="gather"`` fallback.

Sampling is fused into the jitted step (``models.decode.
sample_from_logits``): the engines exchange only ``[B]`` token vectors
with the device, and per-lane sampling state rides fixed-shape arrays —
no shape polymorphism, no recompiles, no host-side logits traffic.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.audit.trace import NULL_TRACER, Tracer
from repro.kernels import ops as kops
from repro.models.decode import CompileWatcher
from repro.models.model import Model
from repro.models.stack import refuse_paged_only
from repro.serve.api import (GREEDY, LaneState, RequestHandle, SamplingParams,
                             host_snapshot, run_requests)
from repro.serve.paging import (BlockAllocator, DevicePageView, HostSwapPool,
                                KVPool, PrefixCache, chain_hashes, pages_for)
from repro.serve.scheduler import (DONE, PREEMPTED, RUNNING, WAITING, Plan,
                                   SchedEntry, Scheduler, SwapCostModel)

@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int = 32
    eos_id: int = -1            # -1: never stops early
    priority: int = 0           # higher preempts lower on OOM (paged path)
    sampling: SamplingParams | None = None   # None => greedy
    # keep_logits: the paged engines append the float32 logits row
    # ([vocab_size]) each output token was chosen from to ``logits`` —
    # how a caller holds served logits to a reference (one host copy of
    # the [slots, vocab] step output per tick while such a request runs)
    keep_logits: bool = False
    out: list[int] = field(default_factory=list)
    logits: list[np.ndarray] = field(default_factory=list)
    finished: bool = False
    cancelled: bool = False
    t_submit: float = 0.0
    t_first: float = 0.0


def _validate(req: Request) -> None:
    """Static request validation shared by both engines' ``submit``."""
    if not req.prompt:
        raise ValueError(f"request {req.rid}: empty prompt (decoding "
                         f"needs at least one token of context)")
    if not -2**31 <= req.rid < 2**31:
        # the rid rides an int32 lane array into the jitted step
        raise ValueError(f"request id {req.rid} does not fit int32")


def _validate_fit(req: Request, max_len: int) -> None:
    """Reject a generation budget the slot geometry cannot hold.  Both
    engines clamp the prompt to ``prompt[-(max_len - max_new):]``; with
    ``max_new >= max_len`` that slice silently degenerates (``[-0:]``
    keeps the whole prompt, larger budgets truncate the wrong end) and
    the request only dies later, deep in page-table binding."""
    if req.max_new < 1:
        raise ValueError(
            f"request {req.rid}: max_new={req.max_new} must be >= 1")
    if req.max_new >= max_len:
        raise ValueError(
            f"request {req.rid}: max_new={req.max_new} must be < "
            f"max_len={max_len} (the prompt is clamped to max_len - "
            f"max_new tokens of context; no context would remain)")


def _samples(req: Request) -> bool:
    return not (req.sampling or GREEDY).greedy


# Fixed-shape slot movers for the gather pathway's swap tier (the paged
# pool's page movers live on ``DevicePageView``).  The slot index is a
# *traced* argument, so each helper compiles exactly once per cache
# shape; eager ``.at[idx].set`` would bake the index into the program and
# pay a fresh XLA compile on nearly every swap.
@jax.jit
def _read_slot(cache, slot):
    """One slot's dense rows ``(layers, max_len, kv, hd)`` (gather mode)."""
    return jax.lax.dynamic_slice_in_dim(cache, slot, 1, axis=1)[:, 0]


@jax.jit
def _write_slot(cache, slot, slab):
    return jax.lax.dynamic_update_slice_in_dim(cache, slab[:, None], slot,
                                               axis=1)


@dataclass
class EngineStats:
    served: int = 0
    cancelled: int = 0
    decode_steps: int = 0
    tokens_out: int = 0
    # bounded occupancy accumulator (running sum + tick count) instead of
    # an unbounded per-tick list: the mean is exact (integer sum / count,
    # same value np.mean produced) and memory is O(1) for long-lived
    # serving processes
    occupancy_sum: int = 0
    occupancy_ticks: int = 0

    def observe_occupancy(self, lanes: int) -> None:
        self.occupancy_sum += lanes
        self.occupancy_ticks += 1

    @property
    def mean_occupancy(self) -> float:
        return (self.occupancy_sum / self.occupancy_ticks
                if self.occupancy_ticks else 0.0)


class ServeEngine:
    def __init__(self, model: Model, params: Any, *, slots: int = 4,
                 max_len: int = 256, tracer: Tracer | None = None):
        refuse_paged_only(model.cfg, "ServeEngine")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        cache = model.zero_cache(slots, max_len)
        self.cache = cache
        self.pos = np.zeros((slots,), np.int32)       # next write position
        self.active: dict[int, Request] = {}          # slot -> request
        self.pending: list[tuple[float, Request]] = []  # (arrival, req) FCFS
        self.now = 0.0                                # step-counter clock
        self.lane = LaneState(slots)
        self.stats = EngineStats()
        self.trace = tracer or NULL_TRACER
        # two fused programs, dispatched per call on whether any lane in
        # the batch actually samples: all-greedy serving (the default)
        # never lowers the sampling pipeline and pays exactly the seed
        # engine's cost; jax.jit is lazy, so the unused variant never
        # compiles.  Distinct watcher names keep the per-program
        # compile expectation (max 1) meaningful for both.
        self._decode = CompileWatcher(
            jax.jit(model.decode_greedy_step, donate_argnums=(1,)),
            "decode_step", on_compile=self._on_compile)
        self._decode_sample = CompileWatcher(
            jax.jit(model.decode_sample_step, donate_argnums=(1,)),
            "decode_sample_step", on_compile=self._on_compile)
        self._last_token = np.zeros((slots, 1), np.int32)
        self.trace.emit("engine-init", engine="contiguous",
                        family=model.cfg.family, arch=model.cfg.name,
                        slots=slots, max_len=max_len)

    def _on_compile(self, fn: str, reason: str, sig: tuple) -> None:
        self.trace.emit("compile", fn=fn, reason=reason, signature=sig)

    # ------------------------------------------------------------ intake
    def submit(self, req: Request, *, arrival: float | None = None
               ) -> RequestHandle:
        _validate(req)
        _validate_fit(req, self.max_len)
        arrival = self.now if arrival is None else arrival
        req.t_submit = req.t_submit or time.perf_counter()
        self.pending.append((arrival, req))
        self.trace.emit("submit", rid=req.rid, tick=self.now,
                        arrival=arrival, prompt_tokens=len(req.prompt),
                        max_new=req.max_new,
                        sampling=(req.sampling or GREEDY).describe())
        return RequestHandle(self, req)

    def has_work(self) -> bool:
        return bool(self.pending or self.active)

    def _free_slots(self) -> list[int]:
        return [s for s in range(self.slots) if s not in self.active]

    def _admit(self, req: Request, slot: int, arrival: float) -> None:
        """Prefill the prompt into this slot serially (single-slot prefill;
        a production engine would batch same-length prompts)."""
        tokens = req.prompt[-(self.max_len - req.max_new):]
        self.lane.set(slot, req)     # step=0: the first output token's key
        # step the prompt through decode one token at a time into the slot
        # rows (slot-local prefill keeps the cache layout identical; other
        # lanes' rows are recomputed idempotently and their sampled tokens
        # discarded — counter-based keys consume no stream state).  Only
        # the final step's token is read, so only it needs the sampled
        # program; every earlier step takes the cheap argmax variant
        # (the cache updates are identical).
        for i, tok in enumerate(tokens):
            self._last_token[slot, 0] = tok
            self.pos[slot] = i
            toks, logits = self._dispatch(
                _samples(req) and i == len(tokens) - 1)
        self.pos[slot] = len(tokens)
        nxt = int(np.asarray(toks)[slot])
        req.out.append(nxt)
        if req.keep_logits:
            req.logits.append(self._row(logits, slot))
        req.t_first = time.perf_counter()
        self._last_token[slot, 0] = nxt
        self.active[slot] = req
        # the serial prefill completes inside the admission tick, so the
        # admit / prefill-done / first-token boundaries coincide — the
        # timeline layer orders them by kind within the tick
        self.trace.emit("admit", rid=req.rid, slot=slot, tick=self.now,
                        prompt_tokens=len(tokens), cached_tokens=0)
        self.trace.emit("prefill-done", rid=req.rid, tick=self.now,
                        slot=slot, consumed=len(tokens))
        self.trace.emit("first-token", rid=req.rid, tick=self.now,
                        ttft_ticks=self.now - arrival)

    def _dispatch(self, sampled: bool) -> tuple[jax.Array, jax.Array]:
        """One fused decode call on snapshots of the host-side lane
        arrays (``host_snapshot``: the prefill loop mutates them before
        the dispatched step has run); returns (tokens, logits)."""
        tok = jnp.asarray(host_snapshot(self._last_token))
        pos = jnp.asarray(host_snapshot(self.pos))
        if sampled:
            toks, logits, self.cache = self._decode_sample(
                self.params, self.cache, tok, pos, self.lane.as_args())
        else:
            toks, logits, self.cache = self._decode(self.params, self.cache,
                                                    tok, pos)
        return toks, logits

    def _row(self, logits: jax.Array, slot: int) -> np.ndarray:
        return np.asarray(logits[slot, :self.model.cfg.vocab_size])

    def _retire(self, slot: int) -> Request:
        req = self.active.pop(slot)
        req.finished = True
        self.lane.clear(slot)
        self.trace.emit("finish", rid=req.rid, slot=slot, tick=self.now,
                        tokens_out=len(req.out))
        self.stats.served += 1
        return req

    # -------------------------------------------------------------- step
    def step(self) -> list[Request]:
        """One engine tick: admit ready pending requests (strict FCFS)
        into free slots, then one batched fused decode call (greedy or
        sampled program, chosen by the batch's request mix)."""
        self.now += 1.0
        done: list[Request] = []
        # FCFS over *ready* requests, matching the paged scheduler's
        # arrival semantics: a future-dated head must not block a ready
        # request behind it (the two Engine implementations agree on
        # out-of-order arrivals)
        i = 0
        while i < len(self.pending) and self._free_slots():
            arrival, req = self.pending[i]
            if arrival > self.now:
                i += 1
                continue
            self.pending.pop(i)
            slot = self._free_slots()[0]
            self._admit(req, slot, arrival)
            # the admission-produced first token can already satisfy the
            # finish conditions (max_new=1, eos on first token): retire
            # now, exactly like the paged engine does after prefill
            tok = req.out[-1]
            if (tok == req.eos_id or len(req.out) >= req.max_new
                    or self.pos[slot] >= self.max_len - 1):
                done.append(self._retire(slot))

        if not self.active:
            return done
        sampled = any(_samples(r) for r in self.active.values())
        if sampled:
            for slot, req in self.active.items():
                self.lane.set(slot, req)
        toks, logits = self._dispatch(sampled)
        self.stats.decode_steps += 1
        self.stats.observe_occupancy(len(self.active))
        self.trace.emit("step", step_kind="decode", lanes=len(self.active))
        nxt = np.asarray(toks)

        finished = []
        for slot, req in self.active.items():
            tok = int(nxt[slot])
            req.out.append(tok)
            if req.keep_logits:
                req.logits.append(self._row(logits, slot))
            self.stats.tokens_out += 1
            self.pos[slot] += 1
            self._last_token[slot, 0] = tok
            if (tok == req.eos_id or len(req.out) >= req.max_new
                    or self.pos[slot] >= self.max_len - 1):
                finished.append(slot)
        return done + [self._retire(slot) for slot in finished]

    def drain(self) -> list[Request]:
        done: list[Request] = []
        while self.has_work():
            done.extend(self.step())
        return done

    # ------------------------------------------------------------ cancel
    def cancel(self, handle: RequestHandle) -> bool:
        req = handle.req
        if req.finished or req.cancelled:
            return False
        phase = None
        for i, (_, r) in enumerate(self.pending):
            if r is req:
                self.pending.pop(i)
                phase = "waiting"
                break
        if phase is None:
            for slot, r in list(self.active.items()):
                if r is req:
                    self.active.pop(slot)
                    self.lane.clear(slot)
                    phase = "decode"     # contiguous has no mid-prefill gap
                    break
        if phase is None:
            return False
        req.cancelled = True
        self.stats.cancelled += 1
        self.trace.emit("cancel", rid=req.rid, phase=phase, tick=self.now,
                        released_pages=0)
        return True

    # ---------------------------------------------------------- run shim
    def run(self, requests: list[Request],
            arrivals: list[float] | None = None) -> list[Request]:
        return run_requests(self, requests, arrivals)

    # -------------------------------------------------------------- report
    def report(self) -> dict:
        return {
            "engine": "contiguous",
            "served": self.stats.served,
            "cancelled": self.stats.cancelled,
            "decode_steps": self.stats.decode_steps,
            "tokens_out": self.stats.tokens_out,
            "mean_batch_occupancy": round(self.stats.mean_occupancy, 2),
            # worst per-program count: each fused variant (greedy /
            # sampled) should compile at most once; a genuine hot-loop
            # recompile shows up as > 1 on a single watcher
            "compiles": max(self._decode.compiles,
                            self._decode_sample.compiles),
        }


# ================================================================== paged


def _chunk_fn_for(model: Model, sampled: bool, attention: str | None,
                  pool_format=None):
    """One jitted chunk step per (Model instance, variant), shared by
    every engine built on it (benchmark sweeps construct many engines;
    recompiling per engine would dominate wall time).  Cached on the
    model itself so its lifetime — and the compiled executables' — ends
    with the model.  Variants on two axes: fused argmax for all-greedy
    batches (the sampling pipeline never lowers) vs fused sampling, and
    the paged step (KV through the page table, attended by the
    ``attention`` implementation, bound statically; its pool pinned to
    ``pool_format`` on the way in and out, one program per device) vs
    the dense-working-cache step (``attention=None``); jax.jit is lazy,
    so unused variants never compile."""
    steps = model.__dict__.setdefault("_serve_steps", {})
    key = (sampled, attention,
           None if pool_format is None else pool_format.sharding)
    fn = steps.get(key)
    if fn is None:
        if attention is None:
            target = (model.decode_sample_chunk if sampled
                      else model.decode_greedy_chunk)
            fn = jax.jit(target, donate_argnums=(1,))
        else:
            # named functions, so that the program and its operations
            # carry a stable name (``jit_paged_greedy_step``) in HLO
            # dumps and profiler traces
            if sampled:
                def paged_sample_step(*args):
                    return model.decode_paged_sample_chunk(
                        *args, attention=attention)
                target = paged_sample_step
            else:
                def paged_greedy_step(*args):
                    return model.decode_paged_greedy_chunk(
                        *args, attention=attention)
                target = paged_greedy_step
            pool = {"paged": {"k": pool_format, "v": pool_format}}
            # (params, pool, tokens, pos, n_new, page_table[, lanes])
            ins = (None, pool, None, None, None, None) + (
                (None,) if sampled else ())
            # (tokens, logits, pool[, the step's counts])
            outs = (None, None, pool) + (
                (None,) if model.cfg.experts_held else ())
            fn = jax.jit(target, donate_argnums=(1,), in_shardings=ins,
                         out_shardings=outs)
        steps[key] = fn
    return fn


def _device_of(params: Any):
    """The one device the params live on (None if spread over several):
    the paged engine keeps its page pool beside its weights."""
    devs = jax.tree.leaves(params)[0].devices()
    return next(iter(devs)) if len(devs) == 1 else None


@dataclass
class PagedStats:
    prefill_tokens: int = 0      # prompt tokens actually computed
    cached_tokens: int = 0       # prompt tokens served from the prefix cache
    admit_retries: int = 0       # admissions bounced by an intra-tick race
    # host swap tier accounting: every readmission of previously-computed
    # rows either restores them from the tier (swap-in) or re-prefills
    # them (recompute) — the restore rate is the tiering pathway's health
    # signal the audit layer gates on
    restored_tokens: int = 0     # KV rows swapped back in on readmission
    recompute_tokens: int = 0    # previously-computed rows re-prefilled
    swap_outs: int = 0           # preemptions that parked pages on host
    swap_ins: int = 0            # readmissions served from the host tier

    @property
    def prefix_hit_rate(self) -> float:
        total = self.prefill_tokens + self.cached_tokens
        return self.cached_tokens / total if total else 0.0

    @property
    def swap_restore_rate(self) -> float:
        total = self.restored_tokens + self.recompute_tokens
        return self.restored_tokens / total if total else 0.0


@dataclass
class _SwapRecord:
    """A preempted request's host-parked state: the KV rows it had
    written, page-granular, plus how many rows they cover.  ``host_ids``
    is empty when the tier was full or swap is disabled — the record
    still rides along so recompute on readmission is attributed."""
    consumed: int
    host_ids: list[int] = field(default_factory=list)


@dataclass
class _Slot:
    entry: SchedEntry
    req: Request
    feed: list[int]              # prompt (clamped) + generated-so-far
    hashes: list[int]            # chain hashes over full blocks of feed
    pending: list[int]           # feed tokens not yet computed
    consumed: int                # KV rows written (= next write position)
    shared: list[int]            # matched prefix pages (refs held)
    private: list[int]           # pages allocated for this request
    registered: int              # full feed blocks registered / matched
    reg_cursor: int = 0          # next private page usable for registration
    next_input: int = -1         # decode-phase input token
    table: list[int] = field(default_factory=list)  # logical block -> page
                                 # (kernel mode: shared then private, in
                                 # feed order; block i's KV lives wholly
                                 # in physical page table[i])


class PagedServeEngine:
    """Paged-KV continuous batching: prefix reuse + chunked prefill.

    Every step is one fixed-shape chunked call: prefill lanes feed up to
    ``chunk`` prompt tokens, decode lanes feed their last sampled token,
    idle lanes feed nothing (n_new=0).

    ``kernel`` selects the KV pathway:

    - ``"paged"`` (default, the production path): KV lives in a shared
      device page pool (``serve.paging.DevicePageView``) and the jitted
      step (``decode_paged_*_chunk``) writes and attends *through the
      page table* via the Pallas paged-attention kernel.  Prefix hits
      are pure metadata — the matched pages simply appear in the new
      slot's table row, zero copies — and registration publishes the
      page a block already lives in.
    - ``"gather"`` (the audited fallback): the dense per-slot working
      cache remains the jitted working set and admissions gather
      registered prefix KV from a host ``KVPool`` into slot rows — the
      contiguous-shaped detour the audit layer flags as
      ``pathway-kernel`` on dense/moe serving.

    Deterministic by construction: the scheduler runs on the engine's
    synthetic tick clock, so a trace (prompts, priorities, arrivals)
    replays to the same schedule and the same token streams — greedy and
    sampled alike, because sampled tokens key on (seed, rid, step), not
    on slots or schedule.

    ``admit_every`` batches scheduler invocations to every N-th tick
    (N=1, the default, schedules every tick).  Values > 1 model a
    misconfigured admission interval: output streams are unchanged but
    TTFT inflates — the audit's per-request latency expectations exist to
    catch exactly this class.
    """

    def __init__(self, model: Model, params: Any, *, slots: int = 4,
                 max_len: int = 256, block_size: int = 16,
                 num_blocks: int | None = None, chunk: int = 8,
                 tick_dt: float = 1.0, use_prefix_cache: bool = True,
                 admit_every: int = 1, kernel: str = "paged",
                 preemption: bool = True, swap: bool = True,
                 host_blocks: int | None = None,
                 swap_cost: SwapCostModel | None = None,
                 tracer: Tracer | None = None):
        if model.cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"paged engine needs an attention cache (dense/moe); "
                f"{model.cfg.family!r} serves through ServeEngine")
        if admit_every < 1:
            raise ValueError(f"admit_every must be >= 1, got {admit_every}")
        if kernel == "gather":
            refuse_paged_only(model.cfg, "the gather pathway")
        if kernel not in ("paged", "gather"):
            raise ValueError(
                f"kernel must be 'paged' (attend through the page table) "
                f"or 'gather' (dense working-cache fallback), got {kernel!r}")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.chunk = chunk
        self.kernel = kernel
        # which attention the jitted step lowers, decided once here and
        # bound statically into the step (reported, never a trace-time
        # branch): Pallas kernel on the TPU, page-table reference off it;
        # the gather pathway attends its dense working cache
        self.attention = (kops.paged_attention_impl() if kernel == "paged"
                          else "dense")
        if num_blocks is None:
            num_blocks = 2 * slots * pages_for(max_len, block_size)
        self.alloc = BlockAllocator(num_blocks, block_size)
        self.prefix = PrefixCache(self.alloc)
        self.prefix_enabled = use_prefix_cache
        # host swap tier: preempted requests park written pages here and
        # readmission swaps them back in instead of re-prefilling; cold
        # prefix pages evicted under pressure spill to the same tier.
        # swap=False models the misconfigured deployment (device-only
        # residency, always-recompute) the tiering audit exists to catch.
        self.swap_enabled = swap
        if host_blocks is None:
            host_blocks = 2 * num_blocks
        self.host = HostSwapPool(host_blocks, block_size)
        self.swap_cost = swap_cost or SwapCostModel()
        self._swap_records: dict[int, _SwapRecord] = {}   # entry.seq -> rec
        if kernel == "paged":
            # KV storage IS the device page pool; no host KVPool, no
            # per-slot working cache, no admission gather.  Geometry
            # comes from the declarative spec the jitted paged step is
            # written against, so the two cannot drift.
            spec = model.paged_cache_specs(num_blocks, block_size,
                                           self.attention)
            layers, _, n_kv, _, width = spec["paged"]["k"].shape
            self.pool = None
            self.view = DevicePageView(
                num_blocks, block_size, layers, n_kv,
                model.cfg.resolved_head_dim, spec["paged"]["k"].dtype,
                slots=slots, max_pages=pages_for(max_len, block_size),
                width=width, device=_device_of(params))
            self.cache = self.view.cache()
        else:
            # dense-cache geometry without materializing it twice
            k = model.abstract_cache(slots, max_len)["self"]["k"]
            layers, _, _, n_kv, hd = k.shape
            self.pool = KVPool(num_blocks, block_size, layers, n_kv, hd,
                               k.dtype)
            self.view = None
            self.cache = model.zero_cache(slots, max_len)
        if swap and use_prefix_cache and kernel == "paged":
            # cold-prefix spill rides the same host tier (kernel mode
            # only: gather-mode registered pages already live in the host
            # KVPool, spilling them would copy host to host)
            self.prefix.attach_spill(
                spill_out=self._spill_page, page_in=self._page_in,
                drop=self.host.decref, capacity=host_blocks)
        self.now = 0.0
        self.tick_dt = tick_dt
        self.admit_every = admit_every
        self._ticks = 0
        self.lane = LaneState(slots)
        # engine events carry ``tick`` (the synthetic clock) in their
        # payload rather than rebinding the caller-owned tracer's clock:
        # replayed traces (same prompts, priorities, arrivals) still
        # produce identical (kind, data) streams, and a tracer shared
        # with other emitters keeps its own timestamps
        self.trace = tracer or NULL_TRACER
        self.sched = Scheduler(slots=slots, clock=lambda: self.now,
                               tracer=self.trace, preemption=preemption)
        self.active: dict[int, _Slot] = {}
        self.stats = EngineStats()
        self.pstats = PagedStats()
        def _on_compile(fn, reason, sig):
            self.trace.emit("compile", fn=fn, reason=reason, signature=sig)

        paged = kernel == "paged"
        attention = self.attention if paged else None
        fmt = self.view.format if paged else None
        self._chunk_fn = CompileWatcher(
            _chunk_fn_for(model, False, attention, fmt),
            "decode_paged_chunk" if paged else "decode_chunk",
            on_compile=_on_compile)
        self._chunk_sample_fn = CompileWatcher(
            _chunk_fn_for(model, True, attention, fmt),
            "decode_paged_sample_chunk" if paged else "decode_sample_chunk",
            on_compile=_on_compile)
        self.trace.emit("engine-init", engine="paged",
                        family=model.cfg.family, arch=model.cfg.name,
                        slots=slots, max_len=max_len, block_size=block_size,
                        chunk=chunk, pages=num_blocks,
                        prefix_cache=use_prefix_cache,
                        admit_every=admit_every, kernel=kernel,
                        attention=self.attention,
                        preemption=preemption, swap=swap,
                        host_pages=host_blocks)

    # ------------------------------------------------------------ intake
    def submit(self, req: Request, *, arrival: float | None = None
               ) -> RequestHandle:
        # reject statically-unplaceable requests here, where only the bad
        # request fails — once queued, it would starve everything behind
        # it (strict head-of-line) without ever becoming admissible
        _validate(req)
        _validate_fit(req, self.max_len)
        worst = pages_for(len(self._feed_of(req)) + req.max_new,
                          self.alloc.block_size)
        if worst > self.alloc.num_blocks:
            raise ValueError(
                f"request {req.rid} needs {worst} pages even fully "
                f"recomputed; pool has {self.alloc.num_blocks}")
        arrival = self.now if arrival is None else arrival
        req.t_submit = req.t_submit or time.perf_counter()
        entry = self.sched.submit(req, priority=req.priority, arrival=arrival)
        self.trace.emit("submit", rid=req.rid, tick=self.now,
                        arrival=arrival, prompt_tokens=len(req.prompt),
                        max_new=req.max_new,
                        sampling=(req.sampling or GREEDY).describe())
        return RequestHandle(self, req, entry)

    def has_work(self) -> bool:
        return self.sched.has_work()

    def _free_slots(self) -> list[int]:
        return [s for s in range(self.slots) if s not in self.active]

    def _feed_of(self, req: Request) -> list[int]:
        prompt = req.prompt[-(self.max_len - req.max_new):]
        return list(prompt) + list(req.out)

    def _cost(self, entry: SchedEntry) -> int:
        """Net new pages if admitted now (prefix hits are shared, free).

        A preempted entry whose readmission the ``SwapCostModel`` prices
        cheaper as a swap-in costs its *full* page count — every page
        comes back as a private page, no prefix sharing — which keeps the
        scheduler's feasibility arithmetic exact for both pathways."""
        req = entry.req
        feed = self._feed_of(req)
        total = pages_for(len(feed) + req.max_new - len(req.out),
                          self.alloc.block_size)
        if self._restorable(entry) is not None:
            return total
        matched = (self.prefix.peek(feed, max_tokens=len(feed) - 1)
                   if self.prefix_enabled else 0)
        return total - matched // self.alloc.block_size

    # --------------------------------------------------------- host tier
    def _restorable(self, entry: SchedEntry) -> _SwapRecord | None:
        """The entry's swap record, iff restoring it beats recomputing."""
        rec = self._swap_records.get(entry.seq)
        if (rec is not None and rec.host_ids
                and self.swap_cost.prefer_swap(len(rec.host_ids),
                                               rec.consumed)):
            return rec
        return None

    def _spill_page(self, bid: int) -> int | None:
        """PrefixCache spill hook: copy one device page's rows to the
        host tier (kernel mode; returns None when the tier is full)."""
        hid = self.host.put(*self.view.read_page(bid))
        if hid is not None:
            self.trace.emit("swap-out", rid=None, tick=self.now,
                            reason="prefix-spill", pages=1,
                            tokens=self.alloc.block_size,
                            pages_in_use=self.alloc.in_use,
                            host_pages_in_use=self.host.in_use)
        return hid

    def _page_in(self, hid: int) -> int | None:
        """PrefixCache restore hook: allocate a device page and copy a
        spilled page's rows back (None when the device pool is empty —
        the match stops at the resident prefix)."""
        if self.alloc.num_free == 0:
            return None
        bid = self.alloc.alloc()
        self.view.write_page(bid, *self.host.get(hid))
        self.cache = self.view.cache()   # rebind: the writes made new arrays
        self.trace.emit("swap-in", rid=None, tick=self.now,
                        reason="prefix-restore", pages=1,
                        tokens=self.alloc.block_size,
                        pages_in_use=self.alloc.in_use,
                        host_pages_in_use=self.host.in_use)
        return bid

    def _drop_swap(self, entry: SchedEntry, *, swapped_in: bool = False
                   ) -> _SwapRecord | None:
        """Release an entry's host-parked pages (readmit or cancel)."""
        rec = self._swap_records.pop(entry.seq, None)
        if rec is not None:
            for hid in rec.host_ids:
                self.host.decref(hid, swapped_in=swapped_in)
        return rec

    # ------------------------------------------------------------- admit
    def _admit(self, entry: SchedEntry,
               victims: tuple[SchedEntry, ...] = ()) -> bool:
        """Place one candidate, preempting its planned ``victims`` only
        once admission is guaranteed.  The budget check happens *after*
        the prefix match — matched pages the plan counted as evictable
        are pinned by the match's references, so measuring free +
        evictable at that point (plus the pages each victim will release)
        is exact: a candidate that fails here fails before any running
        work is flushed."""
        req: Request = entry.req
        bs = self.alloc.block_size
        feed = self._feed_of(req)
        total = pages_for(len(feed) + req.max_new - len(req.out), bs)
        rec = self._restorable(entry)
        if rec is not None:
            return self._admit_restore(entry, feed, total, rec, victims)
        # leave ≥1 token to feed so the last-position logits exist
        if self.prefix_enabled:
            matched_len, shared = self.prefix.match(feed,
                                                    max_tokens=len(feed) - 1)
        else:
            matched_len, shared = 0, []
        need = total - len(shared)
        budget = (self.alloc.num_free + self.prefix.evictable()
                  + sum(v.held_pages for v in victims))
        if need > budget:
            for bid in shared:      # lost an intra-tick race; stay waiting
                self.alloc.decref(bid)
            self.pstats.admit_retries += 1
            return False
        for v in victims:           # guaranteed to buy the admission now
            self._preempt(v)
        if need > self.alloc.num_free:
            self.prefix.evict(need - self.alloc.num_free)
        if need > self.alloc.num_free:  # pragma: no cover - budget-guarded
            for bid in shared:
                self.alloc.decref(bid)
            self.pstats.admit_retries += 1
            return False
        private = [self.alloc.alloc() for _ in range(need)]
        slot = self._free_slots()[0]

        if self.kernel == "paged":
            # zero-copy prefix reuse: the matched pages (and the fresh
            # private ones) become this slot's page-table row; the kernel
            # attends the shared pages in place
            table = shared + private
            self.view.bind_slot(slot, table)
            self.pstats.cached_tokens += matched_len
        else:
            table = []
            if matched_len:         # prefix hit: pages -> slot rows, no math
                kp, vp = self.pool.read(shared)
                kc, vc = self.cache["self"]["k"], self.cache["self"]["v"]
                self.cache["self"]["k"] = kc.at[:, slot, :matched_len].set(
                    jnp.asarray(kp[:, :matched_len]))
                self.cache["self"]["v"] = vc.at[:, slot, :matched_len].set(
                    jnp.asarray(vp[:, :matched_len]))
                self.pstats.cached_tokens += matched_len

        self.active[slot] = _Slot(
            entry=entry, req=req, feed=feed,
            hashes=chain_hashes(feed, bs),
            pending=feed[matched_len:], consumed=matched_len,
            shared=shared, private=private, registered=matched_len // bs,
            table=table)
        self.sched.mark_running(entry, slot, len(private))
        dropped = self._drop_swap(entry)
        if dropped is not None:
            # a readmission the cost model (or a full/disabled tier) sent
            # down the recompute path: previously-computed rows beyond the
            # prefix hit are re-prefilled
            self.pstats.recompute_tokens += max(0,
                                                dropped.consumed - matched_len)
        # pages_in_use rides every occupancy-changing event so the live
        # metrics layer can histogram pool pressure straight off the
        # trace (deterministic: the allocator count is schedule state)
        self.trace.emit("admit", rid=req.rid, slot=slot, tick=self.now,
                        feed_tokens=len(feed), cached_tokens=matched_len,
                        new_pages=len(private), shared_pages=len(shared),
                        pages_in_use=self.alloc.in_use)
        return True

    def _admit_restore(self, entry: SchedEntry, feed: list[int],
                       total: int, rec: _SwapRecord,
                       victims: tuple[SchedEntry, ...] = ()) -> bool:
        """Swap-in readmission: every page comes back as a private page
        (no prefix match — the host copy is already exact), the parked
        rows are copied into the fresh pages, and the slot resumes at the
        preempted position.  Token-exact with the recompute pathway: the
        restored rows ARE the rows an uninterrupted run had written, and
        ``pending = feed[consumed:]`` resumes the same chunk arithmetic."""
        req: Request = entry.req
        bs = self.alloc.block_size
        need = total
        budget = (self.alloc.num_free + self.prefix.evictable()
                  + sum(v.held_pages for v in victims))
        if need > budget:
            # intra-tick race: stay waiting, the record stays parked
            self.pstats.admit_retries += 1
            return False
        for v in victims:
            self._preempt(v)
        if need > self.alloc.num_free:
            self.prefix.evict(need - self.alloc.num_free)
        if need > self.alloc.num_free:  # pragma: no cover - budget-guarded
            self.pstats.admit_retries += 1
            return False
        private = [self.alloc.alloc() for _ in range(need)]
        slot = self._free_slots()[0]
        n_pages = len(rec.host_ids)
        if self.kernel == "paged":
            for bid, hid in zip(private, rec.host_ids):
                self.view.write_page(bid, *self.host.get(hid))
            self.cache = self.view.cache()   # rebind the fresh arrays
            table = list(private)
            self.view.bind_slot(slot, table)
        else:
            table = []
            rows = min(n_pages * bs, self.max_len)
            kc, vc = self.cache["self"]["k"], self.cache["self"]["v"]
            # full-slab write keeps the shape fixed; rows >= consumed are
            # never read before the decode loop rewrites them, so zeros
            # beyond the restored rows are as good as the stale occupant
            k_slab = np.zeros((kc.shape[0],) + tuple(kc.shape[2:]),
                              dtype=kc.dtype)
            v_slab = np.zeros_like(k_slab)
            k_slab[:, :rows] = np.concatenate(
                [self.host.get(h)[0] for h in rec.host_ids],
                axis=1)[:, :rows]
            v_slab[:, :rows] = np.concatenate(
                [self.host.get(h)[1] for h in rec.host_ids],
                axis=1)[:, :rows]
            self.cache["self"]["k"] = _write_slot(kc, slot,
                                                  jnp.asarray(k_slab))
            self.cache["self"]["v"] = _write_slot(vc, slot,
                                                  jnp.asarray(v_slab))
        self.active[slot] = _Slot(
            entry=entry, req=req, feed=feed,
            hashes=chain_hashes(feed, bs),
            pending=feed[rec.consumed:], consumed=rec.consumed,
            shared=[], private=private, registered=0, table=table)
        self.sched.mark_running(entry, slot, len(private))
        self._drop_swap(entry, swapped_in=True)
        self.pstats.restored_tokens += rec.consumed
        self.pstats.swap_ins += 1
        self.trace.emit("swap-in", rid=req.rid, slot=slot, tick=self.now,
                        reason="readmit", pages=n_pages,
                        tokens=rec.consumed,
                        pages_in_use=self.alloc.in_use,
                        host_pages_in_use=self.host.in_use)
        self.trace.emit("admit", rid=req.rid, slot=slot, tick=self.now,
                        feed_tokens=len(feed), cached_tokens=0,
                        new_pages=len(private), shared_pages=0,
                        pages_in_use=self.alloc.in_use)
        return True

    def _register_blocks(self, slot: int, st: _Slot) -> None:
        """Publish newly completed full prompt blocks to the prefix cache.
        Kernel mode: the block's KV already lives in the physical page
        its table entry names — registration is pure metadata (first
        writer wins; the loser keeps its private page).  Gather mode:
        copy the slot's rows out to a private page in the host pool."""
        if not self.prefix_enabled:
            return
        bs = self.alloc.block_size
        while (st.registered < len(st.hashes)
               and (st.registered + 1) * bs <= st.consumed):
            h = st.hashes[st.registered]
            if self.kernel == "paged":
                if not self.prefix.contains(h):
                    # table entries at indices >= matched blocks are this
                    # slot's private pages: fully written, never written
                    # again (writes only target rows >= consumed)
                    self.prefix.insert(h, st.table[st.registered])
            elif (not self.prefix.contains(h)
                    and st.reg_cursor < len(st.private)):
                bid = st.private[st.reg_cursor]
                st.reg_cursor += 1
                a, b = st.registered * bs, (st.registered + 1) * bs
                self.pool.write(
                    bid,
                    np.asarray(self.cache["self"]["k"][:, slot, a:b]),
                    np.asarray(self.cache["self"]["v"][:, slot, a:b]))
                self.prefix.insert(h, bid)
            st.registered += 1

    # ------------------------------------------------------ release paths
    def _release(self, st: _Slot) -> None:
        for bid in st.shared:
            self.alloc.decref(bid)
        for bid in st.private:
            self.alloc.decref(bid)   # registered pages survive via cache ref

    def _swap_out(self, st: _Slot, slot: int) -> int:
        """Park the victim's written pages on the host tier.  Returns the
        page count parked (0: swap disabled, nothing written, or tier
        full — the record still rides along so the readmission's
        recompute is attributed).  Shared prefix pages are copied too:
        the record must survive the prefix cache evicting them."""
        rec = _SwapRecord(consumed=st.consumed)
        self._swap_records[st.entry.seq] = rec
        if not self.swap_enabled or st.consumed <= 0:
            return 0
        bs = self.alloc.block_size
        n_pages = pages_for(st.consumed, bs)
        if self.kernel == "paged":
            pages = [self.view.read_page(b) for b in st.table[:n_pages]]
            k_pages = np.stack([k for k, _ in pages], axis=1)
            v_pages = np.stack([v for _, v in pages], axis=1)
        else:
            rows = min(n_pages * bs, self.max_len)
            pad = ((0, 0), (0, n_pages * bs - rows), (0, 0), (0, 0))
            k_rows = np.pad(np.asarray(
                _read_slot(self.cache["self"]["k"], slot))[:, :rows], pad)
            v_rows = np.pad(np.asarray(
                _read_slot(self.cache["self"]["v"], slot))[:, :rows], pad)
            k_pages = k_rows.reshape(
                k_rows.shape[0], n_pages, bs, *k_rows.shape[2:])
            v_pages = v_rows.reshape(
                v_rows.shape[0], n_pages, bs, *v_rows.shape[2:])
        ids: list[int] = []
        for i in range(n_pages):
            hid = self.host.put(k_pages[:, i], v_pages[:, i])
            if hid is None:             # tier full: recompute on readmit
                for h in ids:
                    self.host.decref(h)
                return 0
            ids.append(hid)
        rec.host_ids = ids
        self.pstats.swap_outs += 1
        self.trace.emit("swap-out", rid=st.req.rid, slot=slot,
                        tick=self.now, reason="preempt", pages=n_pages,
                        tokens=st.consumed,
                        pages_in_use=self.alloc.in_use,
                        host_pages_in_use=self.host.in_use)
        return n_pages

    def _preempt(self, entry: SchedEntry) -> None:
        st = self.active.pop(entry.slot)
        self.lane.clear(entry.slot)
        self._swap_out(st, entry.slot)
        if self.view is not None:
            self.view.clear_slot(entry.slot)
        self._release(st)
        self.trace.emit("preempt", rid=st.req.rid, slot=entry.slot,
                        tick=self.now, consumed=st.consumed,
                        released_pages=len(st.shared) + len(st.private),
                        pages_in_use=self.alloc.in_use)
        self.sched.mark_preempted(entry)

    def _finish(self, slot: int) -> Request:
        st = self.active.pop(slot)
        self.lane.clear(slot)
        if self.view is not None:
            self.view.clear_slot(slot)
        st.req.finished = True
        self._release(st)
        self.trace.emit("finish", rid=st.req.rid, slot=slot, tick=self.now,
                        tokens_out=len(st.req.out),
                        pages_in_use=self.alloc.in_use)
        self.sched.mark_done(st.entry)
        self.stats.served += 1
        return st.req

    # ------------------------------------------------------------ cancel
    def cancel(self, handle: RequestHandle) -> bool:
        """Cancel at any lifecycle stage.  Mid-prefill / mid-decode the
        slot is freed and every page reference the request held (shared
        prefix refs + private pages) is released; blocks it registered in
        the prefix cache survive through the cache's own reference."""
        entry: SchedEntry = handle.entry
        req = handle.req
        if entry is None or entry.state == DONE or req.cancelled:
            return False
        if entry.state == RUNNING:
            st = self.active.pop(entry.slot)
            self.lane.clear(entry.slot)
            if self.view is not None:
                self.view.clear_slot(entry.slot)
            phase = "prefill" if st.pending else "decode"
            released = len(st.shared) + len(st.private)
            self._release(st)
            self.sched.mark_cancelled(entry)
        elif entry.state == PREEMPTED:
            # mid-lifecycle, not unstarted: the request had consumed
            # tokens before losing its slot, and may hold host-parked
            # pages that must be released with it
            phase, released = "preempted", 0
            self._drop_swap(entry)
            self.sched.mark_cancelled(entry)
        elif entry.state == WAITING:
            phase, released = "waiting", 0
            self.sched.mark_cancelled(entry)
        else:
            return False
        req.cancelled = True
        self.stats.cancelled += 1
        self.trace.emit("cancel", rid=req.rid, phase=phase, tick=self.now,
                        released_pages=released,
                        pages_in_use=self.alloc.in_use)
        return True

    # --------------------------------------------------------------- step
    def step(self) -> list[Request]:
        """One engine tick: scheduler plan (every ``admit_every``-th
        tick), then one fused chunked decode+sample call.

        The tick is the profiler step ``serve.step`` (``step_num`` = the
        tick count), cut into five contiguous phases: ``serve.schedule``
        (plan and admissions), ``serve.inputs`` (the step's host arrays
        and their device copies), ``serve.dispatch`` (the jitted call
        until it returns), ``serve.wait`` (the host blocked on the
        sampled tokens) and ``serve.commit`` (tokens appended, blocks
        registered, lanes finished)."""
        self.now += self.tick_dt
        self._ticks += 1
        tr = self.trace
        with tr.phase("serve.step", step_num=self._ticks):
            with tr.phase("serve.schedule"):
                if not self._schedule():
                    return []
            with tr.phase("serve.inputs"):
                fn, args, n_new, counters = self._inputs()
            with tr.phase("serve.dispatch"):
                sampled, logits, self.cache, *rest = fn(*args)
                counts = rest[0] if rest else {}
                if self.kernel == "paged":
                    self.view.adopt(self.cache)
                self.stats.decode_steps += 1
                self.stats.observe_occupancy(len(self.active))
                if counters is not None and not counts:
                    tr.emit("step", **counters)
            with tr.phase("serve.wait"):
                nxt = np.asarray(sampled)
                rows = (np.asarray(logits)
                        if any(st.req.keep_logits
                               for st in self.active.values())
                        else None)
                if counters is not None and counts:
                    # the program's own counts, read to the host only
                    # when traced, once the step has ended
                    tr.emit("step", **counters, **self._step_counts(counts))
            with tr.phase("serve.commit"):
                return self._commit(n_new, nxt, rows)

    def _schedule(self) -> bool:
        """The scheduler's plan and every admission it makes; False when
        no lane is left to run."""
        run_sched = (self._ticks - 1) % self.admit_every == 0
        admitted = 0
        if run_sched:
            plan = self.sched.schedule(
                free_slots=len(self._free_slots()),
                free_pages=self.alloc.num_free + self.prefix.evictable(),
                cost_fn=self._cost)
            # a candidate's victims are preempted only once its own
            # admission is guaranteed: _admit re-prices the candidate
            # against the pool as it stands NOW (earlier admissions this
            # tick consume free and evictable pages the plan's
            # bookkeeping could not see) and commits the preemptions only
            # after its exact budget check passes, so a failed admission
            # never flushes running work for nothing
            for entry in plan.admit:
                victims = tuple(plan.victims.get(entry.seq, ()))
                if not self._free_slots() and not victims:
                    break
                if not self._admit(entry, victims):
                    break   # intra-tick race: keep strict head-of-line order
                admitted += 1
        else:
            plan = Plan()
        if not self.active:
            if (run_sched and admitted == 0 and not plan.preempt
                    and self.sched.waiting
                    and all(e.arrival <= self.now
                            for e in self.sched.waiting)):
                raise RuntimeError(
                    "paged engine cannot place any waiting request: "
                    f"need more than {self.alloc.num_blocks} pages/"
                    f"{self.slots} slots")
            return False
        return True

    def _inputs(self) -> tuple[CompileWatcher, list, np.ndarray, dict | None]:
        """The jitted step to call and its arguments, its ``n_new`` host
        array, and the ``step`` event's payload (None when the tracer is
        off)."""
        toks = np.zeros((self.slots, self.chunk), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        n_new = np.zeros((self.slots,), np.int32)
        need_sample = any(_samples(st.req) for st in self.active.values())
        for slot, st in self.active.items():
            pos[slot] = st.consumed
            if need_sample:          # greedy program never reads the lanes
                self.lane.set(slot, st.req)
            if st.pending:
                n = min(self.chunk, len(st.pending))
                toks[slot, :n] = st.pending[:n]
                n_new[slot] = n
            else:
                toks[slot, 0] = st.next_input
                n_new[slot] = 1

        args = [self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray(n_new)]
        if self.kernel == "paged":
            args.append(jnp.asarray(host_snapshot(self.view.page_table)))
        if need_sample:
            args.append(self.lane.as_args())
        counters = None
        if self.trace.enabled:       # keep the untraced tick allocation-free
            bs = self.alloc.block_size
            # lane kind comes from pending state, not chunk size: a
            # 1-token final prefill chunk is still a prefill lane
            lanes = [(int(n_new[s]), bool(st.pending))
                     for s, st in self.active.items()]
            counters = dict(
                step_kind="chunk", tick=self.now, lanes=len(lanes),
                prefill_lanes=sum(1 for _, p in lanes if p),
                decode_lanes=sum(1 for _, p in lanes if not p),
                prefill_tokens=sum(n for n, p in lanes if p),
                chunk_sizes=tuple(n for n, _ in lanes),
                # what the program computes: (pos, n_new) of every running
                # lane as passed to it, and the rows of its fixed shape
                work=tuple((int(pos[s]), int(n_new[s])) for s in self.active),
                rows=self.slots * self.chunk,
                # pages the running lanes hold, and those their written
                # rows fill, before this step writes
                pages_bound=sum(len(st.shared) + len(st.private)
                                for st in self.active.values()),
                pages_written=sum(pages_for(st.consumed, bs)
                                  for st in self.active.values()))
            if self.model.cfg.sliding_window:
                counters["window_pages"] = self._window_pages(
                    counters["work"])
            if self.kernel == "paged":
                counters["attn_blocks"] = self._attn_blocks(counters["work"])
        fn = self._chunk_sample_fn if need_sample else self._chunk_fn
        return fn, args, n_new, counters

    def _window_pages(self, work) -> int:
        """Pages the kernel walks for the running lanes in the windowed
        layers, summed over those layers: a lane's walk runs from its
        window's first page to its last (``kernels.paged_attention``)."""
        bs = self.alloc.block_size
        n_pages = pages_for(self.max_len, bs)
        window = self.model.cfg.sliding_window
        per_layer = sum(
            min((p + max(n, 1) - 1) // bs, n_pages - 1)
            - max(p - window + 1, 0) // bs + 1 for p, n in work)
        layers = sum(1 for w in self.model.cfg.layer_windows() if w)
        return layers * per_layer

    def _attn_blocks(self, work) -> int:
        """Compute blocks the paged kernel walks for the running lanes,
        summed over the layers: each lane's walk in blocks of the
        kernel's pages, a windowed layer's from its window's first page
        (``kernels.paged_attention.walk_blocks``)."""
        cfg, pool = self.model.cfg, self.view.k
        kv = pool.shape[2]
        return kops.paged_attention_blocks(
            work, cfg.layer_windows(),
            q_shape=(self.slots, self.chunk, kv, cfg.n_heads // kv,
                     cfg.resolved_head_dim),
            q_dtype=cfg.dtype, pool_shape=pool.shape, pool_dtype=pool.dtype,
            n_pages=self.view.max_pages)

    @staticmethod
    def _step_counts(counts: dict) -> dict:
        """The ``step`` event's fields from the paged step's own counts:
        ``expert_rows`` (rows routed to held experts) and
        ``expert_rows_max`` (the most one held expert took), each summed
        over the expert layers."""
        out = {}
        if "experts" in counts:
            rows, most = np.asarray(counts["experts"]).tolist()
            out.update(expert_rows=rows, expert_rows_max=most)
        return out

    def _commit(self, n_new: np.ndarray, nxt: np.ndarray,
                rows: np.ndarray | None) -> list[Request]:
        """Each lane's sampled token appended, full blocks registered,
        finished lanes retired and their pages freed."""
        finished: list[int] = []
        for slot, st in self.active.items():
            req, n = st.req, int(n_new[slot])
            st.consumed += n
            if st.pending:
                st.pending = st.pending[n:]
                self.pstats.prefill_tokens += n
                self._register_blocks(slot, st)
                if st.pending:
                    continue        # mid-prefill: this lane's sample unused
                # prompt fully consumed this tick: the prefill→decode
                # phase boundary (re-fires after a preempt/readmit
                # recompute, unlike first-token)
                self.trace.emit("prefill-done", rid=req.rid, tick=self.now,
                                slot=slot, consumed=st.consumed)
            tok = int(nxt[slot])
            req.out.append(tok)
            if req.keep_logits:
                req.logits.append(rows[slot, :self.model.cfg.vocab_size])
            self.stats.tokens_out += 1
            if not req.t_first:
                req.t_first = time.perf_counter()
                self.trace.emit("first-token", rid=req.rid, tick=self.now,
                                ttft_ticks=self.now - st.entry.arrival)
            st.next_input = tok
            if (tok == req.eos_id or len(req.out) >= req.max_new
                    or st.consumed >= self.max_len - 1):
                finished.append(slot)
        return [self._finish(slot) for slot in finished]

    def drain(self) -> list[Request]:
        done: list[Request] = []
        while self.has_work():
            done.extend(self.step())
        return done

    # ---------------------------------------------------------- run shim
    def run(self, requests: list[Request],
            arrivals: list[float] | None = None) -> list[Request]:
        return run_requests(self, requests, arrivals)

    # -------------------------------------------------------------- report
    def report(self) -> dict:
        return {
            "engine": "paged",
            "served": self.stats.served,
            "cancelled": self.stats.cancelled,
            "decode_steps": self.stats.decode_steps,
            "tokens_out": self.stats.tokens_out,
            "mean_batch_occupancy": round(self.stats.mean_occupancy, 2),
            "prefill_tokens": self.pstats.prefill_tokens,
            "cached_tokens": self.pstats.cached_tokens,
            "prefix_hit_rate": round(self.pstats.prefix_hit_rate, 3),
            # prefix-cache internals: the cluster router's summary feed
            # (serve.cluster refreshes per-replica summaries when the
            # resident chain count moves) and the operator's view of
            # cache health without replaying traces
            "prefix_lookups": self.prefix.stats.lookups,
            "prefix_hit_blocks": self.prefix.stats.hit_blocks,
            "prefix_miss_blocks": self.prefix.stats.miss_blocks,
            "prefix_insertions": self.prefix.stats.insertions,
            "prefix_evictions": self.prefix.stats.evictions,
            "prefix_chains": len(self.prefix),
            "page_peak_utilization": round(
                self.alloc.stats.peak_in_use / self.alloc.num_blocks, 3),
            "pages": self.alloc.num_blocks,
            "block_size": self.alloc.block_size,
            "chunk": self.chunk,
            "prefix_cache": self.prefix_enabled,
            "admit_every": self.admit_every,
            "kernel": self.kernel,
            "attention": self.attention,
            "preemption": self.sched.preemption,
            "preemptions": self.sched.stats.preemptions,
            # host swap tier: the tiering pathway's health signals (the
            # audit layer's pathway-tiering expectations read these)
            "swap": self.swap_enabled,
            "swap_outs": self.pstats.swap_outs,
            "swap_ins": self.pstats.swap_ins,
            "restored_tokens": self.pstats.restored_tokens,
            "recompute_tokens": self.pstats.recompute_tokens,
            "recompute_tokens_saved": self.pstats.restored_tokens,
            "swap_restore_rate": round(self.pstats.swap_restore_rate, 3),
            "prefix_spills": self.prefix.stats.spills,
            "prefix_restores": self.prefix.stats.restores,
            "host_pages": self.host.capacity,
            "host_pages_in_use": self.host.in_use,
            "host_page_peak": self.host.stats.peak_in_use,
            # worst per-program count (greedy / sampled variants each
            # bound at one compile; see ServeEngine.report)
            "compiles": max(self._chunk_fn.compiles,
                            self._chunk_sample_fn.compiles),
        }


# ================================================================= oracle


def token_matrix(done: list[Request], n_requests: int,
                 max_new: int) -> np.ndarray:
    """Output streams as a dense int matrix (pad = -1), rid-ordered so
    completion order does not affect the comparison."""
    out = np.full((n_requests, max_new), -1, np.int64)
    for r in done:
        out[r.rid, :len(r.out)] = r.out
    return out


def compare_engines(model: Model, params: Any,
                    make_requests: Callable[[], list[Request]], *,
                    slots: int = 2, max_len: int = 64, block_size: int = 8,
                    chunk: int = 4, repeats: int = 1,
                    sampling: SamplingParams | None = None,
                    engine_kwargs: dict[str, dict] | None = None,
                    cluster: dict | None = None,
                    logits_tol: float | None = None):
    """The paged engine's correctness proof, in the paper's methodology:
    the same workload under two environments (contiguous oracle vs paged)
    must agree token-for-token.  With ``sampling`` given, both engines
    decode the workload under those SamplingParams — counter-based keys
    make sampled streams engine-independent, so the verdict is the same
    bit-identity as greedy.

    ``engine_kwargs`` pins per-engine construction explicitly instead of
    relying on defaults/globals: ``{"contiguous": {...}, "paged": {...}}``
    — e.g. ``{"paged": {"kernel": "gather"}}`` holds the oracle verdict
    over the dense-fallback pathway while ``{"paged": {"kernel":
    "paged"}}`` pins the Pallas page-table kernel on.

    With ``cluster`` given (a dict of ``ClusterEngine`` kwargs, e.g.
    ``{"replicas": 3, "routing": "random"}``), the comparison becomes
    single paged engine vs a ``ClusterEngine`` over the same geometry:
    routing moves requests between replicas but counter-based sampling
    keys on ``(seed, rid, step)``, so a cluster of any size — under ANY
    routing policy — must reproduce the single engine's streams exactly.
    This is the routing-correctness oracle: a router that corrupted,
    duplicated, or dropped a request would break bit-identity here.

    ``logits_tol`` judges the served logits instead of token identity
    (``core.verify.logits_verdict``): the verdict where the two
    pathways may round differently (the contiguous engine's one-token
    steps and the paged engine's chunks accumulate in different orders;
    a cluster's batches differ from one engine's), so that a greedy
    near-tie that parts the streams is not read as a fault.

    Returns a core.verify.DualEnvReport whose verdicts CI gates on."""
    from repro.core.verify import DualEnvHarness, logits_verdict

    ek = engine_kwargs or {}
    contig_kw = dict(ek.get("contiguous", {}))
    paged_kw = dict(ek.get("paged", {}))
    kept: dict[str, list[Request]] = {}

    def requests() -> list[Request]:
        reqs = make_requests()
        for r in reqs:
            if sampling is not None:
                r.sampling = sampling
            r.keep_logits = logits_tol is not None
        return reqs

    probe = requests()
    n, max_new = len(probe), max(r.max_new for r in probe)

    def result(name: str, done: list[Request]):
        if logits_tol is None:
            return token_matrix(done, n, max_new)
        kept[name] = done
        return None

    def run_contiguous():
        eng = ServeEngine(model, params, slots=slots, max_len=max_len,
                          **contig_kw)
        return result("contiguous", eng.run(requests()))

    def run_paged():
        eng = PagedServeEngine(model, params, slots=slots, max_len=max_len,
                               block_size=block_size, chunk=chunk,
                               **paged_kw)
        return result("paged", eng.run(requests()))

    harness = DualEnvHarness(repeats=repeats, warmup=0)
    if cluster is not None:
        # routing oracle: single paged engine vs the cluster router
        from repro.serve.cluster import ClusterEngine  # local: avoid cycle

        cluster_kw = dict(cluster)

        def run_cluster():
            eng = ClusterEngine(model, params, slots=slots, max_len=max_len,
                                block_size=block_size, chunk=chunk,
                                **cluster_kw)
            return result("cluster", eng.run(requests()))

        names = ("paged", run_paged, "cluster", run_cluster)
    else:
        names = ("contiguous", run_contiguous, "paged", run_paged)
    report = harness.compare(*names, rtol=1e-9, atol=0.5)
    if logits_tol is not None:
        a, b = ({r.rid: (r.out, r.logits) for r in kept[name]}
                for name in names[::2])
        report.verdicts.append(logits_verdict(
            a, b, tol=logits_tol,
            greedy=sampling is None or sampling.greedy))
    return report
