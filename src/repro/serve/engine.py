"""Serving: sharded prefill/decode steps and a continuous-batching engine.

The decode step runs against donated device state (in-place HBM update —
the IMC-style "computation mode" on resident state). Completion of a
request is signaled through the XAIF interrupt analogue
(:class:`repro.core.xaif.InterruptController`), mirroring the paper's
accelerator end-of-computation interrupt, and the finished slot's
memory-bank power domains are clock-gated through the platform
:class:`~repro.core.power.PowerManager`.

Two layers live here:

* :func:`build_sharded_serve` — jit + shardings for pod-scale prefill/decode
  (used by the dry-run and the launch drivers, unchanged API).
* :class:`ContinuousBatchingEngine` — a request-level serving loop: FIFO
  admission queue with backpressure, slot-based batching where new requests
  are prefilled into free decode slots *without stopping in-flight decodes*
  (prefill is chunk-granular), and preemption-safe replay through
  :class:`repro.runtime.ft.RequestJournal`.

Two device backends serve the slots:

* **paged** (default for transformer-family configs, including
  sliding-window ones) — one global KV page pool plus per-slot block
  tables (:mod:`repro.serve.paged`), decoded by the fused paged-attention
  kernel (:mod:`repro.kernels.paged_attention`). Prefix sharing is
  block-table pointing: adopting a resident chain pins page ids (no
  copy-on-write lane materialisation), publishing a completed page is a
  refcount bump (no device gather), and two cold same-prefix prefills
  dedup — the later one stalls on the earlier one's claim, then adopts
  its published pages (mid-flight re-match). Sliding-window configs run
  the same path with **ring block tables**: a slot's table holds at most
  ``ceil(window/page_size) + 1`` entries; when the oldest page falls
  wholly outside the window its table entry is reused — a private page
  goes back to the pool's free list, an adopted shared-prefix page is
  *disowned* (pool ref + table pin released; the table's own residency
  keeps it warm for future admissions) — so a long-running windowed
  request holds O(window) device pages instead of O(seq), and prefix
  adoption is clamped to the pages the window can still see. A Mamba-2
  hybrid takes the same path with a KV arena over its attention layers
  and a per-slot **state arena** beside it (conv tails and recurrent
  state), zeroed for a slot at each admission; its prefixes are never
  shared, since KV pages say nothing of the state at their positions.
* **lanes** (Mamba-2/Griffin/MoE configs, and engines sharing an external
  page table *without* a shared pool) — the PR 2 layout: one full-length
  cache lane per slot (``vmap`` over batch-1 decode), snapshot pages,
  copy-on-write at the slot's first step. Pass ``paged=False`` to force
  it (e.g. as the bit-identity baseline for windowed paged serving).

Since PR 4 the engine no longer has to own its allocation: pass ``pool``
(a cluster-owned :class:`~repro.serve.paged.PagePool`) plus a shared
``page_table`` and the engine becomes one tenant of a multi-model
:class:`~repro.serve.cluster.ServeCluster` — page-table payloads are then
globally valid pool ids, so a shared table no longer forces the lane
backend. ``namespace`` keys the engine's prefix pages (same model + same
weights = same namespace = cross-engine prefix aliasing; different models
stay isolated), ``admission_hook`` lets a scheduler veto each admission
(weighted round-robin grants, power-budget backpressure), and ``reclaim``
replaces the engine's own ``pages.clear()`` under pool pressure with the
cluster's fair cross-tenant eviction.

Dispatch is optionally **async double-buffered** (``async_dispatch=True``):
step N+1 launches before step N's next-token vector is transferred —
decoding lanes take their input token straight from the previous step's
on-device output (the ``feedback`` path), and host bookkeeping for step N
(token journaling, completion interrupts) retires while the device chews
on step N+1. The on-device output is *sampled* per the request's
:class:`~repro.serve.sampling.SamplingParams` (exact argmax at zero
temperature — greedy is the default), with per-lane PRNG keys advancing
on-device in the same launch, so the overlap is invisible in the outputs
for stochastic and greedy decode alike: tokens are bit-identical with
async on or off.

Engine invariants (the test suite holds the engine to these):

* **FIFO admission** — requests are admitted to slots, and complete among
  equal-length requests, strictly in arrival order; preemption re-queues
  in-flight work at the front in the same order.
* **Refcounts never negative** — every ``bank_acquire``/page retain is
  released exactly once (on completion, eviction, or preemption);
  over-release raises instead of corrupting shared state.
* **Replay determinism** — decode is deterministic even when stochastic:
  greedy lanes replay by argmax, sampled lanes re-seed their journaled
  per-request PRNG chain at re-admission and advance it only on emitting
  steps (chain position == produced-token count), so replay after
  ``preempt()`` reproduces every request's tokens bit-for-bit, with or
  without prefix sharing, chunked prefill, paged decode, and async
  dispatch; the journal cross-checks each replayed token and fails loudly
  on divergence.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models import registry
from repro.models.config import ModelConfig
from repro.runtime.ft import RequestJournal
from repro.serve import tracing
from repro.serve.paged import (PagePool, mesh_tp, paged_chunk_fn,
                               paged_step_fn)
from repro.serve.pages import PageTable
from repro.serve.sampling import (GREEDY, SamplingParams, sample, seed_key,
                                  zero_keys)
from repro.sharding import axes as lx_
from repro.sharding import params as P
from repro.sharding import rules as R


@dataclasses.dataclass
class ShardedServe:
    prefill_fn: Any
    decode_fn: Any
    params_abstract: Any
    params_shardings: Any
    cache_abstract: Any
    cache_shardings: Any
    token_sharding: Any
    logit_sharding: Any
    raw_decode_fn: Any = None
    raw_prefill_fn: Any = None


def build_sharded_serve(cfg: ModelConfig, mesh: Mesh, rules: R.Rules,
                        batch: int, max_len: int,
                        prefill_len: int | None = None,
                        fsdp: bool | None = None) -> ShardedServe:
    """jit + shardings for pod-scale prefill/decode of one model config
    (used by the dry-run and launch drivers; API unchanged since PR 0)."""
    from repro.train.trainer import _fsdp_auto

    decls = registry.decls(cfg)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    p_abs = jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, dtype),
                         P.abstract_tree(decls))
    p_axes = P.axes_tree(decls)
    if fsdp is None:
        fsdp = _fsdp_auto(cfg, mesh)
    param_rules = rules if fsdp else rules.override(
        name=rules.name + "+replicated-weights", **{lx_.EMBED: ()})
    p_shard = R.tree_shardings(p_abs, p_axes, param_rules, mesh)

    c_abs = registry.cache_abstract(cfg, batch, max_len)
    c_axes = registry.cache_axes(cfg)
    c_shard = R.tree_shardings(c_abs, c_axes, rules, mesh)

    tok_shard = NamedSharding(mesh, R.spec_for((batch, 1), (lx_.DECODE_BATCH, None),
                                               rules, mesh))
    logit_shard = NamedSharding(
        mesh, R.spec_for((batch, cfg.vocab), (lx_.DECODE_BATCH, lx_.VOCAB),
                         rules, mesh))

    def decode(params, cache, tokens):
        return registry.decode_step(params, cfg, cache, tokens)

    decode_fn = jax.jit(decode,
                        in_shardings=(p_shard, c_shard, tok_shard),
                        out_shardings=(logit_shard, c_shard),
                        donate_argnums=(1,))

    prefill_fn = None
    if prefill_len:
        if cfg.embed_inputs:
            in_abs = jax.ShapeDtypeStruct((batch, prefill_len), jnp.int32)
            in_shard = NamedSharding(
                mesh, R.spec_for(in_abs.shape, (lx_.DECODE_BATCH, lx_.SEQ),
                                 rules, mesh))

            def pf(params, tokens):
                return registry.prefill(params, cfg, tokens=tokens, max_len=max_len)
        else:
            in_abs = jax.ShapeDtypeStruct((batch, prefill_len, cfg.d_model),
                                          jnp.bfloat16)
            in_shard = NamedSharding(
                mesh, R.spec_for(in_abs.shape,
                                 (lx_.DECODE_BATCH, lx_.SEQ, lx_.EMBED),
                                 rules, mesh))

            def pf(params, embeds):
                return registry.prefill(params, cfg, embeds=embeds, max_len=max_len)

        prefill_fn = jax.jit(pf, in_shardings=(p_shard, in_shard),
                             out_shardings=(logit_shard, c_shard))
        prefill_fn._input_abstract = in_abs  # used by the dry-run

    return ShardedServe(prefill_fn, decode_fn, p_abs, p_shard, c_abs, c_shard,
                        tok_shard, logit_shard,
                        raw_decode_fn=decode,
                        raw_prefill_fn=pf if prefill_len else None)


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

COMPLETE_LINE = "serve.complete"     # interrupt line raised per finished request
ADMIT_LINE = "serve.admit"           # raised per slot admission


# Jitted per-slot kernels are shared across engine instances: one step
# function per model config (jax then caches compilations by slot count /
# cache shapes), one reset function globally.
_STEP_FNS: dict = {}
_CHUNK_FNS: dict = {}
_RESET_FN = None
_STATE_RESET_FN = None


def _slot_step_fn(cfg: ModelConfig):
    # ModelConfig is a frozen (hashable) dataclass; an unhashable config
    # must fail loudly here rather than risk a wrong-model cache collision
    if cfg not in _STEP_FNS:
        def one(params, cache, tok, fb, prev, emit, key, temp, tk, tp):
            tok = jnp.where(fb, jnp.full_like(tok, prev), tok)
            logits, cache = registry.decode_step(params, cfg, cache, tok)
            parts = jax.random.split(key)      # [0] carry, [1] use — the
            # same convention as sampling.split_keys, so lane and paged
            # backends walk bit-identical per-request sampling chains
            out = sample(logits[0], parts[1], temp, tk, tp)
            key = jnp.where(emit, parts[0], key)
            return out, cache, key

        vstep = jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0))
        _STEP_FNS[cfg] = jax.jit(vstep, donate_argnums=(1, 6))
    return _STEP_FNS[cfg]


def _chunk_step_fn(cfg: ModelConfig, chunk: int):
    """Per-slot step feeding up to ``chunk`` tokens in one launch.

    Each lane scans over its token buffer; iterations past the lane's
    ``count`` are masked out (the cache carry keeps the old values bitwise,
    so a decode lane with ``count == 1`` is untouched by the padding). The
    returned token is sampled (exact argmax at zero temperature) after the
    lane's last *fed* token — for a lane that just consumed its final
    prompt token, that is its first generated token. The lane's PRNG key
    splits once per launch (every scan iteration draws with the same
    per-launch subkey; only the last fed iteration's token survives, so
    the result is bit-identical to the unchunked path) and the split is
    kept only where ``emit`` is set.
    """
    key = (cfg, chunk)
    if key not in _CHUNK_FNS:
        def one(params, cache, toks, count, fb, prev, emit, rkey, temp,
                tk, tp):
            parts = jax.random.split(rkey)     # [0] carry, [1] use

            def body(cache, xs):
                j, tok = xs
                tok = jnp.where((j == 0) & fb, jnp.full_like(tok, prev), tok)
                logits, new_cache = registry.decode_step(params, cfg, cache, tok)
                out = sample(logits[0], parts[1], temp, tk, tp)
                keep = j < count
                cache = jax.tree.map(
                    lambda n, o: jnp.where(keep, n, o), new_cache, cache)
                return cache, out

            cache, outs = jax.lax.scan(
                body, cache, (jnp.arange(chunk, dtype=jnp.int32), toks))
            last = jax.lax.dynamic_index_in_dim(
                outs, jnp.maximum(count - 1, 0), 0, keepdims=False)
            rkey = jnp.where(emit, parts[0], rkey)
            return last, cache, rkey

        vstep = jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))
        _CHUNK_FNS[key] = jax.jit(vstep, donate_argnums=(1, 7))
    return _CHUNK_FNS[key]


def _slot_reset_fn():
    global _RESET_FN
    if _RESET_FN is None:
        def reset(cache, slot, template):
            # reset one page to the cache family's true initial values (the
            # template), not to zeros — a future family may init non-zero
            return jax.tree.map(
                lambda leaf, init: leaf.at[slot].set(init), cache, template)

        _RESET_FN = jax.jit(reset, donate_argnums=(0,))
    return _RESET_FN


def _state_reset_fn():
    """Jitted zeroing of one slot's rows of the paged state arena, in
    place (the arena is donated; the slot index is traced)."""
    global _STATE_RESET_FN
    if _STATE_RESET_FN is None:
        _STATE_RESET_FN = jax.jit(registry.state_reset, donate_argnums=(0,))
    return _STATE_RESET_FN


# Admission-hook verdict telling the engine to drop the queue head
# entirely (latency-SLO admission control: the request can no longer meet
# its SLO, so serving it would waste capacity). Distinct from False (skip
# this slot) and None (stop the admission scan) — see ``_place``.
SHED = object()


@dataclasses.dataclass
class Request:
    """One generation request. ``tokens`` is filled in by the engine.

    ``slo`` (optional) is a latency target the scheduler and the metrics
    layer read (see :class:`repro.serve.metrics.SLO`); the engine itself
    never interprets it. ``sampling`` (optional) selects stochastic
    decoding (:class:`~repro.serve.sampling.SamplingParams`); ``None``
    means greedy — and rides through preemption/requeue untouched, so a
    replayed admission re-seeds the identical sampling chain.
    ``first_token_time`` stamps the retire of the request's first
    generated token (TTFT = that minus ``arrival_time``);
    ``slo_preempts`` counts scheduler-driven preempt-and-requeue demotions
    (see :meth:`ContinuousBatchingEngine.preempt_slot`).
    ``energy_uj`` accumulates the joules a metered engine attributes to
    this request (prefill + decode + page holding + retention — replay
    energy after a preemption or fault is charged on top, like latency);
    ``energy_cap_uj_per_token`` is a tenant cap the energy-aware admission
    policy compares against the engine's projected marginal cost.
    """

    id: str
    prompt: Sequence[int]
    max_new_tokens: int
    on_complete: Callable[["Request"], None] | None = None
    slo: Any = None
    sampling: SamplingParams | None = None
    # engine-written bookkeeping
    tokens: list = dataclasses.field(default_factory=list)
    arrival_time: float | None = None
    admit_time: float | None = None
    first_token_time: float | None = None
    finish_time: float | None = None
    slo_preempts: int = 0
    energy_uj: float = 0.0
    energy_cap_uj_per_token: float | None = None

    def __post_init__(self):
        self.prompt = tuple(int(t) for t in self.prompt)
        if not self.prompt:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


@dataclasses.dataclass
class _Slot:
    """Host-side state of one decode slot (device state lives in the cache
    lane or, for the paged backend, in the slot's block-table pages)."""

    request: Request
    seq: int                 # FIFO sequence number of the request
    fed: int = 0             # tokens already consumed (prompt, then generated)
    produced: int = 0        # generated tokens so far
    next_token: int = 0      # token to feed at the next engine step
    page_keys: tuple = ()    # pinned shared-prefix pages (released on evict)
    pending_snapshot: Any = None   # lane backend: shared state to CoW at 1st step
    # paged backend: block index -> pool page id. Table entry is
    # ``block % table_width``; for windowed configs the table is a ring, so
    # the dict holds at most ``ceil(window/page_size) + 1`` live blocks
    pages_by_block: dict = dataclasses.field(default_factory=dict)
    blocks_covered: int = 0  # blocks allocated/adopted so far (next to cover)
    claims: list = dataclasses.field(default_factory=list)  # dedup claims held

    @property
    def prefilling(self) -> bool:
        return self.fed < len(self.request.prompt)


@dataclasses.dataclass
class _StepMeta:
    """Host bookkeeping deferred to a step's retire (async dispatch)."""

    emitted: list            # (lane, slot): token value lands at retire
    finished: list           # slots completing in this step, lane order


class ContinuousBatchingEngine:
    """Slot-based continuous batching over paged or per-lane device caches.

    Each of the ``slots`` decode lanes holds one request. One :meth:`step`
    advances every occupied lane: lanes still consuming their prompt are
    teacher-forced (up to ``prefill_chunk`` tokens), lanes past it decode
    under their request's sampling params (greedy by default). New
    requests are admitted into free lanes between steps;
    in-flight lanes never stop. See the module docstring for the paged vs
    lane backends and async double-buffered dispatch.

    The engine is deliberately clock-agnostic: pass ``clock`` (any
    ``() -> float``) and drive :meth:`step` from a scheduler or from the
    deterministic simulation harness in :mod:`repro.serve.sim`.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int, max_len: int,
                 platform=None, queue_capacity: int | None = None,
                 clock: Callable[[], float] = lambda: 0.0,
                 journal: RequestJournal | None = None,
                 pad_token: int = 0, prefill_chunk: int = 1,
                 page_size: int | None = None,
                 page_table: PageTable | None = None,
                 page_capacity: int | None = None,
                 paged: bool | None = None,
                 async_dispatch: bool = False,
                 lane_batch: int | None = None,
                 device_len: int | None = None,
                 pool: PagePool | None = None,
                 namespace: str = "",
                 name: str | None = None,
                 admission_hook=None,
                 reclaim=None,
                 chaos=None,
                 journal_horizon: int | None = None,
                 mesh: Mesh | None = None,
                 tp_axis: str = "model",
                 metered: bool = True,
                 operating_point: str = "max",
                 gate_idle_banks: bool = True):
        from repro.core.platform import Platform, XHeepConfig

        if slots < 1:
            raise ValueError("engine needs at least one decode slot")
        if max_len < 2:
            raise ValueError("max_len must fit a prompt token plus one output")
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.cfg = cfg
        # tensor parallelism: a mesh pins this engine's decode to a device
        # slice — params land head-sharded (wq/wk/wv) through the pool's
        # placement cache, the pool arena shards its KV-head axis, and the
        # jitted step runs under shard_map. All host bookkeeping (slots,
        # block tables, journal, sampling chains) is mesh-invariant, so TP
        # changes where bytes live, never which tokens come out.
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp = mesh_tp(mesh, tp_axis) if mesh is not None else 1
        if mesh is not None:
            R.validate_serve_tp(cfg, self.tp)
        self.params = params
        self.n_slots = slots
        self.max_len = max_len
        owns_platform = platform is None
        self.platform = platform or Platform(XHeepConfig())
        self.queue_capacity = queue_capacity
        self.clock = clock
        self.journal = journal or RequestJournal(horizon=journal_horizon)
        # fault-injection plan (repro.serve.chaos.FaultPlan or None):
        # consulted at the top of every device launch (may raise a
        # retryable DeviceStepFault) and on every retired token (may
        # corrupt the host-transferred value)
        self.chaos = chaos
        self.pad_token = pad_token
        self.prefill_chunk = prefill_chunk
        self.async_dispatch = async_dispatch
        self.namespace = namespace
        self.name = name if name is not None else (namespace or "engine")
        # scheduler callbacks (set by a ServeCluster): ``admission_hook``
        # vetoes each admission, ``reclaim`` replaces pages.clear() under
        # pool pressure with a cross-tenant policy
        self._admission_hook = admission_hook
        self._reclaim = reclaim
        # device-shape canonicalisation: lanes/cache positions may be padded
        # beyond the scheduling shape so engines of different sizes share one
        # compiled step (extra lanes ride idle; extra positions are masked)
        self.n_lanes = max(slots, lane_batch or 0)
        self.device_len = max(max_len, device_len or 0)

        # backend: a global page pool needs family support; an external
        # shared table is paged territory only when its payloads are
        # globally valid pool ids, i.e. the pool is shared (cluster-owned)
        # too — otherwise the table holds other engines' snapshots and the
        # lane backend takes over. Sliding-window configs page like any
        # other transformer config (ring block tables); only MoE routing
        # still forces lanes.
        if pool is not None and not registry.supports_paged(cfg):
            raise ValueError(
                f"{cfg.name} ({cfg.family}) cannot join a shared page pool: "
                "no paged KV decode for this family")
        can_page = registry.supports_paged(cfg) and (
            page_table is None or pool is not None)
        if paged is None:
            paged = can_page
        elif paged and not can_page:
            raise ValueError(
                "paged backend needs a transformer-family KV config (MoE "
                "still routes across lanes) and either an engine-private "
                "page table or a shared (cluster-owned) pool")
        if pool is not None and not paged:
            raise ValueError("a shared pool is a paged-backend resource; "
                             "drop it or drop paged=False")
        if mesh is not None and not paged:
            raise ValueError(
                "tensor parallelism is a paged-backend feature: the lane "
                "backend has no sharded arena to decode against")
        self.paged = paged
        # per-slot recurrent state on the paged backend (a Mamba-2
        # hybrid): a shared prefix's KV pages say nothing of the state at
        # their positions, so such an engine keeps no prefix table; and
        # prompts are fed one token a step
        stateful = paged and registry.holds_state(cfg)
        if stateful and prefill_chunk > 1:
            raise ValueError(
                f"{cfg.name} holds recurrent state: prefill_chunk > 1 has no "
                "paged path for it (prompts are fed one token a step)")
        if stateful and page_table is not None:
            raise ValueError(
                f"{cfg.name} holds recurrent state: its slots cannot adopt "
                "prefixes from a page table")

        # pass `page_table` to share one prefix store across engines (same
        # cfg/max_len — plus a shared `pool` to stay on the paged backend),
        # or just `page_size` for an engine-private table. The private
        # table is always bounded; build a PageTable(capacity_pages=None)
        # yourself if you really want unbounded residency.
        self._ps = (pool.page_size if pool is not None else page_size) or 16
        np_max = -(-self.device_len // self._ps)
        # sliding-window configs: the device ring modulus is the lane
        # cache length (min(window, device_len) — bit-identity with the
        # lane backend), and a slot's block table is a ring of
        # ceil(window/page_size)+1 entries: by the time an entry is
        # reused, its old block's positions fall wholly outside the window
        if cfg.sliding_window:
            self._window: int | None = min(cfg.sliding_window,
                                           self.device_len)
            self._np_slot = min(np_max, -(-self._window // self._ps) + 1)
        else:
            self._window = None
            self._np_slot = np_max
        cap = 0
        self.owns_pool = pool is None
        self._pool: PagePool | None = pool
        self._arena = None
        if self.paged:
            if self._pool is None:
                if page_size and not stateful:
                    cap = (page_capacity if page_capacity is not None
                           else 16 * slots)
                # a windowed engine provisions O(window) pages per slot,
                # not O(device_len) — the ring bound is the pool budget
                self._pool = PagePool(slots * self._np_slot + cap, self._ps)
            self._arena = self._pool.arena(cfg, mesh=mesh, tp_axis=tp_axis)
            if mesh is not None:
                self.params = self._pool.placed_params(cfg, params, mesh,
                                                       tp_axis)
        self._state = None
        if stateful:
            self._state = registry.state_init(cfg, self.n_lanes)
            if mesh is not None:
                self._state = jax.device_put(
                    self._state, NamedSharding(mesh, PartitionSpec()))
            self._state_reset = _state_reset_fn()
        if page_table is not None:
            self.pages: PageTable | None = page_table
        elif page_size and not stateful:
            self.pages = PageTable(
                page_size,
                capacity_pages=(page_capacity if page_capacity is not None
                                else 16 * slots),
                platform=self.platform,
                on_evict=(self._pool.release if self.paged else None))
        else:
            self.pages = None
        if (self.paged and self.pages is not None
                and self.pages.page_size != self._ps):
            raise ValueError(
                f"page table page_size {self.pages.page_size} != pool page "
                f"size {self._ps}: paged payloads are pool pages, the two "
                "extents must coincide")

        self.queue: collections.deque[Request] = collections.deque()
        self._ids: set[str] = set()            # every id ever submitted
        self.slots: list[_Slot | None] = [None] * slots
        self._dirty: set[int] = set()          # lanes holding a dead cache page
        self._seq = 0
        self._pending: tuple[_StepMeta, Any] | None = None  # unretired step
        self._trace: tracing.StepTrace | None = None  # step() in progress
        self._prev_nxt = None                  # device argmax of pending step

        # throughput counters — monotone by construction
        self.steps = 0
        self.tokens_generated = 0
        self.prompt_tokens_processed = 0
        self.prompt_tokens_reused = 0
        self.stalls = 0                        # lane-steps waiting on a sibling
        self.admission_stalls = 0              # admissions vetoed by the hook
        self.rematches = 0                     # mid-flight prefix adoptions
        self.rematched_tokens = 0              # prompt tokens adopted mid-flight
        self.pages_recycled = 0                # ring entries reused (windowed)
        self.completed: list[Request] = []
        self.rejected = 0
        self.shed = 0                          # queue heads dropped by the hook
        self.token_faults = 0                  # corrupted tokens refused
        self.replays = 0                       # quarantine-driven requeues
        # corruption quarantine: slots whose retired token failed the
        # vocab range check or the journal cross-check this step — their
        # requests are evicted and replayed by _recover_faulted()
        self._faulted: list[_Slot] = []
        # slot identities a flush-retire must skip (their journal position
        # is behind the in-flight step; delivering would leave a gap)
        self._skip_retire: frozenset = frozenset()
        self._replay_counts: dict[str, int] = {}
        # livelock guard: a request quarantined this many times stops
        # being "transient corruption" and raises (a real divergence bug
        # would otherwise replay forever)
        self.max_replays = 16
        # energy meter: purely observational joule accounting over the
        # calibrated HEEPocrates domain model. It reads launch shapes and
        # page holdings after the fact and never touches tokens, PRNG
        # state, or admission order — metered outputs are bit-identical
        # to metered=False (the property suite holds the engine to that)
        if metered:
            from repro.serve.energy_meter import EnergyMeter

            self._meter: EnergyMeter | None = EnergyMeter(
                point=operating_point, gate_idle_banks=gate_idle_banks)
        else:
            self._meter = None

        if self.paged:
            self._pstep = paged_step_fn(cfg, self._window, mesh=mesh,
                                        tp_axis=tp_axis)
            self._pchunk = (paged_chunk_fn(cfg, prefill_chunk, self._window,
                                           mesh=mesh, tp_axis=tp_axis)
                            if prefill_chunk > 1 else None)
            self._cache = None
        else:
            self._step_fn = _slot_step_fn(cfg)
            self._chunk_fn = (_chunk_step_fn(cfg, prefill_chunk)
                              if prefill_chunk > 1 else None)
            self._reset_fn = _slot_reset_fn()
            self._page_template = registry.cache_init(cfg, 1, self.device_len)
            self._cache = self._init_cache()
        self._zero_prev = jnp.zeros((self.n_lanes,), jnp.int32)
        # per-lane sampling state: the PRNG keys are device state (donated
        # through the jitted step, advanced on-device on emitting steps);
        # the parameters are host arrays converted per launch. Lanes are
        # (re-)seeded at admission; greedy lanes keep temp 0 = exact argmax
        self._keys = zero_keys(self.n_lanes)
        self._temp = np.zeros((self.n_lanes,), np.float32)
        self._topk = np.zeros((self.n_lanes,), np.int32)
        self._topp = np.ones((self.n_lanes,), np.float32)
        self.sampled_requests = 0              # admissions with sampling on

        n_banks = self.platform.config.n_banks
        self._slot_bank = [f"bank{i % n_banks}" for i in range(slots)]
        # our own platform: the whole idle bank pool starts gated. A shared
        # platform's states are left untouched at construction — another
        # engine may have live slot state in any bank; all wake/gate
        # transitions go through the platform's shared bank refcounts.
        if owns_platform:
            for i in range(n_banks):
                self.platform.power.clock_gate(f"bank{i}")

    # -- device-state plumbing ----------------------------------------------

    def _init_cache(self):
        # one lane per device slot, each an exact copy of the family's
        # batch-1 initial cache (not assumed to be zeros)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_lanes,) + x.shape),
            self._page_template)

    # -- admission -----------------------------------------------------------

    def submit(self, request: Request) -> bool:
        """Enqueue a request; False (and counted) when backpressure rejects it."""
        if len(request.prompt) + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {request.id!r} needs "
                f"{len(request.prompt) + request.max_new_tokens} positions, "
                f"engine max_len is {self.max_len}")
        if request.id in self._ids:
            # ids key the journal; a duplicate would silently interleave two
            # requests' tokens into one record and break preemption replay
            raise ValueError(f"duplicate request id {request.id!r}")
        if (self.queue_capacity is not None
                and len(self.queue) >= self.queue_capacity):
            self.rejected += 1
            return False
        request.arrival_time = (request.arrival_time
                                if request.arrival_time is not None
                                else self.clock())
        self._ids.add(request.id)
        self.queue.append(request)
        return True

    def _admit(self) -> int:
        """Admit queue heads into free slots; returns how many."""
        free = [i for i in range(self.n_slots) if self.slots[i] is None]
        admitted = 0
        while self.queue and free:
            i = self._place(free)
            if i is SHED:
                # latency-SLO admission control: the head can no longer
                # meet its SLO, so the scheduler drops it instead of
                # spending a slot on work that is already worthless
                self.queue.popleft()
                self.shed += 1
                continue
            if i is None:
                break        # head unplaceable: FIFO forbids skipping it
            free.remove(i)
            req = self.queue.popleft()              # FIFO — fairness invariant
            self._admit_into(i, req)
            admitted += 1
        return admitted

    def _place(self, free: list[int]):
        """First free slot the scheduler lets the queue head into (None =
        stalled this step). The hook peeks, never pops: a veto leaves the
        request at the queue head so FIFO order survives the stall. A
        veto's scope is the hook's call: False is per-slot (a later free
        slot may sit on an already-awake bank and admit the same head at
        zero budget cost — and the vetoed slot stays available to the next
        head); None is engine-global (no grant will appear mid-step); the
        ``SHED`` sentinel tells :meth:`_admit` to drop the head outright
        (the one verdict that does pop — admission control, not a stall)."""
        if self._admission_hook is None:
            return free[0]
        for i in free:
            verdict = self._admission_hook(self, i, self.queue[0])
            if verdict is SHED:
                return SHED
            if verdict:
                return i
            self.admission_stalls += 1
            if verdict is None:
                return None
        return None

    def _admit_into(self, i: int, req: Request) -> None:
        """Bind ``req`` to free slot ``i``: page-table acquisition, journal
        open, bank wake, admit interrupt."""
        match = (self.pages.acquire(req.prompt, self.namespace)
                 if self.pages is not None else None)
        if not self.paged and match is None and i in self._dirty:
            self._cache = self._reset_fn(self._cache, i,
                                         self._page_template)
            self._dirty.discard(i)
        rec = self.journal.open(
            req.id, req.prompt, req.max_new_tokens,
            sampling=req.sampling.astuple() if req.sampling else None)
        req.tokens = []
        req.admit_time = self.clock()
        # (re-)seed the lane's sampling chain: replay after any preemption
        # restarts the per-request PRNG chain from the journaled seed, and
        # emit-gated key advance makes chain position == produced count —
        # so the replayed tokens are bit-identical however many prefill
        # launches (prefix adoption, chunking, stalls) the replay takes
        sp = req.sampling or GREEDY
        self._temp[i] = sp.temperature
        self._topk[i] = sp.top_k
        self._topp[i] = sp.top_p
        self._keys = self._keys.at[i].set(jnp.asarray(seed_key(sp.seed)))
        if req.sampling is not None:
            self.sampled_requests += 1
        if self._state is not None:
            # a new (or replayed) request's recurrence starts from zero
            with tracing.span("engine.state_reset", self._trace):
                self._state = self._state_reset(self._state, i)
            if self._trace is not None:
                self._trace.state_resets += 1
        slot = _Slot(request=req, seq=rec.arrival_seq)
        if match is not None:
            # shared prefix admitted pre-consumed. Paged backend: pure
            # block-table pointing — the chain's pool pages are pinned
            # in place, no state is copied, ever. Lane backend: the lane
            # copy is deferred to the first step (copy-on-write), so a
            # slot preempted before it runs never pays for the copy.
            slot.fed = match.tokens_matched
            if self.paged:
                # window clamp: chain pages wholly below the window the
                # slot will ever attend from (positions < fed+1-window)
                # are never read — their tokens still count as reused
                # (nothing recomputes them), but the slot neither pins
                # them in the pool nor keeps them pinned in the table
                first_needed = 0
                if self._window is not None:
                    first_needed = max(
                        0, slot.fed + 1 - self._window) // self._ps
                kept = []
                for b, (key, idx) in enumerate(zip(match.keys, match.chain)):
                    if b < first_needed:
                        continue
                    self._pool.retain(idx)
                    slot.pages_by_block[b] = idx
                    kept.append(key)
                dropped = match.keys[:len(match.keys) - len(kept)]
                if dropped:
                    self.pages.release(dropped, self.namespace)
                slot.page_keys = tuple(kept)
                slot.blocks_covered = slot.fed // self._ps
            else:
                slot.page_keys = match.keys
                slot.pending_snapshot = match.snapshot
            self.prompt_tokens_reused += match.tokens_matched
        slot.next_token = req.prompt[slot.fed]
        self.journal.note_prefix(req.id, slot.fed, slot.page_keys)
        self.slots[i] = slot
        # shared refcount wakes the bank if idle
        self.platform.bank_acquire(self._slot_bank[i])
        self.platform.interrupts.fire(ADMIT_LINE, req)

    # -- the engine step ------------------------------------------------------

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def busy(self) -> bool:
        return (self.active > 0 or bool(self.queue)
                or self._pending is not None)

    def step(self) -> bool:
        """Admit, then advance every occupied lane one scheduling step.

        A decoding lane consumes exactly one token per step; a prefilling
        lane consumes up to ``prefill_chunk`` prompt tokens (clamped to the
        next page boundary when prefix sharing is on, so every lane state
        that completes a page is publishable), or zero while it waits on a
        sibling computing the same page (dedup stall). With async dispatch
        the launch happens before the *previous* step's host bookkeeping,
        so the device never idles on the host. Returns False when idle.

        A step that does work logs one ``StepTrace`` and a profiler span
        per phase (:mod:`repro.serve.tracing`).
        """
        rec = tracing.StepTrace(self.name, time.perf_counter())
        generated = self.tokens_generated
        prompt_fed = self.prompt_tokens_processed
        self._trace = rec
        try:
            with jax.profiler.TraceAnnotation("engine.step"):
                worked = self._step(rec)
        finally:
            self._trace = None
        if worked:
            rec.end = time.perf_counter()
            rec.emitted = self.tokens_generated - generated
            rec.prompt_fed = self.prompt_tokens_processed - prompt_fed
            tracing.record(rec)
        return worked

    def _step(self, rec: tracing.StepTrace) -> bool:
        if self._faulted:
            # quarantine left by a preemption's pending-flush: recover
            # before dispatch — the faulted slot's next_token is stale
            self._recover_faulted()
        if self._meter is not None:
            with tracing.span("engine.meter", rec):
                residents, idle_banks = self._meter_residents()
                self._meter.tick(self.clock(), residents, idle_banks)
        with tracing.span("engine.admit", rec):
            rec.admitted = self._admit()
        rec.queued = len(self.queue)
        if self.active == 0:
            if self._pending is not None:
                self._retire(self._pending)        # drain the in-flight step
                self._pending = None
                self._prev_nxt = None
                if self._faulted:
                    self._recover_faulted()
                return True
            return False
        meta, nxt = self._dispatch()
        self.steps += 1
        if self.async_dispatch:
            prev, self._pending = self._pending, (meta, nxt)
            self._prev_nxt = nxt
            if prev is not None:
                self._retire(prev)   # host catches up while the device runs
        else:
            self._retire((meta, nxt))
        if self._faulted:
            self._recover_faulted()
        return True

    def _dispatch(self) -> tuple[_StepMeta, Any]:
        """Build this step's batch, launch it, and do all host bookkeeping
        that does not need the step's token values (those retire later)."""
        rec = self._trace
        with tracing.span("engine.batch", rec):
            toks, counts, feedback, emit = self._batch(rec)
        with tracing.span("engine.tables", rec):
            tables = self._build_tables() if self.paged else None
        with tracing.span("engine.launch", rec):
            nxt = self._launch(toks, counts, feedback, emit, tables)
        if rec is not None:
            rec.launched = time.perf_counter()
        with tracing.span("engine.account", rec):
            meta = _StepMeta([], [])
            if self._meter is not None:
                occupied = {self._slot_bank[i]
                            for i, s in enumerate(self.slots) if s is not None}
                launch_idle_banks = len(set(self._slot_bank)) - len(occupied)
                charges = []
            for i, slot in enumerate(self.slots):
                if slot is None:
                    continue
                c = int(counts[i])
                was_prefilling = slot.prefilling
                if was_prefilling and c == 0:
                    continue               # stalled this step
                if self._meter is not None:
                    charges.append((slot.request,
                                    "prefill" if was_prefilling else "decode",
                                    c, self._page_share(slot)))
                slot.fed += c
                if self.paged:
                    self._recycle_dead(slot)   # window crossed: free dead
                if was_prefilling:
                    self.prompt_tokens_processed += c
                    self._maybe_publish(i, slot)
                    if slot.prefilling:
                        # still consuming the prompt: teacher-force the next
                        # token
                        slot.next_token = slot.request.prompt[slot.fed]
                        continue
                    self._drop_claims(slot)    # prefill done; nothing to claim
                meta.emitted.append((i, slot))
                slot.produced += 1
                self.tokens_generated += 1
                if slot.produced >= slot.request.max_new_tokens:
                    # the lane is host-known complete the moment the step is
                    # dispatched (greedy decode emits exactly one token per
                    # step); free it now so the next admission overlaps with
                    # the in-flight computation — the token value lands at
                    # retire
                    meta.finished.append(slot)
                    self._evict(i)
        if self._meter is not None:
            with tracing.span("engine.meter", rec):
                self._meter.charge_step(charges, launch_idle_banks)
        return meta, nxt

    def _batch(self, rec: tracing.StepTrace | None):
        """This step's host inputs — tokens, per-lane counts, feedback and
        emit flags — with the pages they will write allocated."""
        chunk = self.prefill_chunk
        n = self.n_lanes
        toks = np.full((n, chunk), self.pad_token, np.int32)
        counts = np.zeros((n,), np.int32)
        feedback = np.zeros((n,), bool)
        # emit[i]: lane i produces a token this launch (decode steps, and
        # the prefill launch consuming the last prompt token) — the gate
        # on the on-device PRNG key advance, so a lane's sampling-chain
        # position always equals its produced-token count, whatever the
        # chunking / prefix adoption / stall pattern of this particular run
        emit = np.zeros((n,), bool)
        pending_emit = ({i: s for i, s in self._pending[0].emitted}
                        if self._pending is not None else {})
        decode_fed = 0

        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            if slot.prefilling:
                if self.paged and self.pages is not None:
                    self._try_rematch(slot)
                prompt = slot.request.prompt
                m = min(chunk, len(prompt) - slot.fed)
                if self.pages is not None:
                    m = min(m, self.pages.page_size
                            - slot.fed % self.pages.page_size)
                if (self.paged and self.pages is not None
                        and self._stalled(slot)):
                    self.stalls += 1
                    continue               # counts[i] stays 0: wait, adopt
                toks[i, :m] = prompt[slot.fed:slot.fed + m]
                counts[i] = m
                emit[i] = slot.fed + m >= len(prompt)
            else:
                counts[i] = 1
                emit[i] = True
                decode_fed += 1
                if self.async_dispatch and pending_emit.get(i) is slot:
                    feedback[i] = True     # token rides on-device from step N
                else:
                    toks[i, 0] = slot.next_token
            if self.paged and counts[i]:
                self._ensure_pages(slot, slot.fed + int(counts[i]))
        if rec is not None:
            rec.decode_fed = decode_fed
        return toks, counts, feedback, emit

    def _launch(self, toks, counts, feedback, emit, tables):
        """One batched device launch; returns the on-device next-token vec
        (sampled per lane — exact argmax for greedy lanes). ``tables`` is
        the paged backend's (block tables, lengths) on the device."""
        if self.chaos is not None:
            # fault-injection point, deliberately before any buffer is
            # donated: a DeviceStepFault here leaves device and host
            # state exactly as they were, so the step is retryable
            # (page allocation above is idempotent-resumable)
            self.chaos.launch(self.name)
        chunk = self.prefill_chunk
        prev = (self._prev_nxt if self._prev_nxt is not None
                else self._zero_prev)
        fb = jnp.asarray(feedback)
        em = jnp.asarray(emit)
        # copies: on the CPU a device array may alias the numpy buffer it
        # came from, and admission rewrites these rows while the launch can
        # still be in flight (async dispatch)
        temp = jnp.asarray(self._temp.copy())
        tk = jnp.asarray(self._topk.copy())
        tp = jnp.asarray(self._topp.copy())
        if self.paged:
            arena = self._arena
            tables, lengths = tables
            if chunk == 1 or int(counts.max()) <= 1:
                nxt, arena.k, arena.v, self._state, self._keys = self._pstep(
                    self.params, arena.k, arena.v, self._state, tables,
                    lengths, jnp.asarray(toks[:, 0]), fb, prev,
                    jnp.asarray(counts > 0), em, self._keys, temp, tk, tp)
            else:
                nxt, arena.k, arena.v, self._keys = self._pchunk(
                    self.params, arena.k, arena.v, tables, lengths,
                    jnp.asarray(toks), jnp.asarray(counts), fb, prev,
                    em, self._keys, temp, tk, tp)
            return nxt
        self._apply_pending_snapshots()
        # empty lanes still ride the batched step (pad token): their lanes
        # are garbage afterwards and must be reset before the next admission
        self._dirty.update(i for i, s in enumerate(self.slots) if s is None)
        self._dirty.update(range(self.n_slots, self.n_lanes))
        toks4 = toks.reshape(self.n_lanes, chunk, 1, 1)
        if chunk == 1 or int(counts.max()) <= 1:
            # steady-state decode: every lane feeds one token, so skip the
            # chunk scan (it would run chunk-1 masked iterations per lane)
            nxt, self._cache, self._keys = self._step_fn(
                self.params, self._cache, jnp.asarray(toks4[:, 0]), fb,
                prev, em, self._keys, temp, tk, tp)
        else:
            nxt, self._cache, self._keys = self._chunk_fn(
                self.params, self._cache, jnp.asarray(toks4),
                jnp.asarray(counts), fb, prev, em, self._keys, temp, tk, tp)
        return nxt

    def _retire(self, pending: tuple[_StepMeta, Any]) -> None:
        """Host-side completion of a dispatched step: transfer the argmax
        vector and run everything that needed the token values.

        Every delivered token runs the corruption gate: the chaos hook
        (if any) may corrupt the host-transferred value, and a token
        failing the vocab range check or the journal's replay
        cross-check is *never* journaled or appended — its slot joins
        the quarantine (``_faulted``) and the request replays from the
        journal (:meth:`_recover_faulted`). Slots in ``_skip_retire``
        (an in-flight step flushed during quarantine recovery) are
        skipped outright: their journal position is behind this step,
        so delivering would corrupt the record's sequence.
        """
        meta, nxt = pending
        with tracing.span("engine.wait", self._trace):
            vals = np.asarray(jax.device_get(nxt)).reshape(-1)
        if self._trace is not None:
            self._trace.waited = time.perf_counter()
        with tracing.span("engine.retire", self._trace):
            now = self.clock()
            for i, slot in meta.emitted:
                if id(slot) in self._skip_retire:
                    continue
                tok = int(vals[i])
                if self.chaos is not None:
                    tok = self.chaos.deliver_token(self.name, tok)
                ok = 0 <= tok < self.cfg.vocab
                if ok:
                    try:
                        self.journal.record_token(slot.request.id, tok)
                    except RuntimeError:
                        # replay cross-check divergence: an in-range corrupt
                        # token caught against the journaled prior run
                        ok = False
                if not ok:
                    self.token_faults += 1
                    self._faulted.append(slot)
                    continue
                if slot.request.first_token_time is None:
                    # TTFT stamp (at retire: the token is host-visible only
                    # once the transfer lands)
                    slot.request.first_token_time = now
                slot.request.tokens.append(tok)
                slot.next_token = tok
            faulted_ids = {id(s) for s in self._faulted}
            for slot in meta.finished:
                if id(slot) in self._skip_retire or id(slot) in faulted_ids:
                    continue           # quarantined: must replay, not finish
                req = slot.request
                req.finish_time = self.clock()
                self.journal.complete(req.id)
                self.completed.append(req)
                # XAIF end-of-computation interrupt, then the request's
                # handler
                self.platform.interrupts.fire(COMPLETE_LINE, req)
                if req.on_complete is not None:
                    req.on_complete(req)

    def _recover_faulted(self) -> None:
        """Quarantine recovery: replay every corruption-faulted request.

        The in-flight async step (if any) is flushed first with the
        quarantined slots masked out — their pending token is discarded
        (the journal stops before the corrupted position, and replay
        regenerates everything after it), while innocent lanes retire
        normally. Each faulted request is then evicted and requeued at
        the front with its bookkeeping reset; re-admission reopens the
        journal record (the pre-fault tokens become the ``prior`` run)
        and ``record_token`` cross-checks the replay token-for-token.
        Recovery is charged to the request's own latency: arrival time
        is preserved, so TTFT/TPOT absorb the replay honestly.
        """
        while self._faulted:
            batch, self._faulted = self._faulted, []
            if self._pending is not None:
                self._skip_retire = frozenset(id(s) for s in batch)
                try:
                    self._retire(self._pending)
                finally:
                    self._skip_retire = frozenset()
                self._pending = None
                self._prev_nxt = None
            with tracing.span("engine.recover", self._trace):
                requeue = []
                for slot in sorted(batch, key=lambda s: s.seq):
                    req = slot.request
                    n = self._replay_counts.get(req.id, 0) + 1
                    self._replay_counts[req.id] = n
                    if n > self.max_replays:
                        raise RuntimeError(
                            f"request {req.id!r} quarantined {n} times — "
                            "persistent divergence, not transient corruption")
                    for i, s in enumerate(self.slots):
                        if s is slot:
                            self._evict(i)
                            break
                    # a host-known-finished slot was already evicted at
                    # dispatch; a preemption racing the quarantine may have
                    # requeued the request itself — never queue it twice
                    if any(r is req for r in self.queue):
                        continue
                    req.tokens = []
                    req.admit_time = None
                    req.first_token_time = req.finish_time = None
                    requeue.append(req)
                    self.replays += 1
                self.queue.extendleft(reversed(requeue))

    # -- paged-backend plumbing ----------------------------------------------

    def _build_tables(self):
        t = np.full((self.n_lanes, self._np_slot), self._pool.null, np.int32)
        lengths = np.zeros((self.n_lanes,), np.int32)
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            for b, idx in slot.pages_by_block.items():
                t[i, b % self._np_slot] = idx
            lengths[i] = slot.fed
        return jnp.asarray(t), jnp.asarray(lengths)

    def _ensure_pages(self, slot: _Slot, target: int) -> None:
        """Grow the slot's block table to cover positions [0, target).

        Windowed configs: the table is a ring — covering a new block first
        recycles whatever older block occupies its entry (by then that
        block's positions fall wholly outside the window), so the slot
        never holds more than ``ceil(window/page_size) + 1`` pages.
        """
        need = -(-target // self._ps)
        while slot.blocks_covered < need:
            b = slot.blocks_covered
            self._free_entry(slot, b)
            if not self._pool.free_count:
                if self._reclaim is not None:
                    self._reclaim(self)    # cluster: fair cross-tenant evict
                elif self.pages is not None:
                    self.pages.clear()     # recycle unpinned shared residency
            slot.pages_by_block[b] = self._pool.alloc(self.name)
            slot.blocks_covered = b + 1

    def _free_entry(self, slot: _Slot, b: int) -> None:
        """Ring recycling backstop: drop whatever older block occupies
        block ``b``'s table entry. Eager recycling (:meth:`_recycle_dead`)
        normally frees dead blocks the moment they fall out of the window,
        so this fires only for blocks an entry-reuse reaches first (e.g. a
        re-match jump landing on an entry whose old block is still barely
        in-window). No-op for non-windowed slots (the full-width table
        never aliases two blocks onto one entry)."""
        if self._window is None:
            return
        width = self._np_slot
        for b_old in [o for o in slot.pages_by_block
                      if o % width == b % width and o != b]:
            self._recycle_block(slot, b_old)

    def _recycle_dead(self, slot: _Slot) -> None:
        """Eager window recycling: free (or disown) every block whose
        positions fall wholly below the slot's attention window, the
        moment ``fed`` crosses the block boundary — a slot that then
        stalls (dedup wait, scheduler preemption) holds no dead pages
        while its peers fight for the shared free list. Pool occupancy
        drops immediately at the crossing instead of lazily at the ring
        entry's next reuse."""
        if self._window is None or not slot.pages_by_block:
            return
        first_needed = max(0, slot.fed + 1 - self._window) // self._ps
        for b_old in [b for b in slot.pages_by_block if b < first_needed]:
            self._recycle_block(slot, b_old)

    def _recycle_block(self, slot: _Slot, b_old: int) -> None:
        """Release one out-of-window block: a private page returns to the
        pool's free list; an adopted shared-prefix page is *disowned* —
        the slot's pool ref and table pin are released, while the table's
        own residency keeps the page warm for future admissions."""
        self._pool.release(slot.pages_by_block.pop(b_old))
        key = slot.request.prompt[:(b_old + 1) * self._ps]
        if key in slot.page_keys:
            self.pages.release((key,), self.namespace)
            slot.page_keys = tuple(k for k in slot.page_keys if k != key)
        self.pages_recycled += 1
        self.journal.note_recycle(slot.request.id, 1)

    def _try_rematch(self, slot: _Slot) -> None:
        """Mid-flight prefix re-match: adopt a sibling's freshly published
        pages covering tokens this slot has not computed yet. Pure
        block-table surgery — any partially-written private page in the
        adopted range is released (its positions hold the same values the
        shared page does, since both ran the same prompt prefix). Windowed
        slots clamp the adoption to the blocks the window can still see
        after the jump; blocks below it are skipped outright (their tokens
        count as reused, their pages are never pinned)."""
        prompt = slot.request.prompt
        m = self.pages.lookup(prompt, self.namespace)
        if m <= slot.fed:
            return
        ps = self.pages.page_size
        from_block = slot.fed // ps
        if self._window is not None:
            from_block = max(from_block, (m + 1 - self._window) // ps)
        ext = self.pages.acquire_range(prompt, from_block, m // ps,
                                       self.namespace)
        if not ext:
            return
        adopted = m - slot.fed
        for key, idx in ext:
            self._pool.retain(idx)
            b = len(key) // ps - 1
            self._free_entry(slot, b)      # ring: evict the entry's old block
            if b in slot.pages_by_block:
                self._pool.release(slot.pages_by_block[b])
            slot.pages_by_block[b] = idx
        slot.page_keys += tuple(k for k, _ in ext)
        slot.blocks_covered = max(slot.blocks_covered, m // ps)
        slot.fed = m
        self._recycle_dead(slot)           # the jump may strand dead blocks
        slot.next_token = prompt[m]
        self.prompt_tokens_reused += adopted
        self.rematches += 1
        self.rematched_tokens += adopted
        self.journal.note_rematch(slot.request.id, adopted)

    def _stalled(self, slot: _Slot) -> bool:
        """Dedup of concurrent identical cold prefills: if another live slot
        already claimed the page this slot would compute next, wait (feed
        nothing this step) and adopt the page when it publishes. Claims
        live in the page table's claim registry (keyed by namespace, like
        the pages themselves), so the claimant may belong to *any* engine
        sharing the table — two replicas bursting the same cold prefix
        dedup across engines, not just across one engine's slots. Claims
        are per-page and dropped the moment the claimant crosses the
        boundary, so a waiter never outlives its claimant's current page."""
        prompt = slot.request.prompt
        ps = self.pages.page_size
        boundary = (slot.fed // ps + 1) * ps
        if boundary > len(prompt) - 1:
            return False                   # tail extent: never publishable
        key = prompt[:boundary]
        if self.pages.has(key, self.namespace):
            return False                   # resident: re-match handles it
        claimant = self.pages.claimant(key, self.namespace)
        if claimant is not None and claimant[1] is not slot:
            c_eng, c_slot = claimant
            alive = any(s is c_slot for s in c_eng.slots)
            if alive and c_slot.prefilling:
                return True
            self.pages.unclaim(key, self.namespace)   # stale claim: steal it
        self.pages.claim(key, (self, slot), self.namespace)
        if key not in slot.claims:
            slot.claims.append(key)
        return False

    def _drop_claims(self, slot: _Slot) -> None:
        if self.pages is not None:
            for key in slot.claims:
                claimant = self.pages.claimant(key, self.namespace)
                if claimant is not None and claimant[1] is slot:
                    self.pages.unclaim(key, self.namespace)
        slot.claims = []

    # -- lane-backend plumbing -----------------------------------------------

    def _apply_pending_snapshots(self) -> None:
        """Copy-on-write (lane backend only): a slot admitted onto shared
        pages borrows them at admission; its private lane copy materialises
        here, right before the lane writes its first divergent token."""
        for i, slot in enumerate(self.slots):
            if slot is None or slot.pending_snapshot is None:
                continue
            self._cache = self._reset_fn(self._cache, i,
                                         slot.pending_snapshot)
            slot.pending_snapshot = None
            self._dirty.discard(i)
            self.pages.note_cow(len(slot.page_keys))

    def _maybe_publish(self, i: int, slot: _Slot) -> None:
        """Publish lane ``i``'s state when prefill lands on a page boundary
        (chunk feeds are clamped so boundaries are always hit exactly).
        Paged backend: a refcount bump on the just-completed pool page —
        O(1), no device work. Lane backend: a device gather of the lane."""
        if self.pages is None:
            return
        fed = slot.fed
        if fed % self.pages.page_size != 0:
            return
        key = slot.request.prompt[:fed]
        self.pages.unclaim(key, self.namespace)   # computed: the claim is moot
        if not self.pages.wants(key, self.namespace):
            return
        if self.paged:
            idx = slot.pages_by_block[fed // self.pages.page_size - 1]
            self._pool.retain(idx)         # residency reference
            if not self.pages.publish(key, idx, self.namespace):
                self._pool.release(idx)
        else:
            snapshot = jax.tree.map(lambda x: x[i], self._cache)
            self.pages.publish(key, snapshot, self.namespace)

    def _evict(self, i: int) -> None:
        slot = self.slots[i]
        if slot is not None:
            if slot.page_keys:
                # refcount release — pinned pages outlive the slot only
                # through the table's own residency, never through this pin
                self.pages.release(slot.page_keys, self.namespace)
                slot.page_keys = ()
            slot.pending_snapshot = None
            if self.paged:
                for idx in slot.pages_by_block.values():
                    self._pool.release(idx)
                slot.pages_by_block = {}
            self._drop_claims(slot)
        self.slots[i] = None
        self._dirty.add(i)
        # shared refcount: gates only when no engine holds the bank
        self.platform.bank_release(self._slot_bank[i])

    @property
    def _bank_load(self) -> dict[str, int]:
        """This engine's live slots per bank — derived, single source of
        truth is slot occupancy (the platform refcounts span all engines)."""
        load = {b: 0 for b in set(self._slot_bank)}
        for i, s in enumerate(self.slots):
            if s is not None:
                load[self._slot_bank[i]] += 1
        return load

    # -- energy metering ------------------------------------------------------

    def _page_share(self, slot: _Slot) -> float:
        """Refcount-weighted KV pages this slot holds: shared-prefix pool
        pages split their holding energy 1/refcount across local holders;
        lane-backend slots count their pinned snapshot pages at weight 1.
        Residual shares (table residency, other engines' pins) stay
        uncharged — modeled as gated-off, never double-charged."""
        if self.paged and slot.pages_by_block:
            refs = self._pool.refcounts()
            return sum(1.0 / max(refs.get(idx, 1), 1)
                       for idx in slot.pages_by_block.values())
        return float(len(slot.page_keys))

    def _meter_residents(self):
        """(residents, idle_banks) for the meter's clock tick: every
        occupied slot with its bank-leak weight (a bank shared by k live
        slots splits its retention leakage k ways) and page share, plus
        how many of the engine's banks host no live slot."""
        load = self._bank_load
        residents = []
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            bank_weight = 1.0 / load[self._slot_bank[i]]
            residents.append((slot.request, bank_weight,
                              self._page_share(slot)))
        idle_banks = sum(1 for n in load.values() if n == 0)
        return residents, idle_banks

    def set_operating_point(self, name: str) -> None:
        """Move the engine's energy meter to a named DVFS point (see
        :data:`repro.core.energy.OPERATING_POINTS`). Accounting only:
        the throttled engine's tokens stay bit-identical — only the
        joules-per-token bookkeeping changes."""
        if self._meter is None:
            raise ValueError("engine built with metered=False has no "
                             "operating point to set")
        self._meter.set_point(name)

    # -- preemption -----------------------------------------------------------

    def preempt(self) -> list[Request]:
        """Evict every lane; re-queue in-flight requests in FIFO order.

        Decode is deterministic (greedy by argmax; sampled lanes re-seed
        their journaled PRNG chain at re-admission), so replay from the
        journal's prompts reproduces the preempted requests' outputs
        bit-for-bit. An in-flight
        async step is retired first — its tokens belong to the
        pre-preemption run and seed the journal's divergence cross-check.
        """
        if self._pending is not None:
            self._retire(self._pending)
            self._pending = None
            self._prev_nxt = None
        inflight = sorted(
            ((i, s) for i, s in enumerate(self.slots) if s is not None),
            key=lambda t: t[1].seq)
        for i, _ in inflight:
            self._evict(i)
        requeued = [s.request for _, s in inflight]
        for req in requeued:
            req.tokens = []
            req.admit_time = req.first_token_time = req.finish_time = None
        self.queue.extendleft(reversed(requeued))
        return requeued

    def preempt_slot(self, i: int, *, front: bool = True) -> Request | None:
        """Preempt one slot: evict lane ``i`` and re-queue its request —
        at the queue front (default, preserving FIFO order like
        :meth:`preempt`) or at the back (``front=False``, the scheduler's
        demote-a-tail move: an SLO-busting request gives up its slot and
        finishes after the salvageable work). Replay runs through the
        same journal machinery as :meth:`preempt`, so the requeued
        request's tokens are reproduced bit-for-bit; an in-flight async
        step is retired first, seeding the journal's divergence
        cross-check. Returns the requeued request, or None when the slot
        is empty (possibly because the flush just completed it)."""
        if self._pending is not None:
            self._retire(self._pending)
            self._pending = None
            self._prev_nxt = None
        slot = self.slots[i]
        if slot is None:
            return None
        self._evict(i)
        req = slot.request
        req.tokens = []
        req.admit_time = req.first_token_time = req.finish_time = None
        if front:
            self.queue.appendleft(req)
        else:
            self.queue.append(req)
        return req

    # -- convenience ----------------------------------------------------------

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        """Step until queue and slots drain (raises if still busy after
        ``max_steps`` — a missing-completion canary for tests)."""
        for _ in range(max_steps):
            if not self.step():
                return
        raise RuntimeError(f"engine still busy after {max_steps} steps")

    def drain_completed(self) -> list[Request]:
        """Hand off finished requests and release their retained state.

        A long-running serving loop must call this periodically (after
        delivering results) or per-request history — completed list, journal
        records, id registry — grows without bound. Drained ids become
        reusable.
        """
        done, self.completed = self.completed, []
        for req in done:
            self.journal.evict(req.id)
            self._ids.discard(req.id)
            self._replay_counts.pop(req.id, None)
        return done

    def occupancy(self) -> dict:
        """Point-in-time load for a scheduler to arbitrate on: slot and
        queue occupancy plus this engine's slice of the (possibly shared)
        page pool. One source of truth — :meth:`stats` embeds the same
        numbers for benchmarks."""
        out = {
            "slots": self.n_slots,
            "active": self.active,
            "slots_free": self.n_slots - self.active,
            "queued": len(self.queue),
        }
        if self._pool is not None:
            out.update(pool_free=self._pool.free_count,
                       pool_in_use=self._pool.in_use,
                       pool_pages_held=self._pool.in_use_by(self.name))
        return out

    def step_cost(self) -> int:
        """Tokens the next :meth:`step` would feed the device (decode lanes
        at one each, prefilling lanes up to ``prefill_chunk``) — the
        per-step cost signal a cluster scheduler weighs admissions with."""
        cost = 0
        for slot in self.slots:
            if slot is None:
                continue
            if slot.prefilling:
                cost += min(self.prefill_chunk,
                            len(slot.request.prompt) - slot.fed)
            else:
                cost += 1
        return cost

    def stats(self) -> dict:
        """Lifetime counters (monotone), plus page-table/pool stats when the
        paged prefix cache is enabled. The ``pool`` entry reports occupancy
        and free-list length — the cluster scheduler and the benchmarks
        read the same numbers."""
        out = {
            "steps": self.steps,
            "tokens_generated": self.tokens_generated,
            "prompt_tokens_processed": self.prompt_tokens_processed,
            "prompt_tokens_reused": self.prompt_tokens_reused,
            "prefill_chunk": self.prefill_chunk,
            "backend": "paged" if self.paged else "lanes",
            "async_dispatch": self.async_dispatch,
            "tp": self.tp,
            "window": self._window,
            "table_entries_per_slot": self._np_slot if self.paged else None,
            "pages_recycled": self.pages_recycled,
            "stalls": self.stalls,
            "admission_stalls": self.admission_stalls,
            "rematches": self.rematches,
            "rematched_tokens": self.rematched_tokens,
            "completed": len(self.completed),
            "sampled_requests": self.sampled_requests,
            "rejected": self.rejected,
            "shed": self.shed,
            "token_faults": self.token_faults,
            "replays": self.replays,
            "queued": len(self.queue),
            "active": self.active,
            "journal": self.journal.size(),
        }
        if self._meter is not None:
            out["energy"] = self._meter.stats()
        if self.pages is not None:
            out["pages"] = dict(self.pages.stats,
                                resident=self.pages.resident,
                                pinned=self.pages.pinned)
        if self._pool is not None:
            out["pool"] = dict(self._pool.stats,
                               pages=self._pool.n_pages,
                               in_use=self._pool.in_use,
                               free=self._pool.free_count,
                               occupancy=round(
                                   self._pool.in_use / self._pool.n_pages, 4),
                               held_by_engine=self._pool.in_use_by(self.name),
                               shared=not self.owns_pool)
        return out
