"""Global KV page pool + jitted paged decode steps for the serving engine.

This is the device side of the paged backend: one pool of fixed-size KV
pages shared by every decode slot (and by the prefix page table), reached
per step through per-slot *block tables*. It replaces the PR 2 layout of
one full-length cache lane per slot — prefix adoption becomes block-table
pointing (no copy-on-write lane materialisation), publishing a page is a
host-side refcount bump (no device gather), and eviction returns page ids
to a free list instead of resetting whole lanes.

Since PR 4 the pool is a *cluster-ownable* resource: the allocator (free
list, refcounts, per-tenant accounting) is model-agnostic and hands out
**globally valid page ids** from one id space, while the device storage
lives in per-cache-signature *arenas* created lazily by :meth:`PagePool.
arena`. Engines serving the same model family/shape share one arena (so a
page id published by one engine is directly readable by another — the
basis of cross-engine prefix sharing), and engines of different shapes
share only the id space and budget — the serving analogue of X-HEEP's
heterogeneous compute units arbitrating one memory pool.

Invariants:

* **Pool refcounts never go negative.** Every page id handed out by
  :meth:`PagePool.alloc` / pinned by :meth:`PagePool.retain` is released
  exactly once; over-release raises (the ``Platform.bank_release``
  discipline, applied to pages).
* **A referenced page is never recycled.** A page returns to the free list
  only when its last holder (slot block table or page-table residency)
  releases it.
* **The null page is write-never and release-never.** Row ``null`` pads
  unused block-table entries; attention masks every position at or beyond
  a slot's length, so its contents are unobservable — and the allocator
  refuses to ``retain``/``release`` it (it is not a real page).
* **One id space, many arenas.** ``alloc`` draws from a single free list
  regardless of which arena the page's bytes will land in, so the pool is
  one shared budget; per-tenant ``in_use_by`` accounting lets a scheduler
  arbitrate it.

The jitted step functions take *device feedback*: a decoding lane's input
token can come straight from the previous step's on-device next token —
sampled per the lane's :class:`~repro.serve.sampling.SamplingParams`,
exact argmax at zero temperature — via ``feedback``/``prev``, so the host
never has to block on a transfer before dispatching the next step: the
data path of the engine's async double-buffered dispatch survives
stochastic sampling because the per-lane PRNG keys advance on-device in
the same launch (see :mod:`repro.serve.sampling`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models import registry
from repro.models.config import ModelConfig
from repro.serve.sampling import sample, split_keys
from repro.sharding import rules as R

__all__ = ["PagePool", "PoolArena", "pool_signature", "paged_step_fn",
           "paged_chunk_fn", "place_params", "mesh_tp"]

# jitted paged kernels shared across engine instances (jax then caches
# compilations per pool/table shape)
_PAGED_FNS: dict = {}


def mesh_tp(mesh: Mesh, tp_axis: str = "model") -> int:
    """Size of the tensor-parallel axis of ``mesh`` (loud on a bad axis)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if tp_axis not in sizes:
        raise ValueError(f"mesh {mesh.axis_names} has no axis {tp_axis!r}")
    return sizes[tp_axis]


def place_params(cfg: ModelConfig, params, mesh: Mesh,
                 tp_axis: str = "model"):
    """Shard a host params tree onto ``mesh`` for the TP paged decode.

    wq/wk/wv land head-sharded over ``tp_axis``; everything else (embed,
    norms, MLP, wo, head) is replicated — the layout
    :func:`repro.sharding.rules.serve_param_specs` derives from the
    registry's logical axes. Every call places a fresh copy; engines share
    one through :meth:`PagePool.placed_params`.
    """
    R.validate_serve_tp(cfg, mesh_tp(mesh, tp_axis))
    specs = R.serve_param_specs(cfg, tp_axis)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)


def pool_signature(cfg: ModelConfig) -> tuple:
    """Cache-shape signature of a config: configs with equal signatures can
    share one device arena (their KV pages are layout-compatible)."""
    return (registry.kv_layers(cfg), cfg.n_kv_heads, cfg.resolved_head_dim)


@dataclasses.dataclass
class PoolArena:
    """Device storage for one cache signature: a (k, v) pair shaped
    ``(L·(n_pages + 1), page_size, Kh·Dh)`` over the ``L`` layers that
    hold KV — one flat page space in which layer ``l``'s page ``p`` is
    row ``l·(n_pages + 1) + p``; each layer's
    extra row is its null page
    (:func:`repro.models.transformer.paged_pool_init`). Page ids stay in
    ``[0, n_pages]``; the step offsets them per layer. Engines mutate
    ``k``/``v`` in place per step (donated buffers);
    same-signature engines share one arena, so page contents written by one
    engine are readable by every other through the shared id space."""

    k: Any
    v: Any


class PagePool:
    """Fixed-size KV page pool: free list, per-page refcounts, per-tenant
    accounting, and lazily created per-signature device arenas.

    Host state is the allocator: ``alloc()`` hands out a globally valid
    page id with one reference; ``retain``/``release`` follow the
    shared-bank discipline. Device state is reached via :meth:`arena` —
    one (k, v) arena per distinct cache signature, created on first use,
    all sharing the one id space (ids are valid rows in every arena).
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError("pool needs at least one page of one token")
        self.page_size = page_size
        self.n_pages = n_pages
        self.null = n_pages                    # sentinel row, never written
        self._arenas: dict[tuple, PoolArena] = {}
        # (id(params), mesh, tp_axis) -> (params, placed copy); holding
        # the host tree pins its id for as long as the entry lives
        self._placed: dict[tuple, tuple] = {}
        self._refs = np.zeros((n_pages,), np.int32)
        self._free = list(range(n_pages - 1, -1, -1))   # pop() -> 0, 1, 2, ...
        self._owner: dict[int, str | None] = {}
        self._by_owner: dict[str | None, int] = {}
        self.stats = {"allocated": 0, "freed": 0, "high_water": 0}
        # fault-injection hook (chaos harness): called with the owner tag
        # at the top of every alloc, before the free list is touched — it
        # may raise (repro.serve.chaos.AllocFault) to model a transient
        # allocation failure. None = off.
        self.fault_hook = None

    def arena(self, cfg: ModelConfig, mesh: Mesh | None = None,
              tp_axis: str = "model") -> PoolArena:
        """Device arena for ``cfg``'s cache signature (created on first
        use). Same-signature configs on the same mesh get the *same*
        arena object.

        With ``mesh`` the arena's head-major lane axis is sharded over
        ``tp_axis`` (:func:`repro.sharding.rules.serve_pool_spec`): each
        device holds the lanes of ``Kh/tp`` heads of every page — the
        arena is *split*, not duplicated, so ``tp`` devices cost the same
        KV bytes as one. Page ids (and the host allocator) are mesh-invariant;
        arenas on different meshes are distinct device storage keyed
        ``(signature, mesh)``, because a page's bytes physically live
        only on the mesh slice that wrote them — replicas on disjoint
        slices therefore never share an arena (see
        :meth:`ServeCluster.add_replica_group`).
        """
        sig = (pool_signature(cfg), mesh, tp_axis if mesh is not None
               else None)
        if sig not in self._arenas:
            k, v = registry.paged_pool_init(cfg, self.n_pages + 1,
                                            self.page_size)
            if mesh is not None:
                R.validate_serve_tp(cfg, mesh_tp(mesh, tp_axis))
                sharding = NamedSharding(mesh, R.serve_pool_spec(tp_axis))
                k = jax.device_put(k, sharding)
                v = jax.device_put(v, sharding)
            self._arenas[sig] = PoolArena(k, v)
        return self._arenas[sig]

    def placed_params(self, cfg: ModelConfig, params, mesh: Mesh,
                      tp_axis: str = "model"):
        """``params`` placed on ``mesh`` (:func:`place_params`), once per
        (params tree, mesh, axis) for the life of this pool: engines that
        share the pool on one mesh share one device copy of the weights,
        and the copy is freed with the pool — a process that builds and
        drops engines on several meshes does not stack copies on device."""
        key = (id(params), mesh, tp_axis)
        if key not in self._placed:
            self._placed[key] = (params,
                                 place_params(cfg, params, mesh, tp_axis))
        return self._placed[key][1]

    def bytes_by_device(self) -> dict[str, int]:
        """Real KV bytes resident per device, summed over every arena's
        addressable shards — the number that shows a TP arena is split
        (Kh/tp heads per device) rather than duplicated. Complements
        :attr:`device_pages`, which counts logical pages per arena."""
        out: dict[str, int] = {}
        for arena in self._arenas.values():
            for arr in (arena.k, arena.v):
                for shard in arr.addressable_shards:
                    dev = str(shard.device)
                    out[dev] = out.get(dev, 0) + shard.data.nbytes
        return out

    def _check(self, idx: int) -> None:
        if not 0 <= idx < self.n_pages:
            raise ValueError(
                f"page id {idx} out of range (the null sentinel "
                f"{self.null} is not a refcounted page)")

    def alloc(self, owner: str | None = None) -> int:
        """Take a free page (one reference held by the caller). ``owner``
        tags the page for per-tenant accounting until it is recycled.
        With a ``fault_hook`` installed (chaos harness) the hook runs
        first and may raise — the pool is untouched in that case, so the
        caller's allocation loop is safely retryable."""
        if self.fault_hook is not None:
            self.fault_hook(owner)
        if not self._free:
            raise RuntimeError(
                f"page pool exhausted ({self.n_pages} pages, all referenced)")
        idx = self._free.pop()
        self._refs[idx] = 1
        self._owner[idx] = owner
        self._by_owner[owner] = self._by_owner.get(owner, 0) + 1
        self.stats["allocated"] += 1
        self.stats["high_water"] = max(self.stats["high_water"], self.in_use)
        return idx

    def retain(self, idx: int) -> None:
        """Add a reference to a live page (block-table pin, residency, or a
        cross-tenant adoption of a sibling engine's page)."""
        self._check(idx)
        if self._refs[idx] <= 0:
            raise ValueError(f"page {idx} retained while free")
        self._refs[idx] += 1

    def release(self, idx: int) -> None:
        """Drop one reference; the last release recycles the page."""
        self._check(idx)
        if self._refs[idx] <= 0:
            raise ValueError(f"page {idx} released more than retained")
        self._refs[idx] -= 1
        if self._refs[idx] == 0:
            self._free.append(idx)
            owner = self._owner.pop(idx, None)
            self._by_owner[owner] = self._by_owner.get(owner, 1) - 1
            if not self._by_owner[owner]:
                del self._by_owner[owner]
            self.stats["freed"] += 1

    @property
    def in_use(self) -> int:
        """Pages currently referenced (allocated and not yet recycled)."""
        return self.n_pages - len(self._free)

    @property
    def free_count(self) -> int:
        """Pages available for allocation."""
        return len(self._free)

    @property
    def device_pages(self) -> int:
        """Device pages actually materialised: every arena carries the full
        id space (plus the null row), so this is arenas × (n_pages + 1) —
        the number to quote when sizing real KV memory, as opposed to the
        shared *id-space* size ``n_pages``."""
        return len(self._arenas) * (self.n_pages + 1)

    def in_use_by(self, owner: str | None) -> int:
        """Live pages carrying ``owner``'s tag. This is **alloc-origin**
        accounting: a page stays charged to the tenant that allocated it
        until its final release recycles it, even while other tenants hold
        adopted references — use it to see who *fills* the pool, and
        :meth:`PageTable.resident_by_ns` to see who *keeps* residency (the
        cluster's fair reclaim arbitrates on the latter)."""
        return self._by_owner.get(owner, 0)

    def owners(self) -> dict[str | None, int]:
        """Tenant tag -> live page count (alloc-origin, see
        :meth:`in_use_by`), for stats and debugging."""
        return dict(self._by_owner)

    def refcounts(self) -> dict[int, int]:
        """Live page id -> reference count (for tests and debugging)."""
        return {i: int(r) for i, r in enumerate(self._refs) if r > 0}


def _decode_call(cfg: ModelConfig, window: int | None,
                 mesh: Mesh | None, tp_axis: str):
    """The decode body shared by the step fns: a direct
    ``registry.decode_step_paged`` on one device, or the same step under
    ``shard_map`` on a mesh — params and pool arrive as per-device head
    slices (in_specs derived from the registry's logical axes), block
    tables / lengths / tokens ride replicated, and the one collective is
    the head all-gather inside the transformer (``tp_axis``). Outputs:
    logits replicated (every device computes the identical post-gather
    tail), pools sharded as they came in. ``state`` is the per-slot state
    arena of a config that ``registry.holds_state`` (None for the rest),
    carried through and returned as the fourth output.
    """
    def call(params, pool_k, pool_v, state, tables, lengths, tok, mask,
             tp=None):
        out = registry.decode_step_paged(
            params, cfg, pool_k, pool_v, tables, lengths, tok,
            append_mask=mask, window=window, tp_axis=tp, state=state)
        return out if registry.holds_state(cfg) else (*out, None)

    if mesh is None:
        return call

    pool_spec = R.serve_pool_spec(tp_axis)
    param_specs = R.serve_param_specs(cfg, tp_axis)
    rep = PartitionSpec()

    def local(params, pool_k, pool_v, state, tables, lengths, tok, mask):
        return call(params, pool_k, pool_v, state, tables, lengths, tok,
                    mask, tp_axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs, pool_spec, pool_spec, rep, rep, rep, rep,
                  rep),
        out_specs=(rep, pool_spec, pool_spec, rep),
        check_vma=False)


def paged_step_fn(cfg: ModelConfig, window: int | None = None,
                  mesh: Mesh | None = None, tp_axis: str = "model"):
    """Jitted single-token paged decode over every lane.

    Signature: ``(params, pool_k, pool_v, state, tables, lengths, toks,
    feedback, prev, mask, emit, keys, temp, top_k, top_p) -> (next_tokens,
    pool_k', pool_v', state', keys')`` where ``state`` is the per-slot
    state arena of a config that ``registry.holds_state`` (None
    otherwise), ``toks`` (B,) are host-chosen tokens,
    ``feedback`` (B,) selects the previous step's on-device next token
    ``prev`` instead (async double-buffering), and ``mask`` (B,) gates the
    KV append (False = idle/stalled lane riding the batch). ``keys`` is the
    per-lane raw PRNG key state; each lane's next token is drawn by
    :func:`~repro.serve.sampling.sample` under its (``temp``, ``top_k``,
    ``top_p``) parameters — exact argmax at zero temperature — and its key
    splits only where ``emit`` (B,) is set, so the sampling chain position
    always equals the lane's produced-token count (replay determinism).
    ``window`` (sliding-window configs) switches the block tables to ring
    semantics — pass the engine's *clamped* window (``min(cfg.sliding_
    window, device cache length)``) so the decode stays bit-identical to
    the lane ring cache. Pools, state and keys are donated.

    ``mesh`` switches the decode to tensor parallelism over ``tp_axis``
    (:func:`_decode_call`): the same jitted step, with the transformer
    body under ``shard_map`` on head-sliced params and pool. Sampling
    runs outside the sharded region on the replicated logits, so the TP
    step's tokens are bit-identical to the single-device step's.
    """
    key = ("step", cfg, window, mesh, tp_axis if mesh is not None else None)
    if key not in _PAGED_FNS:
        decode = _decode_call(cfg, window, mesh, tp_axis)

        def step(params, pool_k, pool_v, state, tables, lengths, toks,
                 feedback, prev, mask, emit, keys, temp, top_k, top_p):
            tok = jnp.where(feedback, prev, toks)
            logits, pool_k, pool_v, state = decode(
                params, pool_k, pool_v, state, tables, lengths, tok, mask)
            with jax.named_scope("sample"):
                carry, use = split_keys(keys)
                nxt = jax.vmap(sample)(logits, use, temp, top_k, top_p)
                keys = jnp.where(emit[:, None], carry, keys)
            return nxt, pool_k, pool_v, state, keys

        _PAGED_FNS[key] = jax.jit(step, donate_argnums=(1, 2, 3, 11))
    return _PAGED_FNS[key]


def paged_chunk_fn(cfg: ModelConfig, chunk: int, window: int | None = None,
                   mesh: Mesh | None = None, tp_axis: str = "model"):
    """Jitted chunked step: up to ``chunk`` tokens per lane in one launch.

    Scans the single-token paged step; iterations past a lane's ``count``
    are masked appends (the pool is untouched bitwise, so a decode lane
    with ``count == 1`` sees exactly one append). The returned token is
    sampled (exact argmax at zero temperature) after each lane's last fed
    token. The per-lane key splits **once per launch** regardless of
    ``count`` — every scan iteration draws with the same per-launch
    subkey and only the last fed iteration's token is kept, so a chunked
    prefill's first generated token is bit-identical to the unchunked
    path's — and the split is kept only where ``emit`` is set (lanes
    whose prefill completes this launch, and decode lanes). ``window``
    and ``mesh``/``tp_axis`` as in :func:`paged_step_fn` (the sharded
    decode runs per scan iteration; the scan carry is the sharded pool).
    A config that ``registry.holds_state`` has no chunked step.
    """
    key = ("chunk", cfg, chunk, window, mesh,
           tp_axis if mesh is not None else None)
    if key not in _PAGED_FNS:
        decode = _decode_call(cfg, window, mesh, tp_axis)

        def step(params, pool_k, pool_v, tables, lengths, toks, counts,
                 feedback, prev, emit, keys, temp, top_k, top_p):
            carry_keys, use = split_keys(keys)

            def body(carry, xs):
                pool_k, pool_v = carry
                j, tok_j = xs
                tok = jnp.where((j == 0) & feedback, prev, tok_j)
                logits, pool_k, pool_v, _ = decode(
                    params, pool_k, pool_v, None, tables, lengths + j, tok,
                    j < counts)
                with jax.named_scope("sample"):
                    nxt = jax.vmap(sample)(logits, use, temp, top_k, top_p)
                return (pool_k, pool_v), nxt

            (pool_k, pool_v), outs = lax.scan(
                body, (pool_k, pool_v),
                (jnp.arange(chunk, dtype=jnp.int32), toks.T))
            last = jnp.take_along_axis(
                outs.T, jnp.maximum(counts - 1, 0)[:, None], 1)[:, 0]
            keys = jnp.where(emit[:, None], carry_keys, keys)
            return last, pool_k, pool_v, keys

        _PAGED_FNS[key] = jax.jit(step, donate_argnums=(1, 2, 10))
    return _PAGED_FNS[key]
