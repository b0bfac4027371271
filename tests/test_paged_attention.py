"""Paged decode attention: Pallas kernel vs ref vs flash/naive attention.

Parity sweeps across page sizes, ragged per-slot valid lengths, GQA/MQA
head layouts, and window masks (interpret=True on CPU), plus the fused
append semantics (tail-page scatter, masked-lane drop) and the contiguous-
equivalence property: gathering a slot's pages reproduces exactly what
causal attention over the contiguous KV prefix computes.

Pools are the flat, lane-dense page space ``(P', page, K·D)`` the serving
arena uses: several layers share it, layer ``l`` reached by offsetting the
block table by ``l·P``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import ops, ref
from repro.models.layers import attention_ref

RNG = np.random.default_rng(11)

# jit the op entry points once per (shape, impl, window) — eager pallas_call
# re-traces every invocation, which would dominate the test wall clock
import functools


@functools.partial(jax.jit, static_argnames=("impl", "window"))
def _paged(q, kp, vp, tables, lengths, *, impl, window=None):
    return ops.paged_attention(q, kp, vp, tables, lengths, window=window,
                               impl=impl)


@functools.partial(jax.jit, static_argnames=("impl", "window"))
def _append(q, k_new, v_new, kp, vp, tables, lengths, mask, *, impl,
            window=None):
    return ops.paged_decode_append(q, k_new, v_new, kp, vp, tables, lengths,
                                   append_mask=mask, impl=impl, window=window)


def t(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


def make_pool(b, max_len, kh, d, ps, dtype=jnp.float32):
    """Disjoint per-slot page chains over a shuffled pool + ragged lengths."""
    np_slot = -(-max_len // ps)
    pool_pages = b * np_slot + 1            # spare page stays unreferenced
    perm = RNG.permutation(pool_pages)
    tables = jnp.asarray(perm[:b * np_slot].reshape(b, np_slot), jnp.int32)
    kp = t(pool_pages, ps, kh * d, dtype=dtype)
    vp = t(pool_pages, ps, kh * d, dtype=dtype)
    lengths = jnp.asarray(RNG.integers(1, max_len + 1, size=(b,)), jnp.int32)
    return kp, vp, tables, lengths


PAGED_CASES = [
    # B, H, K, D, max_len, ps, window
    (3, 4, 2, 16, 32, 8, None),
    (2, 4, 4, 48, 24, 4, None),      # MHA, unaligned D, tiny pages
    (1, 8, 1, 64, 64, 16, None),     # MQA
    (4, 4, 2, 16, 40, 8, 12),        # sliding window
    (2, 6, 3, 32, 33, 16, None),     # max_len not a page multiple
]


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("b,h,k,d,max_len,ps,win", PAGED_CASES)
def test_paged_kernel_vs_ref(b, h, k, d, max_len, ps, win, dtype):
    q = t(b, h, d, dtype=dtype)
    kp, vp, tables, lengths = make_pool(b, max_len, k, d, ps, dtype=dtype)
    want = _paged(q, kp, vp, tables, lengths, impl="ref", window=win)
    got = _paged(q, kp, vp, tables, lengths, impl="pallas", window=win)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_paged_kernel_vs_ref_bf16():
    """One bf16 sweep — the pool dtype the serving engine actually uses."""
    test_paged_kernel_vs_ref(*PAGED_CASES[0], jnp.bfloat16)


def test_paged_matches_causal_attention_over_contiguous_kv():
    """Scattering contiguous KV into pages and attending through the block
    table reproduces causal attention at the last position — the property
    the engine's paged decode rests on."""
    b, s, h, kh, d, ps = 2, 24, 4, 2, 16, 8
    q = t(b, 1, h, d)
    kc, vc = t(b, s, kh, d), t(b, s, kh, d)
    np_slot = s // ps
    pool_pages = b * np_slot + 1
    kp = jnp.zeros((pool_pages, ps, kh * d))
    vp = jnp.zeros((pool_pages, ps, kh * d))
    tables = np.zeros((b, np_slot), np.int32)
    page = 0
    for bi in range(b):
        for j in range(np_slot):
            kp = kp.at[page].set(kc[bi, j * ps:(j + 1) * ps].reshape(ps, -1))
            vp = vp.at[page].set(vc[bi, j * ps:(j + 1) * ps].reshape(ps, -1))
            tables[bi, j] = page
            page += 1
    lengths = jnp.asarray([s, s - 5], jnp.int32)
    want = jax.vmap(
        lambda qb, kb, vb, lb: attention_ref(
            qb[None], kb[None], vb[None], causal=True, q_offset=lb - 1,
            kv_len=lb)[0])(q, kc, vc, lengths)[:, 0]
    for impl in ("ref", "pallas"):
        got = _paged(q[:, 0], kp, vp, jnp.asarray(tables), lengths,
                     impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_append_writes_tail_page_and_masks_idle_lanes(impl):
    b, h, kh, d, ps, max_len = 3, 4, 2, 16, 8, 32
    q = t(b, h, d)
    kp, vp, tables, _ = make_pool(b, max_len, kh, d, ps)
    lengths = jnp.asarray([0, 9, 31], jnp.int32)     # page starts/middles/ends
    k_new, v_new = t(b, kh, d), t(b, kh, d)
    mask = jnp.asarray([True, False, True])
    o, kp2, vp2 = _append(q, k_new, v_new, kp, vp, tables, lengths, mask,
                          impl=impl)
    for bi, (ln, m) in enumerate(zip([0, 9, 31], [True, False, True])):
        page, off = int(tables[bi, ln // ps]), ln % ps
        if m:
            # the entry's K heads lie head-major along the lanes
            np.testing.assert_array_equal(np.asarray(kp2[page, off]),
                                          np.asarray(k_new[bi]).reshape(-1))
            np.testing.assert_array_equal(np.asarray(vp2[page, off]),
                                          np.asarray(v_new[bi]).reshape(-1))
        else:
            # masked lane: the pool is untouched bitwise
            np.testing.assert_array_equal(np.asarray(kp2[page, off]),
                                          np.asarray(kp[page, off]))
    # active lanes attend over the appended entry: lengths+1 with new pool
    want = _paged(q, kp2, vp2, tables, lengths + 1, impl="ref")
    live = np.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)


def test_append_positions_compose_into_a_decode_chain():
    """Sequentially appending tokens through the fused op reproduces
    attention over the full contiguous history at every step."""
    h, kh, d, ps, steps = 4, 2, 8, 4, 10
    np_slot = -(-steps // ps)
    kp = jnp.zeros((np_slot + 1, ps, kh * d))
    vp = jnp.zeros((np_slot + 1, ps, kh * d))
    tables = jnp.asarray([[0, 1, 2][:np_slot]], jnp.int32)
    # fixed-shape contiguous mirror of the appended history (one compile)
    kc = jnp.zeros((1, steps, kh, d))
    vc = jnp.zeros((1, steps, kh, d))
    oracle = jax.jit(lambda q, kc, vc, kv_len, off: attention_ref(
        q[:, None], kc, vc, causal=False, q_offset=off, kv_len=kv_len)[0, 0])
    for step in range(steps):
        q = t(1, h, d)
        kn, vn = t(1, kh, d), t(1, kh, d)
        kc = kc.at[0, step].set(kn[0])
        vc = vc.at[0, step].set(vn[0])
        lengths = jnp.asarray([step], jnp.int32)
        o, kp, vp = _append(q, kn, vn, kp, vp, tables, lengths, None,
                            impl="pallas")
        want = oracle(q, kc, vc, step + 1, step)
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [3, 4, 6, 9])
def test_ring_block_table_decode_matches_windowed_oracle(window):
    """Decoding through a *ring* block table — ceil(window/ps)+1 entries,
    the tail entry wrapping and old pages recycled — reproduces windowed
    attention over the full contiguous history at every step, for windows
    smaller than (3), equal to (4), and not multiples of (6, 9) the page
    size. This is the layout the serving engine keeps for sliding-window
    configs; page ids are deliberately reused so recycled pages carry
    stale positions the mask must hide."""
    h, kh, d, ps, steps = 4, 2, 8, 4, 14
    r = -(-window // ps) + 1
    ids = r + 2                       # rotating live ids; row `ids` = null
    pools = {impl: (jnp.zeros((ids + 1, ps, kh * d)),
                    jnp.zeros((ids + 1, ps, kh * d)))
             for impl in ("ref", "pallas")}
    tables = np.full((1, r), ids, np.int32)
    kc = jnp.zeros((1, steps, kh, d))     # contiguous mirror of the history
    vc = jnp.zeros((1, steps, kh, d))
    oracle = jax.jit(lambda q, kc, vc, n, off: attention_ref(
        q[:, None], kc, vc, causal=False, window=window, q_offset=off,
        kv_len=n)[0, 0])
    for n in range(steps):
        blk = n // ps
        if n % ps == 0:
            # ring install: the entry's previous occupant (block blk - r)
            # is recycled; its page id returns to the rotation
            tables[0, blk % r] = blk % ids
        q = t(1, h, d)
        kn, vn = t(1, kh, d), t(1, kh, d)
        kc, vc = kc.at[0, n].set(kn[0]), vc.at[0, n].set(vn[0])
        want = oracle(q, kc, vc, n + 1, n)
        lengths = jnp.asarray([n], jnp.int32)
        for impl in ("ref", "pallas"):
            kp, vp = pools[impl]
            o, kp, vp = _append(q, kn, vn, kp, vp, jnp.asarray(tables),
                                lengths, None, impl=impl, window=window)
            pools[impl] = (kp, vp)
            np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want),
                                       atol=2e-5, rtol=2e-5,
                                       err_msg=f"{impl} step {n}")


def test_ring_append_wraps_into_the_reused_entry():
    """Past the ring, the fused append lands in the page the wrapped table
    entry points at — offset ``lengths % ps`` of page ``tables[(lengths //
    ps) % R]`` — for both impls."""
    h, kh, d, ps = 4, 2, 8, 4
    window, r = 4, 2
    kp, vp = t(5, ps, kh * d), t(5, ps, kh * d)
    tables = jnp.asarray([[3, 1]], jnp.int32)   # entry 0 now holds block 2
    lengths = jnp.asarray([9], jnp.int32)       # block 2, offset 1 -> entry 0
    q, kn, vn = t(1, h, d), t(1, kh, d), t(1, kh, d)
    for impl in ("ref", "pallas"):
        _, kp2, vp2 = _append(q, kn, vn, kp, vp, tables, lengths, None,
                              impl=impl, window=window)
        np.testing.assert_array_equal(np.asarray(kp2[3, 1]),
                                      np.asarray(kn[0]).reshape(-1))
        np.testing.assert_array_equal(np.asarray(vp2[3, 1]),
                                      np.asarray(vn[0]).reshape(-1))


LAYERS = 3


def layered_arena(b, n_entries, kh, d, ps, dtype=jnp.bfloat16):
    """A flat arena of LAYERS layers, P pages each (every slot's chain plus
    a null page), random bf16 K/V, and one slot-chain table in [0, P)."""
    rows = b * n_entries + 1
    kp = t(LAYERS * rows, ps, kh * d, dtype=dtype)
    vp = t(LAYERS * rows, ps, kh * d, dtype=dtype)
    tables = jnp.asarray(RNG.permutation(rows - 1).reshape(b, n_entries),
                         jnp.int32)
    return kp, vp, tables, rows


@pytest.mark.parametrize("h,kh,window", [
    (32, 8, None),      # granite's heads, contiguous table
    (32, 8, 256),       # ring table of a 256-token sliding window
    (8, 2, None),       # one device's head slice at tp=4
], ids=["contiguous", "window256", "tp4_slice"])
def test_kernel_vs_ref_on_layered_arena(h, kh, window):
    """The kernel reads layer l of the flat arena through tables offset by
    l·P, as the step hands them: it agrees with ref there, and ref there
    agrees with ref on that layer's pages cut out as a pool of their own."""
    b, d, ps, max_len = 2, 64, 16, 1024
    n_entries = -(-max_len // ps) if window is None else -(-window // ps) + 1
    kp, vp, tables, rows = layered_arena(b, n_entries, kh, d, ps)
    q = t(b, h, d, dtype=jnp.bfloat16)
    lengths = jnp.asarray([700, 1000], jnp.int32)
    layer = 1
    at = tables + layer * rows
    want = _paged(q, kp, vp, at, lengths, impl="ref", window=window)
    got = _paged(q, kp, vp, at, lengths, impl="pallas", window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)
    own = slice(layer * rows, (layer + 1) * rows)
    alone = _paged(q, kp[own], vp[own], tables, lengths, impl="ref",
                   window=window)
    np.testing.assert_array_equal(np.asarray(alone, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("layer", range(LAYERS))
def test_append_at_layer_changes_only_its_rows(layer):
    """An append through tables offset by l·P writes rows of layer l only,
    one row a live slot; a masked slot writes nothing."""
    b, kh, d, ps = 3, 2, 16, 8
    kp, vp, tables, rows = layered_arena(b, 4, kh, d, ps, dtype=jnp.float32)
    lengths = jnp.asarray([0, 13, 30], jnp.int32)
    mask = jnp.asarray([True, True, False])
    q, k_new, v_new = t(b, 4, d), t(b, kh, d), t(b, kh, d)
    _, kp2, vp2 = _append(q, k_new, v_new, kp, vp, tables + layer * rows,
                          lengths, mask, impl="ref")
    for before, after in ((kp, kp2), (vp, vp2)):
        changed = np.argwhere(np.any(np.asarray(before) != np.asarray(after),
                                     axis=-1))
        want = sorted((layer * rows + int(tables[bi, int(n) // ps]),
                       int(n) % ps) for bi, n in enumerate(lengths[:2]))
        assert sorted(map(tuple, changed.tolist())) == want


def test_decode_step_appends_each_layer_in_its_own_rows():
    """One paged step of a small model writes, in every layer's rows, the
    tail position of each live slot, and no other row of the arena."""
    from repro import configs
    from repro.models import registry
    from repro.sharding import params as P

    cfg = configs.smoke("granite_3_2b")
    params = P.init_tree(registry.decls(cfg), jax.random.key(0))
    rows, ps = 9, 4
    kp, vp = registry.paged_pool_init(cfg, rows, ps)
    assert kp.shape == (cfg.n_layers * rows, ps,
                        cfg.n_kv_heads * cfg.resolved_head_dim)
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    lengths = jnp.asarray([5, 10], jnp.int32)
    toks = jnp.asarray([3, 7], jnp.int32)
    _, kp2, vp2 = jax.jit(
        lambda p, k, v: registry.decode_step_paged(
            p, cfg, k, v, tables, lengths, toks))(params, kp, vp)
    want = sorted((layer * rows + int(tables[bi, int(n) // ps]), int(n) % ps)
                  for layer in range(cfg.n_layers)
                  for bi, n in enumerate(lengths))
    for after in (kp2, vp2):
        changed = np.argwhere(np.any(np.asarray(after) != 0, axis=-1))
        assert sorted(map(tuple, changed.tolist())) == want


def test_xaif_registers_paged_attention():
    from repro.core.xaif import REGISTRY

    assert "pallas" in REGISTRY.impls("paged_attention")
    spec = REGISTRY.get("paged_attention", "pallas")
    assert any(p.name == "block_table" for p in spec.master_ports)
    assert spec.power_domain is not None
