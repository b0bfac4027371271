"""Property-based invariant suite for :class:`PagePool` / :class:`PageTable`.

Randomized multi-tenant operation sequences (acquire / publish / adopt /
claim / evict / release / disown) against shadow models, asserting the
allocator invariants the serving stack leans on after **every** operation:

* refcounts never negative, and over-release / retain-while-free raises
  instead of corrupting state;
* the free list and the live-page set partition the pool (disjoint, no
  duplicates, counts sum to ``n_pages``);
* draining every outstanding reference leaks nothing — the pool returns
  to all-free, the table to empty;
* namespaces isolate: operations in one namespace never pin, evict, or
  alias pages of another, and chains stay parent-contiguous through any
  eviction order.

The tests draw a single integer seed from ``hypothesis`` and expand it
into an op sequence with ``random.Random``.
"""

import random

import pytest

from hypothesis import example, given, settings, strategies as st

from repro.serve.paged import PagePool
from repro.serve.pages import PageTable

pytestmark = pytest.mark.properties


# ---------------------------------------------------------------------------
# PagePool


def _check_pool(pool: PagePool, model_refs: dict[int, int]) -> None:
    """The allocator invariants, checked against the shadow model."""
    live = pool.refcounts()
    assert live == {i: r for i, r in model_refs.items() if r > 0}
    assert all(r > 0 for r in live.values())           # never negative/zero
    free = pool._free
    assert len(free) == len(set(free))                 # no duplicate frees
    assert set(free).isdisjoint(live)                  # free ∩ live = ∅
    assert pool.in_use + pool.free_count == pool.n_pages
    assert pool.in_use == len(live)
    assert sum(pool.owners().values()) == pool.in_use  # tenant tags cover
    assert pool.stats["high_water"] >= pool.in_use


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_pool_random_multi_tenant_ops_preserve_invariants(seed):
    """A random interleave of alloc/retain/release across three tenants
    keeps the free list and refcounts consistent at every step, raises on
    every misuse, and drains back to all-free with zero leaked pages."""
    rng = random.Random(seed)
    pool = PagePool(n_pages=rng.randint(4, 24), page_size=8)
    refs: dict[int, int] = {}
    tenants = ("a", "b", None)
    for _ in range(rng.randint(30, 120)):
        live = [i for i, r in refs.items() if r > 0]
        op = rng.random()
        if op < 0.40 and pool.free_count:
            idx = pool.alloc(owner=rng.choice(tenants))
            assert refs.get(idx, 0) == 0               # was genuinely free
            refs[idx] = 1
        elif op < 0.55 and live:
            idx = rng.choice(live)                     # cross-tenant adoption
            pool.retain(idx)
            refs[idx] += 1
        elif op < 0.90 and live:
            idx = rng.choice(live)
            pool.release(idx)
            refs[idx] -= 1
        elif op < 0.95:
            # misuse must raise, not corrupt: touch a free page
            dead = [i for i in range(pool.n_pages) if refs.get(i, 0) == 0]
            if dead:
                idx = rng.choice(dead)
                with pytest.raises((ValueError, RuntimeError)):
                    pool.retain(idx)
                with pytest.raises((ValueError, RuntimeError)):
                    pool.release(idx)
        elif pool.free_count == 0:
            with pytest.raises(RuntimeError):
                pool.alloc()
        _check_pool(pool, refs)
    # drain: drop every outstanding reference -> nothing leaks
    for idx, r in refs.items():
        for _ in range(r):
            pool.release(idx)
    assert pool.in_use == 0 and pool.free_count == pool.n_pages
    assert pool.owners() == {}
    assert pool.stats["freed"] == pool.stats["allocated"]


# ---------------------------------------------------------------------------
# PageTable


_PS = 4          # small pages: chains cross page boundaries quickly
_NAMESPACES = ("modelA", "modelB")


def _prompt(ns: str, chain: int, blocks: int) -> tuple:
    """Deterministic per-(ns, chain) token sequence, ``blocks`` pages long.
    Different namespaces reuse the *same* token ids on purpose: equal keys
    across namespaces must still isolate."""
    return tuple((31 * chain + j) % 97 + 1 for j in range(blocks * _PS))


def _check_table(table: PageTable, pins: dict) -> None:
    """Structural invariants over every resident page."""
    pages = table._pages
    for (ns, key), page in pages.items():
        assert page.refs >= 0
        assert len(key) % _PS == 0
        if len(key) > _PS:                 # chains stay parent-contiguous
            assert (ns, key[:-_PS]) in pages, (
                f"orphan page {key} in {ns}: parent evicted under it")
        kids = sum(1 for (n2, k2) in pages
                   if n2 == ns and len(k2) == len(key) + _PS
                   and k2[:len(key)] == key)
        assert page.children == kids
    # every pin we hold is still resident with enough refs to cover us
    held: dict[tuple, int] = {}
    for keys, ns in pins.values():
        for key in keys:
            held[(ns, key)] = held.get((ns, key), 0) + 1
    for loc, n in held.items():
        assert loc in pages and pages[loc].refs >= n
    assert table.resident == len(pages)
    assert table.stats["published"] == table.resident + table.stats["evicted"]


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
@example(seed=9491)  # over-releases a PageTable pin; replayed on every run
def test_table_random_ops_preserve_chains_pins_and_namespaces(seed):
    """Random acquire/publish/adopt/claim/evict/release/clear traffic over
    two namespaces and several chains, with a capacity bound forcing LRU
    pressure: pinned pages and their ancestors survive, chains never
    orphan, evictions fire ``on_evict`` exactly once per page, and a full
    drain empties the table with every payload handed back."""
    rng = random.Random(seed)
    dropped: list = []
    table = PageTable(_PS, capacity_pages=rng.randint(6, 14),
                      on_evict=dropped.append)
    pins: dict[int, tuple[tuple, str]] = {}    # handle -> (keys, ns)
    next_pin = 0
    published = 0
    for _ in range(rng.randint(40, 150)):
        ns = rng.choice(_NAMESPACES)
        chain = rng.randint(0, 2)
        prompt = _prompt(ns, chain, blocks=3)
        op = rng.random()
        if op < 0.35:
            # extend the chain by its next contiguous page (or re-publish)
            blocks = rng.randint(1, 3)
            key = prompt[:blocks * _PS]
            ok = table.publish(key, snapshot=(ns, key), ns=ns)
            if ok:
                published += 1
                assert table.has(key, ns)
            if table.wants(key, ns):
                raise AssertionError("wants() true for a resident page")
        elif op < 0.60:
            # acquire pins the longest resident chain; extra trailing token
            # so the whole prompt is matchable (last token always fed)
            m = table.acquire(prompt + (99,), ns=ns)
            if m is not None:
                assert m.tokens_matched % _PS == 0
                assert m.keys[-1] == prompt[:m.tokens_matched]
                # namespace isolation: payloads carry their origin ns
                assert all(s == (ns, k) for k, s in zip(m.keys, m.chain))
                pins[next_pin] = (m.keys, ns)
                next_pin += 1
        elif op < 0.70:
            # mid-flight adoption of a contiguous block range
            got = table.acquire_range(prompt, 0, rng.randint(1, 3), ns=ns)
            if got:
                pins[next_pin] = (tuple(k for k, _ in got), ns)
                next_pin += 1
        elif op < 0.80 and pins:
            handle = rng.choice(list(pins))
            keys, pns = pins.pop(handle)
            table.release(keys, ns=pns)
        elif op < 0.88:
            table.claim(prompt[:_PS], owner=("eng", chain), ns=ns)
            assert table.claimant(prompt[:_PS], ns=ns) == ("eng", chain)
            table.unclaim(prompt[:_PS], ns=ns)
            assert table.claimant(prompt[:_PS], ns=ns) is None
        elif op < 0.96:
            before = table.resident
            n = table.evict_lru(rng.randint(1, 3),
                                ns=rng.choice((None,) + _NAMESPACES))
            assert table.resident == before - n
        else:
            table.clear()
        _check_table(table, pins)
    # over-release of a drained handle raises instead of going negative
    if pins:
        handle = rng.choice(list(pins))
        keys, pns = pins.pop(handle)
        table.release(keys, ns=pns)
        pages = table._pages
        if any(pages[(pns, k)].refs == 0 for k in keys if (pns, k) in pages):
            with pytest.raises(ValueError):
                table.release(keys, ns=pns)
                table.release(keys, ns=pns)
    # drain every pin, then clear: nothing stays resident, every published
    # page came back through on_evict exactly once
    for keys, pns in pins.values():
        table.release(keys, ns=pns)
    table.clear()
    assert table.resident == 0 and table.pinned == 0
    assert table.stats["published"] == published == table.stats["evicted"]
    assert len(dropped) == published
    assert table.refcounts(None) == {}


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_table_namespace_isolation_under_identical_keys(seed):
    """The same token prefix published in two namespaces: each namespace's
    acquire sees only its own payload, per-namespace eviction never
    crosses, and dropping one namespace leaves the other untouched."""
    rng = random.Random(seed)
    table = PageTable(_PS)
    key = _prompt("", 0, blocks=1)
    for ns in _NAMESPACES:
        assert table.publish(key, snapshot=f"payload-{ns}", ns=ns)
    probe = key + (99,)
    for ns in _NAMESPACES:
        m = table.acquire(probe, ns=ns)
        assert m is not None and m.snapshot == f"payload-{ns}"
        table.release(m.keys, ns=ns)
    assert table.lookup(probe, ns="modelC") == 0       # unknown ns: no match
    victim, survivor = (_NAMESPACES if rng.random() < 0.5
                        else _NAMESPACES[::-1])
    assert table.evict_lru(5, ns=victim) == 1
    assert not table.has(key, victim) and table.has(key, survivor)
    assert table.resident_by_ns() == {survivor: 1}
