"""Paged compressed-block pool (DESIGN.md §10).

Four layers of guarantees:

* allocator invariants — alloc/free never double-assign a page, occupancy
  equals live pages x post-compression page bytes, page tables never alias
  across rows (hypothesis property tests);
* storage parity — every decode path (blockwise scan, fused oracle, fused
  Pallas kernel, materializing oracle) reads identical attention out of
  paged arenas and dense rings, including appends, heterogeneous rows, and
  sliding-window ring reuse;
* serving semantics — memory-pressure admission oversubscribes slots past
  the dense reservation, and a forced preemption + prompt replay leaves
  greedy tokens bit-identical to solo decode for raw, packed, and kivi;
* scheduler hygiene — the ljf pop is a direct index scan whose tie-break
  preserves arrival order, and CacheSpec rejects windows the block ring
  cannot represent.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as C
from repro.core import pool
from repro.core.policy import CompressionPolicy, LayerOverride
from repro.kernels import ops
from repro.models import model as M
from repro.models import registry
from repro.models.config import ModelConfig
from repro.serve.scheduler import Request, Server, ServerConfig


@pytest.fixture(autouse=True)
def _drop_executables_per_test():
    """This module compiles a few Servers' worth of executables per test;
    left to accumulate over the whole module, XLA's CPU compiler segfaults
    in a later compile that succeeds in a fresh process (the last test
    here did).  No compiled function is shared between these tests."""
    yield
    jax.clear_caches()


# ---------------------------------------------------------------------------
# CacheSpec / policy validation (satellites)
# ---------------------------------------------------------------------------


def test_cachespec_rejects_window_not_divisible_by_block():
    with pytest.raises(ValueError, match="must divide window"):
        C.CacheSpec(block_size=16, window=40, max_seq=256)
    # regression: divisible windows (and window=None) are untouched
    assert C.CacheSpec(block_size=16, window=32, max_seq=256).n_blocks == 2
    assert C.CacheSpec(block_size=16, max_seq=256).window is None


def test_cachespec_paged_validation():
    with pytest.raises(ValueError, match="pool_pages"):
        C.CacheSpec(mode="paged")
    with pytest.raises(ValueError, match="mode must be"):
        C.CacheSpec(mode="vram")
    spec = C.CacheSpec(mode="paged", pool_pages=12, block_size=16, max_seq=64)
    assert spec.paged and spec.store_blocks == 12 and spec.n_blocks == 4


def test_policy_mode_threads_to_spec_and_dense_twin():
    pol = CompressionPolicy(layout="packed", mode="paged", block_size=16)
    # without a sized pool every consumer gets the dense twin (solo
    # prefills, api.compress, dryrun)
    assert pol.spec_for_layer(0, max_seq=64).mode == "dense"
    spec = pol.spec_for_layer(0, max_seq=64, pool_pages=9)
    assert spec.mode == "paged" and spec.pool_pages == 9
    with pytest.raises(ValueError, match="uniform block_size"):
        CompressionPolicy(mode="paged",
                          overrides=(LayerOverride(layers=(1,), block_size=32),))
    with pytest.raises(ValueError, match="mode must be"):
        CompressionPolicy(mode="hbm")


def test_model_config_cache_mode_threads():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      vocab_size=64, n_heads=2, n_kv_heads=2,
                      cache_mode="paged", cache_block=16)
    assert cfg.compression_policy().mode == "paged"
    assert M.cache_spec(cfg, 64).mode == "dense"  # dense twin without a pool
    assert M.cache_spec(cfg, 64, pool_pages=7).pool_pages == 7


# ---------------------------------------------------------------------------
# Allocator invariants
# ---------------------------------------------------------------------------


def test_pool_alloc_free_basics():
    p = pool.PagedBlockPool(4, (100, 20))
    a = p.alloc(3)
    assert len(set(a)) == 3 and p.free_pages == 1
    assert p.live_bytes == 3 * 120 and p.total_bytes == 4 * 120
    with pytest.raises(pool.PoolExhausted):
        p.alloc(2)
    assert p.free_pages == 1  # failed alloc takes nothing
    assert p.release(a[:1]) == [a[0]]
    assert p.free_pages == 2 and p.high_water == 3
    with pytest.raises(RuntimeError, match="not live"):
        p.release(a[:1])  # double release
    with pytest.raises(RuntimeError, match="not live"):
        p.release([99])  # never allocated
    with pytest.raises(RuntimeError, match="not live"):
        p.retain([99])  # can't retain a dead page either


def test_pool_property_invariants(rng):
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.booleans(), st.integers(0, 5)), max_size=60))
    def run(ops_):
        p = pool.PagedBlockPool(12, (64,))
        held: list[int] = []
        for is_alloc, n in ops_:
            if is_alloc:
                if n <= p.free_pages:
                    got = p.alloc(n)
                    # never double-assign: fresh pages disjoint from held
                    assert not (set(got) & set(held))
                    held += got
                else:
                    with pytest.raises(pool.PoolExhausted):
                        p.alloc(n)
            elif held:
                k = min(n, len(held))
                assert sorted(p.release(held[:k])) == sorted(held[:k])
                held = held[k:]
            # occupancy == sum of live page bytes, conservation holds
            assert p.live_pages == len(held) == len(set(held))
            assert p.live_bytes == len(held) * 64
            assert p.free_pages + p.live_pages == p.n_pages

    run()


def test_page_tables_never_alias_across_rows():
    """Scheduler-shaped workload on the allocator + a page table mirror:
    whatever interleaving of admissions, per-step assignments, and releases
    happens, no two (row, slot) entries may ever share a physical page."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    B, NB = 4, 8

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, B - 1), st.integers(0, NB - 1),
                              st.integers(0, 2)), max_size=80))
    def run(events):
        p = pool.PagedBlockPool(10, (32,))
        table = np.full((B, NB), -1)
        for row, slot, kind in events:
            if kind == 2:  # release the row (retire / preempt)
                held = table[row][table[row] >= 0]
                if len(held):
                    p.release(held.tolist())
                table[row] = -1
            elif table[row, slot] < 0 and p.free_pages:
                table[row, slot] = p.alloc(1)[0]
            live = table[table >= 0]
            assert len(live) == len(set(live.tolist()))  # no aliasing
            assert set(live.tolist()) == p._live
            assert p.live_bytes == len(live) * 32

    run()


def test_page_nbytes_tracks_compression():
    """The admission unit is post-compression bytes: a packed page must be
    far smaller than a raw page of the same block, and differencing the
    layout's own store shapes must match a hand count for packed."""
    mk = lambda layout: C.CacheSpec(layout=layout, block_size=16, max_seq=64,
                                    rel_scale_k=0.05, rel_scale_v=0.15)
    H, D = 2, 16
    raw_b = pool.page_nbytes(mk("raw"), H, D)
    packed_b = pool.page_nbytes(mk("packed"), H, D)
    assert raw_b == 2 * H * 16 * D * 2  # K+V bf16 blocks
    assert packed_b < raw_b / 2
    spec = mk("packed")
    expect = H * 4 * (spec.words_k(D) + spec.words_v(D))  # u32 payload
    expect += H * 2 * 2 * (D + spec.block_size)           # bf16 min/step K+V
    assert packed_b == expect


# ---------------------------------------------------------------------------
# Storage parity: paged arenas vs dense rings on every decode path
# ---------------------------------------------------------------------------


def _mk_kvq(rng, B, Hkv, G, S, D):
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)).astype(np.float32))
    return k, v, q


def _paged_outputs(cache, q):
    outs = {
        "blockwise": C.attend_blockwise(cache, q),
        "materialized": C.attend_materialized(cache, q),
    }
    if cache.spec.impl.supports_fused:
        outs["fused_oracle"] = ops.cache_decode_attention(cache, q, impl="xla")
        outs["fused_pallas"] = ops.cache_decode_attention(cache, q, impl="pallas")
    return outs


@pytest.mark.parametrize("layout", ["raw", "packed", "kivi", "huffman"])
def test_paged_parity_all_backends(layout, rng):
    """A dense cache re-housed under a shuffled page assignment must attend
    identically on every backend (the paged parity suite)."""
    spec = C.CacheSpec(layout=layout, block_size=16, max_seq=128,
                       rel_scale_k=0.02, rel_scale_v=0.05)
    k, v, q = _mk_kvq(rng, 2, 2, 2, 72, 16)
    dense = C.prefill(spec, k, v)
    B, NB = 2, spec.n_blocks
    perm = rng.permutation(B * NB + 3)[: B * NB].reshape(B, NB).astype(np.int32)
    paged = pool.from_dense(dense, B * NB + 3, perm)
    assert paged.spec.paged and paged.k_store.shape[0] == 1
    ref = C.attend_blockwise(dense, q)
    for name, out in _paged_outputs(paged, q).items():
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-3, err_msg=name)
    # the blockwise path reads identical bits through the indirection
    np.testing.assert_array_equal(
        np.asarray(C.attend_blockwise(paged, q)), np.asarray(ref))


def test_paged_parity_heterogeneous_rows(rng):
    """Per-row nb_valid/buf_len + per-row page tables: rows at different
    positions must match their dense twins bit-for-bit per backend."""
    spec = C.CacheSpec(layout="packed", block_size=16, max_seq=256)
    k, v, q = _mk_kvq(rng, 2, 2, 2, 96, 16)
    c40 = C.prefill(spec, k[:, :, :40], v[:, :, :40])
    c96 = C.prefill(spec, k, v)
    mixed = jax.tree.map(lambda a, b: jnp.stack([a[0], b[1]]), c40, c96)
    NB = spec.n_blocks
    perm = rng.permutation(2 * NB).reshape(2, NB).astype(np.int32)
    paged = pool.from_dense(mixed, 2 * NB, perm)
    dense_outs = {
        "blockwise": C.attend_blockwise(mixed, q),
        "materialized": C.attend_materialized(mixed, q),
        "fused_oracle": ops.cache_decode_attention(mixed, q, impl="xla"),
        "fused_pallas": ops.cache_decode_attention(mixed, q, impl="pallas"),
    }
    for name, out in _paged_outputs(paged, q).items():
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(dense_outs[name]), err_msg=name)


@pytest.mark.parametrize("layout", ["raw", "packed"])
def test_paged_append_and_ring_reuse(layout, rng):
    """Decode-time flushes translate through the page table; a sliding
    window wraps its ring by overwriting the slot's page IN PLACE (no new
    allocation), staying bit-identical to the dense ring."""
    spec = C.CacheSpec(layout=layout, block_size=8, max_seq=512, window=32,
                       rel_scale_k=0.02, rel_scale_v=0.05)
    k, v, q = _mk_kvq(rng, 2, 2, 2, 20, 16)
    dense = C.prefill(spec, k, v)
    paged = pool.from_dense(dense, 2 * spec.n_blocks)
    tab_before = np.asarray(paged.page_tab).copy()
    app = jax.jit(C.append)
    for t in range(40):
        kn = jnp.asarray(rng.normal(size=(2, 2, 16)).astype(np.float32))
        vn = jnp.asarray(rng.normal(size=(2, 2, 16)).astype(np.float32))
        dense = app(dense, kn, vn)
        paged = app(paged, kn, vn)
    assert int(dense.n_flushed[0]) > spec.n_blocks  # the ring wrapped
    np.testing.assert_array_equal(np.asarray(paged.page_tab), tab_before)
    np.testing.assert_array_equal(np.asarray(C.attend_blockwise(paged, q)),
                                  np.asarray(C.attend_blockwise(dense, q)))


def test_paged_prefill_rejected_and_to_dense_roundtrip(rng):
    spec = C.CacheSpec(layout="packed", block_size=16, max_seq=64,
                       mode="paged", pool_pages=8)
    with pytest.raises(ValueError, match="dense twin|from_dense"):
        C.prefill(spec, *(_mk_kvq(rng, 1, 2, 1, 40, 16)[:2]))
    dspec = dataclasses.replace(spec, mode="dense", pool_pages=0)
    k, v, q = _mk_kvq(rng, 1, 2, 1, 40, 16)
    dense = C.prefill(dspec, k, v)
    back = pool.to_dense(pool.from_dense(dense, 8))
    assert not back.spec.paged
    np.testing.assert_array_equal(np.asarray(back.k_store)[:, :, :2],
                                  np.asarray(dense.k_store)[:, :, :2])


# ---------------------------------------------------------------------------
# Serving: admission, oversubscription, preemption (model-backed)
# ---------------------------------------------------------------------------

LENS = (7, 13, 16, 24, 33)
NEWS = (3, 9, 5, 2, 7)


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("yi_6b")
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32) for L in LENS]
    return cfg, params, prompts


def _solo_greedy(cfg, params, prompt, n_new):
    # B=1 block-chunked prefill (the unified admission semantics: chunks
    # attend earlier blocks compressed, as decode will) + greedy decode.
    prompt = np.asarray(prompt, np.int32)
    T = M.cache_specs(cfg, 256)[0].block_size
    state = M.init_decode_state(cfg, 1, 256)
    lg, pos = None, 0
    while pos < len(prompt):
        C = min(T, len(prompt) - pos)
        lg, state = M.prefill_chunk(params, cfg,
                                    jnp.asarray(prompt[None, pos:pos + C]),
                                    jnp.int32(pos), state)
        pos += C
    cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    out = [int(cur[0])]
    while len(out) < n_new:
        lg, state = M.decode_step(params, cfg, cur,
                                  jnp.asarray(pos, jnp.int32), state)
        cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        out.append(int(cur[0]))
        pos += 1
    return out


def _pool_page_bytes(cfg, max_seq=256):
    specs = M.cache_specs(cfg, max_seq)
    return sum(pool.page_nbytes(s, cfg.n_kv_heads, cfg.resolved_head_dim)
               for s in specs), specs[0]


@pytest.mark.parametrize("layout", ["raw", "packed"])
def test_paged_server_mid_flight_matches_solo(setup, layout):
    """The scheduler suite's core contract on paged storage: mixed prompt
    lengths/budgets through few slots, every request bit-identical to its
    solo run, pool fully drained at the end."""
    cfg, params, prompts = setup
    cfg = dataclasses.replace(cfg, cache_layout=layout, cache_block=8)
    server = Server(cfg, params,
                    ServerConfig(max_slots=2, max_seq=256, cache_mode="paged"),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(Request(prompt=p, max_new_tokens=n))
               for p, n in zip(prompts, NEWS)]
    server.run()
    for p, n, h in zip(prompts, NEWS, handles):
        assert h.result().tokens.tolist() == _solo_greedy(cfg, params, p, n), \
            (layout, len(p), n)
    st = server.stats()
    assert st["pool"]["pages_live"] == 0  # every retirement freed its pages
    assert st["pool"]["bytes_live"] == 0


@pytest.mark.parametrize("layout", ["raw", "packed", "kivi"])
def test_preempt_and_resume_bit_identity(setup, layout):
    """A pool too small for the admitted load forces a preemption; the
    preempted request replays its prompt on re-admission and its greedy
    tokens stay bit-identical to a solo run (the acceptance contract)."""
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, cache_layout=layout, cache_block=8)
    page_b, spec0 = _pool_page_bytes(cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, 17).astype(np.int32)
               for _ in range(3)]
    # 5 pages: two requests admit (2 prefill pages + headroom each), their
    # decode flushes exhaust the pool, the youngest gets preempted.
    server = Server(cfg, params,
                    ServerConfig(max_slots=3, max_seq=256, cache_mode="paged",
                                 pool_hbm_bytes=5 * page_b),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(Request(prompt=p, max_new_tokens=10))
               for p in prompts]
    server.run()
    assert server.preemptions >= 1, "workload failed to force a preemption"
    for p, h in zip(prompts, handles):
        assert h.result().tokens.tolist() == _solo_greedy(cfg, params, p, 10)
    assert server.stats()["pool"]["pages_live"] == 0


def test_streaming_survives_preemption(setup):
    """handle.tokens() across a preemption: the regenerated prefix is
    identical, so the stream continues seamlessly."""
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
    page_b, _ = _pool_page_bytes(cfg)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, 17).astype(np.int32)
               for _ in range(3)]
    server = Server(cfg, params,
                    ServerConfig(max_slots=3, max_seq=256, cache_mode="paged",
                                 pool_hbm_bytes=5 * page_b),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(Request(prompt=p, max_new_tokens=10))
               for p in prompts]
    streamed = [list(h.tokens()) for h in handles]
    assert server.preemptions >= 1
    for s, h in zip(streamed, handles):
        assert s == h.result().tokens.tolist() and len(s) == 10


def _device_page_tables(server):
    """Every layer's device page table as a host [B, NB] array."""
    kv = server.state["kv"]
    caches = kv if isinstance(kv, (tuple, list)) else (kv,)
    tabs = []
    for c in caches:
        pt = np.asarray(c.page_tab)
        tabs.extend(pt if pt.ndim == 3 else [pt])  # layer-stacked or single
    return tabs


def test_same_sweep_preemption_drops_stale_page_assignment(setup):
    """Regression: a row granted a page early in an ``_ensure_pages`` sweep
    can be preempted LATER in the same sweep — a younger zero-page row
    exhausts the pool and the victim scan picks the youngest page HOLDER,
    which is the older, already-recorded row.  Its freed page is re-issued
    (LIFO) to the younger row; the stale triple must not re-point the
    cleared device row at it, or the vacated slot's garbage flush lands in
    the other request's live page this very step."""
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
    page_b, _ = _pool_page_bytes(cfg)
    rng = np.random.default_rng(17)
    pa = rng.integers(0, cfg.vocab_size, 14).astype(np.int32)  # 1 prefill page
    pb = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)   # 0 prefill pages
    # 2 pages: both admit together (A takes one for its prompt block), and
    # one decode step later both hit a flush boundary in the SAME sweep
    # (pos 15 and 7).  A — older, visited first — takes the last free page;
    # B holds zero pages, so the victim scan preempts A and the LIFO free
    # list hands B the exact page A was just granted.
    server = Server(cfg, params,
                    ServerConfig(max_slots=2, max_seq=256, cache_mode="paged",
                                 pool_hbm_bytes=2 * page_b),
                    q_chunk=32, kv_chunk=32)
    ha = server.submit(Request(prompt=pa, max_new_tokens=6))
    hb = server.submit(Request(prompt=pb, max_new_tokens=6))
    while server.step():
        # the device tables must mirror the host accounting at every step:
        # under the bug, A's cleared device row resurrects with the stale
        # (row, slot, page) triple pointing into B's page
        for tab in _device_page_tables(server):
            np.testing.assert_array_equal(tab, server._pt_host)
    assert server.preemptions >= 1, "workload failed to force the same-sweep case"
    assert ha.result().tokens.tolist() == _solo_greedy(cfg, params, pa, 6)
    assert hb.result().tokens.tolist() == _solo_greedy(cfg, params, pb, 6)
    assert server.stats()["pool"]["pages_live"] == 0


def test_paged_admits_more_than_dense_at_same_budget(setup):
    """The capacity claim: at one fixed byte budget, paged admission holds
    >= 1.5x the concurrent requests of dense full-ring reservation for a
    compressing layout."""
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
    page_b, spec0 = _pool_page_bytes(cfg)
    budget = 2 * spec0.n_blocks * page_b  # exactly two dense reservations
    dense_slots = budget // (spec0.n_blocks * page_b)
    assert dense_slots == 2
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 17).astype(np.int32),
                    max_new_tokens=8) for _ in range(8)]
    server = Server(cfg, params,
                    ServerConfig(max_slots=len(reqs), max_seq=256,
                                 cache_mode="paged", pool_hbm_bytes=budget),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(r) for r in reqs]
    peak = 0
    while server.step():
        peak = max(peak, server.active)
    assert peak >= 1.5 * dense_slots, (peak, dense_slots)
    for r, h in zip(reqs, handles):
        assert h.result().tokens.tolist() == _solo_greedy(
            cfg, params, r.prompt, r.max_new_tokens)


def test_submit_rejects_request_larger_than_pool(setup):
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
    page_b, _ = _pool_page_bytes(cfg)
    server = Server(cfg, params,
                    ServerConfig(max_slots=2, max_seq=256, cache_mode="paged",
                                 pool_hbm_bytes=3 * page_b),
                    q_chunk=32, kv_chunk=32)
    with pytest.raises(ValueError, match="pool"):
        server.submit(Request(prompt=np.zeros(64, np.int32), max_new_tokens=32))
    # a request that fits the pool is accepted
    server.submit(Request(prompt=np.zeros(9, np.int32), max_new_tokens=4))
    # exact block boundary: the final generated token is never appended, so
    # prompt + max_new = 32 peaks at 31 entries = 3 pages, filling the pool
    # exactly — admissible solo (the old off-by-one rejected it)
    server.submit(Request(prompt=np.zeros(25, np.int32), max_new_tokens=7))


def test_server_stats_shape(setup):
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
    server = Server(cfg, params,
                    ServerConfig(max_slots=2, max_seq=256, cache_mode="paged"),
                    q_chunk=32, kv_chunk=32)
    st = server.stats()
    assert st["cache_mode"] == "paged" and st["preemptions"] == 0
    pl = st["pool"]
    assert pl["pages_free"] == pl["pages_total"] and pl["bytes_live"] == 0
    assert pl["bytes_total"] == pl["pages_total"] * pl["bytes_per_page"]
    assert len(pl["bytes_live_by_layer"]) == cfg.n_layers
    dense = Server(cfg, params, ServerConfig(max_slots=2, max_seq=256),
                   q_chunk=32, kv_chunk=32)
    assert dense.stats()["cache_mode"] == "dense"
    assert "pool" not in dense.stats()


# ---------------------------------------------------------------------------
# Scheduler hygiene: the ljf pop (satellite)
# ---------------------------------------------------------------------------


def test_pop_next_ljf_tie_break_preserves_arrival_order(setup):
    cfg, params, _ = setup
    server = Server(cfg, params,
                    ServerConfig(max_slots=1, max_seq=256, policy="ljf"),
                    q_chunk=32, kv_chunk=32)
    budgets = [3, 5, 2, 5, 5, 4]
    handles = [server.submit(Request(prompt=np.zeros(4, np.int32),
                                     max_new_tokens=b)) for b in budgets]
    order = [server._pop_next() for _ in range(len(budgets))]
    # max budget first; equal budgets leave in arrival order; rest follow
    assert [h.request.max_new_tokens for h in order] == [5, 5, 5, 4, 3, 2]
    assert order[0] is handles[1] and order[1] is handles[3]
    assert order[2] is handles[4]
    assert not server._queue


def test_pop_next_fcfs_is_fifo(setup):
    cfg, params, _ = setup
    server = Server(cfg, params, ServerConfig(max_slots=1, max_seq=256),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(Request(prompt=np.zeros(4, np.int32),
                                     max_new_tokens=b)) for b in (2, 9, 3)]
    assert [server._pop_next() for _ in range(3)] == handles


@pytest.mark.parametrize("layout", ["raw", "packed", "kivi", "huffman"])
def test_chunked_vs_solo_admission_bit_identity_paged(setup, layout):
    """Bit-identity matrix, paged leg: the fused encode-to-page chunk loop
    (chunks quantize straight into pooled pages through a live-arena view)
    must match the blocking solo drain token for token on every layout."""
    cfg, params, prompts = setup
    cfg = dataclasses.replace(cfg, cache_layout=layout, cache_block=8)
    outs = {}
    for mode in ("chunked", "solo"):
        server = Server(cfg, params,
                        ServerConfig(max_slots=2, max_seq=256,
                                     cache_mode="paged", prefill_mode=mode,
                                     prefill_chunk_tokens=8),
                        q_chunk=32, kv_chunk=32)
        hs = [server.submit(Request(prompt=p, max_new_tokens=n))
              for p, n in zip(prompts[:3], NEWS[:3])]
        server.run()
        outs[mode] = [h.result().tokens.tolist() for h in hs]
        st = server.stats()
        assert st["prefill"]["mode"] == mode
        assert st["pool"]["pages_live"] == 0  # drained either way
    assert outs["chunked"] == outs["solo"]


def test_preempt_half_prefilled_row_resumes(setup):
    """A PREFILLING row can lose its pages mid-chunking: an older decoder
    holds part of a pool the long prompt needs, the chunk loop's page
    reclaim preempts the (younger) half-prefilled row itself, and its
    re-admission must still produce solo-identical tokens."""
    cfg, params, _ = setup
    cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
    page_b, _ = _pool_page_bytes(cfg)
    rng = np.random.default_rng(23)
    short = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    long = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)  # 5 blocks
    server = Server(cfg, params,
                    ServerConfig(max_slots=2, max_seq=64, cache_mode="paged",
                                 pool_hbm_bytes=5 * page_b,
                                 prefill_chunk_tokens=8),
                    q_chunk=32, kv_chunk=32)
    h_short = server.submit(Request(prompt=short, max_new_tokens=16))
    server.step()  # the short decoder admits first (it is the OLDER row)
    h_long = server.submit(Request(prompt=long, max_new_tokens=4))
    server.run()
    pf = server.stats()["prefill"]
    assert pf["prefill_preemptions"] >= 1, \
        "workload failed to preempt a half-prefilled row"
    assert h_short.result().tokens.tolist() == _solo_greedy(cfg, params,
                                                            short, 16)
    assert h_long.result().tokens.tolist() == _solo_greedy(cfg, params,
                                                           long, 4)
    assert server.stats()["pool"]["pages_live"] == 0
