"""Serving-engine tests: paged-kernel bit-identity, schedule-invariant
request streams, the block allocator, and the public KV-fmt API.

The determinism contract under test (serving/engine.py module doc): with a
GEMM-identity policy (attention sites + kv_cache_fmt only), a request's
decoded token stream is a pure function of (request seed, prompt,
model) — bit-identical whatever the arrival schedule, slot placement,
page placement, co-tenants, or batch width.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.rounding import parse_spec
from repro.kernels import common
from repro.kernels import flash_attention as FA
from repro.models.model import build_model
from repro.precision import attention as PA
from repro.precision import policy as QP
from repro.serving import BlockAllocator
from repro.serving.engine import (ContinuousBatchingEngine, EngineConfig,
                                  Request)

KEY = jax.random.PRNGKey(13)
WORDS = common.derive_seed(KEY, 0)
SR8 = parse_spec("binary8-sr")
SPECS = FA.AttnSpecs(SR8, SR8, parse_spec("e4m3-sr"))
SITE_TAGS = (QP.TAG_ATTN_QK, QP.TAG_ATTN_AV, QP.TAG_ATTN_OUT)


# ---------------------------------------------------------------- kernel ----
def _paged_fixture(tables, KV=2):
    """A contiguous rounded cache + the same content scattered into pages
    at the placement given by ``tables``."""
    B, n_max = tables.shape
    G, dk, page = 2, 16, 8
    P = max(8, int(tables.max()) + 1)
    smax = page * n_max
    kq = jax.random.fold_in(KEY, 7)
    grid = parse_spec("e4m3-rn")       # lossless under e4m3 packing
    q = jax.random.normal(kq, (B * KV, G, dk), jnp.float32)
    kf = grid(jax.random.normal(jax.random.fold_in(kq, 1),
                                (B * KV, smax, dk)))
    vf = grid(jax.random.normal(jax.random.fold_in(kq, 2),
                                (B * KV, smax, dk)))
    k_pages = np.zeros((P * KV, page, dk), np.float32)
    v_pages = np.zeros((P * KV, page, dk), np.float32)
    for b in range(B):
        for j in range(n_max):
            for h in range(KV):
                row = tables[b, j] * KV + h
                k_pages[row] = np.asarray(kf)[b * KV + h,
                                              j * page:(j + 1) * page]
                v_pages[row] = np.asarray(vf)[b * KV + h,
                                              j * page:(j + 1) * page]
    seeds = PA._site_seeds(WORDS, B * KV, SITE_TAGS)
    return (q, kf, vf, jnp.asarray(k_pages), jnp.asarray(v_pages), seeds,
            dict(B=B, KV=KV, page=page))


LENGTHS = np.array([13, 20], np.int32)
TABLES_A = np.array([[3, 1, 5], [2, 6, 4]], np.int32)
TABLES_B = np.array([[7, 2, 1], [5, 3, 6]], np.int32)


# The lengths the kernel's walk branches on (page 8, 3 pages a request):
# empty, one token, exactly one page, a page plus one, the full table; a
# window shorter than the longest, whose first page then holds no valid
# position.
EDGE_LENGTHS = (0, 1, 8, 9, 24)
TABLES_EDGE = np.array([[4, 11, 2], [9, 1, 14], [6, 15, 3], [12, 7, 10],
                        [5, 13, 8]], np.int32)
WINDOW = 10
PAGED_CASES = [(2, tuple(LENGTHS), 0, None)] + [
    (kv, EDGE_LENGTHS, window, fmt) for kv in (1, 5)
    for window in (0, WINDOW) for fmt in (None, "e4m3")]


def _live(length, page, n_max, window):
    """Logical pages holding a position the decode mask admits."""
    return [j for j in range(n_max)
            if any(k < length and (not window or k > length - 1 - window)
                   for k in range(j * page, (j + 1) * page))]


@pytest.mark.parametrize(
    "n_kv,lengths,window,kv_fmt", PAGED_CASES,
    ids=[f"kv{kv}-len{'.'.join(map(str, n))}-w{w}-{f or 'f32'}"
         for kv, n, w, f in PAGED_CASES])
def test_paged_decode_matches_contiguous_bitwise(n_kv, lengths, window,
                                                 kv_fmt):
    tables = TABLES_A if len(lengths) == 2 else TABLES_EDGE
    q, kf, vf, k_pages, v_pages, seeds, d = _paged_fixture(tables, n_kv)
    B, page = d["B"], d["page"]
    lens_np = np.asarray(lengths, np.int32)
    kw = dict(scale=0.3, window=window, kv_fmt=kv_fmt)

    def pack(x):
        return x if kv_fmt is None else common.pack_block(x, kv_fmt)

    def run():
        lens, tbl = jnp.asarray(lens_np), jnp.asarray(tables)
        kp, vp = pack(k_pages), pack(v_pages)
        o_paged = FA.flash_decode_paged_p(q, kp, vp, seeds, lens, tbl,
                                          SPECS, n_kv=n_kv, **kw)
        o_ref = FA.flash_decode_paged_reference(q, kp, vp, seeds, lens, tbl,
                                                SPECS, n_kv=n_kv, **kw)
        outs = []
        for b in range(B):     # contiguous kernel: one scalar length each
            sl = slice(b * n_kv, (b + 1) * n_kv)
            outs.append(FA.flash_decode_p(q[sl], pack(kf[sl]),
                                          pack(vf[sl]), seeds[sl],
                                          lens_np[b], SPECS, kv_block=page,
                                          **kw))
        return o_paged, o_ref, jnp.concatenate(outs)

    o_paged, o_ref, o_contig = run()
    assert bool(jnp.all(o_paged == o_ref))
    assert bool(jnp.all(o_paged == o_contig))


@pytest.mark.parametrize("window", [0, WINDOW])
@pytest.mark.parametrize("kv_fmt", [None, "e4m3"])
def test_paged_decode_never_reads_dead_pages(window, kv_fmt):
    """Every table entry has a physical page of its own; those outside
    the requests' live ranges (past the length, wholly before the window,
    all of an empty request's) hold poison: NaN, or for packed pages
    e4m3's largest codes (it has no NaN).  The output equals the call
    with those pages zeroed, bit for bit.  A read of NaN in a page before
    the window would turn the output NaN; elsewhere a dead value is
    masked either way."""
    n_kv, page, n_max = 2, 8, 3
    tables = TABLES_EDGE
    q, _, _, k_pages, v_pages, seeds, _ = _paged_fixture(tables, n_kv)
    if kv_fmt is not None:
        k_pages = common.pack_block(k_pages, kv_fmt)
        v_pages = common.pack_block(v_pages, kv_fmt)
    dead = np.ones(k_pages.shape[0], bool)
    for b, n in enumerate(EDGE_LENGTHS):
        for j in _live(n, page, n_max, window):
            dead[tables[b, j] * n_kv:(tables[b, j] + 1) * n_kv] = False
    assert dead.sum() > 2 * n_kv
    poison = (jnp.nan, jnp.nan) if kv_fmt is None else (0x7F, 0xFF)
    kw = dict(scale=0.3, n_kv=n_kv, window=window, kv_fmt=kv_fmt)
    lens, tbl = jnp.asarray(EDGE_LENGTHS, jnp.int32), jnp.asarray(tables)

    def run(kval, vval):
        rows = jnp.asarray(dead)[:, None, None]
        kp = jnp.where(rows, jnp.asarray(kval, k_pages.dtype), k_pages)
        vp = jnp.where(rows, jnp.asarray(vval, v_pages.dtype), v_pages)
        return FA.flash_decode_paged_p(q, kp, vp, seeds, lens, tbl, SPECS,
                                       **kw)

    o_poison, o_zero = run(*poison), run(0, 0)
    assert bool(jnp.all(jnp.isfinite(o_poison)))
    assert bool(jnp.all(o_poison == o_zero))


@pytest.mark.parametrize("window", [0, WINDOW])
def test_paged_kv_block_clamps_to_live_pages(window):
    page, n_max = 8, 3
    lengths = EDGE_LENGTHS + (17,)
    tables = np.arange(1, len(lengths) * n_max + 1,
                       dtype=np.int32).reshape(-1, n_max)
    lens, tbl = jnp.asarray(lengths, jnp.int32), jnp.asarray(tables)
    for b, n in enumerate(lengths):
        live = _live(n, page, n_max, window) or [0]
        blocks = [int(FA.paged_kv_block(b, j, lens, tbl, page=page,
                                        window=window))
                  for j in range(n_max)]
        assert blocks == [tables[b, min(max(j, live[0]), live[-1])]
                          for j in range(n_max)]
        # the pipeline copies a block when its index changes: one copy a
        # live page (one for an empty request, whose block is not used)
        assert 1 + sum(x != y for x, y in zip(blocks, blocks[1:])) == \
            len(live)


def test_paged_decode_packed_and_placement_invariant():
    q, kf, vf, k_pages, v_pages, seeds, d = _paged_fixture(TABLES_A)
    B, KV, page = d["B"], d["KV"], d["page"]
    kw = dict(scale=0.3, window=0)

    @jax.jit
    def run_packed(k_pg, v_pg, tbl):
        kp = common.pack_block(k_pg, "e4m3")
        vp = common.pack_block(v_pg, "e4m3")
        o_paged = FA.flash_decode_paged_p(q, kp, vp, seeds,
                                          jnp.asarray(LENGTHS), tbl, SPECS,
                                          n_kv=KV, kv_fmt="e4m3", **kw)
        outs = []
        for b in range(B):
            sl = slice(b * KV, (b + 1) * KV)
            outs.append(FA.flash_decode_p(
                q[sl], common.pack_block(kf[sl], "e4m3"),
                common.pack_block(vf[sl], "e4m3"), seeds[sl], LENGTHS[b],
                SPECS, kv_fmt="e4m3", kv_block=page, **kw))
        return o_paged, jnp.concatenate(outs)

    o_paged, o_contig = run_packed(k_pages, v_pages, jnp.asarray(TABLES_A))
    assert bool(jnp.all(o_paged == o_contig))

    # same logical content at a different physical placement: the output
    # must not depend on which pages the blocks landed in
    _, _, _, k2, v2, _, _ = _paged_fixture(TABLES_B)
    o_paged2, _ = run_packed(k2, v2, jnp.asarray(TABLES_B))
    assert bool(jnp.all(o_paged == o_paged2))


# --------------------------------------------------------- rounded stores ---
def test_round_kv_request_chunk_and_slot_invariance():
    spec = parse_spec("e4m3-sr")
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 8, 2, 16))
    words = jnp.asarray(
        np.array([[11, 22], [33, 44]], np.uint32))

    whole = PA.round_kv_request(x, spec, words, jnp.zeros(2, jnp.int32))
    lo = PA.round_kv_request(x[:, :4], spec, words, jnp.zeros(2, jnp.int32))
    hi = PA.round_kv_request(x[:, 4:], spec, words,
                             jnp.full((2,), 4, jnp.int32))
    assert bool(jnp.all(whole == jnp.concatenate([lo, hi], axis=1)))

    # slot permutation: each request's rounded values ride with its words,
    # not with its batch row
    perm = PA.round_kv_request(x[::-1], spec, words[::-1],
                               jnp.zeros(2, jnp.int32))
    assert bool(jnp.all(whole == perm[::-1]))


# ---------------------------------------------------------------- engine ----
@pytest.fixture(scope="module")
def served_model():
    pol = QP.make_policy(attn=parse_spec("binary8-sr"),
                         kv_cache_fmt="e4m3-sr")
    cfg = dataclasses.replace(reduced(get_config("tinyllama-1.1b")),
                              gemm_policy=pol)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _requests(cfg, n=5):
    rng = np.random.default_rng(1)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        5 + 3 * i).tolist(),
                    max_new_tokens=3 + i, tenant="ab"[i % 2], seed=100 + i)
            for i in range(n)]


def _run(model, params, reqs, n_slots, pages, arrivals):
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        n_slots=n_slots, page_size=8, total_pages=pages,
        max_pages_per_request=4, prefill_chunk=4, token_budget=8))
    res = eng.run([dataclasses.replace(r) for r in reqs], arrivals=arrivals)
    return {rid: r.tokens for rid, r in res.items()}, eng


def test_engine_streams_bit_identical_across_schedules(served_model):
    model, params = served_model
    reqs = _requests(model.cfg)
    t1, e1 = _run(model, params, reqs, 3, 12, [0, 0, 1, 4, 6])
    t2, e2 = _run(model, params, reqs, 2, 9, [2, 0, 5, 0, 1])
    # different batch widths, page pools, arrival orders, co-tenants —
    # identical per-request token streams, bit for bit
    assert t1 == t2
    assert all(len(t1[r.rid]) == r.max_new_tokens for r in reqs)
    # every page returned to the allocator after completion
    assert e1._alloc.free_pages == 11
    assert e2._alloc.free_pages == 8


def test_engine_single_slot_replay(served_model):
    model, params = served_model
    reqs = _requests(model.cfg, n=3)
    batch, _ = _run(model, params, reqs, 3, 12, [0, 0, 0])
    solo, _ = _run(model, params, reqs, 1, 5, [0, 1, 2])
    assert batch == solo


def test_engine_completes_with_page_pressure(served_model):
    # pool smaller than the aggregate demand: admission must block at the
    # head of the line and recycle freed pages until everyone finishes
    model, params = served_model
    reqs = _requests(model.cfg)
    free_run, _ = _run(model, params, reqs, 3, 12, [0, 0, 1, 4, 6])
    tight, eng = _run(model, params, reqs, 3, 5, [0] * 5)
    assert tight == free_run
    assert eng._alloc.free_pages == 4


def test_engine_submit_validation(served_model):
    model, params = served_model
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        n_slots=2, page_size=8, total_pages=8, max_pages_per_request=2))
    eng.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(Request(rid=1, prompt=[3], max_new_tokens=1))
    with pytest.raises(ValueError):
        eng.submit(Request(rid=2, prompt=[], max_new_tokens=1))
    with pytest.raises(ValueError, match="pages"):
        # needs ceil((20+20)/8) = 5 pages > table width 2
        eng.submit(Request(rid=3, prompt=list(range(1, 21)),
                           max_new_tokens=20))
    assert eng.cancel(1)
    assert not eng.cancel(99)


# ------------------------------------------------------------- allocator ----
def test_block_allocator():
    alloc = BlockAllocator(total_pages=6)
    assert alloc.free_pages == 5          # page 0 is reserved scratch
    a = alloc.alloc(2)
    b = alloc.alloc(3)
    assert a is not None and b is not None
    assert 0 not in a + b
    assert len(set(a + b)) == 5
    assert alloc.alloc(1) is None         # exhausted: caller must wait
    alloc.free(a)
    with pytest.raises(ValueError):
        alloc.free(a)                     # double free
    with pytest.raises(ValueError):
        alloc.free([0])                   # scratch page is never client-owned
    c = alloc.alloc(2)
    assert c is not None and set(c) == set(a)


# ------------------------------------------------------------- public API ---
def test_resolve_kv_cache_fmt():
    assert QP.resolve_kv_cache_fmt(None) is None
    assert QP.resolve_kv_cache_fmt("fp32") is None     # identity -> fp cache
    assert QP.resolve_kv_cache_fmt("e4m3-sr") == "e4m3-sr"
    with pytest.raises(Exception):
        QP.resolve_kv_cache_fmt("not-a-spec")
    pol = QP.policy_with_kv_fmt("binary8-paper", "e4m3-sr")
    assert pol.kv_cache_fmt == "e4m3-sr"
    assert QP.policy_with_kv_fmt(None, None).kv_cache_fmt is None
