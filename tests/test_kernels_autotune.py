"""Autotune-table dispatch: parity, fallback bit-identity, paged decode.

Documented parity tolerances (mirrored in benchmarks/bench_kernels.py):
flash attention max |kernel - ref| <= 3e-2 (bfloat16) / 3e-5 (float32);
rmsnorm <= 2e-2 (bfloat16) / 1e-5 (float32); the paged decode path must be
*bit-identical* to the dense cache path (same values, same eager ops).
The no-entry fallback is pinned harder than a tolerance: with an empty
table, ops.flash_attention must produce byte-for-byte the legacy fixed
512x512 kernel output.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref
from repro.kernels.flash_attention import flash_attention_tpu
from repro.models.attention import decode_attention_ref, write_kv_cache
from repro.serve.paged import (PagedKVCache, gather_paged_kv,
                               paged_decode_attention, paged_write_kv)

FLASH_TOL = {jnp.bfloat16: 3e-2, jnp.float32: 3e-5}
RMSNORM_TOL = {jnp.bfloat16: 2e-2, jnp.float32: 1e-5}


def _qkv(B, S, H, D, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(kk, (B, S, H, D), dtype) for kk in keys)


def _flash_ref(q, k, v, causal):
    return ref.attention_ref(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                             causal=causal).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Table mechanics
# ---------------------------------------------------------------------------

def test_shape_bucket_pow2_except_last_dim():
    assert autotune.shape_bucket((1, 2, 384, 64)) == (1, 2, 512, 64)
    assert autotune.shape_bucket((3, 5, 512, 128)) == (4, 8, 512, 128)
    assert autotune.shape_bucket((1000, 512)) == (1024, 512)


def test_table_roundtrip_and_lookup(tmp_path):
    t = autotune.AutotuneTable()
    t.record("flash_attention", jnp.bfloat16, (1, 2, 500, 128), (256, 512))
    t.record("rmsnorm", jnp.float32, (1000, 512), (128,))
    p = tmp_path / "table.json"
    t.save(str(p))
    loaded = autotune.AutotuneTable.load(str(p))
    # any shape in the same pow2 bucket resolves to the same entry
    assert loaded.lookup("flash_attention", jnp.bfloat16,
                         (1, 2, 300, 128)) == (256, 512)
    assert loaded.lookup("rmsnorm", jnp.float32, (700, 512)) == (128,)
    assert loaded.lookup("rmsnorm", jnp.float32, (700, 256)) is None
    # deterministic serialization: same entries -> same bytes
    t.save(str(tmp_path / "again.json"))
    assert p.read_text() == (tmp_path / "again.json").read_text()


def test_missing_table_file_is_empty_table(tmp_path):
    t = autotune.AutotuneTable.load(str(tmp_path / "nope.json"))
    assert t.entries == {}


def test_committed_table_is_loadable_and_well_formed():
    table = autotune.AutotuneTable.load()
    for key, blocks in table.entries.items():
        kernel = key.split("|")[0]
        assert kernel in ("flash_attention", "rmsnorm", "decode_attention")
        assert all(isinstance(b, int) and b > 0 for b in blocks)


def test_plan_flash_fallback_when_no_entry():
    empty = autotune.AutotuneTable()
    plan = autotune.plan_flash((1, 2, 384, 64), jnp.float32, causal=True,
                               table=empty)
    assert plan == (*autotune.FLASH_DEFAULT, 384, False)


def test_plan_flash_rejects_oversized_padding():
    # entry tuned elsewhere in the bucket: 384 -> pad 512 is 1.33x > limit
    t = autotune.AutotuneTable()
    t.record("flash_attention", jnp.float32, (1, 2, 384, 64), (256, 256))
    plan = autotune.plan_flash((1, 2, 384, 64), jnp.float32, causal=True,
                               table=t)
    assert plan == (*autotune.FLASH_DEFAULT, 384, False)
    # non-causal can never pad, even within the limit
    t.record("flash_attention", jnp.float32, (1, 2, 448, 64), (256, 256))
    plan = autotune.plan_flash((1, 2, 448, 64), jnp.float32, causal=False,
                               table=t)
    assert plan == (*autotune.FLASH_DEFAULT, 448, False)
    # causal within the limit pads
    plan = autotune.plan_flash((1, 2, 448, 64), jnp.float32, causal=True,
                               table=t)
    assert plan == (256, 256, 512, True)


def test_flash_candidates_pruning():
    causal = autotune.flash_candidates(448, causal=True)
    for bq, bk, Sp in causal:
        assert Sp % bq == 0 and Sp % bk == 0
        assert Sp <= 448 * autotune.PAD_OVERHEAD_LIMIT
    assert any(Sp > 448 for _, _, Sp in causal)        # padded ones exist
    # non-causal: only exactly-dividing candidates survive
    for bq, bk, Sp in autotune.flash_candidates(448, causal=False):
        assert Sp == 448 and 448 % bq == 0 and 448 % bk == 0


# ---------------------------------------------------------------------------
# Dispatch parity
# ---------------------------------------------------------------------------

def test_empty_table_is_bit_identical_to_legacy():
    """The acceptance-criteria pin: no table entry -> byte-for-byte the
    fixed 512x512 path (here shrunk to S=256 by the kernel, as before)."""
    q, k, v = _qkv(1, 256, 2, 64, jnp.bfloat16, seed=3)
    with autotune.override(autotune.AutotuneTable()):
        o = ops.flash_attention(q, k, v, causal=True, interpret=True)
    dq, dk = autotune.FLASH_DEFAULT
    legacy = flash_attention_tpu(
        *(a.transpose(0, 2, 1, 3) for a in (q, k, v)), causal=True,
        block_q=dq, block_k=dk, interpret=True).transpose(0, 2, 1, 3)
    assert np.array_equal(np.asarray(o), np.asarray(legacy))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,blocks", [(256, (128, 128)),   # divides
                                      (448, (256, 256))])  # ragged -> pad
def test_autotuned_flash_parity(dtype, S, blocks):
    B, H, D = 1, 2, 64
    t = autotune.AutotuneTable()
    t.record("flash_attention", dtype, (B, H, S, D), blocks)
    q, k, v = _qkv(B, S, H, D, dtype, seed=S)
    with autotune.override(t):
        assert autotune.plan_flash((B, H, S, D), dtype, causal=True)[3]
        o = ops.flash_attention(q, k, v, causal=True, interpret=True)
    r = _flash_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=FLASH_TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_autotuned_rmsnorm_parity(dtype):
    N, D = 1000, 512                                   # ragged row count
    t = autotune.AutotuneTable()
    t.record("rmsnorm", dtype, (N, D), (128,))         # shrinks to 8 in-kernel
    x = jax.random.normal(jax.random.PRNGKey(5), (N, D), dtype)
    w = jax.random.normal(jax.random.PRNGKey(6), (D,), jnp.float32)
    with autotune.override(t):
        y = ops.rmsnorm(x, w, backend="interpret")
        yr, sr = ops.rmsnorm_residual(x, x, w, backend="interpret")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref.rmsnorm_ref(x, w), np.float32),
                               atol=RMSNORM_TOL[dtype])
    ry, rs = ref.rmsnorm_residual_ref(x, x, w)
    np.testing.assert_allclose(np.asarray(yr, np.float32),
                               np.asarray(ry, np.float32),
                               atol=RMSNORM_TOL[dtype])
    np.testing.assert_allclose(np.asarray(sr, np.float32),
                               np.asarray(rs, np.float32),
                               atol=RMSNORM_TOL[dtype])


# ---------------------------------------------------------------------------
# Paged decode
# ---------------------------------------------------------------------------

def _paged_setup(B=4, H=8, S=256, HD=64, KV=4, page=64, seed=7):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (B, H, HD), jnp.float32)
    kc = jax.random.normal(keys[1], (B, S, KV, HD), jnp.float32)
    vc = jax.random.normal(keys[2], (B, S, KV, HD), jnp.float32)
    kn = jax.random.normal(keys[3], (B, KV, HD), jnp.float32)
    vn = jax.random.normal(keys[4], (B, KV, HD), jnp.float32)
    ln = jnp.asarray([37, 255, 128, 5][:B], jnp.int32)
    n = S // page
    # deliberately non-identity page mapping: sequences own interleaved,
    # reversed page ids so a stride bug cannot hide behind a layout match
    rng = np.random.RandomState(0)
    ids = rng.permutation(2 * B * n)[:B * n].astype(np.int32)
    bt = jnp.asarray(ids.reshape(B, n))
    k_pages = jnp.zeros((2 * B * n, page, KV, HD), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)
    k_pages = k_pages.at[bt.reshape(-1)].set(kc.reshape(B * n, page, KV, HD))
    v_pages = v_pages.at[bt.reshape(-1)].set(vc.reshape(B * n, page, KV, HD))
    return q, kc, vc, kn, vn, ln, bt, k_pages, v_pages


def test_gather_reconstructs_contiguous_cache():
    _, kc, vc, *_, bt, k_pages, v_pages = _paged_setup()
    k, v = gather_paged_kv(k_pages, v_pages, bt)
    assert np.array_equal(np.asarray(k), np.asarray(kc))
    assert np.array_equal(np.asarray(v), np.asarray(vc))


def test_paged_decode_matches_dense_bitwise():
    q, kc, vc, kn, vn, ln, bt, k_pages, v_pages = _paged_setup()
    kc2, vc2 = write_kv_cache(kc, vc, kn, vn, ln)
    o_ref = decode_attention_ref(q, kc2, vc2, ln + 1)
    k_pages, v_pages = paged_write_kv(k_pages, v_pages, kn, vn, bt, ln)
    o = paged_decode_attention(q, k_pages, v_pages, bt, ln + 1)
    assert np.array_equal(np.asarray(o), np.asarray(o_ref))


def test_paged_write_lands_in_the_right_page_slot():
    q, kc, vc, kn, vn, ln, bt, k_pages, v_pages = _paged_setup()
    page = k_pages.shape[1]
    k_pages, _ = paged_write_kv(k_pages, v_pages, kn, vn, bt, ln)
    for b, pos in enumerate(np.asarray(ln)):
        pid = int(np.asarray(bt)[b, pos // page])
        got = np.asarray(k_pages)[pid, pos % page]
        np.testing.assert_array_equal(got, np.asarray(kn)[b])


def test_paged_kv_cache_lifecycle_is_deterministic():
    def drive():
        c = PagedKVCache(num_pages=8, page_size=64, num_kv_heads=2,
                         head_dim=32, pages_per_seq=2)
        c.reserve("a")
        c.reserve("b")
        c.release("a")
        c.reserve("c")            # must reuse a's pages, LIFO
        return {s: r.tolist() for s, r in c.tables.items()}, c.free_pages

    t1, f1 = drive()
    t2, f2 = drive()
    assert t1 == t2 and f1 == f2 == 4
    # lowest ids first, and released pages return LIFO: "c" re-claims
    # "a"'s pages in the original order
    assert t1["b"] == [2, 3]
    assert t1["c"] == [0, 1]


def test_paged_kv_cache_exhaustion_and_double_reserve():
    c = PagedKVCache(num_pages=2, page_size=64, num_kv_heads=2, head_dim=32,
                     pages_per_seq=2)
    c.reserve("a")
    with pytest.raises(ValueError):
        c.reserve("a")
    with pytest.raises(RuntimeError):
        c.reserve("b")
    c.release("a")
    c.reserve("b")                 # pool recovered


def test_paged_cache_end_to_end_slot_lifecycle():
    """Admit / decode / retire through PagedKVCache, checking against the
    dense oracle at every decode step (the serve-engine usage pattern)."""
    B, H, S, HD, KV, page = 2, 4, 128, 32, 2, 64
    cache = PagedKVCache(num_pages=3 * (S // page), page_size=page,
                         num_kv_heads=KV, head_dim=HD,
                         pages_per_seq=S // page)
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(keys[0], (B, H, HD), jnp.float32)
    kn = jax.random.normal(keys[1], (B, KV, HD), jnp.float32)
    vn = jax.random.normal(keys[2], (B, KV, HD), jnp.float32)
    dense_k = jnp.zeros((B, S, KV, HD), jnp.float32)
    dense_v = jnp.zeros_like(dense_k)
    cache.reserve("s0")
    cache.reserve("s1")
    lengths = jnp.zeros((B,), jnp.int32)
    for step in range(3):
        cache.append(["s0", "s1"], kn, vn, lengths)
        dense_k, dense_v = write_kv_cache(dense_k, dense_v, kn, vn, lengths)
        lengths = lengths + 1
        o = cache.attend(["s0", "s1"], q, lengths)
        o_ref = decode_attention_ref(q, dense_k, dense_v, lengths)
        assert np.array_equal(np.asarray(o), np.asarray(o_ref))
    cache.release("s0")
    cache.reserve("s2")            # freed pages immediately reusable


def test_plan_decode_page_fallback():
    empty = autotune.AutotuneTable()
    assert autotune.plan_decode_page((4, 8, 256, 64), jnp.float32,
                                     table=empty) == (128, False)
    # non-dividing cache length falls back to a single page
    assert autotune.plan_decode_page((4, 8, 200, 64), jnp.float32,
                                     table=empty) == (200, False)
    t = autotune.AutotuneTable()
    t.record("decode_attention", jnp.float32, (4, 8, 256, 64), (64,))
    assert autotune.plan_decode_page((4, 8, 256, 64), jnp.float32,
                                     table=t) == (64, True)


# ---------------------------------------------------------------------------
# Snapshot artifact
# ---------------------------------------------------------------------------

def test_committed_kernel_snapshot_parity_within_tolerance():
    """The committed BENCH_kernels.json must already satisfy the
    in-snapshot parity gate CI applies (skips if not generated yet)."""
    import os
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_kernels.json")
    if not os.path.exists(path):
        pytest.skip("no committed kernel snapshot")
    with open(path) as f:
        snap = json.load(f)
    for name, res in snap["kernels"].items():
        assert res["max_err"] <= res["tol"], name
