"""Kernel bench harness: autotune sweep + committed BENCH_kernels.json.

Two modes, mirroring the scheduler bench contract:

  python benchmarks/bench_kernels.py --tune
      Sweep the block-size candidates from ``repro.kernels.autotune`` per
      bench point, pick the winner by (analytic roofline fraction, then
      measured wall) and write ``src/repro/kernels/autotune_table.json``.
      A developer-machine step, like refreshing wall baselines.

  python benchmarks/bench_kernels.py
      Run every bench point through the *real* ``ops.py`` dispatch (so the
      committed autotune table is exercised end to end) and write the
      ``BENCH_kernels.json`` snapshot that ``check_bench.py --snapshot
      kernels`` gates in CI.

On this CPU-only harness wall-times are interpret/XLA numbers — regression
tracking only, gated locally and skipped by ``--no-wall`` in CI.  What IS
machine-independent (and therefore exact-gated on every PR) is everything
derived analytically from the chosen blocks: per-kernel FLOPs, HBM bytes,
and the achieved-vs-roofline fraction built from ``benchmarks/roofline.py``
terms — plus the max numeric error against ``kernels/ref.py``, which must
stay within each point's documented tolerance.  If the committed autotune
table and the committed snapshot disagree on the chosen blocks, the exact
comparison fails: that is the table-consistency gate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "src"))
from roofline import TARGET_KIND, chip_peaks               # noqa: E402
from repro.kernels import autotune, ops, ref                # noqa: E402
from repro.models.attention import (                        # noqa: E402
    decode_attention_ref, write_kv_cache)
from repro.serve.paged import (                             # noqa: E402
    paged_decode_attention, paged_write_kv, PagedKVCache)

REPO_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir)
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_kernels.json")

# the analytic terms price the tiles at the peaks of the chip the blocks
# are tuned for
PEAK_FLOPS = chip_peaks(TARGET_KIND)["bf16_flops"]
HBM_BW = chip_peaks(TARGET_KIND)["hbm_bw"]

# documented parity tolerances per dtype (max |kernel - ref| elementwise;
# asserted in tests/test_kernels_autotune.py and re-gated per snapshot)
TOL = {"bfloat16": 3e-2, "float32": 3e-5}
RMSNORM_TOL = {"bfloat16": 2e-2, "float32": 1e-5}


def _timeit(fn, *args, reps: int = 3) -> float:
    jax.tree.leaves(fn(*args))[0].block_until_ready()       # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.tree.leaves(fn(*args))[0].block_until_ready()
    return (time.perf_counter() - t0) / reps


# ---------------------------------------------------------------------------
# Analytic roofline terms (deterministic functions of shape + blocks)
# ---------------------------------------------------------------------------

def _visited_tiles(Sp: int, bq: int, bk: int, causal: bool) -> int:
    """k tiles the flash grid actually enters (causal skips above-diagonal
    tiles via pl.when — see flash_attention.py)."""
    n_q, n_k = Sp // bq, Sp // bk
    if not causal:
        return n_q * n_k
    return sum(min(n_k, ((iq + 1) * bq - 1) // bk + 1) for iq in range(n_q))


def flash_analytics(B: int, H: int, S: int, D: int, dtype, *, causal: bool,
                    bq: int, bk: int, Sp: int) -> Dict[str, float]:
    """FLOPs / HBM bytes of the tiled kernel vs the useful minimum.

    roofline_frac = (time the useful work needs at peak) / (time the actual
    tiled work needs at peak), taking the binding term of each: tile waste
    (masked/padded lanes, k/v re-reads per q row) pushes it below 1.
    """
    db = jnp.dtype(dtype).itemsize
    ebq, ebk = autotune.effective_flash_blocks(Sp, bq, bk)
    tiles = _visited_tiles(Sp, ebq, ebk, causal)
    flops = 4.0 * B * H * D * ebq * ebk * tiles
    # q resident across the k loop; k/v re-read per visited tile; o written
    # once per q row
    hbm = db * B * H * D * (2.0 * Sp + 2.0 * ebk * tiles)
    pairs = S * (S + 1) / 2 if causal else float(S) * S
    useful_flops = 4.0 * B * H * D * pairs
    useful_hbm = db * B * H * D * 4.0 * S
    bound = max(flops / PEAK_FLOPS, hbm / HBM_BW)
    ideal = max(useful_flops / PEAK_FLOPS, useful_hbm / HBM_BW)
    return {"flops": flops, "hbm_bytes": hbm,
            "roofline_frac": ideal / bound}


def rmsnorm_analytics(N: int, D: int, dtype,
                      rows: int) -> Dict[str, float]:
    db = jnp.dtype(dtype).itemsize
    flops = 4.0 * N * D                      # square, mean-acc, rsqrt-mul, w
    hbm = db * 2.0 * N * D + 4.0 * D         # x in, y out, w once
    bound = max(flops / PEAK_FLOPS, hbm / HBM_BW)
    ideal = max(flops / PEAK_FLOPS, (db * 2.0 * N * D) / HBM_BW)
    return {"flops": flops, "hbm_bytes": hbm,
            "roofline_frac": ideal / bound}


def decode_analytics(B: int, H: int, S: int, HD: int, KV: int, dtype,
                     lengths: List[int], page: int) -> Dict[str, float]:
    db = jnp.dtype(dtype).itemsize
    flops = 4.0 * B * H * S * HD             # scores + pv over full pages
    hbm = db * (2.0 * B * S * KV * HD + 2.0 * B * H * HD)
    useful = sum(lengths)
    useful_flops = 4.0 * H * HD * float(useful)
    useful_hbm = db * (2.0 * KV * HD * float(useful) + 2.0 * B * H * HD)
    bound = max(flops / PEAK_FLOPS, hbm / HBM_BW)
    ideal = max(useful_flops / PEAK_FLOPS, useful_hbm / HBM_BW)
    return {"flops": flops, "hbm_bytes": hbm,
            "roofline_frac": ideal / bound}


# ---------------------------------------------------------------------------
# Bench points
# ---------------------------------------------------------------------------

FLASH_POINTS = (
    # name, B, H, S, D, dtype, causal
    ("flash_b1h2s512d128_bf16", 1, 2, 512, 128, jnp.bfloat16, True),
    ("flash_b1h2s384d64_f32", 1, 2, 384, 64, jnp.float32, True),   # ragged
)
RMSNORM_POINTS = (
    ("rmsnorm_4096x1024_bf16", 4096, 1024, jnp.bfloat16),
    ("rmsnorm_1000x512_f32", 1000, 512, jnp.float32),              # ragged
)
# paged decode: B, H, S(cache), HD, KV, dtype, per-seq lengths
DECODE_POINT = ("decode_b4h8s256d64", 4, 8, 256, 64, 4, jnp.float32,
                [37, 255, 128, 5])


def bench_flash(name: str, B: int, H: int, S: int, D: int, dtype,
                causal: bool, table: autotune.AutotuneTable) -> Dict:
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    # ops.flash_attention takes (B, S, H, D); ref takes (B, H, S, D)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), dtype) for kk in keys)
    bq, bk, Sp, hit = autotune.plan_flash((B, H, S, D), dtype, causal=causal,
                                          table=table)
    with autotune.override(table):
        def run(q, k, v):
            return ops.flash_attention(q, k, v, causal=causal,
                                       interpret=True)
        o = run(q, k, v)
        wall = _timeit(run, q, k, v)
    r = ref.attention_ref(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                          causal=causal)
    err = float(jnp.max(jnp.abs(o.transpose(0, 2, 1, 3).astype(jnp.float32)
                                - r.astype(jnp.float32))))
    out = {"block_q": bq, "block_k": bk, "padded_s": Sp,
           "from_table": bool(hit), "max_err": err,
           "tol": TOL[jnp.dtype(dtype).name], "wall_s": wall}
    out.update(flash_analytics(B, H, S, D, dtype, causal=causal,
                               bq=bq, bk=bk, Sp=Sp))
    return out


def bench_rmsnorm(name: str, N: int, D: int, dtype,
                  table: autotune.AutotuneTable) -> Dict:
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (N, D), dtype)
    w = jnp.ones((D,), jnp.float32)
    rows, hit = autotune.plan_rmsnorm((N, D), dtype, table=table)
    with autotune.override(table):
        def run(x, w):
            return ops.rmsnorm(x, w, backend="interpret")
        y = run(x, w)
        wall = _timeit(run, x, w)
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                - ref.rmsnorm_ref(x, w)
                                .astype(jnp.float32))))
    out = {"block_rows": rows, "from_table": bool(hit), "max_err": err,
           "tol": RMSNORM_TOL[jnp.dtype(dtype).name], "wall_s": wall}
    out.update(rmsnorm_analytics(N, D, dtype, rows))
    return out


def bench_decode(name: str, B: int, H: int, S: int, HD: int, KV: int,
                 dtype, lengths: List[int],
                 table: autotune.AutotuneTable) -> Dict:
    page, hit = autotune.plan_decode_page((B, H, S, HD), dtype, table=table)
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(keys[0], (B, H, HD), dtype)
    kc = jax.random.normal(keys[1], (B, S, KV, HD), dtype)
    vc = jax.random.normal(keys[2], (B, S, KV, HD), dtype)
    kn = jax.random.normal(keys[3], (B, KV, HD), dtype)
    vn = jax.random.normal(keys[4], (B, KV, HD), dtype)
    ln = jnp.asarray(lengths, jnp.int32)

    # dense oracle: append + attend on the contiguous cache
    kc2, vc2 = write_kv_cache(kc, vc, kn, vn, ln)
    o_ref = decode_attention_ref(q, kc2, vc2, ln + 1)

    # paged run: scatter the same cache into pages through block tables
    cache = PagedKVCache(num_pages=2 * B * (S // page), page_size=page,
                         num_kv_heads=KV, head_dim=HD,
                         pages_per_seq=S // page, dtype=dtype)
    for b in range(B):
        cache.reserve(b)
    bt = cache.block_tables(range(B))
    k_pages = cache.k_pages.at[bt.reshape(-1)].set(
        kc.reshape(B * (S // page), page, KV, HD))
    v_pages = cache.v_pages.at[bt.reshape(-1)].set(
        vc.reshape(B * (S // page), page, KV, HD))
    k_pages, v_pages = paged_write_kv(k_pages, v_pages, kn, vn, bt, ln)

    def run(q, k_pages, v_pages, bt, ln):
        return paged_decode_attention(q, k_pages, v_pages, bt, ln + 1)

    o = run(q, k_pages, v_pages, bt, ln)
    wall = _timeit(run, q, k_pages, v_pages, bt, ln)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - o_ref.astype(jnp.float32))))
    out = {"page_size": page, "from_table": bool(hit), "max_err": err,
           "tol": 0.0,                 # paged == dense bit-for-bit
           "wall_s": wall}
    out.update(decode_analytics(B, H, S, HD, KV, dtype,
                                [x + 1 for x in lengths], page))
    return out


# ---------------------------------------------------------------------------
# Tune sweep
# ---------------------------------------------------------------------------

def tune(table_path: str) -> autotune.AutotuneTable:
    """Populate the autotune table: per bench point, score every candidate
    by analytic roofline fraction, tie-break on measured wall."""
    table = autotune.AutotuneTable()
    for name, B, H, S, D, dtype, causal in FLASH_POINTS:
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), dtype) for kk in keys)
        scored = []
        for bq, bk, Sp in autotune.flash_candidates(S, causal=causal):
            probe = autotune.AutotuneTable()
            probe.record("flash_attention", dtype, (B, H, S, D), (bq, bk))
            with autotune.override(probe):
                wall = _timeit(lambda q, k, v: ops.flash_attention(
                    q, k, v, causal=causal, interpret=True), q, k, v)
            frac = flash_analytics(B, H, S, D, dtype, causal=causal,
                                   bq=bq, bk=bk, Sp=Sp)["roofline_frac"]
            scored.append((-frac, wall, bq, bk))
            print(f"{name}: bq={bq} bk={bk} Sp={Sp} "
                  f"frac={frac:.3f} wall={wall * 1e6:.0f}us")
        _, _, bq, bk = min(scored)
        table.record("flash_attention", dtype, (B, H, S, D), (bq, bk))
        print(f"{name}: chose bq={bq} bk={bk}")
    for name, N, D, dtype in RMSNORM_POINTS:
        x = jax.random.normal(jax.random.PRNGKey(1), (N, D), dtype)
        w = jnp.ones((D,), jnp.float32)
        scored = []
        for rows in autotune.rmsnorm_candidates(N):
            probe = autotune.AutotuneTable()
            probe.record("rmsnorm", dtype, (N, D), (rows,))
            with autotune.override(probe):
                wall = _timeit(lambda x, w: ops.rmsnorm(
                    x, w, backend="interpret"), x, w)
            frac = rmsnorm_analytics(N, D, dtype, rows)["roofline_frac"]
            scored.append((-frac, wall, rows))
            print(f"{name}: rows={rows} frac={frac:.3f} "
                  f"wall={wall * 1e6:.0f}us")
        _, _, rows = min(scored)
        table.record("rmsnorm", dtype, (N, D), (rows,))
        print(f"{name}: chose rows={rows}")
    name, B, H, S, HD, KV, dtype, lengths = DECODE_POINT
    scored = []
    for page in autotune.decode_page_candidates(S):
        probe = autotune.AutotuneTable()
        probe.record("decode_attention", dtype, (B, H, S, HD), (page,))
        res = bench_decode(name, B, H, S, HD, KV, dtype, lengths, probe)
        scored.append((-res["roofline_frac"], res["wall_s"], page))
        print(f"{name}: page={page} frac={res['roofline_frac']:.3f} "
              f"wall={res['wall_s'] * 1e6:.0f}us")
    _, _, page = min(scored)
    table.record("decode_attention", dtype, (B, H, S, HD), (page,))
    print(f"{name}: chose page={page}")
    table.save(table_path)
    print(f"wrote {table_path} ({len(table.entries)} entries)")
    return table


# ---------------------------------------------------------------------------
# Snapshot
# ---------------------------------------------------------------------------

def snapshot(table_path: str) -> Dict:
    table = autotune.AutotuneTable.load(table_path)
    kernels: Dict[str, Dict] = {}
    for name, B, H, S, D, dtype, causal in FLASH_POINTS:
        kernels[name] = bench_flash(name, B, H, S, D, dtype, causal, table)
    for name, N, D, dtype in RMSNORM_POINTS:
        kernels[name] = bench_rmsnorm(name, N, D, dtype, table)
    name, B, H, S, HD, KV, dtype, lengths = DECODE_POINT
    kernels[name] = bench_decode(name, B, H, S, HD, KV, dtype, lengths,
                                 table)
    return {"bench": "bench_kernels", "table_entries": len(table.entries),
            "kernels": kernels}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tune", action="store_true",
                    help="sweep block candidates and rewrite the autotune "
                         "table instead of snapshotting")
    ap.add_argument("--table", default=autotune.DEFAULT_TABLE_PATH,
                    help="autotune table path")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="snapshot path (default: BENCH_kernels.json)")
    args = ap.parse_args(argv)
    if args.tune:
        tune(args.table)
        return 0
    snap = snapshot(args.table)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
        f.write("\n")
    for name, res in sorted(snap["kernels"].items()):
        print(f"{name}: frac={res['roofline_frac']:.3f} "
              f"max_err={res['max_err']:.2e} (tol {res['tol']:g}) "
              f"from_table={res['from_table']} "
              f"wall={res['wall_s'] * 1e6:.0f}us")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
