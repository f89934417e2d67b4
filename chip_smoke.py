#!/usr/bin/env python3
"""Run SING's device path once on the chip, through its normal entry points.

    python chip_smoke.py              # one chip: platform, train, serve, cluster
    python chip_smoke.py --chips 4    # 2x2 host: sharded step vs one device

Every phase runs tacc-100m at its full width (12 layers, d_model 768, 12/4
heads, vocab 32768) with random weights and data made from ``--seed``:

  platform  what JAX sees; fails unless the devices are TPUs (no fallback)
  train     ``repro.launch.train.train``: compile time, step time, tokens/s,
            MFU, loss trajectory and peak device memory; the loss must be
            finite and fall; after a checkpoint save and restore, one more
            step from the live and from the restored state must give equal
            losses
  serve     ``repro.launch.serve.serve``: requests through ``ServeEngine``;
            every generated token is checked against a full forward pass
  cluster   ``examples/train_cluster.py``: TACC -> LocalExecutor ->
            jax_train / jax_serve, with one injected node failure and one
            preemption, judged from the jobs' states and logs
  sharded   (``--chips 4`` only) the train step on a 2x2 (data, model) mesh
            against the same step on one device, same init, same batches

Each phase prints its own ``[phase]`` lines. The last line of stdout is one
JSON object, printed only when every phase passed; any failure exits
non-zero. Everything runs in this one process (a chip belongs to one
process at a time), and JAX is touched only after ``repro.runtime`` has set
up the platform and the compile cache.
"""
import argparse
import json
import math
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, d) for d in ("src", "benchmarks",
                                                "examples")]

from repro import runtime  # noqa: E402  (fails when run outside the repo)

ARCH = "tacc-100m"
# serve: the largest (max |full-forward logit| - logit of the engine's
# token) over the row's max |logit| that bf16 prefill/decode may show.
# Prefill+decode and the full forward round differently in bf16; on a CPU
# the worst logit differs by ~1.3% of the row's max.
SERVE_TOL = 0.05
# sharded vs one device, per step: the loss within one bf16 rounding
# (2**-8) and the gradient norm within ten
LOSS_RTOL = 2.0 ** -8
GNORM_RTOL = 10 * 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def phase_platform(chips: int) -> dict:
    """The devices JAX sees, as the last line reports them."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("platform", f"jax {jax.__version__}, devices {devs}")
    say("platform", f"platform={dev['platform']} kind={dev['kind']} "
                    f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX runs on {dev['platform']!r}; this smoke never falls "
          f"back to another platform")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    return dev


def _peak_bytes(device) -> str:
    """The peak counters (and the limit) of ``device.memory_stats()``; they
    cover the whole process so far, not one phase."""
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return ", ".join(f"{k} {v} B" for k, v in sorted(stats.items())
                     if k.startswith("peak") or k == "bytes_limit")


def phase_train(cfg, *, steps: int, batch: int, seq: int, seed: int,
                workdir: str) -> None:
    import jax
    import numpy as np
    from repro.ckpt import Checkpointer
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import train
    from roofline import chip_peaks, model_flops

    device = jax.devices()[0]
    mesh = make_local_mesh([device])
    ckpt = os.path.join(workdir, "train_ckpt")
    say("train", f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
                 f"batch {batch} x seq {seq}, {steps} steps, mesh "
                 f"{dict(mesh.shape)}")
    r = train(cfg, mesh, steps=steps, global_batch=batch, seq_len=seq,
              seed=seed, ckpt_dir=ckpt, ckpt_every=steps, log_every=5,
              log=lambda m: say("train", m))
    losses = [m["loss"] for m in r.metrics]
    say("train", f"compile {r.compile_s:.3f} s (AOT lower+compile)")
    say("train", f"first step {r.first_step_s:.4f} s, steady step "
                 f"{r.steady_step_s:.4f} s (block_until_ready, mean of "
                 f"{steps - 1})")
    tok_s = r.tokens_per_step / r.steady_step_s
    say("train", f"tokens/s {tok_s:.1f}")
    flops = model_flops(cfg, ShapeConfig("smoke", seq, batch, "train"))
    if device.platform == "tpu":
        peak = chip_peaks(device.device_kind)["bf16_flops"]
        say("train", f"MFU {flops / r.steady_step_s / peak:.4f} "
                     f"({flops:.4e} model FLOP/step: 6*N_active + "
                     f"unembedding, attention not counted; peak "
                     f"{peak:.4g} FLOP/s for {device.device_kind})")
    say("train", f"device memory: {_peak_bytes(device)}")
    say("train", "loss " + " ".join(f"{x:.4f}" for x in losses))
    check(all(math.isfinite(x) for x in losses), "non-finite loss")

    t0 = time.perf_counter()
    restored, man = Checkpointer(ckpt).restore(shardings=r.state_shardings)
    jax.block_until_ready(restored)
    say("train", f"restored checkpoint step {man['step']} in "
                 f"{time.perf_counter() - t0:.3f} s")
    check(man["step"] == steps, f"checkpoint at step {man['step']}")
    # one more step on batch 0 again: its loss (taken before the update)
    # must have fallen since step 1 saw the same batch — batch-to-batch
    # noise cannot hide or fake the fall
    b = r.batch(0)
    _, m_live = r.step_fn(r.state, b)
    _, m_back = r.step_fn(restored, b)
    live, back = (float(np.asarray(m["loss"])) for m in (m_live, m_back))
    say("train", f"step {steps + 1} (batch 0 again) from live state: loss "
                 f"{live!r}; from restored checkpoint: loss {back!r}")
    check(live == back, "restored state does not continue like the live one")
    check(math.isfinite(live) and live < losses[0],
          f"loss on batch 0 did not fall: {losses[0]:.4f} at step 1, "
          f"{live:.4f} after {steps} steps")


def _forward_gaps(cfg, params, results, max_seq: int, rows: int):
    """Per generated token: (max logit - logit of the engine's token) over
    the row's max |logit|, and whether the token is the argmax, from one
    full forward pass over prompt + generated tokens (teacher forcing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.transformer import train_logits

    @jax.jit
    def score(params, toks, pos, chosen):
        logits, _ = train_logits(cfg, params, {"tokens": toks})
        at = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        top = at.max(-1)
        got = jnp.take_along_axis(at, chosen[:, :, None], axis=-1)[..., 0]
        return (top - got) / jnp.abs(at).max(-1), got == top

    n_new = max(len(r.tokens) for r in results)
    gaps, hits = [], []
    for i in range(0, len(results), rows):
        chunk = results[i:i + rows]
        toks = np.zeros((rows, max_seq), np.int32)
        pos = np.zeros((rows, n_new), np.int32)
        chosen = np.zeros((rows, n_new), np.int32)
        for j, r in enumerate(chunk):
            seq = list(r.prompt) + list(r.tokens[:-1])
            toks[j, :len(seq)] = seq
            pos[j, :len(r.tokens)] = len(r.prompt) - 1 + np.arange(
                len(r.tokens))
            chosen[j, :len(r.tokens)] = r.tokens
        g, h = (np.asarray(a) for a in score(params, toks, pos, chosen))
        for j, r in enumerate(chunk):
            gaps.extend(g[j, :len(r.tokens)])
            hits.extend(h[j, :len(r.tokens)])
    return np.asarray(gaps), np.asarray(hits)


def phase_serve(cfg, *, requests: int, prompt_len: tuple, max_batch: int,
                max_seq: int, max_new: int, seed: int) -> None:
    import jax
    import numpy as np
    from repro.launch.serve import serve, synthetic_prompts
    from repro.models import init_params, model_defs

    params = init_params(model_defs(cfg), jax.random.PRNGKey(seed))
    prompts = synthetic_prompts(cfg, requests, prompt_len[0], prompt_len[1],
                                seed)
    say("serve", f"{cfg.name}: {requests} requests, prompts "
                 f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
                 f"max_new {max_new}, max_batch {max_batch}, max_seq "
                 f"{max_seq}")
    r = serve(cfg, params, prompts, max_batch=max_batch, max_seq=max_seq,
              max_new=max_new)
    ttft, tpot = np.asarray(r.ttft_s()), np.asarray(r.tpot_s())
    say("serve", f"warm-up request (compiles prefill + decode) "
                 f"{r.warmup_s:.3f} s")
    say("serve", f"{len(r.results)} requests, {r.tokens} tokens in "
                 f"{r.wall_s:.4f} s: {r.tokens / r.wall_s:.1f} tokens/s")
    say("serve", f"time to first token (queueing included): p50 "
                 f"{np.median(ttft):.4f} s, max {ttft.max():.4f} s")
    say("serve", f"time per output token: p50 {np.median(tpot):.5f} s, "
                 f"max {tpot.max():.5f} s")
    check(len(r.results) == requests, f"{len(r.results)} results")
    check(all(len(g.tokens) == max_new for g in r.results),
          "a request ended early")
    gaps, hits = _forward_gaps(cfg, params, r.results, max_seq, max_batch)
    say("serve", f"prefill+decode vs full forward over {gaps.size} tokens: "
                 f"max relative logit gap {gaps.max():.5f} (tolerance "
                 f"{SERVE_TOL}), argmax agrees on {hits.mean():.3f}")
    check(bool(gaps.max() <= SERVE_TOL),
          f"engine token {gaps.max():.4f} below the full-forward max")


def phase_cluster(*, train_entry: dict, urgent_entry: dict, steps: int,
                  ckpt_every: int, fail_at: int, urgent_at: int,
                  workdir: str) -> None:
    from train_cluster import check_outcome, run_cluster

    t0 = time.perf_counter()
    svc, jid, uid, injected = run_cluster(
        os.path.join(workdir, "tacc"), train_entry=train_entry,
        urgent_entry=urgent_entry, steps=steps, ckpt_every=ckpt_every,
        fail_at=fail_at, urgent_at=urgent_at,
        log=lambda m: say("cluster", m.strip()))
    say("cluster", f"all jobs ended in {time.perf_counter() - t0:.1f} s")
    for row in svc.status():
        say("cluster", str(row))
    for line in svc.logs(jid, tail=40):
        say("cluster", f"{jid}: {line.rstrip()}")
    check_outcome(svc, jid, uid, injected)


def phase_sharded(cfg, *, chips: int, steps: int, batch: int, seq: int,
                  seed: int) -> None:
    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import train

    runs = {}
    for name, devices in (("sharded", jax.devices()[:chips]),
                          ("one-device", jax.devices()[:1])):
        mesh = make_local_mesh(devices)
        r = train(cfg, mesh, steps=steps, global_batch=batch, seq_len=seq,
                  seed=seed, log_every=steps, log=lambda m: None)
        say("sharded", f"{name}: mesh {dict(mesh.shape)}, compile "
                       f"{r.compile_s:.3f} s, steady step "
                       f"{r.steady_step_s:.4f} s, device memory: "
                       f"{_peak_bytes(devices[0])}")
        runs[name] = r.metrics
        del r
    for i, (a, b) in enumerate(zip(runs["sharded"], runs["one-device"])):
        dl = abs(a["loss"] - b["loss"]) / abs(b["loss"])
        dg = abs(a["grad_norm"] - b["grad_norm"]) / abs(b["grad_norm"])
        say("sharded", f"step {i + 1}: loss {a['loss']!r} vs {b['loss']!r} "
                       f"(rel {dl:.2e}), grad_norm {a['grad_norm']!r} vs "
                       f"{b['grad_norm']!r} (rel {dg:.2e})")
        check(dl <= LOSS_RTOL, f"step {i + 1} loss differs by {dl:.2e}")
        check(dg <= GNORM_RTOL, f"step {i + 1} grad_norm differs by {dg:.2e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-one-device train step")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = runtime.init_from_env()
    import jax
    from repro.configs import get_config

    cache_use = {"requests": 0, "hits": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache_use["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache_use["hits"] += 1

    jax.monitoring.register_event_listener(count)
    say("platform", f"compile cache {cache}")
    try:
        dev = phase_platform(args.chips)
    except SmokeFailure as e:
        say("platform", f"FAILED: {e}")
        return 1

    cfg = get_config(ARCH)
    with tempfile.TemporaryDirectory() as work:
        if args.chips > 1:
            phases = [("sharded", lambda: phase_sharded(
                cfg, chips=args.chips, steps=5, batch=8, seq=2048,
                seed=args.seed))]
        else:
            phases = [
                ("train", lambda: phase_train(
                    cfg, steps=20, batch=8, seq=2048, seed=args.seed,
                    workdir=work)),
                ("serve", lambda: phase_serve(
                    cfg, requests=16, prompt_len=(64, 513), max_batch=8,
                    max_seq=1024, max_new=32, seed=args.seed)),
                ("cluster", lambda: phase_cluster(
                    train_entry={"arch": ARCH, "smoke": False,
                                 "global_batch": 8, "seq_len": 1024,
                                 "lr": 3e-4, "seed": args.seed},
                    urgent_entry={"arch": ARCH, "smoke": False,
                                  "max_batch": 4, "max_seq": 256,
                                  "max_new": 8, "seed": args.seed},
                    steps=90, ckpt_every=25, fail_at=30, urgent_at=50,
                    workdir=work)),
            ]
        failed = []
        for name, run in phases:
            t0 = time.perf_counter()
            try:
                run()
                verdict = "passed"
            except Exception:
                traceback.print_exc()
                failed.append(name)
                verdict = "FAILED"
            say(name, f"{verdict} in {time.perf_counter() - t0:.1f} s")
    say("platform", f"compile cache: {cache_use['requests']} compilations "
                    f"looked up, {cache_use['hits']} hits")
    if failed:
        say("platform", f"FAILED phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
