"""Computation-environment configuration for reproducible runs.

One place for the platform knobs that otherwise end up scattered across
shell wrappers and bench preambles: JAX platform/precision/device-count
selection (must happen before the first JAX computation), and the
process-environment hygiene a many-worker host needs — tcmalloc preload,
single-threaded BLAS/XLA per worker, quiet TF/absl logging.  The scheduler
bench's parallel runner (``bench_scheduler.py --workers``) builds every
worker's environment from :func:`worker_env` / :func:`configure_worker`, so
a multi-policy/multi-seed sweep is reproducibly configured no matter which
host it lands on.

Two idioms are deliberately followed here: the ``config.py`` helper-module
shape (set platform / x64 / cpu-device-count before touching JAX) and the
``run.sh`` env block of many-process JAX training hosts
(``LD_PRELOAD=libtcmalloc``, ``xla_force_host_platform_device_count``,
``TF_CPP_MIN_LOG_LEVEL``) — see SNIPPETS.md.  All JAX imports are deferred
and failure-gated: the simulator and benches are pure Python and must work
on a box with no usable accelerator stack.
"""
from __future__ import annotations

import gc
import os
import warnings
from multiprocessing import cpu_count
from typing import Dict, Optional

# <checkout>/.jax_cache: this file is <checkout>/src/repro/runtime.py
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

# well-known tcmalloc locations, most specific first (HomebrewNLP's run.sh
# preloads the Debian/Ubuntu path; conda ships its own)
_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def _merge_xla_flag(flags: str, flag: str) -> str:
    """Append ``flag`` (``--name=value``) to an XLA_FLAGS string, replacing
    any existing setting of the same ``--name``."""
    name = flag.split("=", 1)[0]
    kept = [f for f in flags.split() if not f.startswith(name + "=")
            and f != name]
    return " ".join(kept + [flag])


def force_host_device_count(n: int,
                            env: Optional[Dict[str, str]] = None) -> int:
    """Expose ``n`` XLA host-platform devices, however many physical cores
    exist (they are *virtual* devices — the dry-run forces 512 to lower
    production meshes on a laptop).  Only effective before JAX initializes
    its backends; mutates ``os.environ`` unless an explicit ``env`` dict is
    given.  Returns the count set."""
    tgt = os.environ if env is None else env
    tgt["XLA_FLAGS"] = _merge_xla_flag(
        tgt.get("XLA_FLAGS", ""),
        f"--xla_force_host_platform_device_count={int(n)}")
    return int(n)


def set_cpu_device_count(n: int, env: Optional[Dict[str, str]] = None) -> int:
    """Expose ``n`` XLA host-platform devices for *compute* workers, clamped
    to the host's cores (one device per core — oversubscription is the
    dry-run's business, see :func:`force_host_device_count`)."""
    total = cpu_count()
    if n > total:
        warnings.warn(f"only {total} CPUs available; using {total}", Warning)
        n = total
    return force_host_device_count(n, env)


def ensure_platform_env(platform: str = "cpu",
                        env: Optional[Dict[str, str]] = None) -> None:
    """Default ``JAX_PLATFORMS`` before jax initializes.  A setdefault: an
    explicit user/CI choice always wins (the test suite pins ``cpu`` so
    collection never trips over a half-configured accelerator)."""
    tgt = os.environ if env is None else env
    tgt.setdefault("JAX_PLATFORMS", platform)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache lives
    at the fixed :data:`DEFAULT_COMPILE_CACHE`, so a second run from the
    same checkout finds what the first one compiled (a directory that moves
    between runs never hits).  Call before the first compilation: JAX
    decides once, at its first compile, whether the cache is in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_COMPILE_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_from_env() -> str:
    """Entrypoint hook for ``launch/`` mains and ``chip_smoke.py``: apply the
    env-driven platform knobs (``REPRO_HOST_DEVICES``, ``REPRO_PLATFORM``,
    ``REPRO_X64``) and turn on the persistent compilation cache, whose
    directory it returns.  Must run before the first jax computation; pure
    env-var work happens first so the jax-touching knobs see it."""
    n = os.environ.get("REPRO_HOST_DEVICES")
    if n:
        force_host_device_count(int(n))
    platform = os.environ.get("REPRO_PLATFORM")
    if platform:
        set_platform(platform)
    x64 = os.environ.get("REPRO_X64")
    if x64 is not None:
        enable_x64(x64.lower() not in ("", "0", "false"))
    return enable_compile_cache()


def set_platform(platform: str = "cpu") -> None:
    """Select the JAX backend (``cpu`` / ``gpu`` / ``tpu``).  Only takes
    effect at the beginning of the program."""
    import jax
    jax.config.update("jax_platform_name", platform)


def enable_x64(use_x64: bool = True) -> None:
    """Toggle 64-bit default precision for JAX arrays."""
    import jax
    jax.config.update("jax_enable_x64", bool(use_x64))


def tcmalloc_path() -> Optional[str]:
    """The first installed tcmalloc shared object, or None.  Preloading it
    (``LD_PRELOAD``) speeds up allocation-heavy many-process hosts; it can
    only be applied to *child* processes (the loader reads LD_PRELOAD at
    exec time), which is why :func:`worker_env` sets it for bench workers
    rather than the current process."""
    for p in _TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def worker_env(worker_threads: int = 1,
               base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The environment for one bench worker process.

    Workers are independent single-threaded simulations, so each one is
    pinned to one BLAS/XLA/OpenMP thread and one XLA host device — N
    workers then saturate N cores without oversubscription — and the noisy
    TF/absl logging that would interleave across the pool is silenced.
    Returns a full environment dict (a copy of ``base`` or ``os.environ``
    with the overrides applied)."""
    env = dict(os.environ if base is None else base)
    t = str(max(1, int(worker_threads)))
    env["OMP_NUM_THREADS"] = t
    env["OPENBLAS_NUM_THREADS"] = t
    env["MKL_NUM_THREADS"] = t
    env["TF_CPP_MIN_LOG_LEVEL"] = "4"             # no dataset warnings
    env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = "60000000000"
    tc = tcmalloc_path()
    if tc:
        env["LD_PRELOAD"] = tc                    # faster malloc
    set_cpu_device_count(max(1, int(worker_threads)), env)
    return env


def configure_worker(gc_generational: bool = False) -> None:
    """Process-level setup at the top of a bench worker, before any heavy
    work: apply the :func:`worker_env` thread pins to this process (for
    libraries not yet loaded) and tune the allocator for one giant
    simulation graph.  With ``gc_generational`` False the cyclic collector
    is disabled — a year-scale replay builds millions of long-lived
    objects whose repeated gen-2 scans dominate wall (the PR 5 gc fix,
    promoted from between-run ``gc.collect`` calls to whole-run isolation);
    each worker process exits afterwards, so nothing leaks."""
    for k, v in worker_env().items():
        if k == "LD_PRELOAD":
            continue          # exec-time only; meaningless mid-process
        os.environ[k] = v
    if not gc_generational:
        gc.collect()
        gc.freeze()           # baseline objects out of every future scan
        gc.disable()
