"""Rehearse ``chip_smoke.py`` on the CPU: every phase at smoke size, its
refusal to run anywhere but a TPU, and the four-chip comparison on four
forced host devices (in a subprocess, so this process keeps one device)."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import local_mesh_shape  # noqa: E402

CFG = get_config("tacc-100m", smoke=True)


def _run(args, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    e.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=600, env=e)


def test_train_phase(tmp_path, capsys):
    chip_smoke.phase_train(CFG, steps=6, batch=4, seq=32, seed=0,
                           workdir=str(tmp_path))
    out = capsys.readouterr().out
    assert "compile" in out and "tokens/s" in out
    assert "from restored checkpoint" in out


def test_serve_phase(capsys):
    chip_smoke.phase_serve(CFG, requests=5, prompt_len=(4, 20), max_batch=2,
                           max_seq=48, max_new=4, seed=0)
    out = capsys.readouterr().out
    assert "time to first token" in out and "argmax agrees" in out


def test_serve_check_catches_wrong_tokens():
    """The full-forward check fails tokens the model would not pick."""
    from repro.launch.serve import serve, synthetic_prompts
    from repro.models import init_params, model_defs
    params = init_params(model_defs(CFG), jax.random.PRNGKey(0))
    r = serve(CFG, params, synthetic_prompts(CFG, 3, 4, 12, 0), max_batch=2,
              max_seq=32, max_new=4)
    gaps, _ = chip_smoke._forward_gaps(CFG, params, r.results, 32, 2)
    assert gaps.max() <= chip_smoke.SERVE_TOL
    for g in r.results:
        g.tokens = [(t + 1) % CFG.vocab_size for t in g.tokens]
    gaps, hits = chip_smoke._forward_gaps(CFG, params, r.results, 32, 2)
    assert gaps.max() > chip_smoke.SERVE_TOL and not hits.any()


def test_cluster_phase(tmp_path, capsys):
    chip_smoke.phase_cluster(
        train_entry={"arch": "tacc-100m", "smoke": True, "global_batch": 4,
                     "seq_len": 32},
        urgent_entry={"arch": "tacc-100m", "smoke": True, "max_batch": 2,
                      "max_new": 3},
        steps=40, ckpt_every=10, fail_at=12, urgent_at=20,
        workdir=str(tmp_path))
    assert "COMPLETED" in capsys.readouterr().out


def test_cluster_check_reads_jobs_not_exit_codes(tmp_path):
    """A run whose failure was never injected does not pass the check."""
    from train_cluster import check_outcome, run_cluster
    svc, jid, uid, injected = run_cluster(
        str(tmp_path), train_entry={"arch": "tacc-100m", "smoke": True,
                                    "global_batch": 4, "seq_len": 32},
        urgent_entry={"arch": "tacc-100m", "smoke": True, "max_batch": 2,
                      "max_new": 3},
        steps=20, ckpt_every=10, fail_at=10**6, urgent_at=10,
        log=lambda m: None)
    with pytest.raises(RuntimeError, match="0 failures injected"):
        check_outcome(svc, jid, uid, injected)


def test_main_refuses_a_cpu_backend():
    r = _run([os.path.join(ROOT, "chip_smoke.py")])
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path, env={"PATH": os.environ["PATH"]})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_four_chip_comparison_on_host_devices():
    r = _run([os.path.join(ROOT, "tests", "distributed_checks.py"),
              "chip_smoke_sharded"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "mesh {'data': 2, 'model': 2}" in r.stdout
    assert "CHECK chip_smoke_sharded PASSED" in r.stdout


@pytest.mark.parametrize("n,shape", [(1, (1, 1)), (2, (2, 1)), (4, (2, 2)),
                                     (8, (4, 2)), (6, (3, 2))])
def test_local_mesh_shape(n, shape):
    assert local_mesh_shape(n) == shape


_CACHE_PROBE = """
import jax, sys
sys.path.insert(0, {src!r})
from repro import runtime
path = runtime.enable_compile_cache()
print("DIR", path, jax.config.jax_compilation_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("outside", [True, False])
def test_compile_cache_dir(tmp_path, outside):
    """JAX_COMPILATION_CACHE_DIR wins when set, and the cache lands there;
    unset, the helper names <checkout>/.jax_cache (the probe then compiles
    nothing into it: it only reads the setting)."""
    probe = _CACHE_PROBE.format(src=os.path.join(ROOT, "src"))
    if outside:
        cache = tmp_path / "cache"
        r = _run(["-c", probe], JAX_COMPILATION_CACHE_DIR=str(cache))
        assert r.returncode == 0, r.stderr[-2000:]
        assert f"DIR {cache} {cache}" in r.stdout
        assert any(cache.iterdir())
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        r = subprocess.run(
            [sys.executable, "-c", probe.split("jax.config.update(")[0]],
            capture_output=True, text=True, timeout=120, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        want = os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert f"DIR {want} {want}" in r.stdout
