"""Multi-device checks, run in a subprocess with 8 forced host devices.

Usage: python tests/distributed_checks.py <check_name>
Exits nonzero on failure. Invoked by tests/test_distributed.py.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import runtime

runtime.force_host_device_count(8)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat


def mesh228():
    return compat.make_mesh((2, 2, 2), ("pod", "data", "model"))


def mesh24():
    return compat.make_mesh((2, 4), ("data", "model"))


def check_moe_ep_matches_oracle():
    """EP shard_map path == dense oracle when capacity is unconstrained."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import model_defs, init_params
    from repro.models.moe import moe_dense_oracle, moe_ep
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0,
                                     n_experts=8, pad_to=8))
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["period"][0]["ffn"])
    mesh = mesh24()
    B, S = 4, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32)
    y_ref, aux_ref = moe_dense_oracle(cfg, p, x)
    with compat.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_ep, aux_ep = jax.jit(lambda p, x: moe_ep(
            cfg, p, x, ep_axis="model", token_axes=("data",)))(p, xs)
    err = float(jnp.max(jnp.abs(y_ep - y_ref)))
    scale = float(jnp.max(jnp.abs(y_ref))) + 1e-9
    assert err / scale < 2e-3, f"EP vs oracle rel err {err/scale}"
    for k in aux_ref:
        a, b = float(aux_ref[k]), float(aux_ep[k])
        assert abs(a - b) < 1e-2 * max(abs(a), 1.0), f"aux {k}: {a} vs {b}"
    print("moe_ep ok", err / scale)


def check_moe_ep_gradients():
    """Gradients flow through the EP dispatch (a2a + scatters)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import model_defs, init_params
    from repro.models.moe import moe_dense_oracle, moe_ep
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0,
                                     n_experts=8, pad_to=8))
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["period"][0]["ffn"])
    mesh = mesh24()
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, cfg.d_model),
                          jnp.float32)

    def loss_ep(p, x):
        y, aux = moe_ep(cfg, p, x, ep_axis="model", token_axes=("data",))
        return jnp.sum(y ** 2) + aux["moe_load_balance"]

    def loss_ref(p, x):
        y, aux = moe_dense_oracle(cfg, p, x)
        return jnp.sum(y ** 2) + aux["moe_load_balance"]

    with compat.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        g_ep = jax.jit(jax.grad(loss_ep))(p, xs)
    g_ref = jax.grad(loss_ref)(p, x)
    for k in ("w_in", "w_out", "router"):
        a = np.asarray(g_ref[k], np.float32)
        b = np.asarray(g_ep[k], np.float32)
        denom = np.abs(a).max() + 1e-6
        assert np.abs(a - b).max() / denom < 5e-3, \
            f"grad {k} mismatch {np.abs(a-b).max()/denom}"
    print("moe_ep grads ok")


def check_moe_allgather_combine():
    """Optimized contiguous-ownership all-gather combine == oracle, including
    a token count not divisible by the EP degree."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import model_defs, init_params
    from repro.models.moe import moe_dense_oracle, moe_ep
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0,
                                     n_experts=8, pad_to=8))
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["period"][0]["ffn"])
    mesh = mesh24()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 10, cfg.d_model),
                          jnp.float32)       # n=20 per shard, 20 % 4 != 0
    y_ref, _ = moe_dense_oracle(cfg, p, x)
    with compat.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_ag, _ = jax.jit(lambda p, x: moe_ep(
            cfg, p, x, combine="allgather"))(p, xs)
        g = jax.jit(jax.grad(lambda p, x: jnp.sum(moe_ep(
            cfg, p, x, combine="allgather")[0] ** 2)))(p, xs)
    err = float(jnp.max(jnp.abs(y_ag - y_ref)))
    scale = float(jnp.max(jnp.abs(y_ref))) + 1e-9
    assert err / scale < 2e-3, err / scale
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in jax.tree.leaves(g))
    print("moe allgather combine ok", err / scale)


def check_sharded_decode_attention():
    from repro.models.attention import write_kv_cache, decode_attention_ref
    from repro.parallel.decode_attn import sharded_decode_attention
    mesh = mesh228()
    B, S, KV, G, D = 4, 32, 2, 2, 16
    H = KV * G
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, D), jnp.float32)
    kc = jax.random.normal(key, (B, S, KV, D), jnp.float32)
    vc = jax.random.normal(jax.random.split(key)[0], (B, S, KV, D),
                           jnp.float32)
    kn = jax.random.normal(key, (B, KV, D), jnp.float32)
    vn = jax.random.normal(jax.random.split(key)[1], (B, KV, D), jnp.float32)
    lens = jnp.asarray([3, 17, 25, 31], jnp.int32)
    kc2, vc2 = write_kv_cache(kc, vc, kn, vn, lens)
    o_ref = decode_attention_ref(q, kc2, vc2, lens + 1)
    with compat.set_mesh(mesh):
        put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
        o, kc3, vc3 = jax.jit(lambda *a: sharded_decode_attention(
            *a, seq_axes=("data", "model"), batch_axes=("pod",)))(
            put(q, P("pod", None, None)),
            put(kc, P("pod", ("data", "model"), None, None)),
            put(vc, P("pod", ("data", "model"), None, None)),
            put(kn, P("pod", None, None)), put(vn, P("pod", None, None)),
            put(lens, P("pod")))
    err = float(jnp.max(jnp.abs(o - o_ref)))
    assert err < 1e-4, f"decode attn err {err}"
    np.testing.assert_allclose(np.asarray(kc3), np.asarray(kc2), atol=1e-6)
    print("sharded decode attention ok", err)


def check_sharded_mla_decode():
    import math
    from repro.parallel.decode_attn import sharded_mla_decode
    mesh = mesh24()
    B, S, H, R, DR = 2, 16, 4, 8, 4
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 6)
    q_lat = jax.random.normal(ks[0], (B, H, R), jnp.float32)
    q_rope = jax.random.normal(ks[1], (B, H, DR), jnp.float32)
    ckv = jax.random.normal(ks[2], (B, S, R), jnp.float32)
    kr = jax.random.normal(ks[3], (B, S, DR), jnp.float32)
    ckv_n = jax.random.normal(ks[4], (B, R), jnp.float32)
    kr_n = jax.random.normal(ks[5], (B, DR), jnp.float32)
    lens = jnp.asarray([5, 11], jnp.int32)
    scale = 1.0 / math.sqrt(R + DR)
    ref, _, _ = sharded_mla_decode(q_lat, q_rope, ckv, kr, ckv_n, kr_n, lens,
                                   sm_scale=scale, seq_axes=())
    with compat.set_mesh(mesh):
        put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
        o, _, _ = jax.jit(lambda *a: sharded_mla_decode(
            *a, sm_scale=scale, seq_axes=("model",), batch_axes=("data",)))(
            put(q_lat, P("data", None, None)),
            put(q_rope, P("data", None, None)),
            put(ckv, P("data", "model", None)),
            put(kr, P("data", "model", None)),
            put(ckv_n, P("data", None)), put(kr_n, P("data", None)),
            put(lens, P("data")))
    err = float(jnp.max(jnp.abs(o - ref)))
    assert err < 1e-4, f"mla decode err {err}"
    print("sharded mla decode ok", err)


def check_distributed_train_step_parity():
    """One train step on the 8-device mesh == single-device step."""
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.models import model_defs, param_shardings
    from repro.models.transformer import RunFlags
    from repro.train import (OptConfig, TrainConfig, build_train_step,
                             init_train_state)
    from repro.train.step import batch_shardings
    cfg = get_config("tacc-100m", smoke=True)
    ocfg = OptConfig(lr=1e-3, warmup_steps=2, total_steps=50)
    state = init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
    data = SyntheticLM(cfg, 8, 32, seed=0)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    step_ref = jax.jit(build_train_step(cfg, ocfg, TrainConfig(2)))
    s_ref, m_ref = step_ref(state, batch)

    mesh = mesh24()
    flags = RunFlags(distributed=True, token_axes=("data",),
                     act_spec=P("data", None, None))
    pshard = param_shardings(model_defs(cfg), mesh)
    scalar = NamedSharding(mesh, P())
    st_sh = {"params": pshard, "opt": {"m": pshard, "v": pshard,
                                       "step": scalar}}
    bshard = batch_shardings(mesh, ("data",), batch)
    with compat.set_mesh(mesh):
        st = jax.device_put(state, st_sh)
        bt = jax.device_put(batch, bshard)
        step_d = jax.jit(build_train_step(cfg, ocfg, TrainConfig(2), flags),
                         in_shardings=(st_sh, bshard),
                         out_shardings=(st_sh, None))
        s_d, m_d = step_d(st, bt)
    assert abs(float(m_ref["loss"]) - float(m_d["loss"])) < 2e-3, \
        (float(m_ref["loss"]), float(m_d["loss"]))
    dmax = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        s_ref["params"], jax.device_get(s_d["params"]))))
    assert dmax < 5e-3, f"param divergence {dmax}"
    print("distributed train parity ok", float(m_ref["loss"]),
          float(m_d["loss"]), dmax)


def check_tiny_dryrun():
    # subprocess-local knob read once by repro.launch.dryrun at import; the
    # runtime helpers don't cover per-entrypoint overrides
    os.environ["REPRO_DRYRUN_DEVICES"] = "8"  # repolint: disable=env-discipline
    from repro.launch.dryrun import run_cell
    for arch, shape in (("internlm2-1.8b", "train_4k"),
                        ("qwen2-moe-a2.7b", "decode_32k")):
        rec = run_cell(arch, shape, "tiny")
        assert rec["status"] == "ok", rec.get("error")
        assert rec["full"]["flops"] > 0
        print("tiny dryrun ok", arch, shape, rec["full"]["flops"])


def check_chip_smoke_sharded():
    """``chip_smoke.py --chips 4``'s phase on four of the host devices: the
    2x2 (data, model) train step against the one-device step."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import chip_smoke
    from repro.configs import get_config
    chip_smoke.phase_sharded(get_config("tacc-100m", smoke=True), chips=4,
                             steps=3, batch=8, seq=32, seed=0)


CHECKS = {k[len("check_"):]: v for k, v in list(globals().items())
          if k.startswith("check_")}

if __name__ == "__main__":
    name = sys.argv[1]
    CHECKS[name]()
    print(f"CHECK {name} PASSED")
