"""Pallas kernel validation: interpret=True (kernel body executed on CPU)
against the pure-jnp oracles across shape/dtype sweeps (assignment req. c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.rmsnorm import rmsnorm_tpu, rmsnorm_residual_tpu

SHAPES = [(1, 2, 128, 64), (2, 4, 256, 128), (1, 1, 512, 128), (2, 2, 384, 64)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_interpret_vs_ref(shape, dtype, causal):
    B, H, S, D = shape
    keys = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 3)
    q, k, v = (jax.random.normal(kk, shape, dtype) for kk in keys)
    bq = bk = 128      # fixed probe blocks; tuned choices live in autotune
    o = flash_attention_tpu(q, k, v, causal=causal, block_q=bq, block_k=bk,
                            interpret=True)
    r = ref.attention_ref(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol)


def test_flash_attention_block_shape_sweep():
    B, H, S, D = 1, 2, 256, 64
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.float32) for kk in keys)
    r = ref.attention_ref(q, k, v, causal=True)
    for bq in (64, 128, 256):
        for bk in (64, 128, 256):
            o = flash_attention_tpu(q, k, v, causal=True, block_q=bq,
                                    block_k=bk, interpret=True)
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       atol=3e-5, err_msg=f"bq={bq} bk={bk}")


def test_ops_dispatcher_bshd_layout():
    """ops.flash_attention takes (B,S,H,D) like the model stack."""
    B, S, H, D = 2, 128, 4, 64
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
               for kk in keys)
    o = ops.flash_attention(q, k, v, causal=True, interpret=True)
    r = ref.attention_ref(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                          causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=3e-2)


def test_ops_dispatcher_takes_a_softmax_scale():
    """A set scale (Granite-4.0-H's 1/head_dim) replaces 1/sqrt(head_dim):
    the same as scaling q by scale * sqrt(head_dim) before the default."""
    B, S, H, D = 1, 256, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in keys)
    o = ops.flash_attention(q, k, v, causal=True, interpret=True,
                            scale=1 / D)
    r = ref.attention_ref(*(t.transpose(0, 2, 1, 3)
                            for t in (q * (D ** 0.5 / D), k, v)),
                          causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=3e-5)


def test_model_attention_scale_matches_on_both_backends():
    """A configuration with its own softmax scale takes the Pallas kernel
    too, and it matches the XLA path's scaled attention (float32: the two
    differ only in the order of their sums)."""
    from dataclasses import replace
    from repro.configs import get_config
    from repro.models import model_defs, init_params
    from repro.models.attention import self_attention
    cfg = replace(get_config("granite-4.0-h-small", smoke=True),
                  dtype="float32")
    assert cfg.attn_scale and cfg.period[1].mixer == "attn"
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["period"][1]["mixer"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
    ox, _ = self_attention(cfg, p, x, pos, backend="xla")
    op, _ = self_attention(cfg, p, x, pos, backend="interpret")
    np.testing.assert_allclose(np.asarray(op), np.asarray(ox), atol=1e-5)


@pytest.mark.parametrize("N,D", [(64, 256), (256, 512), (8, 128), (100, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_interpret_vs_ref(N, D, dtype):
    key = jax.random.PRNGKey(N * D)
    x = jax.random.normal(key, (N, D), dtype)
    w = jax.random.normal(jax.random.split(key)[0], (D,), jnp.float32)
    o = rmsnorm_tpu(x, w, interpret=True)
    r = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=1e-5)


def test_rmsnorm_residual_fused():
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (64, 256), jnp.bfloat16)
    res = jax.random.normal(jax.random.split(key)[0], (64, 256), jnp.bfloat16)
    w = jnp.ones((256,), jnp.float32)
    y, s = rmsnorm_residual_tpu(x, res, w, interpret=True)
    ry, rs = ref.rmsnorm_residual_ref(x, res, w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ry, np.float32), atol=2e-2)
    np.testing.assert_allclose(np.asarray(s, np.float32),
                               np.asarray(rs, np.float32), atol=2e-2)


def test_model_attention_backend_interpret_matches_xla():
    """RunFlags(backend='interpret') routes through the Pallas kernel and must
    match the XLA path end-to-end on a dense smoke model."""
    from repro.configs import get_config
    from repro.models import model_defs, init_params
    from repro.models.transformer import RunFlags, train_logits
    cfg = get_config("tacc-100m", smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    lx, _ = train_logits(cfg, params, batch, flags=RunFlags(backend="xla"))
    lp, _ = train_logits(cfg, params, batch,
                         flags=RunFlags(backend="interpret"))
    err = float(jnp.max(jnp.abs(lx - lp))) / (float(jnp.max(jnp.abs(lx))) + 1e-6)
    assert err < 0.03
