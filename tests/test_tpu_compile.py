"""Compile the main path's kernels at real widths for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse — a
tiling the hardware cannot take, a VMEM budget, HBM — which interpret-mode
tests cannot see. Describing the topology loads libtpu, which one process
holds at a time, so it happens only inside the module fixture below, and
all such compiles stay in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops
from repro.parallel.decode_attn import paged_decode_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("heads,head_dim", [(12, 64), (8, 128)])
def test_flash_attention_compiles_for_v5e(one_chip, heads, head_dim):
    q = jax.ShapeDtypeStruct((1, 4096, heads, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    hlo = _hlo(lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
               q, q, q)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_compiles_for_v5e(one_chip, residual):
    x = jax.ShapeDtypeStruct((8192, 768), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((768,), jnp.float32, sharding=one_chip)
    if residual:
        hlo = _hlo(lambda x, r, w: ops.rmsnorm_residual(x, r, w,
                                                        backend="pallas"),
                   x, x, w)
    else:
        hlo = _hlo(lambda x, w: ops.rmsnorm(x, w, backend="pallas"), x, w)
    assert "tpu_custom_call" in hlo


def test_paged_decode_compiles_for_v5e(one_chip):
    """tacc-100m decode widths: 8 sequences, 12 query / 4 kv heads of 64,
    a 1024-token cache in autotuned pages. The path is XLA (gather, then
    the dense reference math), so no kernel is expected."""
    B, H, KV, HD, S = 8, 12, 4, 64, 1024
    page, _ = autotune.plan_decode_page((B, H, S, HD), jnp.bfloat16)
    n = S // page

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _hlo(paged_decode_attention, spec((B, H, HD)),
               spec((B * n, page, KV, HD)), spec((B * n, page, KV, HD)),
               spec((B, n), jnp.int32), spec((B,), jnp.int32))
    assert "gather" in hlo
