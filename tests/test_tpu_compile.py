"""Compile rehearsals: the four Pallas kernels compiled for a TPU v5e.

No chip is needed. The TPU compiler is installed beside jax, and it compiles
for a described ``v5e:2x2`` topology whose devices are not attached. Each
test lowers one kernel at a real model width through the ``pallas`` mode of
the kernel registry, compiles it for one v5e chip and checks that Mosaic
emitted the kernel (``tpu_custom_call``). Interpret mode cannot catch what
these catch: block shapes the TPU tiling refuses, ops Mosaic cannot lower,
and too much VMEM.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, registry

bf16, f32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_pallas(fn, one_chip, *shapes):
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with registry.kernel_mode_scope("pallas"):
        compiled = jax.jit(fn).lower(*specs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


# qwen2.5-3b: d_model 2048, 16 query heads x 128, 2 KV heads.
@pytest.mark.parametrize("x_shape", [(4, 1, 2048), (4, 64, 2048)],
                         ids=["decode", "prefill"])
def test_rmsnorm_qwen25_3b(one_chip, x_shape):
    _compile_pallas(lambda x, w: ops.rmsnorm(x, w), one_chip,
                    (x_shape, bf16), ((2048,), f32))


def test_rmsnorm_vmapped_as_server_batches(one_chip):
    # RegionServer stacks 4 tenants' (4, 1, 2048) decode activations and
    # vmaps the step over them; the norm weight is shared.
    fn = jax.vmap(lambda x, w: ops.rmsnorm(x, w), in_axes=(0, None))
    _compile_pallas(fn, one_chip, ((4, 4, 1, 2048), bf16), ((2048,), f32))


def test_flash_attention_qwen25_3b(one_chip):
    _compile_pallas(lambda q, k, v: ops.attention(q, k, v, causal=True),
                    one_chip, ((4, 1024, 16, 128), bf16),
                    ((4, 1024, 2, 128), bf16), ((4, 1024, 2, 128), bf16))


def test_ssd_mamba2_370m(one_chip):
    # d_inner 2048 = 32 SSM heads x 64, state 128, one group, chunk 128.
    B, S, H, P, N = 2, 512, 32, 64, 128
    fn = lambda x, dt, A, Bm, Cm: ops.ssd(x, dt, A, Bm, Cm, chunk=128)
    _compile_pallas(fn, one_chip, ((B, S, H, P), f32), ((B, S, H), f32),
                    ((H,), f32), ((B, S, 1, N), f32), ((B, S, 1, N), f32))


@pytest.mark.parametrize("d_in,d_out", [(2048, 768), (768, 2048)],
                         ids=["up", "down"])
def test_grouped_matmul_qwen3_moe(one_chip, d_in, d_out):
    # 128 experts, expert d_ff 768, d_model 2048.
    E, C = 128, 64
    _compile_pallas(ops.grouped_matmul, one_chip,
                    ((E, C, d_in), bf16), ((E, d_in, d_out), bf16))
