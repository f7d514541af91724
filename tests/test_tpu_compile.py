"""Compile the Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a raw kernel at phi3-mini widths (d_model
3072, d_ff 8192, 32 heads x 96; the MoE expert kernel at deepseek-moe-16b
widths) for one chip of a ``v5e:2x2`` topology
and asserts the compiled HLO carries the Mosaic kernel. This is what
interpret mode never checks: block shapes against the TPU tiling rule,
and what Mosaic can lower. The raw kernels are called with
``interpret=False`` because the ``ops`` dispatchers ask
``jax.default_backend()`` and would take their CPU branch here.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import flash_decode_bkgd, \
    flash_decode_paged
from repro.kernels.flash_attention import flash_attention_hsd
from repro.kernels.mxint_matmul import mxint_lowrank_matmul_2d, \
    mxint_lowrank_matmul_batched_2d, mxint_lowrank_matmul_fused_2d
from repro.kernels.mxint_quantize import mxint_quantize_2d
from repro.serve import ServeConfig

D_MODEL, D_FF, HEADS, HEAD_DIM = 3072, 8192, 32, 96
RANK, MX_BLOCK = 16, 32
PROJ_SHAPES = [(D_MODEL, D_MODEL), (D_MODEL, D_FF), (D_FF, D_MODEL)]
DECODE_B, DECODE_S = 8, 2048
PAGE = ServeConfig().page_size


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _codes(k, n, packed):
    return ((k // 2, n), jnp.uint8) if packed else ((k, n), jnp.int8)


@pytest.mark.parametrize("packed", [False, True], ids=["int8", "packed4"])
@pytest.mark.parametrize("kn", PROJ_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_fused_qlr_matmul_compiles(one_chip, kn, packed):
    k, n = kn
    m = 128
    _compile(lambda x, c, s, l, r: mxint_lowrank_matmul_fused_2d(
        x, c, s, l, r, packed=packed), one_chip,
        ((m, k), jnp.float32), _codes(k, n, packed),
        ((k // MX_BLOCK, n), jnp.float32), ((k, RANK), jnp.float32),
        ((RANK, n), jnp.float32))


@pytest.mark.parametrize("kn", PROJ_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_qlr_matmul_compiles(one_chip, kn):
    k, n = kn
    m = 512
    _compile(mxint_lowrank_matmul_2d, one_chip,
             ((m, k), jnp.float32), _codes(k, n, False),
             ((k // MX_BLOCK, n), jnp.float32), ((m, RANK), jnp.float32),
             ((RANK, n), jnp.float32))


def test_batched_qlr_matmul_compiles(one_chip):
    """The MoE expert stack, at deepseek-moe-16b expert widths."""
    g, m, k, n = 8, 128, 2048, 1408
    _compile(mxint_lowrank_matmul_batched_2d, one_chip,
             ((g, m, k), jnp.float32), ((g, k, n), jnp.int8),
             ((g, k // MX_BLOCK, n), jnp.float32),
             ((g, m, RANK), jnp.float32), ((g, RANK, n), jnp.float32))


def test_mxint_quantize_compiles(one_chip):
    _compile(lambda w: mxint_quantize_2d(w, bits=3, mx_block=MX_BLOCK),
             one_chip, ((D_MODEL, D_FF), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_attention_compiles(one_chip, dtype):
    sq, sk = 256, DECODE_S
    _compile(lambda q, k, v, qp, kp: flash_attention_hsd(q, k, v, qp, kp),
             one_chip, ((HEADS, sq, HEAD_DIM), dtype),
             ((HEADS, sk, HEAD_DIM), dtype), ((HEADS, sk, HEAD_DIM), dtype),
             ((sq,), jnp.int32), ((sk,), jnp.int32))


KV_CONTAINERS = {"bf16": (jnp.bfloat16, 1), "int8": (jnp.int8, 1),
                 "int4": (jnp.uint8, 2)}


@pytest.mark.parametrize("kv", list(KV_CONTAINERS))
def test_flash_decode_compiles(one_chip, kv):
    dt, per_byte = KV_CONTAINERS[kv]
    b, s = DECODE_B, DECODE_S
    shapes = [((b, HEADS, 1, HEAD_DIM), jnp.float32),
              ((b, HEADS, s // per_byte, HEAD_DIM), dt),
              ((b, HEADS, s // per_byte, HEAD_DIM), dt),
              ((b,), jnp.int32), ((b, s), jnp.int32)]
    if kv != "bf16":
        shapes += [((b, HEADS, s), jnp.float32)] * 2
    _compile(flash_decode_bkgd, one_chip, *shapes)


@pytest.mark.parametrize("kv", list(KV_CONTAINERS))
def test_flash_decode_paged_compiles(one_chip, kv):
    dt, per_byte = KV_CONTAINERS[kv]
    b, nb = DECODE_B, DECODE_S // PAGE
    pool = b * nb + 1
    shapes = [((b, HEADS, 1, HEAD_DIM), jnp.float32),
              ((pool, HEADS, PAGE // per_byte, HEAD_DIM), dt),
              ((pool, HEADS, PAGE // per_byte, HEAD_DIM), dt),
              ((b,), jnp.int32), ((b, nb * PAGE), jnp.int32),
              ((b, nb), jnp.int32)]
    if kv != "bf16":
        shapes += [((pool, HEADS, PAGE), jnp.float32)] * 2
    _compile(flash_decode_paged, one_chip, *shapes)
