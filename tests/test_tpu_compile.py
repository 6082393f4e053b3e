"""Compile-only checks: every Pallas kernel of the main path compiles for a
described TPU v5e chip at real widths, with no chip attached.

Interpret mode never sees Mosaic's block-shape and lowering rules; these
cases do.  Widths are smollm-135m's (9 query / 3 kv heads, head_dim 64,
d_model 576, d_ff 1536, max batch 8) and ibert-base's (hidden 768, d_ff
3072, 12 heads, sequence 128); the paged decode kernel also compiles at
both benchmark cells' exact shapes.  The topology is described inside a
fixture, never at import, so that only the pytest worker that runs this
file loads the TPU compiler.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core.ibert_ops import LNParams
from repro.kernels import ops

B, H, KVH, HD = 8, 9, 3, 64  # smollm-135m decode lanes and heads
CACHE_LEN = 192  # the serve CLI's largest bucket (128) + decode headroom


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _paged(ps, kv_dtype):
    maxp = -(-CACHE_LEN // ps)
    pages = B * maxp + 1
    kv = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    shapes = [((B, H, HD), jnp.bfloat16), ((pages, ps, KVH, HD), kv),
              ((pages, ps, KVH, HD), kv)]
    if kv_dtype == "int8":
        shapes += [((pages, ps, KVH), jnp.float32)] * 2
    shapes += [((pages, ps), jnp.int32), ((B, maxp), jnp.int32),
               ((B,), jnp.int32), ((B,), jnp.bool_)]
    call = (ops.paged_flash_decode_q if kv_dtype == "int8"
            else ops.paged_flash_decode)

    def f(*a):
        return call(*a[:-1], active=a[-1], impl="pallas")
    return f, shapes


def _paged_cell(b, h, kvh, hd, pages, maxp):
    """The paged decode kernel at a benchmark cell's exact shapes (bf16
    arena, 16-row pages)."""
    shapes = [((b, h, hd), jnp.bfloat16),
              ((pages, 16, kvh, hd), jnp.bfloat16),
              ((pages, 16, kvh, hd), jnp.bfloat16),
              ((pages, 16), jnp.int32), ((b, maxp), jnp.int32),
              ((b,), jnp.int32), ((b,), jnp.bool_)]

    def f(*a):
        return ops.paged_flash_decode(*a[:-1], active=a[-1], impl="pallas")
    return f, shapes


def _dense_decode():
    def f(q, k, v, kpos, qpos, act):
        return ops.flash_decode(q, k, v, kpos, qpos, active=act,
                                impl="pallas")
    sk = 512  # two 256-row KV splits
    return f, [((B, H, HD), jnp.bfloat16), ((B, sk, KVH, HD), jnp.bfloat16),
               ((B, sk, KVH, HD), jnp.bfloat16), ((B, sk), jnp.int32),
               ((B,), jnp.int32), ((B,), jnp.bool_)]


def _prefill():
    def f(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, impl="pallas")
    return f, [((1, 512, H, HD), jnp.bfloat16),
               ((1, 512, KVH, HD), jnp.bfloat16),
               ((1, 512, KVH, HD), jnp.bfloat16)]


def _int8_matmul():
    def f(a, b):
        return ops.int8_matmul(a, b, 0.01, 0.02, impl="pallas")
    return f, [((128, 576), jnp.int8), ((576, 1536), jnp.int8)]


def _i_softmax():
    def f(q):
        return ops.i_softmax(q, 0.05, impl="pallas")
    return f, [((12 * 128, 128), jnp.int32)]


def _i_gelu():
    def f(q):
        return ops.i_gelu(q, 0.05, impl="pallas")
    return f, [((128, 3072), jnp.int32)]


def _i_layernorm():
    def f(q, g, b):
        prep = LNParams(q_gamma=g, s_gamma=1.0, q_beta=b, s_out=1.0)
        return ops.i_layernorm(q, prep, impl="pallas")[0]
    return f, [((128, 768), jnp.int8), ((768,), jnp.int32),
               ((768,), jnp.int32)]


CASES = {
    "paged_decode_bf16_ps16": lambda: _paged(16, "bf16"),
    "paged_decode_bf16_ps128": lambda: _paged(128, "bf16"),
    "paged_decode_int8_ps16": lambda: _paged(16, "int8"),
    "paged_decode_int8_ps128": lambda: _paged(128, "int8"),
    # smollm-135m.sessions: batch 32, 9 q / 3 kv heads, 2369 pages of 74
    "paged_decode_smollm_sessions": lambda: _paged_cell(32, 9, 3, 64, 2369,
                                                        74),
    # phi3-medium-14b.batch: batch 16, 40 q / 10 kv heads, 2305 of 144
    "paged_decode_phi3_batch": lambda: _paged_cell(16, 40, 10, 128, 2305,
                                                   144),
    "flash_decode_dense": _dense_decode,
    "flash_attention_prefill": _prefill,
    "int8_matmul": _int8_matmul,
    "i_softmax": _i_softmax,
    "i_gelu": _i_gelu,
    "i_layernorm": _i_layernorm,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1
