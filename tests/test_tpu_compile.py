"""Compile-only checks: every Pallas kernel of the main path compiles for a
described TPU v5e chip at real widths, with no chip attached.

Interpret mode never sees Mosaic's block-shape and lowering rules; these
cases do.  Widths are smollm-135m's (9 query / 3 kv heads, head_dim 64,
d_model 576, d_ff 1536, max batch 8) and ibert-base's (hidden 768, d_ff
3072, 12 heads, sequence 128); the paged decode kernel also compiles at
both benchmark cells' exact shapes, and its latent mode (one layer, and
26 layers stacked) and the grouped expert matmul at
moonlight-16b-a3b.long-decode's (batch 64, 16 heads over one latent row
of 512 + 64 stored 640 wide, 12289 pages of 192; 8 held of 64 experts,
top 6, hidden 2048, expert width 1408); a latent decode program compiles
with no second arena among its buffers.  The topology is described inside a
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
    shapes = [((B, H, HD), jnp.bfloat16)]
    if kv_dtype == "int8":
        shapes += [((pages, ps, KVH, HD), jnp.int8)] * 2
        shapes += [((pages, ps, KVH), jnp.float32)] * 2
    else:  # one row a token: each kv head's K then its V
        shapes += [((pages, ps, 2 * KVH * HD), jnp.bfloat16)]
    shapes += [((pages, ps), jnp.int32), ((B, maxp), jnp.int32),
               ((B,), jnp.int32), ((B,), jnp.bool_)]
    call = (ops.paged_flash_decode_q if kv_dtype == "int8"
            else ops.paged_flash_decode)

    def f(*a):
        return call(*a[:-1], active=a[-1], impl="pallas")
    return f, shapes


def _paged_cell(b, h, kvh, hd, pages, maxp, layers=0):
    """The paged decode kernel at a benchmark cell's exact shapes (bf16
    arena of [k_h | v_h] rows, 16-row pages); `layers` > 0: that many
    layers' arenas stacked, one read, and the layer's own kpos plane."""
    at = (layers,) if layers else ()
    shapes = [((b, h, hd), jnp.bfloat16),
              ((*at, pages, 16, 2 * kvh * hd), jnp.bfloat16),
              ((pages, 16), jnp.int32), ((b, maxp), jnp.int32),
              ((b,), jnp.int32), ((b,), jnp.bool_)]

    def f(q, kv, kpos, pt, qpos, act):
        return ops.paged_flash_decode(
            q, kv, kpos, pt, qpos, active=act,
            layer=jnp.int32(layers // 2) if layers else None, impl="pallas")
    return f, shapes


def _paged_latent(b, h, r, dv, pages, maxp, layers=0):
    """The latent mode at a cell's exact shapes (bf16 arena, 16-row
    pages); `layers` > 0: that many layers' arenas stacked, one read."""
    at = (layers,) if layers else ()
    shapes = [((b, h, r), jnp.bfloat16), ((*at, pages, 16, r), jnp.bfloat16),
              ((*at, pages, 16), jnp.int32), ((b, maxp), jnp.int32),
              ((b,), jnp.int32), ((b,), jnp.bool_)]

    def f(q, c, kpos, pt, qpos, act):
        return ops.paged_flash_decode_latent(
            q, c, kpos, pt, qpos, dv, active=act,
            layer=jnp.int32(layers // 2) if layers else None, impl="pallas")
    return f, shapes


def _expert_gmm(m, k, n, held, experts):
    def f(x, w, sizes):
        return ops.expert_gmm(x, w, sizes, 0, impl="pallas")
    return f, [((m, k), jnp.bfloat16), ((held, k, n), jnp.bfloat16),
               ((experts,), jnp.int32)]


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
    "paged_decode_smollm_sessions_stacked": lambda: _paged_cell(
        32, 9, 3, 64, 2369, 74, layers=30),
    # phi3-medium-14b.batch: batch 16, 40 q / 10 kv heads, 2305 of 144
    "paged_decode_phi3_batch": lambda: _paged_cell(16, 40, 10, 128, 2305,
                                                   144),
    "paged_decode_phi3_batch_stacked": lambda: _paged_cell(
        16, 40, 10, 128, 2305, 144, layers=10),
    # moonlight-16b-a3b.long-decode: 64 lanes x top 6 expert rows
    "paged_decode_moonlight_latent": lambda: _paged_latent(64, 16, 640, 512,
                                                           12289, 192),
    "paged_decode_moonlight_latent_stacked": lambda: _paged_latent(
        64, 16, 640, 512, 12289, 192, layers=26),
    "expert_gmm_moonlight_gate_up": lambda: _expert_gmm(384, 2048, 1408, 8,
                                                        64),
    "expert_gmm_moonlight_down": lambda: _expert_gmm(384, 1408, 2048, 8, 64),
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


def test_latent_decode_program_holds_one_arena(one_chip):
    """The paged decode program of a latent-attention model carries its
    stacked arena through the layer scan and attends in place: among its
    buffers there is no second arena (as scanned xs/ys there was one, and
    576-wide rows laid out pages-minor cost a whole-arena copy a call).
    Small widths, many pages (2049: laid out pages-minor, 576-wide rows
    would pad less than their own 64 lanes), so the arena dominates every
    other buffer."""
    from repro.configs.base import ModelConfig
    from repro.models.transformer import Model, init_params
    cfg = ModelConfig(name="tiny-mla", family="moe", n_layers=8, d_model=256,
                      n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=512,
                      kv_lora_rank=512, qk_nope_head_dim=32,
                      qk_rope_head_dim=64, v_head_dim=32, mlp_style="swiglu",
                      act="silu", n_experts=16, top_k=2, moe_every=1,
                      n_shared_experts=1, routed_scale=2.0, experts_held=4,
                      first_k_dense=1, dense_d_ff=256, tie_embeddings=False,
                      norm="rmsnorm")
    model = Model(cfg, remat=False)
    b, ps, maxp = 32, 16, 64
    pages = b * maxp + 1
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: model.init_paged_cache(b, pages, ps,
                                                           maxp))

    def pfn(params, caches, token, active, eos, budget):
        return model.decode_steps(params, caches, token, active, 4,
                                  eos_id=eos, budget=budget)
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32)
    args = (params, caches, i32, jax.ShapeDtypeStruct((b,), jnp.bool_),
            i32, i32)
    with ops.pinned_impl("pallas"):
        compiled = jax.jit(
            pfn, donate_argnums=(1,),
            in_shardings=jax.tree.map(lambda _: one_chip, args)
        ).lower(*args).compile()
    arena = caches["scan"]["b0"]["ckv"]
    arena_bytes = arena.size * arena.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < arena_bytes / 2
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        >= 2  # the latent kernel (lead layer and scan) and the expert gmm


def _decode_program(cfg, b, pages, ps, maxp, one_chip):
    """The paged bf16 decode program (4 fused steps) compiled for one
    described v5e, the cache donated; returns it and the cache shapes."""
    from repro.models.transformer import Model, init_params
    model = Model(cfg, remat=False)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: model.init_paged_cache(b, pages, ps,
                                                           maxp))

    def pfn(params, caches, token, active, eos, budget):
        return model.decode_steps(params, caches, token, active, 4,
                                  eos_id=eos, budget=budget)
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32)
    args = (params, caches, i32, jax.ShapeDtypeStruct((b,), jnp.bool_),
            i32, i32)
    with ops.pinned_impl("pallas"):
        compiled = jax.jit(
            pfn, donate_argnums=(1,),
            in_shardings=jax.tree.map(lambda _: one_chip, args)
        ).lower(*args).compile()
    return compiled, caches


DENSE_WIDTHS = {
    # smollm-135m's heads: rows of 2 x 3 x 64 = 384 lanes
    "smollm_heads": dict(n_layers=6, d_model=576, n_heads=9, n_kv_heads=3,
                         head_dim=64, d_ff=1536, b=32, maxp=64),
    # phi3-medium-14b's heads (2 x 10 x 128 = 2560 lanes), narrow d_model
    # and few layers, so that the arena dominates every other buffer
    "phi3_heads": dict(n_layers=3, d_model=1024, n_heads=40, n_kv_heads=10,
                       head_dim=128, d_ff=1024, b=16, maxp=64),
}


@pytest.mark.parametrize("widths", sorted(DENSE_WIDTHS))
def test_dense_decode_program_holds_one_arena(widths, one_chip):
    """The paged bf16 decode program of a dense model carries its stacked
    [k_h | v_h] arena through the layer scan and attends in place: among
    its buffers there is no second arena, and its optimized HLO holds no
    copy, slice or write-back of the stacked arena or of one layer's.
    As scanned xs/ys each layer's arena was sliced out and written back,
    and separate (P, ps, KVH, hd) arenas, whose last dims are not whole
    tiles, were laid out pages-minor and copied whole into the kernel's
    row-major layout."""
    import re

    from repro.configs.base import ModelConfig
    w = dict(DENSE_WIDTHS[widths])
    b, maxp, ps = w.pop("b"), w.pop("maxp"), 16
    cfg = ModelConfig(name=f"tiny-{widths}", family="dense",
                      vocab_size=512, mlp_style="swiglu", act="silu",
                      norm="rmsnorm", **w)
    pages = b * maxp + 1
    compiled, caches = _decode_program(cfg, b, pages, ps, maxp, one_chip)
    arena = caches["scan"]["b0"]["kv"]
    assert arena.shape == (cfg.n_layers, pages, ps,
                           2 * cfg.n_kv_heads * cfg.head_dim)
    arena_bytes = arena.size * arena.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < arena_bytes / 2
    hlo = compiled.as_text()
    dims = [",".join(map(str, arena.shape)),
            ",".join(map(str, arena.shape[1:]))]
    moves = [ln for ln in hlo.splitlines()
             if re.search(r"\b(copy|dynamic-slice|dynamic-update-slice)\(",
                          ln)
             and any(f"bf16[{d}]" in ln for d in dims)]
    assert not moves, moves[:3]
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 1
