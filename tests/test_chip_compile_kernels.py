"""Compiles for a described TPU v5e 2x2 host: the kernels alone.

The flash kernel forward and backward at the cells' head counts and widths,
chunked linear attention, block-sparse attention, and the long-document
cell's check of the two. See ``test_chip_compile.py``.
"""

import jax
import jax.numpy as jnp
import pytest

from chip_programs import KERNEL, cell_dims


# (q shape, kv heads, dtype, with the backward): four are the benchmark
# cells' own calls (Mistral 32/8 heads at 4,096 tokens, one and two
# sequences a chip; InternLM2 16/8 heads at the largest and smallest bucket).
KERNEL_SHAPES = {
    "8x1024x16x64-bf16": ((8, 1024, 16, 64), 16, jnp.bfloat16, True),
    "4x2048x16x128-bf16": ((4, 2048, 16, 128), 16, jnp.bfloat16, True),
    "2x1000x8x64-ragged": ((2, 1000, 8, 64), 8, jnp.bfloat16, True),
    "4x512x8x64-f32": ((4, 512, 8, 64), 8, jnp.float32, True),
    "1x4096x32x128-kv8-bf16": ((1, 4096, 32, 128), 8, jnp.bfloat16, True),
    "2x4096x32x128-kv8-bf16": ((2, 4096, 32, 128), 8, jnp.bfloat16, True),
    "8x2048x16x128-kv8-fwd": ((8, 2048, 16, 128), 8, jnp.bfloat16, False),
    "2x256x16x128-kv8-fwd": ((2, 256, 16, 128), 8, jnp.bfloat16, False),
    # 8,192 resident rows: the tiles ask for more than the default VMEM
    "1x16384x4x128-kv2-long": ((1, 16384, 4, 128), 2, jnp.bfloat16, True),
}


@pytest.mark.parametrize("case", list(KERNEL_SHAPES))
def test_flash_kernel_fwd_bwd_compiles(on_chip, mosaic, case):
    from ray_tpu.ops import flash_attention
    shape, kv_heads, dtype, backward = KERNEL_SHAPES[case]
    q = on_chip(shape, dtype)
    kv = on_chip(shape[:2] + (kv_heads,) + shape[3:], dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    fn = (jax.value_and_grad(loss, argnums=(0, 1, 2)) if backward else loss)
    text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    # forward, dq and dk/dv kernels
    assert text.count(KERNEL) >= (3 if backward else 1)


# -- a stack of several kinds of block ------------------------------------------
# (batch, tokens): the long-document cell's largest shape and its first
# selecting bucket, at MiniCPM-SALA's 32 heads of 128 (2 K/V heads).
MIXED_SHAPES = [(1, 32768), (2, 16384)]


@pytest.mark.parametrize("batch,length", MIXED_SHAPES)
def test_linear_attention_kernel_compiles(on_chip, mosaic, batch, length):
    from ray_tpu.ops.linear_attention import KERNEL_NAME, linear_attention
    x = on_chip((batch, length, 32, 128), jnp.bfloat16)
    rates = on_chip((32,), jnp.float32)
    text = jax.jit(linear_attention).lower(x, x, x, rates).compile().as_text()
    assert KERNEL in text and KERNEL_NAME in text


@pytest.mark.parametrize("batch,length", MIXED_SHAPES)
def test_sparse_attention_kernels_compile(on_chip, mosaic, batch, length):
    from ray_tpu.ops.sparse_attention import (ATTEND_KERNEL, SCORES_KERNEL,
                                              sparse_attention)
    q = on_chip((batch, length, 32, 128), jnp.bfloat16)
    kv = on_chip((batch, length, 2, 128), jnp.bfloat16)
    text = jax.jit(sparse_attention).lower(q, kv, kv).compile().as_text()
    assert text.count(KERNEL) >= 2
    assert SCORES_KERNEL in text and ATTEND_KERNEL in text


@pytest.mark.parametrize("length", [16384, 32768])
def test_the_operations_check_compiles_beside_the_references_weights(
        on_chip, mosaic, length):
    """The long-document cell's check of the two operations at its buckets:
    the three Mosaic calls are in it, and it takes less than the reference's
    own temporaries (3.2 GB at 32,768 tokens), so that with 11.3 GB of
    float32 weights on the device the comparison still fits."""
    _, adapter, dims = cell_dims("minicpm-sala-serve-longdoc")
    key = on_chip((2,), jnp.uint32)
    compiled = jax.jit(lambda k: adapter.operations_rows_off(
        k, length, dims)).lower(key).compile()
    text = compiled.as_text()
    for call in ("sparse_attn_scores", "sparse_attn_fwd", "linear_attn_fwd"):
        assert call in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3.2 * 10 ** 9


# -- the shortcut layer (LongCat-Flash): latent attention's head widths -------------


@pytest.mark.parametrize("batch,length", [(1, 8192), (2, 2048)])
def test_flash_kernel_compiles_at_unequal_head_widths(on_chip, mosaic, batch,
                                                      length):
    """q and k heads of 192 beside v heads of 128, 64 heads, forward only:
    the prefill cell's calls at its largest and smallest bucket."""
    from ray_tpu.ops import flash_attention
    qk = on_chip((batch, length, 64, 192), jnp.bfloat16)
    v = on_chip((batch, length, 64, 128), jnp.bfloat16)
    compiled = jax.jit(flash_attention).lower(qk, qk, v).compile()
    assert "flash_fwd" in compiled.as_text()
    assert compiled.output_shardings is not None


# -- the mixer-and-FFN kinds (LFM2): 64-wide heads, every expert held ------------


@pytest.mark.parametrize("length", [2048, 4096, 8192])
def test_flash_kernel_compiles_at_64_wide_grouped_heads(on_chip, mosaic, length):
    """32 query heads over 8 K/V heads, all 64 wide (half a lane row),
    forward only: the expert-load cell's calls at its three buckets."""
    from ray_tpu.ops import flash_attention
    q = on_chip((1, length, 32, 64), jnp.bfloat16)
    kv = on_chip((1, length, 8, 64), jnp.bfloat16)
    compiled = jax.jit(flash_attention).lower(q, kv, kv).compile()
    assert "flash_fwd" in compiled.as_text()


# -- the latent mixer among the mixer-and-FFN kinds (Kanana-2): the trained step ----


@pytest.mark.parametrize("batch,length,heads", [(1, 8192, 32), (4, 512, 32)])
def test_flash_backward_compiles_at_unequal_head_widths(on_chip, mosaic, batch,
                                                        length, heads):
    """q and k heads of 192 beside v heads of 128, forward and backward: the
    training cell's calls (32 heads at 8,192 tokens) and its comparison's (4
    x 512). dq and dk come at 192, dv at 128: v is not padded."""
    from ray_tpu.ops import flash_attention
    qk = on_chip((batch, length, heads, 192), jnp.bfloat16)
    v = on_chip((batch, length, heads, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile()
    text = compiled.as_text()
    for call in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert call in text
    widths = [leaf.shape[-1] for leaf in jax.tree.leaves(
        compiled.output_shardings and jax.eval_shape(
            jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v))]
    assert widths == [192, 192, 128]


# -- the chunked selective scan (ops/ssd.py) ---------------------------------------
@pytest.mark.parametrize("length", [128, 256, 512, 1024])
def test_the_chunked_scan_compiles_at_the_generating_cells_buckets(
        on_chip, mosaic, length):
    """``ssd_fwd`` at Granite 4.0-H's widths (64 heads of 64 against a state
    of 128, chunks of 256) at the four prefill buckets of
    ``granite4h-serve-chat``, one prompt a call, from an initial state: one
    Mosaic call by its own name, and nothing beside it that a chunk's worth
    of temporaries would not hold."""
    from ray_tpu.ops.ssd import KERNEL_NAME, ssd_fwd
    compiled = jax.jit(
        lambda x, dt, a, b, c, s: ssd_fwd(x, dt, a, b, c, initial_state=s)
    ).lower(on_chip((1, length, 64, 64), jnp.bfloat16),
            on_chip((1, length, 64), jnp.float32),
            on_chip((64,), jnp.float32),
            on_chip((1, length, 128), jnp.bfloat16),
            on_chip((1, length, 128), jnp.bfloat16),
            on_chip((1, 64, 64, 128), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1 and KERNEL_NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_the_stacked_step_compiles_at_the_generating_cells_slots(on_chip,
                                                                 mosaic):
    """``ssd_step_stacked`` at Granite 4.0-H's widths over 64 slots, on a
    stack of three layers that is donated: one Mosaic call by its own name,
    the stack its own output (aliased whole) and nothing beside it, no copy
    of a layer in or out."""
    from ray_tpu.ops.ssd import STEP_KERNEL_NAME, ssd_step_stacked
    stack = on_chip((3, 64, 64, 64, 128), jnp.float32)
    compiled = jax.jit(ssd_step_stacked, donate_argnums=(5,)).lower(
        on_chip((64, 64, 64), jnp.bfloat16), on_chip((64, 64), jnp.float32),
        on_chip((64,), jnp.float32), on_chip((64, 128), jnp.bfloat16),
        on_chip((64, 128), jnp.bfloat16), stack,
        on_chip((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1 and STEP_KERNEL_NAME in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 3 * 64 * 64 * 64 * 128 * 4
    assert memory.temp_size_in_bytes < 4 * 2 ** 20
    assert not any(" copy(" in line and "64,64,128]" in line
                   for line in text.splitlines())
