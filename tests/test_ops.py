"""Pallas kernels vs dense references (interpreter mode on the CPU mesh)."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention


def _dense_attention(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        L, Lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((L, Lk), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_forward(causal):
    B, L, H, D = 2, 256, 2, 64
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
               for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_multi_block_seq():
    B, L, H, D = 1, 512, 1, 64
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
               for i in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad(causal):
    B, L, H, D = 1, 256, 2, 32
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
               for i in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=2e-4, atol=2e-4,
            err_msg=f"grad mismatch for {name}")


def test_flash_attention_bf16():
    B, L, H, D = 2, 128, 2, 64
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D),
                                 dtype=jnp.bfloat16) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_ragged_seqlen(causal):
    """Seqlen not divisible by block size: pad columns must not leak."""
    B, L, H, D = 1, 200, 2, 32
    key = jax.random.PRNGKey(4)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
               for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_ragged_grad():
    B, L, H, D = 1, 200, 1, 32
    key = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
               for i in range(3))
    g_flash = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=False) ** 2))(q)
    g_dense = jax.grad(lambda q: jnp.sum(
        _dense_attention(q, k, v, False) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_dense),
                               rtol=2e-4, atol=2e-4)


# -- tiles from the shape, GQA by index, operands in their own dtype --------


def _qkv(key, B, Lq, Lk, H, KVH, D, dtype=jnp.float32):
    key = jax.random.PRNGKey(key)
    shapes = [(B, Lq, H, D), (B, Lk, KVH, D), (B, Lk, KVH, D)]
    return [jax.random.normal(jax.random.fold_in(key, i), s, dtype=dtype)
            for i, s in enumerate(shapes)]


def _dense_gqa(q, k, v, causal):
    """The dense reference on K and V repeated to q's heads, in float32."""
    rep = q.shape[2] // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return _dense_attention(q, jnp.repeat(k, rep, axis=2),
                            jnp.repeat(v, rep, axis=2), causal)


def _fwd_and_grads(attn, q, k, v):
    """Output and the three gradients of a loss with an uneven cotangent."""
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def loss(q, k, v):
        out = attn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


def _assert_matches_dense(q, k, v, causal, tol, **blocks):
    got = _fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal, **blocks),
        q, k, v)
    want = _fwd_and_grads(lambda q, k, v: _dense_gqa(q, k, v, causal),
                          q, k, v)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g, dtype=np.float32), np.asarray(w), rtol=tol,
            atol=tol, err_msg=f"{name} mismatch")


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 2), (4, 1)],
                         ids=["ratio4", "ratio2", "mqa"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa_unrepeated(heads, kv_heads, causal):
    """K and V with their own head count: query head h reads K/V head
    h // ratio, and dk/dv sum the group's query heads."""
    q, k, v = _qkv(6, 2, 256, 256, heads, kv_heads, 32)
    _assert_matches_dense(q, k, v, causal, 2e-4, block_q=128, block_k=128)


# (Lq, Lk, block_q, block_k, block_major): interior, diagonal, skipped and
# ragged-last tiles, one or several tiles a grid step, several major blocks.
TILINGS = [
    (200, 200, 128, 128, None),    # two tiles, ragged last, one major block
    (640, 640, 128, 128, 256),     # 5x5 tiles, 3 major blocks, last half out
    (640, 640, 256, 128, 128),     # block_q != block_k, one tile a step
    (640, 640, 128, 256, 512),
    (1000, 1000, 256, 256, 512),   # ragged last tile inside a major block
    (1000, 1000, 128, 256, None),
    (384, 640, 128, 128, 256),     # Lq != Lk
    (640, 384, 128, 128, 256),
    (200, 1000, None, None, None),  # tiles from the shape
]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("Lq,Lk,block_q,block_k,block_major", TILINGS, ids=[
    "-".join(str(x) for x in t) for t in TILINGS])
def test_flash_attention_tilings(Lq, Lk, block_q, block_k, block_major,
                                 causal):
    q, k, v = _qkv(7, 1, Lq, Lk, 2, 1, 32)
    _assert_matches_dense(q, k, v, causal, 2e-4, block_q=block_q,
                          block_k=block_k, block_major=block_major)


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 1)],
                         ids=["mha", "gqa4"])
def test_flash_attention_bf16_grads(heads, kv_heads):
    """bfloat16 operands, float32 accumulation: forward and all three
    gradients against the float32 dense reference on the same inputs."""
    q, k, v = _qkv(8, 1, 384, 384, heads, kv_heads, 64, jnp.bfloat16)
    got = _fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=128,
                                        block_k=128, block_major=256),
        q, k, v)
    want = _fwd_and_grads(lambda q, k, v: _dense_gqa(q, k, v, True), q, k, v)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16, name
        g, w = np.asarray(g, dtype=np.float32), np.asarray(w, np.float32)
        # bfloat16 keeps 8 bits: element-wise to 3e-2 of the tensor's scale
        assert np.max(np.abs(g - w)) <= 3e-2 * np.max(np.abs(w)), name
        assert np.linalg.norm(g - w) <= 1e-2 * np.linalg.norm(w), name


def test_flash_attention_refuses_mismatched_kv_heads():
    q, k, v = _qkv(9, 1, 128, 128, 4, 3, 32)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("Lq,Lk,D,itemsize", [
    (4096, 4096, 128, 2), (2048, 2048, 128, 2), (256, 256, 128, 2),
    (1024, 1024, 64, 2), (1000, 1000, 64, 2), (512, 512, 64, 4),
    (200, 200, 32, 4), (64, 64, 32, 4), (32768, 32768, 128, 2)])
@pytest.mark.parametrize("stream_q", [False, True])
def test_flash_tiles_from_the_shape(Lq, Lk, D, itemsize, stream_q):
    """Tiles never exceed their side, are lane multiples where the side has
    128 rows, and the streamed side stays inside its VMEM budget."""
    fa = sys.modules["ray_tpu.ops.flash_attention"]  # the module
    bq, bk, n, vmem = fa._tiles(fa._Blocks(None, None, None), Lq, Lk, D,
                                itemsize, stream_q)
    assert bq <= Lq and bk <= Lk
    for b, L in ((bq, Lq), (bk, Lk)):
        assert b % 128 == 0 if L >= 128 else b == L
    tile, length = (bq, Lq) if stream_q else (bk, Lk)
    assert 1 <= n and n * tile <= length
    assert 4 * n * tile * D * itemsize <= fa._STREAM_BYTES
    assert vmem < 64 * 2 ** 20


def _windowed_reference(q, k, v, window):
    """Plain masked attention through a window that counts the token itself."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    t = jnp.arange(q.shape[1])
    seen = (t[:, None] >= t[None]) & (t[:, None] - t[None] < window)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("length,window,blocks", [
    (300, 100, dict(block_q=128, block_k=128, block_major=128)),
    (300, 100, dict(block_q=128, block_k=128, block_major=256)),
    (512, 130, dict(block_q=128, block_k=128)),
    (257, 8, dict(block_q=128, block_k=128, block_major=128)),
    (640, 300, dict(block_q=256, block_k=128, block_major=256)),
    (384, 1000, {}),
])
def test_flash_attention_through_a_window(length, window, blocks):
    """The forward with a window that is no multiple of the block against the
    plain masked product (interpreted): the walk starts at the first tile the
    window reaches, whatever the major block, and a window longer than the
    sequence is causal attention."""
    keys = jax.random.split(jax.random.PRNGKey(length + window), 3)
    q = jax.random.normal(keys[0], (2, length, 4, 32))
    k, v = (jax.random.normal(key, (2, length, 2, 32)) for key in keys[1:])
    out = flash_attention(q, k, v, window=window, **blocks)
    np.testing.assert_allclose(out, _windowed_reference(q, k, v, window),
                               atol=3e-6)
    if window >= length:
        np.testing.assert_array_equal(
            out, flash_attention(q, k, v, causal=True, **blocks))


def test_flash_attention_refuses_a_windows_gradient_and_a_window_uncaused():
    q = jnp.ones((1, 128, 2, 32))
    with pytest.raises(NotImplementedError, match="no backward pass"):
        jax.grad(lambda q: flash_attention(q, q, q, window=8).sum())(q)
    with pytest.raises(ValueError, match="needs causal"):
        flash_attention(q, q, q, causal=False, window=8)
