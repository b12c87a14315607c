"""A stack of several kinds of block (``TransformerConfig.layer_kinds``), its
two operations (``ops/linear_attention.py``, ``ops/sparse_attention.py``) and
the guard that the default configuration runs the code it ran: at tiny sizes
on the CPU, the kernels in interpreter mode against their ``jax.numpy``
fallbacks."""

import dataclasses
import hashlib
import math
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark import sala_reference
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (DENSE, LINEAR, SPARSE,
                                        TransformerConfig)
from ray_tpu.ops.linear_attention import decay_rates, linear_attention
from ray_tpu.ops.sparse_attention import (SparseConfig, _pooled_probs,
                                          pooled_keys, selected_blocks,
                                          sparse_attention)
from ray_tpu.train.step import make_lm_train_step

# every constant of the published selection divided by 8, dense_len by 256
TINY_SPARSE = SparseConfig(kernel_size=4, kernel_stride=2, block_size=8,
                           topk=8, init_blocks=1, window_size=16,
                           dense_len=32)
SPARSE_DIMS = dataclasses.asdict(TINY_SPARSE)
# one period of the published pattern: a sparse layer and three linear ones
MIXED = TransformerConfig(
    vocab_size=96, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=96,
    max_seq_len=128, dtype=jnp.float32, use_flash=False, remat=False,
    rope_theta=1e4, norm_eps=1e-6,
    layer_kinds=(SPARSE, LINEAR, LINEAR, LINEAR), layer_ids=(9, 10, 11, 12),
    decay_depth=32, embed_scale=12.0, residual_scale=1.4 / math.sqrt(32),
    logit_scale=16 / 64, sparse=TINY_SPARSE)
MIXED_DIMS = {
    "vocab_size": 96, "d_model": 64, "n_layers": 4, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 16, "d_ff": 96, "rope_theta": 1e4,
    "rms_norm_eps": 1e-6, "published_layers": 32, "scale_emb": 12.0,
    "scale_depth": 1.4, "dim_model_base": 16,
    "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3,
    "layer_ids": [9, 10, 11, 12], "sparse_config": SPARSE_DIMS}


def _qkv(seed, batch, length, heads, kv_heads, d=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (batch, length, h, d), dtype)
            for k, h in zip(keys, (heads, kv_heads, kv_heads))]


@pytest.fixture(scope="module")
def mixed():
    """Seeded weights of the mixed stack, every norm weight moved off its
    initial 1 so that a norm left out, or its weight, shows."""
    params = transformer.init_params(jax.random.PRNGKey(37), MIXED)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(38), len(leaves))
    moved = [p * (1 + 0.3 * jax.random.normal(k, p.shape))
             if "norm" in jax.tree_util.keystr(path)
             or "ln" in jax.tree_util.keystr(path) else p
             for (path, p), k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, moved)


def _last_logits(params, tokens, cfg):
    x = transformer.backbone(params, tokens, cfg)
    return transformer.head(params, x, cfg)[:, -1]


# -- the default configuration runs the code it ran ------------------------------


def _digest(fn, *args):
    """A short hash of a function's jaxpr, addresses struck out."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


DEFAULTS = {
    "plain": dict(use_flash=False),
    "gqa_flash": dict(n_kv_heads=2, use_flash=True),
    "looped": dict(use_flash=False, n_passes=2, post_norm=True,
                   exit_beta=0.05),
}
# (parameter tree, init_params, loss_fn, backbone + head, grad of loss_fn):
# what the commit before this file's read, by ``_digests`` below. The looped
# stack's loss and its gradient are as PR 43 left them (its loss goes
# through a ``custom_vjp`` whose backward pass sums the shared weights'
# gradient in place; they read e088e082885428c7 and 648cd66b3866c34c
# before); its tree, its ``init_params`` and its served forward are still
# that commit's. The gradient through the flash kernel is as PR 49 left it:
# the forward rule names its output and log-sum-exp (``checkpoint_name``),
# two ``name`` equations that lower to nothing; with the names taken out it
# reads what it read (``UNNAMED``).
UNNAMED = {"gqa_flash": "a0ff16809fa92166"}
PARENTS = {
    "gqa_flash": ("63404d2623127361", "99f2a7b645963f3c", "2c1b73ef55decd15",
                  "240904abf0065166", "bcb8e9fdcc7e42c8"),
    "looped": ("d130dc7b3d9d7533", "05bd95983f06787a", "cd3c2ca959cf9922",
               "e75bf9fc4ba4aac0", "11e311ad6b7780a9"),
    "plain": ("38bdac6aed5a5dd1", "69fd1c7846b0dcc9", "8971081d5fc69b5d",
              "8edc61409c69836f", "0c2530f2c152b09c"),
}


def _digests(name):
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                            d_ff=96, max_seq_len=32, dtype=jnp.bfloat16,
                            **DEFAULTS[name])
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), key)
    tokens = jax.ShapeDtypeStruct((2, 17), jnp.int32)
    tree = hashlib.sha256(str(jax.tree_util.tree_flatten_with_path(
        params)).encode()).hexdigest()[:16]
    return (
        tree,
        _digest(lambda k: transformer.init_params(k, cfg), key),
        _digest(lambda p, t: transformer.loss_fn(p, t, cfg), params, tokens),
        _digest(lambda p, t: transformer.head(
            p, transformer.backbone(p, t, cfg), cfg), params, tokens),
        _digest(jax.grad(lambda p, t: transformer.loss_fn(p, t, cfg)),
                params, tokens))


@pytest.mark.parametrize("name", sorted(DEFAULTS))
def test_default_kinds_trace_the_programs_the_parent_commit_traced(
        name, monkeypatch):
    """The five accepted cells run a stack of one dense kind: with
    ``layer_kinds`` left alone the parameter tree and the jaxprs of
    ``init_params``, ``loss_fn``, its gradient and ``backbone`` + ``head``
    are the parent commit's, to the letter. Where the gradient goes through
    the flash kernel the only difference from the parent's jaxpr is the two
    ``name`` equations of the kernel's forward rule (PR 49), which lower to
    nothing: with ``checkpoint_name`` an identity the gradient's jaxpr is
    the parent's again."""
    assert _digests(name) == PARENTS[name]
    if name in UNNAMED:
        monkeypatch.setattr(sys.modules["ray_tpu.ops.flash_attention"],
                            "checkpoint_name", lambda x, name: x)
        assert _digests(name) == PARENTS[name][:4] + (UNNAMED[name],)


def test_all_dense_kinds_are_the_default_stack():
    cfg = dataclasses.replace(MIXED, layer_kinds=(DENSE,) * 4)
    assert not cfg.mixed and cfg.kinds == (DENSE,) * 4
    plain = dataclasses.replace(MIXED, layer_kinds=None)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 96)
    a = transformer.init_params(jax.random.PRNGKey(0), cfg)
    b = transformer.init_params(jax.random.PRNGKey(0), plain)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    np.testing.assert_array_equal(_last_logits(a, tokens, cfg),
                                  _last_logits(b, tokens, plain))


@pytest.mark.parametrize("kinds,says", [
    ((SPARSE, "window", LINEAR, LINEAR), "layer_kinds"),
    ((SPARSE, LINEAR), "layer_kinds"),
    ((DENSE, LINEAR, DENSE, DENSE), "of its own kind only"),
])
def test_layer_kinds_are_known_names_one_a_layer(kinds, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(MIXED, layer_kinds=kinds)


# -- the tree of a mixed stack ---------------------------------------------------


def test_mixed_stack_holds_a_stacked_tree_a_kind(mixed):
    assert sorted(mixed["blocks"]) == [LINEAR, SPARSE]
    sparse, linear = mixed["blocks"][SPARSE], mixed["blocks"][LINEAR]
    assert sorted(sparse["attn"]) == ["k_norm", "q_norm", "wg", "wk", "wo",
                                      "wq", "wv"]
    assert sorted(linear["attn"]) == ["k_norm", "o_norm", "q_norm", "wg",
                                      "wk", "wo", "wq", "wv"]
    assert sparse["attn"]["wk"].shape == (1, 64, 2, 16)     # 2 K/V heads
    assert linear["attn"]["wk"].shape == (3, 64, 4, 16)     # as many as q's
    assert linear["attn"]["o_norm"].shape == (3, 64)
    assert sparse["attn"]["wg"].shape == (1, 64, 4, 16)
    assert jax.tree.structure(mixed) == jax.tree.structure(
        transformer.logical_axes(MIXED),
        is_leaf=lambda a: isinstance(a, tuple))
    # a layer draws from its own place's key whatever its kind: the MLP of
    # layer 2 is the plain stack's layer 2
    plain = transformer.init_params(
        jax.random.PRNGKey(37), dataclasses.replace(MIXED, layer_kinds=None))
    fresh = transformer.init_params(jax.random.PRNGKey(37), MIXED)
    np.testing.assert_array_equal(fresh["blocks"][LINEAR]["mlp"]["wi"][1],
                                  plain["blocks"]["mlp"]["wi"][2])
    np.testing.assert_array_equal(fresh["blocks"][SPARSE]["attn"]["wq"][0],
                                  plain["blocks"]["attn"]["wq"][0])


# -- the program against the plain reference ---------------------------------------


@pytest.mark.parametrize("length", [24, 128], ids=["dense-branch",
                                                  "selecting"])
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["fallback", "interpreted"])
def test_mixed_stack_matches_the_reference(mixed, length, kernels):
    """``[minicpm4, lightning x3]`` at a length under and a length past the
    tiny ``dense_len`` (16 blocks, of which a query keeps 8)."""
    cfg = dataclasses.replace(MIXED, use_flash=kernels)
    tokens = jax.random.randint(jax.random.PRNGKey(39), (2, length), 0, 96)
    got = _last_logits(mixed, tokens, cfg)
    want = sala_reference.last_logits(mixed, tokens, MIXED_DIMS)
    assert float(jnp.abs(want).max()) > 0.3      # logits of ordinary size
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("part", ["q_norm", "gate", "window", "decay",
                                  "scale_emb"])
def test_a_part_left_out_or_changed_is_far_outside_that_agreement(mixed,
                                                                  part):
    tokens = jax.random.randint(jax.random.PRNGKey(39), (2, 96), 0, 96)
    want = sala_reference.last_logits(mixed, tokens, MIXED_DIMS)
    params, dims = mixed, MIXED_DIMS
    if part == "q_norm":
        params = jax.tree.map(lambda p: p, mixed)
        attn = params["blocks"][SPARSE]["attn"]
        attn["q_norm"] = jnp.ones_like(attn["q_norm"])
    elif part == "gate":
        params = jax.tree.map(lambda p: p, mixed)
        attn = params["blocks"][LINEAR]["attn"]
        attn["wg"] = jnp.zeros_like(attn["wg"])
    elif part == "window":
        dims = {**dims, "sparse_config": {**SPARSE_DIMS, "window_size": 8}}
    elif part == "decay":
        dims = {**dims, "layer_ids": [9, 20, 21, 22]}
    else:
        dims = {**dims, "scale_emb": 1.0}
    got = sala_reference.last_logits(params, tokens, dims)
    assert float(jnp.abs(got - want).max()) > 2e-3


@pytest.mark.parametrize("length,bucket", [(24, 32), (40, 64), (72, 96)])
def test_last_logits_are_unchanged_by_padding_to_a_bucket(mixed, length,
                                                          bucket):
    """Every layer is causal, so the padding on the right reaches no real
    position, in the dense branch (a bucket of ``dense_len`` itself) and
    where queries select (padded positions are never selected)."""
    tokens = jax.random.randint(jax.random.PRNGKey(40), (2, length), 0, 96)
    padded = jnp.pad(tokens, ((0, 0), (0, bucket - length)))
    x = transformer.backbone(mixed, padded, MIXED)[:, :length]
    got = transformer.head(mixed, x, MIXED)[:, -1]
    np.testing.assert_allclose(got, _last_logits(mixed, tokens, MIXED),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        got, sala_reference.last_logits(mixed, tokens, MIXED_DIMS),
        atol=2e-5, rtol=0)


def test_a_mixed_stack_has_no_train_step():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    with pytest.raises(ValueError, match=r"'linear', 'sparse'.*backward"):
        make_lm_train_step(MIXED, mesh)
    with pytest.raises(ValueError, match="'linear'.*backward"):
        make_lm_train_step(dataclasses.replace(
            MIXED, layer_kinds=(LINEAR,) * 4), mesh)


def test_a_mixed_stack_under_a_mesh_is_refused(mixed):
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("data",))
    tokens = jnp.zeros((2, 24), jnp.int32)
    with pytest.raises(ValueError, match="no mesh"):
        transformer.backbone(mixed, tokens, MIXED, mesh)


# -- chunked linear attention -------------------------------------------------------


def _recurrence(q, k, v, rates):
    """Token by token: S_t = lam S_{t-1} + k_t^T v_t, o_t = q_t S_t /
    sqrt(d)."""
    B, _, H, D = q.shape
    lam = jnp.exp(-jnp.asarray(rates))[None, :, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv
        state = lam * state + jnp.einsum("bhd,bhe->bhde", kt, vt)
        return state, jnp.einsum("bhd,bhde->bhe", qt, state) / math.sqrt(D)

    _, out = jax.lax.scan(step, jnp.zeros((B, H, D, D)), tuple(
        x.transpose(1, 0, 2, 3) for x in (q, k, v)))
    return out.transpose(1, 0, 2, 3)


@pytest.mark.parametrize("length,chunk", [(64, 16), (100, 16), (40, 64)],
                         ids=["whole-chunks", "ragged", "one-chunk"])
def test_chunked_linear_attention_is_the_recurrence(length, chunk):
    q, k, v = _qkv(1, 2, length, 4, 4)
    rates = decay_rates(4, 10, 32)
    want = _recurrence(q, k, v, rates)
    got = linear_attention(q, k, v, rates, chunk=chunk, use_kernel=False)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-5)


@pytest.mark.parametrize("length", [64, 100, 300],
                         ids=["one-step", "ragged", "three-steps"])
def test_linear_attention_kernel_interpreted_matches_its_fallback(length):
    """Chunks of 16 tokens, 8 a grid step: at 300 tokens the state crosses
    two steps' borders."""
    q, k, v = _qkv(2, 2, length, 4, 4)
    rates = decay_rates(4, 3, 8)
    want = linear_attention(q, k, v, rates, chunk=16, use_kernel=False)
    got = linear_attention(q, k, v, rates, chunk=16, use_kernel=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_decay_rates_are_lightnings_slopes_fading_with_depth():
    rates = decay_rates(32, 9, 32)
    assert rates.dtype == np.float32 and rates.shape == (32,)
    np.testing.assert_allclose(rates[0], 2 ** -0.25 * (1 - 9 / 31 + 1e-5),
                               rtol=1e-6)
    np.testing.assert_allclose(rates[31], 2 ** -8 * (1 - 9 / 31 + 1e-5),
                               rtol=1e-6)
    # the last layer hardly forgets, the first forgets fastest
    assert decay_rates(32, 31, 32)[0] < 1e-4 < decay_rates(32, 0, 32)[31]


def test_a_head_that_forgets_at_once_overflows_nothing():
    q, k, v = _qkv(3, 1, 64, 2, 2)
    out = linear_attention(q, k, v, np.array([60.0, 1e-6], np.float32),
                           chunk=16, use_kernel=False)
    assert bool(jnp.isfinite(out).all())
    # with lam = e^-60 a position sees itself alone
    alone = jnp.einsum("blhd,blhd->blh", q, k)[..., None] * v / 4.0
    np.testing.assert_allclose(out[:, :, 0], alone[:, :, 0], atol=1e-5)


# -- block-sparse attention -----------------------------------------------------------


def _dense(q, k, v):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["fallback", "interpreted"])
def test_sparse_attention_is_dense_when_topk_covers_the_sequence(kernels):
    q, k, v = _qkv(4, 2, 64, 4, 2)
    cfg = dataclasses.replace(TINY_SPARSE, topk=8)         # 64 / 8 blocks
    got = sparse_attention(q, k, v, cfg, use_kernel=kernels)
    np.testing.assert_allclose(got, _dense(q, k, v), atol=2e-5, rtol=0)


@pytest.mark.parametrize("length", [128, 192])
def test_sparse_attention_kernels_interpreted_match_their_fallback(length):
    q, k, v = _qkv(5, 2, length, 4, 2)
    want = sparse_attention(q, k, v, TINY_SPARSE, use_kernel=False)
    got = sparse_attention(q, k, v, TINY_SPARSE, use_kernel=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # and it is not dense attention: most blocks are left out
    assert float(jnp.abs(want - _dense(q, k, v)).max()) > 1e-2


def test_the_selection_keeps_the_first_block_the_window_and_the_best():
    q, k, _ = _qkv(6, 1, 128, 4, 2)
    grouped = q.transpose(0, 2, 1, 3).reshape(1, 2, 2, 128, 16)
    keys = pooled_keys(k.transpose(0, 2, 1, 3), TINY_SPARSE)
    assert keys.shape == (1, 2, 63, 16)
    np.testing.assert_allclose(keys[0, 0, 5], k[0, 10:14, 0].mean(0),
                               atol=1e-6)
    probs = _pooled_probs(grouped, keys, 0.25, TINY_SPARSE)
    kept = np.asarray(selected_blocks(probs, TINY_SPARSE))     # [1, 2, L, 16]
    first = np.arange(16) * 8
    # block b = tokens [8 b, 8 b + 8) meets the pooled windows [2 j, 2 j + 4)
    overlap = [[j for j in range(63) if 2 * j < b + 8 and 2 * j + 4 > b]
               for b in first]
    assert overlap[3] == [11, 12, 13, 14, 15]
    for t in (0, 7, 8, 40, 100, 127):
        row = kept[0, 0, t]
        visible = t // 8 + 1
        assert row[0] and not row[visible:].any()
        # the blocks that hold (t - 16, t]
        forced = np.zeros(16, bool)
        forced[max(0, (t - 15) // 8):visible] = forced[0] = True
        assert row[forced].all()
        # then the best by the largest pooled score that meets them, until 8
        # are kept; neighbours share a window, so two may tie at the last
        # place, and then both are kept
        score = np.array([np.asarray(probs)[0, 0, t, js].max()
                          for js in overlap])
        free = np.nonzero(~forced[:visible])[0]
        want = forced.copy()
        if len(free) > 8 - forced.sum():
            last = np.sort(score[free])[::-1][8 - forced.sum() - 1]
            want[free[score[free] >= last]] = True
        else:
            want[:visible] = True
        np.testing.assert_array_equal(row, want)
        assert min(visible, 8) <= row.sum() <= min(visible, 9)
    # the two groups rank by their own heads
    assert (kept[0, 0] != kept[0, 1]).any()


def test_sparse_attention_refuses_what_it_cannot_tile():
    q, k, v = _qkv(7, 1, 60, 4, 2)
    with pytest.raises(ValueError, match="blocks of 8"):
        sparse_attention(q, k, v, TINY_SPARSE, use_kernel=False)
    with pytest.raises(ValueError, match="multiples of kernel_stride"):
        SparseConfig(kernel_size=5, kernel_stride=2)
