"""The two kinds of layer a Granite 4.0-H stack is made of (``mamba``,
``attn``) against ``benchmark/granite_reference.py`` at tiny widths: the
Mamba-2 mixer, NoPE grouped-query attention at a softmax scale that is not
``head_dim ** -0.5``, the block with its four multipliers, and what the two
kinds share with the kinds that were there."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_tiny as tiny
from benchmark import granite_reference as reference
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (ATTN, MAMBA, MambaConfig,
                                        TransformerConfig)
from ray_tpu.observability import metric_names

DIMS = tiny.DIMS


@pytest.fixture(scope="module")
def run():
    """The tiny tree, a batch of tokens and the reference's logits; the
    program's forward compiled once a path."""
    params = tiny.params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 64)
    return {
        "params": params, "tokens": tokens,
        "want": reference.tree_logits(params, tokens, DIMS),
        "apply": {flash: jax.jit(lambda p, t, f=flash: transformer.apply(
            p, t, tiny.config(f))) for flash in (False, True)}}


def test_every_leaf_is_drawn_as_the_reference_draws_it():
    ours = tiny.params()
    theirs = jax.jit(lambda k: reference.draw_tree(k, DIMS))(
        jax.random.PRNGKey(tiny.SEED))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    jax.tree.map(np.testing.assert_array_equal, ours, theirs)
    mamba = ours["blocks"][MAMBA]["mamba"]
    assert mamba["w_in"].shape == (3, 32, 2 * 64 + 2 * 8 + 4)
    assert mamba["conv"].shape == (3, 80, 4)
    a, dt = -np.exp(mamba["a_log"]), jax.nn.softplus(mamba["dt_bias"])
    assert a.min() >= -16.1 and a.max() <= -0.99
    assert dt.min() >= 0.9e-3 and dt.max() <= 0.11
    # the small leaves lie on bfloat16's grid: the serving cast keeps them
    for name in ("a_log", "dt_bias", "d_skip", "conv_bias"):
        np.testing.assert_array_equal(
            mamba[name], mamba[name].astype(jnp.bfloat16).astype(jnp.float32))
    assert "lm_head" not in ours and set(ours["blocks"]) == {MAMBA, ATTN}


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["fallback", "kernels"])
def test_the_stack_is_the_reference(run, use_flash):
    """Both kinds of layer, the four multipliers and the tied head: every
    position's logits, through the ``jax.numpy`` paths and through the two
    kernels interpreted."""
    got = run["apply"][use_flash](run["params"], run["tokens"])
    np.testing.assert_allclose(got, run["want"], rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("kind, layer", [(MAMBA, 0), (ATTN, 2)])
def test_a_mixer_is_the_references(kind, layer):
    cfg, params = tiny.config(), tiny.params()
    ref_layer = reference.from_tree(params, layer, DIMS)
    u = jax.random.normal(jax.random.PRNGKey(9), (2, 20, 32), jnp.float32)
    j = 0 if kind == ATTN else layer
    part = jax.tree.map(lambda p: p[j], params["blocks"][kind][kind])
    if kind == MAMBA:
        got = transformer._mamba_mixer(part, u, cfg)
        want = jax.vmap(lambda row: reference.mamba(ref_layer["mixer"], row,
                                                    DIMS))(u)
    else:
        positions = jnp.broadcast_to(jnp.arange(20)[None], (2, 20))
        got = transformer._attention_mixer(part, u, positions, cfg, None)
        want = jax.vmap(lambda row: reference.attention(
            ref_layer["mixer"], row, DIMS))(u)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("change, moved", [
    ({"attn_scale": None}, True),           # 1/64 is not 64 ** -0.5
    ({"rope": True}, True),                 # no positions
    ({"residual_scale": 1.0}, True), ({"embed_scale": 1.0}, True),
    ({"logit_scale": 1.0}, True), ({"rope_theta": 5.0}, False),
])
def test_each_multiplier_and_the_missing_positions_reach_the_logits(
        run, change, moved):
    cfg = dataclasses.replace(tiny.config(), **change)
    got = transformer.apply(run["params"], run["tokens"], cfg)
    assert bool(np.abs(np.asarray(got - run["want"])).max() > 1e-4) == moved


def test_lengths_leave_a_padded_prompts_state_at_its_last_real_position():
    """A right-padded prompt keeps what the prompt alone keeps: the state and
    the convolution's tail at the last real position, the K and V of the real
    positions."""
    cfg, params = tiny.config(), tiny.params()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, 64)
    _, padded, _ = transformer.prefill(params, tokens, jnp.array([11]), cfg)
    _, alone, _ = transformer.prefill(params, tokens[:, :11],
                                      jnp.array([11]), cfg)
    np.testing.assert_allclose(padded.ssm, alone.ssm, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(padded.conv, alone.conv, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(padded.k[:, :, :11], alone.k, rtol=1e-5,
                               atol=1e-7)
    # without lengths the padding would have run through the state
    _, through, _ = transformer.prefill(params, tokens, jnp.array([16]), cfg)
    assert np.abs(np.asarray(through.ssm - alone.ssm)).max() > 1e-3


def test_the_kinds_guards_and_the_scope():
    assert transformer.PARTS[MAMBA] == ("mamba", "mlp")
    assert transformer.PARTS[ATTN] == ("attn", "mlp")
    assert transformer.DECODABLE[:2] == (MAMBA, ATTN)
    with pytest.raises(ValueError, match="needs mamba="):
        TransformerConfig(n_layers=1, layer_kinds=(MAMBA,))
    with pytest.raises(ValueError, match="among each other only"):
        TransformerConfig(n_layers=2, layer_kinds=(MAMBA, transformer.LINEAR),
                          mamba=MambaConfig(2, 4, 4))
    # the two kinds stand among the other mixer-and-FFN kinds
    TransformerConfig(n_layers=2, layer_kinds=(MAMBA, transformer.CONV),
                      mamba=MambaConfig(2, 4, 4))
    from ray_tpu.train.step import FORWARD_ONLY
    assert MAMBA in FORWARD_ONLY and ATTN not in FORWARD_ONLY
    assert "mamba" in metric_names.LATER_DEVICE_SCOPES
    lowered = jax.jit(lambda p, t: transformer.apply(
        p, t, tiny.config())).lower(tiny.params(),
                                    jnp.zeros((1, 8), jnp.int32))
    names = lowered.as_text(debug_info=True)
    assert "mamba/core" in names and "attn/core" in names


def test_a_residual_scale_reaches_the_older_mixer_and_ffn_kinds_too():
    """``_parts_block`` applies ``residual_scale`` to every kind it runs; at
    1 it emits nothing (the accepted cells' jaxprs are held by hash
    elsewhere)."""
    base = TransformerConfig(vocab_size=32, d_model=16, n_layers=2,
                             n_heads=2, d_ff=24, dtype=jnp.float32,
                             remat=False, use_flash=False,
                             layer_kinds=(transformer.CONV, transformer.CONV))
    params = transformer.init_params(jax.random.PRNGKey(0), base)
    tokens = jnp.arange(12).reshape(1, 12) % 32
    one = transformer.apply(params, tokens, base)
    half = transformer.apply(params, tokens,
                             dataclasses.replace(base, residual_scale=0.5))
    assert np.abs(np.asarray(one - half)).max() > 1e-3
