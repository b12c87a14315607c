"""The plain reference against ``ray_tpu.models.transformer`` at a tiny
size, on seeded random weights."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import reference
from benchmark.harness import prng_key
from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig

# the program's RMSNorm epsilon, so that the comparison is of the
# mathematics and not of the one known departure
PROGRAM_EPS = 1e-6


def _pair(n_heads=4, n_kv_heads=2, theta=1e6):
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=3,
                            n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=160,
                            max_seq_len=32, dtype=jnp.float32,
                            use_flash=False, remat=False, rope_theta=theta)
    dims = dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
                rms_norm_eps=PROGRAM_EPS, rope_theta=theta)
    params = transformer.init_params(prng_key(7), cfg)
    tokens = jax.random.randint(prng_key(8), (3, 33), 0, 128)
    return cfg, dims, params, tokens


@pytest.mark.parametrize("heads,kv_heads,theta", [
    (4, 2, 1e6), (4, 4, 1e4), (8, 2, 1e6)],
    ids=["gqa2", "mha", "gqa4"])
def test_last_position_logits_agree(heads, kv_heads, theta):
    cfg, dims, params, tokens = _pair(heads, kv_heads, theta)
    got = transformer.apply(params, tokens[:, :-1], cfg)[:, -1]
    want = reference.last_logits(params, tokens[:, :-1], dims)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_loss_and_gradient_norm_agree():
    cfg, dims, params, tokens = _pair()
    loss, grads = jax.value_and_grad(
        lambda p: transformer.loss_fn(p, tokens, cfg))(params)
    want_loss, want_norm = reference.loss_and_grad_norm(params, tokens, dims)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(optax.global_norm(grads)) == pytest.approx(
        float(want_norm), rel=1e-4)


def test_the_published_epsilon_is_a_small_known_departure():
    cfg, dims, params, tokens = _pair()
    published = dict(dims, rms_norm_eps=1e-5)
    a = reference.last_logits(params, tokens[:, :-1], dims)
    b = reference.last_logits(params, tokens[:, :-1], published)
    worst = float(jnp.abs(a - b).max())
    assert 0 < worst < 0.1


def test_the_reference_is_causal_and_takes_bfloat16_weights_as_float32():
    cfg, dims, params, tokens = _pair()
    changed = tokens.at[:, 20].set((tokens[:, 20] + 1) % 128)
    h1 = reference.hidden(params, tokens[:, :-1], dims)
    h2 = reference.hidden(params, changed[:, :-1], dims)
    assert np.allclose(h1[:, :20], h2[:, :20], atol=1e-6)
    assert not np.allclose(h1[:, 20:], h2[:, 20:], atol=1e-6)
    half = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    assert reference.last_logits(half, tokens[:, :-1], dims).dtype == \
        jnp.float32


def test_prng_key_takes_seeds_beyond_32_bits():
    a, b = prng_key(2**31 + 5), prng_key(5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    assert np.array_equal(jax.random.key_data(prng_key(2**31 + 5)),
                          jax.random.key_data(a))
