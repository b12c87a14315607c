"""Model stack: transformer + resnet forward/grad, sharded training step."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import resnet, transformer
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.parallel import (MeshConfig, ShardingRules, batch_sharding,
                              build_mesh, shard_pytree)

TINY = TransformerConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                         max_seq_len=128, dtype=jnp.float32, use_flash=False)


def test_transformer_forward_shapes():
    params = transformer.init_params(jax.random.PRNGKey(0), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    logits = transformer.apply(params, tokens, TINY)
    assert logits.shape == (2, 16, 256)
    assert logits.dtype == jnp.float32


def test_transformer_loss_decreases():
    cfg = TINY
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(transformer.loss_fn)(
            params, tokens, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses


def test_transformer_causality():
    """Changing a future token must not affect earlier logits."""
    params = transformer.init_params(jax.random.PRNGKey(0), TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 256)
    logits1 = transformer.apply(params, tokens, TINY)
    tokens2 = tokens.at[0, -1].set((tokens[0, -1] + 1) % 256)
    logits2 = transformer.apply(params, tokens2, TINY)
    np.testing.assert_allclose(np.asarray(logits1[0, :-1]),
                               np.asarray(logits2[0, :-1]),
                               rtol=1e-4, atol=1e-4)


def test_transformer_flash_matches_dense():
    cfg_dense = TINY
    cfg_flash = TransformerConfig(**{**cfg_dense.__dict__, "use_flash": True})
    params = transformer.init_params(jax.random.PRNGKey(0), cfg_dense)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    l_dense = transformer.apply(params, tokens, cfg_dense)
    l_flash = transformer.apply(params, tokens, cfg_flash)
    np.testing.assert_allclose(np.asarray(l_dense), np.asarray(l_flash),
                               rtol=2e-4, atol=2e-4)


GQA = TransformerConfig(**{**TINY.__dict__, "n_kv_heads": 2})
GQA_FLASH = TransformerConfig(**{**GQA.__dict__, "use_flash": True})


def _loss_and_grads(cfg, params, tokens, mesh=None):
    return jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_fn(p, tokens, cfg, mesh)))(params)


def _assert_same_loss_and_grads(got, want, tol):
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=tol)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol,
                                   atol=tol)


def test_transformer_gqa_flash_matches_dense():
    """The kernel reads K/V heads by index; the einsum path repeats them.
    Loss and every gradient (wk and wv sum their group's query heads)."""
    params = transformer.init_params(jax.random.PRNGKey(0), GQA)
    assert params["blocks"]["attn"]["wk"].shape[2] == 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 256)
    _assert_same_loss_and_grads(_loss_and_grads(GQA_FLASH, params, tokens),
                                _loss_and_grads(GQA, params, tokens), 2e-4)


@pytest.mark.parametrize("axes", [dict(data=4, tensor=2),
                                  dict(data=2, fsdp=2, tensor=2)],
                         ids=["data4xtensor2", "data2xfsdp2xtensor2"])
def test_transformer_gqa_flash_under_a_mesh(eight_device_mesh, axes):
    """Under a mesh K and V go into the kernel's shard_map with their own
    head count split like q's: each device's query heads find their K/V
    heads on it, and the result is the single-device one."""
    params = transformer.init_params(jax.random.PRNGKey(0), GQA)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, 256)
    want = _loss_and_grads(GQA, params, tokens)
    mesh = build_mesh(MeshConfig(**axes), eight_device_mesh)
    rules = ShardingRules()
    sharded = shard_pytree(params, transformer.logical_axes(GQA), mesh, rules)
    toks = jax.device_put(tokens, batch_sharding(mesh, rules, ndim=2))
    got = _loss_and_grads(GQA_FLASH, sharded, toks, mesh)
    _assert_same_loss_and_grads(got, want, 5e-4)


def test_transformer_gqa_flash_refuses_heads_that_do_not_split(
        eight_device_mesh):
    cfg = TransformerConfig(**{**GQA_FLASH.__dict__, "n_kv_heads": 1})
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 256)
    mesh = build_mesh(MeshConfig(data=4, tensor=2), eight_device_mesh)
    with pytest.raises(ValueError, match="n_kv_heads=1"):
        transformer.apply(params, tokens, cfg, mesh=mesh)


def test_transformer_gqa_ring_attention_matches(eight_device_mesh):
    """Ring attention still gets K and V repeated to q's heads."""
    params = transformer.init_params(jax.random.PRNGKey(0), GQA)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    ref = transformer.apply(params, tokens, GQA, mesh=None)
    mesh = build_mesh(MeshConfig(data=2, seq=4), eight_device_mesh)
    out = transformer.apply(params, tokens, GQA_FLASH, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_transformer_sharded_train_step(eight_device_mesh):
    """Full fsdp+tp sharded train step over the 8-device mesh."""
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2),
                      eight_device_mesh)
    cfg = TINY
    rules = ShardingRules()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    axes = transformer.logical_axes(cfg)
    params = shard_pytree(params, axes, mesh, rules)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
    tokens = jax.device_put(tokens, batch_sharding(mesh, rules, ndim=2))

    @jax.jit
    def step(params, tokens):
        loss, grads = jax.value_and_grad(transformer.loss_fn)(
            params, tokens, cfg)
        return loss, grads

    loss, grads = step(params, tokens)
    assert np.isfinite(float(loss))
    # Gradient shardings follow parameter shardings.
    g = grads["blocks"]["mlp"]["wi"]
    p = params["blocks"]["mlp"]["wi"]
    assert g.sharding == p.sharding


def test_transformer_seq_parallel_matches(eight_device_mesh):
    """Ring-attention path (seq axis > 1) matches single-device output."""
    cfg = TINY
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    ref = transformer.apply(params, tokens, cfg, mesh=None)
    mesh = build_mesh(MeshConfig(data=2, seq=4), eight_device_mesh)
    out = transformer.apply(params, tokens, cfg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_resnet_forward_and_grad():
    cfg = resnet.resnet18(num_classes=10)
    params = resnet.init_params(jax.random.PRNGKey(0), cfg)
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits = resnet.apply(params, images, cfg)
    assert logits.shape == (2, 10)
    labels = jnp.array([1, 2])
    loss, grads = jax.value_and_grad(resnet.loss_fn)(params, images, labels,
                                                     cfg)
    assert np.isfinite(float(loss))
    gw = grads["head"]["w"]
    assert np.isfinite(np.asarray(gw)).all()


def test_resnet50_params_count():
    cfg = resnet.resnet50()
    params = resnet.init_params(jax.random.PRNGKey(0), cfg)
    n = transformer.num_params(params)
    # torchvision resnet50 has ~25.6M params
    assert 20e6 < n < 30e6, n
