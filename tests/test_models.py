"""Model stack: transformer + resnet forward/grad, sharded training step."""

import collections
import dataclasses
import hashlib
import re

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
# one compiled program a configuration: drawn eagerly, a tree costs some
# hundred one-primitive compiles
_init = jax.jit(transformer.init_params, static_argnums=1)


@pytest.fixture(scope="module")
def tiny_params():
    return _init(jax.random.PRNGKey(0), TINY)


def test_transformer_forward_shapes(tiny_params):
    params = tiny_params
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    logits = transformer.apply(params, tokens, TINY)
    assert logits.shape == (2, 16, 256)
    assert logits.dtype == jnp.float32


def test_transformer_loss_decreases():
    cfg = TINY
    params = _init(jax.random.PRNGKey(0), cfg)
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


def test_transformer_causality(tiny_params):
    """Changing a future token must not affect earlier logits."""
    apply = jax.jit(lambda p, t: transformer.apply(p, t, TINY))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 256)
    logits1 = apply(tiny_params, tokens)
    tokens2 = tokens.at[0, -1].set((tokens[0, -1] + 1) % 256)
    logits2 = apply(tiny_params, tokens2)
    np.testing.assert_allclose(np.asarray(logits1[0, :-1]),
                               np.asarray(logits2[0, :-1]),
                               rtol=1e-4, atol=1e-4)


def test_transformer_flash_matches_dense():
    cfg_dense = TINY
    cfg_flash = TransformerConfig(**{**cfg_dense.__dict__, "use_flash": True})
    params = _init(jax.random.PRNGKey(0), cfg_dense)
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
    params = _init(jax.random.PRNGKey(0), GQA)
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
    params = _init(jax.random.PRNGKey(0), GQA)
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
    params = _init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 256)
    mesh = build_mesh(MeshConfig(data=4, tensor=2), eight_device_mesh)
    with pytest.raises(ValueError, match="n_kv_heads=1"):
        transformer.apply(params, tokens, cfg, mesh=mesh)


def test_transformer_gqa_ring_attention_matches(eight_device_mesh):
    """Ring attention still gets K and V repeated to q's heads."""
    params = _init(jax.random.PRNGKey(0), GQA)
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
    params = _init(jax.random.PRNGKey(0), cfg)
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
    params = _init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    ref = transformer.apply(params, tokens, cfg, mesh=None)
    mesh = build_mesh(MeshConfig(data=2, seq=4), eight_device_mesh)
    out = transformer.apply(params, tokens, cfg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_resnet_forward_and_grad():
    cfg = resnet.resnet18(num_classes=10)
    params = jax.jit(resnet.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits = jax.jit(resnet.apply, static_argnums=2)(params, images, cfg)
    assert logits.shape == (2, 10)
    labels = jnp.array([1, 2])
    loss, grads = jax.jit(jax.value_and_grad(resnet.loss_fn),
                          static_argnums=3)(params, images, labels, cfg)
    assert np.isfinite(float(loss))
    gw = grads["head"]["w"]
    assert np.isfinite(np.asarray(gw)).all()


def test_resnet50_params_count():
    cfg = resnet.resnet50()
    params = jax.eval_shape(
        lambda: resnet.init_params(jax.random.PRNGKey(0), cfg))
    n = transformer.num_params(params)
    # torchvision resnet50 has ~25.6M params
    assert 20e6 < n < 30e6, n


# -- the looped decoder (Ouro / LoopLM) against its plain reference -----------

LOOPED = TransformerConfig(
    vocab_size=96, d_model=64, n_layers=3, n_heads=4, d_ff=96, max_seq_len=32,
    dtype=jnp.float32, use_flash=False, remat=True, rope_theta=1e6,
    norm_eps=1e-6, n_passes=3, post_norm=True, exit_beta=0.05)
LOOPED_DIMS = {"n_heads": 4, "n_kv_heads": 4, "rope_theta": 1e6,
               "rms_norm_eps": 1e-6, "total_ut_steps": 3, "exit_beta": 0.05}


@pytest.fixture(scope="module")
def looped():
    """Seeded weights (norm weights and the gate's bias moved off their
    initial 1 and 0, so that every leaf matters) and tokens [2, 17]."""
    @jax.jit
    def seeded():
        params = transformer.init_params(jax.random.PRNGKey(30), LOOPED)
        leaves, tree = jax.tree_util.tree_flatten_with_path(params)
        keys = jax.random.split(jax.random.PRNGKey(31), len(leaves))
        moved = [p + 0.1 * jax.random.normal(k, p.shape)
                 if "ln" in jax.tree_util.keystr(path)
                 or "exit_gate" in jax.tree_util.keystr(path) else p
                 for (path, p), k in zip(leaves, keys)]
        return jax.tree.unflatten(tree, moved)

    tokens = jax.random.randint(jax.random.PRNGKey(32), (2, 17), 0, 96)
    return seeded(), tokens


def test_default_fields_keep_todays_parameter_tree(tiny_params):
    params = tiny_params
    assert sorted(params) == ["blocks", "embed", "lm_head", "ln_f"]
    assert sorted(params["blocks"]) == ["attn", "ln1", "ln2", "mlp"]
    axes = transformer.logical_axes(TINY)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    # the looped configuration grows two norms a block and the gate
    grown = _init(jax.random.PRNGKey(0), LOOPED)
    assert sorted(grown) == ["blocks", "embed", "exit_gate", "lm_head",
                             "ln_f"]
    assert sorted(grown["blocks"]) == ["attn", "ln1", "ln1_post", "ln2",
                                       "ln2_post", "mlp"]
    assert grown["exit_gate"]["w"].shape == (64,)
    assert float(grown["exit_gate"]["b"]) == 0.0
    assert jax.tree.structure(grown) == jax.tree.structure(
        transformer.logical_axes(LOOPED),
        is_leaf=lambda a: isinstance(a, tuple))
    # the gate's key is beside the others: the weights both have are equal
    plain = _init(jax.random.PRNGKey(0), dataclasses.replace(
        LOOPED, n_passes=1, post_norm=False, exit_beta=None))
    np.testing.assert_array_equal(plain["lm_head"], grown["lm_head"])
    np.testing.assert_array_equal(plain["blocks"]["mlp"]["wi"],
                                  grown["blocks"]["mlp"]["wi"])


def test_looped_every_exit_and_the_exit_distribution_match_the_reference(
        looped):
    from benchmark import looped_reference
    params, tokens = looped
    inputs = tokens[:, :-1]
    states = transformer.pass_states(params, inputs, LOOPED)
    assert states.shape == (3, 2, 16, 64)
    want = looped_reference.every_exit_logits(params, inputs, LOOPED_DIMS)
    for t in range(3):
        got = transformer.head(params, states[t], LOOPED)
        np.testing.assert_allclose(got, want[t], rtol=1e-4, atol=1e-4)
    # served: the last pass's logits
    np.testing.assert_allclose(transformer.apply(params, inputs, LOOPED),
                               want[-1], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        looped_reference.last_logits(params, inputs, LOOPED_DIMS),
        want[-1][:, -1], rtol=1e-5, atol=1e-5)
    p = jnp.exp(transformer.exit_log_probs(params, states, LOOPED))
    np.testing.assert_allclose(p.sum(0), np.ones((2, 16)), atol=1e-6)
    np.testing.assert_allclose(
        p, looped_reference.exit_distribution(params, inputs, LOOPED_DIMS),
        rtol=1e-4, atol=1e-6)
    assert 0.0 < float(p.min()) and float(p.max()) < 1.0


def test_looped_loss_metrics_and_every_gradient_leaf_match_the_reference(
        looped):
    from benchmark import looped_reference
    params, tokens = looped
    (loss, metrics), grads = jax.value_and_grad(
        transformer.loss_and_metrics, has_aux=True)(params, tokens, LOOPED)
    want, want_grads = jax.jit(lambda p, t: looped_reference.loss_and_grads(
        p, t, LOOPED_DIMS))(params, tokens)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert float(transformer.loss_fn(params, tokens, LOOPED)) == float(loss)
    _, exit_p, entropy = jax.jit(lambda p, t: looped_reference.loss_and_exits(
        p, t, LOOPED_DIMS))(params, tokens)
    np.testing.assert_allclose(metrics["exit_p"], exit_p, rtol=1e-4)
    assert float(metrics["exit_entropy"]) == pytest.approx(float(entropy),
                                                           rel=1e-4)
    assert float(jnp.sum(metrics["exit_p"])) == pytest.approx(1.0, abs=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    paths = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(paths, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0, path          # the gate's leaves too
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4 * scale,
                                   err_msg=str(path))


def test_shared_blocks_gradient_is_the_sum_over_unshared_copies(looped):
    """Weight sharing tied to the mathematics: give every pass its own
    copy of the stack, and the copies' gradients add up to the shared
    stack's."""
    params, tokens = looped
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def unshared(copies):          # leaves [passes, layers, ...]
        x = params["embed"][inputs]
        states = []
        for t in range(LOOPED.n_passes):
            h = transformer.apply_layers(
                jax.tree.map(lambda p: p[t], copies), x, LOOPED)
            states.append(h)
            x = transformer._rmsnorm(h, params["ln_f"], LOOPED.norm_eps)
        return transformer.loss_from_states(params, jnp.stack(states),
                                            targets, LOOPED)[0]

    copies = jax.tree.map(lambda p: jnp.stack([p] * LOOPED.n_passes),
                          params["blocks"])
    each = jax.jit(jax.grad(unshared))(copies)
    shared = jax.jit(jax.grad(lambda p: transformer.loss_fn(
        p, tokens, LOOPED)))(params)["blocks"]
    for got, parts in zip(jax.tree.leaves(shared), jax.tree.leaves(each)):
        assert float(jnp.max(jnp.abs(parts[0] - parts[1]))) > 0
        np.testing.assert_allclose(got, parts.sum(0), rtol=1e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(got))))


def test_one_pass_leaves_at_the_only_exit_and_is_the_plain_cross_entropy(
        looped):
    params, tokens = looped
    once = dataclasses.replace(LOOPED, n_passes=1)
    (loss, metrics), grads = jax.value_and_grad(
        transformer.loss_and_metrics, has_aux=True)(params, tokens, once)
    plain = dataclasses.replace(once, exit_beta=None)
    assert float(loss) == pytest.approx(
        float(transformer.loss_fn(params, tokens, plain)), rel=1e-6)
    np.testing.assert_allclose(metrics["exit_p"], [1.0])
    assert float(metrics["exit_entropy"]) == 0.0
    assert float(jnp.max(jnp.abs(grads["exit_gate"]["w"]))) == 0.0
    # without a gate there are no exit metrics, whatever the passes
    assert transformer.loss_and_metrics(params, tokens, plain)[1] == {}
    several = dataclasses.replace(LOOPED, exit_beta=None)
    loss, metrics = transformer.loss_and_metrics(params, tokens, several)
    assert metrics == {}
    states = transformer.pass_states(params, tokens[:, :-1], several)
    assert float(loss) == pytest.approx(float(jnp.mean(transformer.token_nll(
        params, states[-1], tokens[:, 1:], several))), rel=1e-6)


def _loss_of_states(params, states, targets, drop_exit=None):
    """``loss_from_states`` rebuilt from its parts, one exit's cross
    entropy left out of the sum where asked."""
    logp = transformer.exit_log_probs(params, states, LOOPED)
    nll = jnp.stack([transformer.token_nll(params, s, targets, LOOPED)
                     for s in states])
    if drop_exit is not None:
        nll = nll.at[drop_exit].set(0.0)
    p = jnp.exp(logp)
    return jnp.mean(jnp.sum(p * nll, 0) + LOOPED.exit_beta
                    * jnp.sum(p * logp, 0))


def _control(name, params, tokens):
    """A loss function that leaves out one part of the mathematics."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if name == "sound":
        return lambda p: transformer.loss_fn(p, tokens, LOOPED)
    if name == "one_pass_fewer":
        fewer = dataclasses.replace(LOOPED, n_passes=2)
        return lambda p: transformer.loss_fn(p, tokens, fewer)
    if name == "no_post_norms":
        bare = dataclasses.replace(LOOPED, post_norm=False)
        return lambda p: transformer.loss_fn(p, tokens, bare)
    if name == "eight_bit_rounding":
        def rounded(p):
            p8 = jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn)
                              .astype(jnp.float32), p)
            return transformer.loss_fn(p8, tokens, LOOPED)
        return rounded
    if name == "exit_dropped":
        return lambda p: _loss_of_states(
            p, transformer.pass_states(p, inputs, LOOPED), targets,
            drop_exit=1)
    assert name == "no_inter_pass_norm"

    def unnormed(p):
        x, states = p["embed"][inputs], []
        for _ in range(LOOPED.n_passes):
            x = transformer.apply_layers(p["blocks"], x, LOOPED)
            states.append(x)
        return _loss_of_states(p, jnp.stack(states), targets)
    return unnormed


@pytest.fixture(scope="module")
def reference_loss_and_norm(looped):
    from benchmark import looped_reference
    return jax.jit(lambda p, t: looped_reference.loss_and_grad_norm(
        p, t, LOOPED_DIMS))(*looped)


@pytest.mark.parametrize("name", [
    "sound", "one_pass_fewer", "exit_dropped", "no_inter_pass_norm",
    "no_post_norms", "eight_bit_rounding"])
def test_a_part_left_out_fails_by_the_looped_adapters_own_tolerances(
        looped, reference_loss_and_norm, name):
    from benchmark.adapters import looped_decoder
    params, tokens = looped
    value, grads = jax.jit(jax.value_and_grad(
        _control(name, params, tokens)))(params)
    want, want_norm = reference_loss_and_norm
    tol = looped_decoder.TOLERANCES
    inside = (abs(float(value) - float(want))
              <= tol["loss_rtol"] * abs(float(want))
              and abs(float(optax.global_norm(grads)) - float(want_norm))
              <= tol["grad_norm_rtol"] * float(want_norm))
    assert inside == (name == "sound")


# -- the loss head that takes its own gradient on the way forward ------------
# (``transformer.weighted_nll``): against the formulation it replaced, kept
# here as the oracle.

def _autodiff_loss_and_metrics(params, tokens, cfg):
    """``loss_and_metrics`` as it was: ``head`` -> ``log_softmax`` ->
    ``take_along_axis`` -> the exit-weighted sum, all left to autodiff."""
    states = transformer.pass_states(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]

    def nll(x):
        logp = jax.nn.log_softmax(transformer.head(params, x, cfg), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    if cfg.exit_beta is None:
        return jnp.mean(nll(states[-1])), {}
    logp = transformer.exit_log_probs(params, states, cfg)
    p = jnp.exp(logp)
    entropy = -jnp.sum(p * logp, axis=0)
    loss = jnp.mean(jnp.sum(p * jnp.stack([nll(s) for s in states]), axis=0)
                    - cfg.exit_beta * entropy)
    return loss, {"exit_p": jnp.mean(p, axis=(1, 2)),
                  "exit_entropy": jnp.mean(entropy)}


HEAD_CASES = {
    "plain": dataclasses.replace(LOOPED, n_passes=1, exit_beta=None),
    "looped": LOOPED,
    "four_passes": dataclasses.replace(LOOPED, n_passes=4),
}


def _head_case(looped, name, dtype=jnp.float32):
    params, tokens = looped
    cfg = dataclasses.replace(HEAD_CASES[name], dtype=dtype)
    if cfg.exit_beta is None:
        params = {k: v for k, v in params.items() if k != "exit_gate"}
    return params, tokens, cfg


VOCAB_APART = 101      # no other dimension of LOOPED has this size


def _vocab_matmuls(fn, *args, vocab=VOCAB_APART) -> int:
    """How many ``dot_general``s with a vocabulary-sized operand or result
    run in one call of ``fn``: a scan's body counts once a turn."""
    def count(jaxpr, turns):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and any(
                    vocab in v.aval.shape for v in eqn.invars + eqn.outvars):
                total += turns
            inner = turns * (eqn.params["length"]
                             if eqn.primitive.name == "scan" else 1)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += count(sub, inner)
        return total
    return count(jax.make_jaxpr(fn)(*args).jaxpr, 1)


@pytest.mark.parametrize("name", ["plain", "looped"])
def test_loss_head_matches_autodiff_in_float32(looped, name):
    params, tokens, cfg = _head_case(looped, name)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_and_metrics(p, tokens, cfg),
        has_aux=True))(params)
    (want, want_metrics), want_grads = jax.jit(jax.value_and_grad(
        lambda p: _autodiff_loss_and_metrics(p, tokens, cfg),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert sorted(metrics) == sorted(want_metrics)
    for key in metrics:
        np.testing.assert_allclose(metrics[key], want_metrics[key],
                                   rtol=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    paths = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(paths, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0, path      # the gate's w and b, ln_f, lm_head, ...
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=str(path))


@pytest.mark.parametrize("name", ["plain", "looped"])
def test_loss_head_matches_autodiff_in_bfloat16(looped, name):
    """In bfloat16 the two differ by roundings (the float32 logits no longer
    pass through a bfloat16 array): each leaf to the reference tests'
    tolerances, taken over the leaf."""
    params, tokens, cfg = _head_case(looped, name, jnp.bfloat16)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_fn(p, tokens, cfg)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _autodiff_loss_and_metrics(p, tokens, cfg)[0]))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-3)
    paths = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(paths, jax.tree.leaves(want_grads)):
        assert got.dtype == ref.dtype == jnp.float32, path
        off = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        assert off < 3e-2, (path, off)
    assert float(optax.global_norm(grads)) == pytest.approx(
        float(optax.global_norm(want_grads)), rel=1e-2)


@pytest.mark.parametrize("name", ["plain", "looped"])
def test_loss_head_scales_with_a_cotangent_that_is_not_one(looped, name):
    params, tokens, cfg = _head_case(looped, name)
    grads = jax.jit(jax.grad(
        lambda p: transformer.loss_fn(p, tokens, cfg)))(params)
    tripled = jax.jit(jax.grad(
        lambda p: 3.0 * transformer.loss_fn(p, tokens, cfg)))(params)
    for got, ref in zip(jax.tree.leaves(tripled), jax.tree.leaves(grads)):
        np.testing.assert_allclose(got, 3.0 * ref, rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(ref))))


@pytest.mark.parametrize("name", ["plain", "looped", "four_passes"])
def test_loss_head_vocabulary_matmuls_are_three_an_exit(looped, name):
    """The static witness that the mechanism engaged: logits, the gradient
    to the states and the gradient to the head, once each an exit (the
    checkpointed map it replaced ran the logits twice: four an exit). The
    primal alone, with nothing differentiated, runs the logits and no
    more, and gives the same loss."""
    # a vocabulary of its own: LOOPED's 96 is also its d_ff
    cfg = dataclasses.replace(HEAD_CASES[name], vocab_size=VOCAB_APART)
    params = _init(jax.random.PRNGKey(34), cfg)
    tokens = looped[1]
    exits = cfg.n_passes if cfg.exit_beta is not None else 1
    assert _vocab_matmuls(
        lambda p: transformer.loss_fn(p, tokens, cfg), params) == exits
    assert _vocab_matmuls(
        jax.grad(lambda p: transformer.loss_fn(p, tokens, cfg)),
        params) == 3 * exits
    assert _vocab_matmuls(
        jax.grad(lambda p: _autodiff_loss_and_metrics(p, tokens, cfg)[0]),
        params) == 3 * exits        # the counter, on the unrolled oracle
    loss = jax.jit(lambda p: transformer.loss_fn(p, tokens, cfg))(params)
    assert float(loss) == pytest.approx(float(jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_fn(p, tokens, cfg)))(params)[0]), rel=1e-6)
    assert float(loss) == pytest.approx(float(jax.jit(
        lambda p: _autodiff_loss_and_metrics(p, tokens, cfg)[0])(params)),
        rel=1e-5)


@pytest.mark.parametrize("name", ["plain", "looped"])
def test_loss_head_under_fsdp4_matches_one_device(eight_device_mesh, looped,
                                                  name):
    """One train step over ``fsdp=4`` (the state and the batch both split)
    against the same step on one device: the loss and the gradient norm."""
    from ray_tpu.train.step import make_lm_train_step
    _, _, cfg = _head_case(looped, name)
    rows = jax.random.randint(jax.random.PRNGKey(34), (4, 17), 0,
                              cfg.vocab_size)
    seen = []
    for mesh in (build_mesh(MeshConfig(data=1), eight_device_mesh[:1]),
                 build_mesh(MeshConfig(fsdp=4), eight_device_mesh[:4])):
        init_fn, step_fn, shard_batch = make_lm_train_step(cfg, mesh)
        _, metrics = step_fn(init_fn(jax.random.PRNGKey(0)),
                             shard_batch(rows))
        seen.append(jax.device_get(metrics))
    one, four = seen
    assert float(four["loss"]) == pytest.approx(float(one["loss"]), rel=1e-5)
    assert float(four["grad_norm"]) == pytest.approx(float(one["grad_norm"]),
                                                     rel=1e-4)
    if cfg.exit_beta is not None:
        np.testing.assert_allclose(four["exit_p"], one["exit_p"], rtol=1e-4)


# -- the looped stack's own backward pass (``_looped_states_summing``): one ----
# float32 accumulator of the stacked blocks' shape, each layer's weight
# gradient added into its slice where it is made.

def _unrolled_states(blocks, ln_f, x, cfg):
    """The plain reference: the stack called pass by pass on the same shared
    tree, everything left to autodiff."""
    states = []
    for _ in range(cfg.n_passes):
        h = transformer.apply_layers(blocks, x, cfg)
        states.append(h)
        x = transformer._rmsnorm(h, ln_f, cfg.norm_eps)
    return jnp.stack(states)


def _loss_from(states_of, params, nudge, tokens, cfg):
    """The training loss with the looped stack ``states_of``'s to run, from
    the embedded tokens plus ``nudge`` (whose gradient is the cotangent of
    the stack's input)."""
    x = params["embed"].astype(cfg.dtype)[tokens[:, :-1]] + nudge
    states = states_of(params["blocks"], params["ln_f"], x, cfg)
    return transformer.loss_from_states(params, states, tokens[:, 1:], cfg)[0]


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "kept"])
@pytest.mark.parametrize("gate", [True, False], ids=["gate", "no_gate"])
@pytest.mark.parametrize("post_norm", [True, False],
                         ids=["post_norm", "pre_norm"])
@pytest.mark.parametrize("n_passes", [2, 3, 4])
def test_looped_backward_matches_autodiff_of_the_unrolled_stack(
        looped, n_passes, post_norm, gate, remat):
    """Every leaf of the loss's gradient (``blocks``, ``ln_f``, ``embed``,
    the head, the gate) and the cotangent of the stack's input, in float32:
    each within 1e-6 of the reference's, taken over the leaf."""
    params, tokens = looped
    cfg = dataclasses.replace(LOOPED, n_passes=n_passes, post_norm=post_norm,
                              exit_beta=0.05 if gate else None, remat=remat)
    if not gate:
        params = {k: v for k, v in params.items() if k != "exit_gate"}
    if not post_norm:
        params = {**params, "blocks": {
            k: v for k, v in params["blocks"].items()
            if not k.endswith("_post")}}
    nudge = jnp.zeros((2, 16, 64))

    def summing(*args):
        return transformer._looped_states_summing(*args, None, None)

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, n: _loss_from(summing, p, n, tokens, cfg),
        argnums=(0, 1)))(params, nudge)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, n: _loss_from(_unrolled_states, p, n, tokens, cfg),
        argnums=(0, 1)))(params, nudge)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    paths = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(paths, jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(ref)) > 0, path
        off = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        assert off < 1e-6, (jax.tree_util.keystr(path), off)


def _eqns(jaxpr, loops=()):
    """Every equation of a jaxpr and of the jaxprs inside it, with the
    ``scan`` equations it stands in (outermost first)."""
    for eqn in jaxpr.eqns:
        yield eqn, loops
        inner = loops + (eqn,) if eqn.primitive.name == "scan" else loops
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inner)


def _stacked_shapes(cfg):
    """How many leaves of ``blocks`` have each shape."""
    blocks = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))["blocks"]
    return collections.Counter(p.shape for p in jax.tree.leaves(blocks))


def _adds_over_a_stacked_leaf(jaxpr, shapes):
    """The ``add`` and ``add_any`` equations of a jaxpr, wherever they
    stand, one of whose operands has a stacked block leaf's shape."""
    return [eqn for eqn, _ in _eqns(jaxpr)
            if eqn.primitive.name in ("add", "add_any")
            and any(v.aval.shape in shapes for v in eqn.invars)]


def test_looped_backward_adds_no_stacked_tree_and_carries_one_accumulator(
        looped):
    """The static witness: nowhere in the gradient's jaxpr is an array of a
    stacked block leaf's shape added to another (left to autodiff, the
    passes' loop adds a pass's stacked gradients to its running sum: the
    counter finds those on the unrolled oracle's shared tree and on the
    scans without ``remat``), and both loops of the backward pass carry one
    float32 array a leaf of ``blocks``."""
    _, tokens = looped
    # 4 passes round 3 layers: no shape of the passes' is a stacked leaf's
    cfg = dataclasses.replace(LOOPED, n_passes=4)
    params = _init(jax.random.PRNGKey(43), cfg)
    shapes = _stacked_shapes(cfg)
    assert sum(shapes.values()) == len(jax.tree.leaves(params["blocks"]))

    def grad_jaxpr(loss):
        return jax.make_jaxpr(jax.grad(loss))(params).jaxpr

    summing = grad_jaxpr(lambda p: transformer.loss_fn(p, tokens, cfg))
    assert _adds_over_a_stacked_leaf(summing, shapes) == []
    for summed_tree_to_tree in (
            lambda p: _loss_from(_unrolled_states, p, 0.0, tokens, cfg),
            lambda p: transformer.loss_fn(
                p, tokens, dataclasses.replace(cfg, remat=False))):
        assert _adds_over_a_stacked_leaf(grad_jaxpr(summed_tree_to_tree),
                                         shapes)

    def carried(eqn):
        n = eqn.params["num_consts"]
        return collections.Counter(
            v.aval.shape for v in eqn.invars[n:n + eqn.params["num_carry"]]
            if v.aval.shape in shapes and v.aval.dtype == jnp.float32)

    backward = {(len(loops), eqn.params["length"]): carried(eqn)
                for eqn, loops in _eqns(summing)
                if eqn.primitive.name == "scan" and eqn.params["reverse"]}
    assert backward == {(0, cfg.n_passes): shapes, (1, cfg.n_layers): shapes}


def _digest(fn, *args):
    """A short hash of a function's jaxpr, addresses struck out (as
    ``tests/test_mixed_stack.py`` takes it)."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name,digest", [
    ("one_pass_trained", "31779b5804fe3248"),
    ("looped_served", "e3ac1b976ebbb548")])
def test_one_pass_trains_and_a_looped_stack_serves_the_parents_program(
        name, digest):
    """The looped backward is taken by a stack applied more than once, where
    its loss is differentiated, and by nothing else: a single pass's
    training jaxpr and the looped forward as a replica calls it
    (``backbone``) are what commit 2a81911 traced, to the letter."""
    cfg = dataclasses.replace(LOOPED, dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    if name == "one_pass_trained":
        once = dataclasses.replace(cfg, n_passes=1)
        got = _digest(jax.grad(lambda p, t: transformer.loss_fn(p, t, once)),
                      params, jax.ShapeDtypeStruct((2, 17), jnp.int32))
    else:
        got = _digest(lambda p, t: transformer.backbone(p, t, cfg),
                      params, jax.ShapeDtypeStruct((2, 16), jnp.int32))
    assert got == digest
