"""The latent mixer among the mixer-and-FFN kinds (Kanana-2: MLA without a q
bottleneck, a dense FFN or shared experts beside a sigmoid-routed mixture of
which a share is held), the mixture's backward pass, the flash backward at
two head widths and the bias that load moves: at tiny sizes on the CPU, the
kernel in interpreter mode, against ``benchmark/kanana2_reference.py``,
autodiff of a dense masked mixture and plain attention."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import kanana2_reference
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (LATENT, LATENT_MOE, LatentConfig,
                                        TransformerConfig)
from ray_tpu.ops import flash_attention
from ray_tpu.parallel import MeshConfig, ShardingRules, build_mesh, expert
from ray_tpu.parallel.expert import ExpertConfig, held_experts_apply
from ray_tpu.train.step import make_lm_train_step
from test_shortcut_layer import _einsum_attention, _init, _seeded, held

# d 32, 4 heads of 16 + 8 / 16, kv rank 16, 16 routed experts of 24, top-3,
# shared experts of 2 x 24, a dense layer and two mixture layers
MLA = LatentConfig(q_rank=None, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16)
EXPERTS = ExpertConfig(n_routed=16, n_zero=0, top_k=3, scale=2.448, width=24,
                       held=(0, 4), score="sigmoid", choice_bias=True,
                       normalize=True, shared_width=48)
TINY = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=3, n_heads=4, d_ff=48, max_seq_len=64,
    dtype=jnp.float32, use_flash=False, remat=True, rope_theta=1e4,
    norm_eps=1e-6, layer_kinds=(LATENT, LATENT_MOE, LATENT_MOE),
    layer_ids=(0, 1, 2), latent=MLA, experts=EXPERTS)
TINY_DIMS = {
    "vocab_size": 96, "d_model": 32, "n_layers": 3, "layer_ids": [0, 1, 2],
    "n_heads": 4, "nope_dim": 16, "rope_dim": 8, "v_dim": 16, "kv_rank": 16,
    "d_ff": 48, "rope_theta": 1e4, "rms_norm_eps": 1e-6, "first_k_dense": 1,
    "n_routed": 16, "top_k": 3, "scale": 2.448, "expert_width": 24,
    "shared_width": 48, "held": [0, 4], "bias_rate": 1e-3}


def _tokens(seed, batch=2, length=41):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0,
                              TINY.vocab_size)


def _one_device():
    return build_mesh(MeshConfig(data=1), jax.devices()[:1])


@pytest.fixture(scope="module")
def params():
    """``TINY``'s seeded weights: ``use_flash`` and ``remat`` change no leaf,
    so every test of the whole model reads these."""
    return _seeded(TINY, 71)


@pytest.fixture(scope="module")
def tiny_step():
    """``make_lm_train_step`` of ``TINY`` with the default optimizer, compiled
    by the first test that steps it."""
    return make_lm_train_step(TINY, _one_device(), ShardingRules())


# -- the tree --------------------------------------------------------------------


def test_the_tree_has_no_q_bottleneck_and_the_shared_experts(params):
    assert set(params["blocks"]) == {LATENT, LATENT_MOE}
    latent = params["blocks"][LATENT_MOE]["latent"]
    assert set(latent) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert latent["wq"].shape == (2, 32, 4, 24)
    assert latent["wkv_b"].shape == (2, 16, 4, 32)
    moe = params["blocks"][LATENT_MOE]
    assert set(moe) == {"latent", "router", "router_bias", "experts",
                        "shared", "ln1", "ln2"}
    assert moe["shared"]["wi"].shape == (2, 32, 48)
    assert moe["experts"]["wi"].shape == (2, 4, 32, 24)
    assert set(params["blocks"][LATENT]) == {"latent", "mlp", "ln1", "ln2"}
    axes = transformer.logical_axes(TINY)
    is_axes = lambda a: isinstance(a, tuple)   # noqa: E731
    flat_axes = jax.tree_util.tree_flatten_with_path(axes, is_leaf=is_axes)[0]
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [p for p, _ in flat] == [p for p, _ in flat_axes]
    assert all(a.ndim == len(b) for (_, a), (_, b) in zip(flat, flat_axes))


def test_a_share_draws_its_own_published_experts():
    """Expert e of a share that holds it is expert e of the share that holds
    all: the shares of different chips are disjoint and consistent, and
    what every chip computes alike (the shared experts, MLA) is the same."""
    whole = _init(jax.random.PRNGKey(3), held(TINY, 0, 16))
    share = _init(jax.random.PRNGKey(3), held(TINY, 8, 4))
    for name in ("wi", "wg", "wo"):
        np.testing.assert_array_equal(
            share["blocks"][LATENT_MOE]["experts"][name],
            whole["blocks"][LATENT_MOE]["experts"][name][:, 8:12])
    for part in ("shared", "latent", "router", "router_bias"):
        jax.tree.map(np.testing.assert_array_equal,
                     share["blocks"][LATENT_MOE][part],
                     whole["blocks"][LATENT_MOE][part])


def test_a_latent_layer_without_its_sizes_is_refused_by_name():
    with pytest.raises(ValueError, match="latent attention needs latent="):
        dataclasses.replace(TINY, latent=None)
    with pytest.raises(ValueError, match="a mixture needs experts="):
        dataclasses.replace(TINY, experts=None)


# -- the program against the reference, float32 ---------------------------------------


@pytest.fixture(scope="module")
def reference_logits(params):
    tokens = _tokens(1)[:, :-1]
    return tokens, jax.jit(jax.vmap(lambda row: kanana2_reference.logits(
        params, row, TINY_DIMS)))(tokens)


@pytest.mark.parametrize("use_flash", [False, True], ids=["einsum", "flash"])
def test_logits_are_the_references(params, reference_logits, use_flash):
    cfg = dataclasses.replace(TINY, use_flash=use_flash)
    tokens, want = reference_logits
    got = jax.jit(lambda p, t: transformer.apply(p, t, cfg))(params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.fixture(scope="module")
def reference_gradients(params):
    tokens = _tokens(2)
    return tokens, jax.jit(lambda p, t: kanana2_reference.loss_and_grads(
        p, t, TINY_DIMS))(params, tokens)


@pytest.mark.parametrize("use_flash,remat", [(False, False), (True, True)],
                         ids=["einsum", "flash+remat"])
def test_the_loss_and_every_leafs_gradient_are_the_references(
        params, reference_gradients, use_flash, remat):
    """``loss_and_metrics`` (the training path: a scan over the stacked
    leaves, the mixture's and the kernel's own backward passes) against
    autodiff of the plain reference, leaf by leaf; the bias gets none."""
    cfg = dataclasses.replace(TINY, use_flash=use_flash, remat=remat)
    tokens, (want, want_grads) = reference_gradients
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_and_metrics(p, tokens, cfg),
        has_aux=True))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert len(flat) == len(wanted) == 28
    for path, got in flat:
        # float32 sums in another order: 1e-5 of the leaf's largest entry
        scale = float(jnp.max(jnp.abs(wanted[path])))
        np.testing.assert_allclose(got, wanted[path], atol=1e-5 * scale + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))
    assert not np.any(np.asarray(
        grads["blocks"][LATENT_MOE]["router_bias"]))
    # the loads the step's metrics carry: every token's top-3 are counted
    assert metrics["moe_load"].shape == (2, 4)
    assert metrics["moe_counts"].shape == (2, 16)
    np.testing.assert_array_equal(metrics["moe_counts"].sum(-1), 2 * 40 * 3)
    np.testing.assert_array_equal(metrics["moe_load"][:, :3].sum(-1),
                                  2 * 40 * 3)
    np.testing.assert_array_equal(metrics["moe_counts"][:, :4].sum(-1),
                                  metrics["moe_load"][:, 0])


def test_serving_and_training_read_the_same_states(params):
    """The serving scan (over indices, the tree closed over) and the training
    scan (over the stacked leaves) are one layer function."""
    tokens = _tokens(3)
    served = jax.jit(lambda p, t: transformer.token_nll(
        p, transformer.backbone(p, t[:, :-1], TINY), t[:, 1:], TINY))(
            params, tokens)
    trained, _ = jax.jit(lambda p, t: transformer.loss_and_metrics(
        p, t, TINY))(params, tokens)
    np.testing.assert_allclose(jnp.mean(served), trained, rtol=1e-6)


def test_a_mesh_of_more_than_one_device_is_refused_by_name(params):
    mesh = build_mesh(MeshConfig(data=2), jax.devices()[:2])
    tokens = _tokens(4)
    with pytest.raises(ValueError, match="one device only"):
        transformer.loss_and_metrics(params, tokens, TINY, mesh)
    with pytest.raises(ValueError, match="one device only"):
        transformer.apply(params, tokens, TINY, mesh)
    # a mesh of one is the cell's
    jax.jit(lambda p, t: transformer.apply(p, t, TINY, _one_device()))(
        params, tokens)


# -- the share ties to the model ------------------------------------------------------


def test_eight_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """The routed parts that the shares of all devices compute, plus what
    every device computes alike (the shared experts) counted once, are the
    uncut reference's mixture FFN, all 16 experts held."""
    whole, dims = held(TINY, 0, 16), {**TINY_DIMS, "held": [0, 16]}
    params = _seeded(whole, 71)
    layer = jax.tree.map(lambda p: p[1], params["blocks"][LATENT_MOE])
    u = jax.random.normal(jax.random.PRNGKey(5), (50, 32))
    want = jax.jit(lambda u, l: kanana2_reference.moe_ffn(u, l, dims))(
        u, layer)
    total = jax.jit(kanana2_reference.ffn)(layer["shared"], u)
    for first in range(0, 16, 2):           # eight shares of two experts
        cfg = dataclasses.replace(EXPERTS, held=(first, 2))
        mine = jax.tree.map(lambda p: p[None, first:first + 2],
                            layer["experts"])
        part, load = jax.jit(lambda u, l, mine: held_experts_apply(
            u, l["router"], mine, cfg, 0, bias=l["router_bias"]))(
                u, layer, mine)
        assert int(load[0]) + int(load[1]) == 50 * 3
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)
    # and the program's own layer with every expert held is that sum
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 50, 32))
    stack = jax.tree.map(lambda p: p[1:2], params["blocks"][LATENT_MOE])
    got, _ = jax.jit(lambda stack, x: transformer._parts_block(
        stack, 0, x, jnp.arange(50)[None], whole, LATENT_MOE))(stack, x)
    want_block = jax.jit(lambda l, x: kanana2_reference.block(
        l, x, 1, dims))(layer, x[0])
    np.testing.assert_allclose(got[0], want_block, atol=2e-5)


# -- the mixture's backward pass -------------------------------------------------------


def _dense_mixture(u, router, bias, experts, cfg):
    """Every held expert applied to every token, masked by the choice."""
    idx, w = expert.route(u, router, cfg, bias)
    first, count = cfg.held
    mine = jnp.sum(jnp.where(idx[:, :, None] == first + jnp.arange(count),
                             w[:, :, None], 0.0), axis=1)
    each = jax.vmap(lambda p: (jax.nn.silu(u @ p["wi"]) * (u @ p["wg"]))
                    @ p["wo"])(experts)
    return jnp.einsum("tc,ctd->td", mine, each)


def _mixture_case(which, T=70, count=4):
    """A router that sends every pair to one held expert, none to a held
    one, or spreads them, with more pairs than one chunk takes; the first
    ``count`` of the 16 experts held (all 16: the whole mixture, whose
    forward combines by gather)."""
    d, cfg = 32, dataclasses.replace(EXPERTS, held=(0, count))
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    u = jax.random.normal(ks[0], (T, d))
    router = jax.random.normal(ks[1], (d, 16)) / np.sqrt(d)
    bias = jnp.zeros((16,))
    if which == "one":          # the choice is on score + bias
        bias = bias.at[jnp.array([2, 9, 13])].set(10.0)
    elif which == "none":
        bias = bias.at[jnp.array([8, 9, 13])].set(10.0)
    experts = {"wi": jax.random.normal(ks[2], (count, d, 24)) / np.sqrt(d),
               "wg": jax.random.normal(ks[3], (count, d, 24)) / np.sqrt(d),
               "wo": jax.random.normal(ks[4], (count, 24, d)) / np.sqrt(24)}
    g = jax.random.normal(ks[5], (T, d))
    return u, router, bias, experts, g, cfg


@pytest.mark.parametrize("which,count", [
    ("spread", 4), ("one", 4), ("none", 4), ("spread", 16), ("one", 16)])
@pytest.mark.parametrize("chunk", [1024, 16])
def test_the_mixtures_backward_is_autodiff_of_a_dense_masked_mixture(
        monkeypatch, which, count, chunk):
    """``held_experts_apply``'s ``custom_vjp`` against autodiff of the dense
    masked mixture: ``d u``, the router's gradient (through the weights, not
    the choice), the three weight gradients; with every pair on one held
    expert, on none, and with a list longer than one chunk (16 rows a
    step: 70 tokens' held pairs take several). With ``count == n_routed``
    the forward combines by gather and the backward is the one that walks
    the list from the kept operands: still one function and its gradient
    (every pair held; "one" puts them on three experts and leaves thirteen
    without a row)."""
    monkeypatch.setattr(expert, "CHUNK_ROWS", chunk)
    u, router, bias, experts, g, cfg = _mixture_case(which, count=count)
    assert cfg.all_held == (count == 16)

    def program(u, router, bias, experts):
        out, load = held_experts_apply(
            u, router, jax.tree.map(lambda p: p[None], experts), cfg, 0,
            bias=bias)
        return jnp.sum(out * g), load

    def dense(u, router, bias, experts):
        return jnp.sum(_dense_mixture(u, router, bias, experts, cfg) * g)

    (value, load), got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1, 2, 3), has_aux=True))(u, router, bias, experts)
    want_value, want = jax.jit(jax.value_and_grad(
        dense, argnums=(0, 1, 2, 3)))(u, router, bias, experts)
    held_pairs = ({"spread": None, "one": 70, "none": 0}[which]
                  if count == 4 else 210)
    if held_pairs is not None:
        assert int(load[0]) == held_pairs
    else:
        assert int(load[0]) > 2 * 16        # several steps of 16 rows
    np.testing.assert_allclose(value, want_value, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=3e-5)
    assert not np.any(np.asarray(got[2]))           # the bias gets none
    if which == "one":      # the other held experts saw no row: no gradient
        chosen = [c for c in (2, 9, 13) if c < count]
        idle = jnp.array([c for c in range(count) if c not in chosen])
        for name in ("wi", "wg", "wo"):
            assert not np.any(np.asarray(got[3][name][idle]))
            assert all(np.any(np.asarray(got[3][name][c])) for c in chosen)


def test_the_mixtures_backward_reads_the_stack_at_the_layers_index():
    """Two layers' experts stacked, the second meant: the gradient lands in
    the second layer's groups and the first's stay zero."""
    u, router, bias, experts, g, cfg = _mixture_case("spread", T=40)
    stacked = jax.tree.map(lambda p: jnp.stack([p * 0.5, p]), experts)

    def program(stacked, layer):
        out, _ = held_experts_apply(u, router, stacked, cfg, layer, bias=bias)
        return jnp.sum(out * g)

    got = jax.jit(jax.grad(program))(stacked, 1)
    alone = jax.jit(jax.grad(lambda e: program(
        jax.tree.map(lambda p: p[None], e), 0)))(experts)
    for name in ("wi", "wg", "wo"):
        assert not np.any(np.asarray(got[name][0]))
        np.testing.assert_allclose(got[name][1], alone[name], atol=1e-6)


# -- the flash backward at two widths -------------------------------------------------


@pytest.mark.parametrize("shape", [(1, 256, 2, 192, 128), (2, 72, 4, 24, 16)],
                         ids=["192/128", "24/16"])
def test_flash_backward_at_two_widths_is_plain_attentions(shape):
    """The published widths (one head pair, 256 tokens, two tiles of 128) and
    the tiny ones, interpret mode, float32."""
    B, L, H, D, Dv = shape
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(ks[0], (B, L, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, Dv))
    g = jax.random.normal(ks[3], (B, L, H, Dv))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(g * flash_attention(
        *a, block_q=128, block_k=128)), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(g * _einsum_attention(*a)),
                            argnums=(0, 1, 2)))(q, k, v)
    assert [a.shape for a in got] == [q.shape, k.shape, v.shape]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


# -- what the training stack's checkpoint keeps of the kernel ---------------------------


def _kernels(jaxpr):
    """The names of the ``pallas_call``s anywhere inside a jaxpr."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for inner in jax.core.jaxprs_in_params(eqn.params):
            names += _kernels(inner)
    return sorted(names)


def _scans_kernels(jaxpr):
    """``(reverse, kernels in the body)`` of every ``scan`` that holds a
    kernel, outermost scans only, in the order the jaxpr has them."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            held = _kernels(eqn.params["jaxpr"].jaxpr)
            if held:
                found.append((eqn.params["reverse"], held))
            continue
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _scans_kernels(inner)
    return found


FLASH = dataclasses.replace(TINY, use_flash=True, remat=True)


def _loss_and_grads(cfg, params, tokens):
    """Traced anew at every call (the ``jit`` is of a new function), so a
    patch of what the trace reads takes effect."""
    return jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_and_metrics(p, tokens, cfg)[0]))(params)


def test_keeping_the_kernels_output_changes_no_bit_of_the_gradient(
        monkeypatch, params):
    """``_parts_states``' checkpoint keeps the flash forward's output and
    log-sum-exp (``flash_attention.KEPT``): the loss and every leaf's
    gradient are, bit for bit, those of the same stack under a plain
    ``jax.checkpoint`` (the policy patched away: the kept arrays are the
    ones the recomputation would make again), and the file's tolerances away
    from the stack without ``remat``."""
    tokens = _tokens(5)
    loss, grads = _loss_and_grads(FLASH, params, tokens)
    free_loss, free_grads = _loss_and_grads(
        dataclasses.replace(FLASH, remat=False), params, tokens)
    with monkeypatch.context() as patched:
        patched.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
        plain_loss, plain_grads = _loss_and_grads(FLASH, params, tokens)
    assert float(loss) == float(plain_loss)
    plain = dict(jax.tree_util.tree_flatten_with_path(plain_grads)[0])
    free = dict(jax.tree_util.tree_flatten_with_path(free_grads)[0])
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(plain) == 28
    for path, got in flat:
        name = jax.tree_util.keystr(path)
        np.testing.assert_array_equal(got, plain[path], err_msg=name)
        scale = float(jnp.max(jnp.abs(free[path])))
        np.testing.assert_allclose(got, free[path], err_msg=name,
                                   atol=1e-5 * scale + 1e-9)
    np.testing.assert_allclose(loss, free_loss, rtol=1e-6)


def test_the_training_stacks_backward_runs_no_flash_forward(monkeypatch,
                                                            params):
    """In the jaxpr of the differentiated loss each run's forward scan holds
    one ``flash_fwd`` and its backward scan ``flash_dq`` and ``flash_dkv``
    and no ``flash_fwd``: the recomputed block's second call has no reader
    once the first call's results are kept. Under a plain ``jax.checkpoint``
    (the parent's program) the backward scan held it."""
    tokens = _tokens(6)

    def scans():
        return _scans_kernels(jax.make_jaxpr(jax.grad(
            lambda p: transformer.loss_and_metrics(p, tokens, FLASH)[0]))(
                params).jaxpr)

    # a run of one dense-FFN layer, a run of two mixture layers
    assert scans() == 2 * [(False, ["flash_fwd"])] + 2 * [
        (True, ["flash_dkv", "flash_dq"])]
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    assert scans() == 2 * [(False, ["flash_fwd"])] + 2 * [
        (True, ["flash_dkv", "flash_dq", "flash_fwd"])]


def test_the_dense_stacks_backward_still_runs_the_flash_forward():
    """``apply_layers``' checkpoint has no policy (its cells' roofline
    readers count two forwards by rule: ROADMAP S1b): the names in the
    kernel's forward rule keep nothing there and the backward scan's body
    recomputes ``flash_fwd``."""
    dense = TransformerConfig(
        vocab_size=96, d_model=32, n_layers=2, n_heads=4, d_ff=48,
        max_seq_len=64, dtype=jnp.float32, use_flash=True, remat=True)
    params = jax.eval_shape(_init, jax.random.PRNGKey(7), dense)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: transformer.loss_and_metrics(p, _tokens(7), dense)[0]))(
            params).jaxpr
    assert _scans_kernels(jaxpr) == [
        (False, ["flash_fwd"]), (True, ["flash_dkv", "flash_dq", "flash_fwd"])]


# -- the step: the bias, the optimizer, the loss ---------------------------------------


def test_the_bias_moves_by_the_load_and_is_no_parameter():
    """``b_e += bias_rate * sign(mean load - load_e)`` from the counts the
    step's own forward saw, as the reference's ``moved_bias`` says; the
    optimizer holds no state for it, decays nothing of it, and its gradient
    is zero."""
    cfg = TINY
    init_fn, step_fn, shard = make_lm_train_step(
        cfg, _one_device(), ShardingRules(),
        optimizer=optax.adamw(1e-2, weight_decay=0.5))
    key = jax.random.PRNGKey(31)
    state = init_fn(key)
    params = _init(key, cfg)
    moments = jax.tree_util.tree_flatten_with_path(state[1])[0]
    assert moments and not any("router_bias" in jax.tree_util.keystr(path)
                               for path, _ in moments)
    tokens = np.asarray(_tokens(7))
    state, metrics = step_fn(state, shard(tokens))
    counts = np.asarray(metrics["moe_counts"])
    before = params["blocks"][LATENT_MOE]["router_bias"]
    after = state[0]["blocks"][LATENT_MOE]["router_bias"]
    # the reference's counts, from the same float32 weights
    for l in range(2):
        layer = kanana2_reference.layer_of(params, 1 + l, TINY_DIMS)
        want = kanana2_reference.moved_bias(before[l], counts[l], TINY_DIMS)
        np.testing.assert_allclose(after[l], want, atol=1e-7)
        assert layer["router_bias"].shape == (16,)
    moved = np.asarray(after - before) / expert.BIAS_RATE
    np.testing.assert_allclose(moved, np.sign(counts.mean(-1, keepdims=True)
                                              - counts), atol=1e-3)
    assert {-1.0, 1.0} <= set(np.round(moved).ravel())
    # a weight beside it did decay and move
    assert np.any(np.asarray(state[0]["blocks"][LATENT_MOE]["router"]
                             != params["blocks"][LATENT_MOE]["router"]))
    expert.flush_loads()


def test_a_steps_loss_falls_over_twenty_steps_on_a_repeated_batch():
    cfg = dataclasses.replace(TINY, use_flash=True)
    init_fn, step_fn, shard = make_lm_train_step(
        cfg, _one_device(), ShardingRules(), optimizer=optax.adamw(3e-3))
    state = init_fn(jax.random.PRNGKey(41))
    tokens = shard(np.asarray(_tokens(8)))
    losses = []
    for _ in range(20):
        state, metrics = step_fn(state, tokens)
        losses.append(float(metrics["loss"]))
    assert np.all(np.isfinite(losses))
    assert losses[-1] < 0.8 * losses[0]
    assert metrics["grad_norm"] > 0
    expert.flush_loads()


def test_the_steps_loads_reach_the_counters_without_a_call_back(tiny_step):
    """No ``debug_callback`` in the step's jaxpr (its program keeps its key
    in the compile cache); the counters are fed from the step's ``moe_load``
    once the device has it."""
    init_fn, step_fn, shard = tiny_step
    state = init_fn(jax.random.PRNGKey(51))
    tokens = shard(np.asarray(_tokens(9)))
    text = str(jax.make_jaxpr(step_fn.__wrapped__)(state, tokens))
    assert "callback" not in text
    expert.flush_loads()
    seen = []
    real = expert._record

    def record(cfg, loads):
        seen.append((cfg, np.asarray(loads)))
        return real(cfg, loads)

    try:
        expert._record = record
        state, metrics = step_fn(state, tokens)
        expert.flush_loads()
    finally:
        expert._record = real
    assert len(seen) == 1 and seen[0][0] == TINY.experts
    np.testing.assert_array_equal(seen[0][1], metrics["moe_load"])


def test_the_default_optimizers_rate_warms_up_where_the_configuration_asks(
        tiny_step):
    """``warmup_steps`` raises the default optimizer's rate linearly from
    ``3e-4 / steps``: the first update of a weight is that much, not 3e-4
    (Adam's first step moves every weight by its rate); without the field
    the optimizer is the dense cells' constant one."""
    from ray_tpu.train.step import _default_optimizer
    tokens = np.asarray(_tokens(10))
    moved = {}
    assert TINY.warmup_steps == 0
    for steps in (0, 10):
        init_fn, step_fn, shard = tiny_step if steps == 0 else (
            make_lm_train_step(dataclasses.replace(TINY, warmup_steps=steps),
                               _one_device(), ShardingRules()))
        state = init_fn(jax.random.PRNGKey(3))
        before = np.asarray(state[0]["lm_head"])
        state, _ = step_fn(state, shard(tokens))
        moved[steps] = float(np.max(np.abs(np.asarray(state[0]["lm_head"])
                                           - before)))
    expert.flush_loads()
    assert moved[0] == pytest.approx(3e-4, rel=0.05)
    assert moved[10] == pytest.approx(3e-5, rel=0.05)
    rate = _default_optimizer(dataclasses.replace(TINY, warmup_steps=10))
    assert rate.init({"w": jnp.zeros(2)}) is not None
