"""The ``shortcut`` layer kind (LongCat-Flash: two latent-attention blocks,
two FFNs and a routed mixture with zero-compute experts a layer), the flash
kernel at unequal head widths, and the expert layer that is told which
experts it holds: at tiny sizes on the CPU, the kernel in interpreter mode,
against ``benchmark/longcat_reference.py`` and plain einsums."""

import dataclasses
import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import longcat_reference
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (KINDS, SHORTCUT, LatentConfig,
                                        TransformerConfig)
from ray_tpu.ops import flash_attention
from ray_tpu.parallel import expert
from ray_tpu.parallel.expert import ExpertConfig, held_experts_apply
from ray_tpu.train.step import make_lm_train_step
from test_mixed_stack import _digest

# d 64, 4 heads of 16 + 8 / 16, ranks 32 / 16, 16 routed + 8 zero experts,
# top-4, 2 layers: every ratio of the published layer at a sixteenth or so
LATENT = LatentConfig(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                      v_dim=16)
EXPERTS = ExpertConfig(n_routed=16, n_zero=8, top_k=4, scale=6.0, width=32,
                       held=(0, 16))
TINY = TransformerConfig(
    vocab_size=96, d_model=64, n_layers=2, n_heads=4, d_ff=96,
    max_seq_len=64, dtype=jnp.float32, use_flash=False, remat=False,
    rope_theta=1e7, norm_eps=1e-5, layer_kinds=(SHORTCUT,) * 2,
    latent=LATENT, experts=EXPERTS)
TINY_DIMS = {
    "vocab_size": 96, "d_model": 64, "n_layers": 2, "n_heads": 4, "d_ff": 96,
    "rope_theta": 1e7, "rms_norm_eps": 1e-5, "q_rank": 32, "kv_rank": 16,
    "nope_dim": 16, "rope_dim": 8, "v_dim": 16, "n_routed": 16, "n_zero": 8,
    "top_k": 4, "scale": 6.0, "expert_width": 32, "held": [0, 16]}


def held(cfg, first, count):
    return dataclasses.replace(cfg, experts=dataclasses.replace(
        cfg.experts, held=(first, count)))


# one compiled program a configuration: drawn eagerly, a tree costs some
# hundred one-primitive compiles
_init = jax.jit(transformer.init_params, static_argnums=1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _seeded(cfg, seed=41):
    """Seeded weights, every norm weight moved off its initial 1 so that a
    norm left out, or its weight, shows (``test_lfm2_layer.py`` and
    ``test_kanana2_layer.py`` draw theirs here too)."""
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [p * (1 + 0.3 * jax.random.normal(k, p.shape))
             if "norm" in jax.tree_util.keystr(path)
             or "ln" in jax.tree_util.keystr(path) else p
             for (path, p), k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, moved)


@pytest.fixture(scope="module")
def params():
    return _seeded(TINY)


def _of_depth(n):
    """``TINY`` and its reference's sizes at ``n`` layers."""
    return (dataclasses.replace(TINY, n_layers=n,
                                layer_kinds=(SHORTCUT,) * n),
            {**TINY_DIMS, "n_layers": n})


def _one_layer(blocks, l):
    """Layer ``l`` cut out of a stacked tree, as a stack of one."""
    return jax.tree.map(lambda p: p[l:l + 1], blocks)


def _reference_logits(params, tokens, dims):
    return jax.jit(lambda p, t: longcat_reference.tree_last_logits(
        p, t, dims))(params, tokens)


def _last_logits(params, tokens, last, cfg):
    x = transformer.backbone(params, tokens, cfg)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    return transformer.head(params, x, cfg)[:, 0]


# -- the kernel at unequal head widths -----------------------------------------------


def _einsum_attention(q, k, v):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("length,kv_heads", [(200, 4), (128, 4), (333, 2)])
def test_flash_kernel_at_24_16_head_widths_against_an_einsum(length,
                                                             kv_heads):
    """q and k heads of 24, v heads of 16, causal, a ragged last tile."""
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    q = jax.random.normal(keys[0], (2, length, 4, 24))
    k = jax.random.normal(keys[1], (2, length, kv_heads, 24))
    v = jax.random.normal(keys[2], (2, length, kv_heads, 16))
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    rep = 4 // kv_heads
    want = _einsum_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2))
    assert got.shape == (2, length, 4, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("length,kv_heads", [(16, 2), (200, 2), (200, 1)])
def test_flash_backward_at_unequal_widths_is_plain_attentions(length,
                                                              kv_heads):
    """q and k of 24 beside v of 16 (interpret mode): dq and dk come at q's
    width, dv at v's, each the gradient of plain attention, with a ragged
    last tile and grouped K/V heads too."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (2, length, 2, 24))
    k = jax.random.normal(ks[1], (2, length, kv_heads, 24))
    v = jax.random.normal(ks[2], (2, length, kv_heads, 16))
    g = jax.random.normal(ks[3], (2, length, 2, 16))
    rep = 2 // kv_heads

    def plain(q, k, v):
        return jnp.sum(g * _einsum_attention(q, jnp.repeat(k, rep, 2),
                                             jnp.repeat(v, rep, 2)))

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(q, k, v, block_q=128,
                                           block_k=128))

    got = jax.jit(jax.grad(kernel, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(plain, argnums=(0, 1, 2)))(q, k, v)
    assert [a.shape[-1] for a in got] == [24, 24, 16]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_flash_shape_error_names_the_widths_it_takes():
    q = jnp.ones((1, 16, 2, 24))
    with pytest.raises(ValueError, match="head width is its own"):
        flash_attention(q, jnp.ones((1, 16, 2, 16)), jnp.ones((1, 16, 2, 16)))
    with pytest.raises(ValueError, match="head width is its own"):
        flash_attention(q, q, jnp.ones((1, 8, 2, 16)))


def test_flash_at_equal_head_widths_traces_what_the_parent_commit_traced(
        monkeypatch):
    """Every accepted cell calls the kernel with v as wide as q and k: the
    forward's and the backward's jaxprs are the parent commit's, to the
    letter (read off commit cb00583 by this function). Since PR 49 the
    gradient's jaxpr holds two ``name`` equations more (the forward rule
    names its output and log-sum-exp for a checkpoint's policy), which lower
    to nothing: that is its only difference from the parent's, and with
    ``checkpoint_name`` an identity it reads the parent's digest."""
    q = jax.ShapeDtypeStruct((2, 200, 4, 16), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 200, 2, 16), jnp.bfloat16)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    assert (_digest(attend, q, k, k),
            _digest(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)) == (
        "56ae453cd98670d3", "1ce9693422dd360d")
    monkeypatch.setattr(sys.modules["ray_tpu.ops.flash_attention"],
                        "checkpoint_name", lambda x, name: x)
    assert _digest(jax.grad(loss, argnums=(0, 1, 2)), q, k,
                   k) == "46ccfd123954c22c"


# -- the expert layer ----------------------------------------------------------------


def _mixture_weights(seed=0, d=64, cfg=EXPERTS):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    count = cfg.held[1]
    return (jax.random.normal(ks[0], (d, cfg.n_outputs)),
            {"wi": jax.random.normal(ks[1], (count, d, cfg.width)) / 8,
             "wg": jax.random.normal(ks[2], (count, d, cfg.width)) / 8,
             "wo": jax.random.normal(ks[3], (count, cfg.width, d)) / 6})


def _every_expert_on_every_token(u, router, experts, cfg):
    """The plain sum: no sort, no groups."""
    idx, w = expert.route(u, router, cfg)
    out = jnp.sum(jnp.where(idx >= cfg.n_routed, w, 0), -1)[:, None] * u
    for e in range(cfg.held[1]):
        mine = jnp.sum(jnp.where(idx == cfg.held[0] + e, w, 0), -1)
        y = (jax.nn.silu(u @ experts["wi"][e]) * (u @ experts["wg"][e])) \
            @ experts["wo"][e]
        out = out + mine[:, None] * y
    return out


def _alone(experts):
    """One layer's leaves [count, ...] as a stack of one (its layer: 0)."""
    return jax.tree.map(lambda p: p[None], experts)


@pytest.mark.parametrize("first,count,rows", [(0, 16, 1024), (4, 4, 16),
                                              (12, 4, 7), (0, 16, 16),
                                              (0, 16, 7)])
def test_held_experts_part_is_the_plain_sum(monkeypatch, first, count, rows):
    """Whatever the share held and however many steps the dropless loop
    takes (``rows`` pairs a step); all 16 held combine by gather, and a
    third of their picks are zero-compute ones, which have no row."""
    monkeypatch.setattr(expert, "CHUNK_ROWS", rows)
    cfg = dataclasses.replace(EXPERTS, held=(first, count))
    router, experts = _mixture_weights(1, cfg=cfg)
    u = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
    got, load = held_experts_apply(u, router, _alone(experts), cfg, 0)
    want = _every_expert_on_every_token(u, router, experts, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5)
    idx, _ = expert.route(u, router, cfg)
    n_held = int(jnp.sum((idx >= first) & (idx < first + count)))
    n_zero = int(jnp.sum(idx >= cfg.n_routed))
    assert load.tolist()[:3] == [n_held, 200 - n_held - n_zero, n_zero]
    assert load[3] == max(int(jnp.sum(idx == first + e))
                          for e in range(count))


def _forced_router(chosen, cfg=EXPERTS, d=64, tokens=40):
    """A router whose every token picks exactly ``chosen`` (by a large
    logit on a constant feature) and tokens that carry that feature."""
    router = np.zeros((d, cfg.n_outputs), np.float32)
    router[0, list(chosen)] = 50.0 + np.arange(len(chosen))
    u = np.array(jax.random.normal(jax.random.PRNGKey(3), (tokens, d)))
    u[:, 0] = 1.0
    return jnp.asarray(router), jnp.asarray(u)


@pytest.mark.parametrize("rows", [1024, 16])
def test_dropless_when_every_token_goes_to_one_held_expert(monkeypatch,
                                                           rows):
    """The worst imbalance: held expert 5 is handed every token (and three
    absent experts the rest of the picks), the other three held experts
    none; no capacity, nothing dropped."""
    monkeypatch.setattr(expert, "CHUNK_ROWS", rows)
    cfg = dataclasses.replace(EXPERTS, held=(4, 4))
    router, u = _forced_router((5, 0, 1, 2))
    _, experts = _mixture_weights(4, cfg=cfg)
    got, load = held_experts_apply(u, router, _alone(experts), cfg, 0)
    np.testing.assert_allclose(
        got, _every_expert_on_every_token(u, router, experts, cfg),
        rtol=1e-5, atol=1e-5)
    assert load.tolist() == [40, 120, 0, 40]
    assert float(jnp.abs(got).max()) > 0


def test_no_token_for_any_held_expert_gives_the_zero_compute_part_alone():
    cfg = dataclasses.replace(EXPERTS, held=(4, 4))
    router, u = _forced_router((0, 1, 2, 17))
    _, experts = _mixture_weights(5, cfg=cfg)
    got, load = held_experts_apply(u, router, _alone(experts), cfg, 0)
    _, w = expert.route(u, router, cfg)
    # index 17 has the largest logit: top_k lists it first
    np.testing.assert_allclose(got, w[:, :1] * u, rtol=1e-6)
    assert load.tolist() == [0, 120, 40, 0]


def test_a_router_forced_onto_zero_compute_indices_returns_sum_w_times_u():
    router, u = _forced_router((16, 18, 20, 23))
    _, experts = _mixture_weights(6)
    got, load = held_experts_apply(u, router, _alone(experts), EXPERTS, 0)
    _, w = expert.route(u, router, EXPERTS)
    np.testing.assert_allclose(got, jnp.sum(w, -1, keepdims=True) * u,
                               rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, -1), 6.0, rtol=1e-5)  # 6 * sum p
    assert load.tolist() == [0, 0, 160, 0]


def test_the_router_is_float32_whatever_the_operands_are():
    router, _ = _mixture_weights(7)
    u = jax.random.normal(jax.random.PRNGKey(8), (30, 64))
    idx, w = expert.route(u.astype(jnp.bfloat16), router, EXPERTS)
    assert w.dtype == jnp.float32
    # the operands' bfloat16 values, multiplied and summed in float32
    exact = jax.nn.softmax(
        u.astype(jnp.bfloat16).astype(jnp.float32)
        @ router.astype(jnp.bfloat16).astype(jnp.float32), axis=-1)
    p, want = jax.lax.top_k(exact, 4)
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_allclose(w, 6.0 * p, rtol=1e-5)


def test_the_layers_counters_are_in_the_registry_after_a_forward(params):
    from ray_tpu.util import metrics

    def held_pairs():
        return sum(v for f in metrics.snapshot()
                   if f["name"] == "moe_routed_pairs_total"
                   for _, tags, v in f["samples"]
                   if dict(map(tuple, tags)).get("dest") == "held")

    before = held_pairs()
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 24), 0, 96)
    jax.block_until_ready(jax.jit(
        lambda p, t: transformer.backbone(p, t, TINY))(params, tokens))
    jax.effects_barrier()
    names = {f["name"] for f in metrics.snapshot()}
    assert {"moe_routed_pairs_total", "moe_held_load_max_total",
            "moe_layer_calls_total"} <= names
    # every routed pair of 2 layers x 48 tokens x 4 is counted once; with
    # all 16 routed experts held, those not on zero-compute indices are held
    assert 0 < held_pairs() - before <= 2 * 48 * 4


# -- the experts read as groups of the stacked leaf ------------------------------------


def _stacked_mixture(n=3, cfg=EXPERTS):
    """``n`` layers' routers and held experts, the leaves stacked [n, count,
    ...]; every router shuns held expert 2, so no token chooses it."""
    layers = [_mixture_weights(20 + l, cfg=cfg) for l in range(n)]
    routers = jnp.stack([r for r, _ in layers]).at[:, :, cfg.held[0] + 2].set(
        0.0).at[:, 0, cfg.held[0] + 2].set(-1e4)
    experts = jax.tree.map(lambda *ps: jnp.stack(ps),
                           *(e for _, e in layers))
    u = jax.random.normal(jax.random.PRNGKey(21), (50, 64)).at[:, 0].set(1.0)
    return routers, experts, u


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("first,count,rows", [(0, 16, 1024), (4, 4, 7)])
def test_a_layers_experts_in_the_stack_are_its_own_leaves(
        monkeypatch, first, count, rows, layer):
    """The stacked leaves with ``layer=l`` give what layer l's own leaves
    give, sum and load: with an expert no token chose (an empty group
    inside the layer's), with chunks that end inside a group (7 rows a
    step) and under ``jit`` with the index traced."""
    monkeypatch.setattr(expert, "CHUNK_ROWS", rows)
    cfg = dataclasses.replace(EXPERTS, held=(first, count))
    routers, experts, u = _stacked_mixture(cfg=cfg)
    mine = jax.tree.map(lambda p: p[layer], experts)
    want, want_load = held_experts_apply(u, routers[layer], _alone(mine),
                                         cfg, 0)
    got, load = jax.jit(lambda l: held_experts_apply(
        u, routers[layer], experts, cfg, layer=l))(layer)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert load.tolist() == want_load.tolist()
    idx, _ = expert.route(u, routers[layer], cfg)
    bounds = np.cumsum([0] + [int(jnp.sum(idx == first + e))
                             for e in range(count)])
    assert bounds[2] == bounds[3] and bounds[-1] == load[0]  # an empty group
    assert rows == 1024 or any(                 # a step ends inside a group
        lo < step < hi for step in range(rows, bounds[-1], rows)
        for lo, hi in zip(bounds, bounds[1:]))
    np.testing.assert_allclose(
        want, _every_expert_on_every_token(u, routers[layer], mine, cfg),
        atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_only_its_own_layers_experts_reach_a_layers_sum(layer):
    """Every other layer's experts zeroed: the same sum to the bit (their
    groups have no rows); this layer's zeroed: the zero-compute part
    alone."""
    routers, experts, u = _stacked_mixture()
    keep = (jnp.arange(3) == layer)[:, None, None, None]
    got, load = held_experts_apply(u, routers[layer], experts, EXPERTS,
                                   layer=layer)
    others, _ = held_experts_apply(
        u, routers[layer], jax.tree.map(lambda p: jnp.where(keep, p, 0),
                                        experts), EXPERTS, layer=layer)
    np.testing.assert_array_equal(got, others)
    own, own_load = held_experts_apply(
        u, routers[layer], jax.tree.map(lambda p: jnp.where(keep, 0, p),
                                        experts), EXPERTS, layer=layer)
    idx, w = expert.route(u, routers[layer], EXPERTS)
    zero = jnp.sum(jnp.where(idx >= 16, w, 0), -1, keepdims=True) * u
    np.testing.assert_allclose(own, zero, rtol=1e-6, atol=1e-6)
    assert own_load.tolist() == load.tolist()
    assert float(jnp.abs(got - own).max()) > 0.1


@pytest.mark.parametrize("fault", ["float32 under bfloat16 tokens",
                                   "one layer's own leaves"])
def test_leaves_that_would_have_to_be_copied_are_refused_by_name(fault):
    """The mixture converts and cuts nothing: leaves in another dtype than
    the tokens', or not stacked [n, count, ...], raise before any product."""
    routers, experts, u = _stacked_mixture()
    if fault.startswith("float32"):
        u = u.astype(jnp.bfloat16)
    else:
        experts = jax.tree.map(lambda p: p[1], experts)
    with pytest.raises(ValueError, match=r"experts\['w[igo]'\] is .*stacked "
                                         r"leaves are \[\d+, 16, \.\.\.\]"):
        held_experts_apply(u, routers[1], experts, EXPERTS, 1)


# -- the list of held pairs: placed once a layer call, sliced a step -------------------


def _picks(case, T=40, k=4):
    """A call's choices [T, k] among 24 experts (a token picks an expert
    once at most) and the share held, for a named case."""
    rng = np.random.default_rng(7)
    held = (4, 8)
    if case == "all the experts held":
        held = (0, 24)
    if case == "every token on one expert":
        idx = np.tile(np.array([9, 0, 1, 2]), (T, 1))
    elif case == "the held pairs fill their steps exactly":
        idx = np.tile(np.array([0, 1, 2, 3]), (T, 1))
        idx[:32, :2] = [5, 10]                  # 64 held pairs, 16 a step
    else:
        idx = np.stack([rng.permutation(24)[:k] for _ in range(T)])
    if case == "an expert with no token":
        idx[idx == 6] = 23                      # held expert 6 is nobody's,
        idx[(idx == 23).sum(axis=1) > 1] = [0, 1, 2, 23]    # 23 picked once
    return jnp.asarray(idx, jnp.int32), held


CASES = ["all the experts held", "a share held", "an expert with no token",
         "every token on one expert",
         "the held pairs fill their steps exactly"]


@pytest.mark.parametrize("case", CASES)
def test_the_list_is_the_stable_sort_of_the_held_pairs_by_expert(case):
    """``_held_rows`` against ``numpy.argsort(kind="stable")`` of the held
    pairs' experts, the pairs in token order: the same tokens and weights
    in the same rows, the groups' bounds, and past the held pairs the token
    ``T`` at weight 0 to the list's end (whole steps of 16 rows)."""
    idx, (first, count) = _picks(case)
    T, k = idx.shape
    cfg = dataclasses.replace(EXPERTS, n_routed=24, n_zero=0,
                              held=(first, count))
    weights = jax.random.uniform(jax.random.PRNGKey(31), (T, k)) + 0.1
    length = -(-T * k // 16) * 16
    row_tok, row_w, bounds, place = jax.jit(
        lambda i, w: expert._held_rows(i, w, cfg, length))(idx, weights)
    c = np.asarray(idx).reshape(-1) - first
    mine = np.flatnonzero((c >= 0) & (c < count))       # pairs, token order
    order = mine[np.argsort(c[mine], kind="stable")]
    n_held = len(order)
    assert row_tok.shape == row_w.shape == (length,)
    np.testing.assert_array_equal(row_tok[:n_held], order // k)
    np.testing.assert_array_equal(row_w[:n_held],
                                  np.asarray(weights).reshape(-1)[order])
    np.testing.assert_array_equal(row_tok[n_held:], T)
    np.testing.assert_array_equal(row_w[n_held:], 0.0)
    # each pair's row: the inverse of the order, ``length`` if not held
    want_place = np.full(T * k, length)
    want_place[order] = np.arange(n_held)
    np.testing.assert_array_equal(place, want_place)
    np.testing.assert_array_equal(
        bounds, np.concatenate([[0], np.cumsum(np.bincount(
            c[mine], minlength=count))]))
    if case == "an expert with no token":
        assert bounds[2] == bounds[3] and 0 < n_held < T * k
    if case == "every token on one expert":
        assert n_held == T and bounds[5] == 0 and bounds[6] == T
    if case == "the held pairs fill their steps exactly":
        assert n_held == 64


def _poisoned_products(monkeypatch):
    """``lax.ragged_dot`` with NaN in every row that belongs to no group;
    the list it returns collects the rows of each call."""
    plain, calls = jax.lax.ragged_dot, []

    def poison(lhs, rhs, sizes, **kwargs):
        y = plain(lhs, rhs, sizes, **kwargs)
        in_a_group = jnp.arange(y.shape[0]) < jnp.sum(sizes)
        calls.append(y.shape[0])
        return jnp.where(in_a_group[:, None], y, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poison)
    return calls


@pytest.mark.parametrize("rows,n_held", [(16, 40), (16, 64), (1024, 40)])
def test_rows_past_the_held_pairs_add_nothing_whatever_the_product_left(
        monkeypatch, rows, n_held):
    """The grouped product's result poisoned (NaN) in every row that belongs
    to no group: the sum is the same to the bit, since those rows hold the
    token ``T`` and the scatter-add drops them. 40 held pairs end inside a
    step, 64 fill four steps of 16 exactly."""
    monkeypatch.setattr(expert, "CHUNK_ROWS", rows)
    cfg = dataclasses.replace(EXPERTS, held=(4, 4))
    router, u = _forced_router((0, 1, 2, 5))
    if n_held == 64:        # 24 of the 40 tokens choose held expert 6 too
        u = u.at[:, 1].set(0.0).at[:24, 1].set(1.0)
        router = router.at[1, 6].set(60.0)
    _, experts = _mixture_weights(4, cfg=cfg)
    want, load = held_experts_apply(u, router, _alone(experts), cfg, 0)
    assert int(load[0]) == n_held
    poisoned = _poisoned_products(monkeypatch)
    got, _ = held_experts_apply(u, router, _alone(experts), cfg, 0)
    assert poisoned == [min(rows, 160)] * 3
    np.testing.assert_array_equal(got, want)
    assert bool(jnp.all(jnp.isfinite(got))) and float(jnp.abs(got).max()) > 0


@pytest.mark.parametrize("rows", [16, 1024])
def test_all_held_the_gather_reads_no_row_past_the_held_pairs(monkeypatch,
                                                              rows):
    """All 16 held, one pick in four a zero-compute one, 132 tokens (over
    ``STREAM_ROWS``: the call lists its pairs): 396 held pairs end inside a
    step of 16 and inside the one step of 528, and the rows past them, NaN
    in the list the steps write, belong to no pair: a zero-compute pick is
    placed past the list's end and reads as zeros. The sum is the same to
    the bit as with a clean product."""
    monkeypatch.setattr(expert, "CHUNK_ROWS", rows)
    router, u = _forced_router((0, 1, 17, 5), tokens=132)
    _, experts = _mixture_weights(4)
    assert EXPERTS.all_held and EXPERTS.n_zero
    assert u.shape[0] > expert.STREAM_ROWS
    want, load = held_experts_apply(u, router, _alone(experts), EXPERTS, 0)
    assert load.tolist() == [396, 0, 132, 132]
    poisoned = _poisoned_products(monkeypatch)
    got, _ = held_experts_apply(u, router, _alone(experts), EXPERTS, 0)
    assert poisoned == [min(rows, 528)] * 3
    np.testing.assert_array_equal(got, want)
    assert bool(jnp.all(jnp.isfinite(got))) and float(jnp.abs(got).max()) > 0


def _eqns(jaxpr):
    """A jaxpr's equations and those of every jaxpr nested in them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("first,count,T", [(0, 16, 150), (4, 4, 50)])
def test_a_step_of_the_loop_searches_for_nothing(first, count, T):
    """Read off ``jax.make_jaxpr``: the layer call holds one ``while`` (the
    dropless loop), whose body has no loop, sort or search of its own,
    gathers only the rows' tokens from ``u`` (the one gather), and is handed
    no ``[T, count]`` count: it slices the list with two ``dynamic_slice``s.
    The list's two scatters are outside it. **How the step's rows reach the
    sum**: a share held scatter-adds them in the step, and its layer call
    is the parent commit's jaxpr to the letter (read off commit 6fef1d7 by
    ``_digest``); all 16 held write them into the float32 list where they
    lie (a ``dynamic_update_slice``, no scatter-add anywhere) and one
    gather from that list follows the loop (at 150 tokens: a row tile or
    fewer would be streamed and list nothing, ``expert._streams``)."""
    cfg = dataclasses.replace(EXPERTS, held=(first, count))
    router, experts = _mixture_weights(1, cfg=cfg)
    u = jax.random.normal(jax.random.PRNGKey(2), (T, 64))
    assert not expert._streams(cfg, T)

    def call(u):
        return held_experts_apply(u, router, _alone(experts), cfg, 0)

    jaxpr = jax.make_jaxpr(call)(u).jaxpr
    loops = [e for e in _eqns(jaxpr) if e.primitive.name == "while"]
    assert len(loops) == 1
    body = loops[0].params["body_jaxpr"].jaxpr
    inside = [e.primitive.name for e in _eqns(body)]
    assert not {"while", "sort", "scan", "cond", "scatter"} & set(inside)
    assert "searchsorted" not in str(body)
    assert inside.count("gather") == 1
    assert inside.count("dynamic_slice") == 2
    assert inside.count("ragged_dot_general") == 3
    gather, = (e for e in _eqns(body) if e.primitive.name == "gather")
    assert gather.invars[0].aval.shape == u.shape
    shapes = {v.aval.shape for v in body.invars}
    assert not shapes & {(T, count), (count, T)}
    assert (4 * T,) in shapes           # the list: T x 4 pairs, one step's rows
    everywhere = [e.primitive.name for e in _eqns(jaxpr)]
    assert everywhere.count("scatter") == 2 and "sort" not in everywhere
    # the step's own sizes are one ``dynamic_update_slice`` on either path
    if cfg.all_held:
        assert "scatter-add" not in everywhere
        assert inside.count("dynamic_update_slice") == 2
        assert (600, 64) in shapes      # the carried list of weighed rows
        after = [e for e in _eqns(jaxpr) if e.primitive.name == "gather"
                 and e.invars[0].aval.shape == (600, 64)]
        assert len(after) == 1 and after[0].outvars[0].aval.shape == (600, 64)
        assert after[0].invars[1].aval.shape == (600, 1)    # every pair's row
    else:
        assert inside.count("scatter-add") == everywhere.count(
            "scatter-add") == 1
        assert inside.count("dynamic_update_slice") == 1
        assert (4 * T, 64) not in shapes
        assert _digest(call, u) == "0109d6b0f3f67dbd"


# -- the layer against the reference ---------------------------------------------------


@pytest.mark.parametrize("use_flash", [False, True])
def test_prefill_at_two_lengths_with_right_padding_agrees_with_the_reference(
        params, use_flash):
    """Prompts of 9 and 24 tokens in one padded batch of 32: each prompt's
    logits at its last real position are the reference's on the prompt
    alone, within float32 rounding (1e-4 on logits of size 1)."""
    cfg = dataclasses.replace(TINY, use_flash=use_flash)
    key = jax.random.PRNGKey(10)
    prompts = [jax.random.randint(k, (n,), 0, 96)
               for k, n in zip(jax.random.split(key, 2), (9, 24))]
    tokens = jnp.stack([jnp.pad(p, (0, 32 - len(p))) for p in prompts])
    last = jnp.array([len(p) - 1 for p in prompts])
    got = jax.jit(lambda p, t, l: _last_logits(p, t, l, cfg))(params, tokens,
                                                              last)
    for row, prompt in zip(got, prompts):
        want = _reference_logits(params, prompt[None], TINY_DIMS)[0]
        np.testing.assert_allclose(row, want, atol=1e-4)


@pytest.mark.parametrize("n_layers", [1, 2, 3])
def test_the_stacked_forward_agrees_with_the_reference(n_layers):
    """One, two and three layers read out of the stacked tree by one scan
    over their indices: the last position's logits are the reference's."""
    cfg, dims = _of_depth(n_layers)
    params = _seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(14), (2, 20), 0, 96)
    got = jax.jit(lambda p, t, l: _last_logits(p, t, l, cfg))(
        params, tokens, jnp.array([19, 19]))
    want = _reference_logits(params, tokens, dims)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("n_layers", [1, 2, 3])
def test_the_stacked_forward_is_the_block_on_each_layer_cut_out(n_layers):
    """The scan over the stack against ``_shortcut_block`` applied layer by
    layer to that layer's weights alone (a stack of one, read at 0): the
    same states to float32's last digits, and the same loads."""
    cfg, _ = _of_depth(n_layers)
    blocks = _seeded(cfg)["blocks"][SHORTCUT]
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 20, 64))
    positions = jnp.broadcast_to(jnp.arange(20)[None], (2, 20))
    loads = []
    got = jax.jit(lambda b, x: transformer._apply_shortcut(
        b, x, positions, cfg))(blocks, x)
    block = jax.jit(lambda stack, x: transformer._shortcut_block(
        stack, 0, x, positions, cfg))
    want = x
    for l in range(n_layers):
        want, load = block(_one_layer(blocks, l), want)
        loads.append(load)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the wrong layer's weights would show: the layers differ
    if n_layers > 1:
        swapped, _ = block(_one_layer(blocks, 1), x)
        first, _ = block(blocks, x)
        assert float(jnp.abs(swapped - first).max()) > 0.1
    assert all(int(load[0]) > 0 for load in loads)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four shares of four experts each, the zero-compute part counted
    once, are the uncut reference's mixture; and so for the whole layer:
    the layer is linear in the mixture's sum."""
    layer = jax.tree.map(lambda p: p[0], params["blocks"][SHORTCUT])
    u = jax.random.normal(jax.random.PRNGKey(11), (40, 64))
    idx, w = jax.jit(lambda u, r: expert.route(u, r, EXPERTS))(
        u, layer["router"])
    zero = jnp.sum(jnp.where(idx >= 16, w, 0), -1, keepdims=True) * u
    total = zero
    for share in range(4):
        cfg = dataclasses.replace(EXPERTS, held=(4 * share, 4))
        mine = jax.tree.map(lambda p: p[4 * share:4 * share + 4],
                            layer["experts"])
        part, _ = jax.jit(lambda u, r, mine: held_experts_apply(
            u, r, _alone(mine), cfg, 0))(u, layer["router"], mine)
        total = total + (part - zero)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda u, l: longcat_reference.experts_part(
            u, longcat_reference.from_tree(l), TINY_DIMS))(u, layer)
    np.testing.assert_allclose(total, want, atol=2e-5)

    @functools.partial(jax.jit, static_argnums=0)
    def layer_out(cfg, tree, x):
        positions = jnp.arange(x.shape[1])[None]
        stack = jax.tree.map(lambda p: p[None], tree)   # a stack of one
        return transformer._shortcut_block(stack, 0, x, positions, cfg)[0]

    x = jax.random.normal(jax.random.PRNGKey(12), (1, 20, 64))
    uncut = layer_out(TINY, layer, x)
    none = layer_out(held(TINY, 0, 4), {**layer, "experts": jax.tree.map(
        lambda p: jnp.zeros_like(p[:4]), layer["experts"])}, x)
    parts = sum(layer_out(held(TINY, 4 * s, 4), {
        **layer, "experts": jax.tree.map(lambda p: p[4 * s:4 * s + 4],
                                         layer["experts"])}, x) - none
        for s in range(4))
    np.testing.assert_allclose(none + parts, uncut, atol=5e-5)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            uncut[0], jax.jit(lambda l, x: longcat_reference.block(
                longcat_reference.from_tree(l), x, TINY_DIMS))(layer, x[0]),
            atol=5e-5)


def test_shares_of_different_devices_draw_consistent_experts():
    """An expert's weights come from a key folded with its published index:
    the share (4, 4) holds what experts 4 to 7 of the whole are."""
    whole = _init(jax.random.PRNGKey(13), TINY)
    share = _init(jax.random.PRNGKey(13), held(TINY, 4, 4))
    for name in ("wi", "wg", "wo"):
        np.testing.assert_array_equal(
            share["blocks"][SHORTCUT]["experts"][name],
            whole["blocks"][SHORTCUT]["experts"][name][:, 4:8])
    np.testing.assert_array_equal(share["blocks"][SHORTCUT]["router"],
                                  whole["blocks"][SHORTCUT]["router"])


# -- the configuration's guards ----------------------------------------------------------


def test_layer_kinds_message_lists_the_kinds_from_one_tuple():
    with pytest.raises(ValueError) as e:
        TransformerConfig(n_layers=1, layer_kinds=("window",))
    assert all(repr(kind) in str(e.value) for kind in KINDS)
    assert len(KINDS) == 18 and SHORTCUT in KINDS


def test_a_shortcut_layer_stands_among_its_own_kind_and_needs_its_sizes():
    with pytest.raises(ValueError, match="of its own kind only"):
        dataclasses.replace(TINY, layer_kinds=(SHORTCUT, transformer.LINEAR))
    with pytest.raises(ValueError, match="LatentConfig"):
        dataclasses.replace(TINY, latent=None)


def _forward_only(kind):
    """The smallest stack of a kind that has no backward pass."""
    if kind == SHORTCUT:
        return TINY
    from test_mixed_stack import MIXED
    return dataclasses.replace(MIXED, n_layers=1, layer_kinds=(kind,),
                               layer_ids=None)


@pytest.mark.parametrize("kind", [SHORTCUT, "sparse", "linear"])
def test_train_step_refuses_the_shortcut_kind_by_name(kind):
    """The kinds that still have no backward pass, each refused by name."""
    with pytest.raises(ValueError, match=f"{kind}.*no backward pass"):
        make_lm_train_step(_forward_only(kind), mesh=None)


def test_logical_axes_mirror_the_shortcut_tree(params):
    axes = transformer.logical_axes(TINY)
    is_axes = lambda a: isinstance(a, tuple)   # noqa: E731
    flat_axes = jax.tree_util.tree_flatten_with_path(axes, is_leaf=is_axes)[0]
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [p for p, _ in flat] == [p for p, _ in flat_axes]
    assert all(a.ndim == len(b) for (_, a), (_, b) in zip(flat, flat_axes))
