"""The layer kinds made of a mixer and an FFN chosen apart (LFM2: a gated
short convolution or grouped-query attention with q/k norms; a dense FFN or a
sigmoid-routed mixture with a bias for the choice), the tied head and the
router's variants: at tiny sizes on the CPU against
``benchmark/lfm2_reference.py``, ``jnp.convolve`` and plain sums."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import lfm2_reference
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (ATTN_MOE, CONV, CONV_MOE, KINDS,
                                        PARTS, TransformerConfig)
from ray_tpu.observability import metric_names
from ray_tpu.parallel import expert
from ray_tpu.parallel.expert import ExpertConfig, held_experts_apply
from ray_tpu.train.step import make_lm_train_step
from test_mixed_stack import MIXED, _digest
from test_shortcut_layer import TINY as TINY_LONGCAT
from test_shortcut_layer import _init, _last_logits, _seeded, held

# d 64, 4 query heads over 2 K/V heads of 16, 16 experts of 32, top-4, the
# published pattern of the cut: layer 0 and two periods of layers 2-9
EXPERTS = ExpertConfig(n_routed=16, n_zero=0, top_k=4, scale=1.0, width=32,
                       held=(0, 16), score="sigmoid", choice_bias=True,
                       normalize=True)
LAYER_IDS = (0, 2, 3, 4, 5, 6, 7, 8, 9)
LAYER_TYPES = ("conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv")
TINY = TransformerConfig(
    vocab_size=96, d_model=64, n_layers=9, n_heads=4, n_kv_heads=2, d_ff=96,
    max_seq_len=64, dtype=jnp.float32, use_flash=False, remat=False,
    rope_theta=1e6, norm_eps=1e-5,
    layer_kinds=(CONV, ATTN_MOE, CONV_MOE, CONV_MOE, CONV_MOE, ATTN_MOE,
                 CONV_MOE, CONV_MOE, CONV_MOE),
    layer_ids=LAYER_IDS, experts=EXPERTS, qk_norm=True, tie_embeddings=True)
TINY_DIMS = {
    "vocab_size": 96, "d_model": 64, "n_layers": 9, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 16, "d_ff": 96, "rope_theta": 1e6,
    "rms_norm_eps": 1e-5, "conv_width": 3, "num_dense_layers": 2,
    "layer_types": list(LAYER_TYPES), "layer_ids": list(LAYER_IDS),
    "n_routed": 16, "n_zero": 0, "top_k": 4, "scale": 1.0,
    "expert_width": 32, "held": [0, 16]}


def _cut(first, n):
    """``TINY`` and its reference's sizes at layers ``first : first + n``."""
    cfg = dataclasses.replace(
        TINY, n_layers=n, layer_kinds=TINY.layer_kinds[first:first + n],
        layer_ids=LAYER_IDS[first:first + n])
    dims = {**TINY_DIMS, "n_layers": n,
            "layer_types": list(LAYER_TYPES[first:first + n]),
            "layer_ids": list(LAYER_IDS[first:first + n])}
    return cfg, dims




@pytest.fixture(scope="module")
def params():
    """``TINY``'s seeded weights, drawn once for the file."""
    return _seeded(TINY, 51)


# -- the short convolution -----------------------------------------------------------


def _conv_weights(seed=0, d=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w_in": jax.random.normal(ks[0], (d, 3 * d)) / 3,
            "conv": jax.random.normal(ks[1], (d, 3)),
            "w_out": jnp.eye(d)}


def test_the_convolution_is_jnp_convolve_a_channel():
    """``c = convolve(g, w reversed)`` cut to the first L positions, channel
    by channel, ``g = B * z``; and the result ``(C * c) W_out``."""
    p, d = _conv_weights(), 8
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 11, d))
    got = transformer._shortconv_mixer(p, h)
    bcz = np.asarray(h @ p["w_in"])
    gate, c_gate, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    g = gate * z
    for b in range(2):
        for ch in range(d):
            # tap j multiplies g_{t - 2 + j}: the kernel read backwards
            c = np.convolve(g[b, :, ch], np.asarray(p["conv"])[ch, ::-1])[:11]
            np.testing.assert_allclose(got[b, :, ch], c_gate[b, :, ch] * c,
                                       rtol=1e-5, atol=1e-6)


def test_the_convolutions_first_two_positions_read_zeros_before_them():
    p, d = _conv_weights(2), 8
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 5, d))
    got = transformer._shortconv_mixer(p, h)[0]
    bcz = h[0] @ p["w_in"]
    g, c_gate, w = bcz[:, :d] * bcz[:, 2 * d:], bcz[:, d:2 * d], p["conv"]
    np.testing.assert_allclose(got[0], c_gate[0] * w[:, 2] * g[0], rtol=1e-5)
    np.testing.assert_allclose(
        got[1], c_gate[1] * (w[:, 1] * g[0] + w[:, 2] * g[1]), rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(
        got[2], c_gate[2] * (w[:, 0] * g[0] + w[:, 1] * g[1]
                             + w[:, 2] * g[2]), rtol=1e-5, atol=1e-6)
    # causal: a later position moves no earlier one
    moved = transformer._shortconv_mixer(p, h.at[0, 4].add(1.0))[0]
    np.testing.assert_array_equal(moved[:4], got[:4])
    assert float(jnp.abs(moved[4] - got[4]).max()) > 0


def test_the_convolution_agrees_with_the_references_three_shifts():
    p = _conv_weights(4, d=16)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 9, 16))
    np.testing.assert_allclose(transformer._shortconv_mixer(p, h)[0],
                               lfm2_reference.shortconv(p, h[0]),
                               rtol=1e-5, atol=1e-6)


# -- the router's five steps ---------------------------------------------------------


def _router(seed=6, d=64, cfg=EXPERTS):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (40, d)),
            jax.random.normal(ks[1], (d, cfg.n_outputs)) / 8,
            0.05 * jax.random.normal(ks[2], (cfg.n_outputs,)))


def test_step_one_the_scores_are_sigmoids_of_float32_logits():
    u, router, bias = _router()
    cfg = dataclasses.replace(EXPERTS, normalize=False, top_k=16)
    idx, w = expert.route(u.astype(jnp.bfloat16), router, cfg, bias)
    assert w.dtype == jnp.float32
    exact = jax.nn.sigmoid(u.astype(jnp.bfloat16).astype(jnp.float32)
                           @ router.astype(jnp.bfloat16).astype(jnp.float32))
    # all 16 chosen: every score, at its expert's place
    np.testing.assert_allclose(
        jnp.zeros_like(exact).at[jnp.arange(40)[:, None], idx].set(w), exact,
        rtol=1e-5)


def test_step_two_the_bias_moves_the_choice_and_never_a_weight():
    u, router, bias = _router()
    plain = dataclasses.replace(EXPERTS, normalize=False)
    s = jax.nn.sigmoid(u @ router)
    idx, w = expert.route(u, router, plain, bias)
    np.testing.assert_array_equal(idx, jax.lax.top_k(s + bias, 4)[1])
    np.testing.assert_allclose(w, jnp.take_along_axis(s, idx, axis=-1),
                               rtol=1e-6)
    unbiased, _ = expert.route(u, router, plain, jnp.zeros_like(bias))
    assert float(jnp.mean(jnp.any(jnp.sort(idx) != jnp.sort(unbiased),
                                  axis=-1))) > 0.2
    # a bias that hands every token expert 3 changes no weight's value
    forced, w3 = expert.route(u, router, plain, bias.at[3].set(10.0))
    assert bool(jnp.all(forced[:, 0] == 3))
    np.testing.assert_allclose(w3[:, 0], s[:, 3], rtol=1e-6)


def test_steps_three_to_five_the_weights_sum_to_scale_less_the_epsilon():
    u, router, bias = _router()
    cfg = dataclasses.replace(EXPERTS, scale=2.5)
    idx, w = expert.route(u, router, cfg, bias)
    s = jnp.take_along_axis(jax.nn.sigmoid(u @ router), idx, axis=-1)
    total = jnp.sum(s, -1, keepdims=True)
    np.testing.assert_allclose(w, 2.5 * s / (total + 1e-6), rtol=1e-6)
    below = 2.5 - jnp.sum(w, -1)
    assert bool(jnp.all(below > 0)) and bool(jnp.all(below < 1e-5))
    # without the normalisation the weights are the scores times the scale
    _, raw = expert.route(u, router, dataclasses.replace(
        cfg, normalize=False), bias)
    np.testing.assert_allclose(raw, 2.5 * s, rtol=1e-6)


def test_ties_go_to_the_lower_index_on_both_sides():
    """Equal scores (a router of zeros: every score a half) and equal
    biases: ``top_k`` keeps the lowest indices, in the program and in the
    reference alike; a bias picks among the tied."""
    u = jax.random.normal(jax.random.PRNGKey(7), (5, 64))
    router, bias = jnp.zeros((64, 16)), jnp.zeros((16,))
    idx, w = expert.route(u, router, EXPERTS, bias)
    np.testing.assert_array_equal(idx, jnp.tile(jnp.arange(4), (5, 1)))
    np.testing.assert_allclose(w, 0.25, rtol=1e-5)
    want, _ = lfm2_reference.route(u, router, bias, TINY_DIMS)
    np.testing.assert_array_equal(idx, want)
    picked, _ = expert.route(u, router, EXPERTS,
                             bias.at[jnp.array([9, 12])].set(0.1))
    np.testing.assert_array_equal(jnp.sort(picked),
                                  jnp.tile(jnp.array([0, 1, 9, 12]), (5, 1)))


def test_the_defaults_are_longcats_router():
    u, router, _ = _router()
    cfg = ExpertConfig(n_routed=12, n_zero=4, top_k=4, scale=6.0, width=32,
                       held=(0, 12))
    assert (cfg.score, cfg.choice_bias, cfg.normalize) == ("softmax", False,
                                                           False)
    idx, w = expert.route(u, router, cfg)
    p, want = jax.lax.top_k(jax.nn.softmax(u @ router, axis=-1), 4)
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_allclose(w, 6.0 * p, rtol=1e-6)
    with pytest.raises(ValueError, match="'softmax' or 'sigmoid'"):
        dataclasses.replace(cfg, score="tanh")


def test_the_drawn_bias_moves_the_choice_of_most_tokens_and_is_on_bf16s_grid():
    """``expert.BIAS_SCALE``: at seeded weights the bias changes the set of
    chosen experts in a stated share of the tokens (between a third and
    nine tenths), is never zeros, and a cast to bfloat16 leaves it as it
    is."""
    cfg = dataclasses.replace(TINY, experts=dataclasses.replace(
        EXPERTS, n_routed=64, held=(0, 64)))
    params = _init(jax.random.PRNGKey(8), cfg)
    layer = jax.tree.map(lambda p: p[0], params["blocks"][CONV_MOE])
    bias = layer["router_bias"]
    assert bias.shape == (64,) and bias.dtype == jnp.float32
    np.testing.assert_array_equal(
        bias, bias.astype(jnp.bfloat16).astype(jnp.float32))
    assert 0.5 * expert.BIAS_SCALE < float(jnp.std(bias)) \
        < 1.5 * expert.BIAS_SCALE
    u = jax.random.normal(jax.random.PRNGKey(9), (4096, 64))
    route = jax.jit(lambda u, r, b: expert.route(u, r, cfg.experts, b))
    with_bias, _ = route(u, layer["router"], bias)
    without, _ = route(u, layer["router"], jnp.zeros_like(bias))
    moved = float(jnp.mean(jnp.any(jnp.sort(with_bias) != jnp.sort(without),
                                   axis=-1)))
    print(f"the bias moves the choice of {100 * moved:.1f}% of the tokens")
    assert 0.33 < moved < 0.9


# -- the mixture with every expert held, and its shares ------------------------------


def _mixture_layer(seed=10):
    cfg, _ = _cut(2, 1)                 # one conv + mixture layer
    params = _seeded(cfg, seed)
    return jax.tree.map(lambda p: p[0], params["blocks"][CONV_MOE])


@functools.partial(jax.jit, static_argnums=2)
def _reference_mixture(u, layer, held=(0, 16)):
    with jax.default_matmul_precision("highest"):
        return lfm2_reference.mixture(
            u, lfm2_reference.Layer(
                None, None, lambda e: jax.tree.map(lambda p: p[held[0] + e],
                                                   layer["experts"]),
                layer["router"], layer["router_bias"], None, None),
            {**TINY_DIMS, "held": list(held)})


def _steps_of_each_token(u, layer, cfg, rows):
    """The dropless loop's step that holds each of a token's ``k`` pairs,
    [T, k]: the pair's row in the call's list over ``rows``."""
    idx, weights = expert.route(u, layer["router"], cfg, layer["router_bias"])
    length = -(-idx.size // rows) * rows
    place = expert._held_rows(idx, weights, cfg, length)[3]
    assert int(place.max()) < idx.size          # every pair has a row
    return np.asarray(place).reshape(idx.shape) // rows


# (rows a step, tokens, experts every token is forced onto): 200 pairs below
# one step of 1,024 and in one step of exactly 200 (a token's four pairs fall
# in one step); nine steps of 24, the last of 8 rows; 25 steps of 8 (a
# token's four pairs fall in four steps); every token on the same four
# experts, so that each of them holds whole steps and twelve hold no row
COMBINES = [(1024, 50, None), (200, 50, None), (24, 50, None), (8, 50, None),
            (16, 40, (5, 0, 9, 12))]


@pytest.mark.parametrize("rows,T,forced", COMBINES)
def test_all_the_experts_held_and_no_zero_expert_is_the_references_mixture(
        monkeypatch, rows, T, forced):
    """``held = (0, n_routed)``, ``n_zero = 0``: every pair is held, none
    absent, none zero, and the device combines by gather: each step writes
    its weighed rows into the list and one gather sums a token's four, to
    float32 rounding of the float32 reference, wherever a token's pairs
    fall among the steps."""
    monkeypatch.setattr(expert, "CHUNK_ROWS", rows)
    layer = _mixture_layer()
    if forced is not None:          # the choice is on score + bias
        layer = {**layer, "router_bias": layer["router_bias"].at[
            jnp.array(forced)].set(10.0)}
    u = jax.random.normal(jax.random.PRNGKey(11), (T, 64))
    got, load = jax.jit(lambda u, l: held_experts_apply(
        u, l["router"], jax.tree.map(lambda p: p[None], l["experts"]),
        EXPERTS, 0, bias=l["router_bias"]))(u, layer)
    np.testing.assert_allclose(got, _reference_mixture(u, layer), atol=2e-5)
    assert load.tolist()[:3] == [4 * T, 0, 0]
    assert not forced or load[3] == T
    steps = _steps_of_each_token(u, layer, EXPERTS, min(rows, 4 * T))
    apart = np.array([len(set(row)) for row in steps])
    if rows >= 4 * T:
        assert (apart == 1).all()
    elif rows == 8 or forced:
        assert (apart == 4).mean() > 0.9
    else:
        assert steps.max() == -(-4 * T // rows) - 1 and 4 * T % rows


@pytest.mark.parametrize("rows", [1024, 16, 7])
def test_all_held_with_zero_compute_outputs_the_masked_pairs_add_nothing(
        monkeypatch, rows):
    """A mixture held whole whose router also has zero-compute outputs (12
    routed + 4): a zero-compute pick has no row in the list, the gather
    reads it as zeros whatever the list holds, and its ``w u`` comes from
    the part the sum starts from. Against the plain sum of every expert on
    every token."""
    from test_shortcut_layer import _every_expert_on_every_token
    monkeypatch.setattr(expert, "CHUNK_ROWS", rows)
    cfg = dataclasses.replace(EXPERTS, n_routed=12, n_zero=4, held=(0, 12),
                              choice_bias=False)
    assert cfg.all_held
    layer = _mixture_layer(24)
    mine = jax.tree.map(lambda p: p[:12], layer["experts"])
    u = jax.random.normal(jax.random.PRNGKey(25), (50, 64))
    got, load = jax.jit(lambda u, r, mine: held_experts_apply(
        u, r, jax.tree.map(lambda p: p[None], mine), cfg, 0))(
            u, layer["router"], mine)
    want = _every_expert_on_every_token(u, layer["router"], mine, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5)
    n_held, n_absent, n_zero, _ = load.tolist()
    assert n_absent == 0 and n_held + n_zero == 200 and 20 < n_zero < 120


@pytest.mark.parametrize("count", [8, 2])
def test_the_shares_add_up_to_the_uncut_references_layer(count):
    """Two shares of 8 and eight shares of 2 (as ``(0, 32) + (32, 32)`` and
    eight of 8 are of the published 64): each share's partial sum, added,
    is the uncut reference's mixture, and so for the whole layer, which is
    linear in the mixture's sum."""
    layer = _mixture_layer(12)
    u = jax.random.normal(jax.random.PRNGKey(13), (40, 64))
    total = 0.0
    for first in range(0, 16, count):
        cfg = dataclasses.replace(EXPERTS, held=(first, count))
        mine = jax.tree.map(lambda p: p[None, first:first + count],
                            layer["experts"])
        part, load = jax.jit(lambda u, l, mine: held_experts_apply(
            u, l["router"], mine, cfg, 0, bias=l["router_bias"]))(
                u, layer, mine)
        assert int(load[0]) + int(load[1]) == 160 and int(load[2]) == 0
        total = total + part
    np.testing.assert_allclose(total, _reference_mixture(u, layer),
                               atol=3e-5)

    def layer_out(first, count):
        cfg, _ = _cut(2, 1)
        cfg = dataclasses.replace(cfg, experts=dataclasses.replace(
            EXPERTS, held=(first, count)))
        stack = jax.tree.map(lambda p: p[None], {
            **layer, "experts": jax.tree.map(
                lambda p: p[first:first + count], layer["experts"])})
        return block(stack, cfg)

    x = jax.random.normal(jax.random.PRNGKey(14), (1, 20, 64))
    positions = jnp.arange(20)[None]
    block = jax.jit(lambda stack, cfg: transformer._parts_block(
        stack, 0, x, positions, cfg, CONV_MOE)[0], static_argnums=1)
    uncut = layer_out(0, 16)
    # a share's layer less the layer without any expert is its experts' part
    cfg, dims = _cut(2, 1)
    zeroed = jax.tree.map(lambda p: p[None], {
        **layer, "experts": jax.tree.map(jnp.zeros_like, layer["experts"])})
    none = block(zeroed, cfg)
    parts = sum(layer_out(first, count) - none
                for first in range(0, 16, count))
    np.testing.assert_allclose(none + parts, uncut, atol=5e-5)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda layer, x: lfm2_reference.block(
            lfm2_reference.from_tree(
                {"blocks": {CONV_MOE: jax.tree.map(lambda p: p[None],
                                                   layer)}}, 0, dims),
            x, 0, dims))(layer, x[0])
    np.testing.assert_allclose(uncut[0], want, atol=5e-5)


def test_shares_of_different_devices_draw_consistent_experts():
    whole = _init(jax.random.PRNGKey(15), TINY)
    share = _init(jax.random.PRNGKey(15), dataclasses.replace(
        TINY, experts=dataclasses.replace(EXPERTS, held=(4, 8))))
    for kind in (ATTN_MOE, CONV_MOE):
        for name in ("wi", "wg", "wo"):
            np.testing.assert_array_equal(
                share["blocks"][kind]["experts"][name],
                whole["blocks"][kind]["experts"][name][:, 4:12])
        for name in ("router", "router_bias"):
            np.testing.assert_array_equal(share["blocks"][kind][name],
                                          whole["blocks"][kind][name])


def test_the_route_span_carries_the_loops_steps(monkeypatch):
    """``steps``: the layer calls' held pairs, ``CHUNK_ROWS`` a step (or a
    call's pairs, if fewer), summed: what a reader divides the pairs by."""
    from ray_tpu import observability
    seen = []
    monkeypatch.setattr(
        observability, "span",
        lambda name, **attrs: seen.append((name, attrs)) or _Null())
    monkeypatch.setattr(expert, "CHUNK_ROWS", 64)
    share = dataclasses.replace(EXPERTS, n_routed=64, held=(8, 16))
    expert._record(share, np.array([[200, 0, 0, 20], [64, 136, 0, 9],
                                    [0, 30, 2, 0], [65, 5, 0, 65]]))
    (name, attrs), = seen
    # ceil(200 / 64) + ceil(64 / 64) + 0 + ceil(65 / 64)
    assert name == "moe.route" and attrs["steps"] == 4 + 1 + 0 + 2
    assert (attrs["held"], attrs["layers"], attrs["experts"]) == (329, 4, 16)
    assert attrs["placed"] == 329 and attrs["gathered"] == 0
    # a call of fewer pairs than a step's rows takes them in one step
    seen.clear()
    expert._record(share, np.array([[20, 4, 0, 3]]))
    assert seen[0][1]["steps"] == 1
    # a device that holds the whole mixture steps through its list alike
    # (calls of 150 and 130 tokens: over a row tile), and says that its
    # pairs were combined by the gather
    seen.clear()
    expert._record(EXPERTS, np.array([[600, 0, 0, 20], [520, 0, 0, 65]]))
    assert seen[0][1]["steps"] == 10 + 9
    assert seen[0][1]["gathered"] == seen[0][1]["held"] == 1120
    assert seen[0][1]["streamed"] == 0
    # ... and a call of a row tile or fewer (128 x 4 pairs, 50 x 4 with two
    # zero-compute picks) lists nothing and takes no step: its held pairs
    # were streamed; a call of 3 tokens routes fewer pairs than there are
    # experts and takes the loop
    seen.clear()
    expert._record(EXPERTS, np.array([[512, 0, 0, 40], [600, 0, 0, 20],
                                      [198, 0, 2, 30], [12, 0, 0, 2]]))
    attrs = seen[0][1]
    assert attrs["streamed"] == 512 + 198 and attrs["gathered"] == 612
    assert attrs["placed"] == 612 and attrs["steps"] == 10 + 1
    assert attrs["held"] == 1322


@pytest.mark.parametrize("first,count,T", [(4, 8, 50), (0, 16, 150),
                                           (0, 16, 50)])
def test_the_route_span_says_the_pairs_were_placed(monkeypatch, first, count,
                                                   T):
    """``placed``: the pairs the layer calls' counting pass wrote into their
    lists, through a jitted forward's own call-back: every held pair once,
    so ``placed == held`` (a share held: fewer than the routed pairs), and a
    reader can tell a program that lists once from one that searches.
    ``gathered``: the pairs the gather after the loop combined, all the held
    ones where the device holds the whole mixture and none where it holds a
    share; the registry's ``moe_combined_pairs_total`` splits the same way.
    ``steps`` is what the program ran: counted here by a call-back in each
    grouped product, three a step. ``streamed``: where the device holds the
    whole mixture and a call brings a row tile of tokens or fewer (50), all
    its held pairs: nothing was listed or gathered, no grouped product ran,
    and the counter says ``by="streamed"``."""
    from ray_tpu import observability
    from ray_tpu.util import metrics
    seen, ran = [], []
    monkeypatch.setattr(
        observability, "span",
        lambda name, **attrs: seen.append((name, attrs)) or _Null())
    monkeypatch.setattr(expert, "CHUNK_ROWS", 64)
    plain = jax.lax.ragged_dot

    def counted(*args, **kwargs):
        jax.debug.callback(lambda: ran.append(1))
        return plain(*args, **kwargs)

    monkeypatch.setattr(jax.lax, "ragged_dot", counted)
    cfg = dataclasses.replace(EXPERTS, held=(first, count))
    layer = _mixture_layer(22)
    mine = jax.tree.map(lambda p: p[None, first:first + count],
                        layer["experts"])
    u = jax.random.normal(jax.random.PRNGKey(23), (2, T, 64))
    streams = expert._streams(cfg, T)
    assert streams == (count == 16 and T == 50)

    @jax.jit
    def forward(u):
        outs, loads = zip(*(held_experts_apply(
            x, layer["router"], mine, cfg, 0, bias=layer["router_bias"])
            for x in u))
        expert.record_load(jnp.stack(loads), cfg)
        return jnp.stack(outs), jnp.stack(loads)

    def combined():
        return {dict(map(tuple, tags))["by"]: v
                for f in metrics.snapshot()
                if f["name"] == "moe_combined_pairs_total"
                for _, tags, v in f["samples"]}

    before = combined()
    _, loads = forward(u)
    jax.effects_barrier()
    (name, attrs), = seen
    held = int(loads[:, 0].sum())
    assert name == "moe.route" and attrs["held"] == held
    assert attrs["placed"] == (0 if streams else held)
    assert attrs["layers"] == 2
    idx = [expert.route(x, layer["router"], cfg, layer["router_bias"])[0]
           for x in u]
    assert held == sum(int(jnp.sum((i >= first) & (i < first + count)))
                       for i in idx)
    assert (0 < held < 2 * 200) if count == 8 else held == 2 * T * 4
    assert attrs["streamed"] == (held if streams else 0)
    assert attrs["gathered"] == (held if cfg.all_held and not streams else 0)
    after = combined()
    by = {how: after[how] - before.get(how, 0)
          for how in ("gather", "scatter_add", "streamed")}
    assert by == {"gather": attrs["gathered"], "streamed": attrs["streamed"],
                  "scatter_add": held - attrs["gathered"] - attrs["streamed"]}
    assert attrs["steps"] == len(ran) / 3 == (0 if streams else sum(
        -(-int(n) // 64) for n in loads[:, 0]))


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# -- the layers and the stack against the reference ------------------------------------


@pytest.mark.parametrize("first,kind", [(0, CONV), (1, ATTN_MOE),
                                        (2, CONV_MOE)])
@pytest.mark.parametrize("use_flash", [False, True])
def test_each_layer_shape_agrees_with_the_reference(first, kind, use_flash):
    """A stack of one layer of each of the three tree shapes: the last
    position's logits (through the tied head) are the reference's."""
    cfg, dims = _cut(first, 1)
    assert cfg.layer_kinds == (kind,)
    cfg = dataclasses.replace(cfg, use_flash=use_flash)
    params = _seeded(cfg, 16 + first)
    assert set(params["blocks"]) == {kind}
    tokens = jax.random.randint(jax.random.PRNGKey(17), (2, 20), 0, 96)
    got = jax.jit(lambda p, t, l: _last_logits(p, t, l, cfg))(
        params, tokens, jnp.array([19, 19]))
    want = jax.jit(lambda p, t: lfm2_reference.tree_last_logits(p, t, dims))(
        params, tokens)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert float(jnp.abs(want).max()) > 0.05


def test_the_nine_layer_stack_agrees_with_the_reference_under_jit_and_padding(
        params):
    """The cut's own pattern, prompts of 9 and 24 tokens in one padded batch
    of 32: each prompt's logits at its last real position are the
    reference's on the prompt alone."""
    assert {k: p["ln1"].shape[0] for k, p in params["blocks"].items()} == {
        CONV: 1, ATTN_MOE: 2, CONV_MOE: 6}
    key = jax.random.PRNGKey(19)
    prompts = [jax.random.randint(k, (n,), 0, 96)
               for k, n in zip(jax.random.split(key, 2), (9, 24))]
    tokens = jnp.stack([jnp.pad(p, (0, 32 - len(p))) for p in prompts])
    last = jnp.array([len(p) - 1 for p in prompts])
    got = jax.jit(lambda p, t, l: _last_logits(p, t, l, TINY))(params, tokens,
                                                               last)
    for row, prompt in zip(got, prompts):
        want = jax.jit(lambda p, t: lfm2_reference.tree_last_logits(
            p, t, TINY_DIMS))(params, prompt[None])[0]
        np.testing.assert_allclose(row, want, atol=2e-4)


def test_the_runs_scan_is_the_block_on_each_layer_in_the_published_order(
        params):
    """``_apply_parts`` against ``_parts_block`` applied layer by layer, each
    on its own kind's stack at its own index: the same states, and a layer
    of the wrong index would show."""
    x = jax.random.normal(jax.random.PRNGKey(21), (2, 20, 64))
    positions = jnp.broadcast_to(jnp.arange(20)[None], (2, 20))
    got = jax.jit(lambda b, x: transformer._apply_parts(
        b, x, positions, TINY))(params["blocks"], x)
    block = jax.jit(lambda stack, index, x, kind: transformer._parts_block(
        stack, index, x, positions, TINY, kind), static_argnums=(1, 3))
    want, taken = x, dict.fromkeys(PARTS, 0)
    for kind in TINY.layer_kinds:
        want, load = block(params["blocks"][kind], taken[kind], want, kind)
        assert (load is None) == (kind == CONV)
        taken[kind] += 1
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    swapped, _ = block(params["blocks"][CONV_MOE], 1, x, CONV_MOE)
    first, _ = block(params["blocks"][CONV_MOE], 0, x, CONV_MOE)
    assert float(jnp.abs(swapped - first).max()) > 0.1


def test_a_forwards_loads_reach_the_counters_in_one_call_back(params):
    from ray_tpu.util import metrics

    def calls():
        return sum(v for f in metrics.snapshot()
                   if f["name"] == "moe_layer_calls_total"
                   for _, _, v in f["samples"])

    before = calls()
    tokens = jax.random.randint(jax.random.PRNGKey(23), (1, 16), 0, 96)
    jaxpr = str(jax.make_jaxpr(
        lambda p, t: transformer.backbone(p, t, TINY))(params, tokens))
    assert jaxpr.count("debug_callback[") == 1
    jax.block_until_ready(jax.jit(
        lambda p, t: transformer.backbone(p, t, TINY))(params, tokens))
    jax.effects_barrier()
    assert calls() - before == 8            # the mixture layers of the nine


def test_the_q_and_k_norms_come_before_the_rotation_and_only_by_the_field():
    """A dense stack with ``qk_norm``: two more leaves a layer, the logits
    those of the reference's attention (norm, then rotation); a norm after
    the rotation, or none, is another number."""
    cfg = TransformerConfig(vocab_size=96, d_model=64, n_layers=1, n_heads=4,
                            n_kv_heads=2, d_ff=96, dtype=jnp.float32,
                            use_flash=False, remat=False, rope_theta=1e6,
                            norm_eps=1e-5, qk_norm=True)
    params = _seeded(cfg, 24)
    attn = jax.tree.map(lambda p: p[0], params["blocks"]["attn"])
    assert attn["q_norm"].shape == attn["k_norm"].shape == (16,)
    h = jax.random.normal(jax.random.PRNGKey(25), (1, 12, 64))
    positions = jnp.arange(12)[None]
    mixer = jax.jit(lambda a, h, cfg: transformer._attention_mixer(
        a, h, positions, cfg, None)[0], static_argnums=2)
    got = mixer(attn, h, cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda a, h: lfm2_reference.attention(
            a, h, {**TINY_DIMS}))(attn, h[0])
    np.testing.assert_allclose(got, want, atol=2e-5)
    plain = dataclasses.replace(cfg, qk_norm=False)
    assert "q_norm" not in jax.eval_shape(
        _init, jax.random.PRNGKey(0), plain)["blocks"]["attn"]
    off = mixer(attn, h, plain)
    assert float(jnp.abs(off - got).max()) > 1e-2


def test_the_tied_head_is_the_embedding_transposed(params):
    assert "lm_head" not in params
    x = jax.random.normal(jax.random.PRNGKey(27), (2, 3, 64))
    got = transformer.head(params, x, TINY)
    untied = dataclasses.replace(TINY, tie_embeddings=False)
    want = transformer.head({**params, "lm_head": params["embed"].T}, x,
                            untied)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(transformer._head_weight(params, TINY),
                                  params["embed"].T)
    assert "lm_head" in jax.eval_shape(_init, jax.random.PRNGKey(0), untied)


# -- the configuration's guards ------------------------------------------------------


def test_the_kinds_are_eighteen_and_the_new_ones_are_a_mixer_and_an_ffn():
    assert len(KINDS) == 18 and set(PARTS) == {
        CONV, CONV_MOE, ATTN_MOE, transformer.LATENT, transformer.LATENT_MOE,
        transformer.MAMBA, transformer.ATTN, transformer.WINDOW_MOE,
        transformer.GLOBAL_MOE, *transformer.SAMBAY}
    assert {PARTS[k] for k in PARTS} == {("shortconv", "mlp"),
                                         ("shortconv", "moe"),
                                         ("attn", "moe"), ("latent", "mlp"),
                                         ("latent", "moe"), ("mamba", "mlp"),
                                         ("attn", "mlp"), ("mamba1", "mlp"),
                                         ("diff", "mlp"), ("gmu", "mlp")}
    with pytest.raises(ValueError, match="among each other only"):
        dataclasses.replace(TINY, n_layers=2,
                            layer_kinds=(CONV, transformer.LINEAR),
                            layer_ids=None)
    with pytest.raises(ValueError, match="of its own kind only"):
        dataclasses.replace(TINY, n_layers=2,
                            layer_kinds=(CONV, transformer.DENSE),
                            layer_ids=None)
    with pytest.raises(ValueError, match="a mixture needs experts="):
        dataclasses.replace(TINY, experts=None)
    # a stack of conv layers with dense FFNs needs none
    dataclasses.replace(TINY, n_layers=2, layer_kinds=(CONV, CONV),
                        layer_ids=None, experts=None)


# a published layer of each kind: (kind, layer_types' value, published index)
A_LAYER = {CONV: ("conv", 0), CONV_MOE: ("conv", 2),
           ATTN_MOE: ("full_attention", 5)}


@pytest.mark.parametrize("kinds", [(CONV,), (CONV_MOE,), (ATTN_MOE,),
                                   TINY.layer_kinds],
                         ids=["conv", "conv_moe", "attn_moe", "the_cut"])
def test_a_train_step_of_the_kinds_is_the_references_autodiff(kinds):
    """Every ``PARTS`` kind trains: one step of ``make_lm_train_step`` on a
    mesh of one device reads the loss and the gradient norm that autodiff of
    the plain reference reads on the same float32 weights, and a second step
    reads a smaller loss. The router's bias is moved by load, never by the
    optimizer, which holds no state for it."""
    from ray_tpu.parallel import MeshConfig, ShardingRules, build_mesh
    if kinds == TINY.layer_kinds:
        cfg, dims = TINY, TINY_DIMS
    else:
        types, ids = zip(*(A_LAYER[kind] for kind in kinds))
        cfg = dataclasses.replace(TINY, n_layers=len(kinds),
                                  layer_kinds=kinds, layer_ids=ids)
        dims = {**TINY_DIMS, "n_layers": len(kinds),
                "layer_types": list(types), "layer_ids": list(ids)}
    mesh = build_mesh(MeshConfig(data=1), jax.devices()[:1])
    init_fn, step_fn, shard = make_lm_train_step(cfg, mesh, ShardingRules())
    key = jax.random.PRNGKey(61)
    state = init_fn(key)
    params = _init(key, cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(62), (2, 25),
                                           0, cfg.vocab_size))
    want_loss, want_norm = jax.jit(
        lambda p, t: lfm2_reference.loss_and_grad_norm(p, t, dims))(
            params, jnp.asarray(tokens))
    moments = jax.tree_util.tree_flatten_with_path(state[1])[0]
    assert not any("router_bias" in jax.tree_util.keystr(path)
                   for path, _ in moments)
    state, first = step_fn(state, shard(tokens))
    np.testing.assert_allclose(first["loss"], want_loss, rtol=2e-5)
    np.testing.assert_allclose(first["grad_norm"], want_norm, rtol=2e-4)
    state, second = step_fn(state, shard(tokens))
    assert float(second["loss"]) < float(first["loss"])
    expert.flush_loads()
    for kind in set(kinds) - {CONV}:
        moved = (state[0]["blocks"][kind]["router_bias"]
                 - params["blocks"][kind]["router_bias"])
        steps = np.round(np.asarray(moved) / expert.BIAS_RATE)
        np.testing.assert_allclose(np.asarray(moved),
                                   steps * expert.BIAS_RATE, atol=1e-7)
        assert set(np.unique(steps)) <= {-2.0, -1.0, 0.0, 1.0, 2.0}
        assert np.any(steps != 0)


def test_logical_axes_mirror_the_tree(params):
    axes = transformer.logical_axes(TINY)
    is_axes = lambda a: isinstance(a, tuple)   # noqa: E731
    flat_axes = jax.tree_util.tree_flatten_with_path(axes, is_leaf=is_axes)[0]
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [p for p, _ in flat] == [p for p, _ in flat_axes]
    assert all(a.ndim == len(b) for (_, a), (_, b) in zip(flat, flat_axes))


def test_the_conv_mixer_runs_under_a_scope_of_its_own_at_the_layers_top(
        params):
    """``shortconv`` is the first scope on the path of the mixer's
    operations (not inside ``attn``), it is declared, and no accepted scope
    was renamed for it."""
    assert metric_names.LATER_DEVICE_SCOPES == {"shortconv", "mamba", "swa",
                                                "nope", "global", "cross",
                                                "gmu"}
    assert not metric_names.LATER_DEVICE_SCOPES & metric_names.DEVICE_SCOPES
    tokens = jnp.zeros((1, 16), jnp.int32)
    text = jax.jit(lambda p, t: transformer.backbone(p, t, TINY)).lower(
        params, tokens).compile().as_text()
    import re
    scopes = metric_names.DEVICE_SCOPES | metric_names.LATER_DEVICE_SCOPES
    paths = {tuple(t for t in re.split(r"[/():]", name) if t in scopes)
             for name in re.findall(r'op_name="([^"]*)"', text)}
    firsts = {path[0] for path in paths if path}
    assert {"shortconv", "attn", "mlp", "moe"} <= firsts
    assert not any("shortconv" in path[1:] for path in paths)
    assert ("attn", "core") in paths and ("moe", "router") in {
        tuple(dict.fromkeys(p))[:2] for p in paths}


# -- the accepted programs are the parent commit's -------------------------------------


@pytest.mark.parametrize("name,cfg,want", [
    ("sala", MIXED, ("b02615aa0512187f", "8ca80f33e12a0f43")),
    ("longcat", held(TINY_LONGCAT, 0, 8),
     ("83631133cd5a9c93", "4c9b2b767d4c83d3")),
])
def test_the_mixed_and_the_shortcut_programs_trace_what_the_parent_traced(
        name, cfg, want):
    """The long-document and the prefill cell's programs at the tests' tiny
    sizes: ``init_params`` and ``backbone`` + ``head`` are the jaxprs of
    the parent commit, to the letter (read off it by this function): the
    router's new fields default to LongCat's router, and the ``sparse`` and
    ``linear`` runs still scan their own slices. **LongCat's pair is read
    with the tiny stack cut as the cell is, a share of the routed experts
    held (8 of 16; the cell holds 16 of 512), off commit 6fef1d7, PR 54's
    parent**: a device that holds a share scatter-adds a step's rows as it
    did, to the letter. Until PR 54 this case read the tiny stack with all
    16 held (``08181005a897495d``, ``74d336bce51ce986``, the second read off
    the commit that lists a call's held pairs once, ``expert._held_rows``,
    and then off the one that gave the dropless loop a backward pass, PR 48:
    the same equations inside one ``custom_vjp_call``); a stack that holds
    every routed expert now combines by gather (``ExpertConfig.all_held``)
    and traces another program on purpose, which
    ``test_shortcut_layer.py::test_a_step_of_the_loop_searches_for_nothing``
    reads. The mixed stack's pair is the older commit's."""
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), key)
    tokens = jax.ShapeDtypeStruct((2, 48), jnp.int32)
    assert (_digest(lambda k: transformer.init_params(k, cfg), key),
            _digest(lambda p, t: transformer.head(
                p, transformer.backbone(p, t, cfg), cfg), params,
                tokens)) == want
