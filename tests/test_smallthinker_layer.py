"""SmallThinker's layer (window attention with positions beside global
attention without, a router that reads the block's input, ReGLU experts) at
toy widths against ``benchmark/smallthinker_reference.py``: the forward, and
prefill, insert and decode through the two caches, with four planted faults
that the comparison must catch."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import smallthinker_reference as reference
from benchmark.adapters import smallthinker_decoder
from ray_tpu.models import transformer
from smallthinker_tiny import TINY_DIMS

W = TINY_DIMS["window"]
SLOTS, CACHE, NEW = 5, 48, 20               # 2.5 turns of a ring of 8
# (prompt length, its bucket, slot): shorter than the window in a bucket
# shorter than it (the answer crosses the window), longer than the window,
# shorter than it in a long bucket
PROMPTS = ((5, 6, 1), (20, 24, 3), (7, 24, 4))


def _config(use_flash=False):
    return smallthinker_decoder.program_config(
        TINY_DIMS, CACHE, {"dtype": "float32", "use_flash": use_flash})


@pytest.fixture(scope="module")
def tiny():
    """Seeded weights with the norms moved off 1, the three sequences and
    the reference's logits and k at every position of each."""
    cfg = _config()
    params = transformer.init_params(jax.random.PRNGKey(55), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(56), len(leaves))
    params = jax.tree.unflatten(tree, [
        p * (1 + 0.3 * jax.random.normal(k, p.shape))
        if "ln" in jax.tree_util.keystr(path) else p
        for (path, p), k in zip(leaves, keys)])
    longest = max(n for n, _, _ in PROMPTS) + NEW
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, longest), 0,
                                cfg.vocab_size)
    logits, keys = reference.tree_logits_and_keys(params, tokens, TINY_DIMS)
    return {"cfg": cfg, "params": params, "tokens": tokens,
            "logits": np.asarray(logits), "keys": np.asarray(keys)}


def test_the_tree_is_two_stacks_and_a_head_of_its_own_width(tiny):
    cfg, blocks = tiny["cfg"], tiny["params"]["blocks"]
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads
    assert sorted(blocks) == ["global_moe", "window_moe"]
    assert blocks["window_moe"]["attn"]["wq"].shape == (6, 48, 4, 16)
    assert blocks["global_moe"]["attn"]["wk"].shape == (2, 48, 2, 16)
    assert blocks["window_moe"]["experts"]["wi"].shape == (6, 8, 48, 24)
    assert blocks["global_moe"]["router"].shape == (2, 48, 8)
    assert "mlp" not in blocks["window_moe"] and "lm_head" in tiny["params"]
    assert jax.tree.structure(tiny["params"]) == jax.tree.structure(
        transformer.logical_axes(cfg), is_leaf=lambda a: isinstance(a, tuple))


def test_the_reference_draws_what_init_params_draws_leaf_for_leaf():
    key = jax.random.PRNGKey(7)
    mine = transformer.init_params(key, _config())
    theirs = reference.draw_tree(key, TINY_DIMS)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("use_flash", [False, True])
def test_apply_is_the_reference(tiny, use_flash):
    """The forward over 40 positions, five windows long: the masked product
    and the flash kernel with its window (interpreted)."""
    got = transformer.apply(tiny["params"], tiny["tokens"],
                            _config(use_flash))
    np.testing.assert_allclose(got, tiny["logits"], rtol=2e-4, atol=2e-5)


def test_the_streamed_reference_is_the_tree_reference():
    key = jax.random.PRNGKey(9)
    params = reference.draw_tree(key, TINY_DIMS)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (30,), 0, 96)
    logits, keys, margins = reference.logits_and_keys_from(key, tokens, 25,
                                                           4, TINY_DIMS)
    want, want_keys = reference.tree_logits_and_keys(params, tokens[None],
                                                     TINY_DIMS)
    np.testing.assert_allclose(logits, want[0, 25:29], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(keys, want_keys[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        reference.last_logits(key, tokens[None], TINY_DIMS), want[:, -1],
        rtol=1e-5, atol=1e-6)
    # a layer's margins: the first layer's router reads the embedding
    first = np.asarray(params["embed"][tokens] @ reference.from_tree(
        params, 0, TINY_DIMS)["router"])
    ranked = np.sort(first, axis=-1)[:, ::-1]
    k = TINY_DIMS["top_k"]
    assert margins.shape == (TINY_DIMS["n_layers"], 30)
    np.testing.assert_allclose(
        margins[0], (ranked[:, k - 1] - ranked[:, k]) / first.std(-1),
        rtol=1e-4, atol=1e-6)
    assert (np.asarray(margins) >= 0).all()


def _served(tiny, cfg=None):
    """Each prompt prefilled alone at its bucket, inserted into its slot of
    a state whose every slot holds another sequence already, and ``NEW``
    steps with its own next tokens: each sequence's logits at its last
    prompt position and at every generated one, ``[1 + NEW, V]``, and the
    state after the last step."""
    cfg = cfg or tiny["cfg"]
    params, tokens = tiny["params"], tiny["tokens"]
    prefill = jax.jit(lambda t, n: transformer.prefill(params, t, n,
                                                       cfg)[:2])
    step = jax.jit(lambda t, s: transformer.decode_step(params, t, s,
                                                        cfg)[:2])
    state = transformer.init_decode_state(cfg, SLOTS, CACHE)
    # every slot occupied by something else first: a sequence of 30 tokens,
    # which has been round the ring
    other = jax.random.randint(jax.random.PRNGKey(3), (SLOTS, 32), 0, 96)
    _, piece = prefill(other, jnp.full((SLOTS,), 30))
    state = transformer.insert_state(state, piece, 0)
    out = {}
    for r, (n, bucket, slot) in enumerate(PROMPTS):
        prompt = jnp.where(jnp.arange(bucket) < n, tokens[r, :bucket], 5)
        last, piece = prefill(prompt[None], jnp.array([n]))
        out[r] = [transformer.head(params, last[:, None], cfg)[0, 0]]
        state = transformer.insert_state(state, piece, slot)
    for i in range(NEW):
        feed = jnp.zeros((SLOTS,), jnp.int32)
        for r, (n, _, slot) in enumerate(PROMPTS):
            feed = feed.at[slot].set(tokens[r, n + i])
        logits, state = step(feed, state)
        for r, (_, _, slot) in enumerate(PROMPTS):
            out[r].append(logits[slot])
    return {r: np.asarray(jnp.stack(v)) for r, v in out.items()}, state


def _worst(tiny, got):
    return max(float(np.abs(got[r] - tiny["logits"][r, n - 1:n + NEW]).max())
               for r, (n, _, _) in enumerate(PROMPTS))


@pytest.fixture(scope="module")
def sound(tiny):
    return _served(tiny)


@pytest.mark.parametrize("program", ["plain", "kernels",
                                     "kernels_tiles_of_8_rows"])
def test_prefill_insert_and_steps_through_both_caches_are_the_reference(
        tiny, sound, program, monkeypatch):
    """Logits at every generated position of the three, decoded beside two
    other occupied slots for 2.5 turns of the ring; and what the two caches
    hold after the last step is the reference's k at those positions. With
    the kernels a step's attention is ``ops.decode_attention`` over the full
    cache and over the ring (a ring of 8 rows is one tile; in tiles of 8 the
    full cache's 48 rows are six, and the three sequences cross their
    edges)."""
    if program == "plain":
        got, state = sound
    else:
        if program.endswith("rows"):
            monkeypatch.setattr(
                importlib.import_module("ray_tpu.ops.decode_attention"),
                "_ROWS", 8)
        got, state = _served(tiny, _config(use_flash=True))
    for r, (n, _, _) in enumerate(PROMPTS):
        np.testing.assert_allclose(got[r], tiny["logits"][r, n - 1:n + NEW],
                                   rtol=2e-4, atol=2e-5)
    assert state.k.shape == (2, SLOTS, CACHE, 32)
    assert state.ring_k.shape == state.ring_v.shape == (6, SLOTS, W, 32)
    assert state.ssm.size == state.conv.size == 0
    is_window = np.array([t == "window" for t in TINY_DIMS["layer_types"]])
    for r, (n, _, slot) in enumerate(PROMPTS):
        held = n + NEW                      # positions the slot has taken in
        assert int(state.lengths[slot]) == held
        keys = tiny["keys"][r]              # [L, S, kv]
        np.testing.assert_allclose(state.k[:, slot, :held],
                                   keys[~is_window, :held],
                                   rtol=2e-4, atol=2e-5)
        positions = np.arange(held - W, held)
        np.testing.assert_allclose(
            state.ring_k[:, slot][:, positions % W],
            keys[is_window][:, positions], rtol=2e-4, atol=2e-5)


FAULTS = {
    "router_after_attention": ("EARLY_ROUTED", ()),
    "window_layer_sees_everything": (
        "_kind_attention",
        lambda cfg, kind: (kind == transformer.WINDOW_MOE, None)),
    "global_layer_with_positions": (
        "_kind_attention",
        lambda cfg, kind: (True, cfg.window
                           if kind == transformer.WINDOW_MOE else None)),
    "ring_one_row_off": ("_ring_row", lambda p, rows: (p + 1) % rows),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(tiny, sound, fault,
                                              monkeypatch):
    """The same comparison with one thing wrong in the program reads a
    hundred times the sound program's error or more."""
    name, planted = FAULTS[fault]
    monkeypatch.setattr(transformer, name, planted)
    broken = _worst(tiny, _served(tiny)[0])
    assert _worst(tiny, sound[0]) < 1e-4 and broken > 1e-2


def test_a_window_layer_needs_its_window_and_differentiating_it_is_refused():
    with pytest.raises(ValueError, match="needs window="):
        dataclasses.replace(_config(), window=None)
    cfg = _config(use_flash=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 17), jnp.int32)
    with pytest.raises(NotImplementedError, match="no backward pass"):
        jax.grad(lambda p: transformer.loss_fn(p, tokens, cfg))(params)
    # without the kernel the masked product differentiates as plain JAX
    loss = jax.grad(lambda p: transformer.loss_fn(p, tokens, _config()))(
        params)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(loss))


# -- the ReLU-gated expert, the blocked mixture, the slots' room ---------------------


def _plain_mixture(u, idx, w, experts, act):
    out = jnp.zeros(u.shape, jnp.float32)
    for e in range(experts["wi"].shape[0]):
        mine = jnp.sum(jnp.where(idx == e, w, 0), -1)
        y = (act(u @ experts["wi"][e]) * (u @ experts["wg"][e])) \
            @ experts["wo"][e]
        out = out + mine[:, None] * y
    return out


@pytest.mark.parametrize("held", [(0, 8), (2, 4)])
def test_the_relu_expert_and_its_gradient_are_plain_jnp(held):
    """``ExpertConfig(activation="relu")`` through the dropless loop and its
    backward pass, a device that holds every expert (gather) and one that
    holds a share (scatter-add), against the plain sum under autodiff (136
    tokens: a row tile or fewer held whole would be streamed, below)."""
    from ray_tpu.parallel import expert
    cfg = dataclasses.replace(_config().experts, held=held)
    first, count = held
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    u = jax.random.normal(ks[0], (136, 48))
    assert not expert._streams(cfg, 136)
    router = jax.random.normal(ks[1], (48, 8))
    experts = {"wi": jax.random.normal(ks[2], (count, 48, 24)) / 7,
               "wg": jax.random.normal(ks[3], (count, 48, 24)) / 7,
               "wo": jax.random.normal(ks[4], (count, 24, 48)) / 5}
    idx, w = expert.route(u, router, cfg)

    def ours(u, experts):
        return expert.held_pairs_apply(
            u, idx, w, jax.tree.map(lambda p: p[None], experts), cfg, 0)[0]

    def plain(u, experts):
        return _plain_mixture(u, idx - first, w, experts, jax.nn.relu)

    np.testing.assert_allclose(ours(u, experts), plain(u, experts),
                               atol=2e-5)
    # the silu expert would not pass for it
    assert float(jnp.abs(plain(u, experts) - _plain_mixture(
        u, idx - first, w, experts, jax.nn.silu)).max()) > 1e-2
    got = jax.grad(lambda u, e: jnp.sum(ours(u, e) ** 2), (0, 1))(u, experts)
    want = jax.grad(lambda u, e: jnp.sum(plain(u, e) ** 2), (0, 1))(u,
                                                                    experts)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4),
                 got, want)
    with pytest.raises(ValueError, match="'silu' or 'relu'"):
        dataclasses.replace(cfg, activation="gelu")


def test_a_long_calls_mixture_goes_through_the_loop_in_blocks(tiny,
                                                              monkeypatch):
    """Above ``LIST_PAIRS`` routed pairs a call the tokens take the dropless
    loop in equal blocks (of 130 tokens here: over a row tile, so each block
    lists its pairs as the whole call does): the same sum, and the same load
    (the most-loaded expert is the most chosen one)."""
    cfg, params = tiny["cfg"], tiny["params"]
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 390, 48))
    stack = params["blocks"]["window_moe"]
    whole = transformer._mixture(stack, 2, x, None, cfg)
    monkeypatch.setattr(transformer, "LIST_PAIRS", 600)  # 1560 pairs: 3 blocks
    blocked = transformer._mixture(stack, 2, x, None, cfg)
    np.testing.assert_allclose(blocked[0], whole[0], atol=2e-6)
    np.testing.assert_array_equal(blocked[1], whole[1])
    np.testing.assert_array_equal(blocked[2], whole[2])
    assert int(whole[1][0]) == 1560 and int(whole[1][3]) == int(
        whole[2].max())


# -- a row tile of tokens or fewer: every expert streamed once ------------------------

# what each case changes of: 20 tokens, 8 experts all held, 4 a token, ReLU,
# float32, one layer's leaves, a seeded router
STREAMED = {
    "relu": {},
    "silu": {"activation": "silu"},
    "zero-compute picks": {"n_zero": 3},
    "experts that no row chose": {"only": (1, 2, 4, 6, 7)},
    "a traced layer of three, the others NaN": {"layers": 3, "layer": 1},
    "rows not a multiple of the sublanes": {"T": 11},
    "bfloat16, rows not a multiple of 16": {"dtype": jnp.bfloat16, "T": 24},
    "a full row tile": {"T": 128},
}


def _streamed_case(activation="relu", n_zero=0, only=None, layers=1, layer=0,
                   T=20, dtype=jnp.float32):
    from ray_tpu.parallel import expert
    cfg = dataclasses.replace(_config().experts, activation=activation,
                              n_zero=n_zero)
    ks = jax.random.split(jax.random.PRNGKey(41), 5)
    u = jax.random.normal(ks[0], (T, 48)).astype(dtype)
    router = jax.random.normal(ks[1], (48, 8 + n_zero))
    if only is not None:    # the other experts' logits far below every one's
        shut = jnp.array([e not in only for e in range(8)])
        router = jnp.where(shut, 0.0, router)
        u = u.at[:, 0].set(1.0)
        router = router.at[0].set(jnp.where(shut, -60.0, router[0]))
    experts = {"wi": jax.random.normal(ks[2], (layers, 8, 48, 24)) / 7,
               "wg": jax.random.normal(ks[3], (layers, 8, 48, 24)) / 7,
               "wo": jax.random.normal(ks[4], (layers, 8, 24, 48)) / 5}
    experts = jax.tree.map(lambda p: p.astype(dtype), experts)
    # the other layers' weights NaN: the index maps read ``layer``'s alone
    # (the CPU's grouped product multiplies every group, so the loop and
    # both backward passes are given the clean leaves)
    poisoned = jax.tree.map(
        lambda p: jnp.where(jnp.arange(layers).reshape(-1, 1, 1, 1) == layer,
                            p, jnp.nan), experts)
    idx, w = expert.route(u, router, cfg)
    return cfg, u, experts, poisoned, idx, w, layer


def _ways(fn, *args):
    """The primitives of ``fn``'s jaxpr that tell the three ways apart."""
    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    # a function of its own each time: a trace is kept by the function
    jaxpr = jax.make_jaxpr(lambda *args: fn(*args))(*args).jaxpr
    names = [e.primitive.name for e in eqns(jaxpr)]
    return {name: names.count(name)
            for name in ("pallas_call", "while", "ragged_dot_general")}


@pytest.mark.parametrize("case", sorted(STREAMED))
def test_a_row_tile_or_fewer_streams_every_expert_once(case, monkeypatch):
    """Where the device holds every expert and a call brings a row tile of
    tokens or fewer, ``held_pairs_apply`` is one Mosaic call
    (``ops.expert_stream``, interpreted here) and no loop: against the plain
    float32 sum, and against the dropless loop on the same inputs (which a
    ``STREAM_ROWS`` of 0 brings back), sum, loads and gradient (the loop's
    own backward, from the list that only a gradient makes)."""
    from ray_tpu.parallel import expert
    cfg, u, experts, poisoned, idx, w, layer = _streamed_case(
        **STREAMED[case])
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.relu
    assert expert._streams(cfg, u.shape[0])

    def ours(u, experts, layer):
        return expert.held_pairs_apply(u, idx, w, experts, cfg, layer)

    def plain(u, experts):
        f32 = jax.tree.map(lambda p: p[layer].astype(jnp.float32), experts)
        zero = jnp.sum(jnp.where(idx >= cfg.n_routed, w, 0.0), -1)
        return (_plain_mixture(u.astype(jnp.float32), idx, w, f32, act)
                + zero[:, None] * u.astype(jnp.float32))

    def loss(fn):
        return lambda u, e: jnp.sum(fn(u, e).astype(jnp.float32) ** 2)

    def run(experts_fwd):
        """The sum and the loads under a traced ``layer``, the gradient, and
        which way the trace took (each traced anew)."""
        traced = jnp.asarray(layer, jnp.int32)
        return (*jax.jit(lambda *args: ours(*args))(u, experts_fwd, traced),
                jax.grad(loss(lambda u, e: ours(u, e, layer)[0]), (0, 1))(
                    u, experts), _ways(ours, u, experts, traced))

    got, load, grad, ways = run(poisoned)
    assert ways == {"pallas_call": 1, "while": 0, "ragged_dot_general": 0}
    monkeypatch.setattr(expert, "STREAM_ROWS", 0)
    looped, looped_load, looped_grad, ways = run(experts)
    assert ways == {"pallas_call": 0, "while": 1, "ragged_dot_general": 3}
    want = plain(u, experts)

    exact = u.dtype == jnp.float32
    assert got.dtype == u.dtype and bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=2e-5 if exact else 0.05)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               looped.astype(jnp.float32),
                               atol=2e-5 if exact else 0.05)
    np.testing.assert_array_equal(load, looped_load)
    assert int(load[2]) == int(jnp.sum(idx >= cfg.n_routed))
    if case == "zero-compute picks":
        assert 0 < int(load[2]) < idx.size
    if case == "experts that no row chose":
        assert set(np.unique(idx)) <= {1, 2, 4, 6, 7}
    if exact:   # in bfloat16 the two sums' rounding reaches the gradients
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4),
                     grad, looped_grad)
    if not STREAMED[case]:
        auto = jax.grad(loss(plain), (0, 1))(u, experts)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4),
                     grad, auto)


@pytest.mark.parametrize("T,held", [(1, (0, 8)), (20, (2, 4)), (129, (0, 8))])
def test_one_row_a_share_held_and_more_than_a_row_tile_take_the_loop(T, held):
    """What picks the way is read off the call's shapes: a call whose pairs
    are fewer than the experts (one token's 4 of 8), a device that holds a
    share, and a call of more rows than the MXU's row tile list their pairs
    and take the dropless loop, with no Mosaic call."""
    from ray_tpu.parallel import expert
    cfg = dataclasses.replace(_config().experts, held=held)
    ks = jax.random.split(jax.random.PRNGKey(43), 5)
    u = jax.random.normal(ks[0], (T, 48))
    experts = {"wi": jax.random.normal(ks[2], (1, held[1], 48, 24)) / 7,
               "wg": jax.random.normal(ks[3], (1, held[1], 48, 24)) / 7,
               "wo": jax.random.normal(ks[4], (1, held[1], 24, 48)) / 5}
    idx, w = expert.route(u, jax.random.normal(ks[1], (48, 8)), cfg)
    assert not expert._streams(cfg, T)
    assert _ways(lambda u: expert.held_pairs_apply(
        u, idx, w, experts, cfg, 0), u) == {
            "pallas_call": 0, "while": 1, "ragged_dot_general": 3}
    got, _ = expert.held_pairs_apply(u, idx, w, experts, cfg, 0)
    want = _plain_mixture(u, idx - held[0], w,
                          jax.tree.map(lambda p: p[0], experts), jax.nn.relu)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_request_past_a_slots_13312_positions_is_refused_with_a_reply():
    """``TransformerGenerator.check`` and the engine at the cell's lengths: a
    prompt over the last bucket, and a prompt and an answer that pass
    ``cache_len`` together, are refused with an error that says so and cost
    no slot; the longest that fits is taken."""
    from ray_tpu.models.generation import TransformerGenerator
    from ray_tpu.serve.generation import GenerationEngine
    cfg = _config()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    model = TransformerGenerator(
        cfg, params, slots=2, cache_len=13312,
        length_buckets=(512, 1024, 2048, 4096, 8192, 12288))
    assert model.state.k.shape == (2, 2, 13312, 32)
    assert model.state.ring_k.shape == (6, 2, W, 32)
    model.check([1] * 12288, 1024)
    with pytest.raises(ValueError, match="longer than the last bucket"):
        model.check([1] * 12289, 1)
    with pytest.raises(ValueError, match="do not fit a slot's 13312"):
        model.check([1] * 12288, 1025)
    assert model.live_rows([5, 20]) == 2 * 25 + 6 * (5 + W)
    # the masked product reads every allocated row of both slots; the kernel
    # a slot's tiles up to its newest row: one of 512 a global layer, the
    # ring's one of 8
    assert model.read_rows([5, 20]) == 2 * 2 * 13312 + 6 * 2 * W
    model.cfg = dataclasses.replace(cfg, use_flash=True)
    assert model.read_rows([5, 20]) == 2 * (512 + 512) + 6 * (W + W)
    assert model.read_rows([5, 513]) == 2 * (512 + 1024) + 6 * (W + W)
    # the generator's programs hand the mixtures' loads out as a result: no
    # call-back that the device would wait for at every step
    step = model._decode_step.lower(model.params, model.tokens, model.state,
                                    jnp.zeros((2,), bool))
    assert "callback" not in step.as_text()
    assert jax.eval_shape(lambda *a: model._decode_step(*a), model.params,
                          model.tokens, model.state,
                          jnp.zeros((2,), bool))[3].shape == (8, 4)
    engine = GenerationEngine(model, "room", "room-engine")
    with pytest.raises(ValueError, match="do not fit a slot's 13312"):
        engine.submit({"prompt": [1] * 12288,
                       "max_new_tokens": 1025})
    assert engine.counts()["generate_admitted"] == 0
    with pytest.raises(ValueError, match="the longest prompt bucket"):
        TransformerGenerator(cfg, params, slots=1, cache_len=8192,
                             length_buckets=(12288,))


def test_the_two_attentions_are_told_apart_inside_attn(tiny):
    """The compiled prefill and the compiled step name a window layer's
    attention ``attn/swa`` and a global layer's ``attn/nope``, ``core``
    inside them, and the early router, the mixture and its experts under
    ``moe``; no model's work is left without a scope."""
    import re

    from ray_tpu.observability.metric_names import (DEVICE_SCOPES,
                                                     LATER_DEVICE_SCOPES)
    cfg, params = tiny["cfg"], tiny["params"]
    scopes = DEVICE_SCOPES | LATER_DEVICE_SCOPES
    state = transformer.init_decode_state(cfg, SLOTS, CACHE)
    programs = {
        "prefill": jax.jit(lambda t, n: transformer.prefill(
            params, t, n, cfg)).lower(jnp.zeros((1, 24), jnp.int32),
                                      jnp.array([20])),
        "step": jax.jit(lambda t, s: transformer.decode_step(
            params, t, s, cfg)).lower(jnp.zeros((SLOTS,), jnp.int32), state)}
    for name, lowered in programs.items():
        names = set(re.findall(r'op_name="([^"]*)"',
                               lowered.compile().as_text()))
        paths = {tuple(t for t in re.split(r"[/():]", n) if t in scopes)
                 for n in names}
        assert {("attn", "swa", "core"), ("attn", "nope", "core"),
                ("moe", "router")} <= paths, name
        # (an operation outside every scope has the empty path)
        assert any(p[:1] == ("moe",) and p[-1:] == ("experts",)
                   for p in paths), name
        assert all(p[0] == "attn" for p in paths
                   if {"swa", "nope"} & set(p)), name
        bare = sorted(n for n in names if any(
            w in n for w in ("dot_general", "ragged_dot", "pallas_call"))
            and not [t for t in re.split(r"[/():]", n) if t in scopes])
        assert bare == [], name
