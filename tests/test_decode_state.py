"""``transformer.prefill`` / ``decode_step`` / ``insert_state``: a prompt
prefilled and then stepped gives the full forward's logits at every position,
whatever shares the slots with it."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer
import granite_tiny as tiny

SLOTS, CACHE, PAD, NEW = 5, 40, 16, 8
LENGTHS = (10, 16, 7)


@pytest.fixture(scope="module")
def run():
    """The programs compiled once, and a batch of three prompts of different
    lengths (rows of one token array) with the full forward's logits."""
    cfg, params = tiny.config(), tiny.params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, PAD + NEW), 0,
                                cfg.vocab_size)
    return {
        "cfg": cfg, "params": params, "tokens": tokens,
        "full": transformer.apply(params, tokens, cfg),
        "prefill": jax.jit(lambda t, n: transformer.prefill(params, t, n,
                                                            cfg)[:2]),
        "insert": jax.jit(transformer.insert_state),
        "step": jax.jit(lambda t, s, a: transformer.decode_step(
            params, t, s, cfg, a)[:2]),
        "empty": transformer.init_decode_state(cfg, SLOTS, CACHE)}


def _prefilled(run, rows):
    """Rows ``rows`` of the batch prefilled together at their lengths."""
    rows = list(rows)
    lengths = jnp.array([LENGTHS[r] for r in rows])
    # what lies past a prompt's length must not matter: other tokens there
    prompt = jnp.where(jnp.arange(PAD)[None] < lengths[:, None],
                       run["tokens"][jnp.array(rows), :PAD], 5)
    return run["prefill"](prompt, lengths)


def _decoded(run, state, slots):
    """``NEW`` steps with row r's own next tokens in slot ``slots[r]``: each
    row's logits [NEW, V] and tokens' worth of state."""
    out = {r: [] for r in slots}
    active = jnp.zeros((SLOTS,), bool).at[jnp.array(list(slots.values()))
                                          ].set(True)
    for i in range(NEW):
        feed = jnp.zeros((SLOTS,), jnp.int32)
        for r, slot in slots.items():
            feed = feed.at[slot].set(run["tokens"][r, LENGTHS[r] + i])
        logits, state = run["step"](feed, state, active)
        for r, slot in slots.items():
            out[r].append(logits[slot])
    return {r: jnp.stack(v) for r, v in out.items()}, state


def test_prefill_then_steps_is_the_full_forward(run):
    """Both kinds of layer, three slots of different lengths among five: the
    first token's logits from ``prefill`` and every step's from
    ``decode_step`` are ``apply``'s at that position."""
    last, piece = _prefilled(run, range(3))
    first = transformer.head(run["params"], last[:, None], run["cfg"])[:, 0]
    for r, n in enumerate(LENGTHS):
        np.testing.assert_allclose(first[r], run["full"][r, n - 1],
                                   rtol=1e-4, atol=1e-6)
    assert piece.ssm.shape == (3, 3, 4, 16, 8)
    assert piece.conv.shape == (3, 3, 3, 80)
    assert piece.k.shape == piece.v.shape == (1, 3, PAD, 16)
    state = run["insert"](run["empty"], piece, 1)
    got, state = _decoded(run, state, {0: 1, 1: 2, 2: 3})
    for r, n in enumerate(LENGTHS):
        np.testing.assert_allclose(got[r], run["full"][r, n:n + NEW],
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(
        state.lengths, [0, LENGTHS[0] + NEW, LENGTHS[1] + NEW,
                        LENGTHS[2] + NEW, 0])


def test_a_sequence_is_the_same_alone_and_among_neighbours(run):
    """Row 0 prefilled alone into slot 3 of empty slots, and among three
    neighbours: the same greedy tokens and the same logits."""
    _, alone = _prefilled(run, [0])
    got_alone, _ = _decoded(run, run["insert"](run["empty"], alone, 3),
                            {0: 3})
    state = run["empty"]
    for r, slot in ((1, 0), (0, 3), (2, 4), (1, 2)):
        state = run["insert"](state, _prefilled(run, [r])[1], slot)
    got_among, _ = _decoded(run, state, {1: 0, 0: 3, 2: 4})
    np.testing.assert_array_equal(jnp.argmax(got_alone[0], -1),
                                  jnp.argmax(got_among[0], -1))
    np.testing.assert_allclose(got_alone[0], got_among[0], rtol=1e-5,
                               atol=1e-7)


def test_an_insert_leaves_the_other_slots_to_the_bit(run):
    _, piece = _prefilled(run, range(3))
    state = run["insert"](run["empty"], piece, 0)
    _, state = _decoded(run, state, {0: 0, 1: 1, 2: 2})
    after = run["insert"](state, _prefilled(run, [2])[1], 1)
    for name in ("ssm", "conv", "k", "v"):
        before, now = getattr(state, name), getattr(after, name)
        others = jnp.array([0, 2, 3, 4])
        np.testing.assert_array_equal(before[:, others], now[:, others])
        assert not np.array_equal(before[:, 1], now[:, 1])
    np.testing.assert_array_equal(
        after.lengths, state.lengths.at[1].set(LENGTHS[2]))


@pytest.mark.parametrize("rows", [None, 8],
                         ids=["a_slots_rows_one_tile", "tiles_of_8_rows"])
def test_the_kernels_step_is_the_plain_step(run, rows, monkeypatch):
    """``decode_step`` with ``use_flash`` (the recurrence a Mosaic call on the
    stacked state and the attention one that walks each slot's tiles of the
    cache, interpreted here) against without, from one ``prefill`` over
    ``NEW`` steps: every step's logits and all five leaves of the state it
    leaves. Two runs of Mamba layers, so the layer's index is traced. With
    tiles of 8 rows the three slots' lengths (10, 16 and 7, then 8 more)
    cross a tile's edge while empty slots stay in their first."""
    if rows:
        monkeypatch.setattr(
            importlib.import_module("ray_tpu.ops.decode_attention"), "_ROWS",
            rows)
    cfg = tiny.config(use_flash=True)
    assert cfg.use_flash and not run["cfg"].use_flash
    kernels = jax.jit(lambda t, s, a: transformer.decode_step(
        run["params"], t, s, cfg, a)[:2])
    _, piece = _prefilled(run, range(3))
    plain = with_kernels = run["insert"](run["empty"], piece, 1)
    active = jnp.array([False, True, True, True, False])
    for i in range(NEW):
        feed = jnp.zeros((SLOTS,), jnp.int32).at[1:4].set(jnp.stack(
            [run["tokens"][r, LENGTHS[r] + i] for r in range(3)]))
        want, plain = run["step"](feed, plain, active)
        got, with_kernels = kernels(feed, with_kernels, active)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for name in ("ssm", "conv", "k", "v"):
        np.testing.assert_allclose(
            getattr(with_kernels, name), getattr(plain, name), rtol=1e-4,
            atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(with_kernels.lengths, plain.lengths)
    assert float(jnp.max(jnp.abs(plain.ssm[:, 1:4]))) > 1e-2


def test_only_a_stack_of_the_two_kinds_keeps_a_state():
    dense = transformer.TransformerConfig(vocab_size=16, d_model=8,
                                          n_layers=1, n_heads=2)
    with pytest.raises(ValueError, match="keeps a state across calls"):
        transformer.init_decode_state(dense, 2, 8)
    with pytest.raises(ValueError, match="needs mamba="):
        transformer.TransformerConfig(n_layers=1, layer_kinds=("mamba",))
