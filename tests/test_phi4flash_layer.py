"""SambaY's kinds of layer (Phi-4-mini-flash: ``mamba1``, ``diff_window``,
``diff_global``, ``diff_cross``, ``gmu``) against the plain reference
(``benchmark/phi4flash_reference.py``, which imports nothing of the program),
at tiny sizes on the CPU: each mixer, the scan in its three forms, the full
forward, ``prefill`` and ``decode_step`` past the ring's length, slots of
different lengths, and a fault a piece of the mathematics that a tolerance
might not see."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import phi4flash_tiny as tiny
from benchmark import phi4flash_reference as ref
from ray_tpu.models import transformer
from ray_tpu.ops.selective_scan import selective_scan, selective_scan_step

DIMS, W = tiny.DIMS, tiny.DIMS["window"]
TOL = dict(rtol=2e-4, atol=2e-6)


def _tokens(shape, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              DIMS["vocab_size"])


def _layer(i):
    """Layer ``i`` of the tiny tree as the reference names its parts."""
    return ref.from_tree(tiny.params(), i, DIMS)


def _stack_index(i):
    kind = DIMS["layer_types"][i]
    return sum(k == kind for k in DIMS["layer_types"][:i])


def _normed(seed, length=20):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (length, DIMS["d_model"]), jnp.float32)


# -- the parts ------------------------------------------------------------------------


def test_the_layers_lie_as_phi4flash_lays_them():
    assert ref.layer_types(32) == tuple(
        ["mamba", "swa"] * 8 + ["mamba", "full"] + ["gmu", "cross"] * 7)
    assert DIMS["layer_types"] == ["mamba", "swa", "mamba", "swa", "mamba",
                                   "full", "gmu", "cross"]
    cfg = tiny.config()
    assert cfg.kinds == ("mamba1", "diff_window") * 2 + (
        "mamba1", "diff_global", "gmu", "diff_cross")
    # 32 layers are five loops, each of one period
    import dataclasses
    whole = dataclasses.replace(
        cfg, n_layers=32, layer_ids=None, layer_kinds=tuple(
            {"mamba": "mamba1", "swa": "diff_window", "full": "diff_global",
             "cross": "diff_cross", "gmu": "gmu"}[t]
            for t in ref.layer_types(32)))
    assert [(p, n, at) for p, _, n, at in transformer._sambay_runs(whole)] \
        == [(("mamba1", "diff_window"), 8, 0),
            (("mamba1", "diff_global"), 1, 16),
            (("gmu", "diff_cross"), 7, 18)]
    assert [(p, s, n) for p, s, n, _ in transformer._sambay_runs(whole, 17)] \
        == [(("diff_global",), (0,), 1), (("gmu", "diff_cross"), (0, 0), 7)]
    assert [(p, s, n) for p, s, n, _ in
            transformer._sambay_runs(whole, 0, 17)] \
        == [(("mamba1", "diff_window"), (0, 0), 8), (("mamba1",), (8,), 1)]


@pytest.mark.parametrize("l", [0, 1, 15, 17, 31])
def test_lambda_init_by_layer(l):
    want = 0.8 - 0.6 * np.exp(-0.3 * l)
    assert float(transformer.lambda_init(l)) == pytest.approx(want, rel=1e-6)
    assert float(ref.lambda_init(l)) == pytest.approx(want, rel=1e-6)


def test_a_stack_of_other_kinds_or_out_of_order_is_refused():
    import dataclasses
    cfg = tiny.config()
    for kinds, says in [
            (("mamba1", "attn") * 4, "stand among each other"),
            (("gmu",) + cfg.kinds[1:], "before every"),
            (cfg.kinds[:6] + ("diff_global", "diff_cross"), "at most one"),
            (("diff_cross",) + cfg.kinds[1:], "before every")]:
        with pytest.raises(ValueError, match=says):
            dataclasses.replace(cfg, layer_kinds=kinds)
    with pytest.raises(ValueError, match="layer_norm"):
        dataclasses.replace(cfg, layer_norm=False)


@pytest.mark.parametrize("i", [0, 2, 4])
def test_the_mamba1_mixer_is_the_references(i):
    cfg, u = tiny.config(), _normed(i)
    want, memory, state = ref.mamba(_layer(i)["mixer"], u, DIMS, 19)
    p = jax.tree.map(lambda a: a[_stack_index(i)],
                     tiny.params()["blocks"]["mamba1"]["mamba1"])
    mixer = jax.jit(lambda p, u, n=None: transformer._mamba1_mixer(
        p, u, cfg, n))
    out, (kept, tail, m) = mixer(p, u[None])
    np.testing.assert_allclose(out[0], want, **TOL)
    np.testing.assert_allclose(m[0], memory, **TOL)
    np.testing.assert_allclose(kept[0], state.T, **TOL)
    assert tail.shape == (1, 3, DIMS["d_inner"])
    # a padded prompt leaves the state and the tail of its last position
    out, (kept, tail2, _) = mixer(p, u[None], jnp.array([13]))
    np.testing.assert_allclose(
        kept[0], ref.mamba(_layer(i)["mixer"], u, DIMS, 12)[2].T, **TOL)
    np.testing.assert_allclose(tail2, mixer(p, u[None, :13])[1][1], **TOL)


@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_the_chunked_scan_the_step_and_the_sequential_scan_agree(chunk):
    B, L, C, N = 2, 21, 24, 4
    ks = jax.random.split(jax.random.PRNGKey(chunk), 5)
    x = jax.random.normal(ks[0], (B, L, C))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, C)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[2], (N, C)))
    b, c = (jax.random.normal(k, (B, L, N)) for k in ks[3:])
    y, state = selective_scan(x, dt, a, b, c, chunk=chunk)
    for row in range(B):
        want, kept = ref.selective_scan(x[row], dt[row], a.T, b[row],
                                        c[row], L - 1)
        np.testing.assert_allclose(y[row], want, **TOL)
        np.testing.assert_allclose(state[row], kept.T, **TOL)
    # token by token from an empty state, as a decode loop runs it
    s = jnp.zeros((B, N, C))
    for t in range(L):
        y_t, s = selective_scan_step(x[:, t], dt[:, t], a, b[:, t], c[:, t],
                                     s)
        np.testing.assert_allclose(y_t, y[:, t], **TOL)
    np.testing.assert_allclose(s, state, **TOL)
    # and from a state carried on
    y2, state2 = selective_scan(x[:, 9:], dt[:, 9:], a, b[:, 9:], c[:, 9:],
                                selective_scan(x[:, :9], dt[:, :9], a,
                                               b[:, :9], c[:, :9])[1], chunk)
    np.testing.assert_allclose(y2, y[:, 9:], **TOL)
    np.testing.assert_allclose(state2, state, **TOL)


def _scan_inputs(seed, lead, C, N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (*lead, C)),
            jax.nn.softplus(jax.random.normal(ks[1], (*lead, C)) - 2.0),
            -jnp.exp(jax.random.normal(ks[2], (N, C))),
            jax.random.normal(ks[3], (*lead, N)),
            jax.random.normal(ks[4], (*lead, N)), ks[5])


@pytest.mark.parametrize("B, L, C, N", [
    (2, 24, 64, 4),         # a block spans the channels; three trips of eight
    (1, 20, 256, 16),       # no block of whole tiles in 20: the plain loop
    (1, 512, 1024, 16),     # two time blocks by two channel blocks
])
def test_the_scans_kernel_is_the_plain_loop(B, L, C, N):
    x, dt, a, b, c, key = _scan_inputs(L, (B, L), C, N)
    s0 = jax.random.normal(key, (B, N, C))
    y, state = selective_scan(x, dt, a, b, c, s0)
    ky, kstate = jax.jit(lambda *args: selective_scan(
        *args, use_kernel=True))(x, dt, a, b, c, s0)
    np.testing.assert_allclose(ky, y, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(kstate, state, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("i", [1, 3, 5])
def test_differential_attention_is_the_references(i, use_flash):
    cfg, u = tiny.config(use_flash), _normed(10 + i)
    layer = _layer(i)["mixer"]
    kind = cfg.kinds[i]
    window = W if kind == "diff_window" else None
    q, k, v = ref.qkv(layer, u, DIMS)
    want = ref.differential(layer, q, k, v, DIMS, i, window)
    p = jax.tree.map(lambda a: a[_stack_index(i)],
                     tiny.params()["blocks"][kind]["diff"])
    ours = transformer._diff_projected(p, u[None], cfg)
    for a, b in zip(ours, (q, k, v)):
        np.testing.assert_allclose(a[0], b, **TOL)
    o = transformer._diff_core(*ours, cfg, window)
    np.testing.assert_allclose(
        transformer._diff_combine(p, o, i, cfg)[0], want, **TOL)
    # one row against a cache: the decode step's form of the same layer
    cache = [jnp.pad(a.reshape(1, 1, 20, -1),
                     ((0, 0), (0, 0), (0, 4), (0, 0))) for a in ours[1:]]
    out, _, _ = transformer._diff_row(p, u[-1:], cfg, *cache, 0,
                                      jnp.array([19]), i)
    if window is None:
        np.testing.assert_allclose(out[0], want[-1], **TOL)


def test_the_gated_memory_unit_is_the_references():
    u, memory = _normed(5), _normed(6)[:, :1].repeat(DIMS["d_inner"], 1)
    p = jax.tree.map(lambda a: a[0], tiny.params()["blocks"]["gmu"]["gmu"])
    np.testing.assert_allclose(transformer._gmu(p, u, memory),
                               ref.gmu(_layer(6)["mixer"], u, memory), **TOL)


# -- the whole stack -----------------------------------------------------------------------


@pytest.mark.parametrize("use_flash", [False, True])
def test_the_full_forward_is_the_references(use_flash):
    tokens = _tokens((2, 24))
    want = ref.tree_logits(tiny.params(), tokens, DIMS)
    ours = jax.jit(lambda p, t: transformer.apply(
        p, t, tiny.config(use_flash)))(tiny.params(), tokens)
    np.testing.assert_allclose(ours, want, **TOL)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_piece_left_out_of_the_mathematics_shows(fault):
    """The sub-norm, ``1 - lambda_init``, ``D x`` and the shared K/V: each
    left out of the reference moves the logits a thousand times further than
    the program lies from the sound one."""
    tokens = _tokens((1, 24))
    ours = transformer.apply(tiny.params(), tokens, tiny.config())
    sound = ref.tree_logits(tiny.params(), tokens, DIMS)
    faulty = ref.tree_logits(tiny.params(), tokens, DIMS, faults=(fault,))
    assert np.abs(ours - sound).max() < 5e-6
    assert np.abs(ours - faulty).max() > 5e-3


@pytest.mark.parametrize("fault", ["subln", "init_scale", "skip", "own_kv"])
def test_the_same_piece_left_out_of_the_program_shows(fault, monkeypatch):
    """The same four planted in the program, against the sound reference."""
    if fault == "subln":
        def combine(p, o, depth, cfg):      # the pairs' difference, unnormed
            init = transformer.lambda_init(depth)
            lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
                   - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)
            pairs = o.reshape(*o.shape[:-2], cfg.n_heads // 2, 2, o.shape[-1])
            d = (pairs[..., 0, :] - lam * pairs[..., 1, :]) * p["subln"] \
                * (1 - init)
            return d.reshape(*o.shape[:-2], -1) @ p["wo"] + p["bo"]

        monkeypatch.setattr(transformer, "_diff_combine", combine)
    elif fault == "init_scale":
        monkeypatch.setattr(transformer, "lambda_init",
                            lambda depth: jnp.float32(0.0))
    elif fault == "skip":
        right = transformer._mamba1_output
        monkeypatch.setattr(
            transformer, "_mamba1_output",
            lambda p, y, x, z: right(p, y, jnp.zeros_like(x), z))
    else:
        # a cross layer attends its own input's K and V, made by the full
        # layer's projection: what a stack with a cache a layer would do
        full = jax.tree.map(lambda a: a[0],
                            tiny.params()["blocks"]["diff_global"]["diff"])
        block = transformer._sambay_block

        def reads_own(stack, l, x, cfg, kind, depth, carried, lengths=None):
            if kind == "diff_cross":
                _, norm = transformer._sambay_parts(stack, l, cfg)
                _, k, v = transformer._diff_projected(full, norm(x, "ln1"),
                                                      cfg)
                carried = {**carried, "k": k, "v": v}
            return block(stack, l, x, cfg, kind, depth, carried, lengths)

        monkeypatch.setattr(transformer, "_sambay_block", reads_own)
    tokens = _tokens((1, 24))
    ours = transformer.apply(tiny.params(), tokens, tiny.config())
    sound = ref.tree_logits(tiny.params(), tokens, DIMS)
    assert np.abs(ours - sound).max() > 5e-3


def _decode(cfg, tokens, lengths, steps, cache_len=40):
    """``prefill`` of the two prompts ``tokens[:, :16]`` cut to ``lengths``,
    inserted into slots 2 and 0 of four, then ``steps`` tokens of each taken
    from ``tokens``: the logits a step and the state at the end."""
    params = tiny.params()
    prefill = jax.jit(lambda p, t, n: transformer.prefill(p, t, n, cfg))
    step = jax.jit(lambda p, t, s, a: transformer.decode_step(p, t, s, cfg,
                                                              a))
    state = transformer.init_decode_state(cfg, 4, cache_len)
    firsts = []
    for row, slot in ((0, 2), (1, 0)):
        last, piece, _ = prefill(params, tokens[row:row + 1, :16],
                                 lengths[row:row + 1])
        firsts.append(transformer.head(params, last[:, None], cfg)[0, 0])
        state = transformer.insert_state(state, piece, slot)
    active = jnp.array([True, False, True, False])
    at = np.array(lengths)
    logits = []
    for _ in range(steps):
        fed = jnp.zeros((4,), jnp.int32).at[2].set(tokens[0, at[0]]) \
            .at[0].set(tokens[1, at[1]])
        out, state, _ = step(params, fed, state, active)
        logits.append((out[2], out[0]))
        at += 1
    return firsts, logits, state


@pytest.mark.parametrize("use_flash", [False, True])
def test_prefill_then_steps_past_the_ring_are_the_references_forward(
        use_flash):
    """Two prompts of different lengths in two of four slots, decoded well
    past the window of 8: every step's logits, the scan layers' states, the
    ring's rows by position and the one full cache against the reference's
    whole forward over the same tokens."""
    cfg = tiny.config(use_flash)
    tokens, lengths = _tokens((2, 40), 7), jnp.array([13, 5])
    steps = 22
    firsts, logits, state = _decode(cfg, tokens, lengths, steps)
    assert state.k.shape == (1, 4, 40, 16) and state.ring_k.shape == (
        2, 4, W, 16) and state.ssm.shape == (3, 4, 4, 64)
    np.testing.assert_array_equal(state.lengths, [5 + steps, 0, 13 + steps,
                                                  0])
    for row, slot in ((0, 2), (1, 0)):
        n = int(lengths[row])
        want, states, rows = jax.jit(
            lambda p, t: ref.tree_forward(p, t, DIMS))(
                tiny.params(), tokens[row, :n + steps])
        np.testing.assert_allclose(firsts[row], want[n - 1], **TOL)
        for i in range(steps):
            np.testing.assert_allclose(logits[i][row], want[n + i], **TOL)
        np.testing.assert_allclose(state.ssm[:, slot],
                                   states.swapaxes(1, 2), **TOL)
        # the full layer's K and V, a row a position
        k, v = rows[5]
        np.testing.assert_allclose(state.k[0, slot, :n + steps],
                                   k.reshape(n + steps, -1), **TOL)
        np.testing.assert_allclose(state.v[0, slot, :n + steps],
                                   v.reshape(n + steps, -1), **TOL)
        # a window layer's ring: position p in row p % W
        for j, layer in enumerate((1, 3)):
            k, v = rows[layer]
            for p in range(n + steps - W, n + steps):
                np.testing.assert_allclose(state.ring_k[j, slot, p % W],
                                           k[p].reshape(-1), **TOL)
                np.testing.assert_allclose(state.ring_v[j, slot, p % W],
                                           v[p].reshape(-1), **TOL)


def test_prefills_last_position_cross_decoder_is_the_backbones():
    """``prefill`` runs the layers from the full attention on over the last
    real position alone; its state there is the whole forward's."""
    cfg, tokens = tiny.config(), _tokens((3, 16), 9)
    lengths = jnp.array([16, 9, 3])
    last, state, loads = jax.jit(
        lambda p, t, n: transformer.prefill(p, t, n, cfg))(
            tiny.params(), tokens, lengths)
    assert loads is None and state.k.shape == (1, 3, 16, 16)
    # causal: a row's state at its last real position is the padded batch's
    whole = jax.jit(lambda p, t: transformer.backbone(p, t, cfg))(
        tiny.params(), tokens)
    for row in range(3):
        np.testing.assert_allclose(last[row],
                                   whole[row, int(lengths[row]) - 1], **TOL)
    # a ring of a prompt longer than the window holds its last W positions
    _, _, rows = ref.tree_forward(tiny.params(), tokens[0], DIMS)
    for p in range(16 - W, 16):
        np.testing.assert_allclose(state.ring_k[0, 0, p % W],
                                   rows[1][0][p].reshape(-1), **TOL)


def test_an_inserted_slot_leaves_the_others_bits():
    cfg, tokens = tiny.config(), _tokens((2, 40), 11)
    _, _, before = _decode(cfg, tokens, jnp.array([13, 5]), 3)
    _, piece, _ = transformer.prefill(tiny.params(), tokens[:1, :8],
                                      jnp.array([6]), cfg)
    after = transformer.insert_state(before, piece, 1)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(np.delete(a, 1, axis=a.ndim > 1),
                                      np.delete(b, 1, axis=b.ndim > 1))
    assert int(after.lengths[1]) == 6


def test_the_kinds_are_served_not_trained():
    from ray_tpu.train import step
    assert set(transformer.SAMBAY) <= step.FORWARD_ONLY
    assert set(transformer.SAMBAY) <= set(transformer.DECODABLE)


# (the reference's names of a stack's layers, the layers that read the full
# cache, the window layers)
READERS = [
    (tiny.DIMS["layer_types"], 2, 2),
    # one window layer and three readers of the one cache: the counts differ
    (["mamba", "swa", "mamba", "full", "gmu", "cross", "gmu", "cross"], 3, 1),
    # three window layers and the full layer alone
    (["mamba", "swa", "mamba", "swa", "mamba", "swa", "mamba", "full"], 1, 3),
]


@pytest.mark.parametrize("types, full, ring", READERS)
def test_a_shared_cache_counts_once_a_reader_and_a_ring_once_a_layer(
        types, full, ring):
    """``transformer.cache_readers`` and the generator's ``live_rows`` /
    ``read_rows`` over it: a row of the one full cache counts once for each
    layer that reads it, a ring's once for its own layer, whatever the other
    count is."""
    from ray_tpu.models.generation import TransformerGenerator
    cfg = tiny.config(dims={**DIMS, "layer_types": types})
    assert transformer.cache_readers(cfg) == {"full": full, "ring": ring}
    params = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), jax.random.PRNGKey(0))
    model = TransformerGenerator(cfg, params, slots=2, cache_len=32,
                                 length_buckets=(16,))
    assert model.state.k.shape[0] == 1 and model.state.ring_k.shape[0] == ring
    assert model.live_rows([5, 20]) == full * 25 + ring * (5 + W)
    # the masked product reads every row allocated
    assert model.read_rows([5, 20]) == full * 2 * 32 + ring * 2 * W


@pytest.mark.parametrize("kinds, full, ring", [
    ((transformer.MAMBA, transformer.ATTN, transformer.MAMBA), 1, 0),
    ((transformer.WINDOW_MOE,) * 3 + (transformer.GLOBAL_MOE,), 1, 3),
])
def test_the_other_stacks_readers_are_their_own_layers(kinds, full, ring):
    import types
    assert transformer.cache_readers(types.SimpleNamespace(kinds=kinds)) == {
        "full": full, "ring": ring}
