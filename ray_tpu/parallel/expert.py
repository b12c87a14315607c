"""Expert parallelism: what one device of an expert-parallel layer computes,
and a switch-style layer with its ``all_to_all`` exchange.

``held_experts_apply`` is the function the model calls
(``models/transformer.py``: the ``shortcut`` layer kind, and the kinds whose
FFN is a mixture, ``conv_moe`` and ``attn_moe``). It is told which
experts this device holds (``ExpertConfig.held``: a share, or all of them),
routes every token over *all* the experts at the published width and
experts-per-token (``route``: softmax or sigmoid scores, the choice on the
scores or on scores plus a bias, the weights as they are or normalised over
the chosen: ``ExpertConfig``'s fields), computes its own experts' part of the
result for the tokens routed to them, dropless (the pairs routed to held
experts are listed by expert once a layer call, each placed by one counting
pass, and a loop takes the list ``CHUNK_ROWS`` rows a step: gather the rows'
tokens, three grouped products, weigh), and adds what a zero-compute expert
returns (weight x token, computed where the token lives). How a step's
weighed rows reach their tokens' sums depends on what the device holds
(``ExpertConfig.all_held``): a device that holds a share of the experts
scatter-adds them, a step at a time, into the sum the loop carries (few of a
token's pairs have a row there); a device that holds every expert writes
them into a float32 list where they lie, and one gather after the loop sums
each token's ``k`` rows. A third way lists nothing: where the device holds
every expert and a call brings one row tile of the MXU or fewer tokens
(``STREAM_ROWS``: a decode step's row a slot) with as many pairs as there are
experts, every expert is streamed past all the rows once, in one Mosaic call
that sums the products the router chose (``ops.expert_stream``): at 4.5 rows
an expert the call is bound by the experts' bytes, a weight tile in the MXU
takes 48 rows for the price of one, and the three grouped products with their
list, gathers and scatters read the weights at 59% of the chip's bandwidth
where the one call reads them at 92% (PERF.md section 6, PR 56). Which way a
call takes is read off its shapes and ``cfg`` (``_streams``). What the absent
experts would add is left out: on one device the layer runs without its
exchange, and the partial sum is what goes on. The model's stack hands it the
experts of all its layers and the layer's index: they are read as groups of
the stacked leaves (the streamed way: by the call's index maps), not cut out.

It trains too: the dropless loop has a backward pass of its own
(``_held_sum``, a ``custom_vjp``: the loop's trip count is traced) that
walks the same list a chunk at a time from the kept operands, whichever way
the forward combined (its ``d u`` is a scatter-add a step on every device: no
cell trains a mixture held whole; the streamed forward's list is made for the
backward alone, and a program that takes no gradient drops it), and the
router differentiates as
plain JAX, through its weights and not through its choice. A router's
``choice_bias`` is no parameter: ``choice_counts`` counts a call's choices
and ``moved_bias`` moves the bias by them, outside the gradient
(``train.step`` calls it after the optimizer's update). A train step's loads
reach the counters below without a call-back (``record_load_when_ready``).

``moe_apply`` is the older switch layer: top-1 routing with a capacity
limit (dropped tokens pass through the residual path), experts sharded over
the ``expert`` mesh axis and tokens sent to their expert's device by one
``jax.lax.all_to_all`` (the EP pattern the reference has no analogue for:
its parallelism stops at process-level DP, SURVEY §2.5). ``x`` replicated
over the expert axis is ``moe_apply``'s alone; no model calls it, and its
exchange is what ``held_experts_apply`` across devices will build on.
"""

from __future__ import annotations

import collections
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.expert_stream import experts_streamed

# Rows of routed (token, expert) pairs one step of the dropless loop gathers,
# multiplies and scatters: two row tiles of the TPU's ragged product.
CHUNK_ROWS = 1024
# The most tokens a call may bring for its mixture to be streamed
# (``_streams``): one row tile of the MXU, which multiplies so many rows by a
# weight tile for the price of one.
STREAM_ROWS = 128
# What ``normalize`` adds to the chosen weights' sum before it divides by it.
NORM_EPS = 1e-6
# The spread of a drawn ``choice_bias`` (``transformer.init_params``): a
# sigmoid's fourth and fifth largest of 64 scores lie 0.012 apart in the
# median, and a bias of this spread moves the choice of 60% of the tokens of
# seeded weights (tests/test_lfm2_layer.py measures the share).
BIAS_SCALE = 0.02
# Training: what a step adds to or takes from a ``choice_bias`` by the step's
# load (``moved_bias``; DeepSeek-V3's bias update speed).
BIAS_RATE = 1e-3


@dataclass(frozen=True)
class ExpertConfig:
    """A routed mixture's sizes and this device's share of it."""
    n_routed: int                 # published routed experts (router outputs
    n_zero: int                   # ... n_routed + n_zero: zero-compute ones)
    top_k: int                    # experts a token
    scale: float                  # routed_scaling_factor, on every weight
    width: int                    # an expert's hidden width
    held: Tuple[int, int]         # (first published index, count) held here
    # The router's variants; the defaults are LongCat-Flash's.
    score: str = "softmax"        # or "sigmoid": scores from the logits
    # the choice is on score + a per-expert bias (a float32 leaf beside the
    # router, never part of a weight) and not on the score alone
    choice_bias: bool = False
    # the chosen weights are divided by their sum + NORM_EPS (before scale)
    normalize: bool = False
    # Shared experts: one dense SwiGLU of this width beside the routed ones
    # (the layer's ``shared`` leaf), which every token takes on every device
    # alike; 0: none. The model computes it (``models/transformer.py``).
    shared_width: int = 0
    # A routed expert's gate: ``act(x W_i) * (x W_g)``, ``silu`` (SwiGLU) or
    # ``relu`` (ReGLU: SmallThinker's sparse experts).
    activation: str = "silu"

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {self.score!r}: 'softmax' or 'sigmoid'")
        if self.activation not in ("silu", "relu"):
            raise ValueError(
                f"activation {self.activation!r}: 'silu' or 'relu'")

    @property
    def n_outputs(self) -> int:
        return self.n_routed + self.n_zero

    @property
    def all_held(self) -> bool:
        """This device holds the whole mixture: every routed pair has a row
        in the call's list, which is what lets the dropless sum be combined
        by one gather (``_held_sum``)."""
        return self.held == (0, self.n_routed)


def _precision(dtype) -> jax.lax.Precision:
    """float32 operands multiply as float32; below 32 bits a product is
    exact in float32, so it is one pass whatever precision the caller's
    context asks for (the TPU's ragged product refuses any other)."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype).itemsize >= 4
            else jax.lax.Precision.DEFAULT)


def _streams(cfg: ExpertConfig, T: int) -> bool:
    """Whether a call of ``T`` tokens streams every expert past its rows
    (``ops.expert_stream``) and lists nothing: the device holds them all, the
    rows are one MXU row tile or fewer, and the call's pairs are as many as
    the experts (below that most experts have no row, and the dropless loop,
    which does not read an empty group, reads less)."""
    return (cfg.all_held and T <= STREAM_ROWS
            and T * cfg.top_k >= cfg.held[1])


def route(u: jax.Array, router: jax.Array, cfg: ExpertConfig,
          bias: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """``u`` [T, d], ``router`` [d, n_routed + n_zero] -> each token's
    ``top_k`` expert indices [T, k] and weights [T, k], float32. Logits,
    scores and choice are float32: the operands go to the MXU in ``u``'s
    dtype, where a product of two bfloat16 values is exact, and accumulate
    in float32. In five steps: ``s = softmax(logits)`` or
    ``sigmoid(logits)`` (``cfg.score``); the ``top_k`` largest of ``s``, or
    with ``cfg.choice_bias`` of ``s + bias`` (``bias`` [n_routed + n_zero],
    used for the choice alone); ``w = s[chosen]``; with ``cfg.normalize``
    ``w / (sum(w) + NORM_EPS)``; ``cfg.scale * w``. The defaults are
    LongCat-Flash's: softmax, the choice on ``p`` (its published bias is a
    buffer of zeros), weights ``scale * p`` not renormalised."""
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", u, router.astype(u.dtype),
                            precision=_precision(u.dtype),
                            preferred_element_type=jnp.float32)
        s = (jax.nn.softmax(logits, axis=-1) if cfg.score == "softmax"
             else jax.nn.sigmoid(logits))
        if cfg.choice_bias:
            _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), cfg.top_k)
            w = jnp.take_along_axis(s, idx, axis=-1)
        else:
            w, idx = jax.lax.top_k(s, cfg.top_k)
        if cfg.normalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_EPS)
        return idx, cfg.scale * w


def _held_rows(idx, weights, cfg: ExpertConfig, length: int):
    """The call's pairs routed to held experts, listed by expert and within
    an expert by token: ``(row_tok [length], row_w [length], bounds [count +
    1], place [T x k])``, ``length`` the ``T x k`` pairs or more. Held expert
    c's pairs are rows ``bounds[c]:bounds[c + 1]`` of the list; row s holds
    its pair's token and weight; ``bounds[count]`` pairs are held in all, and
    the rows past them hold the token ``T`` (no token) at weight 0; pair (t,
    j)'s row is ``place[t x k + j]``, ``length`` if it is not held. Counting,
    not a sort (a sort of a call's pairs takes the TPU compiler half a minute
    a shape): pair (t, j) on held expert c is the ``running[t, c]``-th of c's,
    so its row is ``bounds[c] + running[t, c] - 1``, and a scatter of the
    ``T x k`` pairs' tokens and one of their weights write the list (a token
    picks an expert once at most: a held pair has one row and a row one
    pair). A pair that is not held is placed past the list's end, which the
    scatters drop."""
    first, count = cfg.held
    T, k = idx.shape
    chosen = (idx - first)[:, :, None] == jnp.arange(count)     # [T, k, count]
    running = jnp.cumsum(jnp.any(chosen, axis=1).astype(jnp.int32), axis=0)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(running[-1])])
    # the pair's row, read off its expert's column by the one-hot (a gather
    # of T x k elements costs the TPU what a scatter of them does)
    place = jnp.sum(jnp.where(
        chosen, (bounds[:-1] + running - 1)[:, None, :], 0), axis=-1)
    place = jnp.where(jnp.any(chosen, axis=-1), place, length).reshape(-1)
    tok = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, k))
    row_tok = jnp.full((length,), T, jnp.int32).at[place].set(
        tok.reshape(-1), mode="drop")
    row_w = jnp.zeros((length,), jnp.float32).at[place].set(
        weights.reshape(-1), mode="drop")
    return row_tok, row_w, bounds, place


def _transposed(w):
    """The grouped weights with their last two axes swapped, materialised: a
    grouped product that contracts its right side's last axis is expanded by
    the TPU compiler into a dense product over every group (PERF.md section
    6, PR 48), so the backward's products against ``W^T`` are given ``W^T``."""
    return {name: jnp.swapaxes(p, 1, 2) for name, p in w.items()}


def _chunks(rows, count, n_groups, layer, row_tok, row_w, bounds):
    """``chunk(i) -> (tok, wt, sizes)`` of the dropless loop's step ``i``: the
    step's tokens and weights sliced off the list, and its rows' counts by
    group (zero outside ``layer``'s ``count`` groups)."""
    def chunk(i):
        start = i * rows
        tok = jax.lax.dynamic_slice(row_tok, (start,), (rows,))
        wt = jax.lax.dynamic_slice(row_w, (start,), (rows,))
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_groups,), jnp.int32),
            jnp.clip(bounds[1:] - start, 0, rows)
            - jnp.clip(bounds[:-1] - start, 0, rows), (layer * count,))
        return tok, wt, sizes
    return chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_sum(rows, activation, out, u, row_w, w, row_tok, bounds, layer,
              place, by_expert):
    """``out + sum_s row_w[s] Expert_{e(s)}(u[row_tok[s]])`` over the listed
    pairs (``_held_rows``), ``rows`` of the list a step, for as many steps as
    the held pairs fill: the dropless loop. ``w``: the grouped weights
    ``wi``, ``wg`` [groups, d, width] and ``wo`` [groups, width, d], of which
    ``layer``'s ``count = len(bounds) - 1`` groups are meant; an expert is
    ``(act(x wi) * (x wg)) wo``, ``act`` the ``activation`` (``silu`` |
    ``relu``). float32 [T, d].

    How a step's weighed products reach their tokens' sums is ``place``'s to
    say. ``None`` (a device that holds a share of the experts: few of a
    token's pairs have a row): each step scatter-adds its rows into the sum
    the loop carries. The pairs' rows in the list [T x k] (a device that
    holds every expert: the list is dense): a step writes its rows into a
    float32 list [length, d] where they lie, and after the loop one gather
    sums each token's ``k`` rows, in the order of its choice; a pair that is
    not held (a zero-compute pick, placed at ``length``) reads as zeros,
    whatever the list holds. A third way takes no list at all: where
    ``by_expert`` [T, count] is given (each token's weight on each held
    expert, 0 where it did not choose it: the list's pairs again, laid out by
    expert) every expert is streamed past all the rows once, in one Mosaic
    call that sums the products ``by_expert`` selects
    (``ops.expert_stream.experts_streamed``; ``held_pairs_apply`` says when),
    and the list is read by the backward alone.

    The loop's trip count is traced, so autodiff cannot reverse it: the
    backward (``_held_sum_bwd``) walks the same list the same ``rows`` at a
    time, from the operands alone, whichever way the forward combined
    (``by_expert`` gets no cotangent of its own: its weights are ``row_w``'s,
    which carries their gradient)."""
    count = bounds.shape[0] - 1
    if by_expert is not None:
        # the groups as the stacked leaves they were cut from
        stacked = {name: p.reshape(-1, count, *p.shape[1:])
                   for name, p in w.items()}
        return out + experts_streamed(u, by_expert, stacked, layer,
                                      activation)
    product = functools.partial(jax.lax.ragged_dot,
                                precision=_precision(u.dtype))
    chunk = _chunks(rows, count, w["wi"].shape[0], layer, row_tok, row_w,
                    bounds)
    steps = (bounds[count] + rows - 1) // rows
    act = jax.nn.silu if activation == "silu" else jax.nn.relu

    def weighed(i):
        tok, wt, sizes = chunk(i)
        # rows past the held pairs hold token T: the gather clamps it,
        # the rows belong to no group, and whatever the product left
        # there no sum reads (the scatter-add drops it, the list's gather
        # has no pair placed there)
        x = u.at[tok].get(mode="clip")
        hidden = (act(product(x, w["wi"], sizes))
                  * product(x, w["wg"], sizes))
        y = product(hidden, w["wo"], sizes,
                    preferred_element_type=jnp.float32)
        return tok, y * wt[:, None]

    if place is None:
        def step(i, out):
            tok, y = weighed(i)
            return out.at[tok].add(y, mode="drop")

        return jax.lax.fori_loop(0, steps, step, out)

    def step(i, listed):
        return jax.lax.dynamic_update_slice(listed, weighed(i)[1],
                                            (i * rows, 0))

    T, d = out.shape
    listed = jax.lax.fori_loop(
        0, steps, step, jnp.zeros((row_tok.shape[0], d), jnp.float32))
    # the tokens' first choices, then their second, ...: k slabs [T, d] to
    # add (summed token by token, [T, k, d] over its middle axis, the same
    # gather and sum took 1.1 ms more a layer call of 8,192 tokens: PERF.md
    # section 6, PR 54)
    by_choice = place.reshape(T, -1).T.reshape(-1)
    picked = listed.at[by_choice].get(mode="fill", fill_value=0.0)
    return out + jnp.sum(picked.reshape(-1, T, d), axis=0)


def _held_sum_fwd(rows, activation, out, u, row_w, w, row_tok, bounds, layer,
                  place, by_expert):
    return (_held_sum(rows, activation, out, u, row_w, w, row_tok, bounds,
                      layer, place, by_expert),
            (u, row_w, w, row_tok, bounds, layer))


def _held_sum_bwd(rows, activation, kept, g):
    """The dropless loop backwards, a step of the same list at a time: the
    step's rows gathered again and the two first products made again; ``g``'s
    rows against ``wo^T`` (``d hidden`` before the pair's weight, whose
    product with ``hidden`` along the width is ``d row_w``); ``d x`` against
    ``wi^T`` and ``wg^T``, scatter-added to ``d u``. Each step leaves its
    rows of the five operands of the weight gradients (``x``, ``d a``, ``d
    b``, ``hidden``, the weighted ``d y``) in lists as long as the pairs'
    own, and after the loop three grouped products whose *contracted*
    dimension is the ragged one (the rows) make the weight gradients from
    the whole lists at once: summed a step at a time, every step read and
    wrote three float32 accumulators of the layer's held weights (2.4 ms a
    step of 1,024 rows at 16 experts of 2048 x 768, sixteen times the
    products' own time: PERF.md section 6, PR 48). No pair is dropped and no
    group capped, as in the forward."""
    u, row_w, w, row_tok, bounds, layer = kept
    count = bounds.shape[0] - 1
    n_groups, n_held = w["wi"].shape[0], bounds[count]
    precision = _precision(u.dtype)
    product = functools.partial(jax.lax.ragged_dot, precision=precision,
                                preferred_element_type=jnp.float32)
    by_rows = functools.partial(
        jax.lax.ragged_dot_general, precision=precision,
        preferred_element_type=jnp.float32,
        ragged_dot_dimension_numbers=jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]))
    w_t = _transposed(w)
    chunk = _chunks(rows, count, n_groups, layer, row_tok, row_w, bounds)
    length, d, width = row_tok.shape[0], u.shape[1], w["wi"].shape[2]

    def step(i, carry):
        d_u, d_row_w, lists = carry
        tok, wt, sizes = chunk(i)
        # a row past the held pairs belongs to no group: what the products
        # left there is masked before it meets a sum
        held = (i * rows + jnp.arange(rows) < n_held)[:, None]
        x = u.at[tok].get(mode="clip")
        a = jnp.where(held, product(x, w["wi"], sizes), 0.0)
        b = jnp.where(held, product(x, w["wg"], sizes), 0.0)
        # act(a) = a * gate: silu's sigmoid, or relu's step (its own slope)
        gate = (jax.nn.sigmoid(a) if activation == "silu"
                else (a > 0).astype(a.dtype))
        hidden = a * gate * b
        d_y = jnp.where(held, g.at[tok].get(mode="clip"), 0.0)
        # d hidden for a pair of weight 1
        d_h = jnp.where(held, product(d_y.astype(u.dtype), w_t["wo"], sizes),
                        0.0)
        d_wt = jnp.sum(d_h * hidden, axis=-1)
        d_h = d_h * wt[:, None]
        d_a = d_h * b * gate
        if activation == "silu":
            d_a = d_a * (1.0 + a * (1.0 - gate))
        d_a = d_a.astype(u.dtype)
        d_b = (d_h * a * gate).astype(u.dtype)
        d_x = (product(d_a, w_t["wi"], sizes)
               + product(d_b, w_t["wg"], sizes))
        d_u = d_u.at[tok].add(jnp.where(held, d_x, 0.0), mode="drop")
        at = (i * rows, 0)
        put = jax.lax.dynamic_update_slice
        lists = {"x": put(lists["x"], x, at),
                 "d_a": put(lists["d_a"], d_a, at),
                 "d_b": put(lists["d_b"], d_b, at),
                 "hidden": put(lists["hidden"], hidden.astype(u.dtype), at),
                 "d_y": put(lists["d_y"],
                            (d_y * wt[:, None]).astype(u.dtype), at)}
        return d_u, put(d_row_w, d_wt, at[:1]), lists

    # traced where the loss is transposed, outside the scopes the forward
    # entered
    with jax.named_scope("moe"), jax.named_scope("experts"):
        d_u, d_row_w, lists = jax.lax.fori_loop(
            0, (n_held + rows - 1) // rows, step,
            (jnp.zeros(u.shape, jnp.float32), jnp.zeros_like(row_w),
             {name: jnp.zeros((length, wide), u.dtype) for name, wide in
              (("x", d), ("d_a", width), ("d_b", width), ("hidden", width),
               ("d_y", d))}))
        # the whole list's rows by group: the steps' rows past the held pairs
        # hold zeros and belong to no group
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_groups,), jnp.int32), bounds[1:] - bounds[:-1],
            (layer * count,))
        d_w = {"wi": by_rows(lists["x"], lists["d_a"], sizes),
               "wg": by_rows(lists["x"], lists["d_b"], sizes),
               "wo": by_rows(lists["hidden"], lists["d_y"], sizes)}
        return (g, d_u.astype(u.dtype), d_row_w,
                {name: p.astype(w[name].dtype) for name, p in d_w.items()},
                None, None, None, None, None)


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def held_pairs_apply(u: jax.Array, idx: jax.Array, weights: jax.Array,
                     experts: Dict[str, jax.Array], cfg: ExpertConfig, layer
                     ) -> Tuple[jax.Array, jax.Array]:
    """``held_experts_apply`` from the router's choice on: ``idx`` and
    ``weights`` [T, k] as ``route`` gives them."""
    with jax.named_scope("moe"):
        T, d = u.shape
        count = cfg.held[1]
        n = experts["wi"].shape[0]
        for name, p in experts.items():
            if p.dtype != u.dtype or p.shape[:2] != (n, count):
                raise ValueError(
                    f"experts[{name!r}] is {p.dtype}{list(p.shape)}: the "
                    f"stacked leaves are [{n}, {count}, ...] in the tokens' "
                    f"{u.dtype}")
        zero = idx >= cfg.n_routed
        out = (jnp.sum(jnp.where(zero, weights, 0.0), axis=-1, keepdims=True)
               * u.astype(jnp.float32))

        rows = min(CHUNK_ROWS, T * cfg.top_k)
        # whole steps of rows, so that the last step's slice is its own
        row_tok, row_w, bounds, place = _held_rows(
            idx, weights, cfg, -(-idx.size // rows) * rows)
        n_held = bounds[count]
        w = {name: p.reshape(n * count, *p.shape[2:])
             for name, p in experts.items()}
        by_expert = None
        if _streams(cfg, T):
            # each token's weight on each expert; nothing of this way reads
            # the list, so a program that takes no gradient drops its making
            chosen = idx[:, :, None] == jnp.arange(count)       # [T, k, count]
            by_expert = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0),
                                axis=1)
            place = None
        with jax.named_scope("experts"):
            out = _held_sum(rows, cfg.activation, out, u, row_w, w, row_tok,
                            bounds, layer, place if cfg.all_held else None,
                            by_expert)
        n_zero = jnp.sum(zero, dtype=jnp.int32)
        if by_expert is not None:
            # the same loads without the list: every expert is held, so a
            # pair is held or zero-compute, and the most-loaded expert is the
            # one most chosen
            n_held = idx.size - n_zero
        load = jnp.stack([
            n_held, idx.size - n_held - n_zero, n_zero,
            jnp.max(bounds[1:] - bounds[:-1]) if by_expert is None
            else jnp.max(jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32))])
        return out.astype(u.dtype), load


def held_experts_apply(u: jax.Array, router: jax.Array,
                       experts: Dict[str, jax.Array], cfg: ExpertConfig,
                       layer, bias: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """This device's part of a routed mixture, for tokens ``u`` [T, d]:
    ``sum_j w_tj Expert_e(u_t)`` over the chosen experts e that are held
    here, plus ``sum_j w_tj u_t`` over the chosen zero-compute indices
    (``e >= n_routed``, identity). ``experts``: ``wi``, ``wg`` [n, count, d,
    width] and ``wo`` [n, count, width, d] (SwiGLU, or ReGLU where
    ``cfg.activation`` is ``relu``), the held experts of a
    stack's ``n`` layers (one layer's own leaves: ``p[None]``, layer 0), in
    ``u``'s dtype; ``layer``, an index, traced or not, says whose are meant;
    ``bias``, the router's bias for the choice where ``cfg.choice_bias``.

    The leaves are viewed as ``n x count`` groups, of which the grouped
    product is given sizes that are zero outside ``layer x count : (layer +
    1) x count``. No layer's experts are cut out of the stack (the grouped
    product takes a materialised operand: the cut would be a copy of them,
    and so would a conversion, which is why the dtype is the caller's to
    match); a group of no rows is not read.

    Dropless: no capacity. The pairs routed to held experts are listed by
    expert once a call, outside the loop (``_held_rows``: counting, not a
    sort; each pair is placed where its expert's running count says), and
    taken ``CHUNK_ROWS`` at a time, for as many steps as they fill: slice
    the step's tokens and weights off the list, gather the tokens' rows,
    three grouped products over the held experts (``lax.ragged_dot``, which
    the TPU compiles to one Mosaic call over ragged groups), weigh, and
    combine: where ``cfg.held`` is a share of the experts the step
    scatter-adds its rows into the sum; where it is all of them
    (``cfg.all_held``) the step writes its rows into a float32 list and one
    gather after the loop sums each token's ``k`` rows, in the order of its
    choice (``_held_sum``). So the products' work grows with the routed
    pairs, not with T x experts (only the list's making, a [T, count]
    running count of integers and one placement of the T x k pairs, does),
    and every token sent to one expert or none is exact alike.

    A row tile or fewer, all held (``_streams``: ``cfg.all_held``, ``T <=
    STREAM_ROWS``, ``T x top_k >= count``; a decode step's slots): no list
    and no loop. One Mosaic call reads each expert's three matrices once, in
    expert order, off the stacked leaves (its index maps take ``layer``), with
    the T rows resident in VMEM, multiplies every row by every expert and sums
    in float32 the products that each row's weights select
    (``ops.expert_stream.experts_streamed``; the same operands, accumulation
    and rounding of ``hidden`` as the loop's). Its work is T x experts
    products, which cost what the pairs' would: a weight tile in the MXU
    takes a row tile for the price of one row, and the call is bound by the
    weights' bytes. The load is the same four numbers, from ``idx`` alone.

    Differentiable in ``u``, ``router`` and ``experts``: the router's scores
    and weights and the list's making are plain JAX (the gradient flows
    through the weights, not through the choice, and ``bias`` gets none),
    and the loop has a backward pass of its own (``_held_sum``), which the
    streamed way shares.

    Returns the partial sum [T, d] in ``u``'s dtype and the layer's load,
    int32 [4]: pairs routed to held, absent and zero-compute experts, and
    the most-loaded held expert's pairs (``record_load``)."""
    with jax.named_scope("moe"):
        # a router without a bias is called as it always was (callers that
        # stand a router of their own in ``route``'s place take three)
        idx, weights = (route(u, router, cfg) if bias is None
                        else route(u, router, cfg, bias))
        return held_pairs_apply(u, idx, weights, experts, cfg, layer)


def choice_counts(idx: jax.Array, cfg: ExpertConfig) -> jax.Array:
    """How many of the call's tokens chose each routed expert, int32
    [n_routed]: the load that moves a ``choice_bias`` (``moved_bias``)."""
    with jax.named_scope("moe"), jax.named_scope("router"):
        return jnp.sum(idx[:, :, None] == jnp.arange(cfg.n_routed),
                       axis=(0, 1), dtype=jnp.int32)


def moved_bias(bias: jax.Array, counts: jax.Array, cfg: ExpertConfig
               ) -> jax.Array:
    """A router's ``choice_bias`` after a step whose tokens chose the routed
    experts ``counts`` [..., n_routed] times: ``b_e + BIAS_RATE * sign(mean
    load - load_e)`` (DeepSeek-V3's auxiliary-loss-free balancing,
    arXiv:2412.19437 section 2.1.2): an expert chosen less than the mean is
    chosen more readily by the next step. Outside the gradient and outside
    the optimizer. A zero-compute index's bias stays."""
    counts = counts.astype(jnp.float32)
    move = BIAS_RATE * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)
    return bias.at[..., :cfg.n_routed].add(move.astype(bias.dtype))


# -- the program's counters ----------------------------------------------------


@functools.lru_cache(maxsize=1)
def _counters():
    from ray_tpu.util import metrics
    return (
        metrics.Counter(
            "moe_routed_pairs_total",
            "(token, expert) pairs routed by held_experts_apply, by where "
            "the expert lives: held here, absent (another device's), "
            "zero (zero-compute)", tag_keys=("dest",)),
        metrics.Counter(
            "moe_held_load_max_total",
            "pairs of the most-loaded held expert, summed over layer calls"),
        metrics.Counter(
            "moe_layer_calls_total", "layer calls of held_experts_apply"),
        metrics.Counter(
            "moe_combined_pairs_total",
            "held pairs whose weighed products reached their tokens' sums, "
            "by how: gather (one after the loop, where the device holds "
            "every expert), scatter_add (a step, where it holds a share), "
            "streamed (summed inside the one call that streams every expert "
            "past a row tile of tokens or fewer)", tag_keys=("by",)))


def _record(cfg: ExpertConfig, loads) -> None:
    """Host side of ``record_load``: the registry's counters, and one span
    (``moe.route``) that carries the same increments while a profiler
    session or the ring records, so that a reader finds a window's share."""
    from ray_tpu import observability
    loads = np.asarray(loads).reshape(-1, 4)
    # which way a layer call combined is in ``cfg`` and the call's tokens
    # (its pairs over ``top_k``), as it was when the program was traced
    # (``held_pairs_apply``)
    routed = loads[:, :3].sum(axis=1)
    listed = np.array([not _streams(cfg, int(n) // cfg.top_k)
                       for n in routed], bool)
    # the dropless loop's steps: a listed layer call's held pairs,
    # ``CHUNK_ROWS`` (or all the call's pairs, if fewer) a step
    rows = np.minimum(CHUNK_ROWS, routed)
    steps = int((-(-loads[:, 0] // np.maximum(rows, 1)))[listed].sum())
    held, absent, zero, most = (int(n) for n in loads.sum(axis=0))
    streamed = held - int(loads[listed, 0].sum())
    pairs, load_max, calls, combined = _counters()
    for dest, n in (("held", held), ("absent", absent), ("zero", zero)):
        pairs.inc(n, tags={"dest": dest})
    load_max.inc(most)
    calls.inc(len(loads))
    gathered = held - streamed if cfg.all_held else 0
    combined.inc(gathered, tags={"by": "gather"})
    combined.inc(held - streamed - gathered, tags={"by": "scatter_add"})
    combined.inc(streamed, tags={"by": "streamed"})
    # ``placed``: the pairs the layer calls' one counting pass wrote into
    # their lists, every listed pair once (a program that searched for its
    # rows a step has no such attribute); ``gathered``: those of them that
    # the gather after the loop combined (a program that scatter-added every
    # step's rows has no such attribute, a device that holds a share says 0);
    # ``streamed``: the held pairs of the layer calls that listed nothing (a
    # program without that way has no such attribute)
    with observability.span("moe.route", held=held, absent=absent, zero=zero,
                            load_max=most, layers=len(loads),
                            experts=cfg.held[1], steps=steps,
                            placed=held - streamed, gathered=gathered,
                            streamed=streamed):
        pass


def record_load(loads: jax.Array, cfg: ExpertConfig) -> None:
    """Feed the loads of a forward's layers (``held_experts_apply``'s
    second result, stacked [layers, 4]) to the program's counters, from
    inside a jitted program: one call-back a forward."""
    with jax.named_scope("moe"):
        jax.debug.callback(functools.partial(_record, cfg), loads)


# loads a train step left on the device, oldest first, with their mixture's
# sizes
_PENDING: collections.deque = collections.deque()


def record_load_when_ready(loads: jax.Array, cfg: ExpertConfig) -> None:
    """``record_load`` from outside a jitted program, for ``loads`` that a
    step just dispatched may still be computing: queued, and fed to the
    counters (oldest first, by this call or a later one) once the device has
    them, so that the caller never waits for a step. ``flush_loads`` waits
    for what is left."""
    _PENDING.append((loads, cfg))
    flush_loads(wait=False)


def flush_loads(wait: bool = True) -> None:
    """Feed the queued loads to the counters, oldest first: all of them,
    waiting for the device, or with ``wait`` false those it already has."""
    while _PENDING and (wait or _PENDING[0][0].is_ready()):
        ready, cfg = _PENDING.popleft()
        _record(cfg, ready)


@functools.lru_cache(maxsize=128)
def _moe_sharded(expert_fn: Callable, mesh: Mesh, axis: str,
                 n_exp_total: int, n_shards: int, exp_per_shard: int,
                 capacity_factor: float) -> Callable:
    """shard_map'd MoE dispatch, memoized on its statics so repeat calls
    with the same mesh/routing config reuse one compiled callable."""

    def per_device(x_loc, rw, params):
        tokens, d = x_loc.shape
        capacity = max(1, int(capacity_factor * tokens / n_exp_total))
        with jax.named_scope("router"):
            gates = jax.nn.softmax(x_loc @ rw, axis=-1)        # [T, E]
            expert_idx = jnp.argmax(gates, axis=-1)            # [T]
            gate_val = jnp.take_along_axis(
                gates, expert_idx[:, None], axis=-1)[:, 0]     # [T]
        # Position of each token within its expert's capacity buffer.
        onehot = jax.nn.one_hot(expert_idx, n_exp_total, dtype=jnp.int32)
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # [T, E]
        pos = jnp.sum(pos_in_expert, axis=-1)                  # [T]
        keep = pos < capacity
        # Scatter tokens into [E, capacity, d] dispatch buffer.
        disp = jnp.zeros((n_exp_total, capacity, d), x_loc.dtype)
        tok_ids = jnp.arange(tokens)
        disp = disp.at[expert_idx, jnp.clip(pos, 0, capacity - 1)].add(
            jnp.where(keep[:, None], x_loc, 0.0))
        # Exchange: [E, cap, d] -> experts grouped by owning shard.
        disp = disp.reshape(n_shards, exp_per_shard, capacity, d)
        recv = jax.lax.all_to_all(disp, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: [n_shards, exp_per_shard, capacity, d] — all shards' tokens
        # destined for MY experts. Flatten senders into the capacity dim.
        recv = recv.transpose(1, 0, 2, 3).reshape(
            exp_per_shard, n_shards * capacity, d)
        # in_specs P(axis) already hands this device its expert slice
        # (leading dim == exp_per_shard).
        with jax.named_scope("experts"):
            out = jax.vmap(expert_fn)(params, recv)
        # Undo: [exp_per_shard, n_shards, capacity, d] -> all_to_all back.
        out = out.reshape(exp_per_shard, n_shards, capacity, d).transpose(
            1, 0, 2, 3)
        back = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        back = back.reshape(n_exp_total, capacity, d)
        # Gather each token's expert output; dropped tokens get zeros.
        y = back[expert_idx, jnp.clip(pos, 0, capacity - 1)]
        y = jnp.where(keep[:, None], y, 0.0)
        return x_loc + gate_val[:, None] * y  # residual + gated expert out

    return shard_map(per_device, mesh=mesh,
                     in_specs=(P(), P(), P(axis)),
                     out_specs=P(), check_vma=False)


def moe_apply(x: jax.Array, router_weights: jax.Array, expert_params: Any,
              expert_fn: Callable, mesh: Mesh, axis: str = "expert",
              capacity_factor: float = 1.25) -> jax.Array:
    """x: [tokens, d_model] (replicated over ``axis``); router_weights:
    [d_model, n_experts]; expert_params leaves have leading dim n_experts
    (sharded over ``axis``). Returns [tokens, d_model]."""
    n_exp_total = router_weights.shape[-1]
    n_shards = mesh.shape[axis]
    if n_exp_total % n_shards != 0:
        raise ValueError(f"{n_exp_total} experts not divisible over "
                         f"{n_shards} expert shards")
    fn = _moe_sharded(expert_fn, mesh, axis, n_exp_total, n_shards,
                      n_exp_total // n_shards, capacity_factor)
    with jax.named_scope("moe"):
        return fn(x, router_weights, expert_params)
