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
pass, and a loop takes the list ``CHUNK_ROWS`` rows a step), and adds what a
zero-compute expert returns (weight x token, computed where the token
lives). What the absent experts would add is left out: on one device the
layer runs without its exchange, and the partial sum is what goes on. The
model's stack hands it the experts of all its layers and the layer's index:
they are read as groups of the stacked leaves, not cut out.

``moe_apply`` is the older switch layer: top-1 routing with a capacity
limit (dropped tokens pass through the residual path), experts sharded over
the ``expert`` mesh axis and tokens sent to their expert's device by one
``jax.lax.all_to_all`` (the EP pattern the reference has no analogue for:
its parallelism stops at process-level DP, SURVEY §2.5). ``x`` replicated
over the expert axis is ``moe_apply``'s alone; no model calls it, and its
exchange is what ``held_experts_apply`` across devices will build on.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# Rows of routed (token, expert) pairs one step of the dropless loop gathers,
# multiplies and scatters: two row tiles of the TPU's ragged product.
CHUNK_ROWS = 1024
# What ``normalize`` adds to the chosen weights' sum before it divides by it.
NORM_EPS = 1e-6
# The spread of a drawn ``choice_bias`` (``transformer.init_params``): a
# sigmoid's fourth and fifth largest of 64 scores lie 0.012 apart in the
# median, and a bias of this spread moves the choice of 60% of the tokens of
# seeded weights (tests/test_lfm2_layer.py measures the share).
BIAS_SCALE = 0.02


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

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {self.score!r}: 'softmax' or 'sigmoid'")

    @property
    def n_outputs(self) -> int:
        return self.n_routed + self.n_zero


def _precision(dtype) -> jax.lax.Precision:
    """float32 operands multiply as float32; below 32 bits a product is
    exact in float32, so it is one pass whatever precision the caller's
    context asks for (the TPU's ragged product refuses any other)."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype).itemsize >= 4
            else jax.lax.Precision.DEFAULT)


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
    1])``, ``length`` the ``T x k`` pairs or more. Held expert c's pairs are
    rows ``bounds[c]:bounds[c + 1]`` of the list; row s holds its pair's
    token and weight; ``bounds[count]`` pairs are held in all, and the rows
    past them hold the token ``T`` (no token) at weight 0. Counting, not a
    sort (a sort of a call's pairs takes the TPU compiler half a minute a
    shape): pair (t, j) on held expert c is the ``running[t, c]``-th of c's,
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
    return row_tok, row_w, bounds


def held_experts_apply(u: jax.Array, router: jax.Array,
                       experts: Dict[str, jax.Array], cfg: ExpertConfig,
                       layer, bias: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """This device's part of a routed mixture, for tokens ``u`` [T, d]:
    ``sum_j w_tj Expert_e(u_t)`` over the chosen experts e that are held
    here, plus ``sum_j w_tj u_t`` over the chosen zero-compute indices
    (``e >= n_routed``, identity). ``experts``: ``wi``, ``wg`` [n, count, d,
    width] and ``wo`` [n, count, width, d] (SwiGLU), the held experts of a
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
    the TPU compiles to one Mosaic call over ragged groups), weigh,
    scatter-add. So the products' work grows with the routed pairs, not with
    T x experts (only the list's making, a [T, count] running count of
    integers and one placement of the T x k pairs, does), and every token
    sent to one expert or none is exact alike.

    Returns the partial sum [T, d] in ``u``'s dtype and the layer's load,
    int32 [4]: pairs routed to held, absent and zero-compute experts, and
    the most-loaded held expert's pairs (``record_load``)."""
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
        # a router without a bias is called as it always was (callers that
        # stand a router of their own in ``route``'s place take three)
        idx, weights = (route(u, router, cfg) if bias is None
                        else route(u, router, cfg, bias))
        zero = idx >= cfg.n_routed
        out = (jnp.sum(jnp.where(zero, weights, 0.0), axis=-1, keepdims=True)
               * u.astype(jnp.float32))

        rows = min(CHUNK_ROWS, T * cfg.top_k)
        # whole steps of rows, so that the last step's slice is its own
        row_tok, row_w, bounds = _held_rows(idx, weights, cfg,
                                            -(-idx.size // rows) * rows)
        n_held = bounds[count]
        w = {name: p.reshape(n * count, *p.shape[2:])
             for name, p in experts.items()}
        product = functools.partial(jax.lax.ragged_dot,
                                    precision=_precision(u.dtype))

        def step(i, out):
            start = i * rows
            tok = jax.lax.dynamic_slice(row_tok, (start,), (rows,))
            wt = jax.lax.dynamic_slice(row_w, (start,), (rows,))
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n * count,), jnp.int32),
                jnp.clip(bounds[1:] - start, 0, rows)
                - jnp.clip(bounds[:-1] - start, 0, rows), (layer * count,))
            # rows past the held pairs hold token T: the gather clamps it,
            # the rows belong to no group, and whatever the product left
            # there the scatter-add drops
            x = u.at[tok].get(mode="clip")
            hidden = (jax.nn.silu(product(x, w["wi"], sizes))
                      * product(x, w["wg"], sizes))
            y = product(hidden, w["wo"], sizes,
                        preferred_element_type=jnp.float32)
            return out.at[tok].add(y * wt[:, None], mode="drop")

        with jax.named_scope("experts"):
            out = jax.lax.fori_loop(0, (n_held + rows - 1) // rows, step, out)
        n_zero = jnp.sum(zero, dtype=jnp.int32)
        load = jnp.stack([n_held, idx.size - n_held - n_zero, n_zero,
                          jnp.max(bounds[1:] - bounds[:-1])])
        return out.astype(u.dtype), load


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
            "moe_layer_calls_total", "layer calls of held_experts_apply"))


def _record(count: int, loads) -> None:
    """Host side of ``record_load``: the registry's counters, and one span
    (``moe.route``) that carries the same increments while a profiler
    session or the ring records, so that a reader finds a window's share."""
    from ray_tpu import observability
    loads = np.asarray(loads).reshape(-1, 4)
    # the dropless loop's steps: a layer call's held pairs, ``CHUNK_ROWS`` (or
    # all the call's pairs, if fewer) a step
    rows = np.minimum(CHUNK_ROWS, loads[:, :3].sum(axis=1))
    steps = int((-(-loads[:, 0] // np.maximum(rows, 1))).sum())
    held, absent, zero, most = (int(n) for n in loads.sum(axis=0))
    pairs, load_max, calls = _counters()
    for dest, n in (("held", held), ("absent", absent), ("zero", zero)):
        pairs.inc(n, tags={"dest": dest})
    load_max.inc(most)
    calls.inc(len(loads))
    # ``placed``: the pairs the layer calls' one counting pass wrote into
    # their lists, every held pair once (a program that searched for its
    # rows a step has no such attribute)
    with observability.span("moe.route", held=held, absent=absent, zero=zero,
                            load_max=most, layers=len(loads), experts=count,
                            steps=steps, placed=held):
        pass


def record_load(loads: jax.Array, cfg: ExpertConfig) -> None:
    """Feed the loads of a forward's layers (``held_experts_apply``'s
    second result, stacked [layers, 4]) to the program's counters, from
    inside a jitted program: one call-back a forward."""
    with jax.named_scope("moe"):
        jax.debug.callback(functools.partial(_record, cfg.held[1]), loads)


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
