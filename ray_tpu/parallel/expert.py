"""Expert parallelism: what one device of an expert-parallel layer computes,
and a switch-style layer with its ``all_to_all`` exchange.

``held_experts_apply`` is the function the model calls
(``models/transformer.py``, the ``shortcut`` layer kind). It is told which
experts this device holds (``ExpertConfig.held``), routes every token over
*all* the experts at the published width and experts-per-token, computes its
own experts' part of the result for the tokens routed to them, dropless,
and adds what a zero-compute expert returns (weight x token, computed where
the token lives). What the absent experts would add is left out: on one
device the layer runs without its exchange, and the partial sum is what
goes on. The model's stack hands it the experts of all its layers and the
layer's index: they are read as groups of the stacked leaves, not cut out.

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
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# Rows of routed (token, expert) pairs one step of the dropless loop gathers,
# multiplies and scatters: two row tiles of the TPU's ragged product.
CHUNK_ROWS = 1024


@dataclass(frozen=True)
class ExpertConfig:
    """A routed mixture's sizes and this device's share of it."""
    n_routed: int                 # published routed experts (router outputs
    n_zero: int                   # ... n_routed + n_zero: zero-compute ones)
    top_k: int                    # experts a token
    scale: float                  # routed_scaling_factor, on every weight
    width: int                    # an expert's hidden width
    held: Tuple[int, int]         # (first published index, count) held here

    @property
    def n_outputs(self) -> int:
        return self.n_routed + self.n_zero


def _precision(dtype) -> jax.lax.Precision:
    """float32 operands multiply as float32; below 32 bits a product is
    exact in float32, so it is one pass whatever precision the caller's
    context asks for (the TPU's ragged product refuses any other)."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype).itemsize >= 4
            else jax.lax.Precision.DEFAULT)


def route(u: jax.Array, router: jax.Array, cfg: ExpertConfig
          ) -> Tuple[jax.Array, jax.Array]:
    """``u`` [T, d], ``router`` [d, n_routed + n_zero] -> each token's
    ``top_k`` expert indices [T, k] and weights [T, k], float32. Logits,
    softmax and choice are float32: the operands go to the MXU in ``u``'s
    dtype, where a product of two bfloat16 values is exact, and accumulate
    in float32. The weights are ``scale * p`` and are not renormalised; the
    choice is on ``p`` (the published correction bias is a buffer of zeros
    used for the choice alone)."""
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", u, router.astype(u.dtype),
                            precision=_precision(u.dtype),
                            preferred_element_type=jnp.float32)
        p, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
        return idx, cfg.scale * p


def _held_rows(idx, weights, cfg: ExpertConfig):
    """Which tokens chose which held expert (a token picks an expert once at
    most): ``(weight [T, count], running count [count, T], bounds
    [count + 1])``. Held expert e's pairs, in token order, are rows
    ``bounds[e]:bounds[e + 1]`` of the sorted list that ``_rows_tokens``
    reads; ``bounds[count]`` pairs are held in all. Counting, not a sort: a
    sort of a call's pairs takes the TPU compiler half a minute a shape."""
    first, count = cfg.held
    chosen = idx[:, :, None] == first + jnp.arange(count)       # [T, k, count]
    weight = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0), axis=1)
    running = jnp.cumsum(jnp.any(chosen, axis=1).astype(jnp.int32), axis=0)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(running[-1])])
    return weight, running.T, bounds


def _rows_tokens(rows, running, bounds):
    """The (token, held expert) of rows ``rows`` of the sorted list: row s
    is the ``s - bounds[e] + 1``-th token that chose expert e, found by a
    binary search of e's running count. Rows past ``bounds[-1]`` give
    whatever; the caller masks them."""
    count, T = running.shape
    e = jnp.minimum(jnp.searchsorted(bounds[1:], rows, side="right"),
                    count - 1)
    nth = rows - bounds[e] + 1
    lo, hi = jnp.zeros_like(rows), jnp.full_like(rows, T - 1)
    for _ in range(max(1, (T - 1).bit_length())):
        mid = (lo + hi) // 2
        reached = running[e, mid] >= nth
        lo, hi = jnp.where(reached, lo, mid + 1), jnp.where(reached, mid, hi)
    return jnp.minimum(lo, T - 1), e


def held_experts_apply(u: jax.Array, router: jax.Array,
                       experts: Dict[str, jax.Array], cfg: ExpertConfig,
                       layer) -> Tuple[jax.Array, jax.Array]:
    """This device's part of a routed mixture, for tokens ``u`` [T, d]:
    ``sum_j w_tj Expert_e(u_t)`` over the chosen experts e that are held
    here, plus ``sum_j w_tj u_t`` over the chosen zero-compute indices
    (``e >= n_routed``, identity). ``experts``: ``wi``, ``wg`` [n, count, d,
    width] and ``wo`` [n, count, width, d] (SwiGLU), the held experts of a
    stack's ``n`` layers (one layer's own leaves: ``p[None]``, layer 0), in
    ``u``'s dtype; ``layer``, an index, traced or not, says whose are meant.

    The leaves are viewed as ``n x count`` groups, of which the grouped
    product is given sizes that are zero outside ``layer x count : (layer +
    1) x count``. No layer's experts are cut out of the stack (the grouped
    product takes a materialised operand: the cut would be a copy of them,
    and so would a conversion, which is why the dtype is the caller's to
    match); a group of no rows is not read.

    Dropless: no capacity. The pairs routed to held experts are listed by
    expert and taken ``CHUNK_ROWS`` at a time, for as many steps as they
    fill: gather the rows' tokens, three grouped products over the held
    experts (``lax.ragged_dot``, which the TPU compiles to one Mosaic call
    over ragged groups), weigh, scatter-add. So the products' work grows
    with the routed pairs, not with T x experts (only the list's
    bookkeeping, a [T, count] running count of integers, does), and every
    token sent to one expert or none is exact alike.

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
        idx, weights = route(u, router, cfg)
        zero = idx >= cfg.n_routed
        out = (jnp.sum(jnp.where(zero, weights, 0.0), axis=-1, keepdims=True)
               * u.astype(jnp.float32))

        weight, running, bounds = _held_rows(idx, weights, cfg)
        n_held = bounds[count]
        rows = min(CHUNK_ROWS, T * cfg.top_k)
        w = {name: p.reshape(n * count, *p.shape[2:])
             for name, p in experts.items()}
        product = functools.partial(jax.lax.ragged_dot,
                                    precision=_precision(u.dtype))

        def step(i, out):
            start = i * rows
            mine = start + jnp.arange(rows, dtype=jnp.int32)
            valid = mine < n_held
            tok, e = _rows_tokens(mine, running, bounds)
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n * count,), jnp.int32),
                jnp.clip(bounds[1:] - start, 0, rows)
                - jnp.clip(bounds[:-1] - start, 0, rows), (layer * count,))
            x = u[tok]
            hidden = (jax.nn.silu(product(x, w["wi"], sizes))
                      * product(x, w["wg"], sizes))
            y = product(hidden, w["wo"], sizes,
                        preferred_element_type=jnp.float32)
            # rows past the held pairs belong to no group: whatever the product
            # left there is dropped
            y = jnp.where(valid[:, None], y * weight[tok, e][:, None], 0.0)
            return out.at[jnp.where(valid, tok, T)].add(y, mode="drop")

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
    held, absent, zero, most = (int(n) for n in loads.sum(axis=0))
    pairs, load_max, calls = _counters()
    for dest, n in (("held", held), ("absent", absent), ("zero", zero)):
        pairs.inc(n, tags={"dest": dest})
    load_max.inc(most)
    calls.inc(len(loads))
    with observability.span("moe.route", held=held, absent=absent, zero=zero,
                            load_max=most, layers=len(loads), experts=count):
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
