"""Flagship model: decoder-only transformer, TPU-first.

Design points (vs the reference, which delegates all modeling to torch):
- Pure-functional params pytree with a parallel *logical axes* pytree, so the
  whole model shards with one ``ShardingRules`` table (DP/FSDP/TP/SP/PP are
  config edits, not code changes).
- bfloat16 activations/params with float32 RMSNorm/softmax accumulation —
  the MXU-native dtype recipe.
- Attention runs the Pallas flash kernel on TPU (``ray_tpu.ops``) or ring
  attention when the mesh has a nontrivial ``seq`` axis (long-context path).
- ``jax.checkpoint`` (remat) per block trades FLOPs for HBM.
- RoPE positions, SwiGLU MLP, RMSNorm: the standard modern decoder recipe.

A looped decoder (Ouro / LoopLM: ``n_passes``, ``post_norm``, ``exit_beta``)
is the same stack applied ``n_passes`` times with the same weights
(``pass_states``): each pass ends in the shared final norm, whose output
feeds the next pass and that pass's exit. A block then also norms what each
sub-layer returns, before the residual add (``ln1_post``, ``ln2_post``).
The exit gate, one ``Linear(d_model, 1)`` on a pass's normed state, gives
the probability ``lambda_t`` of stopping there; the probability of leaving
at pass t is ``p_t = lambda_t * prod_{j<t} (1 - lambda_j)``, the last pass
taking the remainder. Training minimises ``sum_t p_t * CE_t - exit_beta *
H(p)`` a position (``loss_and_metrics``); serving replies with the last
pass's logits (``backbone`` + ``head``: the published
``early_exit_threshold`` of 1 never leaves early, and there is no decode
loop to leave). The training loss differentiates the looped stack by a
backward pass of its own (``_looped_states_summing``): the shared weights'
gradient is one float32 accumulator, each layer's share added into its
slice where it is made. With the defaults none of this exists: the
parameter tree and the compiled programs are the plain decoder's.

A stack of several kinds of block (MiniCPM-SALA: ``layer_kinds``) holds one
stacked parameter tree a kind, ``blocks[kind]``, and scans each run of
neighbouring layers of one kind, in the published order. Such a stack is
made of two kinds, both forward only (``train.step.FORWARD_ONLY``;
``dense``, the block above, stands in a stack of its own kind alone):
``sparse``
(per-head RMSNorm of q and k, no positions, causal flash attention up to
``sparse.dense_len`` tokens and block-sparse attention with a learned
selection past it, a sigmoid output gate) and ``linear`` (the same norms,
rotary positions, Lightning linear attention with a per-head decay, an output
norm over the joined heads, the gate). ``embed_scale``, ``residual_scale``
and ``logit_scale`` are the muP factors of such a model; at 1 they emit
nothing.

A third kind, ``shortcut`` (LongCat-Flash), stands in a stack of its own
kind and is forward only too (its mixture and its attention are
differentiable; the layer's late sum has no training path yet): one layer holds two latent-attention blocks
(MLA: low-rank q and k/v projections with their norms and scalings, heads of
``nope_dim + rope_dim`` for q and k beside ``v_dim`` for v, interleaved
rotary positions on the ``rope_dim`` part, which all heads share for k), two
dense SwiGLU FFNs and a routed mixture of experts whose sum joins the stream
one sub-layer late (``_shortcut_block``; sizes in ``LatentConfig`` and
``parallel.expert.ExpertConfig``). The mixture is this device's share of an
expert-parallel layer: ``parallel.expert.held_experts_apply``, whose dropless
loop (gather, three grouped products, weigh) scatter-adds a step's rows into
the sum where the device holds a share of the experts, as here, and where it
holds them all (LFM2 below) writes them into a list that one gather after the
loop sums.

Five more kinds are *a token mixer and an FFN chosen apart* (LFM2, Kanana-2;
Granite's ``mamba`` and ``attn`` and SmallThinker's two below are made so too:
``PARTS``), served and trained on one device, and stand among each other in
any order: ``conv`` (a gated short convolution and a dense SwiGLU FFN),
``conv_moe`` (the same mixer and a routed mixture), ``attn_moe`` (the dense
block's grouped-query attention and the mixture), ``latent`` (latent
attention, one block of the shortcut layer's, without a q bottleneck where
``LatentConfig.q_rank`` is None, and a dense FFN) and ``latent_moe`` (latent
attention and the mixture). A mixture layer may add *shared experts*
(``ExpertConfig.shared_width``: one dense SwiGLU that every token takes, on
every device alike). The short-convolution mixer is ``[B | C | z] = u
W_in``, ``g = B * z``, a causal depthwise convolution of ``conv_width`` taps
over ``g`` and ``(C * c) W_out`` (``_shortconv_mixer``: shifted
multiply-adds, no kernel); the mixture is ``held_experts_apply`` with the
router ``ExpertConfig`` describes (here sigmoid scores, a bias for the choice
alone, weights normalised over the chosen), combined by gather where every
expert of the layer is held (LFM2's served cut) and by a scatter-add a step
where a share is (Kanana-2's trained one). A run of neighbouring layers of
one such kind is a scan over the layers' indices with the stacked tree closed
over: each weight is read from the stack where it is used (``_at``), the
experts' leaves go to the mixture whole with the layer's index. ``qk_norm``
(per-head RMSNorm of q and k before the rotation) and ``tie_embeddings`` (no
``lm_head`` leaf: the head reads the embedding) are fields of the dense
attention path and of the head, not of a kind.

Two more of ``PARTS``' kinds are SmallThinker's blocks, grouped-query
attention at a ``head_dim`` of its own (``head_width``: 28 heads of 128 on a
stream of 2,560) with the mixture as the FFN: ``window_moe`` (rotary
positions by halves; position ``t`` attends the keys ``t - window < j <= t``:
the flash kernel with its ``window`` in prefill) and ``global_moe`` (no
positions, every key), whatever ``cfg.rope`` says (``_kind_attention``). Both
are ``EARLY_ROUTED``: **the router reads the block's input** ``x`` itself,
before the block's first norm, and its choice is handed across the attention
to the experts, which read ``N2`` of the state after it (``_parts_block``,
``_mixture``); an expert is a ReGLU (``ExpertConfig.activation``). Where a
device holds every expert and a call routes more than ``LIST_PAIRS`` pairs
the tokens take the dropless loop in equal blocks.

A stack of ``DECODABLE`` kinds (``mamba``, ``attn``, ``window_moe``,
``global_moe``) keeps a state across calls (``DecodeState``; ``prefill``,
``insert_state``, ``decode_step``): a Mamba layer its recurrent state and its
convolution's tail, an attention layer K and V. **There are two K/V stacks of
different length**: ``k`` / ``v`` with a row a position up to ``cache_len``
for the layers that attend over everything, and ``ring_k`` / ``ring_v`` for
the window layers, a ring of ``window`` rows in which position ``p`` lives in
row ``p % window`` (k is kept rotated, so the rows' order does not matter to
the softmax). ``prefill`` hands over a prompt's last ``window`` positions in
ring order, ``decode_step`` writes the token at ``lengths % window``, rotates
q and k at each slot's own position and reads a slot's rows up to the newest
the sequence has reached (``ops.decode_attention`` under ``use_flash``: a
step's bytes are the rows the slots hold; the plain path masks all the rows
the stack has); the mixture runs in the step on the slots' ``[S, d]`` rows.

Training a stack of ``PARTS``' kinds (``loss_and_metrics`` ->
``_parts_states``) scans each run over its stacked leaves, so that a layer's
weight gradient is written once; the mixture and the flash kernel at two head
widths have backward passes of their own (``parallel.expert._held_sum``,
``ops.flash_attention``), the router's gradient flows through its weights
and not through its choice, and the mixture layers' loads come back beside
the loss (``moe_load``, ``moe_counts``) for the step that moves the router's
bias by them (``train.step``).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import math
from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec

from ray_tpu.ops import decode_attention, flash_attention, linear_attention
from ray_tpu.ops import sparse_attention, ssd
from ray_tpu.ops.flash_attention import KEPT as FLASH_KEPT
from ray_tpu.ops.linear_attention import decay_rates
from ray_tpu.ops.selective_scan import selective_scan, selective_scan_step
from ray_tpu.ops.sparse_attention import SparseConfig
from ray_tpu.parallel import expert
from ray_tpu.parallel.expert import ExpertConfig
from ray_tpu.parallel.sequence import ring_attention
from ray_tpu.parallel.sharding import ShardingRules


# a layer's kind
(DENSE, SPARSE, LINEAR, SHORTCUT, CONV, CONV_MOE, ATTN_MOE, LATENT,
 LATENT_MOE, MAMBA, ATTN, WINDOW_MOE, GLOBAL_MOE, MAMBA1, DIFF_WINDOW,
 DIFF_GLOBAL, DIFF_CROSS, GMU) = KINDS = (
    "dense", "sparse", "linear", "shortcut", "conv", "conv_moe", "attn_moe",
    "latent", "latent_moe", "mamba", "attn", "window_moe", "global_moe",
    "mamba1", "diff_window", "diff_global", "diff_cross", "gmu")
# the kinds made of a token mixer and an FFN chosen apart: (mixer, FFN), each
# the name of the layer's sub-tree; the FFN's name, ``mlp`` | ``moe``, is its
# device scope too, and the mixer's scope is ``shortconv``, ``mamba`` or, for
# either attention (grouped-query ``attn``, latent ``latent``), ``attn``
PARTS = {CONV: ("shortconv", "mlp"), CONV_MOE: ("shortconv", "moe"),
         ATTN_MOE: ("attn", "moe"), LATENT: ("latent", "mlp"),
         LATENT_MOE: ("latent", "moe"), MAMBA: ("mamba", "mlp"),
         ATTN: ("attn", "mlp"), WINDOW_MOE: ("attn", "moe"),
         GLOBAL_MOE: ("attn", "moe"), MAMBA1: ("mamba1", "mlp"),
         DIFF_WINDOW: ("diff", "mlp"), DIFF_GLOBAL: ("diff", "mlp"),
         DIFF_CROSS: ("diff", "mlp"), GMU: ("gmu", "mlp")}
# the kinds whose router reads the block's input, before the mixer's norm, and
# hands its choice across the attention to the experts (SmallThinker)
EARLY_ROUTED = (WINDOW_MOE, GLOBAL_MOE)
# SambaY's kinds (Phi-4-mini-flash): a self-decoder of Mamba-1 scans and
# differential attention through a window, one full differential attention
# whose K and V every later attention reads, and a cross-decoder of gated
# memory units and attention with no K/V of its own. They stand among each
# other only: a ``GMU`` reads the last ``MAMBA1`` layer's scan output, a
# ``DIFF_CROSS`` layer the one ``DIFF_GLOBAL`` layer's K and V
SAMBAY = (MAMBA1, DIFF_WINDOW, DIFF_GLOBAL, DIFF_CROSS, GMU)
# the kinds that keep a state across calls (``prefill``, ``decode_step``)
DECODABLE = (MAMBA, ATTN, WINDOW_MOE, GLOBAL_MOE) + SAMBAY


@dataclass(frozen=True)
class LatentConfig:
    """Latent attention's (MLA's) sizes: the ranks of the q and the k/v
    bottlenecks (``q_rank`` None: q has none, one projection ``wq`` and no
    norm), and a head's widths: q and k are ``nope_dim + rope_dim`` wide (the
    ``rope_dim`` part rotated, interleaved pairs, and for k shared by every
    head), v is ``v_dim`` wide."""
    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int


@dataclass(frozen=True)
class MambaConfig:
    """A Mamba-2 mixer's sizes: ``n_heads`` heads of ``head_dim`` values
    against a state ``d_state`` wide that every head's B and C share (one
    group), a causal depthwise convolution of ``conv_width`` taps over ``[x |
    B | C]`` and the scan's chunk (published ``mamba_n_heads``,
    ``mamba_d_head``, ``mamba_d_state``, ``mamba_d_conv``,
    ``mamba_chunk_size``)."""
    n_heads: int
    head_dim: int
    d_state: int
    conv_width: int = 4
    chunk: int = ssd.CHUNK

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.d_state


@dataclass(frozen=True)
class Mamba1Config:
    """A Mamba-1 mixer's sizes: ``d_inner`` channels, each with a state
    ``d_state`` wide and a decay of its own a state, a step a channel through
    a projection of rank ``dt_rank``, and a causal depthwise convolution of
    ``conv_width`` taps over ``x`` (``phi4flash``'s ``mamba_expand`` x
    ``hidden_size``, ``mamba_d_state``, ``mamba_dt_rank`` and
    ``mamba_d_conv``)."""
    d_inner: int
    d_state: int
    dt_rank: int
    conv_width: int = 4


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: Optional[int] = None     # GQA; defaults to n_heads
    d_ff: Optional[int] = None           # defaults to 4 * d_model (SwiGLU 8/3)
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash: bool = True
    rope_theta: float = 10000.0
    # RMSNorm's epsilon (published ``rms_norm_eps``).
    norm_eps: float = 1e-6
    # How many times the whole stack is applied, with the same weights and
    # the final norm between passes (published ``total_ut_steps``).
    n_passes: int = 1
    # A block norms each sub-layer's output too, before the residual add:
    # two more norm weights a block (the Ouro block's sandwich norms).
    post_norm: bool = False
    # None: no exit gate. A number: the gate exists (``exit_gate`` in the
    # tree) and the loss is the exit-weighted objective with this weight on
    # the exit distribution's entropy (the LoopLM paper's beta).
    exit_beta: Optional[float] = None
    # Each layer's kind, in order (``DENSE``, ``SPARSE``, ``LINEAR``); None:
    # every layer is dense and ``blocks`` is one stacked tree.
    layer_kinds: Optional[Tuple[str, ...]] = None
    # Each layer's index in the published stack of ``decay_depth`` layers
    # (a linear layer's decay depends on it); None: 0 .. n_layers - 1.
    layer_ids: Optional[Tuple[int, ...]] = None
    decay_depth: Optional[int] = None
    # muP: the embedding times ``embed_scale``, every sub-layer's output
    # times ``residual_scale`` before the residual add, the head's input
    # times ``logit_scale``.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    sparse: SparseConfig = SparseConfig()
    # A ``SHORTCUT``, ``LATENT`` or ``LATENT_MOE`` layer's attention; a
    # ``SHORTCUT`` layer's mixture of experts, and that of ``PARTS``' kinds
    # whose FFN is one.
    latent: Optional[LatentConfig] = None
    experts: Optional[ExpertConfig] = None
    # Per-head RMSNorm of q and k (weights of ``head_dim``) before the rotary
    # positions, in the dense block's attention: ``q_norm``, ``k_norm``.
    qk_norm: bool = False
    # Taps of a short-convolution mixer's causal depthwise convolution
    # (published ``conv_L_cache``).
    conv_width: int = 3
    # The head reads the embedding, transposed: the tree has no ``lm_head``.
    tie_embeddings: bool = False
    # Grouped-query attention without positions (published
    # ``position_embedding_type`` ``nope``) where False, and its softmax scale
    # where that is not ``head_dim ** -0.5`` (``attention_multiplier``).
    rope: bool = True
    attn_scale: Optional[float] = None
    # A head's width where it is not ``d_model // n_heads`` (published
    # ``head_dim``): ``cfg.head_dim`` reads it.
    head_width: Optional[int] = None
    # A ``WINDOW_MOE`` layer's reach: position ``t`` attends the keys ``t -
    # window < j <= t`` (published ``sliding_window_size``).
    window: Optional[int] = None
    # A ``MAMBA`` layer's mixer.
    mamba: Optional[MambaConfig] = None
    # A ``MAMBA1`` layer's mixer (and the width of what a ``GMU`` gates).
    mamba1: Optional[Mamba1Config] = None
    # ``SAMBAY``'s kinds: every norm of the stack (a block's two, the final
    # one) is a LayerNorm with a weight and a bias (leaves ``<name>_b``), and
    # the attention's projections have biases.
    layer_norm: bool = False
    # Training: steps over which ``train.step``'s default optimizer raises its
    # learning rate linearly to its full value (0: constant from the first
    # step, as the dense cells train). A router trained from seeded weights
    # at the full rate collapses onto one choice for every token within
    # twenty steps (PERF.md section 6, PR 48).
    warmup_steps: int = 0

    def __post_init__(self):
        kinds = self.layer_kinds
        if kinds is None:
            return
        unknown = sorted(set(kinds) - set(KINDS))
        if unknown or len(kinds) != self.n_layers:
            raise ValueError(
                f"layer_kinds {kinds}: {self.n_layers} layers, each one of "
                + ", ".join(repr(k) for k in KINDS))
        for alone in (DENSE, SHORTCUT):
            if alone in kinds and set(kinds) != {alone}:
                raise ValueError(
                    f"layer_kinds {kinds}: a {alone!r} layer stands in a "
                    f"stack of its own kind only (its block has no "
                    f"residual_scale)")
        if set(kinds) & set(PARTS) and not set(kinds) <= set(PARTS):
            raise ValueError(
                f"layer_kinds {kinds}: the kinds {sorted(PARTS)} stand "
                "among each other only (their runs scan a stack they close "
                "over)")
        if {WINDOW_MOE, DIFF_WINDOW} & set(kinds) and not self.window:
            raise ValueError(
                f"layer_kinds {kinds}: a {WINDOW_MOE!r} or {DIFF_WINDOW!r} "
                "layer needs window=")
        if set(kinds) & set(SAMBAY):
            self._check_sambay(kinds)
        if MAMBA in kinds and self.mamba is None:
            raise ValueError(
                f"layer_kinds {kinds}: a {MAMBA!r} layer needs mamba= "
                "(MambaConfig)")
        if SHORTCUT in kinds and (self.latent is None
                                  or self.experts is None):
            raise ValueError(
                f"layer_kinds {kinds}: a {SHORTCUT!r} layer needs latent= "
                "(LatentConfig) and experts= (ExpertConfig)")
        if self.latent is None and any(
                PARTS.get(kind, ("", ""))[0] == "latent" for kind in kinds):
            raise ValueError(
                f"layer_kinds {kinds}: latent attention needs latent= "
                "(LatentConfig)")
        if self.experts is None and any(
                PARTS.get(kind, ("", ""))[1] == "moe" for kind in kinds):
            raise ValueError(
                f"layer_kinds {kinds}: a mixture needs experts= "
                "(ExpertConfig)")
        if self.layer_ids is not None and len(self.layer_ids) != len(kinds):
            raise ValueError(f"layer_ids {self.layer_ids} for {kinds}")

    def _check_sambay(self, kinds) -> None:
        def first(kind):
            return kinds.index(kind) if kind in kinds else len(kinds)

        def last(kind):
            return len(kinds) - 1 - kinds[::-1].index(kind)

        if not set(kinds) <= set(SAMBAY):
            raise ValueError(
                f"layer_kinds {kinds}: the kinds {SAMBAY} stand among each "
                "other only (one layer's scan output and one layer's K and V "
                "are handed down the stack)")
        if self.mamba1 is None or not self.layer_norm:
            raise ValueError(
                f"layer_kinds {kinds}: {SAMBAY} need mamba1= (Mamba1Config) "
                "and layer_norm=True")
        if (kinds.count(DIFF_GLOBAL) > 1
                or DIFF_CROSS in kinds and not (
                    DIFF_GLOBAL in kinds
                    and last(DIFF_GLOBAL) < first(DIFF_CROSS))
                or GMU in kinds and not (
                    MAMBA1 in kinds and last(MAMBA1) < first(GMU))):
            raise ValueError(
                f"layer_kinds {kinds}: at most one {DIFF_GLOBAL!r} layer, "
                f"before every {DIFF_CROSS!r} layer, and every {MAMBA1!r} "
                f"layer before every {GMU!r} layer")
        if self.n_heads % 2 or self.kv_heads % 2 or self.n_passes != 1:
            raise ValueError(
                "differential attention pairs neighbouring heads: n_heads "
                f"{self.n_heads} and n_kv_heads {self.kv_heads} are even, "
                "in a stack applied once")

    @property
    def kinds(self) -> Tuple[str, ...]:
        return self.layer_kinds or (DENSE,) * self.n_layers

    @property
    def mixed(self) -> bool:
        """Whether ``blocks`` holds a tree a kind (any layer not dense)."""
        return set(self.kinds) != {DENSE}

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    """Initialize the parameter pytree (float32 master copy)."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    d, h, kvh, hd, f = (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                        cfg.ff_dim)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def latent_attention(k):
        """MLA's weights. The up-projections draw at 1 / sqrt(d), not 1 /
        sqrt(rank): the forward multiplies what they read by sqrt(d / rank),
        so q, k and v come out at unit variance and the scores at order 1,
        as the dense block's do (at 1 / sqrt(rank) the scores' spread is
        5.7, attention all but picks one key, and a rounding of 2^-8 grows
        2.5 times a layer). Without a q bottleneck (``q_rank`` None) q is
        one projection, ``wq``, drawn from the first of the five keys."""
        a = cfg.latent
        qk = a.nope_dim + a.rope_dim
        ks = jax.random.split(k, 5)
        q = ({"wq": dense(ks[0], (d, h, qk), d)} if a.q_rank is None else
             {"wq_a": dense(ks[0], (d, a.q_rank), d),
              "q_norm": jnp.ones((a.q_rank,), jnp.float32),
              "wq_b": dense(ks[1], (a.q_rank, h, qk), d)})
        return {**q,
                "wkv_a": dense(ks[2], (d, a.kv_rank + a.rope_dim), d),
                "kv_norm": jnp.ones((a.kv_rank,), jnp.float32),
                "wkv_b": dense(ks[3], (a.kv_rank, h, a.nope_dim + a.v_dim),
                               d),
                "wo": dense(ks[4], (h, a.v_dim, d), h * a.v_dim)}

    def mamba_mixer(k):
        """A Mamba-2 mixer's weights. ``A = -exp(a_log)`` is drawn uniform in
        -1..-16 and ``dt_bias`` so that ``softplus(dt_bias)`` is log-uniform
        in 0.001..0.1, as Mamba-2 initialises them (a state then neither dies
        within a chunk nor never decays); ``d_skip`` is ones, as there; the
        convolution's bias is drawn at 0.02. The four small leaves are drawn
        on bfloat16's grid, so that a cast of the tree to the serving dtype
        leaves them as they are."""
        m = cfg.mamba
        ks = jax.random.split(k, 6)
        step = jnp.exp(jax.random.uniform(
            ks[4], (m.n_heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            # [z | x B C | dt]
            "w_in": dense(ks[0], (d, 2 * m.d_inner + 2 * m.d_state
                                  + m.n_heads), d),
            "conv": dense(ks[1], (m.conv_dim, m.conv_width), m.conv_width),
            "conv_bias": on_grid(0.02 * jax.random.normal(
                ks[2], (m.conv_dim,), jnp.float32)),
            "a_log": on_grid(jnp.log(jax.random.uniform(
                ks[3], (m.n_heads,), jnp.float32, 1.0, 16.0))),
            # softplus's inverse of the step
            "dt_bias": on_grid(step + jnp.log(-jnp.expm1(-step))),
            "d_skip": jnp.ones((m.n_heads,), jnp.float32),
            "norm": jnp.ones((m.d_inner,), jnp.float32),
            "w_out": dense(ks[5], (m.d_inner, d), m.d_inner)}

    def shortcut_layer(k):
        """Two latent-attention blocks and two FFNs (leaves stacked [2, ...]
        in the order they run), the router, and the held experts, each
        drawn from a key folded with its *published* index: the shares of
        different devices draw disjoint, consistent experts."""
        e = cfg.experts
        ks = jax.random.split(k, 4)
        first, count = e.held
        return {
            "attn": jax.vmap(latent_attention)(jax.random.split(ks[0], 2)),
            "mlp": jax.vmap(functools.partial(ffn, width=f))(
                jax.random.split(ks[1], 2)),
            "router": dense(ks[2], (d, e.n_outputs), d),
            "experts": jax.vmap(lambda i: ffn(jax.random.fold_in(ks[3], i),
                                              e.width))(
                first + jnp.arange(count)),
            "ln_attn": jnp.ones((2, d), jnp.float32),
            "ln_mlp": jnp.ones((2, d), jnp.float32),
        }

    def ffn(k, width):
        ks = jax.random.split(k, 3)
        return {"wi": dense(ks[0], (d, width), d),       # gate
                "wg": dense(ks[1], (d, width), d),       # up
                "wo": dense(ks[2], (width, d), width)}

    def qk_norms():
        return ({"q_norm": jnp.ones((hd,), jnp.float32),
                 "k_norm": jnp.ones((hd,), jnp.float32)}
                if cfg.qk_norm else {})

    def on_grid(v):
        return v.astype(jnp.bfloat16).astype(jnp.float32)

    def small(k, shape, scale=0.02):
        """A bias or a vector drawn at ``scale`` on bfloat16's grid (zeros
        would leave its path unread; a cast to the serving dtype leaves it as
        drawn)."""
        return on_grid(scale * jax.random.normal(k, shape, jnp.float32))

    def mamba1_mixer(k):
        """A Mamba-1 mixer's weights: ``A = -exp(a_log)`` [d_inner, d_state]
        uniform in -1..-16 and ``dt_bias`` so that ``softplus(dt_bias)`` is
        log-uniform in 0.001..0.1 a channel (as ``mamba_mixer`` draws a
        head's), ``d_skip`` ones; no bias on a projection."""
        m = cfg.mamba1
        ks = jax.random.split(k, 8)
        step = jnp.exp(jax.random.uniform(
            ks[5], (m.d_inner,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        return {
            "w_in": dense(ks[0], (d, 2 * m.d_inner), d),           # [x | z]
            "conv": dense(ks[1], (m.d_inner, m.conv_width), m.conv_width),
            "conv_bias": small(ks[2], (m.d_inner,)),
            # [dt's rank | B | C]
            "w_x": dense(ks[3], (m.d_inner, m.dt_rank + 2 * m.d_state),
                         m.d_inner),
            "w_dt": dense(ks[4], (m.dt_rank, m.d_inner), m.dt_rank),
            "dt_bias": on_grid(step + jnp.log(-jnp.expm1(-step))),
            "a_log": on_grid(jnp.log(jax.random.uniform(
                ks[6], (m.d_inner, m.d_state), jnp.float32, 1.0, 16.0))),
            "d_skip": jnp.ones((m.d_inner,), jnp.float32),
            "w_out": dense(ks[7], (m.d_inner, d), m.d_inner)}

    def diff_attention(k, kind):
        """Differential attention's weights: a fused ``[q | k | v]``
        projection with its bias (a ``DIFF_CROSS`` layer has q alone: it
        reads another layer's K and V), the output projection with its bias,
        the four ``lambda`` vectors of ``head_dim`` (drawn at 0.1, as the
        Differential Transformer initialises them) and the sub-norm's weight
        over ``2 head_dim``."""
        ks = jax.random.split(k, 8)
        q_only = kind == DIFF_CROSS
        width = h * hd if q_only else (h + 2 * kvh) * hd
        name = "q" if q_only else "qkv"
        return {
            "w" + name: dense(ks[0], (d, width), d),
            "b" + name: small(ks[1], (width,)),
            "wo": dense(ks[2], (h * hd, d), h * hd),
            "bo": small(ks[3], (d,)),
            **{n: small(kk, (hd,), 0.1) for n, kk in zip(
                ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"),
                ks[4:])},
            "subln": jnp.ones((2 * hd,), jnp.float32)}

    def sambay_layer(k, kind):
        """One of ``SAMBAY``'s layers: the mixer and the dense FFN from the
        halves of the layer's key, as ``parts_layer``, and the two
        LayerNorms' biases from the key folded with 2."""
        mixer, _ = PARTS[kind]
        k_mixer, k_ffn = jax.random.split(k)
        k_b1, k_b2 = jax.random.split(jax.random.fold_in(k, 2))
        if mixer == "mamba1":
            mixed = mamba1_mixer(k_mixer)
        elif mixer == "gmu":
            ks = jax.random.split(k_mixer)
            mixed = {"w_in": dense(ks[0], (d, cfg.mamba1.d_inner), d),
                     "w_out": dense(ks[1], (cfg.mamba1.d_inner, d),
                                    cfg.mamba1.d_inner)}
        else:
            mixed = diff_attention(k_mixer, kind)
        return {mixer: mixed, "mlp": ffn(k_ffn, f),
                "ln1": jnp.ones((d,), jnp.float32),
                "ln1_b": small(k_b1, (d,)),
                "ln2": jnp.ones((d,), jnp.float32),
                "ln2_b": small(k_b2, (d,))}

    def parts_layer(k, kind):
        """A mixer and an FFN (``PARTS``), each from its own half of the
        layer's key. The router's bias, used for the choice alone, is drawn
        (a trained buffer in the published model; zeros would leave its path
        unread) at ``expert.BIAS_SCALE`` on bfloat16's grid, so that a cast of
        the tree to the serving dtype leaves it as it is. An expert draws
        from a key folded with its published index, as a shortcut layer's;
        the shared experts (``experts.shared_width``) from the FFN's key
        folded with 3, beside the three it is split into."""
        if kind in SAMBAY:
            return sambay_layer(k, kind)
        mixer, feed = PARTS[kind]
        k_mixer, k_ffn = jax.random.split(k)
        if mixer == "latent":
            mixed = latent_attention(k_mixer)
        elif mixer == "mamba":
            mixed = mamba_mixer(k_mixer)
        elif mixer == "attn":
            ks = jax.random.split(k_mixer, 4)
            mixed = {"wq": dense(ks[0], (d, h, hd), d),
                     "wk": dense(ks[1], (d, kvh, hd), d),
                     "wv": dense(ks[2], (d, kvh, hd), d),
                     "wo": dense(ks[3], (h, hd, d), h * hd),
                     **qk_norms()}
        else:
            ks = jax.random.split(k_mixer, 3)
            mixed = {"w_in": dense(ks[0], (d, 3 * d), d),     # [B | C | z]
                     "conv": dense(ks[1], (d, cfg.conv_width),
                                   cfg.conv_width),
                     "w_out": dense(ks[2], (d, d), d)}
        if feed == "mlp":
            fed = {"mlp": ffn(k_ffn, f)}
        else:
            e = cfg.experts
            ks = jax.random.split(k_ffn, 3)
            first, count = e.held
            fed = {"router": dense(ks[0], (d, e.n_outputs), d),
                   "experts": jax.vmap(
                       lambda i: ffn(jax.random.fold_in(ks[2], i), e.width))(
                           first + jnp.arange(count))}
            if e.choice_bias:
                fed["router_bias"] = (
                    expert.BIAS_SCALE * jax.random.normal(
                        ks[1], (e.n_outputs,), jnp.float32)
                ).astype(jnp.bfloat16).astype(jnp.float32)
            if e.shared_width:
                fed["shared"] = ffn(jax.random.fold_in(k_ffn, 3),
                                    e.shared_width)
        return {mixer: mixed, **fed,
                "ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32)}

    def layer(k, kind=DENSE):
        if kind == SHORTCUT:
            return shortcut_layer(k)
        if kind in PARTS:
            return parts_layer(k, kind)
        ks = jax.random.split(k, 7)
        post = ({"ln1_post": jnp.ones((d,), jnp.float32),
                 "ln2_post": jnp.ones((d,), jnp.float32)}
                if cfg.post_norm else {})
        # a linear layer has as many K/V heads as query heads
        kv = h if kind == LINEAR else kvh
        extra = {}
        if kind != DENSE:
            # the gate's key is folded in beside the seven, as the exit
            # gate's is below
            extra = {"wg": dense(jax.random.fold_in(k, 7), (d, h, hd), d),
                     "q_norm": jnp.ones((hd,), jnp.float32),
                     "k_norm": jnp.ones((hd,), jnp.float32)}
        if kind == LINEAR:
            extra["o_norm"] = jnp.ones((h * hd,), jnp.float32)
        if kind == DENSE:
            extra = qk_norms()
        return {
            **post,
            "attn": {
                "wq": dense(ks[0], (d, h, hd), d),
                "wk": dense(ks[1], (d, kv, hd), d),
                "wv": dense(ks[2], (d, kv, hd), d),
                "wo": dense(ks[3], (h, hd, d), h * hd),
                **extra,
            },
            "mlp": {
                "wi": dense(ks[4], (d, f), d),       # gate
                "wg": dense(ks[5], (d, f), d),       # up
                "wo": dense(ks[6], (f, d), f),
            },
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
        }

    layer_keys = jax.random.split(k_layers, cfg.n_layers)

    def blocks():
        if not cfg.mixed:
            return jax.vmap(layer)(layer_keys)      # stacked: [L, ...]
        # a stacked tree a kind, each layer drawing from its own place's key
        return {kind: jax.vmap(functools.partial(layer, kind=kind))(
            layer_keys[jnp.array([i for i, k in enumerate(cfg.kinds)
                                  if k == kind])])
            for kind in dict.fromkeys(cfg.kinds)}

    # the gate's key is folded in beside the three, so that a configuration
    # without one draws the weights it always drew
    gate = ({"exit_gate": {"w": dense(jax.random.fold_in(key, 3), (d,), d),
                           "b": jnp.zeros((), jnp.float32)}}
            if cfg.exit_beta is not None else {})
    return {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, d),
                                   jnp.float32) * 0.02,
        "blocks": blocks(),
        "ln_f": jnp.ones((d,), jnp.float32),
        # the final LayerNorm's bias, from the key folded with 4
        **({"ln_f_b": small(jax.random.fold_in(key, 4), (d,))}
           if cfg.layer_norm else {}),
        # tied: no leaf, the head reads the embedding
        **({} if cfg.tie_embeddings
           else {"lm_head": dense(k_head, (d, cfg.vocab_size), d)}),
        **gate,
    }


def logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical-axis pytree mirroring ``init_params`` output (leaves = tuples
    of logical names consumed by ``ShardingRules``). The leading "layers" dim
    of the stacked blocks maps to the pipeline axis when pipe > 1."""
    blk = {
        "attn": {
            "wq": ("layers", "embed", "heads", "kv"),
            "wk": ("layers", "embed", "heads", "kv"),
            "wv": ("layers", "embed", "heads", "kv"),
            "wo": ("layers", "heads", "kv", "embed"),
        },
        "mlp": {
            "wi": ("layers", "embed", "mlp"),
            "wg": ("layers", "embed", "mlp"),
            "wo": ("layers", "mlp", "embed"),
        },
        "ln1": ("layers", None),
        "ln2": ("layers", None),
    }
    if cfg.post_norm:
        blk.update(ln1_post=("layers", None), ln2_post=("layers", None))
    if cfg.mixed:
        # a mixture's held experts [n, count, ...]: the experts' axis unnamed
        experts = {"wi": ("layers", None, "embed", "mlp"),
                   "wg": ("layers", None, "embed", "mlp"),
                   "wo": ("layers", None, "mlp", "embed")}

        def latent_axes():
            # a ``PARTS`` layer's latent attention: one block a layer
            a = {"wkv_a": ("layers", "embed", None),
                 "kv_norm": ("layers", None),
                 "wkv_b": ("layers", None, "heads", "kv"),
                 "wo": ("layers", "heads", "kv", "embed")}
            if cfg.latent.q_rank is None:
                return {"wq": ("layers", "embed", "heads", "kv"), **a}
            return {"wq_a": ("layers", "embed", None),
                    "q_norm": ("layers", None),
                    "wq_b": ("layers", None, "heads", "kv"), **a}

        def of_kind(kind):
            if kind == SHORTCUT:
                # on one device: only the layers' axis is named (the two
                # sub-blocks' axis and the experts' are not)
                return {
                    "attn": {"wq_a": ("layers", None, "embed", None),
                             "q_norm": ("layers", None, None),
                             "wq_b": ("layers", None, None, "heads", "kv"),
                             "wkv_a": ("layers", None, "embed", None),
                             "kv_norm": ("layers", None, None),
                             "wkv_b": ("layers", None, None, "heads", "kv"),
                             "wo": ("layers", None, "heads", "kv", "embed")},
                    "mlp": {"wi": ("layers", None, "embed", "mlp"),
                            "wg": ("layers", None, "embed", "mlp"),
                            "wo": ("layers", None, "mlp", "embed")},
                    "router": ("layers", "embed", None),
                    "experts": experts,
                    "ln_attn": ("layers", None, None),
                    "ln_mlp": ("layers", None, None),
                }
            if kind in SAMBAY:
                # on one device too: only the layers' axis is named
                mixer, _ = PARTS[kind]
                vec, mat = ("layers", None), ("layers", None, None)
                if mixer == "mamba1":
                    mixed = {"w_in": ("layers", "embed", None), "conv": mat,
                             "conv_bias": vec, "w_x": mat, "w_dt": mat,
                             "dt_bias": vec, "a_log": mat, "d_skip": vec,
                             "w_out": ("layers", None, "embed")}
                elif mixer == "gmu":
                    mixed = {"w_in": ("layers", "embed", None),
                             "w_out": ("layers", None, "embed")}
                else:
                    name = "q" if kind == DIFF_CROSS else "qkv"
                    mixed = {"w" + name: ("layers", "embed", None),
                             "b" + name: vec,
                             "wo": ("layers", None, "embed"), "bo": vec,
                             "lambda_q1": vec, "lambda_k1": vec,
                             "lambda_q2": vec, "lambda_k2": vec,
                             "subln": vec}
                return {mixer: mixed, "mlp": blk["mlp"], "ln1": vec,
                        "ln1_b": vec, "ln2": vec, "ln2_b": vec}
            if kind in PARTS:
                # on one device, as the shortcut kind
                mixer, feed = PARTS[kind]
                norms = ({"q_norm": ("layers", None),
                          "k_norm": ("layers", None)} if cfg.qk_norm else {})
                mixed = (latent_axes() if mixer == "latent" else
                         {**blk["attn"], **norms} if mixer == "attn" else
                         {"w_in": ("layers", "embed", None),
                          "conv": ("layers", None, None),
                          "conv_bias": ("layers", None),
                          "a_log": ("layers", None),
                          "dt_bias": ("layers", None),
                          "d_skip": ("layers", None),
                          "norm": ("layers", None),
                          "w_out": ("layers", None, "embed")}
                         if mixer == "mamba" else
                         {"w_in": ("layers", "embed", None),
                          "conv": ("layers", None, None),
                          "w_out": ("layers", None, "embed")})
                if feed == "mlp":
                    fed = {"mlp": blk["mlp"]}
                else:
                    fed = {"router": ("layers", "embed", None),
                           "experts": experts}
                    if cfg.experts.choice_bias:
                        fed["router_bias"] = ("layers", None)
                    if cfg.experts.shared_width:
                        fed["shared"] = blk["mlp"]
                return {mixer: mixed, **fed, "ln1": ("layers", None),
                        "ln2": ("layers", None)}
            extra = {"wg": ("layers", "embed", "heads", "kv"),
                     "q_norm": ("layers", None), "k_norm": ("layers", None)}
            if kind == LINEAR:
                extra["o_norm"] = ("layers", None)
            return {**blk, "attn": {**blk["attn"], **extra}}
        blk = {kind: of_kind(kind) for kind in dict.fromkeys(cfg.kinds)}
    elif cfg.qk_norm:
        blk["attn"].update(q_norm=("layers", None), k_norm=("layers", None))
    axes = {
        "embed": ("vocab", "embed"),
        "blocks": blk,
        "ln_f": (None,),
    }
    if cfg.layer_norm:
        axes["ln_f_b"] = (None,)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.exit_beta is not None:
        axes["exit_gate"] = {"w": (None,), "b": ()}
    return axes


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def _rope_cos_sin(half: int, theta: float, positions: jax.Array):
    """cos and sin of position times ``theta ** (-i / half)``, [B, L, 1,
    half] float32."""
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # B L 1 half
    return jnp.cos(angles), jnp.sin(angles)


def _rope(x: jax.Array, theta: float, positions: jax.Array) -> jax.Array:
    """x: [B, L, H, D]; rotate pairs along D."""
    half = x.shape[-1] // 2
    cos, sin = _rope_cos_sin(half, theta, positions)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def _rope_interleaved(x: jax.Array, theta: float, positions: jax.Array
                      ) -> jax.Array:
    """x: [B, L, H, D]; rotate the neighbouring pairs (2i, 2i + 1) of D."""
    half = x.shape[-1] // 2
    cos, sin = _rope_cos_sin(half, theta, positions)
    pairs = x.reshape(*x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


@functools.lru_cache(maxsize=128)
def _flash_sharded(mesh: Mesh, spec: PartitionSpec):
    """The flash kernel under ``shard_map``, memoized on its statics like
    the ``parallel/`` wrappers. XLA cannot partition a Mosaic kernel, so
    each device runs it on its own shard; attention is independent across
    batch and heads, so no collective is needed. K and V come with their
    own head count, split over the same axes as q's heads, so a device's
    query heads find their K/V heads on that device."""
    return shard_map(functools.partial(flash_attention, causal=True),
                     mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)


def _repeat_kv(q, k, v):
    """GQA for the paths that want K and V at q's head count."""
    rep = q.shape[2] // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _attention(q, k, v, cfg: TransformerConfig, mesh: Optional[Mesh],
               rules: Optional[ShardingRules] = None,
               window: Optional[int] = None):
    """``mesh`` is the mesh of the enclosing jit, or None when the caller
    already runs per device (one chip, or inside a ``shard_map``). ``k`` and
    ``v`` carry ``cfg.kv_heads`` heads: the flash kernel takes them so, the
    other two paths repeat them first. ``window``: a position attends the
    ``window`` last keys, itself among them (on one device only)."""
    if window is not None and mesh is not None:
        raise ValueError("attention through a window runs on one device "
                         "(neither the ring nor the sharded kernel has one)")
    if mesh is not None and "seq" in mesh.axis_names and mesh.shape["seq"] > 1:
        return ring_attention(q, *_repeat_kv(q, k, v), mesh, causal=True)
    if cfg.use_flash:
        if window is not None:
            return flash_attention(q, k, v, causal=True, window=window)
        if mesh is None:
            return flash_attention(q, k, v, causal=True)
        # batch over the rules' batch axes, heads over tensor
        spec = (rules or ShardingRules()).sharding(
            mesh, ("batch", None, "act_heads", None)).spec
        split = math.prod(mesh.shape[a] for a in jax.tree.leaves(spec[2]))
        if cfg.kv_heads % split:
            raise ValueError(
                f"flash attention under a mesh splits heads {split} ways "
                f"({spec[2]}): n_kv_heads={cfg.kv_heads} does not divide")
        return _flash_sharded(mesh, spec)(q, k, v)
    k, v = _repeat_kv(q, k, v)
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(D)
    L, Lk = q.shape[1], k.shape[1]
    mask = jnp.tril(jnp.ones((L, Lk), bool))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((L, Lk), bool), -window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _kind_attention(cfg: TransformerConfig, kind: str
                    ) -> Tuple[bool, Optional[int]]:
    """``(rotary positions, window)`` of a kind's grouped-query attention: a
    ``WINDOW_MOE`` layer rotates and reaches ``cfg.window`` keys back, a
    ``GLOBAL_MOE`` layer does neither, and any other kind is as ``cfg.rope``
    says, over everything."""
    if kind == WINDOW_MOE:
        return True, cfg.window
    if kind == GLOBAL_MOE:
        return False, None
    return cfg.rope, None


def _kind_scope(kind: str):
    """The scope inside ``attn`` that tells a kind's attention from the
    others': ``swa``, ``nope``, ``global``, ``cross`` or none (the names are
    literals: the registry's test reads the source)."""
    if kind in (WINDOW_MOE, DIFF_WINDOW):
        return jax.named_scope("swa")
    if kind == GLOBAL_MOE:
        return jax.named_scope("nope")
    if kind == DIFF_GLOBAL:
        return jax.named_scope("global")
    if kind == DIFF_CROSS:
        return jax.named_scope("cross")
    return contextlib.nullcontext()


def _early_choice(kind: str, x, router, cfg: TransformerConfig):
    """An ``EARLY_ROUTED`` kind's choice ``(idx, weights)`` from the block's
    input rows ``x`` [T, d], before any norm; None for any other kind."""
    if kind not in EARLY_ROUTED:
        return None
    with jax.named_scope("moe"):
        return expert.route(x, router(), cfg.experts)


def _attention_qkv(attn, h, positions, cfg: TransformerConfig,
                   rope: Optional[bool] = None):
    """q, k and v of grouped-query attention from the normed states ``h`` [B,
    L, d]: the projections, with ``cfg.qk_norm`` an RMSNorm of each head of q
    and k, rotary positions (by halves; none where ``rope``, by default
    ``cfg.rope``, is False), and
    with ``cfg.attn_scale`` q times ``attn_scale * sqrt(head_dim)``, so that
    the attention's own ``head_dim ** -0.5`` leaves the published scale."""
    q = jnp.einsum("bld,dhk->blhk", h, attn["wq"].astype(h.dtype))
    k = jnp.einsum("bld,dhk->blhk", h, attn["wk"].astype(h.dtype))
    v = jnp.einsum("bld,dhk->blhk", h, attn["wv"].astype(h.dtype))
    if cfg.qk_norm:
        q = _rmsnorm(q, attn["q_norm"], cfg.norm_eps)
        k = _rmsnorm(k, attn["k_norm"], cfg.norm_eps)
    if cfg.rope if rope is None else rope:
        q = _rope(q, cfg.rope_theta, positions)
        k = _rope(k, cfg.rope_theta, positions)
    if cfg.attn_scale is not None:
        q = q * (cfg.attn_scale * math.sqrt(cfg.head_dim))
    return q, k, v


def _attention_mixer(attn, h, positions, cfg: TransformerConfig, mesh,
                     rules=None, kept: Optional[list] = None,
                     rope: Optional[bool] = None,
                     window: Optional[int] = None):
    """Grouped-query attention on the normed states ``h`` [B, L, d]:
    ``_attention_qkv``, causal softmax attention (``core``; through
    ``window`` where given) and ``W_o``. ``kept``, a list, is handed k (as
    rotated) and v (what a decode loop keeps). The caller enters the ``attn``
    scope."""
    q, k, v = _attention_qkv(attn, h, positions, cfg, rope)
    if kept is not None:
        kept.extend((k, v))
    with jax.named_scope("core"):
        o = _attention(q, k, v, cfg, mesh, rules, window)
    return jnp.einsum("blhk,hkd->bld", o, attn["wo"].astype(h.dtype))


def _ring_row(positions, rows: int):
    """The row of a ring of ``rows`` rows that holds position ``p``: ``p %
    rows``."""
    return positions % rows


def _attention_step(attn, h, cfg: TransformerConfig, k_cache, v_cache, l,
                    lengths, rope: Optional[bool] = None, ring: bool = False):
    """One token a slot in attention layer ``l`` of the stacked caches: ``h``
    [S, d] normed states, every layer's K and V [n, S, T, kv_heads x head_dim]
    (a position's heads side by side: one row) and how many positions each
    slot holds. q and k are rotated at each slot's own position (``rope``,
    by default ``cfg.rope``). With ``ring`` the T rows are a window layer's
    ring: position ``p`` lives in row ``p % T`` (k is kept rotated, so the
    rows' order does not matter to the softmax), the token overwrites the
    position ``T`` before it, and a row counts once the sequence has reached
    it (every row from position ``T - 1`` on), which is the window ``t - T <
    j <= t`` itself. The token's k and v are written as a row at ``(l, slot,
    position)`` of the stack itself (a scatter of S rows, in place: written
    into the layer's slice and the slice written back, the compiler moved the
    whole slice three times) and the query reads the slot's cache up to and
    with it. Each query head is laid out over all the K/V heads' lanes with
    zeros outside its own group's, so that both products read a cache row as
    it lies (a product batched over the K/V heads had the TPU compiler
    transpose the whole cache every step, and back for the write): 8 times
    the scores' FLOPs, which are nothing beside the cache's bytes. With
    ``cfg.use_flash`` the two products and the softmax between them are one
    Mosaic call on the stacks themselves that walks each slot's tiles up to
    its newest row and no further (``ops.decode_attention``: a step's bytes
    are the rows its slots hold, not the rows allocated); without, and for a
    cache whose rows are no multiple of 8, a masked product over all T rows.
    Returns the mixer's output and the two stacks."""
    _, S, T, _ = k_cache.shape
    H, G, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q, k, v = _attention_qkv(attn, h[:, None], lengths[:, None], cfg, rope)
    newest = jnp.minimum(lengths, T - 1)
    at = _ring_row(lengths, T) if ring else newest
    slot = jnp.arange(S)
    k_cache = k_cache.at[l, slot, at].set(
        k[:, 0].reshape(S, G * D).astype(k_cache.dtype))
    v_cache = v_cache.at[l, slot, at].set(
        v[:, 0].reshape(S, G * D).astype(v_cache.dtype))
    with jax.named_scope("core"):
        # own[h, g]: query head h reads K/V head g
        own = (jnp.arange(H)[:, None] // (H // G)
               == jnp.arange(G)[None])[None, :, :, None]
        spread = jnp.where(own, q[:, 0, :, None, :], 0).reshape(S, H, G * D)
        o = decode_attention(spread, k_cache, v_cache, l, newest,
                             1 / math.sqrt(D), use_kernel=cfg.use_flash)
        o = jnp.sum(jnp.where(own, o.reshape(S, H, G, D), 0), axis=2)
    return (jnp.einsum("shk,hkd->sd", o, attn["wo"].astype(h.dtype)),
            k_cache, v_cache)


def _mamba_inputs(p, h, cfg: TransformerConfig):
    """``[z | xBC | dt] = h W_in`` for normed states ``h`` [..., d]."""
    m = cfg.mamba
    u = jnp.einsum("...d,de->...e", h, p["w_in"].astype(h.dtype))
    return (u[..., :m.d_inner], u[..., m.d_inner:m.d_inner + m.conv_dim],
            u[..., m.d_inner + m.conv_dim:])


def _mamba_scan_inputs(p, window, dt, cfg: TransformerConfig):
    """From the convolution's window ``window`` [..., taps, conv_dim] (the
    position's own row last) and the raw step ``dt`` [..., heads]: ``[x | B |
    C] = silu(conv + bias)`` in the compute dtype, ``softplus(dt + dt_bias)``
    and ``A = -exp(a_log)``, float32."""
    m = cfg.mamba
    taps = p["conv"].astype(jnp.float32).T                      # [taps, C]
    c = jnp.sum(window.astype(jnp.float32) * taps, axis=-2) \
        + p["conv_bias"].astype(jnp.float32)
    xbc = jax.nn.silu(c).astype(window.dtype)
    x = xbc[..., :m.d_inner].reshape(*xbc.shape[:-1], m.n_heads, m.head_dim)
    b = xbc[..., m.d_inner:m.d_inner + m.d_state]
    c = xbc[..., m.d_inner + m.d_state:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return x, b, c, dt, -jnp.exp(p["a_log"].astype(jnp.float32))


def _mamba_output(p, y, x, z, cfg: TransformerConfig):
    """``(RMSNorm(y' * silu(z)) * w) W_out`` with ``y' = y + D x``: ``y`` and
    ``x`` [..., heads, head_dim], ``z`` [..., d_inner]; the norm over all
    ``d_inner`` channels (one group)."""
    y = y.astype(jnp.float32) + x.astype(jnp.float32) \
        * p["d_skip"].astype(jnp.float32)[:, None]
    g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    g = _rmsnorm(g.astype(z.dtype), p["norm"], cfg.norm_eps)
    return jnp.einsum("...e,ed->...d", g, p["w_out"].astype(z.dtype))


def _mamba_mixer(p, h, cfg: TransformerConfig, lengths=None,
                 kept: Optional[list] = None):
    """A Mamba-2 mixer on the normed states ``h`` [B, L, d]: ``[z | xBC | dt]
    = h W_in``; ``[x | B | C] = silu(conv(xBC) + b)``, depthwise and causal;
    ``dt = softplus(dt + dt_bias)``; a head's ``S_t = exp(dt_t A) S_{t-1} +
    dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` (``ops.ssd``: ``core``); the
    gated norm; ``W_out``. With ``lengths`` [B] a position past a sequence's
    length steps by 0, so the state passes through it unchanged. ``kept``, a
    list, is handed what a decode loop keeps: the state after the last real
    position [B, heads, head_dim, d_state] float32 and the convolution's tail
    there, the last ``conv_width - 1`` rows of ``xBC``. The caller enters the
    ``mamba`` scope."""
    m = cfg.mamba
    B, L, _ = h.shape
    z, xbc, dt = _mamba_inputs(p, h, cfg)
    back = m.conv_width - 1
    padded = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))
    window = jnp.stack([padded[:, j:j + L] for j in range(m.conv_width)],
                       axis=2)                              # [B, L, taps, C]
    x, b, c, dt, a = _mamba_scan_inputs(p, window, dt, cfg)
    if lengths is not None:
        dt = jnp.where((jnp.arange(L)[None] < lengths[:, None])[..., None],
                       dt, 0.0)
    with jax.named_scope("core"):
        y, state = ssd.ssd_fwd(x, dt, a, b, c, chunk=m.chunk,
                               use_kernel=cfg.use_flash)
    if kept is not None:
        ends = jnp.full((B,), L) if lengths is None else lengths
        tail = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
            rows, n, back))(padded, ends)                   # [B, back, C]
        kept.extend((state, tail))
    return _mamba_output(p, y, x, z, cfg)


def _mamba_step(p, h, cfg: TransformerConfig, ssm, conv, l):
    """One token a slot in Mamba layer ``l`` of the stacked state: ``h`` [S,
    d] normed states, every layer's state ``ssm`` [n, S, heads, head_dim,
    d_state] float32 and convolution tail ``conv`` [n, S, conv_width - 1,
    conv_dim]. ``_mamba_mixer``'s arithmetic with the recurrence itself for
    the scan, inside ``core``. With ``cfg.use_flash`` (which chooses the
    scan's kernel in ``_mamba_mixer`` too) that is one Mosaic call on the
    stack itself, ``ssd.ssd_step_stacked``: layer ``l``'s state comes into
    VMEM a slot at a time, is stepped, summed against ``C`` and written back,
    read once and written once. Without it, ``ssd.ssd_step`` on the layer's
    state, read from the stack and written back where it was read: the update
    in place too, but the sum over the new state is a fusion of its own and a
    third pass. Returns the output and the two stacks."""
    z, xbc, dt = _mamba_inputs(p, h, cfg)
    tail = jax.lax.dynamic_index_in_dim(conv, l, 0, keepdims=False)
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc[:, None]], axis=1)
    x, b, c, dt, a = _mamba_scan_inputs(p, window, dt, cfg)
    with jax.named_scope("core"):
        if cfg.use_flash:
            y, ssm = ssd.ssd_step_stacked(x, dt, a, b, c, ssm, l)
        else:
            y, state = ssd.ssd_step(
                x, dt, a, b, c,
                jax.lax.dynamic_index_in_dim(ssm, l, 0, keepdims=False))
            ssm = jax.lax.dynamic_update_index_in_dim(ssm, state, l, 0)
    conv = jax.lax.dynamic_update_index_in_dim(
        conv, window[:, 1:].astype(conv.dtype), l, 0)
    return _mamba_output(p, y.astype(h.dtype), x, z, cfg), ssm, conv


def _shortconv_mixer(conv, h):
    """A gated short convolution on the normed states ``h`` [B, L, d]: ``[B |
    C | z] = h W_in``; ``g = B * z``; ``c_t = sum_j w_j g_{t - (k - 1) + j}``
    a channel (depthwise, causal: zeros before the first position, so the
    last tap reads the position itself); ``(C * c) W_out``. No bias, no
    activation. ``k`` shifted multiply-adds, float32 like a norm's; nothing
    outlives the call (a decode loop would keep the last ``k - 1`` rows of
    ``g``). The caller enters the ``shortconv`` scope."""
    gate, c_gate, z = jnp.split(
        jnp.einsum("bld,de->ble", h, conv["w_in"].astype(h.dtype)), 3, axis=-1)
    g = gate.astype(jnp.float32) * z.astype(jnp.float32)
    taps = conv["conv"].astype(jnp.float32)            # [d, k]
    k = taps.shape[-1]
    c = g * taps[:, k - 1]
    for back in range(1, k):    # the tap that reads ``back`` positions back
        shifted = jnp.pad(g, ((0, 0), (back, 0), (0, 0)))[:, :g.shape[1]]
        c = c + shifted * taps[:, k - 1 - back]
    return jnp.einsum("bld,de->ble", c_gate * c.astype(h.dtype),
                      conv["w_out"].astype(h.dtype))


def _layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float
               ) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * w.astype(x.dtype) + b.astype(x.dtype)


# -- SambaY: Mamba-1, differential attention, the gated memory unit ----------


def _mamba1_scan_inputs(p, window, cfg: TransformerConfig):
    """From the convolution's window ``window`` [..., taps, d_inner] (the
    position's own row last): ``x = silu(conv + bias)`` in the compute dtype,
    ``[r | B | C] = x W_x`` and ``dt = softplus(r W_dt + dt_bias)`` a channel
    (both products leave the MXU in float32: the step's size is the state's
    rounding), and ``A = -exp(a_log)`` laid [d_state, d_inner], float32."""
    m = cfg.mamba1
    f32 = jnp.float32
    taps = p["conv"].astype(f32).T                              # [taps, C]
    x = jax.nn.silu(jnp.sum(window.astype(f32) * taps, axis=-2)
                    + p["conv_bias"].astype(f32)).astype(window.dtype)
    rbc = jnp.einsum("...c,ce->...e", x, p["w_x"].astype(x.dtype),
                     preferred_element_type=f32)
    r, b, c = (rbc[..., :m.dt_rank],
               rbc[..., m.dt_rank:m.dt_rank + m.d_state],
               rbc[..., m.dt_rank + m.d_state:])
    dt = jax.nn.softplus(
        jnp.einsum("...r,rc->...c", r.astype(x.dtype),
                   p["w_dt"].astype(x.dtype), preferred_element_type=f32)
        + p["dt_bias"].astype(f32))
    return x, b, c, dt, -jnp.exp(p["a_log"].astype(f32)).T


def _mamba1_output(p, y, x, z):
    """``(W_out(m * silu(z)), m)`` with ``m = y + D x``, the scan's output
    before its gate: what a ``GMU`` layer gates again (in the compute
    dtype)."""
    m = y.astype(jnp.float32) + x.astype(jnp.float32) \
        * p["d_skip"].astype(jnp.float32)
    g = (m * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return (jnp.einsum("...e,ed->...d", g, p["w_out"].astype(z.dtype)),
            m.astype(z.dtype))


def _mamba1_mixer(p, h, cfg: TransformerConfig, lengths=None):
    """A Mamba-1 mixer on the normed states ``h`` [B, L, d]: ``[x | z] = h
    W_in``; ``x = silu(conv(x) + b)``, depthwise and causal; ``[r | B | C] =
    x W_x``; ``dt = softplus(r W_dt + dt_bias)``; a channel's ``S_t =
    exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
    (``ops.selective_scan``: ``core``; its Mosaic call with
    ``cfg.use_flash``); ``W_out(y * silu(z))``. With
    ``lengths`` [B] a position past a sequence's length steps by 0. Returns
    the output and what is kept: the state after the last real position [B,
    d_state, d_inner] float32, the convolution's tail there (the last
    ``conv_width - 1`` rows of ``x`` before the convolution) and ``y`` itself
    [B, L, d_inner], the memory. The caller enters the ``mamba`` scope."""
    m = cfg.mamba1
    B, L, _ = h.shape
    u = jnp.einsum("bld,de->ble", h, p["w_in"].astype(h.dtype))
    x, z = u[..., :m.d_inner], u[..., m.d_inner:]
    back = m.conv_width - 1
    padded = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))
    window = jnp.stack([padded[:, j:j + L] for j in range(m.conv_width)],
                       axis=2)                              # [B, L, taps, C]
    x, b, c, dt, a = _mamba1_scan_inputs(p, window, cfg)
    if lengths is not None:
        dt = jnp.where((jnp.arange(L)[None] < lengths[:, None])[..., None],
                       dt, 0.0)
    with jax.named_scope("core"):
        y, state = selective_scan(x, dt, a, b, c, use_kernel=cfg.use_flash)
    ends = jnp.full((B,), L) if lengths is None else lengths
    tail = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
        rows, n, back))(padded, ends)                       # [B, back, C]
    out, memory = _mamba1_output(p, y, x, z)
    return out, (state, tail, memory)


def _mamba1_step(p, h, cfg: TransformerConfig, ssm, conv, l):
    """One token a slot in Mamba-1 layer ``l`` of the stacked state: ``h`` [S,
    d] normed states, ``ssm`` [n, S, d_state, d_inner] float32 and ``conv``
    [n, S, conv_width - 1, d_inner]. ``_mamba1_mixer``'s arithmetic with the
    recurrence itself (``ops.selective_scan_step``) on the layer's state, read
    from the stack and written back where it was read. Returns the output,
    the memory [S, d_inner] and the two stacks."""
    m = cfg.mamba1
    u = jnp.einsum("sd,de->se", h, p["w_in"].astype(h.dtype))
    x, z = u[..., :m.d_inner], u[..., m.d_inner:]
    tail = jax.lax.dynamic_index_in_dim(conv, l, 0, keepdims=False)
    window = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    x, b, c, dt, a = _mamba1_scan_inputs(p, window, cfg)
    with jax.named_scope("core"):
        y, state = selective_scan_step(
            x, dt, a, b, c,
            jax.lax.dynamic_index_in_dim(ssm, l, 0, keepdims=False))
        ssm = jax.lax.dynamic_update_index_in_dim(ssm, state, l, 0)
    conv = jax.lax.dynamic_update_index_in_dim(
        conv, window[:, 1:].astype(conv.dtype), l, 0)
    out, memory = _mamba1_output(p, y, x, z)
    return out, memory, ssm, conv


def _gmu(p, h, memory):
    """A gated memory unit on the normed states ``h`` [..., d]: ``W_out(m *
    silu(h W_in))``, ``m`` [..., d_inner] the kept scan output at the same
    position. The caller enters the ``gmu`` scope."""
    gate = jnp.einsum("...d,de->...e", h, p["w_in"].astype(h.dtype))
    g = (memory.astype(jnp.float32)
         * jax.nn.silu(gate.astype(jnp.float32))).astype(h.dtype)
    return jnp.einsum("...e,ed->...d", g, p["w_out"].astype(h.dtype))


def lambda_init(depth):
    """Differential attention's ``lambda_init`` at layer index ``depth`` (a
    number or a traced one): ``0.8 - 0.6 exp(-0.3 depth)``."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def _diff_projected(p, h, cfg: TransformerConfig):
    """``[q | k | v] = h W + b`` as heads, q [..., H, D] and k, v [..., G,
    D]; of a layer with q alone (``DIFF_CROSS``) ``(q, None, None)``."""
    H, G, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    if "wq" in p:
        q = jnp.einsum("...d,de->...e", h, p["wq"].astype(h.dtype)) \
            + p["bq"].astype(h.dtype)
        return q.reshape(*q.shape[:-1], H, D), None, None
    u = jnp.einsum("...d,de->...e", h, p["wqkv"].astype(h.dtype)) \
        + p["bqkv"].astype(h.dtype)
    lead = u.shape[:-1]
    return (u[..., :H * D].reshape(*lead, H, D),
            u[..., H * D:(H + G) * D].reshape(*lead, G, D),
            u[..., (H + G) * D:].reshape(*lead, G, D))


def _diff_combine(p, o, depth, cfg: TransformerConfig):
    """From each query head's own map times its group's values, ``o`` [...,
    H, 2 D] (head ``2 p + i`` is map ``i`` of pair ``p``): ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``; ``o_p = RMSNorm_2D(o_2p
    - lambda o_2p+1) * (1 - lambda_init)``, float32; the pairs side by side
    through ``W_out`` with its bias."""
    f32 = jnp.float32
    init = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                           * p["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                             * p["lambda_k2"].astype(f32))) + init)
    lead = o.shape[:-2]
    pairs = o.astype(f32).reshape(*lead, cfg.n_heads // 2, 2, o.shape[-1])
    d = pairs[..., 0, :] - lam * pairs[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                          + cfg.norm_eps) \
        * p["subln"].astype(f32) * (1.0 - init)
    flat = d.astype(o.dtype).reshape(*lead, -1)
    return jnp.einsum("...e,ed->...d", flat, p["wo"].astype(o.dtype)) \
        + p["bo"].astype(o.dtype)


def _diff_core(q, k, v, cfg: TransformerConfig, window: Optional[int]):
    """Every query head's causal softmax map over its own key head, times its
    group's values: q [B, L, H, D], k and v [B, Lk, G, D] -> [B, L, H, 2 D].
    Query head ``2 p + i`` (map ``i`` of pair ``p``) reads key head ``2 g +
    i`` of its group ``g = p // (H / G)`` and the group's values ``[v_2g |
    v_2g+1]``. The heads of a group are put map by map (``i`` before ``p``),
    which is the order in which head ``h`` reads K/V head ``h // (H / G)``, as
    ``_attention`` groups them; K head ``2 g + i`` brings the group's values,
    twice as wide as a key (the flash kernel takes a value width of its own;
    a window is the kernel's)."""
    B, L, H, D = q.shape
    G, rep = k.shape[2], H // k.shape[2]

    wide = jnp.repeat(v.reshape(*v.shape[:2], G // 2, 2 * D), 2, axis=2)
    q = q.reshape(B, L, G // 2, rep, 2, D).swapaxes(3, 4).reshape(B, L, H, D)
    o = _attention(q, k, wide, cfg, None, None, window)
    return o.reshape(B, L, G // 2, 2, rep, 2 * D).swapaxes(3, 4).reshape(
        B, L, H, 2 * D)


def _diff_row(p, h, cfg: TransformerConfig, k_cache, v_cache, l, newest,
              depth, write_at=None):
    """One query a slot in differential attention over layer ``l`` of the
    stacks ``k_cache``, ``v_cache`` [n, S, T, G x D]: ``h`` [S, d] normed
    states, ``newest`` [S] the last row each slot's query sees. With
    ``write_at`` [S] the layer's own k and v are first written as a row
    there (a window layer's ring at ``position % T``, the full cache at the
    position); a layer with q alone reads what another layer wrote. Both maps
    are one call of ``ops.decode_attention`` as grouped-query attention has
    it (``_attention_step``): a query head laid over its own key head's
    lanes, 2 H softmaxes each normalised by itself, and each returns its map
    times *all* the V heads, of which the head's group's two are taken. Then
    ``_diff_combine``. Returns the output and the two stacks."""
    S = h.shape[0]
    H, G, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q, k, v = _diff_projected(p, h, cfg)
    if write_at is not None:
        slot = jnp.arange(S)
        k_cache = k_cache.at[l, slot, write_at].set(
            k.reshape(S, G * D).astype(k_cache.dtype))
        v_cache = v_cache.at[l, slot, write_at].set(
            v.reshape(S, G * D).astype(v_cache.dtype))
    with jax.named_scope("core"):
        group = jnp.arange(H) // (2 * (H // G))
        # own[h, j]: query head h reads K head j
        own = ((2 * group + jnp.arange(H) % 2)[:, None]
               == jnp.arange(G)[None])[None, :, :, None]
        spread = jnp.where(own, q[:, :, None, :], 0).reshape(S, H, G * D)
        o = decode_attention(spread.astype(k_cache.dtype), k_cache, v_cache,
                             l, newest, 1 / math.sqrt(D),
                             use_kernel=cfg.use_flash)
        mine = (group[:, None] == jnp.arange(G // 2)[None])[None, :, :, None]
        o = jnp.sum(jnp.where(mine, o.reshape(S, H, G // 2, 2 * D), 0),
                    axis=2)
    return _diff_combine(p, o, depth, cfg), k_cache, v_cache


def _sambay_runs(cfg: TransformerConfig, lo: int = 0,
                 hi: Optional[int] = None):
    """The layers ``lo .. hi - 1`` of a ``SAMBAY`` stack as runs of a period
    repeated: ``(the period's kinds, each kind's first index in blocks[kind],
    repeats, the first layer's place in the stack)``. A period is two
    neighbouring layers of different kinds, else one (the published stack is
    8 x (scan, window), (scan, full), 7 x (GMU, cross)): a run is one loop
    whose body holds the period's layers, so 32 layers compile as five."""
    kinds = cfg.kinds
    hi = len(kinds) if hi is None else hi
    taken = collections.Counter(kinds[:lo])

    def repeats(at, width):
        """How often the ``width`` layers from ``at`` on come in a row (0: a
        pair of one kind, or past the end)."""
        period = kinds[at:at + width]
        if at + width > hi or len(set(period)) < width:
            return 0
        n = 1
        while (at + (n + 1) * width <= hi
               and kinds[at + n * width:at + (n + 1) * width] == period):
            n += 1
        return n

    at = lo
    while at < hi:
        # a pair, unless it comes once and its second layer starts a pair
        # that repeats
        pairs = repeats(at, 2)
        width = 2 if pairs > 1 or pairs == 1 and repeats(at + 1, 2) < 2 else 1
        period, n = kinds[at:at + width], repeats(at, width)
        yield period, tuple(taken[kind] for kind in period), n, at
        taken.update({kind: n for kind in period})
        at += n * width


def _layer_depths(cfg: TransformerConfig) -> jax.Array:
    """Each layer's index in the published stack (``lambda_init`` reads
    it)."""
    return jnp.asarray(cfg.layer_ids or range(cfg.n_layers), jnp.int32)


def _sambay_parts(stack, l, cfg: TransformerConfig):
    """``(part, norm)`` of layer ``l`` of a stacked ``SAMBAY`` tree: a
    sub-tree read from the stack where it is used (``_at``) and the block's
    LayerNorm ``name`` of ``x``."""
    def part(name):
        return jax.tree.map(lambda p: _at(p, l), stack[name])

    def norm(x, name):
        return _layernorm(x, part(name), part(name + "_b"), cfg.norm_eps)

    return part, norm


def _sambay_block(stack, l, x, cfg: TransformerConfig, kind: str, depth,
                  carried: Dict[str, jax.Array], lengths=None):
    """Layer ``l`` of the stacked tree ``stack`` of one of ``SAMBAY``'s kinds
    over every position: ``h = x + Mixer(LN1(x))``; ``y = h + MLP(LN2(h))``,
    the mixer a Mamba-1 scan, a gated memory unit over ``carried["memory"]``,
    or differential attention (through ``cfg.window`` for ``DIFF_WINDOW``; a
    ``DIFF_CROSS`` layer's over ``carried["k"]``, ``carried["v"]``).
    ``depth`` is the layer's published index. Returns the states and what
    the mixer hands on: ``(state, tail, memory)`` of a scan, ``(k, v)`` of an
    attention with K and V of its own."""
    part, norm = _sambay_parts(stack, l, cfg)
    mixer = PARTS[kind][0]
    kept: tuple = ()
    if mixer == "mamba1":
        with jax.named_scope("mamba"):
            out, kept = _mamba1_mixer(part("mamba1"), norm(x, "ln1"), cfg,
                                      lengths)
            x = x + out
    elif mixer == "gmu":
        with jax.named_scope("gmu"):
            x = x + _gmu(part("gmu"), norm(x, "ln1"), carried["memory"])
    else:
        with jax.named_scope("attn"), _kind_scope(kind):
            p = part("diff")
            q, k, v = _diff_projected(p, norm(x, "ln1"), cfg)
            if kind == DIFF_CROSS:
                k, v = carried["k"], carried["v"]
            else:
                kept = (k, v)
            with jax.named_scope("core"):
                o = _diff_core(q, k, v, cfg,
                               cfg.window if kind == DIFF_WINDOW else None)
            x = x + _diff_combine(p, o, depth, cfg)
    with jax.named_scope("mlp"):
        return x + _mlp(part("mlp"), norm(x, "ln2")), kept


def _apply_sambay(blocks, x, cfg: TransformerConfig, lengths=None,
                  hi: Optional[int] = None):
    """The layers before place ``hi`` (all, if None) of a ``SAMBAY`` stack
    over every position of ``x`` [B, L, d]: a scan a run (``_sambay_runs``)
    over the run's periods, the stacked trees closed over. The last scan
    layer's output and the full attention layer's K and V are handed down
    the stack to the layers that read them. Returns the states and, by kind,
    what each run's layers kept, stacked over the run's repeats (``MAMBA1``:
    state, tail and, of the run that holds the kind's last layer alone, the
    memory; an attention kind: k, v)."""
    depths = _layer_depths(cfg)
    last_scan = cfg.kinds.count(MAMBA1) - 1
    carried: Dict[str, jax.Array] = {}
    kept_by_kind: Dict[str, list] = collections.defaultdict(list)
    for period, starts, n, at in _sambay_runs(cfg, 0, hi):
        # the memory leaves the run that holds the last scan layer
        remembers = [kind == MAMBA1 and start + n - 1 == last_scan
                     for kind, start in zip(period, starts)]

        def one(x, i, period=period, starts=starts, at=at,
                remembers=remembers):
            out = []
            for j, (kind, start) in enumerate(zip(period, starts)):
                fn = functools.partial(_sambay_block, cfg=cfg, kind=kind)
                if cfg.remat:
                    fn = jax.checkpoint(fn)
                x, kept = fn(blocks[kind], start + i, x,
                             depth=depths[at + i * len(period) + j],
                             carried=carried, lengths=lengths)
                out.append(kept if remembers[j] or kind != MAMBA1
                           else kept[:2])
            return x, tuple(out)

        x, outs = jax.lax.scan(one, x, jnp.arange(n))
        for kind, kept, remembered in zip(period, outs, remembers):
            kept_by_kind[kind].append(kept)
            if remembered:
                carried["memory"] = kept[2][-1]
            if kind == DIFF_GLOBAL:
                carried["k"], carried["v"] = kept[0][-1], kept[1][-1]
    return x, kept_by_kind


def _sambay_rows(blocks, x, cfg: TransformerConfig, lo: int, pieces, memory,
                 lengths, newest, write: bool):
    """The layers from place ``lo`` on of a ``SAMBAY`` stack on one row a
    sequence, ``x`` [S, d]: the decode step's layers (``write``: a scan layer
    steps its state, an attention layer writes its k and v at position
    ``lengths``, a window layer into its ring) and, without ``write``, the
    cross-decoder on a prompt's last position (``prefill``: nothing is
    written; the full attention reads what the prompt's positions left).
    ``pieces`` is ``(ssm, conv, k, v, ring_k, ring_v)``, ``memory`` [S,
    d_inner] what a ``GMU`` gates until a scan layer of the run has made its
    own, ``newest`` [S] the last row of the full cache a query sees. Each run
    is a loop over its periods with the stacks as the carry, read and written
    where they lie. Returns the states and the stacks."""
    depths = _layer_depths(cfg)
    rows = pieces[4].shape[2]
    for period, starts, n, at in _sambay_runs(cfg, lo):
        def one(i, carry, period=period, starts=starts, at=at):
            x, ssm, conv, k_cache, v_cache, ring_k, ring_v, memory = carry
            for j, (kind, start) in enumerate(zip(period, starts)):
                l = start + i
                depth = depths[at + i * len(period) + j]
                part, norm = _sambay_parts(blocks[kind], l, cfg)
                mixer = PARTS[kind][0]
                if mixer == "mamba1":
                    with jax.named_scope("mamba"):
                        out, memory, ssm, conv = _mamba1_step(
                            part("mamba1"), norm(x, "ln1"), cfg, ssm, conv, l)
                        x = x + out
                elif mixer == "gmu":
                    with jax.named_scope("gmu"):
                        x = x + _gmu(part("gmu"), norm(x, "ln1"), memory)
                elif kind == DIFF_WINDOW:
                    with jax.named_scope("attn"), _kind_scope(kind):
                        out, ring_k, ring_v = _diff_row(
                            part("diff"), norm(x, "ln1"), cfg, ring_k, ring_v,
                            l, jnp.minimum(lengths, rows - 1), depth,
                            _ring_row(lengths, rows))
                        x = x + out
                else:       # the one full cache: its writer or a reader
                    with jax.named_scope("attn"), _kind_scope(kind):
                        out, k_cache, v_cache = _diff_row(
                            part("diff"), norm(x, "ln1"), cfg, k_cache,
                            v_cache, 0, newest, depth,
                            newest if write and kind == DIFF_GLOBAL else None)
                        x = x + out
                with jax.named_scope("mlp"):
                    x = x + _mlp(part("mlp"), norm(x, "ln2")[None])[0]
            return x, ssm, conv, k_cache, v_cache, ring_k, ring_v, memory

        x, *pieces, memory = jax.lax.fori_loop(0, n, one,
                                               (x, *pieces, memory))
    return x, pieces


def _block(params, x, positions, cfg: TransformerConfig, mesh, rules=None):
    norm = functools.partial(_rmsnorm, eps=cfg.norm_eps)
    with jax.named_scope("attn"):
        out = _attention_mixer(params["attn"], norm(x, params["ln1"]),
                               positions, cfg, mesh, rules)
        if cfg.post_norm:
            out = norm(out, params["ln1_post"])
        x = x + out
    with jax.named_scope("mlp"):
        h = norm(x, params["ln2"])
        out = _mlp(params["mlp"], h)
        if cfg.post_norm:
            out = norm(out, params["ln2_post"])
        return x + out


def _mlp(mlp, h):
    gate = jnp.einsum("bld,df->blf", h, mlp["wi"].astype(h.dtype))
    up = jnp.einsum("bld,df->blf", h, mlp["wg"].astype(h.dtype))
    ff = jax.nn.silu(gate) * up
    return jnp.einsum("blf,fd->bld", ff, mlp["wo"].astype(h.dtype))


def _mixed_block(layer, x, positions, cfg: TransformerConfig, kind: str):
    """One ``SPARSE`` or ``LINEAR`` layer. ``layer``: the layer's
    parameters and, for a linear layer, its heads' decay rates [h]."""
    params, rates = layer
    B, L, _ = x.shape
    norm = functools.partial(_rmsnorm, eps=cfg.norm_eps)
    with jax.named_scope("attn"):
        attn = {k: w.astype(x.dtype) for k, w in params["attn"].items()}
        h = norm(x, params["ln1"])
        q = norm(jnp.einsum("bld,dhk->blhk", h, attn["wq"]), attn["q_norm"])
        k = norm(jnp.einsum("bld,dhk->blhk", h, attn["wk"]), attn["k_norm"])
        v = jnp.einsum("bld,dhk->blhk", h, attn["wv"])
        if kind == LINEAR:
            q = _rope(q, cfg.rope_theta, positions)
            k = _rope(k, cfg.rope_theta, positions)
            with jax.named_scope("core"):
                o = linear_attention(q, k, v, rates, use_kernel=cfg.use_flash)
            o = norm(o.reshape(B, L, -1), attn["o_norm"]).reshape(o.shape)
        else:
            with jax.named_scope("core"):   # scores, ranking and forward
                if L <= cfg.sparse.dense_len:
                    o = _attention(q, k, v, cfg, None)
                else:
                    o = sparse_attention(q, k, v, cfg.sparse,
                                         use_kernel=cfg.use_flash)
        o = o * jax.nn.sigmoid(jnp.einsum("bld,dhk->blhk", h, attn["wg"]))
        x = x + cfg.residual_scale * jnp.einsum("blhk,hkd->bld", o,
                                                attn["wo"])
    with jax.named_scope("mlp"):
        out = _mlp(params["mlp"], norm(x, params["ln2"]))
        return x + cfg.residual_scale * out


def _latent_attention(params, h, positions, cfg: TransformerConfig):
    """MLA on the normed states ``h`` [B, L, d]: ``c_q = N(h W_qa)``, ``q =
    c_q W_qb * sqrt(d / q_rank)`` as heads of ``nope_dim + rope_dim`` (or,
    without a q bottleneck, ``q = h W_q``: no norm, no scaling);
    ``[c_kv | k_r] = h W_kva``, ``c_kv = N(c_kv) * sqrt(d / kv_rank)``,
    ``[k_n | v] = c_kv W_kvb`` as heads of ``nope_dim | v_dim``; rotary
    positions on q's last ``rope_dim`` and on ``k_r``, which every head
    shares; causal softmax attention at ``(nope_dim + rope_dim) ** -0.5``
    with v (and o) at their own width; ``W_o``. Prefill expands the latent
    to per-head K and V, as the published forward does. The caller enters
    the ``attn`` scope (its norm and residual belong there too); the
    attention alone is ``core`` here.

    The four projections take the tokens as rows, [1, B x L, ...]: over
    ``[B, L]`` the TPU compiler asked at B = 2 for some weights in another
    layout, and a weight that is a slice of a stacked leaf
    (``_shortcut_block``) is then relaid as the whole leaf, once a forward
    and held through the layers' loop (1.2 GB for the FFNs' ``wo``)."""
    a = cfg.latent
    B, L, _ = h.shape
    norm = functools.partial(_rmsnorm, eps=cfg.norm_eps)
    w = {k: p.astype(h.dtype) for k, p in params.items()}

    def rows(x):        # [B, L, ...] -> [1, B x L, ...]
        return x.reshape(1, B * L, *x.shape[2:])

    def seqs(x):        # and back
        return x.reshape(B, L, *x.shape[2:])

    h = rows(h)
    if a.q_rank is None:
        q = seqs(jnp.einsum("bld,dhk->blhk", h, w["wq"]))
    else:
        c_q = norm(jnp.einsum("bld,dr->blr", h, w["wq_a"]), w["q_norm"])
        q = seqs(jnp.einsum("blr,rhk->blhk", c_q, w["wq_b"])) \
            * math.sqrt(cfg.d_model / a.q_rank)
    kv = jnp.einsum("bld,dr->blr", h, w["wkv_a"])
    c_kv = norm(kv[..., :a.kv_rank], w["kv_norm"]) \
        * math.sqrt(cfg.d_model / a.kv_rank)
    k_v = seqs(jnp.einsum("blr,rhk->blhk", c_kv, w["wkv_b"]))
    k_r = _rope_interleaved(seqs(kv[..., None, a.kv_rank:]), cfg.rope_theta,
                            positions)
    q = jnp.concatenate(
        [q[..., :a.nope_dim],
         _rope_interleaved(q[..., a.nope_dim:], cfg.rope_theta, positions)],
        axis=-1)
    k = jnp.concatenate(
        [k_v[..., :a.nope_dim],
         jnp.broadcast_to(k_r, (*k_v.shape[:3], a.rope_dim))], axis=-1)
    with jax.named_scope("core"):
        o = _attention(q, k, k_v[..., a.nope_dim:], cfg, None)
    return seqs(jnp.einsum("blhk,hkd->bld", rows(o), w["wo"]))


def _at(p: jax.Array, *index) -> jax.Array:
    """``p[index]`` for leading indices of which some are traced, as one
    ``lax.dynamic_slice``: taken where the weight is used, it is the
    product's own read of the stacked leaf and no copy of the slice."""
    k = len(index)
    cut = jax.lax.dynamic_slice(p, (*index, *(0,) * (p.ndim - k)),
                                (*(1,) * k, *p.shape[k:]))
    return cut.reshape(p.shape[k:])


def _shortcut_block(blocks, l, x, positions, cfg: TransformerConfig):
    """Layer ``l`` of the stacked ``SHORTCUT`` tree ``blocks``: ``h = x +
    MLA_0(N(x))``; ``u = N(h)``; ``s = MoE(u)``; ``h = h + FFN_0(u)``; ``h =
    h + MLA_1(N(h))``; ``y = h + FFN_1(N(h)) + s``. The experts' sum joins
    the stream one sub-layer late (in a deployment its exchange overlaps the
    second attention). Returns the states and the mixture's load
    (``expert.held_experts_apply``).

    The layer's weights are not cut out of the stack first: each leaf of the
    two-a-layer trees is read at ``(l, i)`` and the router at ``(l,)`` where
    it is used (``_at``), and the experts' leaves go to the mixture whole,
    with ``l``."""
    B, L, d = x.shape
    norm = functools.partial(_rmsnorm, eps=cfg.norm_eps)

    def part(name, i):
        return jax.tree.map(lambda p: _at(p, l, i), blocks[name])

    def attention(h, i):
        with jax.named_scope("attn"):
            return h + _latent_attention(
                part("attn", i), norm(h, part("ln_attn", i)), positions, cfg)

    def ffn(h, i):      # the tokens as rows: ``_latent_attention`` says why
        return _mlp(part("mlp", i), h.reshape(1, B * L, d)).reshape(B, L, d)

    h = attention(x, 0)
    with jax.named_scope("mlp"):    # the norm both FFN_0 and the mixture read
        u = norm(h, part("ln_mlp", 0))
    s, load = expert.held_experts_apply(
        u.reshape(B * L, d), _at(blocks["router"], l), blocks["experts"],
        cfg.experts, layer=l)
    with jax.named_scope("mlp"):
        h = h + ffn(u, 0)
    h = attention(h, 1)
    with jax.named_scope("mlp"):
        y = h + ffn(norm(h, part("ln_mlp", 1)), 1)
    with jax.named_scope("moe"):
        return y + s.reshape(B, L, d), load


def _apply_shortcut(blocks, x, positions, cfg: TransformerConfig):
    """A stack of ``SHORTCUT`` layers: one ``lax.scan`` (one compiled body)
    over the layers' indices, the stacked tree closed over, so that the loop
    slices no layer out of it (a scan over the tree itself copied every
    leaf's slice, 2.5 GB a layer at the published widths; the block reads
    each weight from the stack where it uses it). The layers' loads go to
    the program's counters in one call-back a forward."""
    fn = functools.partial(_shortcut_block, cfg=cfg)
    if cfg.remat:
        fn = jax.checkpoint(fn)
    n = blocks["router"].shape[0]
    x, loads = jax.lax.scan(lambda x, l: fn(blocks, l, x, positions), x,
                            jnp.arange(n))
    expert.record_load(loads, cfg.experts)
    return x


def _parts_block(stack, l, x, positions, cfg: TransformerConfig, kind: str,
                 lengths=None, kept: Optional[list] = None):
    """Layer ``l`` of the stacked tree ``stack`` of one of ``PARTS``' kinds:
    ``h = x + r Mixer(N(x))``; ``y = h + r FFN(N(h))`` (``r`` =
    ``cfg.residual_scale``; at 1 nothing is emitted), the mixer a short
    convolution, a Mamba-2 scan, grouped-query or latent attention, the FFN
    dense or the routed mixture, to which the shared experts
    (``experts.shared_width``: one dense SwiGLU on every token) are added.
    ``lengths`` [B] reach the Mamba mixer (a right-padded prompt leaves the
    state of its last real position) and ``kept``, a list, is handed what a
    decode loop keeps of a ``DECODABLE`` kind's mixer (``prefill``). Each weight is read from the
    stack at ``l`` where it is used (``_at``) and the experts' leaves go to
    the mixture whole, with ``l``, as ``_shortcut_block`` does. Returns the
    states and, of a mixture, its load (``expert.held_experts_apply``) and
    how often each routed expert was chosen (``expert.choice_counts``);
    ``None`` for a dense FFN."""
    mixer, feed = PARTS[kind]
    B, L, d = x.shape
    norm = functools.partial(_rmsnorm, eps=cfg.norm_eps)

    def part(name):
        return jax.tree.map(lambda p: _at(p, l), stack[name])

    def scaled(out):        # muP's residual factor; at 1 the old program
        return out if cfg.residual_scale == 1.0 else cfg.residual_scale * out

    routed = _early_choice(kind, x.reshape(B * L, d),
                           lambda: part("router"), cfg)
    # a scope's name is a literal (the registry's test reads the source)
    with (jax.named_scope("shortconv") if mixer == "shortconv"
          else jax.named_scope("mamba") if mixer == "mamba"
          else jax.named_scope("attn")):
        h = norm(x, part("ln1"))
        if mixer == "latent":
            x = x + scaled(_latent_attention(part("latent"), h, positions,
                                             cfg))
        elif mixer == "attn":
            rope, window = _kind_attention(cfg, kind)
            with _kind_scope(kind):
                x = x + scaled(_attention_mixer(
                    part("attn"), h, positions, cfg, None, kept=kept,
                    rope=rope, window=window))
        elif mixer == "mamba":
            x = x + scaled(_mamba_mixer(part("mamba"), h, cfg, lengths, kept))
        else:
            x = x + scaled(_shortconv_mixer(part("shortconv"), h))
    if feed == "mlp":
        with jax.named_scope("mlp"):
            return x + scaled(_mlp(part("mlp"), norm(x, part("ln2")))), None
    s, load, counts = _mixture(stack, l, x, routed, cfg)
    with jax.named_scope("moe"):
        return x + scaled(s.reshape(B, L, d)), (load, counts)


# The most routed pairs one call of the dropless loop takes where the device
# holds every expert: the call's weighed rows are a float32 list [pairs, d]
# that one gather reads again (two arrays of 0.76 GB each at 12,288 tokens of
# SmallThinker's 6 x 2,560). A longer call's tokens go through the loop in
# equal blocks of at most so many pairs, one after another (LFM2's longest,
# 8,192 x 4, is one block).
LIST_PAIRS = 32768


def _mixture(stack, l, x, routed, cfg: TransformerConfig):
    """The routed mixture of layer ``l`` of ``stack`` on the states ``x`` [B,
    L, d] that the FFN's norm reads, taken as ``T = B x L`` rows: ``sum_j w_j
    Expert_j(N2(x))`` and the shared experts where the configuration has
    them. ``routed`` is the choice ``(idx, weights)`` an ``EARLY_ROUTED``
    kind made from the block's input, or None: the router then reads
    ``N2(x)``. Returns the sum [T, d], the layer's load and how often each
    routed expert was chosen."""
    e = cfg.experts
    B, L, d = x.shape

    def part(name):
        return jax.tree.map(lambda p: _at(p, l), stack[name])

    with jax.named_scope("moe"):
        u = _rmsnorm(x, part("ln2"), cfg.norm_eps).reshape(B * L, d)
        # a router without a bias is called as it always was (callers that
        # stand a router of their own in ``route``'s place take three)
        idx, weights = routed if routed is not None else (
            expert.route(u, part("router"), e, part("router_bias"))
            if e.choice_bias else expert.route(u, part("router"), e))
        blocks = e.all_held and idx.size > LIST_PAIRS
        if blocks:
            T, k = idx.shape
            n = next(n for n in range(-(-idx.size // LIST_PAIRS), T + 1)
                     if T % n == 0)
            s, loads = jax.lax.map(
                lambda at: expert.held_pairs_apply(*at, stack["experts"], e,
                                                   l),
                (u.reshape(n, T // n, d), idx.reshape(n, T // n, k),
                 weights.reshape(n, T // n, k)))
            s = s.reshape(T, d)
        else:
            s, load = expert.held_pairs_apply(u, idx, weights,
                                              stack["experts"], e, l)
        counts = expert.choice_counts(idx, e)
        if blocks:
            # every pair is held: the call's most-loaded expert is the one
            # most chosen
            load = jnp.concatenate([jnp.sum(loads[:, :3], axis=0),
                                    jnp.max(counts)[None]])
    if e.shared_width:
        with jax.named_scope("mlp"):    # the tokens as rows, like the mixture
            s = s + _mlp(part("shared"), u[None])[0]
    return s, load, counts


def _parts_runs(cfg: TransformerConfig):
    """A stack of ``PARTS``' kinds as its runs of neighbouring layers of one
    kind, in order: ``(kind, first index in blocks[kind], layers)``."""
    taken = dict.fromkeys(cfg.kinds, 0)
    for kind, run in itertools.groupby(cfg.kinds):
        n = len(list(run))
        yield kind, taken[kind], n
        taken[kind] += n


def _apply_parts(blocks, x, positions, cfg: TransformerConfig):
    """A stack of ``PARTS``' kinds: each run of neighbouring layers of one
    kind is one ``lax.scan`` over those layers' indices in ``blocks[kind]``,
    the stacked tree closed over, so that no run's layers are cut out of it
    (a slice of a run of three mixture layers would copy 3.6 GB at LFM2's
    widths). The mixtures' loads go to the program's counters in one
    call-back a forward. (Differentiated, every step of such a scan adds a
    stack-sized, mostly zero gradient to its carry: the training loss takes
    ``_parts_states``.)"""
    loads = []
    for kind, start, n in _parts_runs(cfg):
        fn = functools.partial(_parts_block, cfg=cfg, kind=kind)
        if cfg.remat:
            fn = jax.checkpoint(fn)

        def layer(x, l, fn=fn, kind=kind):
            x, load = fn(blocks[kind], l, x, positions)
            return x, None if load is None else load[0]     # not the counts

        x, load = jax.lax.scan(layer, x, start + jnp.arange(n))
        if load is not None:
            loads.append(load)
    if loads:
        expert.record_load(jnp.concatenate(loads), cfg.experts)
    return x


def _parts_states(blocks, x: jax.Array, cfg: TransformerConfig):
    """``_apply_parts`` for the training loss: each run is a ``lax.scan``
    over the run's *stacked leaves* (``xs``), a layer's experts converted
    to the compute dtype where the scan hands them over (the grouped product
    takes its weights in the tokens' dtype; the other weights are converted
    where they are used) and the layer's block reading it as a stack of one. Differentiated, the scan's transpose writes layer l's weight
    gradient once, as slice l of the stacked gradient; the serving scan
    over indices, with the tree closed over, would add a whole stacked
    gradient to its carry every step. A run that is part of its kind's
    stack is cut out of it first (a copy: LFM2's kinds, which no cell
    trains). No call-back: the loads come back, ``(loads [n_moe, 4], counts
    [n_moe, n_routed])`` over the mixture layers in order (``None`` without
    one), and leave the step in its metrics.

    Under ``remat`` a layer's checkpoint keeps, beside the block's input,
    the flash kernel's output and log-sum-exp (``flash_attention.KEPT``: o
    ``[B x heads, L, v_dim]`` in the compute dtype and a float32 row a
    head, 68 MB a layer at Kanana-2's 32 heads of 128 over 8,192 tokens):
    the backward recomputes the projections, norms, rotary, q, k, v and the
    mixture as before, but not ``flash_fwd``, whose recomputed call has no
    reader and is dropped, so the forward kernel runs once a layer and
    ``flash_dq`` / ``flash_dkv`` read the arrays the first call made (the
    same bits). This checkpoint alone has that policy: the dense and the
    looped stacks' own checkpoints keep nothing of the kernel (ROADMAP
    S1b)."""
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    loads = []
    for kind, start, n in _parts_runs(cfg):
        def layer(x, params, kind=kind):
            # the conversion is inside what ``remat`` recomputes: the scan
            # keeps the layer's float32 slice, not a second copy of it (the
            # TPU compiler still converts every stacked leaf whole before
            # the loop, 2 B a parameter held through the step, with or
            # without a barrier here: PERF.md section 4)
            if "experts" in params:
                params = {**params, "experts": jax.tree.map(
                    lambda p: p.astype(cfg.dtype), params["experts"])}
            return _parts_block(jax.tree.map(lambda p: p[None], params), 0,
                                x, positions, cfg, kind)

        if cfg.remat:
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.save_only_these_names(
                    *FLASH_KEPT))
        run = jax.tree.map(lambda p: p[start:start + n], blocks[kind])
        x, load = jax.lax.scan(layer, x, run)
        if load is not None:
            loads.append(load)
    if not loads:
        return x, None
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *loads)


def _one_device(cfg: TransformerConfig, mesh) -> None:
    if mesh is not None and mesh.devices.size > 1:
        raise ValueError(f"layer kinds {sorted(set(cfg.kinds))} run on one "
                         "device only: no mesh of more (their kernels have "
                         "no shard_map wrapper)")


def _apply_mixed(blocks, x, positions, cfg: TransformerConfig, mesh):
    """``blocks[kind]`` stacked over that kind's layers: each run of
    neighbouring layers of one kind is one scan, the runs in the published
    order. A ``SPARSE`` or ``LINEAR`` run scans its own slice of the stack
    (the long-document cell's program, left as it was measured)."""
    _one_device(cfg, mesh)
    if SHORTCUT in cfg.kinds:       # a stack of its own kind
        return _apply_shortcut(blocks[SHORTCUT], x, positions, cfg)
    if set(cfg.kinds) <= set(SAMBAY):
        return _apply_sambay(blocks, x, cfg)[0]
    if set(cfg.kinds) <= set(PARTS):
        return _apply_parts(blocks, x, positions, cfg)
    ids = cfg.layer_ids or tuple(range(cfg.n_layers))
    taken = dict.fromkeys(cfg.kinds, 0)
    at = 0
    for kind, run in itertools.groupby(cfg.kinds):
        n = len(list(run))
        start = taken[kind]
        layers = jax.tree.map(lambda p: p[start:start + n], blocks[kind])
        rates = jnp.stack([decay_rates(cfg.n_heads, i,
                                       cfg.decay_depth or cfg.n_layers)
                           for i in ids[at:at + n]])
        fn = functools.partial(_mixed_block, cfg=cfg, kind=kind)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        x, _ = jax.lax.scan(
            lambda x, layer: (fn(layer, x, positions), None), x,
            (layers, rates))
        taken[kind] += n
        at += n
    return x


def apply_layers(blocks, x: jax.Array, cfg: TransformerConfig,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None) -> jax.Array:
    """A run of stacked layers (``blocks`` leaves: [n, ...]) applied to the
    states ``x`` [B, L, d]: the whole stack for ``pass_states``, one stage's
    layers for the pipeline (``train.step``, which passes ``mesh=None``
    because a stage already runs per device).

    One scan over the stacked layer params: compiles a single block body
    (fast compiles at depth) and keeps the layer dim shardable for PP."""
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    if cfg.mixed:
        return _apply_mixed(blocks, x, positions, cfg, mesh)
    block_fn = functools.partial(_block, cfg=cfg, mesh=mesh, rules=rules)
    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    def scan_body(x, layer_params):
        return block_fn(layer_params, x, positions), None

    x, _ = jax.lax.scan(scan_body, x, blocks)
    return x


def _by_sublayer(fn, acc, grads):
    """``jax.tree.map(fn, acc, grads)`` over a dense block's parameter
    trees, each top-level part under the scope of the sub-layer it belongs
    to (``mlp`` and its norms ``ln2*``; the rest is ``attn``): a weight's
    gradient is that sub-layer's backward work."""
    out = {}
    for part in sorted(acc):            # the order jax.tree.map takes
        with (jax.named_scope("mlp") if part in ("mlp", "ln2", "ln2_post")
              else jax.named_scope("attn")):
            out[part] = jax.tree.map(fn, acc[part], grads[part])
    return out


def _looped_states(blocks, ln_f, x: jax.Array, cfg: TransformerConfig,
                   mesh: Optional[Mesh], rules: Optional[ShardingRules]
                   ) -> jax.Array:
    """The stack ``cfg.n_passes`` times over from the states ``x``, the
    shared final norm between passes: every pass's pre-final-norm states.

    The passes are a second ``lax.scan`` round the layers' scan, not
    unrolled calls: one block body is compiled whatever ``n_passes`` is, and
    on the v5e the step's memory was the smaller that way (PERF.md section
    4)."""
    def one_pass(x, _):
        h = apply_layers(blocks, x, cfg, mesh, rules)
        with jax.named_scope("head"):       # the final norm, between passes
            return _rmsnorm(h, ln_f, cfg.norm_eps), h

    _, states = jax.lax.scan(one_pass, x, None, length=cfg.n_passes)
    return states


def _looped_states_summing(blocks, ln_f, x: jax.Array,
                           cfg: TransformerConfig, mesh: Optional[Mesh],
                           rules: Optional[ShardingRules]) -> jax.Array:
    """``_looped_states`` of a dense, rematerialised stack with a backward
    pass of its own: the same two loops run backwards, and the step that
    makes layer l's weight gradient adds it into slice l of **one float32
    accumulator of the stacked blocks' shape**, carried through both loops.

    Left to autodiff, the layers' scan hands each pass's weight gradients
    on as a stacked float32 tree of their own and the passes' scan adds
    that tree to its running sum: 12 bytes a parameter a pass read and
    written for nothing (PERF.md section 6, PR 43). The sum is the same
    float32 values added in the same order, last pass first.

    The forward keeps what ``jax.checkpoint`` kept (each block
    application's input, and the passes' states, which are the result);
    the backward recomputes one block at a time, as ``remat`` did. Not
    differentiated, it is ``_looped_states`` itself; and so it is for a
    stack that keeps its blocks' residuals (``cfg.remat`` false) or is
    made of other kinds of layer."""
    if not cfg.remat or cfg.mixed:
        return _looped_states(blocks, ln_f, x, cfg, mesh, rules)
    norm = functools.partial(_rmsnorm, eps=cfg.norm_eps)
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    block = functools.partial(_block, positions=positions, cfg=cfg,
                              mesh=mesh, rules=rules)

    @jax.custom_vjp
    def states_of(blocks, ln_f, x):
        return _looped_states(blocks, ln_f, x, cfg, mesh, rules)

    def forward(blocks, ln_f, x):
        def one_pass(x, _):
            h, inputs = jax.lax.scan(lambda x, layer: (block(layer, x), x),
                                     x, blocks)
            with jax.named_scope("head"):
                return norm(h, ln_f), (h, inputs)

        _, (states, inputs) = jax.lax.scan(one_pass, x, None,
                                           length=cfg.n_passes)
        return states, (blocks, ln_f, inputs, states)

    def backward(kept, d_states):
        blocks, ln_f, inputs, states = kept
        # float32, as they are
        acc = _by_sublayer(lambda _, p: jnp.zeros_like(p), blocks, blocks)
        if mesh is not None:        # the accumulator lies as the blocks do
            acc = jax.tree.map(
                lambda a, axes: jax.lax.with_sharding_constraint(
                    a, (rules or ShardingRules()).sharding(mesh, axes)),
                acc, logical_axes(cfg)["blocks"],
                is_leaf=lambda axes: isinstance(axes, tuple))

        def one_layer(carry, at):
            d_x, acc = carry
            l, layer, x = at
            _, pull = jax.vjp(block, layer, x)
            d_layer, d_x = pull(d_x)
            # read slice l, add, write slice l: in place, and on the TPU in
            # the fusion of the matmul that made the gradient
            acc = _by_sublayer(
                lambda a, g: jax.lax.dynamic_update_index_in_dim(
                    a, jax.lax.dynamic_index_in_dim(a, l, 0) + g[None], l, 0),
                acc, d_layer)
            return (d_x, acc), None

        def one_pass(carry, at):
            d_next, acc, d_ln_f = carry    # d_next: of this pass's normed states
            h, pass_inputs, d_h = at
            # a custom_vjp's backward is traced where it is transposed: the
            # final norm's part of it enters the norm's scope itself (the
            # recomputed block's scopes ride its ``jax.vjp``)
            with jax.named_scope("head"):
                _, pull = jax.vjp(norm, h, ln_f)
                through_norm, d_w = pull(d_next)
                d_h = d_h + through_norm
            (d_x, acc), _ = jax.lax.scan(
                one_layer, (d_h, acc),
                (jnp.arange(cfg.n_layers), blocks, pass_inputs),
                reverse=True)
            with jax.named_scope("head"):
                return (d_x, acc, d_ln_f + d_w), None

        with jax.named_scope("head"):
            first = (jnp.zeros_like(d_states[0]), acc, jnp.zeros_like(ln_f))
        (d_x, acc, d_ln_f), _ = jax.lax.scan(
            one_pass, first, (states, inputs, d_states), reverse=True)
        return acc, d_ln_f, d_x

    states_of.defvjp(forward, backward)
    return states_of(blocks, ln_f, x)


def _embed(params, tokens, cfg: TransformerConfig) -> jax.Array:
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.embed_scale != 1.0:
            x = x * cfg.embed_scale
        return x


def _pass_states(params, tokens, cfg: TransformerConfig, mesh, rules,
                 looped) -> jax.Array:
    """``pass_states``, several passes being ``looped``'s to run."""
    x = _embed(params, tokens, cfg)
    if cfg.n_passes == 1:
        return apply_layers(params["blocks"], x, cfg, mesh, rules)[None]
    return looped(params["blocks"], params["ln_f"], x, cfg, mesh, rules)


def pass_states(params: Dict[str, Any], tokens: jax.Array,
                cfg: TransformerConfig, mesh: Optional[Mesh] = None,
                rules: Optional[ShardingRules] = None) -> jax.Array:
    """Embedding, then the stack ``cfg.n_passes`` times over: every pass's
    pre-final-norm states, [n_passes, B, L, d]. Pass t + 1 starts from the
    final norm of pass t's states, which is also what ``head`` makes of
    them.

    Several passes are ``_looped_states``' two scans (the training loss
    takes the same two with a backward pass of its own,
    ``_looped_states_summing``). A single pass builds no loop over passes
    at all: the plain decoder's program."""
    return _pass_states(params, tokens, cfg, mesh, rules, _looped_states)


def backbone(params: Dict[str, Any], tokens: jax.Array,
             cfg: TransformerConfig, mesh: Optional[Mesh] = None,
             rules: Optional[ShardingRules] = None) -> jax.Array:
    """Embedding + all transformer blocks, every pass of them; returns the
    last pass's pre-final-norm states."""
    return pass_states(params, tokens, cfg, mesh, rules)[-1]


def _head_weight(params: Dict[str, Any], cfg: TransformerConfig) -> jax.Array:
    """The lm head's weight [d, V] in the compute dtype: the ``lm_head``
    leaf, or with ``cfg.tie_embeddings`` the embedding transposed (a
    product's own read of it, no copy)."""
    if cfg.tie_embeddings:
        return params["embed"].astype(cfg.dtype).T
    return params["lm_head"].astype(cfg.dtype)


def _final_norm(params: Dict[str, Any], x: jax.Array,
                cfg: TransformerConfig) -> jax.Array:
    if cfg.layer_norm:
        return _layernorm(x, params["ln_f"], params["ln_f_b"], cfg.norm_eps)
    return _rmsnorm(x, params["ln_f"], cfg.norm_eps)


def head(params: Dict[str, Any], x: jax.Array,
         cfg: TransformerConfig) -> jax.Array:
    """Final norm + lm-head projection -> float32 logits. The single logits
    path shared by inference (``apply``) and training (``token_nll``)."""
    with jax.named_scope("head"):
        x = _final_norm(params, x, cfg)
        if cfg.logit_scale != 1.0:
            x = x * cfg.logit_scale
        logits = jnp.einsum("bld,dv->blv", x, _head_weight(params, cfg))
        return logits.astype(jnp.float32)


def apply(params: Dict[str, Any], tokens: jax.Array,
          cfg: TransformerConfig, mesh: Optional[Mesh] = None,
          rules: Optional[ShardingRules] = None) -> jax.Array:
    """tokens: [B, L] int32 -> logits [B, L, vocab] (float32)."""
    x = backbone(params, tokens, cfg, mesh, rules)
    return head(params, x, cfg)


# -- state that outlives a call: prefill, then a token a step ------------------------


class DecodeState(NamedTuple):
    """What ``S`` sequences keep between tokens, stacked over the layers of a
    kind in their order in the stack: the Mamba layers' state ``ssm`` [n_mamba,
    S, heads, head_dim, d_state] float32 and convolution tail ``conv``
    [n_mamba, S, conv_width - 1, conv_dim], the attention layers' ``k`` and
    ``v`` [n_attn, S, T, kv_heads x head_dim] (a position's heads side by
    side, one row of the cache), both in the compute dtype,
    ``lengths`` [S], the positions each sequence holds, and the window
    layers' ``ring_k`` and ``ring_v`` [n_window, S, W, kv_heads x head_dim]:
    a ring of ``W = min(window, T)`` rows in which position ``p`` lives in row
    ``p % W`` (``_attention_step``). ``k`` and ``v`` are the layers' that
    attend over everything (``ATTN``, ``GLOBAL_MOE``). A stack without a kind
    of layer holds an empty array in its place.

    A ``SAMBAY`` stack keeps three kinds of state: a ``MAMBA1`` layer's
    ``ssm`` [n, S, d_state, d_inner] float32 (a channel's states down a lane)
    and ``conv`` [n, S, conv_width - 1, d_inner]; a ``DIFF_WINDOW`` layer's
    ring; and **one** ``k`` / ``v`` [1, S, T, ...] that the ``DIFF_GLOBAL``
    layer writes and that layer and every ``DIFF_CROSS`` layer read (a
    ``GMU`` and a ``DIFF_CROSS`` layer keep nothing: what a ``GMU`` gates is
    made again each step by the last scan layer)."""
    ssm: jax.Array
    conv: jax.Array
    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    ring_k: jax.Array
    ring_v: jax.Array


def _cache_of(kind: str) -> str:
    """Which of ``DecodeState``'s stacks a ``DECODABLE`` kind's layer keeps
    its share in: ``state`` (``ssm`` and ``conv``), ``full`` (``k`` and
    ``v``), ``ring`` (``ring_k`` and ``ring_v``) or ``none`` (a layer that
    reads what other layers keep)."""
    return ("state" if kind in (MAMBA, MAMBA1)
            else "ring" if kind in (WINDOW_MOE, DIFF_WINDOW)
            else "none" if kind in (DIFF_CROSS, GMU) else "full")


def cache_readers(cfg: TransformerConfig) -> Dict[str, int]:
    """How many layers of the stack read each K/V stack of ``DecodeState`` in
    a decode step: ``full`` (``k`` and ``v``: the layers that keep a cache of
    their own, and every ``DIFF_CROSS`` layer beside the one ``DIFF_GLOBAL``
    layer whose cache it reads) and ``ring`` (the window layers, a ring
    each)."""
    held = collections.Counter(_cache_of(kind) for kind in cfg.kinds)
    return {"full": held["full"] + cfg.kinds.count(DIFF_CROSS),
            "ring": held["ring"]}


def _decodable(cfg: TransformerConfig) -> None:
    if not set(cfg.kinds) <= set(DECODABLE) or cfg.n_passes != 1:
        raise ValueError(
            f"layer kinds {sorted(set(cfg.kinds))}: only a stack of "
            f"{DECODABLE} keeps a state across calls")


def init_decode_state(cfg: TransformerConfig, slots: int,
                      cache_len: int) -> DecodeState:
    """``slots`` empty sequences with room for ``cache_len`` positions."""
    _decodable(cfg)
    n = collections.Counter(_cache_of(kind) for kind in cfg.kinds)
    n_mamba = n["state"]
    m = cfg.mamba or MambaConfig(0, 0, 0)
    row = cfg.kv_heads * cfg.head_dim
    kv = (n["full"], slots, cache_len, row)
    ring = (n["ring"], slots, min(cfg.window or 0, cache_len), row)
    if cfg.mamba1 is not None:
        m1 = cfg.mamba1
        ssm, conv = ((n_mamba, slots, m1.d_state, m1.d_inner),
                     (n_mamba, slots, m1.conv_width - 1, m1.d_inner))
    else:
        ssm, conv = ((n_mamba, slots, m.n_heads, m.head_dim, m.d_state),
                     (n_mamba, slots, m.conv_width - 1, m.conv_dim))
    return DecodeState(
        ssm=jnp.zeros(ssm, jnp.float32), conv=jnp.zeros(conv, cfg.dtype),
        k=jnp.zeros(kv, cfg.dtype), v=jnp.zeros(kv, cfg.dtype),
        lengths=jnp.zeros((slots,), jnp.int32),
        ring_k=jnp.zeros(ring, cfg.dtype), ring_v=jnp.zeros(ring, cfg.dtype))


def _joined_loads(loads: list) -> Optional[jax.Array]:
    """The mixture layers' loads of one program (a list of ``[n, 4]``, a run
    each) as one array ``[n_moe, 4]``, ``None`` without a mixture: a result
    of the program, which the caller feeds to the counters once the device
    has it (``expert.record_load_when_ready``). A call-back of the program's
    own would have the device wait for the host (2.2 ms a decode step on the
    v5e: PERF.md section 6, PR 55)."""
    return jnp.concatenate(loads) if loads else None


def prefill(params: Dict[str, Any], tokens: jax.Array, lengths: jax.Array,
            cfg: TransformerConfig
            ) -> Tuple[jax.Array, DecodeState, Optional[jax.Array]]:
    """The right-padded prompts ``tokens`` [B, L] of ``lengths`` [B] through
    the stack: each prompt's pre-final-norm state at its last real position
    [B, d] (``head`` makes the first token's logits of it) and what the B
    sequences keep, a ``DecodeState`` of B slots whose K and V hold L
    positions; a window layer's are handed over as its ring, the prompt's
    last ``W`` positions each in row ``p % W`` (all ``L`` in order where ``L
    <= W``). Each run of layers is a scan over its indices that hands out
    what the layers keep (one prompt's is small: 2 MB a Mamba layer). The
    third result is the mixtures' loads (``_joined_loads``)."""
    _decodable(cfg)
    if set(cfg.kinds) <= set(SAMBAY):
        return _sambay_prefill(params, tokens, lengths, cfg)
    B, L = tokens.shape
    x = _embed(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    blocks = params["blocks"]
    kept_by_cache: Dict[str, list] = {"state": [], "full": [], "ring": []}
    run_loads = []
    for kind, start, n in _parts_runs(cfg):
        def layer(x, l, kind=kind):
            kept: list = []
            x, load = _parts_block(blocks[kind], l, x, positions, cfg, kind,
                                   lengths, kept)
            return x, (tuple(kept), None if load is None else load[0])

        x, (kept, load) = jax.lax.scan(layer, x, start + jnp.arange(n))
        kept_by_cache[_cache_of(kind)].append(kept)
        if load is not None:
            run_loads.append(load)
    empty = init_decode_state(cfg, B, L)
    rows = empty.ring_k.shape[2]

    def joined(cache, i, otherwise):
        runs = kept_by_cache[cache]
        if not runs:
            return otherwise
        whole = jnp.concatenate([run[i] for run in runs])
        if cache != "state":  # [n, B, L, kv_heads, head_dim]: a row a position
            whole = whole.reshape(*whole.shape[:3], -1)
        if cache == "ring":
            whole = _as_ring(whole, lengths, rows)
        return whole.astype(otherwise.dtype)

    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, DecodeState(
        ssm=joined("state", 0, empty.ssm), conv=joined("state", 1, empty.conv),
        k=joined("full", 0, empty.k), v=joined("full", 1, empty.v),
        lengths=lengths.astype(jnp.int32),
        ring_k=joined("ring", 0, empty.ring_k),
        ring_v=joined("ring", 1, empty.ring_v)), _joined_loads(run_loads)


def _as_ring(whole, lengths, rows: int):
    """K or V of the window layers over a prompt's positions, ``whole`` [n,
    B, L, row], as their rings of ``rows`` rows: row r holds the last
    position p < length with p % rows == r (a row the prompt has not reached
    holds whatever: its age masks it); all L in order where L <= rows."""
    L = whole.shape[2]
    if rows >= L:
        return whole
    r = jnp.arange(rows)[None]
    p = r + rows * ((lengths[:, None] - 1 - r) // rows)
    return jnp.take_along_axis(
        whole, jnp.clip(p, 0, L - 1)[None, :, :, None], axis=2)


def _sambay_prefill(params, tokens, lengths, cfg: TransformerConfig):
    """``prefill`` of a ``SAMBAY`` stack. The self-decoder (every layer
    before the full attention) runs over every position; the full attention
    layer makes K and V of every position, which is all the cross-decoder
    ever reads of the prompt, so that layer's query and every layer after it
    run **on the last real position alone** (``_sambay_rows`` without a
    write: the decode step's layers over the prompt's K and V as a cache of
    L rows, the last scan layer's output there as the memory). Of a prompt's
    FLOPs 14 of 32 published layers are then one row's."""
    B, L = tokens.shape
    blocks, kinds = params["blocks"], cfg.kinds
    at = kinds.index(DIFF_GLOBAL) if DIFF_GLOBAL in kinds else len(kinds)
    x, kept = _apply_sambay(blocks, _embed(params, tokens, cfg), cfg, lengths,
                            at)
    empty = init_decode_state(cfg, B, L)

    def joined(kind, i, otherwise):
        if not kept[kind]:
            return otherwise
        return jnp.concatenate([run[i] for run in kept[kind]])

    def rows(a):    # [n, B, L, G, D] -> a row a position
        return a.reshape(*a.shape[:3], -1)

    ring = [_as_ring(rows(joined(DIFF_WINDOW, i, e)), lengths,
                     e.shape[2]).astype(e.dtype)
            if kept[DIFF_WINDOW] else e
            for i, e in enumerate((empty.ring_k, empty.ring_v))]
    ssm, conv = (joined(MAMBA1, i, e).astype(e.dtype)
                 for i, e in enumerate((empty.ssm, empty.conv)))
    last = (lengths - 1).astype(jnp.int32)

    def at_last(a):     # [B, L, w] -> [B, w] at each prompt's last position
        return jnp.take_along_axis(a, last[:, None, None], axis=1)[:, 0]

    k, v = empty.k, empty.v
    if at < len(kinds):
        with jax.named_scope("attn"), _kind_scope(DIFF_GLOBAL):
            # the full attention's K and V at every position; its query is
            # made again at the last one, with the layers after it
            part, norm = _sambay_parts(blocks[DIFF_GLOBAL], 0, cfg)
            _, k, v = _diff_projected(part("diff"), norm(x, "ln1"), cfg)
            k, v = (rows(a[None]).astype(e.dtype)
                    for a, e in ((k, empty.k), (v, empty.v)))
        memory = (at_last(kept[MAMBA1][-1][2][-1]) if kept[MAMBA1] else
                  jnp.zeros((B, cfg.mamba1.d_inner), cfg.dtype))
        x, _ = _sambay_rows(blocks, at_last(x), cfg, at,
                            (ssm, conv, k, v, *ring), memory, lengths, last,
                            write=False)
    else:
        x = at_last(x)
    return x, DecodeState(ssm, conv, k, v, lengths.astype(jnp.int32),
                          *ring), None


def insert_state(state: DecodeState, piece: DecodeState, slot
                 ) -> DecodeState:
    """``piece`` (a ``prefill`` of one or more sequences, its K and V and
    its rings no longer than the slots') written into ``state`` from slot
    ``slot`` on: a
    ``dynamic_update_slice`` a leaf, in place where ``state`` is donated.
    Every other slot keeps its bits."""
    def put(whole, part):
        at = (0, slot) + (0,) * (whole.ndim - 2)
        return jax.lax.dynamic_update_slice(whole, part.astype(whole.dtype),
                                            at)

    return DecodeState(
        ssm=put(state.ssm, piece.ssm), conv=put(state.conv, piece.conv),
        k=put(state.k, piece.k), v=put(state.v, piece.v),
        lengths=jax.lax.dynamic_update_slice(state.lengths, piece.lengths,
                                             (slot,)),
        ring_k=put(state.ring_k, piece.ring_k),
        ring_v=put(state.ring_v, piece.ring_v))


def decode_step(params: Dict[str, Any], tokens: jax.Array,
                state: DecodeState, cfg: TransformerConfig,
                active: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, DecodeState, Optional[jax.Array]]:
    """One token for every slot: ``tokens`` [S] -> float32 logits [S, V] of
    the next, the state with the token taken in and the mixtures' loads
    (``_joined_loads``). ``active`` [S] (all, if
    None) says which slots hold a sequence: only their ``lengths`` advance
    (an empty slot computes on whatever it holds, so the program has one
    shape). Each run of layers is a loop over its indices with the state as
    the carry: a layer's slice is read where it is used and written back
    where it was read (``_mamba_step``, ``_attention_step``), so a donated
    state is updated in place (as ``xs`` and ``ys`` of a scan it would be held
    twice). With ``cfg.use_flash`` a Mamba layer's recurrence is a Mosaic call
    that takes the whole stack of states and the layer's index and is its
    own output: it moves that layer's bytes alone, once in and once out. An
    attention layer reads and writes its own stack, a window layer its ring,
    any other the full cache: with ``cfg.use_flash`` a Mosaic call that reads
    the rows each slot has reached (``_attention_step``), else a masked
    product over the rows the stack has. A mixture runs on the slots' [S, d]
    rows through the
    call a prompt's tokens take (``_mixture``), routed from the block's input
    where the kind says so (``EARLY_ROUTED``): where the device holds every
    expert and S is a row tile or fewer, one Mosaic call a layer that streams
    the layer's experts past the S rows once (``expert._streams``,
    ``ops.expert_stream``); else the dropless loop at ``S x top_k`` pairs."""
    _decodable(cfg)
    if set(cfg.kinds) <= set(SAMBAY):
        return _sambay_decode_step(params, tokens, state, cfg, active)
    norm = functools.partial(_rmsnorm, eps=cfg.norm_eps)
    blocks = params["blocks"]
    r = cfg.residual_scale
    x = _embed(params, tokens[:, None], cfg)[:, 0]              # [S, d]
    ssm, conv, k_cache, v_cache, lengths, ring_k, ring_v = state
    taken = collections.Counter()   # a cache's layers in the runs before
    step_loads = []

    for kind, start, n in _parts_runs(cfg):
        def part(name, l, kind=kind):
            return jax.tree.map(lambda p: _at(p, l), blocks[kind][name])

        def ffn(x, l, routed=None, part=part, kind=kind):
            """``(x + r FFN(N2(x)), the mixture's load or None)``."""
            if PARTS[kind][1] == "mlp":
                with jax.named_scope("mlp"):
                    return x + r * _mlp(part("mlp", l),
                                        norm(x, part("ln2", l))[None])[0], None
            s, load, _ = _mixture(blocks[kind], l, x[None], routed, cfg)
            with jax.named_scope("moe"):
                return x + r * s, load

        def mamba_layer(i, carry, start=start, part=part, ffn=ffn):
            x, ssm, conv = carry
            l = start + i
            with jax.named_scope("mamba"):
                out, ssm, conv = _mamba_step(
                    part("mamba", l), norm(x, part("ln1", l)), cfg, ssm,
                    conv, l)
                x = x + r * out
            return ffn(x, l)[0], ssm, conv

        def attn_layer(i, carry, start=start, part=part, ffn=ffn, kind=kind,
                       first=taken[_cache_of(kind)]):
            x, k_cache, v_cache, loads = carry
            l = start + i
            routed = _early_choice(kind, x, lambda: part("router", l), cfg)
            rope, window = _kind_attention(cfg, kind)
            with jax.named_scope("attn"), _kind_scope(kind):
                out, k_cache, v_cache = _attention_step(
                    part("attn", l), norm(x, part("ln1", l)), cfg, k_cache,
                    v_cache, first + i, lengths, rope, window is not None)
                x = x + r * out
            x, load = ffn(x, l, routed)
            if load is not None:
                loads = loads.at[i].set(load)
            return x, k_cache, v_cache, loads

        run_loads = (None if PARTS[kind][1] == "mlp"
                     else jnp.zeros((n, 4), jnp.int32))
        if kind == MAMBA:
            x, ssm, conv = jax.lax.fori_loop(0, n, mamba_layer,
                                             (x, ssm, conv))
        elif _cache_of(kind) == "ring":
            x, ring_k, ring_v, run_loads = jax.lax.fori_loop(
                0, n, attn_layer, (x, ring_k, ring_v, run_loads))
        else:
            x, k_cache, v_cache, run_loads = jax.lax.fori_loop(
                0, n, attn_layer, (x, k_cache, v_cache, run_loads))
        taken[_cache_of(kind)] += n
        if run_loads is not None:
            step_loads.append(run_loads)
    step = 1 if active is None else active.astype(lengths.dtype)
    logits = head(params, x[:, None], cfg)[:, 0]
    return logits, DecodeState(ssm, conv, k_cache, v_cache, lengths + step,
                               ring_k, ring_v), _joined_loads(step_loads)


def _sambay_decode_step(params, tokens, state: DecodeState,
                        cfg: TransformerConfig, active):
    """``decode_step`` of a ``SAMBAY`` stack: every layer on the slots' rows
    (``_sambay_rows`` with a write). The full attention layer writes the
    token's k and v into the one cache and reads it; every cross layer after
    it reads the same rows, the token's own among them; a ``GMU`` gates the
    step's own output of the last scan layer."""
    ssm, conv, k_cache, v_cache, lengths, ring_k, ring_v = state
    x = _embed(params, tokens[:, None], cfg)[:, 0]              # [S, d]
    S = x.shape[0]
    memory = jnp.zeros((S, cfg.mamba1.d_inner), cfg.dtype)
    newest = jnp.minimum(lengths, max(k_cache.shape[2], 1) - 1)
    x, pieces = _sambay_rows(
        params["blocks"], x, cfg, 0,
        (ssm, conv, k_cache, v_cache, ring_k, ring_v), memory, lengths,
        newest, write=True)
    ssm, conv, k_cache, v_cache, ring_k, ring_v = pieces
    step = 1 if active is None else active.astype(lengths.dtype)
    logits = head(params, x[:, None], cfg)[:, 0]
    return logits, DecodeState(ssm, conv, k_cache, v_cache, lengths + step,
                               ring_k, ring_v), None


def _exit_nll(x, w_head, targets):
    """One exit's float32 logits [B, L, V] straight from the MXU's
    accumulator, each position's cross entropy [B, L], and what the
    gradient needs of both: the log-sum-exp and where the target sits. No
    ``log_softmax`` array, and a comparison in place of a gather."""
    logits = jnp.einsum("bld,dv->blv", x, w_head,
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) \
        == targets[..., None]
    nll = lse - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return logits, lse, hit, nll


def _scan_exits(fn, carry, xs):
    """``lax.scan`` of ``fn`` over the exits (the leading axis of ``xs``'s
    leaves). A single exit builds no loop, as ``pass_states`` builds none
    for a single pass: the plain decoder's program."""
    if jax.tree.leaves(xs)[0].shape[0] > 1:
        return jax.lax.scan(fn, carry, xs)
    carry, out = fn(carry, jax.tree.map(lambda a: a[0], xs))
    return carry, jax.tree.map(lambda a: a[None], out)


@jax.custom_vjp
def weighted_nll(x: jax.Array, w_head: jax.Array, targets: jax.Array,
                 weights: jax.Array) -> jax.Array:
    """``sum(weights * nll)``, float32: the lm head and the next-token cross
    entropy of every exit, from the exits' *normed* states ``x`` [T, B, L,
    d], the head's weight ``w_head`` [d, V] in ``x``'s dtype, ``targets``
    [B, L] and float32 ``weights`` [T, B, L].

    Differentiated, it takes its own gradient on the way forward
    (``_weighted_nll_fwd``): an exit's logits are made once, used for the
    loss and for ``softmax - onehot``, and dropped. Three vocabulary-sized
    matmuls an exit (logits, the gradient to the states, the gradient to
    the head) and nothing vocabulary-sized kept for the backward pass but
    the head's gradient itself."""
    with jax.named_scope("head"):
        _, nll = _scan_exits(
            lambda _, x_t: (None, _exit_nll(x_t, w_head, targets)[3]), None,
            x)
        return jnp.sum(weights * nll)


def _weighted_nll_fwd(x, w_head, targets, weights):
    def one_exit(d_head, exit_t):
        x_t, weight_t = exit_t
        logits, lse, hit, nll = _exit_nll(x_t, w_head, targets)
        # d(sum weights * nll) / d logits, rounded once to the operands'
        # dtype: where autodiff's cotangent met the head's cast
        d_logits = ((jnp.exp(logits - lse[..., None]) - hit)
                    * weight_t[..., None]).astype(x.dtype)
        d_x = jnp.einsum("blv,dv->bld", d_logits, w_head)
        d_head = d_head + jnp.einsum("bld,blv->dv", x_t, d_logits,
                                     preferred_element_type=d_head.dtype)
        return d_head, (nll, d_x)

    # several exits sum the head's gradient in float32 and round it once at
    # the end; a single exit has nothing to sum, and its gradient leaves the
    # MXU in the head's dtype as autodiff's did
    sum_dtype = jnp.float32 if x.shape[0] > 1 else w_head.dtype
    # autodiff calls this in weighted_nll's place, outside the scope its
    # body enters
    with jax.named_scope("head"):
        d_head, (nll, d_x) = _scan_exits(
            one_exit, jnp.zeros(w_head.shape, sum_dtype), (x, weights))
        # finished here, while the logits are: left to itself XLA fuses this
        # matmul into the head's optimizer update at the far end of the step
        # and keeps an exit's float32 logits alive until then
        d_head = jax.lax.optimization_barrier(d_head.astype(w_head.dtype))
        return jnp.sum(weights * nll), (d_x, d_head, nll)


def _weighted_nll_bwd(residuals, c):
    d_x, d_head, nll = residuals
    # linear in the weights, so nll is their exact gradient (it reaches the
    # exit gate through p_t); targets are integers
    with jax.named_scope("head"):   # traced where the loss is transposed
        return ((c * d_x).astype(d_x.dtype),
                (c * d_head).astype(d_head.dtype), None, c * nll)


weighted_nll.defvjp(_weighted_nll_fwd, _weighted_nll_bwd)


def token_nll(params, x: jax.Array, targets: jax.Array,
              cfg: TransformerConfig) -> jax.Array:
    """Final norm + lm head + each position's next-token cross entropy,
    [B, L] float32 (evaluation and the tests' oracles; training goes
    through ``weighted_nll``)."""
    with jax.named_scope("head"):
        x = _final_norm(params, x, cfg)
        return _exit_nll(x, _head_weight(params, cfg), targets)[3]


def exit_log_probs(params, states: jax.Array,
                   cfg: TransformerConfig) -> jax.Array:
    """log p_t of leaving at each pass, [n_passes, B, L] float32, from the
    gate on each pass's normed states: p_t = lambda_t * prod_{j<t} (1 -
    lambda_j), and the last pass takes what is left (so one pass has p = 1
    whatever its gate says). Kept in logs: log(1 - sigmoid(z)) is
    log_sigmoid(-z)."""
    with jax.named_scope("head"):
        x = _rmsnorm(states, params["ln_f"],
                     cfg.norm_eps).astype(jnp.float32)
        # a sum of float32 products, not a matmul: the MXU would round x to
        # bfloat16 again
        z = jnp.sum(x * params["exit_gate"]["w"], -1) \
            + params["exit_gate"]["b"]
        stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)      # sum_{j<=t}
        before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
        leave = jax.nn.log_sigmoid(z).at[-1].set(0.0)
        return before + leave


def loss_from_states(params, states: jax.Array, targets: jax.Array,
                     cfg: TransformerConfig
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The training loss from every pass's pre-final-norm states
    [n_passes, B, L, d], and the metrics that come with it; shared by the
    scan path (``loss_and_metrics``) and the pipeline-parallel path
    (train.step).

    Without an exit gate: the last pass's mean next-token cross entropy, no
    metrics. With one: the mean over positions of ``sum_t p_t * CE_t -
    exit_beta * H(p)``, with ``exit_p`` [n_passes] (mean p_t) and
    ``exit_entropy`` (mean H(p)). Either way the heads are one call of
    ``weighted_nll``, with each position's share of the mean as its
    weight: one exit's float32 logits [B, L, vocab] are alive at a time,
    and only while the forward pass is there."""
    def heads(states, weights):
        return weighted_nll(_final_norm(params, states, cfg),
                            _head_weight(params, cfg), targets,
                            weights / targets.size)

    with jax.named_scope("head"):
        if cfg.exit_beta is None:
            last = states[-1:]
            return heads(last, jnp.ones(last.shape[:3], jnp.float32)), {}
        logp = exit_log_probs(params, states, cfg)
        p = jnp.exp(logp)
        entropy = -jnp.sum(p * logp, axis=0)
        loss = heads(states, p) - cfg.exit_beta * jnp.mean(entropy)
        return loss, {"exit_p": jnp.mean(p, axis=(1, 2)),
                      "exit_entropy": jnp.mean(entropy)}


def loss_and_metrics(params, tokens, cfg: TransformerConfig,
                     mesh: Optional[Mesh] = None,
                     rules: Optional[ShardingRules] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The training loss of ``tokens`` (which serve as their own labels) and
    its metrics, as ``loss_from_states`` gives them. A stack of ``PARTS``'
    kinds with a mixture adds the mixture layers' loads: ``moe_load`` [n_moe,
    4] (``expert.held_experts_apply``'s) and ``moe_counts`` [n_moe,
    n_routed], how often the step's tokens chose each routed expert."""
    if cfg.n_passes == 1 and set(cfg.kinds) <= set(PARTS):
        _one_device(cfg, mesh)
        x, loads = _parts_states(params["blocks"],
                                 _embed(params, tokens[:, :-1], cfg), cfg)
        loss, metrics = loss_from_states(params, x[None], tokens[:, 1:], cfg)
        if loads is not None:
            metrics = {**metrics, "moe_load": loads[0],
                       "moe_counts": loads[1]}
        return loss, metrics
    states = _pass_states(params, tokens[:, :-1], cfg, mesh, rules,
                          _looped_states_summing)
    return loss_from_states(params, states, tokens[:, 1:], cfg)


def loss_fn(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> jax.Array:
    """The training loss alone: next-token cross entropy, exit-weighted
    where the configuration has an exit gate."""
    return loss_and_metrics(params, tokens, cfg, mesh, rules)[0]


def num_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
