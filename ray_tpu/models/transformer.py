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
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec

from ray_tpu.ops import flash_attention
from ray_tpu.parallel.sequence import ring_attention
from ray_tpu.parallel.sharding import ShardingRules


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

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

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

    def layer(k):
        ks = jax.random.split(k, 7)
        return {
            "attn": {
                "wq": dense(ks[0], (d, h, hd), d),
                "wk": dense(ks[1], (d, kvh, hd), d),
                "wv": dense(ks[2], (d, kvh, hd), d),
                "wo": dense(ks[3], (h, hd, d), h * hd),
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
    return {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, d),
                                   jnp.float32) * 0.02,
        "blocks": jax.vmap(layer)(layer_keys),      # stacked: [L, ...]
        "ln_f": jnp.ones((d,), jnp.float32),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
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
    return {
        "embed": ("vocab", "embed"),
        "blocks": blk,
        "ln_f": (None,),
        "lm_head": ("embed", "vocab"),
    }


def _rmsnorm(x: jax.Array, w: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * w.astype(x.dtype)


def _rope(x: jax.Array, theta: float, positions: jax.Array) -> jax.Array:
    """x: [B, L, H, D]; rotate pairs along D."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # B L 1 half
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


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
               rules: Optional[ShardingRules] = None):
    """``mesh`` is the mesh of the enclosing jit, or None when the caller
    already runs per device (one chip, or inside a ``shard_map``). ``k`` and
    ``v`` carry ``cfg.kv_heads`` heads: the flash kernel takes them so, the
    other two paths repeat them first."""
    if mesh is not None and "seq" in mesh.axis_names and mesh.shape["seq"] > 1:
        return ring_attention(q, *_repeat_kv(q, k, v), mesh, causal=True)
    if cfg.use_flash:
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
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block(params, x, positions, cfg: TransformerConfig, mesh, rules=None):
    B, L, d = x.shape
    h = _rmsnorm(x, params["ln1"])
    q = jnp.einsum("bld,dhk->blhk", h, params["attn"]["wq"].astype(x.dtype))
    k = jnp.einsum("bld,dhk->blhk", h, params["attn"]["wk"].astype(x.dtype))
    v = jnp.einsum("bld,dhk->blhk", h, params["attn"]["wv"].astype(x.dtype))
    q = _rope(q, cfg.rope_theta, positions)
    k = _rope(k, cfg.rope_theta, positions)
    attn = _attention(q, k, v, cfg, mesh, rules)
    x = x + jnp.einsum("blhk,hkd->bld", attn,
                       params["attn"]["wo"].astype(x.dtype))
    h = _rmsnorm(x, params["ln2"])
    gate = jnp.einsum("bld,df->blf", h, params["mlp"]["wi"].astype(x.dtype))
    up = jnp.einsum("bld,df->blf", h, params["mlp"]["wg"].astype(x.dtype))
    ff = jax.nn.silu(gate) * up
    x = x + jnp.einsum("blf,fd->bld", ff, params["mlp"]["wo"].astype(x.dtype))
    return x


def backbone(params: Dict[str, Any], tokens: jax.Array,
             cfg: TransformerConfig, mesh: Optional[Mesh] = None,
             rules: Optional[ShardingRules] = None) -> jax.Array:
    """Embedding + all transformer blocks; returns pre-final-norm states."""
    B, L = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))

    block_fn = functools.partial(_block, cfg=cfg, mesh=mesh, rules=rules)
    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    def scan_body(x, layer_params):
        return block_fn(layer_params, x, positions), None

    # One scan over the stacked layer params: compiles a single block body
    # (fast compiles at depth) and keeps the layer dim shardable for PP.
    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    return x


def head(params: Dict[str, Any], x: jax.Array,
         cfg: TransformerConfig) -> jax.Array:
    """Final norm + lm-head projection -> float32 logits. The single logits
    path shared by inference (``apply``) and training (``head_and_loss``)."""
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("bld,dv->blv", x,
                        params["lm_head"].astype(cfg.dtype))
    return logits.astype(jnp.float32)


def apply(params: Dict[str, Any], tokens: jax.Array,
          cfg: TransformerConfig, mesh: Optional[Mesh] = None,
          rules: Optional[ShardingRules] = None) -> jax.Array:
    """tokens: [B, L] int32 -> logits [B, L, vocab] (float32)."""
    x = backbone(params, tokens, cfg, mesh, rules)
    return head(params, x, cfg)


def head_and_loss(params, x: jax.Array, targets: jax.Array,
                  cfg: TransformerConfig) -> jax.Array:
    """Final norm + lm head + next-token cross entropy, shared by the scan
    path (``loss_fn``) and the pipeline-parallel path (train.step)."""
    logits = head(params, x, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_fn(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None,
            rules: Optional[ShardingRules] = None) -> jax.Array:
    """Next-token cross entropy (tokens serve as their own labels)."""
    x = backbone(params, tokens[:, :-1], cfg, mesh, rules)
    return head_and_loss(params, x, tokens[:, 1:], cfg)


def num_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
