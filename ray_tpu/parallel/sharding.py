"""Logical-axis sharding rules: annotate once, let XLA insert collectives.

Parameters and activations are described by *logical* axis names
("embed", "heads", "batch", ...); a ``ShardingRules`` table maps each to a
mesh axis (or replication). This is the pjit/scaling-book methodology —
shardings are data, not code, so switching DP↔FSDP↔TP↔SP is a config edit,
not a rewrite. (Capability net-new vs the reference; SURVEY §2.5.)
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger("ray_tpu")

MeshAxes = Union[None, str, Tuple[str, ...]]

# Logical-axis names already warned about this process — a typo surfaces
# once, loudly, instead of flooding every step (R27 is the static half).
_warned_axes: set = set()


DEFAULT_RULES: Dict[str, MeshAxes] = {
    # activations
    "batch": ("data", "fsdp"),      # per-example axis: all data-parallel axes
    "seq": "seq",                   # sequence/context parallelism
    "act_embed": None,              # activation feature dim stays replicated
    "act_heads": "tensor",
    # parameters
    "embed": "fsdp",                # ZeRO-3: shard params along embed over fsdp
    "vocab": "tensor",
    "heads": "tensor",              # attention heads over tensor axis
    "kv": None,
    "mlp": "tensor",                # ffn hidden over tensor axis
    # mixture of experts
    "expert": "expert",
    # pipeline
    "stage": "pipe",
    "layers": None,                 # stacked-layer dim; train.step maps it
                                    # to "pipe" when the mesh has stages
    "layers": None,                 # stacked-layer dim; train.step maps it
                                    # to "pipe" when the mesh has stages
}


@dataclass(frozen=True)
class ShardingRules:
    rules: Dict[str, MeshAxes] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def with_overrides(self, **overrides: MeshAxes) -> "ShardingRules":
        merged = dict(self.rules)
        merged.update(overrides)
        return ShardingRules(merged)

    def spec(self, logical_axes: Tuple[Optional[str], ...],
             strict: bool = False) -> P:
        """PartitionSpec for a tensor described by logical axis names.

        An axis name missing from the table replicates that dimension.
        With ``strict=True`` an *unknown* name (as opposed to one mapped
        to ``None`` on purpose) raises instead — a one-character typo
        would otherwise silently replicate a tensor; the default path
        logs a one-shot warning per unknown name.
        """
        parts = []
        used = set()
        for ax in logical_axes:
            if ax is None:
                parts.append(None)
                continue
            if ax not in self.rules:
                if strict:
                    raise ValueError(
                        f"unknown logical axis {ax!r}: not in this "
                        f"ShardingRules table (known: "
                        f"{', '.join(sorted(self.rules))}); without "
                        "strict=True this dimension would silently "
                        "replicate")
                if ax not in _warned_axes:
                    _warned_axes.add(ax)
                    logger.warning(
                        "ShardingRules: unknown logical axis %r replicates "
                        "its dimension (known: %s); pass strict=True to "
                        "raise on typos", ax,
                        ", ".join(sorted(self.rules)))
                parts.append(None)
                continue
            mesh_axes = self.rules.get(ax)
            if mesh_axes is None:
                parts.append(None)
                continue
            if isinstance(mesh_axes, str):
                mesh_axes = (mesh_axes,)
            fresh = tuple(a for a in mesh_axes if a not in used)
            used.update(fresh)
            if not fresh:
                parts.append(None)
            elif len(fresh) == 1:
                parts.append(fresh[0])
            else:
                parts.append(fresh)
        return P(*parts)

    def sharding(self, mesh: Mesh,
                 logical_axes: Tuple[Optional[str], ...],
                 strict: bool = False) -> NamedSharding:
        """NamedSharding on *mesh*, dropping mesh axes sized 1 there.

        With ``strict=True``, unknown logical names raise (see ``spec``)
        and so does a rule naming a mesh axis this mesh does not have —
        geometry drift between the rules table and the mesh.  Size-1
        axes are still dropped silently in both modes: a collapsed axis
        is legitimate single-way parallelism, not a typo.
        """
        spec = self.spec(logical_axes, strict=strict)
        # Drop axes not present in (or sized 1 on) this mesh.
        cleaned = []
        for part in spec:
            if part is None:
                cleaned.append(None)
            elif isinstance(part, tuple):
                missing = [a for a in part if a not in mesh.axis_names]
                if missing and strict:
                    raise ValueError(
                        f"rules name mesh axes {missing} absent from this "
                        f"mesh (axes: {', '.join(mesh.axis_names)})")
                keep = tuple(a for a in part if a in mesh.axis_names
                             and mesh.shape[a] > 1)
                cleaned.append(keep if keep else None)
            else:
                if part not in mesh.axis_names and strict:
                    raise ValueError(
                        f"rules name mesh axis {part!r} absent from this "
                        f"mesh (axes: {', '.join(mesh.axis_names)})")
                cleaned.append(part if part in mesh.axis_names
                               and mesh.shape[part] > 1 else None)
        return NamedSharding(mesh, P(*cleaned))


def _axes_mismatch_path(tree: Any, axes: Any,
                        path: str = "") -> Optional[str]:
    """First path where ``axes`` stops mirroring ``tree``, else None.

    Containers (dict/list/tuple) of ``tree`` must be matched by the same
    container shape in ``axes``; at a ``tree`` leaf any axes value is
    acceptable (tuples of names, a single name, or None).
    """
    if isinstance(tree, dict):
        if not isinstance(axes, dict):
            return (f"{path or '<root>'}: tree has a dict, axes_tree has "
                    f"{type(axes).__name__}")
        if set(tree) != set(axes):
            missing = sorted(set(tree) - set(axes))
            extra = sorted(set(axes) - set(tree))
            detail = []
            if missing:
                detail.append(f"missing keys {missing}")
            if extra:
                detail.append(f"extra keys {extra}")
            return f"{path or '<root>'}: {', '.join(detail)}"
        for k in sorted(tree):
            sub = _axes_mismatch_path(tree[k], axes[k], f"{path}[{k!r}]")
            if sub is not None:
                return sub
        return None
    if isinstance(tree, (list, tuple)):
        if not isinstance(axes, type(tree)) or len(axes) != len(tree):
            return (f"{path or '<root>'}: tree has {type(tree).__name__} "
                    f"of {len(tree)}, axes_tree has "
                    f"{type(axes).__name__} of "
                    f"{len(axes) if isinstance(axes, (list, tuple)) else 1}")
        for i, (t, a) in enumerate(zip(tree, axes)):
            sub = _axes_mismatch_path(t, a, f"{path}[{i}]")
            if sub is not None:
                return sub
    return None


def shard_pytree(tree: Any, axes_tree: Any, mesh: Mesh,
                 rules: Optional[ShardingRules] = None,
                 strict: bool = False) -> Any:
    """Device-put every leaf with the sharding derived from its logical axes.

    ``axes_tree`` mirrors ``tree`` with tuples of logical axis names; a
    mis-shaped ``axes_tree`` raises naming the first mismatched path
    instead of jax.tree.map's opaque structure dump.  ``strict`` is
    forwarded to :meth:`ShardingRules.sharding`.
    """
    rules = rules or ShardingRules()

    def _place(leaf, axes):
        return jax.device_put(leaf, rules.sharding(mesh, axes,
                                                   strict=strict))

    try:
        return jax.tree.map(_place, tree, axes_tree,
                            is_leaf=lambda x: x is None)
    except (ValueError, TypeError) as e:
        where = _axes_mismatch_path(tree, axes_tree)
        if where is None:
            raise
        raise ValueError(
            f"axes_tree does not mirror tree at {where}") from e


def batch_sharding(mesh: Mesh, rules: Optional[ShardingRules] = None,
                   ndim: int = 2) -> NamedSharding:
    """Sharding for a [batch, ...] input array."""
    rules = rules or ShardingRules()
    return rules.sharding(mesh, ("batch",) + (None,) * (ndim - 1))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
