"""Plain re-export of ``jax.shard_map``.

The repo runs on one installation (jax 0.9.0), where ``shard_map`` is
top-level and takes ``check_vma``; there is no other version to bridge.
The module stays because ten call sites import ``shard_map`` from here and
the linter resolves the name through it (``devtools/linter.py``,
``shardprop.py``); ROADMAP D10 moves them to ``from jax import shard_map``.
"""

from __future__ import annotations

from jax import shard_map

__all__ = ["shard_map"]
