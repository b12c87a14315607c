"""The transformer as a *slot model* of the generation engine
(``ray_tpu/serve/generation.py``): the weights and ``S`` sequences' state on
one device behind ``admit``, ``step`` and ``read``, over this package's
``transformer.prefill``, ``insert_state`` and ``decode_step``."""

from __future__ import annotations

from typing import List, Sequence

import numpy as np


class TransformerGenerator:
    """The slot model over ``models.transformer``: the weights, a
    ``DecodeState`` of ``slots`` sequences with room for ``cache_len``
    positions and the slots' next input tokens, all on ``device``, and three
    jitted programs a reader of a trace finds by name: ``jit_prefill`` (one
    right-padded prompt ``[1, bucket]`` -> its first token, that token's logit
    and what the sequence keeps), ``jit_insert`` (that into a slot, the state
    donated) and ``jit_decode_step`` (one token for every slot, the state
    donated, each slot's largest logit its next input). A
    deployment's class subclasses it or holds one; ``warm_up()`` runs every
    shape once."""

    def __init__(self, cfg, params, *, slots: int, cache_len: int,
                 length_buckets: Sequence[int], device=None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import transformer

        self.cfg, self.params = cfg, params
        self.slots, self.cache_len = int(slots), int(cache_len)
        self.length_buckets = tuple(sorted(int(b) for b in length_buckets))
        self.device = device if device is not None else jax.devices()[0]
        if self.length_buckets[-1] > self.cache_len:
            raise ValueError(
                f"the longest prompt bucket ({self.length_buckets[-1]}) does "
                f"not fit a slot's {self.cache_len} positions")
        with jax.default_device(self.device):
            state = jax.jit(lambda: transformer.init_decode_state(
                cfg, self.slots, self.cache_len))()
        # committed to the device, as every later state and token array is (a
        # program's results are): one compilation a shape, not a second one
        # for the first call's uncommitted arguments
        self.state = jax.device_put(state, self.device)
        # (layers that read it, rows a slot) of each K/V stack that holds
        # rows
        readers = transformer.cache_readers(cfg)
        self._stacks = [(readers[name], a.shape[2])
                        for name, a in (("full", state.k),
                                        ("ring", state.ring_k)) if a.size]
        self.tokens = self._put(np.zeros((self.slots,), np.int32))

        def best(logits):
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    jnp.max(logits, axis=-1))

        # a stack with a mixture hands its layers' loads out of the program
        # (the last result: None without one), and ``_count_loads`` feeds
        # them to the counters once the device has them: no call-back that
        # the device would wait for, and programs the compile cache keeps
        def prefill(params, prompt, length):
            last, piece, loads = transformer.prefill(params, prompt, length,
                                                     cfg)
            token, logit = best(transformer.head(params, last[:, None],
                                                 cfg)[:, 0])
            return token, logit, piece, loads

        def insert(state, tokens, piece, token, slot):
            return (transformer.insert_state(state, piece, slot),
                    jax.lax.dynamic_update_slice(tokens, token, (slot,)))

        def decode_step(params, tokens, state, active):
            logits, state, loads = transformer.decode_step(
                params, tokens, state, cfg, active)
            token, logit = best(logits)
            return token, logit, state, loads

        # the state alone is donated: a step's tokens are the next step's
        # input and also what the host reads a step later
        self._prefill = jax.jit(prefill)
        self._insert = jax.jit(insert, donate_argnums=(0,))
        self._decode_step = jax.jit(decode_step, donate_argnums=(2,))

    def bucket(self, length: int) -> int:
        for b in self.length_buckets:
            if b >= length:
                return b
        raise ValueError(f"a prompt of {length} tokens is longer than the "
                         f"last bucket ({self.length_buckets[-1]})")

    def check(self, prompt: List[int], n: int) -> None:
        self.bucket(len(prompt))
        if len(prompt) + n > self.cache_len:
            raise ValueError(
                f"a prompt of {len(prompt)} tokens and {n} new ones do not "
                f"fit a slot's {self.cache_len} positions")

    def live_rows(self, lengths: Sequence[int]) -> int:
        """The K/V rows that sequences of these lengths hold as the
        attention layers see them: a window layer the last ``window``
        positions of each, any other every position (a row of a cache that
        several layers read counts once for each)."""
        return sum(n * min(int(held), T) for n, T in self._stacks
                   for held in lengths)

    def read_rows(self, lengths: Sequence[int]) -> int:
        """The K/V rows a step's attention reads for sequences of these
        lengths: with the kernels the tiles ``ops.decode_attention`` walks
        up to each one's newest row (``live_rows`` rounded up to whole
        tiles, a layer and slot), without them every row allocated."""
        from ray_tpu.ops.decode_attention import read_rows
        if not self.cfg.use_flash:
            return sum(n * self.slots * T for n, T in self._stacks)
        held = np.asarray(lengths, np.int64)
        return sum(n * int(read_rows(np.minimum(held, T) - 1, T).sum())
                   for n, T in self._stacks)

    def _count_loads(self, loads) -> None:
        if loads is not None:
            from ray_tpu.parallel import expert
            expert.record_load_when_ready(loads, self.cfg.experts)

    def _put(self, array):
        import jax
        return jax.device_put(array, self.device)

    def admit(self, prompt: List[int], slot: int):
        bucket = self.bucket(len(prompt))
        row = np.zeros((1, bucket), np.int32)
        row[0, :len(prompt)] = prompt
        length = self._put(np.array([len(prompt)], np.int32))
        token, logit, piece, loads = self._prefill(
            self.params, self._put(row), length)
        self._count_loads(loads)
        self.state, self.tokens = self._insert(
            self.state, self.tokens, piece, token,
            self._put(np.int32(slot)))
        return (token, logit), bucket

    def step(self, active: np.ndarray):
        token, logit, self.state, loads = self._decode_step(
            self.params, self.tokens, self.state, self._put(active))
        self._count_loads(loads)
        self.tokens = token
        return token, logit

    def read(self, handle):
        import jax
        return jax.device_get(handle)

    def warm_up(self) -> None:
        """Every shape the engine can ask for, once: a prompt of each bucket
        into slot 0, then a step. The slots are left as they are found
        (empty ones hold what the warm-up wrote: an insert overwrites it)."""
        import jax
        for bucket in self.length_buckets:
            self.admit([0] * bucket, 0)
        jax.block_until_ready(self.step(np.zeros((self.slots,), bool)))
