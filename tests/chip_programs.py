"""What the chip-compile tests lower, and how they read a compiled module.

``tests/test_chip_compile.py`` (train steps), ``test_chip_compile_kernels.py``
and ``test_chip_compile_serve.py`` share these: the shapes and shardings a
program is lowered on for a described device, and the readers of an
optimized module's text. The fixtures (``topo``, ``no_compile_cache``,
``mosaic``) are in ``tests/conftest.py``.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.parallel import (MeshConfig, ShardingRules, batch_sharding,
                              build_mesh)
from ray_tpu.train.step import make_lm_train_step

KERNEL = "tpu_custom_call"

# chip_smoke.py's width: the widest transformer the repo runs.
CFG = TransformerConfig(vocab_size=32000, d_model=1024, n_layers=12,
                        n_heads=16, max_seq_len=1024, dtype=jnp.bfloat16,
                        use_flash=True)
# The same with grouped K/V heads (4 query heads a K/V head, as Mistral-7B):
# the kernel takes K and V un-repeated, and under a mesh their head axis is
# split like q's.
CFG_GQA = dataclasses.replace(CFG, n_kv_heads=4)
BATCH, SEQ = 8, 1024


def mesh_of(devices, **axes) -> Mesh:
    return build_mesh(MeshConfig(**axes), devices)


def param_shapes(on_chip, cfg, dtype=None):
    """The shapes of ``init_params``' tree on the described chip (the
    ``on_chip`` fixture); in ``dtype`` where a replica would have cast them."""
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(
        lambda leaf: on_chip(leaf.shape, dtype or leaf.dtype), params)


def cell_dims(cell_name):
    """A benchmark cell, its adapter, and the sizes the adapter reads of the
    cell's configuration."""
    from benchmark import manifest
    cell = manifest.Manifest().cell(cell_name)
    adapter = manifest.adapter(cell.config)
    return cell, adapter, adapter.dims(cell.config, cell.job, cell.chips)


def lower_train_step(mesh: Mesh, cfg: TransformerConfig = CFG,
                      batch: int = BATCH, seq: int = SEQ):
    """A described device cannot hold an array: lower ``step_fn`` on the
    shapes and shardings ``init_fn`` would have produced."""
    rules = ShardingRules()
    init_fn, step_fn, _ = make_lm_train_step(cfg, mesh, rules)
    state = init_fn.eval_shape(jax.ShapeDtypeStruct((2,), jnp.uint32))
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                  sharding=batch_sharding(mesh, rules, 2))
    # __wrapped__: the jitted step under goodput.instrument_jit
    return step_fn.__wrapped__.lower(state, tokens)


def lower_cell_step(topo, cell_name):
    """A one-chip training cell's step at the cell's own sizes."""
    cell, adapter, dims = cell_dims(cell_name)
    seq_len = int(cell.traffic["seq_len"])
    cfg = adapter.program_config(dims, seq_len, cell.deploy.get("model", {}))
    return lower_train_step(
        mesh_of(topo.devices[:1], data=1), cfg,
        int(cell.traffic["sequences_per_step"]), seq_len)


def first_token(on_chip, cell_name, batch=None, length=None):
    """A serving cell's served program (``serve_job``'s ``first_token``),
    lowered at ``[batch, length]``: the cell's largest shape by default."""
    cell, adapter, dims = cell_dims(cell_name)
    deployment = cell.deploy["deployment"]
    batch = batch or max(deployment["pad_batch_to"])
    length = length or max(deployment["length_buckets"])
    cfg = adapter.program_config(dims, max(deployment["length_buckets"]),
                                 cell.deploy.get("model", {}))
    params = param_shapes(on_chip, cfg, cfg.dtype)
    tokens = on_chip((batch, length), jnp.int32)
    last = on_chip((batch,), jnp.int32)

    def first_token(params, tokens, last):
        x = transformer.backbone(params, tokens, cfg)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = transformer.head(params, x, cfg)[:, 0]
        return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1)

    return jax.jit(first_token).lower(params, tokens, last), params


# -- readers of an optimized module's text ----------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?[\w.\-]+ = .*?\s(fusion|convolution|custom-call)\(")


def computations(text):
    """The optimized module's computations, ``{name: lines}``, and the
    entry's name."""
    bodies, entry, name = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            bodies[name] = []
            if line.startswith("ENTRY"):
                entry = name
        elif name is not None:
            bodies[name].append(line)
    return bodies, entry


def unscoped_work(text):
    """The optimized module's ``fusion``, ``convolution`` and
    ``custom-call`` instructions outside a fused computation whose
    ``op_name`` names no scope, each as (kind, line): a fusion round a
    convolution is a ``convolution fusion``, a Mosaic call ``mosaic``."""
    from ray_tpu.observability.metric_names import DEVICE_SCOPES
    bodies, _ = computations(text)
    fused = {m.group(1) for body in bodies.values() for line in body
             for m in [re.search(r"\scalls=%?([\w.\-]+)", line)]
             if m and " fusion(" in line}
    out = []
    for name, body in bodies.items():
        if name in fused:
            continue
        for line in body:
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            op_name = re.search(r'op_name="([^"]*)"', line)
            tokens = re.split(r"[/():]", op_name.group(1)) if op_name else ()
            if DEVICE_SCOPES & set(tokens):
                continue
            kind = m.group(1)
            calls = re.search(r"\scalls=%?([\w.\-]+)", line)
            if kind == "fusion" and any(
                    " convolution(" in inner
                    for inner in bodies.get(calls.group(1), ())):
                kind = "convolution fusion"
            elif KERNEL in line:
                kind = "mosaic"
            out.append((kind, line.strip()))
    return out


ASSIGNED = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?)\s([\w\-]+)\(")
_ARRAY = re.compile(r"\b([a-z]+?)(\d*)\[([\d,]*)\]")


def loops(body):
    """The bodies of a computation's ``while`` instructions."""
    return [re.search(r"body=%?([\w.\-]+)", line).group(1)
            for line in body if " while(" in line]


def step_bodies(bodies):
    """The ``while`` bodies that hold a grouped product (``ragged-dot``): the
    dropless loop's steps, one computation for each place it is traced."""
    return sorted({body for lines in bodies.values() for body in loops(lines)
                   if any("ragged-dot" in line for line in bodies[body])})


def _result_bytes(shape):
    """Bytes of an instruction's result, a tuple's elements summed."""
    return sum(math.prod(int(n) for n in dims.split(",") if n)
               * int(bits or 8) // 8
               for _, bits, dims in _ARRAY.findall(shape))


def weight_copies(text, weights, least=32 * 2 ** 20):
    """What the layers' loop (the entry's ``while``, and the loops nested in
    it) writes of its weights before it uses them: every ``copy`` and every
    fusion that holds no ``convolution`` and no custom call, outside a fused
    computation, that reads a stacked weight (an operand of one of the
    shapes ``weights``), a ``bitcast``, ``reshape`` or tuple element of one,
    or such a copy of one, and whose result has ``least`` bytes or more, as
    (name, bytes, op_name). A slice that a product reads for itself is a
    fusion nested in the product's and is not on the list."""
    bodies, entry = computations(text)
    inside, todo = [], loops(bodies[entry])
    while todo:
        inside.append(todo.pop())
        todo += loops(bodies[inside[-1]])
    found = []
    for name in inside:
        held = set()            # the computation's weights and their copies
        for line in bodies[name]:
            m = ASSIGNED.match(line)
            if not m:
                continue
            result, shape, kind = m.groups()
            calls = re.search(r"\scalls=%?([\w.\-]+)", line)
            operands = re.findall(r"%([\w.\-]+)", line.split(f" {kind}(")[1])
            if kind in ("parameter", "get-tuple-element", "bitcast",
                        "reshape"):     # no bytes written: a weight by its
                if (shape.split("{")[0] in weights      # shape, or one's view
                        or held & set(operands)):
                    held.add(result)
                continue
            if (not held & set(operands) or kind not in ("copy", "fusion")
                    or calls and any(
                        " convolution(" in inner or " custom-call(" in inner
                        for inner in bodies[calls.group(1)])):
                continue
            held.add(result)
            size = _result_bytes(shape)
            if size >= least:
                op_name = re.search(r'op_name="([^"]*)"', line)
                found.append((result, size,
                              op_name.group(1) if op_name else ""))
    return found
