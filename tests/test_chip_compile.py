"""Compiles for a described TPU v5e 2x2 host, without a chip.

The TPU compiler is installed where the tests run, and it compiles for a
topology that is described and not attached. These tests keep the main
path's programs compiling at their real widths: the flash kernel forward
and backward, the whole LM train step on one chip and on three four-chip
meshes, the serve forward at its batch buckets, and the two operations of
a mixed stack (chunked linear attention, block-sparse attention) alone and
inside the served forward of the long-document cell. Each asserts the Pallas
kernel is in the compiled program (``tpu_custom_call``). Nothing runs, so
they say nothing about results or times.

The topology is described inside the module-scoped ``topo`` fixture and
nowhere else: only one process may load the TPU library, so a call made
while a module is imported would give xdist workers different tests to
collect. Keep every such test in this one file for the same reason.
"""

import contextlib
import dataclasses
import math
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import ray_tpu.ops.flash_attention  # noqa: F401  (the module, not the function)
import ray_tpu.ops.linear_attention  # noqa: F401
import ray_tpu.ops.sparse_attention  # noqa: F401
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


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep these tests out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, topo, no_compile_cache):
    """The process's backend is the CPU, where the kernel would take
    interpret mode; these compiles are for the described chip."""
    for module in ("flash_attention", "linear_attention", "sparse_attention"):
        monkeypatch.setattr(sys.modules[f"ray_tpu.ops.{module}"],
                            "_backend_is_cpu", lambda: False)


def _mesh(devices, **axes) -> Mesh:
    return build_mesh(MeshConfig(**axes), devices)


def _shape(leaf, sharding):
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)


# (q shape, kv heads, dtype, with the backward): four are the benchmark
# cells' own calls (Mistral 32/8 heads at 4,096 tokens, one and two
# sequences a chip; InternLM2 16/8 heads at the largest and smallest bucket).
KERNEL_SHAPES = {
    "8x1024x16x64-bf16": ((8, 1024, 16, 64), 16, jnp.bfloat16, True),
    "4x2048x16x128-bf16": ((4, 2048, 16, 128), 16, jnp.bfloat16, True),
    "2x1000x8x64-ragged": ((2, 1000, 8, 64), 8, jnp.bfloat16, True),
    "4x512x8x64-f32": ((4, 512, 8, 64), 8, jnp.float32, True),
    "1x4096x32x128-kv8-bf16": ((1, 4096, 32, 128), 8, jnp.bfloat16, True),
    "2x4096x32x128-kv8-bf16": ((2, 4096, 32, 128), 8, jnp.bfloat16, True),
    "8x2048x16x128-kv8-fwd": ((8, 2048, 16, 128), 8, jnp.bfloat16, False),
    "2x256x16x128-kv8-fwd": ((2, 256, 16, 128), 8, jnp.bfloat16, False),
    # 8,192 resident rows: the tiles ask for more than the default VMEM
    "1x16384x4x128-kv2-long": ((1, 16384, 4, 128), 2, jnp.bfloat16, True),
}


@pytest.mark.parametrize("case", list(KERNEL_SHAPES))
def test_flash_kernel_fwd_bwd_compiles(topo, mosaic, case):
    from ray_tpu.ops import flash_attention
    shape, kv_heads, dtype, backward = KERNEL_SHAPES[case]
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(shape[:2] + (kv_heads,) + shape[3:], dtype,
                              sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    fn = (jax.value_and_grad(loss, argnums=(0, 1, 2)) if backward else loss)
    text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    # forward, dq and dk/dv kernels
    assert text.count(KERNEL) >= (3 if backward else 1)


def _lower_train_step(mesh: Mesh, cfg: TransformerConfig = CFG,
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


def test_train_step_compiles_on_one_chip(topo, mosaic):
    compiled = _lower_train_step(_mesh(topo.devices[:1], data=1),
                                 CFG_GQA).compile()
    assert KERNEL in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 2 ** 30)


def test_looped_train_step_compiles_on_one_chip(topo, mosaic):
    """The looped decoder (the stack applied four times, sandwich norms,
    the exit gate and its loss): the kernel is inside two nested scans and
    the per-exit heads inside ``weighted_nll``'s scan."""
    looped = dataclasses.replace(CFG, n_layers=2, n_passes=4, post_norm=True,
                                 exit_beta=0.05)
    compiled = _lower_train_step(_mesh(topo.devices[:1], data=1),
                                 looped).compile()
    text = compiled.as_text()
    assert KERNEL in text
    # the shared weights' gradient is summed where each layer makes it: no
    # operation adds a whole stacked float32 leaf to another (autodiff's
    # ``add_any`` in the passes' loop, a ``select_add_fusion`` over
    # f32[2, ...] a leaf on this chip)
    blocks = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), looped))["blocks"]
    stacked = {"f32[" + ",".join(map(str, p.shape)) + "]"
               for p in jax.tree.leaves(blocks)}
    assert [line for line in text.splitlines()
            if "add_any" in line and " = " in line
            and line.split(" = ")[1].split("{")[0] in stacked] == []
    # one exit's float32 logits at a time: [8, 1024, 32000] is 1.05 GB, and
    # four of them alive with their backward would not leave it here
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 8 * 2 ** 30)


# A one-chip training cell of the benchmark and the most its step may take
# by ``memory_analysis()`` (arguments + temporaries): what the step took
# before the loss head computed its own gradient (PERF.md section 4: 13.51 GB
# at 2 layers, 14.80 GB at 7 layers x 4 passes). The head may not cost memory.
CELL_STEP_BYTES = {"mistral7b-train-4k": 13.51e9, "ouro2.6b-train-4k": 14.80e9}


def _lower_cell_step(topo, cell_name):
    """A one-chip training cell's step at the cell's own sizes."""
    from benchmark import manifest
    cell = manifest.Manifest().cell(cell_name)
    adapter = manifest.adapter(cell.config)
    seq_len = int(cell.traffic["seq_len"])
    cfg = adapter.program_config(
        adapter.dims(cell.config, cell.job, cell.chips), seq_len,
        cell.deploy.get("model", {}))
    return _lower_train_step(
        _mesh(topo.devices[:1], data=1), cfg,
        int(cell.traffic["sequences_per_step"]), seq_len)


@pytest.mark.parametrize("cell_name", list(CELL_STEP_BYTES))
def test_benchmark_cells_train_step_fits_what_it_took(topo, mosaic,
                                                      cell_name):
    compiled = _lower_cell_step(topo, cell_name).compile()
    assert KERNEL in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            <= CELL_STEP_BYTES[cell_name])


@pytest.mark.parametrize("axes", [
    dict(data=4), dict(data=2, tensor=2), dict(data=1, fsdp=4)],
    ids=["data4", "data2xtensor2", "fsdp4"])
def test_train_step_compiles_on_four_chip_mesh(topo, mosaic, axes):
    text = _lower_train_step(_mesh(topo.devices, **axes),
                             CFG_GQA).compile().as_text()
    assert KERNEL in text
    # the gradient reduction over the batch axes
    assert "all-reduce" in text or "reduce-scatter" in text


@pytest.mark.parametrize("bucket", [2, 4, 8])
def test_serve_forward_compiles_at_bucket(topo, mosaic, bucket):
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), CFG))
    params = jax.tree.map(lambda leaf: _shape(leaf, one_chip), params)
    tokens = jax.ShapeDtypeStruct((bucket, SEQ), jnp.int32,
                                  sharding=one_chip)
    text = jax.jit(lambda p, t: transformer.apply(p, t, CFG)).lower(
        params, tokens).compile().as_text()
    assert KERNEL in text


# -- a stack of several kinds of block ------------------------------------------
# (batch, tokens): the long-document cell's largest shape and its first
# selecting bucket, at MiniCPM-SALA's 32 heads of 128 (2 K/V heads).
MIXED_SHAPES = [(1, 32768), (2, 16384)]


@pytest.mark.parametrize("batch,length", MIXED_SHAPES)
def test_linear_attention_kernel_compiles(topo, mosaic, batch, length):
    from ray_tpu.ops.linear_attention import KERNEL_NAME, linear_attention
    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((batch, length, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    rates = jax.ShapeDtypeStruct((32,), jnp.float32, sharding=one_chip)
    text = jax.jit(linear_attention).lower(x, x, x, rates).compile().as_text()
    assert KERNEL in text and KERNEL_NAME in text


@pytest.mark.parametrize("batch,length", MIXED_SHAPES)
def test_sparse_attention_kernels_compile(topo, mosaic, batch, length):
    from ray_tpu.ops.sparse_attention import (ATTEND_KERNEL, SCORES_KERNEL,
                                              sparse_attention)
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((batch, length, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((batch, length, 2, 128), jnp.bfloat16,
                              sharding=one_chip)
    text = jax.jit(sparse_attention).lower(q, kv, kv).compile().as_text()
    assert text.count(KERNEL) >= 2
    assert SCORES_KERNEL in text and ATTEND_KERNEL in text


@pytest.mark.parametrize("length,calls", [(8192, ["flash_fwd"]),
                                          (16384, ["sparse_attn_fwd"])])
def test_mixed_stack_serve_forward_compiles(topo, mosaic, length, calls):
    """The long-document cell's forward at its published widths, one period
    of the stack (a sparse layer and three linear ones): up to ``dense_len``
    the sparse layer is the flash kernel, past it the selection."""
    from benchmark import manifest
    cell = manifest.Manifest().cell("minicpm-sala-serve-longdoc")
    adapter = manifest.adapter(cell.config)
    dims = adapter.dims(cell.config, cell.job, cell.chips)
    dims = {**dims, "n_layers": 4, "mixer_types": dims["mixer_types"][:4],
            "layer_ids": dims["layer_ids"][:4]}
    cfg = adapter.program_config(dims, length, cell.deploy["model"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda p: p.astype(cfg.dtype),
        transformer.init_params(jax.random.PRNGKey(0), cfg)))
    params = jax.tree.map(lambda leaf: _shape(leaf, one_chip), params)
    tokens = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: transformer.head(
        p, transformer.backbone(p, t, cfg)[:, -1:], cfg)).lower(
            params, tokens).compile()
    text = compiled.as_text()
    for call in calls + ["linear_attn_fwd"]:
        assert call in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15 * 10 ** 9)


@pytest.mark.parametrize("length", [16384, 32768])
def test_the_operations_check_compiles_beside_the_references_weights(
        topo, mosaic, length):
    """The long-document cell's check of the two operations at its buckets:
    the three Mosaic calls are in it, and it takes less than the reference's
    own temporaries (3.2 GB at 32,768 tokens), so that with 11.3 GB of
    float32 weights on the device the comparison still fits."""
    from benchmark import manifest
    cell = manifest.Manifest().cell("minicpm-sala-serve-longdoc")
    adapter = manifest.adapter(cell.config)
    dims = adapter.dims(cell.config, cell.job, cell.chips)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(lambda k: adapter.operations_rows_off(
        k, length, dims)).lower(key).compile()
    text = compiled.as_text()
    for call in ("sparse_attn_scores", "sparse_attn_fwd", "linear_attn_fwd"):
        assert call in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3.2 * 10 ** 9


# -- the shortcut layer (LongCat-Flash): latent attention's head widths -------------


@pytest.mark.parametrize("batch,length", [(1, 8192), (2, 2048)])
def test_flash_kernel_compiles_at_unequal_head_widths(topo, mosaic, batch,
                                                      length):
    """q and k heads of 192 beside v heads of 128, 64 heads, forward only:
    the prefill cell's calls at its largest and smallest bucket."""
    from ray_tpu.ops import flash_attention
    one_chip = SingleDeviceSharding(topo.devices[0])
    qk = jax.ShapeDtypeStruct((batch, length, 64, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((batch, length, 64, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(flash_attention).lower(qk, qk, v).compile()
    assert "flash_fwd" in compiled.as_text()
    assert compiled.output_shardings is not None


def test_shortcut_layer_serve_forward_compiles(topo, mosaic):
    """The prefill cell's forward at its published widths, one layer of the
    four: both kernels are in it, the flash call and the ragged product the
    compiler makes of ``lax.ragged_dot``."""
    from benchmark import manifest
    cell = manifest.Manifest().cell("longcat-flash-serve-prefill")
    adapter = manifest.adapter(cell.config)
    dims = {**adapter.dims(cell.config, cell.job, cell.chips), "n_layers": 1}
    cfg = adapter.program_config(dims, 2048, cell.deploy["model"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda p: p.astype(cfg.dtype),
        transformer.init_params(jax.random.PRNGKey(0), cfg)))
    params = jax.tree.map(lambda leaf: _shape(leaf, one_chip), params)
    tokens = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda p, t: transformer.apply(p, t, cfg)).lower(
        params, tokens).compile().as_text()
    assert "flash_fwd" in text and "ragged-dot" in text


# -- the device scopes (``metric_names.DEVICE_SCOPES``) ----------------------


def _first_token(topo, cell_name, batch=None, length=None):
    """A serving cell's served program (``serve_job``'s ``first_token``),
    lowered at ``[batch, length]``: the cell's largest shape by default."""
    from benchmark import manifest
    cell = manifest.Manifest().cell(cell_name)
    adapter = manifest.adapter(cell.config)
    deployment = cell.deploy["deployment"]
    batch = batch or max(deployment["pad_batch_to"])
    length = length or max(deployment["length_buckets"])
    cfg = adapter.program_config(
        adapter.dims(cell.config, cell.job, cell.chips),
        max(deployment["length_buckets"]), cell.deploy.get("model", {}))
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda p: p.astype(cfg.dtype),
        transformer.init_params(jax.random.PRNGKey(0), cfg)))
    params = jax.tree.map(lambda leaf: _shape(leaf, one_chip), params)
    tokens = jax.ShapeDtypeStruct((batch, length), jnp.int32,
                                  sharding=one_chip)
    last = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)

    def first_token(params, tokens, last):
        x = transformer.backbone(params, tokens, cfg)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = transformer.head(params, x, cfg)[:, 0]
        return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1)

    return jax.jit(first_token).lower(params, tokens, last), params


def _internlm2_forward(topo):
    """The offline cell's served program at its largest shape."""
    return _first_token(topo, "internlm2-serve-offline")[0]


PROGRAMS = {"mistral-step": lambda topo: _lower_cell_step(
                topo, "mistral7b-train-4k"),
            "internlm2-forward": _internlm2_forward}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?[\w.\-]+ = .*?\s(fusion|convolution|custom-call)\(")


def _computations(text):
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


def _unscoped_work(text):
    """The optimized module's ``fusion``, ``convolution`` and
    ``custom-call`` instructions outside a fused computation whose
    ``op_name`` names no scope, each as (kind, line): a fusion round a
    convolution is a ``convolution fusion``, a Mosaic call ``mosaic``."""
    from ray_tpu.observability.metric_names import DEVICE_SCOPES
    bodies, _ = _computations(text)
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


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_chips_matmuls_and_kernels_all_name_a_scope(topo, mosaic,
                                                        program):
    """What the compiler for the chip keeps of the scopes: every matmul
    (alone or as a fusion's root) and every Mosaic call of the Mistral step
    and of the InternLM2 forward carries one in its ``op_name``; what does
    not is the compiler's own (copies, slices of the stacked weights,
    multi-output fusions, which carry no metadata at all)."""
    text = PROGRAMS[program](topo).compile().as_text()
    assert KERNEL in text and 'op_name="jit(' in text
    left = _unscoped_work(text)
    assert [entry for entry in left
            if entry[0] in ("convolution", "convolution fusion", "mosaic")
            ] == []
    # and the list is no empty claim: the scan's own operations are on it
    assert any("dynamic" in line for _, line in left)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_scopes_change_an_operations_metadata_and_nothing_else(
        topo, mosaic, monkeypatch, program):
    """The witness that the programs are equal: with every
    ``metadata={...}`` struck out, and the tables of files, functions and
    stack frames at the module's head that ``stack_frame_id`` indexes, the
    optimized module is the same string with the scopes and with
    ``jax.named_scope`` patched to a null context. No instruction's name
    turned out to come from a scope, so nothing else is normalised."""
    def strip(text):
        assert "metadata={" in text and "\nStackFrames\n" in text
        text = re.sub(r"(?ms)^FileNames\n.*?^StackFrames\n.*?\n\n", "",
                      text)
        return re.sub(r",? ?metadata=\{[^{}]*\}", "", text)

    scoped = PROGRAMS[program](topo).compile().as_text()
    assert "/attn/core/" in scoped and "/mlp/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = PROGRAMS[program](topo).compile().as_text()
    assert "/attn/" not in bare and "/mlp/" not in bare
    assert strip(bare) == strip(scoped)


# -- the shortcut stack reads its weights where they lie ---------------------

_ASSIGNED = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?)\s([\w\-]+)\(")
_ARRAY = re.compile(r"\b([a-z]+?)(\d*)\[([\d,]*)\]")


def _loops(body):
    """The bodies of a computation's ``while`` instructions."""
    return [re.search(r"body=%?([\w.\-]+)", line).group(1)
            for line in body if " while(" in line]


def _step_bodies(bodies):
    """The ``while`` bodies that hold a grouped product (``ragged-dot``): the
    dropless loop's steps, one computation for each place it is traced."""
    return sorted({body for lines in bodies.values() for body in _loops(lines)
                   if any("ragged-dot" in line for line in bodies[body])})


def _result_bytes(shape):
    """Bytes of an instruction's result, a tuple's elements summed."""
    return sum(math.prod(int(n) for n in dims.split(",") if n)
               * int(bits or 8) // 8
               for _, bits, dims in _ARRAY.findall(shape))


def _weight_copies(text, weights, least=32 * 2 ** 20):
    """What the layers' loop (the entry's ``while``, and the loops nested in
    it) writes of its weights before it uses them: every ``copy`` and every
    fusion that holds no ``convolution`` and no custom call, outside a fused
    computation, that reads a stacked weight (an operand of one of the
    shapes ``weights``), a ``bitcast``, ``reshape`` or tuple element of one,
    or such a copy of one, and whose result has ``least`` bytes or more, as
    (name, bytes, op_name). A slice that a product reads for itself is a
    fusion nested in the product's and is not on the list."""
    bodies, entry = _computations(text)
    inside, todo = [], _loops(bodies[entry])
    while todo:
        inside.append(todo.pop())
        todo += _loops(bodies[inside[-1]])
    found = []
    for name in inside:
        held = set()            # the computation's weights and their copies
        for line in bodies[name]:
            m = _ASSIGNED.match(line)
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


_PLANTED = """\
%steps (arg.1: (s32[], bf16[8,512,256])) -> (s32[], bf16[8,512,256]) {
  %arg.1 = (s32[], bf16[8,512,256]{2,1,0}) parameter(0)
  %groups = bf16[8,512,256]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg.1), index=1
  %cut.1 = bf16[4,512,256]{2,1,0} fusion(%groups, %i), kind=kLoop, calls=%sliced, metadata={op_name="jit(f)/while/body/moe/experts/dynamic_slice"}
  %small = bf16[1,512,256]{2,1,0} fusion(%groups, %i), kind=kLoop, calls=%sliced
  %ragged-dot = bf16[64,256]{1,0} custom-call(%x, %cut.1), custom_call_target="ragged"
  ROOT %tuple.1 = (s32[], bf16[8,512,256]{2,1,0}) tuple(%i, %groups)
}

%layers (arg.2: (s32[], bf16[2,4,512,256])) -> (s32[], bf16[2,4,512,256]) {
  %arg.2 = (s32[], bf16[2,4,512,256]{3,2,1,0}) parameter(0)
  %leaf = bf16[2,4,512,256]{3,2,1,0} get-tuple-element(%arg.2), index=1
  %bitcast.1 = bf16[8,512,256]{2,1,0} bitcast(%leaf)
  %copy.1 = bf16[8,512,256]{2,1,0} copy(%bitcast.1), metadata={op_name="jit(f)/while/body/moe/reshape"}
  %used = bf16[64,256]{1,0} fusion(%x, %leaf), kind=kOutput, calls=%product
  %tuple.2 = (s32[], bf16[8,512,256]{2,1,0}) tuple(%i, %copy.1)
  %while.2 = (s32[], bf16[8,512,256]{2,1,0}) while(%tuple.2), condition=%cond, body=%steps
  ROOT %tuple.3 = (s32[], bf16[2,4,512,256]{3,2,1,0}) tuple(%i, %leaf)
}

%sliced (p.0: bf16[8,512,256], p.1: s32[]) -> bf16[4,512,256] {
  ROOT %dynamic-slice.1 = bf16[4,512,256]{2,1,0} dynamic-slice(%p.0, %p.1, %c, %c)
}

%product (p.2: bf16[64,512], p.3: bf16[2,4,512,256]) -> bf16[64,256] {
  ROOT %convolution.1 = bf16[64,256]{1,0} convolution(%p.2, %slice.1), dim_labels=bf_io->bf
}

ENTRY %main (w: bf16[2,4,512,256]) -> bf16[2,4,512,256] {
  %w = bf16[2,4,512,256]{3,2,1,0} parameter(0)
  %outside = bf16[2,4,512,256]{3,2,1,0} copy(%w)
  %tuple.4 = (s32[], bf16[2,4,512,256]{3,2,1,0}) tuple(%c, %outside)
  %while.1 = (s32[], bf16[2,4,512,256]{3,2,1,0}) while(%tuple.4), condition=%cond, body=%layers
  ROOT %out = bf16[2,4,512,256]{3,2,1,0} get-tuple-element(%while.1), index=1
}
"""


def test_the_helper_follows_a_weight_through_a_bitcast_and_into_the_steps():
    """``_weight_copies`` on a module written by hand: a copy of the stack
    behind its ``bitcast`` to groups, in the layers' loop, and a cut of one
    layer's groups in the loop nested in it are both found, with their
    bytes and op_names; a copy outside the loops, a cut under the least
    size and a product's own read of the leaf are not."""
    weights = {"bf16[2,4,512,256]", "bf16[8,512,256]"}
    found = _weight_copies(_PLANTED, weights, least=2 ** 20)
    assert sorted(found) == [
        ("copy.1", 2 * 8 * 512 * 256, "jit(f)/while/body/moe/reshape"),
        ("cut.1", 2 * 4 * 512 * 256,
         "jit(f)/while/body/moe/experts/dynamic_slice")]
    # unseeded with the groups' shape, the nested loop's cut goes unseen
    assert [name for name, _, _ in _weight_copies(
        _PLANTED, {"bf16[2,4,512,256]"}, least=2 ** 20)] == ["copy.1"]


def test_the_prefill_cells_layers_loop_copies_no_weight(topo, mosaic):
    """The prefill cell's served program, four layers at ``[1, 2048]`` and
    the published widths: the layers are one ``while`` with the dropless
    steps' ``while`` nested in it (``longcat_counts.expert_ops`` tells the
    mixture's operations by that), both kernels are in the text, and the
    loop writes no copy of a weight: each product reads its slice of the
    stacked leaf, the grouped product its groups. A scan over the stacked
    tree wrote 2.50 GB of such copies a layer (eleven of 32 MB or more) and
    held 1.35 GB of temporaries."""
    lowered, params = _first_token(topo, "longcat-flash-serve-prefill",
                                   batch=1, length=2048)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and "ragged-dot" in text
    bodies, entry = _computations(text)
    layers = _loops(bodies[entry])
    assert len(layers) == 1
    steps = _loops(bodies[layers[0]])
    assert len(steps) == 1 and any(
        "ragged-dot" in line for line in bodies[steps[0]])
    # the leaves of which one layer's slice is large enough to count (a norm's
    # weight rides the fusion that applies it)
    def named(*shape):
        return "bf16[" + ",".join(map(str, shape)) + "]"

    blocks = params["blocks"][transformer.SHORTCUT]
    stacked = {named(*p.shape) for p in jax.tree.leaves(blocks)
               if 2 * math.prod(p.shape[1:]) >= 32 * 2 ** 20}
    # and the experts' as the dropless loop is handed them: n x count groups
    stacked |= {named(p.shape[0] * p.shape[1], *p.shape[2:])
                for p in blocks["experts"].values()}
    assert any(f" {shape}" in line for shape in stacked
               for line in bodies[steps[0]])
    copies = _weight_copies(text, stacked)
    print(f"weight copies in the layers' loop: {len(copies)}, "
          f"{sum(size for _, size, _ in copies) / 1e9:.3f} GB a layer")
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


# -- the mixer-and-FFN kinds (LFM2): 64-wide heads, every expert held ------------


@pytest.mark.parametrize("length", [2048, 4096, 8192])
def test_flash_kernel_compiles_at_64_wide_grouped_heads(topo, mosaic, length):
    """32 query heads over 8 K/V heads, all 64 wide (half a lane row),
    forward only: the expert-load cell's calls at its three buckets."""
    from ray_tpu.ops import flash_attention
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, length, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, length, 8, 64), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(flash_attention).lower(q, kv, kv).compile()
    assert "flash_fwd" in compiled.as_text()


# what ``memory_analysis()`` reads of the served program at [1, 8192], as the
# configuration's ``reduced["serve.1"]["why"]`` states it (GB)
# (0.273 GB of temporaries while a step of the dropless loop searched for its
# rows; 0.270 since a layer call lists them once, PR 47: the list is two
# vectors of 32,768 elements)
LFM2_ARGUMENT_GB, LFM2_TEMP_GB = 10.356, 0.273


@pytest.mark.parametrize("length", [2048, 4096, 8192])
def test_the_expert_load_cells_forward_compiles_and_copies_no_weight(
        topo, mosaic, length):
    """The served program of ``lfm2-24b-serve-prefill`` at its published
    widths and its three shapes: both kernels are in the text; the runs of
    mixture layers are ``while`` loops with the dropless steps' loop nested
    in them, and no loop writes a copy of a weight (the helper above,
    seeded with this tree's leaf shapes, the experts' ``[n x 64, ...]``
    groups among them): each product reads its slice of the stacked leaf.
    At ``[1, 8192]`` the compiler's own account of the memory is what the
    configuration's file says, which bounds a copy made outside the loops
    too (one layer's experts are 0.6 GB)."""
    from benchmark import manifest
    lowered, params = _first_token(topo, "lfm2-24b-serve-prefill", batch=1,
                                   length=length)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and "ragged-dot" in text
    bodies, entry = _computations(text)
    runs = [body for body in _loops(bodies[entry])
            if any(any("ragged-dot" in line for line in bodies[steps])
                   for steps in _loops(bodies[body]))]
    assert len(runs) == 2           # the two runs of three conv_moe layers

    def named(*shape):
        return "bf16[" + ",".join(map(str, shape)) + "]"

    stacked = set()
    for blocks in params["blocks"].values():
        stacked |= {named(*p.shape) for p in jax.tree.leaves(blocks)
                    if 2 * math.prod(p.shape[1:]) >= 2 ** 20}
        stacked |= {named(p.shape[0] * p.shape[1], *p.shape[2:])
                    for p in blocks.get("experts", {}).values()}
    assert named(6 * 64, 2048, 1536) in stacked
    # the helper knows a weight by its shape: at 2,048 tokens the states
    # [1, L, d] have the one convolution layer's ``w_out``'s [1, d, d]
    stacked.discard(named(1, length, 2048))
    assert any(f" {shape}" in line for shape in stacked for run in runs
               for steps in _loops(bodies[run]) for line in bodies[steps])
    copies = _weight_copies(text, stacked, least=2 ** 20)
    print(f"weight copies in the layers' loops: {len(copies)}, "
          f"{sum(size for _, size, _ in copies) / 1e9:.3f} GB")
    assert copies == []
    # a step slices the list of its pairs and searches for nothing: the
    # loops that hold the grouped products, one a mixture layer's trace,
    # nest no loop and are handed no [64, T] count to gather from (the
    # parent's carried ``s32[64, T]`` and ran a ``searchsorted`` loop and
    # 13 gathers a step); the module's sorts are the router's top-k and
    # the compiler's own of a step's 1,024 scatter-add indices, as in the
    # parent: the call's pairs are placed by counting, no sort of them
    steps = _step_bodies(bodies)
    assert len(steps) == 4          # two runs' and the two attention layers'
    for body in steps:
        assert _loops(bodies[body]) == []
        assert not any(f"s32[{shape}]" in line for line in bodies[body]
                       for shape in (f"64,{length}", f"{length},64"))
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert sorts and all(
        re.search(r'op_name="[^"]*/(router/top_k|experts/while/body/'
                  r'scatter-add)"', line) for line in sorts)
    assert not any(f"[{4 * length}]" in line for line in sorts)
    memory = compiled.memory_analysis()
    print(f"[1, {length}]: arguments {memory.argument_size_in_bytes / 1e9:.3f}"
          f" GB, temporaries {memory.temp_size_in_bytes / 1e9:.3f} GB")
    assert memory.temp_size_in_bytes < 0.4e9
    if length == 8192:
        assert memory.argument_size_in_bytes / 1e9 == pytest.approx(
            LFM2_ARGUMENT_GB, abs=2e-3)
        assert memory.temp_size_in_bytes / 1e9 == pytest.approx(
            LFM2_TEMP_GB, abs=0.03)
        why = manifest.Manifest().cell("lfm2-24b-serve-prefill").config[
            "reduced"]["serve.1"]["why"]
        assert f"{LFM2_ARGUMENT_GB:.2f} GB" in why
        assert f"{LFM2_TEMP_GB:.2f} GB" in why


# -- the latent mixer among the mixer-and-FFN kinds (Kanana-2): the trained step ----


@pytest.mark.parametrize("batch,length,heads", [(1, 8192, 32), (4, 512, 32)])
def test_flash_backward_compiles_at_unequal_head_widths(topo, mosaic, batch,
                                                        length, heads):
    """q and k heads of 192 beside v heads of 128, forward and backward: the
    training cell's calls (32 heads at 8,192 tokens) and its comparison's (4
    x 512). dq and dk come at 192, dv at 128: v is not padded."""
    from ray_tpu.ops import flash_attention
    one_chip = SingleDeviceSharding(topo.devices[0])
    qk = jax.ShapeDtypeStruct((batch, length, heads, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((batch, length, heads, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile()
    text = compiled.as_text()
    for call in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert call in text
    widths = [leaf.shape[-1] for leaf in jax.tree.leaves(
        compiled.output_shardings and jax.eval_shape(
            jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v))]
    assert widths == [192, 192, 128]


# what ``memory_analysis()`` read of the cell's step at 1 x 8,192 tokens
# (arguments + temporaries + outputs - aliased, GB) when ISSUE 48's rule fixed
# the depth, as the configuration's ``reduced["train.1"]["why"]`` states it:
# the rule takes 5 mixture layers at 15.0 GB or less, else 4 (5 read 15.68)
KANANA2_STEP_GB = 13.60
# what it reads since the layers' checkpoint keeps the flash forward's output
# and log-sum-exp (PR 49): 0.34 GB of kept arrays over the five layers by the
# compiler's own peak (``peak_memory_in_bytes`` 11.65 -> 12.01), 0.62 GB by
# the temporaries it sets aside
KANANA2_STEP_KEPT_GB = 14.23


def _flash_calls(lines):
    """The flash kernels a computation calls itself, by the instructions'
    names (``%flash_fwd.31 = ... custom-call(``), sorted."""
    return sorted(m.group(1).split(".")[0]
                  for m in map(_ASSIGNED.match, lines)
                  if m and m.group(3) == "custom-call"
                  and m.group(1).startswith("flash_"))


def test_the_kanana2_cells_step_fits_and_writes_a_layers_gradient_once(
        topo, mosaic):
    """``kanana2-train-8k``'s step at its own sizes: the three flash kernels
    and the grouped products are in it, **the forward kernel once a layer**
    (each run's forward loop calls ``flash_fwd``, its backward loop
    ``flash_dq`` and ``flash_dkv`` and no ``flash_fwd``: the checkpoint
    keeps the first call's output and log-sum-exp); the compiler's account
    of its memory is what the rule that fixed the depth read and the kept
    arrays; and in the layers' backward
    loop a stacked float32 gradient is only ever written by a
    ``dynamic-update-slice`` of the layer's slice: no operation of a loop
    adds a whole stacked leaf to another (what a scan over the layers'
    indices with the tree closed over did at every step)."""
    from benchmark import manifest
    lowered = _lower_cell_step(topo, "kanana2-train-8k")
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    bodies, entry = _computations(text)
    # the dense first layer is a run of one, which the compiler unrolls
    # into the entry; the four mixture layers are a loop each way
    assert _flash_calls(bodies[entry]) == ["flash_dkv", "flash_dq",
                                           "flash_fwd"]
    assert sorted(_flash_calls(bodies[loop])
                  for loop in _loops(bodies[entry])) == [
        ["flash_dkv", "flash_dq"], ["flash_fwd"]]
    assert len(_flash_calls(text.splitlines())) == 6
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"kanana2-train-8k: arguments {mem.argument_size_in_bytes / 1e9:.3f}"
          f" + temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB = "
          f"{total / 1e9:.3f} GB")
    assert total <= 15.0e9
    assert total / 1e9 == pytest.approx(KANANA2_STEP_KEPT_GB, abs=0.15)
    cell = manifest.Manifest().cell("kanana2-train-8k")
    assert f"{KANANA2_STEP_GB:.2f} GB" in cell.config["reduced"]["train.1"][
        "why"]
    # the stacked float32 leaves of the mixture layers, large ones
    adapter = manifest.adapter(cell.config)
    cfg = adapter.program_config(
        adapter.dims(cell.config, cell.job, cell.chips), 8192,
        cell.deploy["model"])
    blocks = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg))["blocks"][transformer.LATENT_MOE]
    stacked = {"f32[" + ",".join(map(str, p.shape)) + "]"
               for p in jax.tree.leaves(blocks)
               if 4 * math.prod(p.shape[1:]) >= 2 ** 20}
    assert "f32[4,16,2048,768]" in stacked
    bodies, entry = _computations(text)
    inside, todo = [], _loops(bodies[entry])
    while todo:
        inside.append(todo.pop())
        todo += _loops(bodies[inside[-1]])
    written, other = set(), []
    for name in inside:
        for line in bodies[name]:
            m = _ASSIGNED.match(line)
            if not m or m.group(2).split("{")[0] not in stacked:
                continue
            result, shape, kind = m.groups()
            if kind in ("parameter", "get-tuple-element", "bitcast"):
                continue
            if "dynamic-update-slice" in result or kind == \
                    "dynamic-update-slice":
                written.add(shape.split("{")[0])
            else:
                other.append((name, result, kind))
    assert other == []
    assert written == stacked
