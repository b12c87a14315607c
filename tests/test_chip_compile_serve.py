"""Compiles for a described TPU v5e 2x2 host: the served forwards.

The serve forward at its batch buckets, and the served programs of the
long-document, prefill and expert-load cells at their published widths, with
what their layers' loops may not copy. See ``test_chip_compile.py``.
"""

import itertools
import math
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.decode_attention import KERNEL_NAME as ATTEND
from ray_tpu.ops.expert_stream import KERNEL_NAME as STREAM

from chip_programs import (CFG, KERNEL, SEQ, cell_dims, computations,
                           first_token, loops, param_shapes, step_bodies,
                           weight_copies)
from ray_tpu.models import transformer


@pytest.mark.parametrize("bucket", [2, 4, 8])
def test_serve_forward_compiles_at_bucket(on_chip, mosaic, bucket):
    params = param_shapes(on_chip, CFG)
    tokens = on_chip((bucket, SEQ), jnp.int32)
    text = jax.jit(lambda p, t: transformer.apply(p, t, CFG)).lower(
        params, tokens).compile().as_text()
    assert KERNEL in text


@pytest.mark.parametrize("length,calls", [(8192, ["flash_fwd"]),
                                          (16384, ["sparse_attn_fwd"])])
def test_mixed_stack_serve_forward_compiles(on_chip, mosaic, length, calls):
    """The long-document cell's forward at its published widths, one period
    of the stack (a sparse layer and three linear ones): up to ``dense_len``
    the sparse layer is the flash kernel, past it the selection."""
    cell, adapter, dims = cell_dims("minicpm-sala-serve-longdoc")
    dims = {**dims, "n_layers": 4, "mixer_types": dims["mixer_types"][:4],
            "layer_ids": dims["layer_ids"][:4]}
    cfg = adapter.program_config(dims, length, cell.deploy["model"])
    params = param_shapes(on_chip, cfg, cfg.dtype)
    tokens = on_chip((1, length), jnp.int32)
    compiled = jax.jit(lambda p, t: transformer.head(
        p, transformer.backbone(p, t, cfg)[:, -1:], cfg)).lower(
            params, tokens).compile()
    text = compiled.as_text()
    for call in calls + ["linear_attn_fwd"]:
        assert call in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15 * 10 ** 9)


def test_shortcut_layer_serve_forward_compiles(on_chip, mosaic):
    """The prefill cell's forward at its published widths, one layer of the
    four: both kernels are in it, the flash call and the ragged product the
    compiler makes of ``lax.ragged_dot``."""
    cell, adapter, dims = cell_dims("longcat-flash-serve-prefill")
    dims = {**dims, "n_layers": 1}
    cfg = adapter.program_config(dims, 2048, cell.deploy["model"])
    params = param_shapes(on_chip, cfg, cfg.dtype)
    tokens = on_chip((1, 2048), jnp.int32)
    text = jax.jit(lambda p, t: transformer.apply(p, t, cfg)).lower(
        params, tokens).compile().as_text()
    assert "flash_fwd" in text and "ragged-dot" in text


# -- the shortcut stack reads its weights where they lie ---------------------

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
    """``weight_copies`` on a module written by hand: a copy of the stack
    behind its ``bitcast`` to groups, in the layers' loop, and a cut of one
    layer's groups in the loop nested in it are both found, with their
    bytes and op_names; a copy outside the loops, a cut under the least
    size and a product's own read of the leaf are not."""
    weights = {"bf16[2,4,512,256]", "bf16[8,512,256]"}
    found = weight_copies(_PLANTED, weights, least=2 ** 20)
    assert sorted(found) == [
        ("copy.1", 2 * 8 * 512 * 256, "jit(f)/while/body/moe/reshape"),
        ("cut.1", 2 * 4 * 512 * 256,
         "jit(f)/while/body/moe/experts/dynamic_slice")]
    # unseeded with the groups' shape, the nested loop's cut goes unseen
    assert [name for name, _, _ in weight_copies(
        _PLANTED, {"bf16[2,4,512,256]"}, least=2 ** 20)] == ["copy.1"]


def test_the_prefill_cells_layers_loop_copies_no_weight(on_chip, mosaic):
    """The prefill cell's served program, four layers at ``[1, 2048]`` and
    the published widths: the layers are one ``while`` with the dropless
    steps' ``while`` nested in it (``longcat_counts.expert_ops`` tells the
    mixture's operations by that), both kernels are in the text, and the
    loop writes no copy of a weight: each product reads its slice of the
    stacked leaf, the grouped product its groups. A scan over the stacked
    tree wrote 2.50 GB of such copies a layer (eleven of 32 MB or more) and
    held 1.35 GB of temporaries."""
    lowered, params = first_token(on_chip, "longcat-flash-serve-prefill",
                                   batch=1, length=2048)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and "ragged-dot" in text
    bodies, entry = computations(text)
    layers = loops(bodies[entry])
    assert len(layers) == 1
    steps = loops(bodies[layers[0]])
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
    copies = weight_copies(text, stacked)
    print(f"weight copies in the layers' loop: {len(copies)}, "
          f"{sum(size for _, size, _ in copies) / 1e9:.3f} GB a layer")
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


# what ``memory_analysis()`` reads of the served program at [1, 8192] (GB).
# The arguments are what the configuration's ``reduced["serve.1"]["why"]``
# states; its 0.27 GB of temporaries are what the rule read when it fixed the
# depth (0.273 while a step of the dropless loop searched for its rows, 0.270
# since a layer call lists them once, PR 47). Since PR 54 a layer call keeps
# its weighed rows in a float32 list [32768, 2048] (268 MB) and the gather
# after the loop writes as much again before its sum: 0.788 (0.587 with the
# gathered rows summed token by token), which leaves the rule's 15.0 GB
# where it was (11.14 for 10.63).
LFM2_ARGUMENT_GB, LFM2_TEMP_GB, LFM2_RULE_TEMP_GB = 10.356, 0.788, 0.27


@pytest.mark.parametrize("length", [2048, 4096, 8192])
def test_the_expert_load_cells_forward_compiles_and_copies_no_weight(
        on_chip, mosaic, length):
    """The served program of ``lfm2-24b-serve-prefill`` at its published
    widths and its three shapes: both kernels are in the text; the runs of
    mixture layers are ``while`` loops with the dropless steps' loop nested
    in them, and no loop writes a copy of a weight (the helper above,
    seeded with this tree's leaf shapes, the experts' ``[n x 64, ...]``
    groups among them): each product reads its slice of the stacked leaf.
    At ``[1, 8192]`` the compiler's own account of the memory is what the
    configuration's file says, which bounds a copy made outside the loops
    too (one layer's experts are 0.6 GB)."""
    lowered, params = first_token(on_chip, "lfm2-24b-serve-prefill", batch=1,
                                   length=length)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and "ragged-dot" in text
    bodies, entry = computations(text)
    runs = [body for body in loops(bodies[entry])
            if any(any("ragged-dot" in line for line in bodies[steps])
                   for steps in loops(bodies[body]))]
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
               for steps in loops(bodies[run]) for line in bodies[steps])
    copies = weight_copies(text, stacked, least=2 ** 20)
    print(f"weight copies in the layers' loops: {len(copies)}, "
          f"{sum(size for _, size, _ in copies) / 1e9:.3f} GB")
    assert copies == []
    # a step slices the list of its pairs and searches for nothing: the
    # loops that hold the grouped products, one a mixture layer's trace,
    # nest no loop and are handed no [64, T] count to gather from (until PR
    # 47 they carried ``s32[64, T]`` and ran a ``searchsorted`` loop and 13
    # gathers a step); the module's sorts are the router's top-k alone: the
    # call's pairs are placed by counting, no sort of them, and since PR 54
    # a device that holds all 64 experts scatter-adds nothing (the
    # compiler sorted a step's 1,024 indices for it): a step writes its
    # weighed rows into the float32 list the loop carries, in place, in
    # the fusion that weighs them, and one gather a layer call reads it
    steps = step_bodies(bodies)
    assert len(steps) == 4          # two runs' and the two attention layers'
    for body in steps:
        assert loops(bodies[body]) == []
        assert not any(f"s32[{shape}]" in line for line in bodies[body]
                       for shape in (f"64,{length}", f"{length},64"))
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert sorts and all(
        re.search(r'op_name="[^"]*/router/top_k"', line) for line in sorts)
    assert not any(f"[{4 * length}]" in line for line in sorts)
    assert "scatter-add" not in text
    listed = f"f32[{4 * length},2048]"
    writes = [line for body in steps for line in bodies[body]
              if " fusion(" in line and "dynamic-update-slice" in line
              and f"= {listed}" in line]
    assert len(writes) == 4         # one a step's trace, with the weighing
    assert all("multiply" in line.split(" = ")[0] for line in writes)
    gathers = [line for line in text.splitlines() if " fusion(" in line
               and f"= {listed}" in line and "experts/gather" in line]
    assert len(gathers) == 4        # one a mixture layer's trace
    memory = compiled.memory_analysis()
    print(f"[1, {length}]: arguments {memory.argument_size_in_bytes / 1e9:.3f}"
          f" GB, temporaries {memory.temp_size_in_bytes / 1e9:.3f} GB")
    assert memory.temp_size_in_bytes < 0.9e9
    if length == 8192:
        assert memory.argument_size_in_bytes / 1e9 == pytest.approx(
            LFM2_ARGUMENT_GB, abs=2e-3)
        assert memory.temp_size_in_bytes / 1e9 == pytest.approx(
            LFM2_TEMP_GB, abs=0.03)
        why = cell_dims("lfm2-24b-serve-prefill")[0].config[
            "reduced"]["serve.1"]["why"]
        assert f"{LFM2_ARGUMENT_GB:.2f} GB" in why
        assert f"{LFM2_RULE_TEMP_GB:.2f} GB" in why


# -- the generating cell: a decode step and a prefill beside the slots' state ------


def _generating(on_chip, name):
    """A generating cell's three programs as
    ``models.generation.TransformerGenerator`` jits them, at the cell's own
    sizes: the decode step, the longest prefill and its insert."""
    cell, adapter, dims = cell_dims(name)
    opts = cell.deploy["deployment"]
    slots, cache = int(opts["slots"]), int(opts["cache_len"])
    longest = max(opts["length_buckets"])
    cfg = adapter.program_config(dims, cache, cell.deploy.get("model", {}))
    params = param_shapes(on_chip, cfg, cfg.dtype)

    def shaped(tree):
        return jax.tree.map(lambda a: on_chip(a.shape, a.dtype), tree)

    state = shaped(jax.eval_shape(
        lambda: transformer.init_decode_state(cfg, slots, cache)))
    piece = shaped(jax.eval_shape(
        lambda: transformer.init_decode_state(cfg, 1, longest)))

    def decode_step(params, tokens, state, active):
        logits, state, loads = transformer.decode_step(params, tokens, state,
                                                       cfg, active)
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                jnp.max(logits, -1), state, loads)

    def prefill(params, prompt, length):
        last, piece, loads = transformer.prefill(params, prompt, length, cfg)
        logits = transformer.head(params, last[:, None], cfg)[:, 0]
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                jnp.max(logits, -1), piece, loads)

    def insert(state, tokens, piece, token, slot):
        return (transformer.insert_state(state, piece, slot),
                jax.lax.dynamic_update_slice(tokens, token, (slot,)))

    return {
        "slots": slots, "cell": cell,
        "bytes": {"params": _tree_bytes(params), "state": _tree_bytes(state)},
        "decode_step": jax.jit(decode_step, donate_argnums=(2,)).lower(
            params, on_chip((slots,), jnp.int32), state,
            on_chip((slots,), jnp.bool_)).compile(),
        "prefill": jax.jit(prefill).lower(
            params, on_chip((1, longest), jnp.int32),
            on_chip((1,), jnp.int32)).compile(),
        "insert": jax.jit(insert, donate_argnums=(0,)).lower(
            state, on_chip((slots,), jnp.int32), piece,
            on_chip((1,), jnp.int32), on_chip((), jnp.int32)).compile()}


@pytest.fixture(scope="module")
def generating(on_chip, mosaic):
    """``granite4h-serve-chat``'s programs (64 slots of 1,408 positions, the
    whole model), compiled once for the module."""
    return _generating(on_chip, "granite4h-serve-chat")


@pytest.fixture(scope="module")
def generating_kv(on_chip, mosaic):
    """``smallthinker-serve-mixed``'s programs (48 slots of two caches,
    13,312 rows and a ring of 4,096; 8 layers of 64 experts), compiled once
    for the module."""
    return _generating(on_chip, "smallthinker-serve-mixed")


def _tree_bytes(tree):
    return sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


def _held_gb(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) / 1e9


def _reads_its_stacks_where_they_lie(calls, step, runs):
    """The compiled step's ``decode_attn`` calls, ``runs[stack]`` of them on
    each stack's shape (one a run of attention layers): each takes K's and
    V's whole stack of its run (no layer cut out of one), under ``attn`` and
    ``core``, and nothing in the step copies a stack or holds a slot's
    scores over every row."""
    assert len(calls) == sum(runs.values())
    for stack, n in runs.items():
        assert sum(line.count(stack) == 2 for line in calls) == n
    assert all("/attn/" in line and "/core/" in line for line in calls)
    rows = "|".join({stack.split(",")[2] for stack in runs})
    for line in step.splitlines():
        if " copy(" in line or " dynamic-slice(" in line:
            assert not any(stack in line for stack in runs), line[:200]
        assert not re.search(rf"= f32\[\d+,\d+,({rows})\]", line), line[:200]


def test_a_decode_step_updates_the_slots_state_in_place(generating):
    """The rule the issue fixed for 64 slots: the step with the weights and
    the state reads 15.0 GB or less. The state (5.63 GB) is aliased, not
    copied: under 1 GB of temporaries (a layer's 134 MB of float32 state is
    read where it is used and written back where it was read; the K/V cache
    lies a row a position and is neither transposed for the two products nor
    for the token's write)."""
    compiled = generating["decode_step"]
    m = compiled.memory_analysis()
    held = generating["bytes"]
    assert held["params"] == pytest.approx(6.383e9, rel=1e-3)
    assert held["state"] == pytest.approx(5.630e9, rel=1e-3)
    assert m.alias_size_in_bytes >= held["state"]
    assert m.temp_size_in_bytes < 1e9
    assert _held_gb(compiled) <= 15.0
    print(f"decode step at {generating['slots']} slots: "
          f"{_held_gb(compiled):.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    # what the configuration's file says the rule read
    read = generating["cell"].config["reduced"]["generate.1"]["slots_read"]
    assert f"{_held_gb(compiled):.2f} GB" in read


def test_a_decode_step_steps_the_state_in_one_call_on_the_stack(generating):
    """The recurrence of a Mamba layer in the compiled step is the Mosaic
    call ``ssd_step``, one for each run of Mamba layers' loop, handed the
    whole stack of states: the stack is still aliased, the temporaries are
    what they were, and no layer's ``[.., 64, 64, 128]`` float32 state is
    copied out of the stack or into it."""
    from ray_tpu.ops.ssd import STEP_KERNEL_NAME
    compiled = generating["decode_step"]
    lines = compiled.as_text().splitlines()
    calls = [line for line in lines
             if " custom-call(" in line and KERNEL in line
             and re.search(rf"\s%?{STEP_KERNEL_NAME}[.\d]* = ", line)]
    kinds = cell_dims("granite4h-serve-chat")[2]["layer_types"]
    runs = [kind for kind, _ in itertools.groupby(kinds)]
    assert len(calls) == runs.count("mamba") == 5
    stack = f"f32[36,{generating['slots']},64,64,128]"
    assert all(stack in line and "output_to_operand_aliasing" in line
               for line in calls)
    assert all("/mamba/core/" in line for line in calls)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= generating["bytes"]["state"]
    assert m.temp_size_in_bytes < 1e9
    copies = [line.strip()[:160] for line in lines
              if " copy(" in line and re.search(r"f32\[[\d,]*64,64,128\]",
                                                line)]
    assert copies == []
    # the sum over the state is the call's: no fusion beside it reads a
    # layer of the stack
    assert not any("add_dynamic-update-slice_fusion" in line
                   and stack in line for line in lines)
    # and an attention layer's query walks its slots' rows of the K/V stack
    # in one call on the stack itself, one a run of attention layers
    attend = [line for line in lines
              if " custom-call(" in line and KERNEL in line
              and re.search(rf"\s%?{ATTEND}[.\d]* = ", line)]
    _reads_its_stacks_where_they_lie(
        attend, compiled.as_text(),
        {f"bf16[4,{generating['slots']},1408,512]":
         len(runs) - runs.count("mamba")})
    assert len(attend) == 4


def test_the_longest_prefill_fits_beside_the_resident_state(generating):
    """``[1, 1024]`` through 40 layers with the scan's kernel and the flash
    kernel in it: its own temporaries and what it hands the insert, beside
    the weights and the slots' state that stay resident, 15.0 GB or less; the
    insert is in place too."""
    compiled = generating["prefill"]
    text = compiled.as_text()
    assert "ssd_fwd" in text and "flash_fwd" in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.5e9
    beside = _held_gb(compiled) + generating["bytes"]["state"] / 1e9
    assert beside <= 15.0
    insert = generating["insert"].memory_analysis()
    assert insert.alias_size_in_bytes >= generating["bytes"]["state"]
    assert insert.temp_size_in_bytes < 2 ** 20
    print(f"prefill [1, 1024] beside the state: {beside:.3f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB")
    read = generating["cell"].config["reduced"]["generate.1"]["slots_read"]
    assert f"{beside:.2f} GB" in read


# -- the second generating cell: two caches of different length and a mixture -------


def test_the_slots_rule_of_the_two_caches_holds_at_48_slots(generating_kv):
    """``smallthinker-serve-mixed``'s rule: the decode step with the weights
    (7.93 GB) and the state (5.03 GB: 48 slots of 2 x 13,312 + 6 x 4,096
    rows), the state aliased with the one it returns, and the [1, 12288]
    prefill beside the resident state, each 15.0 GB or less; the
    configuration's file holds the prefill's number as read here and the
    step's as PR 55 read it (13.00 GB with the dropless loop's lists and
    gathers among its temporaries), which the step may not pass: since PR 56
    a layer's mixture is one call whose buffers are VMEM."""
    g = generating_kv
    assert g["slots"] == 48
    assert g["bytes"]["params"] == pytest.approx(7.934e9, rel=1e-3)
    assert g["bytes"]["state"] == pytest.approx(5.033e9, rel=1e-3)
    step, prefill = g["decode_step"], g["prefill"]
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= g["bytes"]["state"]
    assert m.temp_size_in_bytes < 0.5e9
    assert _held_gb(step) <= 15.0
    beside = _held_gb(prefill) + g["bytes"]["state"] / 1e9
    assert beside <= 15.0
    insert = g["insert"].memory_analysis()
    assert insert.alias_size_in_bytes >= g["bytes"]["state"]
    print(f"decode step at 48 slots: {_held_gb(step):.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB; prefill [1, 12288] beside "
          f"the state: {beside:.3f} GB, temporaries "
          f"{prefill.memory_analysis().temp_size_in_bytes / 1e9:.3f} GB")
    read = g["cell"].config["reduced"]["generate_kv.1"]["slots_read"]
    assert "of temporaries = 13.00 GB" in read and _held_gb(step) <= 13.00
    assert f"{beside:.2f} GB" in read


def test_the_windows_prefill_runs_the_kernel_and_the_step_writes_in_place(
        generating_kv):
    """The compiled prefill holds the flash kernel (with its window in six
    layers of eight) and the grouped products' Mosaic calls, and no streamed
    mixture (12,288 tokens: the dropless loop); the compiled step holds no
    kernel for its attention (a masked product over the rows a stack has),
    the streamed mixture once a run of layers (48 rows: ``expert._streams``)
    with no grouped product and no list (the only scatters left write the
    caches' rows), and both caches are its own outputs."""
    prefill = generating_kv["prefill"].as_text()
    step = generating_kv["decode_step"].as_text()
    def calls(text, name):      # Mosaic calls by their own name
        return [line for line in text.splitlines()
                if " custom-call(" in line and KERNEL in line
                and re.search(rf"\s%?{name}[.\d]* = ", line)]

    # one compiled body a run of layers: global, window, global, window
    assert len(calls(prefill, "flash_fwd")) == 4
    assert "ragged-dot" in prefill and not calls(prefill, STREAM)
    assert not calls(step, "flash_fwd") and "ragged-dot" not in step
    assert len(calls(step, STREAM)) == 4
    # each reads its layer's experts off the stacked leaves, uncopied
    for line in calls(step, STREAM):
        assert line.count("bf16[2,64,2560,768]") + line.count(
            "bf16[6,64,2560,768]") == 2
    assert not re.search(r"= \S+ (scatter|gather)\([^\n]*\[288", step)
    assert "f32[288,2560]" not in step
    stacks = ("bf16[2,48,13312,512]", "bf16[6,48,4096,512]")
    for stack in stacks:
        assert stack in step
    # global, window, global, window
    _reads_its_stacks_where_they_lie(calls(step, ATTEND), step,
                                     dict.fromkeys(stacks, 2))


# -- the third generating cell: a shared cache, rings and Mamba-1 states ------------


@pytest.fixture(scope="module")
def generating_sambay(on_chip, mosaic):
    """``phi4flash-serve-reason``'s programs (96 slots of nine states, eight
    rings of 512 and one full cache of 3,072 rows; the whole model),
    compiled once for the module."""
    return _generating(on_chip, "phi4flash-serve-reason")


def test_the_whole_sambay_model_and_96_slots_fit_the_chip(generating_sambay):
    """7.70 GB of weights and 3.83 GB of state (what ``phi4flash_counts``
    counts, to the byte), the state aliased with the one the step returns,
    and the [1, 1024] prefill beside the resident state, each 15.0 GB or
    less."""
    from benchmark import phi4flash_counts
    g = generating_sambay
    dims = cell_dims("phi4flash-serve-reason")[2]
    assert g["slots"] == 96
    assert g["bytes"]["params"] == 2 * phi4flash_counts.param_count(dims)
    assert g["bytes"]["state"] - 96 * 4 == sum(
        phi4flash_counts.state_bytes(96, 3072, dims).values())
    step, prefill = g["decode_step"], g["prefill"]
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= g["bytes"]["state"] - 96 * 4
    assert m.temp_size_in_bytes < 0.2e9
    assert 11.4 <= _held_gb(step) <= 11.7
    beside = _held_gb(prefill) + g["bytes"]["state"] / 1e9
    assert beside <= 15.0
    assert prefill.memory_analysis().temp_size_in_bytes < 0.5e9
    insert = g["insert"].memory_analysis()
    assert insert.alias_size_in_bytes >= g["bytes"]["state"] - 96 * 4
    # what a step must move is what the compiled step holds, and the rows
    # its eight readers see of the one cache: at every slot full, the
    # weights, the states in and out and 96 x (8 x 512 + 8 x 3,072) rows
    full = phi4flash_counts.decode_step_bytes(
        96, 96 * phi4flash_counts.slot_rows(3072, dims), dims)
    assert full == pytest.approx(
        g["bytes"]["params"] + 0.62e9 + 96 * 28672 * 5120, rel=2e-3)
    print(f"decode step at 96 slots: {_held_gb(step):.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB; prefill [1, 1024] beside "
          f"the state: {beside:.3f} GB")


def test_the_sambay_step_reads_one_cache_from_eight_layers(
        generating_sambay):
    """32 layers are three loops (8 x scan and window, scan and full, 7 x
    GMU and cross), each with one ``decode_attn`` call on the stack itself:
    the rings', and two on **the one** full cache, which no layer copies; a
    scan layer's float32 states are read from the stack and written back
    where they lie (plain ``jax.numpy``, no Mosaic call). The prefill holds
    the flash kernel once (the window layers' loop) and ``decode_attn`` over
    the prompt's rows for the full layer's and the cross layers' one
    query."""
    step = generating_sambay["decode_step"].as_text()
    prefill = generating_sambay["prefill"].as_text()

    def calls(text, name):      # Mosaic calls by their own name
        return [line for line in text.splitlines()
                if " custom-call(" in line and KERNEL in line
                and re.search(rf"\s%?{name}[.\d]* = ", line)]

    ring, cache, states = ("bf16[8,96,512,1280]", "bf16[1,96,3072,1280]",
                           "f32[9,96,16,5120]")
    attend = calls(step, ATTEND)
    assert len(attend) == 3
    assert sum(line.count(ring) == 2 for line in attend) == 1
    assert sum(line.count(cache) == 2 for line in attend) == 2
    assert all("/attn/" in line and "/core/" in line for line in attend)
    assert sum("/swa/" in l for l in attend) == 1 == sum(
        "/global/" in l for l in attend) == sum("/cross/" in l
                                                for l in attend)
    for line in step.splitlines():
        if " copy(" in line or " dynamic-slice(" in line:
            assert not any(s in line for s in (ring, cache)), line[:200]
        # the plain scan step reads a layer's states out of the stack inside
        # its fusion and writes them back where they lie: no copy of the stack
        assert not (" copy(" in line and states in line), line[:200]
    from ray_tpu.ops.selective_scan import KERNEL_NAME
    assert not calls(step, "flash_fwd")
    assert len(calls(prefill, "flash_fwd")) == 1
    assert len(calls(prefill, KERNEL_NAME)) == 2
    assert len(calls(prefill, ATTEND)) == 2
    assert "bf16[1,1,1024,1280]" in prefill
