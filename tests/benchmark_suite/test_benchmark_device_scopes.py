"""The reader of the device's scopes (``benchmark/device_scopes.py``): its
decoder of the trace's wire format against the two traces recorded on the
chip (PR 25, PR 26) and against ``trace_reduce.load``'s reading of the same
events, the classes and shares on a plane this file encodes itself, the
nine metric files, and the search for a run's trace."""

import json
import os
import shutil
import struct
import subprocess
import sys
import tempfile

import pytest

import benchmark_tiny
from benchmark import device_scopes as ds, manifest, reducers, trace_reduce
from benchmark.trace_reduce import Reduced

REPO = benchmark_tiny.REPO
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
TRAIN = os.path.join(FIXTURES, "train_steps.xplane.pb")
STEADY = os.path.join(FIXTURES, "serve_steady.xplane.pb")
SCOPES = frozenset({"embed", "attn", "core", "mlp", "moe", "router",
                    "experts", "head", "optimizer"})
METRICS = {
    "attn_share_pct.train": "attn", "mlp_share_pct.train": "mlp",
    "head_share_pct.train": "head",
    "optimizer_share_pct.train": "optimizer",
    "unscoped_share_pct.train": "unscoped",
    "attn_share_pct.offline": "attn", "mlp_share_pct.offline": "mlp",
    "head_share_pct.offline": "head",
    "unscoped_share_pct.offline": "unscoped"}


def _ctx(reduced):
    return reducers.Context(cell=type("C", (), {"name": "cell"})(),
                            trace=reduced, counters={},
                            device_kind="TPU v5 lite")


# -- the traces recorded on the chip ----------------------------------------


def _events_of(path):
    return trace_reduce.load(path, "bench.", "window")


@pytest.mark.parametrize("path,events,program", [
    (TRAIN, 3248, "jit(step)"), (STEADY, 21456, "jit(first_token)")])
def test_every_operation_is_joined_to_its_record(path, events, program):
    window, ops = ds.read_trace(path)
    reduced = _events_of(path)
    assert window == tuple(reduced.window)
    assert len(ops) == events == len(reduced.first.ops)
    assert ds.read_ops(path) is ops
    # joined by metadata_id: every event has its record, with the HLO text
    # that ProfileData gives as the event's name
    assert all(op.record.name for op in ops)
    assert sorted((op.start, op.end, op.record.name.split(" = ")[0]
                   .lstrip("%")) for op in ops) == sorted(
        (e.start, e.end, e.name) for e in reduced.first.ops)
    named = [op for op in ops if op.record.tf_op]
    assert named and all(op.record.tf_op.startswith(program)
                         for op in named)
    # where the trace gives the Python that made an operation, most of it
    # is the model's
    sources = [op.record.source for op in named if op.record.source]
    assert sum(s.startswith("/root/repo/ray_tpu/models/transformer.py:")
               for s in sources) > len(sources) // 2
    assert {op.record.category for op in ops} >= {
        "convolution fusion", "loop fusion", "custom-call"}


def test_the_training_traces_leaves_and_their_unnamed_share():
    window, ops = ds.read_trace(TRAIN)
    whole = (min(op.start for op in ops), max(op.end for op in ops))
    mine = ds.window_leaves(ops, whole)
    theirs = trace_reduce.leaves(_events_of(TRAIN).first.ops)
    # the same events as the reduction's own leaves, to the nanosecond
    assert sorted((op.start, op.end) for op in mine) == sorted(
        (e.start, e.end) for e in theirs)
    # 8 ``while`` containers of 773.4 ms with no tf_op; 3,240 other events,
    # of which 56 have no duration
    held = set(mine)
    containers = [op for op in ops if op.end > op.start and op not in held]
    assert len(containers) == 8 and len(ops) - len(containers) == 3240
    assert all(c.record.tf_op == "" and " while(" in c.record.name
               for c in containers)
    assert sum(c.seconds for c in containers) == pytest.approx(0.7734,
                                                               abs=1e-4)
    total = sum(op.seconds for op in mine)
    assert len(mine) == 3184 and total == pytest.approx(1.0132, abs=1e-4)
    unnamed = sum(op.seconds for op in mine if not op.record.tf_op)
    assert 100 * unnamed / total == pytest.approx(2.72, abs=0.01)


def test_the_old_steps_add_is_the_optimizers_update_by_its_bytes():
    """``jit(step)/add`` of the program from before the scopes: 23 ms a
    step that reads and writes 22 bytes a parameter."""
    _, ops = ds.read_trace(TRAIN)
    adds = [op for op in ops if op.record.tf_op == "jit(step)/add:"]
    steps = 4
    assert sum(op.seconds for op in adds) / steps == pytest.approx(
        0.0232, abs=2e-4)
    per_step = sum(op.record.bytes_accessed for op in adds) / steps
    assert per_step == pytest.approx(15.7e9, rel=0.01)
    assert per_step / 704.7e6 == pytest.approx(22, abs=0.5)


@pytest.mark.parametrize("path", [TRAIN, STEADY])
def test_a_program_from_before_the_scopes_reads_nothing(path, tmp_path,
                                                        monkeypatch):
    """The traces name no scope: every share is left out, as it is on the
    parent commit's program, and the table still prints."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    held = tmp_path / "bench_trace_x" / "plugins" / "profile" / "t"
    held.mkdir(parents=True)
    shutil.copy(path, held)
    reduced = _events_of(path)
    ctx = _ctx(reduced)
    for scope in ("attn", "unscoped"):
        assert ds.scope_share_pct(ctx, {"scope": scope}) is None
    # this checkout declares scopes: such a trace is a stale compile cache's
    assert ctx.notes == [ds.STALE_NOTE]
    monkeypatch.setattr(ds, "program_scopes", lambda: frozenset())
    ctx = _ctx(reduced)
    assert ds.scope_share_pct(ctx, {"scope": "attn"}) is None
    assert ctx.notes == []
    text = ds.table(ds.window_leaves(ds.read_ops(path), reduced.window),
                    SCOPES)
    assert "tf_op names no scope" in text and "pallas_call" in text


def test_the_module_prints_the_table_of_a_file():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.device_scopes", TRAIN], cwd=REPO,
        capture_output=True, text=True, timeout=120, check=True).stdout
    assert out.startswith(ds.NOTE_HEAD + " (3184 leaf operations, 1.013")
    assert "no tf_op" in out and " 2.72" in out
    assert "largest unscoped operations" in out


# -- a plane this file encodes ----------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One field on the wire: an int as a varint, a float as a double,
    bytes or text length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(data)) + data


def _entry(key, message):
    return _field(1, key) + _field(2, message)


STAT_NAMES = {1: "tf_op", 2: "hlo_category", 3: "flops",
              4: "bytes_accessed", 5: "source", 6: "convolution fusion",
              7: "occupancy"}
US = 1000 * 1000      # picoseconds in a microsecond


def _metadata(id_, name, tf_op="", category=None, flops=0, bytes_=0,
              source=""):
    """An ``XEventMetadata``: strings as ``str_value``, counts as
    ``uint64_value``, the category as a ``ref_value`` where it is one of
    the plane's stat names, one ``double_value`` nobody asks for."""
    stats = [_field(1, 7) + _field(2, 0.5)]
    if tf_op:
        stats.append(_field(1, 1) + _field(5, tf_op))
    if category == "convolution fusion":
        stats.append(_field(1, 2) + _field(7, 6))
    elif category:
        stats.append(_field(1, 2) + _field(5, category))
    if flops:
        stats.append(_field(1, 3) + _field(3, flops))
    if bytes_:
        stats.append(_field(1, 4) + _field(3, bytes_))
    if source:
        stats.append(_field(1, 5) + _field(5, source))
    return (_field(1, id_) + _field(2, name)
            + b"".join(_field(5, s) for s in stats))


def _event(metadata_id, start_us, end_us):
    """From ``start_us`` to ``end_us`` after ``T0_NS``."""
    return (_field(1, metadata_id) + _field(2, (LEAD_US + start_us) * US)
            + _field(3, (end_us - start_us) * US))


def _plane(name, lines, metadata, stat_names=STAT_NAMES):
    body = _field(2, name)
    for line_name, timestamp_ns, events in lines:
        body += _field(3, _field(2, line_name) + _field(3, timestamp_ns)
                       + b"".join(_field(4, e) for e in events))
    for id_, record in metadata.items():
        body += _field(4, _entry(id_, record))
    for id_, stat in stat_names.items():
        body += _field(5, _entry(id_, _field(1, id_) + _field(2, stat)))
    return body


# the lines' timestamp, and how long after it the step starts
BASE_NS, LEAD_US = 5_000_000, 300
T0_NS = BASE_NS + LEAD_US * 1000
# a step of 1,000 us inside a ``while``: attention 100 + 300 of it in the
# kernel, the MLP 400 (forward 150, backward 250), a copy the compiler
# made, a slice that names no scope, 50 idle; one operation before the
# window and a second device that would read otherwise
RECORDS = {
    1: _metadata(1, "%while.1 = (s32[]) while(%tuple.1)"),
    2: _metadata(2, "%fusion.1 = bf16[8,64] fusion(%p0)",
                 "jit(step)/jvp()/while/body/closed_call/attn/"
                 "bld,dhk->blhk/dot_general:", "convolution fusion",
                 flops=2_000_000, bytes_=4_000, source="transformer.py:451"),
    3: _metadata(3, "%flash_fwd.1 = bf16[8,64] custom-call(%fusion.1), "
                 "custom_call_target=\"tpu_custom_call\"",
                 "jit(step)/jvp()/while/body/closed_call/attn/core/"
                 "flash_fwd/pallas_call:", "custom-call"),
    4: _metadata(4, "%fusion.2 = bf16[8,96] fusion(%p1)",
                 "jit(step)/jvp()/while/body/closed_call/mlp/"
                 "bld,df->blf/dot_general:", "convolution fusion",
                 flops=3_000_000, bytes_=6_000),
    5: _metadata(5, "%fusion.3 = bf16[8,96] fusion(%p2)",
                 "jit(step)/transpose(jvp())/while/body/closed_call/"
                 "checkpoint/mlp/bld,df->blf/dot_general:",
                 "convolution fusion", flops=5_000_000, bytes_=10_000),
    6: _metadata(6, "%copy.1 = bf16[8,96] copy(%p3)", category="copy",
                 bytes_=3_072),
    7: _metadata(7, "%dynamic-slice.1 = f32[8] dynamic-slice(%p4)",
                 "jit(step)/jvp()/while/body/dynamic_slice:", "loop fusion",
                 bytes_=64, source="transformer.py:622"),
}
STEP = [_event(1, 0, 1000), _event(2, 0, 100), _event(3, 100, 400),
        _event(4, 400, 550), _event(5, 550, 800), _event(6, 800, 900),
        _event(7, 900, 950)]


def _space(window_us=(0, 1000)):
    host = _plane(
        "/host:CPU",
        [("python", BASE_NS, [_event(9, -50, 0)]),
         ("main", BASE_NS, [_event(8, *window_us), _event(8, 2000, 3000)])],
        {8: _field(1, 8) + _field(2, "bench.window"),
         9: _field(1, 9) + _field(2, "bench.warmup")}, {})
    other = _plane("/device:TPU:1",
                   [("XLA Ops", BASE_NS, [_event(6, 0, 1000)])], RECORDS)
    first = _plane(
        "/device:TPU:0",
        [("XLA Modules", BASE_NS, [_event(1, 0, 1000)]),
         ("XLA Ops", BASE_NS, [_event(4, -300, -100)] + STEP)], RECORDS)
    return b"".join(_field(1, p) for p in (host, other, first))


def _written(root, space, name="bench_trace_a"):
    held = root / name / "plugins" / "profile" / "t"
    held.mkdir(parents=True)
    path = held / "host.xplane.pb"
    path.write_bytes(space)
    return str(path)


def test_the_encoded_plane_is_one_the_profilers_reader_takes(tmp_path):
    path = _written(tmp_path, _space(window_us=(-50, 1000)))
    reduced = _events_of(path)
    window, ops = ds.read_trace(path)
    assert window == tuple(reduced.window) == (
        T0_NS - 50_000, T0_NS + 1_000_000)
    assert sorted((op.start, op.end) for op in ops) == sorted(
        (e.start, e.end) for e in reduced.devices[0].ops)
    assert len(ops) == 8 and sorted(reduced.devices) == [0, 1]
    kernel = next(op.record for op in ops if "flash_fwd" in op.record.name)
    assert kernel.tf_op.endswith("attn/core/flash_fwd/pallas_call:")
    assert (kernel.category, kernel.flops) == ("custom-call", 0)
    matmul = next(op.record for op in ops if op.record.name.startswith(
        "%fusion.1 "))
    # a ref_value is the stat name it points at
    assert matmul.category == "convolution fusion"
    assert (matmul.flops, matmul.bytes_accessed, matmul.source) == (
        2_000_000, 4_000, "transformer.py:451")


@pytest.mark.parametrize("tf_op,path", [
    ("jit(step)/jvp()/while/body/closed_call/attn/core/flash_fwd/"
     "pallas_call:", ("attn", "core")),
    ("jit(step)/transpose(jvp(head))/head/mul:", ("head", "head")),
    ("jit(step)/jvp(embed)/gather:", ("embed",)),
    ("jit(first_token)/while/body/closed_call/moe/router/td,de->te/"
     "dot_general:", ("moe", "router")),
    ("jit(step)/optimizer/add:", ("optimizer",)),
    # a scope is a whole token: an einsum or a primitive that holds its
    # letters names none
    ("jit(step)/jvp()/while/body/header/attnx/core_of/dot_general:", ()),
    ("jit(step)/add:", ()),
    ("", ()),
])
def test_an_operations_class_is_its_paths_first_scope(tf_op, path):
    assert ds.scope_path(tf_op, SCOPES) == path
    assert ds.scope_of(tf_op, SCOPES) == (path[0] if path else None)
    # a program that declares no scope has none
    assert ds.scope_of(tf_op, frozenset()) is None


def test_the_classes_of_the_encoded_step(tmp_path):
    _, ops = ds.read_trace(_written(tmp_path, _space()))
    mine = ds.window_leaves(ops, (T0_NS, T0_NS + 1_000_000))
    assert len(mine) == 6          # not the container, not the early one
    rows = ds.by_class(mine, SCOPES)
    us = {key: round(row.seconds * 1e6) for key, row in rows.items()}
    assert us == {("attn",): 400, ("attn", "core"): 300, ("mlp",): 400,
                  ("unscoped",): 150, ("unscoped", ds.NO_TF_OP): 100,
                  ("unscoped", ds.NO_SCOPE): 50}
    assert rows[("mlp",)].flops == 8_000_000
    assert rows[("attn",)].bytes_accessed == 4_000
    text = ds.table(mine, SCOPES).splitlines()
    assert text[0] == ds.NOTE_HEAD + " (6 leaf operations, 0.000950 s):"
    labels = [line.split()[0] for line in text[2:9]]
    assert labels == ["attn", "core", "mlp", "unscoped", "no", "tf_op", "the"]
    # the MLP: 8 MFLOP in 400 us is 0.02 TFLOP/s, 16 kB is 0.04 GB/s
    mlp = next(line for line in text if line.split()[0] == "mlp")
    assert mlp.split() == ["mlp", "2", "0.000400", "42.11", "0.0", "0.0"]
    assert "%copy.1 (copy) tf_op=- source=-" in text[-2]
    assert "%dynamic-slice.1 (loop fusion) tf_op=jit(step)/jvp()/while/" \
        "body/dynamic_slice: source=transformer.py:622" in text[-1]


def test_the_shares_of_a_run_sum_to_a_hundred(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(ds, "program_scopes", lambda: SCOPES)
    _written(tmp_path, _space(window_us=(2000, 3000)), "bench_trace_other")
    path = _written(tmp_path, _space())
    reduced = _events_of(path)
    ctx = _ctx(reduced)
    got = {scope: ds.scope_share_pct(ctx, {"scope": scope})
           for scope in sorted(SCOPES | {"unscoped"})}
    assert got["attn"] == pytest.approx(100 * 400 / 950)
    assert got["mlp"] == pytest.approx(100 * 400 / 950)
    assert got["unscoped"] == pytest.approx(100 * 150 / 950)
    # core is inside attn and a class of nobody; a scope nobody entered is
    # nought, not missing
    assert got["core"] == got["head"] == got["optimizer"] == 0.0
    assert sum(got.values()) == pytest.approx(100.0)
    # one table a run, however many metrics read it
    assert [n.splitlines()[0] for n in ctx.notes] == [
        ds.NOTE_HEAD + " (6 leaf operations, 0.000950 s):",
        ctx.notes[1]] and ctx.notes[1].startswith("device_scopes: decoded")
    # a window that is off by a nanosecond is nobody's; no device, no trace
    off = Reduced((reduced.window[0] + 1, reduced.window[1]),
                  reduced.devices, [])
    assert ds.scope_share_pct(_ctx(off), {"scope": "attn"}) is None
    assert ds.scope_share_pct(_ctx(Reduced(reduced.window, {}, [])),
                              {"scope": "attn"}) is None
    assert ds.scope_share_pct(_ctx(None), {"scope": "attn"}) is None
    # the program of the parent commit declares no scope
    monkeypatch.setattr(ds, "program_scopes", lambda: frozenset())
    assert ds.scope_share_pct(_ctx(reduced), {"scope": "unscoped"}) is None


def test_another_runs_file_is_read_for_its_window_alone(tmp_path,
                                                        monkeypatch):
    """A run killed at its limit leaves its trace directory behind: the
    search for this run's file decodes no operation of it."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    other = _written(tmp_path, _space(window_us=(2000, 3000)),
                     "bench_trace_0")
    mine = _written(tmp_path, _space(), "bench_trace_1")
    decoded = []
    monkeypatch.setattr(ds, "_ops",
                        lambda plane: decoded.append(plane) or iter(()))
    ds.read_trace.cache_clear()
    try:
        assert ds.trace_window(other) != ds.trace_window(mine)
        assert ds.find_trace(ds.trace_window(mine)) == mine
        assert decoded == []
        assert ds.read_trace(mine) == (ds.trace_window(mine), ())
        assert len(decoded) == 1
    finally:
        ds.read_trace.cache_clear()     # what it holds was not decoded


def test_the_readers_scopes_are_the_programs():
    from ray_tpu.observability import metric_names
    assert ds.program_scopes() == metric_names.DEVICE_SCOPES == SCOPES
    assert set(ds.INNER) | {s for v in ds.INNER.values() for s in v} \
        <= SCOPES


# -- the metric files -------------------------------------------------------


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_scope_metrics_file_agrees_with_its_entry(name):
    m = manifest.Manifest(REPO)
    entry, = [e for e in m.data["per_layer"] if e["name"] == name]
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    for key, value in entry.items():
        assert spec[key] == value, (name, key)
    assert (entry["source"], entry["unit"], entry["better"]) == (
        "device_trace", "%", "lower")
    assert spec["reducer"] == "benchmark.device_scopes:scope_share_pct"
    assert reducers.resolve(spec["reducer"]) is ds.scope_share_pct
    assert spec["params"] == {"scope": METRICS[name]} and spec["what"]
    train = name.endswith(".train")
    # PR 60: the mixture training cell's five entries named the same reader
    # and scope and folded into these
    assert entry["workloads"] == (
        ["mistral7b-train-4k", "mistral7b-train-4k-fsdp4",
         "kanana2-train-8k"] if train else ["internlm2-serve-offline"])
    assert entry["moves"] == ("train_tokens_per_s" if train
                              else "serve_tokens_per_s")
    assert entry["layer"] == ("Step builder and sharding"
                              if METRICS[name] == "optimizer" else "Model")
    for cell in entry["workloads"]:
        mine, = [x for x in m.cell(cell).per_layer if x["name"] == name]
        assert mine["reducer"] == spec["reducer"]


def test_the_benchmark_gained_the_nine_at_its_end_and_nothing_else():
    """What must hold of the list whatever later PRs add or fold (the pin
    of 62 entries went stale with PR 46's first addition): the manifest is
    clean, the list keeps room, the nine stand together where PR 44 put
    them, after the entries that were there before. That no two entries
    repeat a reader, and that every cell reports what it reported, is
    ``test_benchmark_folded.py``'s."""
    m = manifest.Manifest(REPO)
    assert manifest.check(m) == []
    names = [e["name"] for e in m.data["per_layer"]]
    assert len(names) == len(set(names)) <= 100
    first = names.index("attn_share_pct.train")
    assert set(names[first:first + len(METRICS)]) == set(METRICS)
    assert names.index("reply_ms.steady") < first
    # the three cells whose lists of metrics are pinned report none
    for cell in ("ouro2.6b-train-4k", "minicpm-sala-serve-longdoc",
                 "longcat-flash-serve-prefill", "internlm2-serve-steady"):
        assert not [x["name"] for x in m.cell(cell).per_layer
                    if x["name"] in METRICS]
