"""Finds a cell's data files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``. Its configuration is
``configs/<config>.json``, its traffic mix ``traffic/<traffic>.json``, how
it is deployed ``workloads/<cell>.json``, and each per-layer metric
``layer_metrics/<metric>.json``: a later PR adds a cell or a metric by
adding files and entries, and edits nothing that is here.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def _load(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e
    if not isinstance(data, dict):
        raise ManifestError(f"{path}: not a JSON object")
    return data


@dataclasses.dataclass(frozen=True)
class Cell:
    """One cell with everything its files say."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]        # configs/<config>.json
    traffic: Dict[str, Any]       # traffic/<traffic>.json
    deploy: Dict[str, Any]        # workloads/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]   # BENCHMARK.json entry + its file
    root: str

    @property
    def job(self) -> str:
        return self.deploy["job"]


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = _load(os.path.join(root, "BENCHMARK.json"))
        paths = self.data.get("paths") or []
        if not paths:
            raise ManifestError("BENCHMARK.json: no paths")
        self.dir = os.path.join(root, paths[0])

    def _file(self, kind: str, name: str) -> Dict[str, Any]:
        if not NAME_RE.match(name):
            raise ManifestError(f"{kind} name {name!r} is not a name")
        return _load(os.path.join(self.dir, kind, name + ".json"))

    def cell_names(self) -> List[str]:
        return [w["name"] for w in self.data["workloads"]]

    def _applies(self, metric: Dict[str, Any], cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.data["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (it has "
                f"{', '.join(self.cell_names())})")
        cfg_entry = next((c for c in self.data["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise ManifestError(f"cell {name}: no config {entry['config']!r}")
        config = _load(os.path.join(self.root, cfg_entry["file"]))
        deploy = self._file("workloads", name)
        if deploy.get("chips") != entry["chips"]:
            raise ManifestError(
                f"cell {name}: workloads/{name}.json says chips="
                f"{deploy.get('chips')}, BENCHMARK.json {entry['chips']}")
        per_layer = []
        for m in self.data["per_layer"]:
            if self._applies(m, name):
                per_layer.append({**self._file("layer_metrics", m["name"]),
                                  **m})
        return Cell(
            name=name, chips=int(entry["chips"]),
            config_name=entry["config"], config=config,
            traffic=self._file("traffic", entry["traffic"]),
            deploy=deploy,
            end_to_end=[m for m in self.data["end_to_end"]
                        if self._applies(m, name)],
            per_layer=per_layer, root=self.root)


def model_dims(config: Dict[str, Any], job: str, chips: int
               ) -> Dict[str, Any]:
    """The sizes a cell runs: the published keys of ``config`` with the cut
    that ``reduced`` lists for this (job, chips). A cell whose (job, chips)
    has no entry is an error: every size that is run was written down."""
    key = f"{job}.{chips}"
    cuts = config.get("reduced", {})
    if key not in cuts:
        raise ManifestError(
            f"configuration {config.get('name')!r} has no 'reduced' entry "
            f"for {key!r} (it has {sorted(cuts)}): say what is cut, or that "
            "nothing is, before running it there")
    sizes = {**config, **{k: v for k, v in cuts[key].items()
                          if k in config}}
    heads = int(sizes["num_attention_heads"])
    return {
        "vocab_size": int(sizes["vocab_size"]),
        "d_model": int(sizes["hidden_size"]),
        "n_layers": int(sizes["num_hidden_layers"]),
        "n_heads": heads,
        "n_kv_heads": int(sizes["num_key_value_heads"]),
        "head_dim": int(sizes.get("head_dim")
                        or sizes["hidden_size"] // heads),
        "d_ff": int(sizes["intermediate_size"]),
        "rope_theta": float(sizes["rope_theta"]),
        "rms_norm_eps": float(sizes["rms_norm_eps"]),
    }


def check(manifest: Manifest) -> List[str]:
    """Every fault of form found in ``BENCHMARK.json`` and its files (the
    rules of the benchmark's contract that can be checked without a run)."""
    d, bad = manifest.data, []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(d) != want:
        bad.append(f"keys {sorted(d)} are not exactly {sorted(want)}")
        return bad

    def name_ok(what: str, n: Any) -> None:
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what}: {n!r} is not a name")

    def line_ok(what: str, s: Any) -> None:
        if not (isinstance(s, str) and 1 <= len(s) <= 200
                and "\n" not in s and "\t" not in s):
            bad.append(f"{what}: not one line of 1 to 200 characters")

    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        bad.append("run_seconds is not a whole number from 1 to 51")
    for word in d["command"]:
        line_ok("command", word)
    configs = {c["name"]: c for c in d["configs"]}
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok("config", c["name"])
        line_ok(f"config {c['name']} source", c["source"])
        line_ok(f"config {c['name']} why", c["why"])
        if not any(c["file"].startswith(p + "/") for p in d["paths"]):
            bad.append(f"config {c['name']}: file outside paths")
        if not os.path.exists(os.path.join(manifest.root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        for k in c["reduced"]:
            name_ok(f"config {c['name']} reduced", k)
            if (k.endswith(("_dim", "_rank", "_size"))
                    and k != "vocab_size") or "head" in k:
                bad.append(f"config {c['name']}: reduced names a width {k}")
    e2e = {m["name"]: m for m in d["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no end-to-end metric setup_s")
    for m in d["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}:
            bad.append(f"metric {m.get('name')}: keys {sorted(m)}")
        if not (isinstance(m.get("bound"), (int, float))
                and 0 < m["bound"] <= 0.1):
            bad.append(f"metric {m['name']}: bound {m.get('bound')}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: source {m['source']}")
    cells = {}
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w.get('name')}: keys {sorted(w)}")
        name_ok("cell", w["name"])
        name_ok("traffic", w["traffic"])
        line_ok(f"cell {w['name']} why", w["why"])
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        cells[w["name"]] = w
    if len(cells) != len(d["workloads"]):
        bad.append("two cells share a name")
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    if len(set(pairs)) != len(pairs):
        bad.append("a pair of configuration and traffic appears twice")
    four = [w["name"] for w in d["workloads"] if w["chips"] == 4]
    if len(four) > max(1, len(cells) // 4):
        bad.append(f"too many four-chip cells: {four}")
    for c in configs:
        if not any(w["config"] == c for w in d["workloads"]):
            bad.append(f"config {c} is used by no cell")

    def reported(metric: Dict[str, Any]) -> set:
        return set(metric.get("workloads", cells))

    names = set()
    for m in d["end_to_end"] + d["per_layer"]:
        name_ok("metric", m["name"])
        if m["name"] in names:
            bad.append(f"two metrics are named {m['name']}")
        names.add(m["name"])
        if not UNIT_RE.match(str(m.get("unit", ""))):
            bad.append(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        for w in m.get("workloads", ()):
            if w not in cells:
                bad.append(f"metric {m['name']}: unknown cell {w}")
    for m in d["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}:
            bad.append(f"metric {m['name']}: keys {sorted(m)}")
        line_ok(f"metric {m['name']} layer", m.get("layer"))
        moved = e2e.get(m.get("moves"))
        if moved is None:
            bad.append(f"metric {m['name']}: moves {m.get('moves')!r} is "
                       "not an end-to-end metric")
        elif not reported(m) <= reported(moved):
            bad.append(f"metric {m['name']}: moves {m['moves']}, which "
                       f"{sorted(reported(m) - reported(moved))} do not "
                       "report")
    for cell in cells:
        e = [m["name"] for m in d["end_to_end"] if cell in reported(m)]
        if "setup_s" not in e or len(e) < 2:
            bad.append(f"cell {cell}: end-to-end metrics {e}")
        if not any(cell in reported(m) for m in d["per_layer"]):
            bad.append(f"cell {cell}: no per-layer metric")
        try:
            c = manifest.cell(cell)
            model_dims(c.config, c.job, c.chips)
            bad.extend(f"metric {m['name']}: its file names no reducer"
                       for m in c.per_layer if "reducer" not in m)
        except ManifestError as e:
            bad.append(str(e))
    return bad
