"""What one cell is, read from ``BENCHMARK.json`` and the data files.

A cell ``<config>.<traffic>`` finds ``configs/<config>.toml``,
``traffic/<traffic>.json`` and its own load in ``cells/<cell>.json`` by
name. Which metrics it reports follows from ``BENCHMARK.json`` alone: an
end-to-end metric with no ``workloads`` key belongs to every cell, a
per-layer metric with none to every cell that reports the metric it
moves. A later PR adds a cell by adding files and entries.

A configuration names its block's file: the top-level key ``reference =
"<stem>"`` of its toml is ``references/<stem>.py``, loaded by path and
carried on the cell. That file holds all the benchmark believes about
the block's mathematics, the harness none of it. Its whole interface,
for the PR that adds the next block (``model`` is the toml's ``[model]``):

``make_weights(model) -> weights``
    The reference's own weights, made on the device from the recipe the
    program follows, placed where they fit (over the chips, if several).
``logits(model, weights, sequences, first, quant="") -> [array]``
    For each token sequence, float32 logits ``[T - first, vocab]`` of the
    positions from ``first`` on, by the plain forward pass; ``quant``
    names a lower precision, the control's (``check.control_gaps``).
``decode_step(model, rows, live_tokens) -> {"flops": .., "bytes": ..}``
    What one decode step over ``rows`` sequences holding ``live_tokens``
    cached positions needs by the block's equations: the roofline count.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import tomllib
import types

from benchmark import schedule

REPO = os.path.dirname(schedule.HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict      # configs/<config>.toml
    reference: types.ModuleType  # references/<the config's reference>.py
    traffic: dict     # traffic/<traffic>.json
    load: dict        # cells/<cell>.json
    root: str         # the benchmark directory the files came from
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _belongs(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_module(name: str, path: str) -> types.ModuleType:
    """A file of the benchmark's, loaded by its path and not by an
    import: a probe tree's and a later PR's are found the same way."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reference(conf: dict, config: dict, root: str):
    """The block's file a configuration names."""
    stem = config.get("reference")
    if not isinstance(stem, str) or not stem:
        raise SystemExit(
            f"{conf['file']} names no block: it needs a top-level key "
            f"reference = \"<stem>\", a file {root}/references/<stem>.py")
    path = os.path.join(root, "references", stem + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"{conf['file']} has reference = {stem!r}, and "
                         f"there is no {path}")
    return load_module("benchmark_reference_" + stem, path)


def load_cell(name: str, repo: str = REPO, root: str | None = None) -> Cell:
    root = root or os.path.join(repo, "benchmark")
    with open(os.path.join(repo, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(there are: {known})")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(repo, conf["file"]), "rb") as fh:
        config = tomllib.load(fh)
    end_to_end = tuple(m for m in bench["end_to_end"]
                       if _belongs(m, name, set()))
    reported = {m["name"] for m in end_to_end}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _belongs(m, name, reported))
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        reference=load_reference(conf, config, root),
        traffic=schedule.load_json("traffic", entry["traffic"], root),
        load=schedule.load_json("cells", name, root), root=root,
        end_to_end=end_to_end, per_layer=per_layer)


def runtime_document(cell: Cell, state_dir: str, platform: str,
                     overrides: dict | None = None) -> dict:
    """The runtime-config document the serve payload starts from: the
    configuration's ``[model]``, ``[mesh]`` and ``[payload]`` sections
    as they stand, the entry point's own keys around them."""
    payload = {"kind": "serve", "serving": "paged",
               **cell.config["payload"], **(overrides or {})}
    return {
        "runtime": {"name": "bench-" + cell.name, "state_dir": state_dir},
        "tpu": {"platform": platform, "expected_chips": cell.chips},
        "status": {"bind": "127.0.0.1", "port": 0},
        "mesh": cell.config["mesh"],
        "model": cell.config["model"],
        "payload": payload,
    }
