"""What one cell is, read from ``BENCHMARK.json`` and the data files.

A cell ``<config>.<traffic>`` finds ``configs/<config>.json``,
``traffic/<traffic>.json`` and its own load in ``cells/<cell>.json`` by
name. Which metrics it reports follows from ``BENCHMARK.json`` alone: an
end-to-end metric with no ``workloads`` key belongs to every cell, a
per-layer metric with none to every cell that reports the metric it
moves. A later PR adds a cell by adding files and entries.

A configuration's file is one JSON object, the only form there is (the
driver takes no other, and neither does :func:`load_cell`). Its keys:

the source's own keys, at the top level, under their published names
    ``hidden_size``, ``num_hidden_layers``, ``num_key_value_heads``, ...;
    for another block ``num_local_experts``, ``layer_types``,
    ``mamba_d_state``, ... . Nested groups are copied whole. A key that
    is not in ``reduced`` holds the published value. A key in ``reduced``
    holds what is run here (the depth, the experts this chip holds, its
    slice of the vocabulary); no width is ever in ``reduced``. These are
    the one statement of each size: the file has no ``model`` of its own,
    the block's file makes the program's from them (``model_of``, below).
``published``
    an object with the published value of every name in ``reduced``.
``reference``, ``source``, ``reduced``, ``assumed``, ``deployment``
    ``reference`` is the stem of ``references/<stem>.py`` (below);
    ``source`` and ``reduced`` equal the configuration's entry in
    ``BENCHMARK.json``; ``assumed`` lists sizes the source lacks;
    ``deployment`` says what the cut stands for: over how many chips a
    layer is shared, where it is, and what of it this chip holds.
``departures``
    an object: where the program's block leaves the published one, under
    the published key it does not run as stated (``rope_theta``,
    ``use_bias``, ...) or, where no key says it, under a short name.
``mesh``, ``payload``
    objects under the program's own names, which :func:`runtime_document`
    passes on as they stand: the runtime-config document's sections.
``notes``
    an object from a top-level key, ``"mesh"``, ``"payload"`` or
    ``"<section>.<key>"`` to a sentence on why that value (JSON has no
    comments).

A configuration names its block's file: its key ``"reference":
"<stem>"`` is ``references/<stem>.py``, loaded by path and carried on
the cell. That file holds all the benchmark believes about the block's
mathematics, the harness none of it. Its whole interface, for the PR
that adds the next block (``config`` is the configuration's file,
``model`` what ``model_of`` made of it):

``model_of(config) -> model``
    The program's ``[model]`` section, under the program's names, made
    from the published keys as the file holds them and from nothing
    else: ``{"d_model": config["hidden_size"], ...}``. :func:`load_cell`
    keeps it as ``cell.config["model"]``; the server starts from it and
    the three functions below are handed it, so what the file states is
    what runs, for every configuration.
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
import types

from benchmark import schedule

REPO = os.path.dirname(schedule.HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict      # configs/<config>.json, and "model" (model_of)
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
            f"\"reference\": \"<stem>\", a file "
            f"{root}/references/<stem>.py")
    path = os.path.join(root, "references", stem + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"{conf['file']} has \"reference\": \"{stem}\", "
                         f"and there is no {path}")
    return load_module("benchmark_reference_" + stem, path)


def load_config(conf: dict, repo: str) -> dict:
    """The JSON object a configuration's entry names as its ``file``."""
    wanted = (
        f"{conf['file']} has to hold a JSON object: the source's keys "
        "under their published names, \"reference\", \"source\", "
        "\"reduced\", \"published\", and the objects \"mesh\" and "
        "\"payload\" (benchmark/cellspec.py has the layout)")
    try:
        with open(os.path.join(repo, conf["file"])) as fh:
            config = json.load(fh)
    except ValueError as error:
        raise SystemExit(f"{wanted}; it does not parse: {error}")
    if not isinstance(config, dict):
        raise SystemExit(f"{wanted}; it holds a {type(config).__name__}")
    lacking = [k for k in ("mesh", "payload")
               if not isinstance(config.get(k), dict)]
    if lacking:
        raise SystemExit(f"{wanted}; it has no object "
                         + ", ".join(f'"{k}"' for k in lacking))
    if "model" in config:
        raise SystemExit(
            f"{wanted}; it has a \"model\" of its own, a second "
            "statement of the sizes: the block's file makes the "
            "program's from the published keys (model_of)")
    return config


def model_of(conf: dict, config: dict, reference) -> dict:
    """The program's ``model`` as the block's file makes it from the
    configuration's published keys."""
    make = getattr(reference, "model_of", None)
    if make is None:
        raise SystemExit(
            f"{reference.__file__} has no model_of(config): {conf['file']} "
            "states sizes under their published names, and the block's "
            "file says which of the program's each is")
    try:
        return make(config)
    except KeyError as error:
        raise SystemExit(f"{conf['file']} lacks the key {error} that "
                         f"{reference.__file__} reads in model_of")


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
    config = load_config(conf, repo)
    reference = load_reference(conf, config, root)
    config["model"] = model_of(conf, config, reference)
    end_to_end = tuple(m for m in bench["end_to_end"]
                       if _belongs(m, name, set()))
    reported = {m["name"] for m in end_to_end}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _belongs(m, name, reported))
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        reference=reference,
        traffic=schedule.load_json("traffic", entry["traffic"], root),
        load=schedule.load_json("cells", name, root), root=root,
        end_to_end=end_to_end, per_layer=per_layer)


def runtime_document(cell: Cell, state_dir: str, platform: str,
                     overrides: dict | None = None) -> dict:
    """The runtime-config document the serve payload starts from: the
    ``model`` the block's file made of the configuration, its ``mesh``
    and ``payload`` objects as they stand, the entry point's own keys
    around them."""
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
