"""Chip smoke: train -> checkpoint -> paged serve, once, on the TPU.

The quickest proof that the system still starts on the chip. It drives
the path users run, through the entry points they run it through — a
``[payload] kind = "train"`` runtime over a ``KVFEED01`` corpus and the
native feeder, orbax checkpoints in the state dir, then ``kind =
"serve"`` with ``serving = "paged"`` restoring that checkpoint and
answering ``POST /generate`` on the status port — at the full width of
the 209M dense shape (vocab 32,000, d_model 1024, 16 layers, 16 query /
4 KV heads of 64, d_ff 4096, bf16 compute), written as ``[model]`` TOML.

    python chip_smoke.py             # one chip, also where the host
                                     # holds more
    python chip_smoke.py --chips 4   # the {data=2, model=2} mesh and its
                                     # one-chip reference, nothing else

Everything worth reading goes on earlier lines; the last line of stdout
is one JSON object, ``{"ok": true, "device": {"platform": "tpu",
"kind": "...", "count": 1}}``. Exit code 0 only with ``"ok": true``:
not on a CPU backend, not on a degraded runtime handle, not when a
request fails or the pool had to heal itself, not when the compiled
decode kernel and the gather give different bits or tokens, and not
when the SSM or the delta step kernel and its plain one-token form part.

One process holds the chip; nothing here starts a child that needs it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import re
import shutil
import sys
import tempfile
import threading
import time
import tomllib
import traceback
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# One-chip against four-chip: bf16 compute, and tensor parallelism sums
# each contraction in two halves, so the numbers agree closely, not
# bitwise. Loss is ~ln(vocab).
LOSS_TOLERANCE = 0.05
# A greedy stream may leave its reference only at a near-tie: where the
# reference model itself, teacher-forced on its own stream, separates
# the two tokens by less than this share of its largest logit. bf16
# activations carry 2^-8 relative rounding through every layer; 2^-5
# leaves room for sixteen of them and still refuses a wrong token.
NEAR_TIE = 2.0 ** -5


class SmokeFailure(RuntimeError):
    """A phase found the system not doing what the smoke requires."""


@dataclasses.dataclass(frozen=True)
class SmokeShape:
    """What the smoke runs. Widths come from ``model``; the rest sizes
    the run. Tests pass a tiny one; the script only ever runs 209M."""

    model: str          # body of the [model] TOML section
    vocab: int
    seq: int            # train sequence == serving max_seq
    batch: int
    steps: int
    checkpoint_every: int
    page_size: int
    prompt: int         # prompt tokens per request
    shared: int         # leading tokens the prefix pair shares (pages)
    n_new: int


# The default train batch (8 x 2,048 tokens a step): the described-v5e
# compile of this step needs 7.3 GB of the chip's 16 (PERF.md). 192-token
# prompts are three 64-token prefill chunks; 66 new tokens are one
# 64-step decode window that crosses the page boundary at 256, plus the
# per-step tail.
SHAPE_209M = SmokeShape(
    model=("vocab = 32000\nd_model = 1024\nn_layers = 16\n"
           "n_heads = 16\nn_kv_heads = 4\nd_ff = 4096\n"),
    vocab=32000, seq=2048, batch=8, steps=6, checkpoint_every=3,
    page_size=128, prompt=192, shared=128, n_new=66,
)


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling, and how the
    persistent cache answered, between two reads. Compiles happen on
    whichever thread dispatches first (the decode thread, mostly)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = dict.fromkeys(
            [*_COMPILE_EVENTS.values(), *_CACHE_EVENTS.values()], 0.0)
        self.programs: list[tuple[str, float]] = []

    def _on_duration(self, event, duration, **kw):
        key = _COMPILE_EVENTS.get(event)
        if key is None:
            return
        with self._lock:
            self._totals[key] += duration
            if key == "compile_s":
                self.programs.append((kw.get("fun_name", "?"), duration))

    def _on_event(self, event, **kw):
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            with self._lock:
                self._totals[key] += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def read(self) -> dict:
        with self._lock:
            return dict(self._totals)


class Smoke:
    """One run's context: shape, workdir, meter, and the report that
    ends up on stdout and in ``chiprun_out/chip_smoke.json``."""

    def __init__(self, shape: SmokeShape, *, chips: int, platform: str,
                 seed: int, workdir: str, meter: CompileMeter):
        self.shape, self.chips, self.platform = shape, chips, platform
        self.seed, self.workdir, self.meter = seed, workdir, meter
        self.report: dict = {"phases": {}}
        self.corpus = os.path.join(workdir, "corpus.kvfeed")

    def say(self, text: str) -> None:
        print(f"[chip-smoke] {text}", flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase, split into compile and everything else."""
        before, start = self.meter.read(), time.perf_counter()
        entry = self.report["phases"].setdefault(name, {})
        try:
            yield entry
        finally:
            wall = time.perf_counter() - start
            after = self.meter.read()
            delta = {k: after[k] - before[k] for k in after}
            building = (delta["trace_s"] + delta["lower_s"]
                        + delta["compile_s"])
            entry.update(
                wall_s=round(wall, 2), compile_s=round(building, 2),
                run_s=round(max(wall - building, 0.0), 2),
                cache_hits=int(delta["cache_hits"]),
                cache_misses=int(delta["cache_misses"]),
            )
            self.say(f"phase {name}: wall {entry['wall_s']} s = "
                     f"compile {entry['compile_s']} s (trace+lower+XLA; "
                     f"cache hits {entry['cache_hits']}, misses "
                     f"{entry['cache_misses']}) + run {entry['run_s']} s")

    def toml(self, *, kind: str, state: str, mesh: str,
             checkpoint_dir: str = "", paged_attention: str = "") -> str:
        s = self.shape
        lines = [
            "[runtime]", f'name = "chip-smoke-{kind}"',
            f'state_dir = "{os.path.join(self.workdir, state)}"',
        ]
        if checkpoint_dir:
            lines.append(f'checkpoint_dir = "{checkpoint_dir}"')
        lines += [
            "[tpu]", f'platform = "{self.platform}"',
            f"expected_chips = {self.chips}",
            "[mesh]", f"axes = {mesh}",
            "[status]", 'bind = "127.0.0.1"', "port = 0",
            "[model]", s.model.rstrip("\n"),
            "[payload]", f'kind = "{kind}"',
            f'corpus = "{self.corpus}"',
            f"steps = {s.steps}", f"batch = {s.batch}", f"seq = {s.seq}",
            f"checkpoint_every = {s.checkpoint_every}",
            'serving = "paged"', f"serving_page_size = {s.page_size}",
        ]
        if paged_attention:
            lines.append(f'paged_attention = "{paged_attention}"')
        return "\n".join(lines) + "\n"

    def config(self, **kw):
        from kvedge_tpu.config.runtime_config import RuntimeConfig

        return RuntimeConfig.parse(self.toml(**kw))


# ---- phases -------------------------------------------------------------


def phase_environment(smoke: Smoke, cache_dir: str, cache_was_empty: bool):
    import jax
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    smoke.report["environment"] = env = {
        "python": sys.version.split()[0], "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "hbm_bytes_limit": stats.get("bytes_limit"),
        "compile_cache_dir": cache_dir,
        "compile_cache_empty_at_start": cache_was_empty,
    }
    smoke.say("environment: " + json.dumps(env))
    if len(devices) != smoke.chips:
        raise SmokeFailure(
            f"{len(devices)} devices visible, this run needs "
            f"{smoke.chips} (python chip_smoke.py"
            f"{' --chips 4' if len(devices) == 4 else ''})")


def phase_corpus(smoke: Smoke):
    """A seeded random corpus through the repo's own writer, read back
    through the native feeder — or the smoke fails saying which ran."""
    from kvedge_tpu import cli
    from kvedge_tpu.data.feeder import TokenFeeder, open_feeder

    s = smoke.shape
    n_tokens = s.batch * s.seq  # the feeder wraps: one batch, repeated
    rc = cli.main(["corpus", "--random", str(n_tokens), "--vocab",
                   str(s.vocab), "--seed", str(smoke.seed), "--out",
                   smoke.corpus])
    if rc != 0:
        raise SmokeFailure(f"`kvedge-tpu corpus --random` exited {rc}")
    with open_feeder(smoke.corpus, batch=s.batch, seq=s.seq) as feeder:
        kind = type(feeder).__name__
        first = next(feeder)
    smoke.report["feeder"] = kind
    smoke.say(f"corpus: {n_tokens} tokens, vocab {s.vocab}, seed "
              f"{smoke.seed}; feeder: {kind} "
              f"({'native' if kind == 'TokenFeeder' else 'PYTHON FALLBACK'})"
              f", first batch {first.shape}")
    if not isinstance(feeder, TokenFeeder):
        raise SmokeFailure(
            "the native feeder did not build from the committed sources "
            "(is there a g++ and a make?); the Python fallback is not "
            "the path users run")


def _started(smoke: Smoke, cfg):
    """start_runtime, refusing a degraded handle."""
    from kvedge_tpu.runtime.boot import start_runtime

    handle = start_runtime(cfg)
    if not handle.check.ok:
        error = handle.check.error
        handle.shutdown()
        raise SmokeFailure(f"payload {cfg.payload!r} came back degraded: "
                           f"{error}")
    return handle


def _check_losses(smoke: Smoke, losses: list) -> None:
    s = smoke.shape
    if len(losses) < s.steps or any(
            x is None or not math.isfinite(x) for x in losses):
        raise SmokeFailure(f"expected {s.steps} finite losses, got {losses}")
    # Decreasing or flat: the corpus is one batch long, so every step
    # sees the same tokens and the loss should fall; 2% is "flat".
    if losses[-1] > losses[0] * 1.02:
        raise SmokeFailure(f"loss rose over the run: {losses}")


def phase_train(smoke: Smoke, *, state: str, mesh: str) -> list:
    """The train payload through start_runtime; returns per-step losses.

    JAX writes every program it lowers during the phase to a directory
    (``jax_dump_ir_to``), so what is reported about the train step is
    read from the text of the program that ran, not from a twin."""
    import jax

    from kvedge_tpu.runtime.workload import train_model_config

    cfg = smoke.config(kind="train", state=state, mesh=mesh)
    ir_dir = os.path.join(smoke.workdir, f"ir-{state}")
    with smoke.phase(f"train[{state}]") as entry:
        jax.config.update("jax_dump_ir_to", ir_dir)
        try:
            handle = _started(smoke, cfg)
        finally:
            jax.config.update("jax_dump_ir_to", None)
        try:
            progress = handle.snapshot()["train_progress"] or {}
        finally:
            handle.shutdown()
        losses = progress.get("losses") or []
        entry.update(losses=losses, final_step=progress.get("step"))
        smoke.say(f"train[{state}] mesh {mesh}: steps "
                  f"{progress.get('step')}/{smoke.shape.steps}, loss per "
                  f"step {losses}")
        _check_losses(smoke, losses)
        if progress.get("step") != smoke.shape.steps:
            raise SmokeFailure(f"trained to step {progress.get('step')}, "
                               f"wanted {smoke.shape.steps}")
        tcfg, _ = train_model_config(cfg)
        kernel_asked = tcfg.attention == "flash" or tcfg.fused_xent
        entry["train_step"] = facts = _train_step_facts(ir_dir)
        entry["memory"] = device_memory(smoke, f"after train[{state}]")
        smoke.say(f"train step [{state}], as lowered for the run: "
                  f"{tcfg.param_count:,} params, attention="
                  f"{tcfg.attention}, remat={tcfg.remat}/"
                  f"{tcfg.remat_policy}, fused_xent={tcfg.fused_xent}, "
                  f"{tcfg.dtype} (Pallas kernel expected: "
                  f"{'yes' if kernel_asked else 'no'}); "
                  f"{facts['programs']} program(s), tpu_custom_call "
                  f"x{facts['tpu_custom_calls']}, "
                  f"{facts['num_partitions']} partition(s), "
                  f"{facts['arguments_split']} arguments split over mesh "
                  f"axes {facts['mesh']}")
        if bool(facts["tpu_custom_calls"]) != kernel_asked:
            raise SmokeFailure(
                f"the train step holds {facts['tpu_custom_calls']} "
                f"tpu_custom_call(s) where the config "
                + ("asks for a kernel" if kernel_asked else "asks for none"))
        if facts["num_partitions"] != smoke.chips:
            raise SmokeFailure(
                f"the train step was lowered for {facts['num_partitions']} "
                f"partition(s) on a {smoke.chips}-chip mesh")
    return losses


def _train_step_facts(ir_dir: str) -> dict:
    """Read the StableHLO JAX dumped for ``jit(train_step)``. Collectives
    are not in this text (XLA's partitioner adds them after it); the
    partition count and the split arguments that cause them are."""
    texts = []
    for name in sorted(os.listdir(ir_dir) if os.path.isdir(ir_dir) else []):
        if "jit_train_step" in name:
            with open(os.path.join(ir_dir, name)) as fh:
                texts.append(fh.read())
    if not texts:
        raise SmokeFailure("the train payload lowered no jit(train_step)")
    text = texts[-1]
    found = re.search(r"mhlo\.num_partitions = (\d+)", text)
    partitions = int(found.group(1)) if found else 1
    mesh = re.search(r"sdy\.mesh @\w+ = <([^>]*)>", text)
    # An argument laid out over at least one named axis, {"model"}; on
    # one partition every axis has size 1 and nothing is split.
    named = re.findall(
        r"%arg\d+: tensor<[^>]*> \{[^}]*sdy\.sharding = "
        r"#sdy\.sharding<@\w+, \[[^\]]*\{\"", text)
    return {
        "programs": len(texts),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "num_partitions": partitions,
        "mesh": mesh.group(1) if mesh else "none",
        "arguments_split": len(named) if partitions > 1 else 0,
    }


def _collectives(text: str) -> dict:
    return {name: text.count(f" {name}(") + text.count(f" {name}-start(")
            for name in _COLLECTIVES}


def _post(port: int, doc: dict, timeout: float = 900.0):
    """POST /generate; returns the JSON body, or for a stream the list
    of ndjson documents."""
    request = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=timeout) as response:
        body = response.read().decode()
    if doc.get("stream"):
        return [json.loads(line) for line in body.splitlines() if line]
    return json.loads(body)


def _metrics(port: int) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=60) as response:
        text = response.read().decode()
    out = {}
    for line in text.splitlines():
        if line.startswith("kvedge_") and "{" not in line:
            name, _, value = line.partition(" ")
            with contextlib.suppress(ValueError):
                out[name] = float(value)
    return out


def _prompts(smoke: Smoke) -> dict:
    """Seeded prompts: one alone, a pair sharing ``shared`` leading
    tokens (whole pages), and four for the concurrent request."""
    import numpy as np

    s = smoke.shape
    rng = np.random.default_rng(smoke.seed + 1)

    def row(n):
        return [int(t) for t in rng.integers(0, s.vocab, size=n)]

    stem = row(s.shared)
    return {
        "solo": row(s.prompt),
        "pair": [stem + row(s.prompt - s.shared) for _ in range(2)],
        "batch": [row(s.prompt) for _ in range(4)],
    }


class Client:
    """Counts what it sends, so /metrics can be held against it."""

    def __init__(self, smoke: Smoke, port: int):
        self.smoke, self.port = smoke, port
        self.sent = self.succeeded = self.failed = 0

    def generate(self, label: str, doc: dict):
        s = self.smoke.shape
        self.sent += 1
        start = time.perf_counter()
        try:
            out = _post(self.port, doc)
        except (urllib.error.URLError, OSError, ValueError) as e:
            self.failed += 1
            detail = e.read().decode() if hasattr(e, "read") else ""
            raise SmokeFailure(f"request {label!r} failed: {e!r} {detail}")
        final = out[-1] if isinstance(out, list) else out
        rows = final.get("tokens")
        want = len(doc["tokens"])
        if (not isinstance(rows, list) or len(rows) != want or any(
                len(r) != s.prompt + s.n_new
                or any(not 0 <= t < s.vocab for t in r) for r in rows)):
            self.failed += 1
            raise SmokeFailure(f"request {label!r}: wanted {want} rows of "
                               f"{s.prompt + s.n_new} in-vocab tokens, got "
                               f"{str(final)[:300]}")
        if final.get("restored_step") != s.steps:
            self.failed += 1
            raise SmokeFailure(f"request {label!r} was served from step "
                               f"{final.get('restored_step')}, trained to "
                               f"{s.steps}")
        self.succeeded += 1
        self.smoke.say(
            f"request {label}: {want} row(s), {s.n_new} new tokens each in "
            f"{time.perf_counter() - start:.2f} s, restored_step "
            f"{final.get('restored_step')}; row 0 generated "
            f"{rows[0][s.prompt:s.prompt + 12]}...")
        return out


def _greedy_set(client: Client, prompts: dict) -> dict:
    """The greedy requests every server in a run answers: alone, the
    prefix pair one after the other, four rows at once."""
    n_new = client.smoke.shape.n_new
    out = {"solo": client.generate(
        "greedy", {"tokens": [prompts["solo"]], "n_new": n_new})}
    for i, prompt in enumerate(prompts["pair"]):
        out[f"pair{i}"] = client.generate(
            f"greedy shared-prefix {i + 1}/2",
            {"tokens": [prompt], "n_new": n_new})
    out["batch"] = client.generate(
        "greedy 4 rows at once", {"tokens": prompts["batch"], "n_new": n_new})
    return {k: v["tokens"] for k, v in out.items()}


def _window_program(smoke: Smoke, handle, entry: dict, *,
                    want_kernel: bool):
    """The decode window the live server dispatches, lowered from its
    own params and pool: kernel or gather, collectives, pool placement."""
    lowered = handle.serve_fn.server.lower_decode_window()
    custom_calls = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()
    collectives = _collectives(compiled.as_text())
    path = "kernel" if custom_calls else "gather"
    # Dynamic arguments only: (params, state, tokens, active, caps, stops).
    pool = compiled.input_shardings[0][1].pool_k
    placement = (f"{getattr(pool, 'spec', pool)} over "
                 f"{len(pool.device_set)} device(s)")
    entry["decode_window"] = {
        "tpu_custom_calls": custom_calls, "collectives": collectives,
        "attention_path": path, "pool_placement": placement}
    present = {k: v for k, v in collectives.items() if v}
    smoke.say(f"decode window program: attention path = {path} "
              f"(prefill: gather, always); tpu_custom_call "
              f"x{custom_calls}, collectives {present or 'none'}; KV pool "
              f"[layers, pages, page, kv_heads * d_head] placed "
              f"{placement}")
    if (path == "kernel") != want_kernel:
        raise SmokeFailure(
            f"the decode window lowered to the {path} path; expected "
            + ("the Pallas kernel (a tpu_custom_call)" if want_kernel
               else "the gather"))


def phase_serve(smoke: Smoke, *, state: str, mesh: str, label: str,
                checkpoint_dir: str = "", paged_attention: str = "",
                full: bool, want_kernel: bool) -> dict:
    """The serve payload through start_runtime, asked over HTTP: the
    greedy set, and with ``full`` a streamed and two seeded-sampled
    requests after it. Returns the greedy tokens."""
    s = smoke.shape
    prompts = _prompts(smoke)
    with smoke.phase(f"serve[{label}]") as entry:
        handle = _started(smoke, smoke.config(
            kind="serve", state=state, mesh=mesh,
            checkpoint_dir=checkpoint_dir,
            paged_attention=paged_attention))
        try:
            port = handle.status_port
            client = Client(smoke, port)
            greedy = _greedy_set(client, prompts)
            if full:
                docs = client.generate(
                    "streamed", {"tokens": [prompts["solo"]],
                                 "n_new": s.n_new, "stream": True})
                streamed = [d["token"] for d in docs if "token" in d]
                if streamed != greedy["solo"][0][s.prompt:]:
                    raise SmokeFailure(
                        "the streamed tokens differ from the buffered "
                        "answer to the same greedy request")
                sampled = [client.generate(
                    f"sampled seed 7 ({i + 1}/2)",
                    {"tokens": [prompts["solo"]], "n_new": s.n_new,
                     "temperature": 0.8, "top_p": 0.9, "seed": 7})
                    for i in range(2)]
                if sampled[0]["tokens"] != sampled[1]["tokens"]:
                    raise SmokeFailure("the same seed sampled two "
                                       "different streams")
            stats = handle.serve_fn.stats()
            metrics = _metrics(port)
            windows = stats["window_dispatch_harvest_ms"]["count"]
            entry.update(
                requests={"sent": client.sent,
                          "succeeded": client.succeeded,
                          "failed": client.failed},
                metrics={k: metrics.get(k) for k in (
                    "kvedge_serve_requests_total",
                    "kvedge_serve_completed_total",
                    "kvedge_serve_rejected_total",
                    "kvedge_serve_unavailable_total",
                    "kvedge_serve_errors_total",
                    "kvedge_serve_recoveries_total",
                    "kvedge_serve_tokens_generated_total",
                    "kvedge_serve_prefix_hits_total")},
                windows_dispatched=windows, window_cap=stats["window"],
                prefix_hits=stats["prefix_hits"],
                prefix_tokens_saved=stats["prefix_tokens_saved"])
            smoke.say(f"serve[{label}] requests: sent {client.sent}, "
                      f"succeeded {client.succeeded}, failed "
                      f"{client.failed}; /metrics {entry['metrics']}; "
                      f"{windows} decode windows dispatched (cap "
                      f"{stats['window']}), prefix hits "
                      f"{stats['prefix_hits']} saving "
                      f"{stats['prefix_tokens_saved']} prompt tokens")
            _window_program(smoke, handle, entry, want_kernel=want_kernel)
            if metrics.get("kvedge_serve_completed_total") != client.sent \
                    or metrics.get("kvedge_serve_errors_total") \
                    or metrics.get("kvedge_serve_unavailable_total") \
                    or metrics.get("kvedge_serve_rejected_total"):
                raise SmokeFailure("/metrics disagrees with what was "
                                   f"sent: {entry['metrics']}")
            if metrics.get("kvedge_serve_recoveries_total", 0) != 0 \
                    or stats.get("degraded"):
                raise SmokeFailure("the pool poisoned and healed during "
                                   "the smoke: that is a failure here")
            if windows < 1:
                raise SmokeFailure("no decode window was dispatched")
            if stats["prefix_hits"] < 1:
                raise SmokeFailure("the shared-prefix pair counted no "
                                   "prefix hit")
            entry["memory"] = device_memory(smoke, f"serve[{label}] live")
        finally:
            handle.shutdown()
    return greedy


def compare_tokens(smoke: Smoke, name: str, a: dict, b: dict) -> list:
    """Exact comparison of two servers' greedy answers. Returns the
    places they part as ``(request, row, index)``, index into the whole
    row; a difference is reported with its position, never papered
    over."""
    parted = []
    for key in a:
        for i, (ra, rb) in enumerate(zip(a[key], b[key])):
            if ra != rb:
                parted.append((key, i, next(
                    j for j, (x, y) in enumerate(zip(ra, rb)) if x != y)))
    verdict = "equal" if not parted else "DIFFERENT: " + "; ".join(
        f"{key}[{i}] first differs at generated token "
        f"{at - smoke.shape.prompt}" for key, i, at in parted)
    n_rows = sum(len(rows) for rows in a.values())
    smoke.report.setdefault("comparisons", {})[name] = verdict
    smoke.say(f"compare {name}: {n_rows} greedy rows x "
              f"{smoke.shape.n_new} tokens: {verdict}")
    return parted


def check_near_ties(smoke: Smoke, tcfg, params, a: dict, b: dict,
                    parted: list) -> None:
    """Hold each divergence of ``b`` from the reference ``a`` to the
    stated tolerance: the plain cache-less forward pass over ``a``'s own
    stream must call the two tokens a near-tie (NEAR_TIE)."""
    import jax
    import jax.numpy as jnp

    from kvedge_tpu.models import forward

    teacher = jax.jit(lambda p, t: forward(p, t, tcfg))
    for key, i, at in parted:
        logits = teacher(params, jnp.asarray([a[key][i]], jnp.int32))
        row = jax.device_get(logits[0, at - 1])
        margin = float(row[a[key][i][at]] - row[b[key][i][at]])
        allowed = NEAR_TIE * float(abs(row).max())
        smoke.say(f"divergence {key}[{i}] at generated token "
                  f"{at - smoke.shape.prompt}: reference logits separate "
                  f"{a[key][i][at]} from {b[key][i][at]} by {margin:.4f}; "
                  f"a near-tie is below {allowed:.4f}")
        if not margin <= allowed:
            raise SmokeFailure(
                f"{key}[{i}] left the one-chip answer where the "
                f"reference is not tied: margin {margin:.4f} > "
                f"{allowed:.4f}")


def phase_attention_op(smoke: Smoke) -> None:
    """The decode kernel against a plain gather reference on one random
    pool at the smoke's widths: query positions on and around a page
    boundary, on and around the boundary of one fetched block of pages,
    and at the cap, between two rows that are not decoding (position
    -1, tables far outside the pool). The pool is handed over as the
    server stores it, two layers of [pages, page, kv_heads * d_head],
    and the kernel reads the second where it lies; the reference
    gathers that layer's pages per head. Compiled for the TPU a live
    row must agree with it in every bit: that is the contract
    ``paged_attention = "auto"`` rests on (kvcache._use_paged_kernel),
    held here where it holds; a dead row must come back as zeros. The
    CPU interpreter sums the weights-times-V contraction in another
    order than XLA:CPU's own einsum, so there live rows are held to
    rounding only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.ops import pallas_interpret
    from kvedge_tpu.ops.paged_attention import (
        block_pages, paged_decode_attention,
    )

    s = smoke.shape
    model = tomllib.loads(s.model)
    heads, kv = model["n_heads"], model["n_kv_heads"]
    dh, page = model["d_model"] // heads, s.page_size
    max_pages = s.seq // page
    block = block_pages(max_pages, page, kv * dh) * page  # its tokens
    lives = sorted({page - 1, page, page + 1, block - 1, block, s.seq - 1})
    lives = [-1, *(p for p in lives if p < s.seq), -1]
    with smoke.phase("attention-op") as entry:
        keys = jax.random.split(jax.random.PRNGKey(smoke.seed), 3)
        pages = len(lives) * max_pages + 1
        q = jax.random.normal(keys[0], (len(lives), heads, dh), jnp.bfloat16)
        layer = 1
        pool_k = jax.random.normal(keys[1], (2, pages, page, kv * dh),
                                   jnp.bfloat16)
        pool_v = jax.random.normal(keys[2], (2, pages, page, kv * dh),
                                   jnp.bfloat16)
        tables = 1 + np.arange(len(lives) * max_pages).reshape(len(lives), -1)
        pos = jnp.asarray(lives, jnp.int32)
        live = np.asarray(lives) >= 0
        tables = jnp.asarray(np.where(live[:, None], tables, 2 ** 30),
                             jnp.int32)

        def gather(q, pool_k, pool_v, tables, pos):
            b, span = q.shape[0], max_pages * page
            k = pool_k[layer, tables].reshape(b, span, kv, dh)
            v = pool_v[layer, tables].reshape(b, span, kv, dh)
            qg = q.reshape(b, 1, kv, heads // kv, dh)
            scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / (dh ** 0.5)
            seen = jnp.arange(span)[None, :] <= pos[:, None]
            scores = jnp.where(seen[:, None, None, None], scores,
                               jnp.finfo(q.dtype).min)
            weights = jax.nn.softmax(
                scores.astype(jnp.float32), -1).astype(q.dtype)
            out = jnp.einsum("bkgqs,bskd->bqkgd", weights, v)
            return out.reshape(b, heads, dh)

        want = np.asarray(jax.jit(gather)(
            q, pool_k, pool_v, jnp.where(live[:, None], tables, 0), pos))
        got = np.asarray(jax.jit(
            lambda *a: paged_decode_attention(
                *a, interpret=pallas_interpret())
        )(q, pool_k, pool_v, tables, pos, jnp.asarray(layer, jnp.int32)))
        bits = lambda x: x.view(np.uint16).astype(np.int32)  # noqa: E731
        dead_nonzero = int((bits(got[~live]) != 0).sum())
        got, want = got[live], want[live]
        differing = int((bits(got) != bits(want)).sum())
        worst = float(np.abs(got.astype(np.float32)
                             - want.astype(np.float32)).max())
        entry.update(elements=int(got.size), differing=differing,
                     max_abs_diff=worst, live_lengths=lives,
                     dead_nonzero=dead_nonzero)
        smoke.say(f"attention op, kernel vs plain gather at query "
                  f"positions {lives} (-1: a row that is not decoding): "
                  f"held bit for bit in live rows, {differing} of "
                  f"{got.size} bf16 outputs differ, max |diff| "
                  f"{worst:.3g}; held to zeros in dead rows, "
                  f"{dead_nonzero} outputs are not")
        if dead_nonzero:
            raise SmokeFailure(
                f"the decode kernel wrote {dead_nonzero} nonzero outputs "
                f"for rows that are not decoding")
        if differing and not pallas_interpret():
            raise SmokeFailure(
                f"the compiled decode kernel is not bit-identical to the "
                f"gather: {differing} of {got.size} outputs differ")
        if not np.isfinite(got.astype(np.float32)).all() or worst > 0.05:
            raise SmokeFailure("the decode kernel disagrees with the "
                               "gather reference beyond bf16 rounding")


def phase_ssm_op(smoke: Smoke) -> None:
    """The one-pass SSM step kernel (ops/ssm_step.py) against the plain
    ``models.ssm._one_token`` on one random stacked state at a small
    size it tiles (3 layers, 5 slots, 4 heads of 64, state 128; the
    first 4 slots are the batch's rows, one of them not decoding): the
    live rows' new state and ``y`` within float32 rounding, and every
    other layer, the row that is not decoding and the slot past the
    batch unchanged in every bit, after a call that donates the state.
    Compiled on the TPU, interpreted elsewhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.models.ssm import _one_token
    from kvedge_tpu.ops import pallas_interpret
    from kvedge_tpu.ops.ssm_step import ssm_step

    layers, slots, rows, heads, p, n, layer = 3, 5, 4, 4, 64, 128, 1
    with smoke.phase("ssm-op") as entry:
        keys = jax.random.split(jax.random.PRNGKey(smoke.seed), 6)
        state = jax.random.normal(keys[0], (layers, slots, heads * p, n),
                                  jnp.float32)
        x = jax.random.normal(keys[1], (rows, heads, p), jnp.float32)
        b = jax.random.normal(keys[2], (rows, n), jnp.float32)
        c = jax.random.normal(keys[3], (rows, n), jnp.float32)
        dt = jax.random.uniform(keys[4], (rows, heads), jnp.float32,
                                1e-3, 1e-1)
        a = -jax.random.uniform(keys[5], (heads,), jnp.float32, 1.0, 16.0)
        live = np.array([True, True, False, True])
        before = np.asarray(state)
        want_y, want = jax.jit(_one_token)(state[layer, :rows], x, b, c,
                                           dt, a)
        got_y, got = jax.jit(
            lambda *args: ssm_step(*args, interpret=pallas_interpret()),
            donate_argnums=(0,),
        )(state, jnp.asarray(layer, jnp.int32), x, b, c, dt, a,
          jnp.asarray(live))
        got, got_y = np.asarray(got), np.asarray(got_y)[live]
        want, want_y = np.asarray(want)[live], np.asarray(want_y)[live]
        touched = np.zeros(before.shape[:2], bool)
        touched[layer, :rows] = live
        moved = int((got[~touched].view(np.uint32)
                     != before[~touched].view(np.uint32)).sum())
        new = got[layer, :rows][live]
        differing = int((new.view(np.uint32) != want.view(np.uint32)).sum())
        state_gap = float(np.abs(new - want).max() / np.abs(want).max())
        y_gap = float(np.abs(got_y - want_y).max() / np.abs(want_y).max())
        entry.update(elements=int(new.size), differing=differing,
                     state_rel_gap=state_gap, y_rel_gap=y_gap,
                     untouched_moved=moved)
        smoke.say(f"ssm op, kernel vs plain one-token form at {rows} rows "
                  f"of {heads} heads of {p}, state {n}, layer {layer} of "
                  f"{layers}: {differing} of {new.size} float32 elements "
                  f"of the new state differ, largest gap {state_gap:.3g} "
                  f"of its scale; y within {y_gap:.3g} of its scale; "
                  f"{moved} elements of the other layers, the dead row "
                  f"and the slot past the batch moved")
        if moved:
            raise SmokeFailure(
                f"the ssm step kernel changed {moved} elements of state "
                f"it was not asked to touch")
        if not (state_gap <= 1e-6 and y_gap <= 1e-5):
            raise SmokeFailure("the ssm step kernel disagrees with the "
                               "plain one-token form beyond float32 "
                               "rounding")


def phase_delta_op(smoke: Smoke) -> None:
    """The delta-rule mixer's two forms (models/delta.py) against the
    recurrence written out, on one random stacked state at a small size
    (3 layers, 5 slots, 4 heads of 128 key and 128 value channels, 48
    positions): the recurrence position by position in numpy float64 is
    what both are held to; the one-token form steps layer 1's first 4
    slots through the positions, the chunk form runs the same positions
    in blocks of 16 from the same state (its triangular solve as the
    backend expands it). Compiled by the backend's own compiler: on the
    TPU this is where a product or a solve in less than float32 would
    show. Then the one-pass step kernel (ops/delta_step.py) against
    ``_one_token`` on the whole stacked state, one position, the third
    of the four rows not decoding, after a call that donates the state:
    the live rows' new state and ``o`` within float32 rounding, and
    every other layer, the row that is not decoding and the slot past
    the batch unchanged in every bit. Compiled on the TPU, interpreted
    elsewhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.models import delta
    from kvedge_tpu.ops import pallas_interpret
    from kvedge_tpu.ops.delta_step import delta_step

    layers, slots, rows, heads, dk, dv, layer = 3, 5, 4, 4, 128, 128, 1
    positions, block = 48, 16
    with smoke.phase("delta-op") as entry:
        keys = jax.random.split(jax.random.PRNGKey(smoke.seed), 6)
        state = 0.1 * jax.random.normal(
            keys[0], (layers, slots, heads, dk, dv), jnp.float32)
        shape = (rows, positions, heads)
        q = delta._l2norm(jax.random.normal(keys[1], (*shape, dk))) \
            * dk ** -0.5
        k = delta._l2norm(jax.random.normal(keys[2], (*shape, dk)))
        v = jax.random.normal(keys[3], (*shape, dv))
        # decays from none at all to all of it within a position
        g = -jnp.exp(jax.random.uniform(keys[4], (*shape, dk), jnp.float32,
                                        -12.0, 4.0))
        beta = 2.0 * jax.nn.sigmoid(
            2.0 * jax.random.normal(keys[5], shape))
        start = state[layer, :rows]

        @jax.jit
        def stepped(S):
            def step(S, now):
                o, S = delta._one_token(S, *now)
                return S, o
            S, o = jax.lax.scan(
                step, S, tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
            return o.swapaxes(0, 1), S

        @jax.jit
        def chunked(S):
            out = []
            for lo in range(0, positions, block):
                o, S = jax.vmap(delta._block)(
                    S, *(a[:, lo:lo + block] for a in (q, k, v, g, beta)))
                out.append(o)
            return jnp.concatenate(out, axis=1), S

        f64 = [np.asarray(a, np.float64) for a in (q, k, v, g, beta)]
        want_S = np.asarray(start, np.float64)
        want_o = np.zeros((rows, positions, heads, dv))
        for t in range(positions):
            qt, kt, vt, gt, bt = (a[:, t] for a in f64)
            decayed = np.exp(gt)[..., None] * want_S
            u = bt[..., None] * (vt - np.einsum("rhkv,rhk->rhv", decayed,
                                                kt))
            want_S = decayed + kt[..., None] * u[:, :, None, :]
            want_o[:, t] = np.einsum("rhkv,rhk->rhv", want_S, qt)
        gaps = {}
        for name, form in (("one-token", stepped), ("chunk", chunked)):
            o, S = form(start)
            gaps[name] = (
                float(np.abs(np.asarray(o) - want_o).max()
                      / np.abs(want_o).max()),
                float(np.abs(np.asarray(S) - want_S).max()
                      / np.abs(want_S).max()))
        entry.update(positions=positions, block=block, gaps=gaps)
        smoke.say(
            f"delta op, {rows} rows of {heads} heads of {dk} x {dv} over "
            f"{positions} positions against the recurrence in float64: "
            + "; ".join(f"the {name} form's outputs within {o:.3g} of their "
                        f"scale and its state within {S:.3g}"
                        for name, (o, S) in gaps.items()))
        if not all(o <= 2e-5 and S <= 2e-5 for o, S in gaps.values()):
            raise SmokeFailure("a form of the delta-rule mixer disagrees "
                               "with the recurrence beyond float32 "
                               "rounding")

        now = [a[:, 0] for a in (q, k, v, g, beta)]
        live = np.array([True, True, False, True])
        before = np.asarray(state)
        want_o, want = jax.jit(delta._one_token)(start, *now)
        got_o, got = jax.jit(
            lambda *args: delta_step(*args, interpret=pallas_interpret()),
            donate_argnums=(0,),
        )(state, jnp.asarray(layer, jnp.int32), *now, jnp.asarray(live))
        got, got_o = np.asarray(got), np.asarray(got_o)
        want, want_o = np.asarray(want)[live], np.asarray(want_o)[live]
        touched = np.zeros(before.shape[:2], bool)
        touched[layer, :rows] = live
        moved = int((got[~touched].view(np.uint32)
                     != before[~touched].view(np.uint32)).sum())
        moved += int((got_o[~live] != 0).sum())
        new = got[layer, :rows][live]
        differing = int((new.view(np.uint32) != want.view(np.uint32)).sum())
        state_gap = float(np.abs(new - want).max() / np.abs(want).max())
        o_gap = float(np.abs(got_o[live] - want_o).max()
                      / np.abs(want_o).max())
        entry.update(elements=int(new.size), differing=differing,
                     state_rel_gap=state_gap, o_rel_gap=o_gap,
                     untouched_moved=moved)
        smoke.say(f"delta op, kernel vs plain one-token form at {rows} rows "
                  f"of {heads} heads of {dk} x {dv}, layer {layer} of "
                  f"{layers}: {differing} of {new.size} float32 elements "
                  f"of the new state differ, largest gap {state_gap:.3g} "
                  f"of its scale; o within {o_gap:.3g} of its scale; "
                  f"{moved} elements of the other layers, the dead row "
                  f"and the slot past the batch moved")
        if moved:
            raise SmokeFailure(
                f"the delta step kernel changed {moved} elements of state "
                f"it was not asked to touch")
        if not (state_gap <= 2e-6 and o_gap <= 1e-5):
            raise SmokeFailure("the delta step kernel disagrees with the "
                               "plain one-token form beyond float32 "
                               "rounding")


def phase_reference_one_chip(smoke: Smoke):
    """What the four-chip path is compared with: the same steps and the
    same greedy requests on ONE chip of this process — params and pool
    on the default device, no mesh. Library entry points (the runtime's
    mesh always spans every visible device), same corpus, same seed.
    Returns (losses, greedy tokens, model config, params on the host)."""
    import jax
    import numpy as np

    from kvedge_tpu.data.feeder import open_feeder
    from kvedge_tpu.models.training import run_training
    from kvedge_tpu.runtime.devicecheck import run_device_check
    from kvedge_tpu.runtime.workload import _build_serve, train_model_config

    s = smoke.shape
    # paged_attention = "gather", as the sharded server pins itself: the
    # two sides then differ in the mesh and in nothing else.
    cfg = smoke.config(kind="serve", state="state-ref",
                       mesh="{ data = 2, model = 2 }",
                       paged_attention="gather")
    tcfg, _ = train_model_config(cfg)
    with smoke.phase("reference-train[1 chip]") as entry:
        with open_feeder(smoke.corpus, batch=s.batch, seq=s.seq) as feeder:
            result = run_training(
                tcfg, cfg.state_dir, num_steps=s.steps,
                batches=(np.asarray(b) % tcfg.vocab for b in feeder),
                checkpoint_every=s.checkpoint_every)
        losses = [round(x, 6) for x in result.losses]
        entry["losses"] = losses
        smoke.say(f"reference train on {jax.devices()[0]}: loss per step "
                  f"{losses}")
        _check_losses(smoke, losses)
    with smoke.phase("reference-serve[1 chip]"):
        check, serve_fn = _build_serve(
            cfg, run_device_check(cfg), tcfg, result.params, result.step)
        try:
            if not check.ok:
                raise SmokeFailure(f"reference serve: {check.error}")
            prompts = _prompts(smoke)
            docs = {"solo": [prompts["solo"]],
                    "pair0": [prompts["pair"][0]],
                    "pair1": [prompts["pair"][1]],
                    "batch": prompts["batch"]}
            greedy = {k: serve_fn({"tokens": rows, "n_new": s.n_new})["tokens"]
                      for k, rows in docs.items()}
        finally:
            serve_fn.close()
    host_params = jax.device_get(result.params)
    del result, serve_fn
    gc.collect()
    return losses, greedy, tcfg, host_params


def device_memory(smoke: Smoke, when: str) -> list:
    import jax

    per_device = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        per_device.append({"id": d.id,
                           "bytes_in_use": stats.get("bytes_in_use"),
                           "peak_bytes_in_use":
                               stats.get("peak_bytes_in_use")})
    smoke.say(f"memory_stats per device, {when} (bytes): "
              + json.dumps(per_device))
    return per_device


# ---- the two runs -------------------------------------------------------


def run_one_chip(smoke: Smoke) -> None:
    mesh = "{ data = 0, model = 1 }"
    phase_corpus(smoke)
    phase_train(smoke, state="state", mesh=mesh)
    on_tpu = smoke.platform == "tpu"
    kernel = phase_serve(smoke, state="state", mesh=mesh, label="auto",
                         full=True, want_kernel=on_tpu)
    # A second state dir over the same checkpoints: the first server's
    # persisted prefix cache must not hand this one its K/V.
    checkpoints = os.path.join(smoke.workdir, "state", "checkpoints")
    gather = phase_serve(smoke, state="state-gather", mesh=mesh,
                         label="gather", checkpoint_dir=checkpoints,
                         paged_attention="gather", full=False,
                         want_kernel=False)
    parted = compare_tokens(smoke, "paged_attention auto (kernel on the "
                            "chip) vs gather", kernel, gather)
    phase_attention_op(smoke)
    phase_ssm_op(smoke)
    phase_delta_op(smoke)
    if parted:
        # "auto" is a routing choice only while both paths give one
        # answer; the op comparison above says how far apart they are.
        raise SmokeFailure("an 'auto' and a 'gather' server answered the "
                           f"same greedy requests differently: {parted}")


def run_four_chips(smoke: Smoke) -> None:
    mesh = "{ data = 2, model = 2 }"
    phase_corpus(smoke)
    ref_losses, ref_tokens, tcfg, ref_params = phase_reference_one_chip(smoke)
    losses = phase_train(smoke, state="state", mesh=mesh)
    worst = max(abs(a - b) for a, b in zip(ref_losses, losses))
    smoke.report.setdefault("comparisons", {})["losses"] = {
        "one_chip": ref_losses, "four_chips": losses,
        "max_abs_diff": worst, "tolerance": LOSS_TOLERANCE}
    smoke.say(f"compare losses one chip vs {mesh}: max |diff| "
              f"{worst:.4f} (tolerance {LOSS_TOLERANCE})")
    if worst > LOSS_TOLERANCE:
        raise SmokeFailure("four-chip losses left the one-chip ones by "
                           f"{worst:.4f} > {LOSS_TOLERANCE}")
    tokens = phase_serve(smoke, state="state", mesh=mesh, label=mesh,
                         full=False, want_kernel=False)
    parted = compare_tokens(smoke, f"one chip vs {mesh}", ref_tokens, tokens)
    check_near_ties(smoke, tcfg, ref_params, ref_tokens, tokens, parted)
    phases = smoke.report["phases"]
    if not phases["train[state]"]["train_step"]["arguments_split"]:
        raise SmokeFailure("the four-chip train step split no argument "
                           "over a mesh axis")
    served = phases[f"serve[{mesh}]"]
    if not any(served["decode_window"]["collectives"].values()):
        raise SmokeFailure("decode window: no collective in a program "
                           "that spans four chips")
    # Params and pool live on every chip, not on device 0 with the rest
    # idle: read while the four-chip server was up.
    in_use = [m["bytes_in_use"] for m in served["memory"]]
    if None not in in_use and min(in_use) < 0.5 * max(in_use):
        raise SmokeFailure(f"device memory is lopsided: {in_use}")


def run_phases(shape: SmokeShape, *, chips: int, platform: str, seed: int,
               workdir: str, cache_dir: str = "",
               cache_was_empty: bool = True) -> tuple[bool, dict]:
    """Run every phase of the one- or four-chip smoke; (ok, report).

    ``platform`` is what the runtime configs expect ("tpu" from the
    script; the CPU tests pass "cpu" and a tiny shape). Failures are
    reported, not raised: the caller prints the last line either way.
    """
    with CompileMeter() as meter:
        smoke = Smoke(shape, chips=chips, platform=platform, seed=seed,
                      workdir=workdir, meter=meter)
        started = time.perf_counter()
        ok = False
        try:
            phase_environment(smoke, cache_dir, cache_was_empty)
            (run_four_chips if chips == 4 else run_one_chip)(smoke)
            ok = True
        except Exception as e:
            smoke.report["error"] = f"{type(e).__name__}: {e}"
            smoke.say(f"FAILED: {smoke.report['error']}")
            if not isinstance(e, SmokeFailure):
                traceback.print_exc(file=sys.stdout)
        totals = meter.read()
        smoke.report["totals"] = {
            "wall_s": round(time.perf_counter() - started, 2),
            "compile_s": round(totals["trace_s"] + totals["lower_s"]
                               + totals["compile_s"], 2),
            "xla_compile_s": round(totals["compile_s"], 2),
            "cache_hits": int(totals["cache_hits"]),
            "cache_misses": int(totals["cache_misses"]),
        }
        slowest = sorted(meter.programs, key=lambda p: -p[1])[:8]
        smoke.report["slowest_compiles"] = [
            [name, round(secs, 2)] for name, secs in slowest]
        smoke.say(f"totals: {smoke.report['totals']}; slowest compiles "
                  f"{smoke.report['slowest_compiles']}")
    return ok, smoke.report


def last_line(ok: bool, device: dict) -> str:
    """The contract's final stdout line. Never ok off the TPU."""
    return json.dumps({"ok": bool(ok and device["platform"] == "tpu"),
                       "device": device})


def _one_chip_only() -> None:
    """Before JAX starts: let libtpu open one chip, whatever the host
    holds. A one-chip host is unchanged; an operator's own setting of
    any of these wins."""
    os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
    os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    if args.chips == 1:
        _one_chip_only()

    from kvedge_tpu.runtime.compilecache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"[chip-smoke] no TPU: JAX found {device}; nothing was run",
              flush=True)
        print(last_line(False, device), flush=True)
        return 1
    cache_was_empty = not (os.path.isdir(cache_dir) and os.listdir(cache_dir))
    workdir = tempfile.mkdtemp(prefix="kvedge-chip-smoke-")
    try:
        ok, report = run_phases(
            SHAPE_209M, chips=args.chips, platform="tpu", seed=args.seed,
            workdir=workdir, cache_dir=cache_dir,
            cache_was_empty=cache_was_empty)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    # The report of the run before this one, if this checkout saw one,
    # says what the compile cache saved; then this run's replaces it.
    path = os.path.join(REPO, "chiprun_out",
                        f"chip_smoke_{args.chips}chip.json")
    with contextlib.suppress(OSError, ValueError, KeyError):
        with open(path) as fh:
            before = json.load(fh)
        was, now = before["totals"], report["totals"]
        was_empty = before["environment"]["compile_cache_empty_at_start"]
        print(f"[chip-smoke] compile seconds: this run {now['compile_s']} "
              f"(cache {'empty' if cache_was_empty else 'warm'} at start, "
              f"{now['cache_hits']} hits), the run before "
              f"{was['compile_s']} (cache "
              f"{'empty' if was_empty else 'warm'} at start): difference "
              f"{round(was['compile_s'] - now['compile_s'], 2)} s; wall "
              f"{now['wall_s']} s against {was['wall_s']} s", flush=True)
    with contextlib.suppress(OSError):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            json.dump(report, fh, indent=1)
    print(last_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
