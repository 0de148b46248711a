"""One server, one load generator, one window.

The server is the product's own: ``start_runtime`` on a ``[payload] kind
= "serve"``, ``serving = "paged"`` document, asked over HTTP ``POST
/generate`` with streaming. The load generator is a child process that
never imports JAX (``loadgen.py``). This process holds the chip, samples
the server's own counters while the window runs, and, in a traced run,
profiles a few seconds in the middle of it.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from benchmark import cellspec, schedule

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_LOWERED = _COMPILE_EVENTS[1]  # once per new program, cached or not
TRACE_SECONDS = 4.0
SAMPLE_EVERY = 0.25


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling, and when each
    new program was lowered (on the monotonic clock)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs: list[tuple[str, float]] = []  # (name, lowered at)

    def _on_duration(self, event, duration, **kw):
        if event not in _COMPILE_EVENTS:
            return
        with self._lock:
            self.seconds += duration
            if event == _LOWERED:
                self.programs.append((str(kw.get("fun_name", "?")),
                                      time.monotonic()))

    def install(self) -> "CompileMeter":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        return self

    def lowered_between(self, lo: float, hi: float) -> list[str]:
        with self._lock:
            return [name for name, t in self.programs if lo <= t < hi]


class GcMeter:
    """Pauses of this process's garbage collector of 20 ms and more, as
    (began, seconds, generation): a stall of the host inside a window
    has one cause the report can then name or rule out."""

    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []
        self._began = 0.0

    def _on_gc(self, phase, info):
        now = time.monotonic()
        if phase == "start":
            self._began = now
        elif now - self._began >= 0.02:
            self.pauses.append((self._began, now - self._began,
                                info["generation"]))

    def install(self) -> "GcMeter":
        gc.callbacks.append(self._on_gc)
        return self

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


class Sampler(threading.Thread):
    """The server's counters every quarter second, stamped. ``stats()``
    takes the server's lock, which the decode loop holds while a window
    is dispatched and harvested: ``took`` is how long a sample waited."""

    def __init__(self, stats):
        super().__init__(name="bench-sampler", daemon=True)
        self._stats, self._stop_evt = stats, threading.Event()
        self.samples: list[dict] = []

    def run(self):
        keep = ("in_flight", "free_pages", "pages_total", "slots_total",
                "reserved_pages", "overlap_inflight_depth",
                "sched_queue_depth_interactive", "sched_queue_depth_batch",
                "tokens_done_total", "sched_preemptions_total")
        while not self._stop_evt.wait(SAMPLE_EVERY):
            asked = time.monotonic()
            s = self._stats()
            row = {k: s.get(k, 0) for k in keep}
            row["t"] = time.monotonic()
            row["took"] = row["t"] - asked
            self.samples.append(row)

    def stop(self):
        self._stop_evt.set()
        if self.is_alive():
            self.join(timeout=10)


class Harness:
    """Set-up once, then one or more windows against the same server."""

    def __init__(self, cell: cellspec.Cell, *, t_process: float,
                 overrides: dict | None = None):
        self.cell = cell
        self.t_process = t_process
        self.overrides = overrides or {}
        self.meter = CompileMeter()
        self.gc = GcMeter()
        self.split: dict = {}
        self.handle = None
        self._before: set = set()
        self.workdir = tempfile.mkdtemp(prefix="kvedge-bench-")

    # ---- set-up ---------------------------------------------------------

    def start(self, platform: str) -> None:
        """The serve payload through the normal entry point."""
        import jax

        from kvedge_tpu.config.runtime_config import RuntimeConfig
        from kvedge_tpu.runtime.boot import start_runtime

        self.meter.install()
        self.gc.install()
        self._before = {id(a) for a in jax.live_arrays()}
        doc = cellspec.runtime_document(
            self.cell, os.path.join(self.workdir, "state"), platform,
            self.overrides)
        cfg = RuntimeConfig.from_mapping(doc)
        t = time.monotonic()
        self.split["import_s"] = t - self.t_process
        self.handle = start_runtime(cfg)
        if not self.handle.check.ok or self.handle.serve_fn is None:
            error = self.handle.check.error
            self.stop()
            raise RuntimeError(f"the serve payload did not start: {error}")
        self.split["start_runtime_s"] = time.monotonic() - t
        self.stats = self.handle.serve_fn.stats

    def stop(self) -> None:
        """Shut the server down and free what it made on the device (the
        reference needs the room, and ``memory_peak_bytes`` stays the
        program's)."""
        import jax

        if self.handle is not None:
            self.handle.shutdown()
            self.handle = None
        self.stats = None
        self.gc.remove()
        gc.collect()
        for array in jax.live_arrays():
            if id(array) not in self._before:
                array.delete()
        shutil.rmtree(self.workdir, ignore_errors=True)

    # ---- one window -----------------------------------------------------

    def run_window(self, plan: dict, *, warm: bool, trace: bool,
                   tag: str = "") -> dict:
        """Warm-up (first window only), ramp, window, drain. Returns the
        load generator's records and everything sampled beside them."""
        port = self.handle.status_port
        out_path = os.path.join(self.workdir, f"records{tag}.json")
        plan = {**plan, "port": port, "out": out_path,
                "warmup": (schedule.warmup_requests(
                    self.cell.traffic, self.cell.load) if warm else [])}
        plan_path = os.path.join(self.workdir, f"plan{tag}.json")
        with open(plan_path, "w") as fh:
            json.dump(plan, fh)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        env["PYTHONPATH"] = cellspec.REPO
        t_warm = time.monotonic()
        child = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen", plan_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=cellspec.REPO)
        sampler = Sampler(self.stats)
        tracer = None
        try:
            line = child.stdout.readline().strip()
            if line != "warm":
                raise RuntimeError(
                    f"the load generator said {line!r}, exit code "
                    f"{child.wait(timeout=30)}")
            self.split["warm_s"] = time.monotonic() - t_warm
            t0 = time.monotonic() + 0.25 + plan["ramp_s"]
            child.stdin.write(f"go {t0!r}\n")
            child.stdin.flush()
            sampler.start()
            seconds = plan["seconds"]
            if trace:
                tracer = _TraceWindow(
                    os.path.join(self.workdir, f"trace{tag}"),
                    t0 + max(0.0, (seconds - TRACE_SECONDS) / 2),
                    min(TRACE_SECONDS, seconds))
                tracer.start()
            _sleep_until(t0)
            start = self.stats()
            _sleep_until(t0 + seconds)
            end = self.stats()
            memory = self.memory()
            rc = child.wait(timeout=plan["drain_s"] + 60)
            if rc != 0:
                raise RuntimeError(f"the load generator exited {rc}")
        finally:
            sampler.stop()
            if tracer is not None:
                tracer.join(timeout=120)
            if child.poll() is None:
                child.kill()
                child.wait()
        with open(out_path) as fh:
            child_out = json.load(fh)
        records = child_out["records"]
        for s in sampler.samples:
            s["t"] -= t0
        return {
            "t0": t0, "records": records, "samples": sampler.samples,
            "stats_start": start, "stats_end": end, "memory": memory,
            "window_lowered": self.meter.lowered_between(t0, t0 + seconds),
            "gc_pauses": [[at - t0, took, gen]
                          for at, took, gen in self.gc.pauses
                          if t0 <= at < t0 + seconds],
            "loadgen_stall_max_s": child_out["stall_max_s"],
            "trace_dir": tracer.path if tracer else None,
            "trace_at": (tracer.began - t0) if tracer else None,
        }

    def memory(self) -> dict:
        """Peak bytes on the fullest chip, as the runtime reports it."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()]
        return {"memory_peak_bytes": int(max(peaks))}


class _TraceWindow(threading.Thread):
    """The profiler on for a few seconds in the middle of the window."""

    def __init__(self, path: str, at: float, seconds: float):
        super().__init__(name="bench-trace", daemon=True)
        self.path, self.at, self.seconds = path, at, seconds
        self.began = at

    def run(self):
        import jax

        _sleep_until(self.at)
        jax.profiler.start_trace(self.path)
        self.began = time.monotonic()
        try:
            time.sleep(self.seconds)
        finally:
            jax.profiler.stop_trace()


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.2))
