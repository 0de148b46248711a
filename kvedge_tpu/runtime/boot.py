"""Runtime boot orchestration: applied config -> payload -> heartbeat + status.

This is what ``kvedge-runtime boot`` (the final ``runcmd`` of the boot
document) executes — the analogue of the IoT Edge daemon starting after
``iotedge config apply`` (``_helper.tpl:74``). In a real pod it never
returns; ``once=True`` performs a single heartbeat cycle for tests and
local verification.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Callable

from kvedge_tpu.config.runtime_config import RuntimeConfig
from kvedge_tpu.parallel.distributed import DistributedState, maybe_initialize
from kvedge_tpu.runtime import heartbeat, recovery
from kvedge_tpu.runtime.compilecache import enable_compile_cache
from kvedge_tpu.runtime.devicecheck import DeviceCheckResult, run_device_check
from kvedge_tpu.runtime.profiling import CaptureUnavailable, TraceCapture
from kvedge_tpu.runtime.status import GenerateUnavailable, StatusServer


@dataclasses.dataclass
class RuntimeHandle:
    """A started runtime: payload result, heartbeat writer, status server."""

    cfg: RuntimeConfig
    check: DeviceCheckResult
    writer: heartbeat.HeartbeatWriter
    server: StatusServer
    boot_count: int
    started_at: float
    distributed: DistributedState = dataclasses.field(
        default_factory=lambda: DistributedState(active=False)
    )
    # Set by the ``serve`` payload once its model is restored; the status
    # server's POST /generate routes through it.
    serve_fn: Callable[[dict], dict] | None = None

    @property
    def status_port(self) -> int:
        return self.server.port

    def snapshot(self) -> dict:
        last = heartbeat.read_heartbeat(self.cfg.state_dir) or {}
        return {
            "name": self.cfg.name,
            "ok": self.check.ok,
            "payload": self.cfg.payload,
            "check": self.check.to_dict(),
            "boot_count": self.boot_count,
            "uptime_s": round(time.time() - self.started_at, 3),
            "heartbeat_seq": last.get("seq", 0),
            "heartbeat_age_s": (
                round(time.time() - last["ts"], 3) if "ts" in last else None
            ),
            "distributed": self.distributed.to_dict(),
            # Supervision history from the native PID-1 supervisor
            # (native/kvedge-init.cc) — restarts, give-ups, forwarded
            # signals — persisted on the state volume across pod
            # generations: the pod-world `systemctl status`.
            "init_events": heartbeat.read_init_events(self.cfg.state_dir),
            # Live (or last-known) train-payload progress; None unless a
            # train payload has written it.
            "train_progress": heartbeat.read_train_progress(
                self.cfg.state_dir
            ),
            # Serving request/pool stats; None unless the serve payload
            # is live (runtime/workload.py attaches .stats to serve_fn).
            "serving": (
                self.serve_fn.stats()
                if getattr(self.serve_fn, "stats", None) is not None
                else None
            ),
            # Post-mortem of the last serving failure, persisted on the
            # state volume (runtime/heartbeat.py) — survives rescheduling
            # so the replacement pod reports why its predecessor died.
            "last_failure": heartbeat.read_failure_record(
                self.cfg.state_dir
            ),
        }

    def shutdown(self) -> None:
        self.writer.stop()
        self.server.shutdown()
        # The serve payload's backend may own a decode thread + device
        # page pool (models/serving.py); release them with the runtime.
        closer = getattr(self.serve_fn, "close", None)
        if closer is not None:
            closer()


def _degraded(error: str) -> DeviceCheckResult:
    """A failed check that still serves /status (degraded, debuggable from
    outside — like ssh-ing into a VM whose payload daemon failed) instead
    of crash-looping the pod with a raw traceback."""
    return DeviceCheckResult(
        ok=False, platform="unknown", device_count=0, device_kinds=(),
        mesh_axes=(), mesh_shape=(), probe_ms=0.0, probe_checksum=0.0,
        error=error,
    )


def _topology_mismatch(cfg: RuntimeConfig) -> str:
    """Non-empty iff the chart topology and the config TOML disagree.

    The multi-host chart re-states its replica count as
    ``KVEDGE_EXPECTED_PROCESSES`` (render/manifests.py:runtime_statefulset);
    plain Helm cannot parse the config TOML at install time, so this
    boot-time check is what catches a TOML whose ``[distributed]`` section
    is missing or wrong — otherwise N pods would boot as N healthy,
    *independent* single-host runtimes and the misconfiguration would be
    invisible.
    """
    expected_raw = os.environ.get("KVEDGE_EXPECTED_PROCESSES", "")
    if not expected_raw:
        return ""
    try:
        expected = int(expected_raw)
    except ValueError:
        return f"KVEDGE_EXPECTED_PROCESSES={expected_raw!r} is not an integer"
    if expected != cfg.distributed.num_processes:
        return (
            f"topology mismatch: the chart was rendered for {expected} "
            f"hosts (KVEDGE_EXPECTED_PROCESSES) but the runtime config "
            f"declares [distributed] num_processes="
            f"{cfg.distributed.num_processes}; fix the config TOML"
        )
    return ""


def _booting() -> DeviceCheckResult:
    """The pre-payload state served while boot work is still in flight."""
    return DeviceCheckResult(
        ok=False, platform="booting", device_count=0, device_kinds=(),
        mesh_axes=(), mesh_shape=(), probe_ms=0.0, probe_checksum=0.0,
        error="boot in progress (multi-host join / payload not finished)",
    )


def _run_payload(cfg: RuntimeConfig,
                 handle: "RuntimeHandle") -> DeviceCheckResult:
    if cfg.payload == "none":
        return DeviceCheckResult(
            ok=True, platform="skipped", device_count=0, device_kinds=(),
            mesh_axes=(), mesh_shape=(), probe_ms=0.0, probe_checksum=0.0,
        )
    try:
        if cfg.payload == "transformer-probe":
            from kvedge_tpu.runtime.workload import run_transformer_probe

            return run_transformer_probe(cfg)
        if cfg.payload == "inference-probe":
            from kvedge_tpu.runtime.workload import run_inference_probe

            return run_inference_probe(cfg)
        if cfg.payload == "train":
            from kvedge_tpu.runtime.workload import run_train_payload

            return run_train_payload(cfg)
        if cfg.payload == "eval":
            from kvedge_tpu.runtime.workload import run_eval_payload

            return run_eval_payload(cfg)
        if cfg.payload == "serve":
            from kvedge_tpu.runtime.workload import run_serve_payload

            check, serve_fn = run_serve_payload(cfg)
            handle.serve_fn = serve_fn
            return check
        return run_device_check(cfg)
    except Exception as e:
        return _degraded(f"payload {cfg.payload!r} failed: {e!r}")


def start_runtime(cfg: RuntimeConfig) -> RuntimeHandle:
    """Start the status server, run the boot work, keep the heartbeat going.

    The status server starts FIRST, serving the ``booting`` state, because
    the boot work can block for minutes: a multi-host join waits for every
    pod in the slice, and the first payload compile is slow. If the server
    only came up afterwards, kubelet's liveness probe (which targets
    /version) would kill and restart the pod mid-join — precisely the
    crash-loop the degraded-state design exists to avoid.
    """
    started_at = time.time()
    enable_compile_cache()  # before the payload's first compile
    boot_count = heartbeat.next_boot_count(cfg.state_dir)

    handle: RuntimeHandle = None  # assigned below; closures capture it

    # Every consumer (heartbeat, /healthz, /status) reads handle.check —
    # one source of truth, so a later update (e.g. a re-probe) cannot
    # leave the endpoints disagreeing about health.
    def build_heartbeat() -> dict:
        return {
            "name": cfg.name,
            "ok": handle.check.ok,
            "payload": cfg.payload,
            "boot_count": boot_count,
            "check": handle.check.to_dict(),
        }

    writer = heartbeat.HeartbeatWriter(
        cfg.state_dir, cfg.heartbeat_interval_s, build_heartbeat
    )

    # The profiler must not run before boot completes: a capture touches
    # the JAX backend, and initializing the backend from the handler
    # thread would permanently break the multi-host join below
    # (jax.distributed.initialize must precede any backend init).
    boot_complete = threading.Event()
    trace_capture = TraceCapture(cfg.state_dir)

    def profile(seconds: float) -> dict:
        if not boot_complete.is_set():
            raise CaptureUnavailable(
                "runtime is still booting; retry once /status shows the "
                "payload check"
            )
        return trace_capture.capture(seconds)

    def generate(doc: dict) -> dict:
        # The handler thread reads handle.serve_fn at request time: it is
        # None until the serve payload finishes restoring its model.
        if handle.serve_fn is None:
            raise GenerateUnavailable(
                "no generation backend yet (payload is not 'serve', it "
                "failed, or the runtime is still booting)"
            )
        return handle.serve_fn(doc)

    def trace_doc() -> dict | None:
        # GET /trace: the serving flight recorder as Chrome trace-event
        # JSON. Read at request time — None (404) until the serve
        # payload is live AND [payload] serving_trace is enabled.
        tracer = getattr(handle.serve_fn, "tracer", None)
        return tracer.export_chrome() if tracer is not None else None

    def profile_traces() -> list:
        # GET /profile/traces: on-disk profiler captures under
        # <state_dir>/traces/ (newest last; TraceCapture.list).
        return trace_capture.list()

    def slo_doc() -> dict | None:
        # GET /slo: the rolling SLI + burn-rate document. Read at
        # request time — None (404) until the serve payload is live
        # AND [payload] serving_slo is enabled.
        fn = getattr(handle.serve_fn, "slo", None)
        return fn() if fn is not None else None

    def bundle_doc() -> dict | None:
        # GET /debug/bundle: the flight-recorder bundle, assembled on
        # demand under one server lock acquisition so its metrics,
        # SLO state, and page books are mutually consistent.
        fn = getattr(handle.serve_fn, "bundle", None)
        return fn() if fn is not None else None

    def serve_degraded() -> str | None:
        # Lock-free by contract (workload.py attaches a plain attribute
        # read): /healthz is hit by liveness probes every few seconds
        # and must never queue behind the serving work lock.
        fn = getattr(handle.serve_fn, "degraded", None)
        return fn() if fn is not None else None

    def health_detail() -> dict | None:
        # Enriches an unhealthy /healthz body. A poisoned serving pool
        # under active recovery (runtime/recovery.py) reports 503
        # NON-terminal with a retry-after hint, so probes
        # (healthcheck.wait_healthy) keep polling through the heal;
        # without a supervisor — or after its escalation — the poison
        # is terminal (it only clears by rescheduling) and probes stop
        # polling early.
        reason = serve_degraded()
        if reason is not None:
            rec = getattr(handle.serve_fn, "recovery", None)
            if rec is not None:
                try:
                    doc = rec()
                except Exception:
                    doc = None
                if doc and doc.get("state") == "recovering":
                    out = {"reason": reason, "terminal": False,
                           "recovering": True}
                    # Always a retry hint: the supervisor's measured
                    # estimate when it has one, else the operator's
                    # configured reschedule window — a recovering 503
                    # must never leave the client guessing.
                    out["retry_after_s"] = (
                        doc["retry_after_s"]
                        if doc.get("retry_after_s") is not None
                        else cfg.serving_retry_after_s
                    )
                    # Capacity context (pages_free, pages_total,
                    # bucket) rides along when the serve path exposes
                    # its lock-free probe — operators triaging a
                    # recovery see how much pool the revive must
                    # rebuild without touching the work lock.
                    cap = getattr(handle.serve_fn, "capacity", None)
                    if cap is not None:
                        try:
                            out.update(cap())
                        except Exception:
                            pass
                    return out
            return {"reason": reason, "terminal": True}
        if not handle.check.ok and handle.check.error:
            return {"reason": handle.check.error}
        return None

    server = StatusServer(
        cfg.status_bind, cfg.status_port,
        snapshot=lambda: handle.snapshot(),
        healthy=lambda: handle.check.ok and serve_degraded() is None,
        profiler=profile,
        token=cfg.status_token,
        generator=generate,
        health_detail=health_detail,
        trace_doc=trace_doc,
        profile_traces=profile_traces,
        slo_doc=slo_doc,
        bundle_doc=bundle_doc,
    )
    handle = RuntimeHandle(
        cfg=cfg, check=_booting(), writer=writer, server=server,
        boot_count=boot_count, started_at=started_at,
        distributed=DistributedState(active=False),
    )
    # Sweep atomic-write leftovers before anything writes to the state
    # dir: a SIGKILL mid-dump strands `<name>.tmp` (a prefix dump can be
    # hundreds of MB) and no other writer exists this early, so every
    # surviving tmp is garbage by definition.
    swept = recovery.sweep_stranded_tmp(cfg.state_dir)
    if swept:
        print(f"[kvedge-boot] swept {len(swept)} stranded tmp file(s) "
              f"from the state dir: {', '.join(swept)}", flush=True)
    writer.beat_once()  # heartbeat visible before the server answers
    server.start()

    # Multi-host: join the cross-host JAX cluster BEFORE the payload, so
    # jax.devices() sees the whole slice. A join failure degrades the pod
    # (status stays queryable) instead of crash-looping it.
    topo_error = _topology_mismatch(cfg)
    if topo_error:
        handle.check = _degraded(topo_error)
    else:
        try:
            handle.distributed = maybe_initialize(cfg.distributed)
        except Exception as e:
            handle.check = _degraded(
                f"multi-host join failed "
                f"(num_processes={cfg.distributed.num_processes}): {e!r}"
            )
        else:
            handle.check = _run_payload(cfg, handle)
    boot_complete.set()  # safe to touch the backend from handler threads now
    writer.beat_once()  # refresh: the booting heartbeat is now stale
    return handle


class DegradedBoot(RuntimeError):
    """``boot(once=True)`` finished with a degraded check."""


def boot(config_path: str, once: bool = False, root: str = "/") -> None:
    """Entry for ``kvedge-runtime boot --config <path>``.

    ``root`` is accepted for signature symmetry with the other boot
    commands; paths inside the config were already rebased when
    ``kvedge-bootstrap apply`` wrote it.

    A long-running pod whose payload failed stays up degraded, so it
    can be debugged through /status. ``once=True`` has no such reader:
    its only result is the exit code, so a degraded check raises
    :class:`DegradedBoot` after the shutdown.
    """
    del root
    with open(config_path, "r", encoding="utf-8") as fh:
        cfg = RuntimeConfig.parse(fh.read())
    handle = start_runtime(cfg)
    print(
        f"[kvedge-runtime] {cfg.name}: payload={cfg.payload} "
        f"ok={handle.check.ok} devices={handle.check.device_count} "
        f"status=:{handle.status_port} boot_count={handle.boot_count}",
        flush=True,
    )
    if not handle.check.ok:
        # Degraded: keep serving /status (debuggable from outside, like
        # ssh-ing into a VM whose payload failed), but say so loudly.
        print(f"[kvedge-runtime] DEGRADED: {handle.check.error}", flush=True)
    if once:
        handle.shutdown()
        if not handle.check.ok:
            raise DegradedBoot(handle.check.error)
        return
    try:
        handle.writer.run()  # heartbeat loop on the main thread, forever
    finally:
        handle.shutdown()
