"""The opaque runtime-config payload: a TOML document, applied at boot.

This is the analogue of the IoT Edge ``config.toml`` the reference treats as
an opaque value: the operator passes a TOML file at install time
(``--set-file azIotEdgeConfig=config.toml``, reference ``README.md:60``), the
chart base64's it into a Secret under the key ``userdata``
(``aziot-edge-runtime-config-secret.yaml:6``), the Secret surfaces in the
guest as a serial-tagged disk, and cloud-init copies it to
``/etc/aziot/config.toml`` and runs ``iotedge config apply``
(``_helper.tpl:70-74``).

Here the payload is the JAX runtime's config: mesh shape, expected TPU
topology, state/heartbeat layout, status endpoint, and which payload to run.
``kvedge config apply`` (:func:`RuntimeConfig.apply`) validates it and
materializes it at ``/etc/kvedge/config.toml``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tomllib
from typing import Mapping


def _toml_str(value: str) -> str:
    """Quote a string as a TOML basic string (JSON escaping is TOML-valid)."""
    return json.dumps(value, ensure_ascii=True)

DEFAULT_CONFIG_PATH = "/etc/kvedge/config.toml"
DEFAULT_STATE_DIR = "/var/lib/kvedge/state"

_VALID_PAYLOADS = (
    "devicecheck", "transformer-probe", "inference-probe", "train", "eval",
    "serve", "none",
)
# "" = auto (ring iff the mesh declares a seq axis); the rest match
# TransformerConfig.attention (models/transformer.py).
_VALID_ATTENTION = ("", "naive", "flash", "ring", "ulysses")


class RuntimeConfigError(ValueError):
    """Raised when the runtime config TOML fails validation."""


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical device-mesh shape the runtime should assemble.

    Axis order is meaningful: it is the order handed to
    ``jax.sharding.Mesh``. A zero value means "infer from device count"
    (at most one axis may be zero).
    """

    # Default: all visible devices on the data axis (0 = inferred).
    axes: tuple[tuple[str, int], ...] = (("data", 0), ("model", 1))

    def validate(self) -> None:
        if not self.axes:
            raise RuntimeConfigError("[mesh] axes must be a non-empty table")
        for axis, size in self.axes:
            if not axis:
                raise RuntimeConfigError("mesh axis names must be non-empty")
            if not isinstance(size, int) or isinstance(size, bool) or size < 0:
                raise RuntimeConfigError(
                    f"mesh axis {axis!r} size must be a non-negative int"
                )
        names = self.axis_names()
        if len(set(names)) != len(names):
            raise RuntimeConfigError(f"duplicate mesh axis names in {names}")
        if sum(1 for _, size in self.axes if size == 0) > 1:
            raise RuntimeConfigError("at most one mesh axis may be 0 (inferred)")

    def axis_names(self) -> tuple[str, ...]:
        return tuple(name for name, _ in self.axes)

    def resolved_shape(self, n_devices: int) -> tuple[int, ...]:
        """Concrete mesh shape for ``n_devices``, inferring any zero axis."""
        sizes = [size for _, size in self.axes]
        self.validate()
        zeros = [i for i, s in enumerate(sizes) if s == 0]
        fixed = 1
        for s in sizes:
            if s:
                fixed *= s
        if zeros:
            if n_devices % fixed:
                raise RuntimeConfigError(
                    f"{n_devices} devices not divisible by fixed axes ({fixed})"
                )
            sizes[zeros[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise RuntimeConfigError(
                f"mesh {dict(self.axes)} wants {fixed} devices, have {n_devices}"
            )
        return tuple(sizes)


# Model-shape presets the [model] section may name; the shape tables
# themselves live with the model (kvedge_tpu/models/transformer.py
# PRESETS) — this module stays importable without jax.
_VALID_PRESETS = ("", "probe", "flagship")


# [payload] keys retired in PR 48 with their old defaults: a document an
# older ``to_toml`` wrote still parses, anything else is refused by name.
_RETIRED_SPECULATION_KEYS = {
    "serving_speculative": 0,
    "serving_spec_window": 0,
    "serving_spec_sampled_window": True,
}


def _refuse_speculation(payload_doc: Mapping) -> None:
    for key, default in _RETIRED_SPECULATION_KEYS.items():
        value = payload_doc.get(key, default)
        if type(value) is not type(default) or value != default:
            raise RuntimeConfigError(
                f"[payload] {key} = {value!r} is refused: the paged "
                "server does not speculate since PR 48")


def _parse_window(value):
    """``serving_window``: an int window cap or the string "auto"
    (the online controller, SERVING.md rung 26, picks the window per
    boundary from measured R/t). Type errors surface in validate()
    with the full accepted-values message."""
    if isinstance(value, str):
        return value  # validate() accepts only "auto"
    return int(value)


def _parse_trace(value):
    """``serving_trace``: "off"/"on" or a per-request sample rate in
    (0, 1]. Type errors surface in validate() with the full
    accepted-values message."""
    if isinstance(value, str):
        return value  # validate() accepts only "off"/"on"
    if isinstance(value, bool):
        raise RuntimeConfigError(
            "[payload] serving_trace must be 'off', 'on' or a sample "
            "rate in (0, 1] — not a boolean"
        )
    return float(value)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """The payload model's architecture ([model] TOML section).

    The reference's most distinctive mechanism is an *opaque payload
    config* pipeline so the operator controls what the payload runs
    (reference ``_helper.tpl:61-74``, ``values.yaml:13-14``); here the
    model IS the payload, so its shape belongs in the same TOML. A
    ``preset`` names a base shape ("probe" — the tiny default — or
    "flagship", the 41.6M-param bench model); any explicitly-set field
    overrides the preset. Zero means "from the preset" (and for
    ``n_heads``/``experts``, "adapted to the mesh" — see
    runtime/workload.py derive_model_config). Explicitly-set values are
    authoritative: a mesh they cannot run on is *refused* with a clear
    error, never silently adjusted.
    """

    preset: str = ""  # "" = "probe"
    vocab: int = 0
    d_model: int = 0
    n_heads: int = 0
    # 0 here means "from the preset" (both presets are MHA); an explicit
    # value enables grouped-query attention (models/decode.py KV-cache
    # shrink by n_heads/n_kv_heads).
    n_kv_heads: int = 0
    n_layers: int = 0
    d_ff: int = 0
    # Mixture-of-experts expert count; 0 = derived from the mesh's
    # ``expert`` axis (dense when the mesh has none).
    experts: int = 0
    expert_top_k: int = 0  # 0 = 1 (Switch); 2 = GShard top-2
    # 0.0 = provably drop-free capacity (factor * top_k >= experts).
    expert_capacity_factor: float = 0.0
    # Pipeline backward schedule when the mesh has a ``stage`` axis:
    # "" / "gpipe" = GPipe + remat (general — composes with MoE and
    # sequence parallelism); "1f1b" = the fused 1F1B schedule with an
    # O(stages) activation stash (dense models, standard attention —
    # parallel/pipeline1f1b.py documents the refusals).
    pipeline_schedule: str = ""
    # A patterned block (models/hybrid.py; SERVING.md "Recurrent
    # state", "Two page pools"): one period of layer kinds, "attention",
    # "window" (attention over the last ``attention_window`` positions,
    # always rotary, its keys and values in a page pool of its own) and
    # at most one recurrent kind ("mamba" or "delta", sized by the
    # ``ssm_*`` keys),
    # repeated to ``n_layers``. () = every layer rotary attention with a
    # GELU feed-forward, the block above. With a pattern every layer's
    # feed-forward is ``experts`` routed experts of width ``d_ff``,
    # ``expert_top_k`` (any number) a token, plus a shared expert of
    # width ``shared_ff`` (0 = none), gated when ``ffn_gated`` by
    # ``ffn_activation`` ("" = "silu"; "relu"); ``router_before_mixer``
    # routes on the mixer's normed input; this device
    # holds ``experts_held`` of them (0 = all) from ``expert_first`` on.
    # ``head_dim`` is the attention heads' size (0 = d_model / n_heads),
    # ``attention_gate`` an output gate on the attention layers,
    # ``untied_head`` a head that is not the embedding.
    # ``dense_layers`` leading layers (counted in ``n_layers``) come
    # before the periods with a gated MLP of ``dense_ff`` in the experts'
    # place and the mixer the pattern, continued backwards, gives them.
    # ``router_score`` ("" = "softmax"; "sigmoid": each expert scored
    # alone, the gates the picked scores over their sum times
    # ``router_scale``, 0.0 = 1; ``router_bias``: a choice bias an
    # expert, drawn with the tree). ``qk_norm``: an RMSNorm of each
    # head's q and k; ``norm_after``: a sublayer's norm on its output,
    # none on its input.
    # Served by ``serving = "paged"`` on one device, nothing else.
    layer_pattern: tuple = ()
    dense_layers: int = 0
    dense_ff: int = 0
    router_score: str = ""  # "" = "softmax"
    router_bias: bool = False
    router_scale: float = 0.0
    qk_norm: bool = False
    norm_after: bool = False
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0   # 0 = 4
    ssm_chunk: int = 0  # 0 = 256
    ssm_gate_rank: int = 0
    experts_held: int = 0
    expert_first: int = 0
    shared_ff: int = 0
    head_dim: int = 0
    ffn_gated: bool = False
    attention_gate: bool = False
    untied_head: bool = False
    # 0.0 = the plain block's: 1, 1, 1/sqrt(d_head), 1.
    embedding_multiplier: float = 0.0
    residual_multiplier: float = 0.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 0.0
    rotary: bool = True
    norm_eps: float = 0.0  # 0.0 = 1e-6
    attention_window: int = 0
    router_before_mixer: bool = False
    ffn_activation: str = ""  # "" = "silu"
    # The rotary base, for the plain block too (0.0 = 10,000).
    rope_theta: float = 0.0

    _PATTERN_INTS = (
        "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_conv", "ssm_chunk",
        "experts_held", "expert_first", "shared_ff",
        "ssm_gate_rank", "head_dim", "attention_window",
        "dense_layers", "dense_ff",
    )
    _PATTERN_BOOLS = ("ffn_gated", "attention_gate", "untied_head",
                      "router_before_mixer", "router_bias", "qk_norm",
                      "norm_after")
    _PATTERN_FLOATS = (
        "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling", "norm_eps",
        "router_scale",
    )
    _PATTERN_KEYS = _PATTERN_INTS + _PATTERN_BOOLS + _PATTERN_FLOATS
    # Keys a document states only where they are set: a block without
    # them keeps the document it had before they existed.
    _PATTERN_LATER = ("ssm_gate_rank", "head_dim", "attention_gate",
                      "untied_head", "attention_window",
                      "router_before_mixer", "dense_layers", "dense_ff",
                      "router_bias", "router_scale", "qk_norm",
                      "norm_after")

    def validate(self) -> None:
        if self.preset not in _VALID_PRESETS:
            raise RuntimeConfigError(
                f"[model] preset must be one of {_VALID_PRESETS[1:]}, "
                f"got {self.preset!r}"
            )
        for field_name in ("vocab", "d_model", "n_heads", "n_kv_heads",
                           "n_layers", "d_ff", "experts", "expert_top_k"):
            value = getattr(self, field_name)
            if not isinstance(value, int) or isinstance(value, bool) \
                    or value < 0:
                raise RuntimeConfigError(
                    f"[model] {field_name} must be a non-negative int "
                    "(0 = from the preset)"
                )
        if self.expert_capacity_factor < 0:
            raise RuntimeConfigError(
                "[model] expert_capacity_factor must be >= 0 "
                "(0 = drop-free capacity)"
            )
        if self.expert_top_k not in (0, 1, 2) and not self.layer_pattern:
            raise RuntimeConfigError(
                "[model] expert_top_k must be 1 or 2 (0 = default 1)"
            )
        if not all(isinstance(kind, str) for kind in self.layer_pattern):
            raise RuntimeConfigError(
                "[model] layer_pattern must be a list of \"mamba\", "
                "\"delta\", \"attention\" and \"window\"")
        for field_name in self._PATTERN_INTS:
            value = getattr(self, field_name)
            if not isinstance(value, int) or isinstance(value, bool) \
                    or value < 0:
                raise RuntimeConfigError(
                    f"[model] {field_name} must be a non-negative int")
        for field_name in self._PATTERN_FLOATS:
            if getattr(self, field_name) < 0:
                raise RuntimeConfigError(
                    f"[model] {field_name} must be >= 0 (0 = the plain "
                    "block's)")
        if self.ffn_activation not in ("", "silu", "relu"):
            raise RuntimeConfigError(
                "[model] ffn_activation must be \"silu\" or \"relu\" "
                f"(\"\" = silu), got {self.ffn_activation!r}")
        if self.rope_theta < 0:
            raise RuntimeConfigError(
                "[model] rope_theta must be >= 0 (0 = 10,000)")
        if self.router_score not in ("", "softmax", "sigmoid"):
            raise RuntimeConfigError(
                "[model] router_score must be \"softmax\" or \"sigmoid\" "
                f"(\"\" = softmax), got {self.router_score!r}")
        if not self.layer_pattern:
            stray = [k for k in self._PATTERN_KEYS if getattr(self, k)]
            if self.ffn_activation:
                stray.append("ffn_activation")
            if self.router_score:
                stray.append("router_score")
            if stray or not self.rotary:
                raise RuntimeConfigError(
                    "[model] " + ", ".join(stray or ["rotary = false"])
                    + " belong to a patterned block: set layer_pattern")
        if self.pipeline_schedule not in ("", "gpipe", "1f1b"):
            raise RuntimeConfigError(
                "[model] pipeline_schedule must be 'gpipe' or '1f1b' "
                "('' = gpipe)"
            )


@dataclasses.dataclass(frozen=True)
class DistributedSpec:
    """Multi-host topology the runtime should join at boot.

    ``num_processes == 1`` (the default) means single-host: no
    coordination service, no ``jax.distributed`` — identical to the
    pre-multi-host behavior. With N > 1 hosts, each pod resolves its own
    process id and the coordinator address at boot
    (:mod:`kvedge_tpu.parallel.distributed`); ``-1`` / ``""`` mean
    "infer from pod identity" (TPU_WORKER_ID / TPU_WORKER_HOSTNAMES env
    on GKE multi-host slices, or a ``<name>-<ordinal>`` hostname).
    """

    num_processes: int = 1
    coordinator_address: str = ""  # "" = infer; "host" or "host:port"
    coordinator_port: int = 8478
    process_id: int = -1  # -1 = infer

    def validate(self) -> None:
        if self.num_processes < 1:
            raise RuntimeConfigError(
                "[distributed] num_processes must be >= 1"
            )
        if not (0 < self.coordinator_port < 65536):
            raise RuntimeConfigError("[distributed] coordinator_port out of range")
        if self.process_id < -1 or self.process_id >= self.num_processes:
            raise RuntimeConfigError(
                f"[distributed] process_id {self.process_id} not in "
                f"[-1, {self.num_processes})"
            )


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Validated runtime config (the parsed form of the opaque TOML)."""

    name: str = "kvedge-tpu"
    state_dir: str = DEFAULT_STATE_DIR
    # Where training checkpoints live. "" = <state_dir>/checkpoints on the
    # per-host PVC (single-host default). Multi-host slices must point
    # this at storage every host can reach — a shared-filesystem mount or
    # a remote URI like "gs://bucket/prefix" (resolved by orbax via
    # etils.epath). Heartbeats always stay on the per-host PVC.
    checkpoint_dir: str = ""
    heartbeat_interval_s: float = 10.0
    expected_platform: str = "tpu"
    expected_chips: int = 0  # 0 = accept whatever is visible
    mesh: MeshSpec = MeshSpec()
    model: ModelSpec = ModelSpec()
    distributed: DistributedSpec = DistributedSpec()
    status_port: int = 8476
    status_bind: str = "0.0.0.0"
    # Bearer token gating the mutating status routes (POST /profile).
    # Delivered through the runtime-config Secret like the rest of this
    # TOML, so it never appears in chart values or pod env. "" leaves the
    # POST surface open — acceptable only when the status port is not
    # exposed through the LoadBalancer (the GET surface is read-only by
    # design and stays open either way).
    status_token: str = ""
    payload: str = "devicecheck"
    # Attention mode for the transformer-probe payload. "" = auto: the
    # ring when the mesh has a seq axis, naive otherwise. Explicit values
    # select a specific sequence-parallel strategy ("ring"/"ulysses") or
    # kernel ("flash"/"naive").
    payload_attention: str = ""
    # Decode backend for the "serve" payload. "" / "contiguous" = one
    # uniform-batch cache per request (simple, request-serial); "paged" =
    # the continuous-batching server over the paged KV cache
    # (models/serving.py): concurrent requests share one page pool and
    # one batched decode step.
    payload_serving: str = ""
    # Paged DECODE attention impl ([payload] paged_attention): "" =
    # "auto" (the Pallas block-table kernel in its measured win domain —
    # TPU, long caps, big pages — gather elsewhere); "gather" forces the
    # bit-stable padded-gather path (the kernel is numerically
    # equivalent within bf16 rounding, not bit-identical); "kernel"
    # forces the kernel. The deployment-level escape hatch for the
    # trace-time auto policy (models/kvcache.py _use_paged_kernel).
    payload_paged_attention: str = ""
    # Paged-backend pool sizing ([payload] serving_*): how many requests
    # decode concurrently (slots), the KV page granule (page_size), and
    # the total page pool. pages = 0 auto-sizes the pool so every slot
    # can hold a worst-case (max_seq) request — admission then only ever
    # waits on slots. Operators trading memory for queueing can set
    # pages lower; requests that can never fit are rejected up front
    # (models/serving.py admission rules).
    serving_slots: int = 4
    serving_page_size: int = 16
    serving_pages: int = 0
    # HBM byte budget for the page pool ([payload]
    # serving_hbm_budget_mb, 0 = off): instead of counting pages, the
    # operator states how many MB of accelerator memory the KV pool may
    # hold and the pool sizes itself to ``budget // page_bytes`` pages
    # (page_bytes covers K + V at the storage dtype, plus the fp32
    # scale slabs when serving_kv_dtype="int8"). Mutually exclusive
    # with an explicit serving_pages — two sources of truth for one
    # pool would silently shadow each other.
    serving_hbm_budget_mb: int = 0
    # Free-page watermarks (fractions of the pool, 0 = off): below
    # ``low`` unreserved headroom, non-top-priority admissions shed
    # with page-capacity terms; a preempted request resumes only while
    # headroom sits at or above ``high`` (hysteresis against
    # preempt/resume thrash). low <= high when both are set.
    serving_page_low_watermark: float = 0.0
    serving_page_high_watermark: float = 0.0
    # Bucketed compile cache for the paged backend ([payload]
    # serving_min_bucket, 0 = off): the device batch dim runs at the
    # smallest power-of-two bucket (from this floor, capped at
    # serving_slots) covering the occupied rows, so hundreds of slots
    # cost compile time only when traffic actually reaches them —
    # admissions within a bucket never retrace. Single-host paged
    # backend only (the slice op stream pins shapes at slots).
    serving_min_bucket: int = 0
    # KV-cache storage dtype for the paged backend: "" = the compute
    # dtype (bf16, bit-exact vs the contiguous backend); "int8" =
    # per-token-row symmetric quantization with fp32 scales — the
    # per-token KV HBM bill roughly HALVES, doubling servable
    # context/slots on the same pool budget. Lossy (error bounded by
    # one int8 step of each row's amax; decode can diverge at
    # near-ties), so it is an explicit opt-in, never a default.
    serving_kv_dtype: str = ""
    # Prefill granule for the paged backend: prompts land in chunks of
    # this many tokens, with the admission lock released between chunks
    # (in-flight decode proceeds) and one compiled program per chunk
    # length instead of per prompt length. 0 = whole-prompt prefill.
    serving_prefill_chunk: int = 64
    # Prefix sharing for the paged backend: completed prompts register
    # page-aligned prefixes; a later prompt with the same prefix reuses
    # the pinned K/V pages read-only and prefills only its suffix
    # (exact — K/V depend only on prompt tokens/positions). Pins are
    # evicted LRU under pool pressure.
    serving_prefix_cache: bool = True
    # Host-RAM byte budget for the prefix cache's residency tier
    # ([payload] serving_prefix_host_mb, 0 = off): evicted prefix
    # entries demote their verbatim page bytes (int8 scale slabs ride
    # along) to host RAM instead of dropping, and a later prompt
    # matching a host-resident prefix swaps it back into HBM at
    # admission. LRU within the budget; requires
    # serving_prefix_cache=true to have any effect.
    serving_prefix_host_mb: int = 0
    # Prefix-cache persistence: on shutdown the registry's pinned K/V
    # pages dump to ``<state_dir>/prefix-cache.npz`` and a rescheduled
    # serve pod re-pins them at boot — warm prefixes ride the state
    # volume like checkpoints do. Guarded by a fingerprint (checkpoint
    # step + model geometry): a cache from different params is ignored,
    # never half-trusted. Single-host paged backend only.
    serving_prefix_persist: bool = True
    # Device-side decode window cap for the paged backend: up to this
    # many greedy steps run in ONE dispatched scan (one host round trip
    # per window instead of per token — the knob that decouples decode
    # throughput from that round trip). Compiled programs stay the
    # powers of two {2..serving_window}. Tradeoff: a new request joins
    # at the next window boundary, so admission latency grows with the
    # window (SERVING.md's performance model). 1 = per-step dispatch. "auto"
    # hands the choice to the online controller (SERVING.md rung 26):
    # every harvested window feeds EWMAs of the measured host
    # turnaround R and per-step device time t, and the next window is
    # the smallest power of two with W*t >= R — the saturation point
    # of the rung-16 law, re-picked at every boundary inside
    # [serving_window_min, serving_window_max].
    serving_window: int | str = 64
    # Controller bounds for serving_window="auto" (ignored for a
    # static window): the smallest/largest window the controller may
    # pick. Floored to powers of two. The floor guards boundary
    # staleness (cancels and newcomers wait up to a window); the cap
    # bounds the compiled-program set and admission latency.
    serving_window_min: int = 1
    serving_window_max: int = 256
    # Retry-after hint (seconds) carried by poisoned-pool refusals and
    # /healthz while degraded — what a refused client is told to wait
    # before retrying. When the recovery supervisor is active and a
    # heal is in flight, the hint is overridden by the MEASURED
    # recovery time; this static value is the fallback (no supervisor,
    # or no recovery has completed yet).
    serving_retry_after_s: float = 30.0
    # In-process recovery for the paged serving pool (SERVING.md rung
    # 15): how many heal attempts (slice reformation + warm restart,
    # exponential backoff between them) the supervisor makes before
    # escalating to the terminal 503 / reschedule path. 0 disables the
    # supervisor entirely — every poisoning failure is immediately
    # terminal, the pre-rung-15 behavior.
    serving_recovery_attempts: int = 2
    # SLO-aware admission scheduling for the paged backend (SERVING.md
    # rung 17, models/scheduler.py). Policy across priority classes
    # (requests carry [payload-level] "priority": interactive|batch):
    # "strict" admits the best class first (FIFO within a class),
    # "weighted" shares by serving_sched_weights, "fifo" ignores
    # classes — the baseline the bench overload leg compares against.
    serving_sched_policy: str = "strict"
    # Weighted-policy shares, "class=weight,..." (ignored unless
    # serving_sched_policy = "weighted"). Higher weight = more
    # admissions per round; every class with weight > 0 keeps making
    # progress, so batch traffic is never starved outright.
    serving_sched_weights: str = "interactive=4,batch=1"
    # Overload shedding watermarks: reject a submit IMMEDIATELY (with
    # the measured per-class queue wait as the retry_after hint)
    # instead of letting it burn its timeout — when more than this many
    # requests are already parked (0 = no depth watermark) ...
    serving_sched_max_queue_depth: int = 0
    # ... or when the measured queue wait for the request's class
    # exceeds this many seconds (0 = no wait watermark).
    serving_sched_max_queue_wait_s: float = 0.0
    # Host-RAM budget (MB) for preemptive KV swap: when a higher-class
    # request cannot admit, the scheduler may swap a lower-class
    # victim's live pages to host RAM at a window boundary and resume
    # it later, bit-identically. 0 disables preemption (priority
    # ordering still applies at admission).
    serving_sched_swap_budget_mb: int = 0
    # Request-scoped tracing for the paged backend (SERVING.md rung 18,
    # runtime/tracing.py): "off" (default — zero recorder in the
    # process), "on" (every request traced), or a sample rate in
    # (0, 1] — the per-request decision is a deterministic hash of the
    # request ID, so all spans of one request share fate. Tracing on is
    # token-bit-identical to off; the flight recorder's tail ships in
    # the last-failure.json post-mortem and GET /trace exports
    # Chrome/Perfetto trace-event JSON.
    serving_trace: str | float = "off"
    # Lock-discipline assertions (SERVING.md rung 19): swap the
    # serving stack's work lock for an ownership-asserting DebugLock
    # and wrap every *_locked method to verify the calling thread
    # holds it — the runtime twin of `tools/locklint.py`. Debug/test
    # only: correct code behaves identically, violations raise
    # LockDisciplineError instead of racing.
    serving_debug_locks: bool = False
    # Boundary checkpointing for in-flight durability (SERVING.md rung
    # 22, runtime/journal.py): every N quiescent pipeline boundaries
    # the decode loop journals each live request's resumable state (KV
    # pages as verbatim swapout bytes, token log, sampler position,
    # original ticket) so poison/revive and slice reformation RESUME
    # in-flight requests bit-identically instead of failing them, and
    # clients reconnect exactly-once via X-Request-Id +
    # emitted_offset. 0 (default) = off: today's fail-and-retry poison
    # semantics, zero overhead. Cost per checkpoint is roughly
    # pages_live x swap bandwidth; 16 is a reasonable cadence when on.
    serving_checkpoint_every: int = 0
    # Page-conservation audit (rung 22's invariant 1): assert
    # free + live == pages_total at every quiescent boundary, raising
    # a typed PageAccountingError — loud, attributable leak detection
    # for debug/test runs (the chaos soak runs with it on).
    serving_debug_pages: bool = False
    # SLO engine (SERVING.md rung 25, runtime/slo.py): rolling
    # multi-window SLIs (TTFT/inter-token/queue-wait p99, goodput,
    # shed rate) computed from boundary-snapshot deltas of the
    # cumulative serving histograms, with fast/slow-window error-
    # budget burn-rate alerts. Off (default) = no engine in the
    # process; on exposes GET /slo and the serve_slo_* gauges. Tokens
    # are bit-identical either way (pinned by tests/test_slo.py).
    serving_slo: bool = False
    # Compliance target: the error budget is 1 - target; burn rate
    # over a window is bad_fraction / (1 - target).
    serving_slo_target: float = 0.99
    # Latency objectives (ms): the per-window over-objective fraction
    # of each is a bad-event fraction competing for the error budget.
    serving_slo_ttft_ms: float = 1000.0
    serving_slo_itl_ms: float = 250.0
    serving_slo_queue_ms: float = 1000.0
    # The multi-window burn-rate pair (seconds): the slow window
    # proves an incident is real, the fast window proves it is still
    # happening. Alert thresholds are the SRE-workbook constants
    # (14x fast / 6x slow), not knobs.
    serving_slo_fast_s: float = 60.0
    serving_slo_slow_s: float = 600.0
    # Burn-gated shedding: while the multi-window alert fires, the
    # scheduler sheds non-top classes at the door. Off (default)
    # keeps the rung-17 shed paths byte-for-byte; requires
    # serving_slo.
    serving_slo_shed: bool = False
    # Flight-recorder bundle (rung 25): on poison the workload layer
    # writes flight-bundle.json (one versioned document: metrics
    # snapshot, SLO/burn state, occupancy tail, journal summary, page
    # books, config fingerprint, trace tail) next to
    # last-failure.json, and GET /debug/bundle serves the same
    # document live. Off (default) = neither.
    serving_bundle: bool = False
    # Occupancy timeline ring depth (samples; 0 = off): HBM/page/
    # bucket/prefix-residency gauges sampled at quiescent boundaries,
    # exported as serve_occupancy_* gauges, Chrome counter tracks in
    # GET /trace, and the bundle's occupancy tail. 256 is a
    # reasonable depth when on.
    serving_occupancy_ring: int = 0
    # The "train" payload: resumable training over a token corpus on the
    # state volume. ``train_corpus`` is the corpus path (required for the
    # payload; rebased like every other in-pod path); steps count from 0
    # across ALL pod generations — a rescheduled pod resumes from the
    # latest checkpoint and the feeder continues at the exact batch.
    train_corpus: str = ""
    # Held-out corpus for the "eval" payload ([payload] eval_corpus).
    # "" falls back to the TRAINING corpus — eval then reports training
    # loss, not held-out loss, and says so loudly. Produce a split with
    # `kvedge-tpu corpus --holdout 0.1` (writes <out> and <out>.eval).
    eval_corpus: str = ""
    train_steps: int = 100
    train_batch: int = 8
    train_seq: int = 128
    train_checkpoint_every: int = 10

    @classmethod
    def parse(cls, text: str) -> "RuntimeConfig":
        """Parse and validate the TOML document."""
        try:
            doc = tomllib.loads(text)
        except tomllib.TOMLDecodeError as e:
            raise RuntimeConfigError(f"invalid TOML: {e}") from e
        return cls.from_mapping(doc)

    @classmethod
    def from_mapping(cls, doc: Mapping) -> "RuntimeConfig":
        runtime = dict(doc.get("runtime", {}))
        tpu = dict(doc.get("tpu", {}))
        mesh_doc = dict(doc.get("mesh", {}))
        model_doc = dict(doc.get("model", {}))
        dist_doc = dict(doc.get("distributed", {}))
        status = dict(doc.get("status", {}))
        payload_doc = dict(doc.get("payload", {}))
        _refuse_speculation(payload_doc)

        axes_doc = mesh_doc.get("axes", dict(MeshSpec.axes))
        if not isinstance(axes_doc, Mapping):
            raise RuntimeConfigError("[mesh] axes must be a table")
        axes = [(str(axis), size) for axis, size in axes_doc.items()]

        try:
            cfg = cls(
                name=str(runtime.get("name", cls.name)),
                state_dir=str(runtime.get("state_dir", cls.state_dir)),
                checkpoint_dir=str(
                    runtime.get("checkpoint_dir", cls.checkpoint_dir)
                ),
                heartbeat_interval_s=float(
                    runtime.get("heartbeat_interval_s", cls.heartbeat_interval_s)
                ),
                expected_platform=str(tpu.get("platform", cls.expected_platform)),
                expected_chips=int(tpu.get("expected_chips", cls.expected_chips)),
                mesh=MeshSpec(axes=tuple(axes)),
                model=ModelSpec(
                    preset=str(model_doc.get("preset", ModelSpec.preset)),
                    vocab=int(model_doc.get("vocab", ModelSpec.vocab)),
                    d_model=int(model_doc.get("d_model", ModelSpec.d_model)),
                    n_heads=int(model_doc.get("n_heads", ModelSpec.n_heads)),
                    n_kv_heads=int(
                        model_doc.get("n_kv_heads", ModelSpec.n_kv_heads)
                    ),
                    n_layers=int(
                        model_doc.get("n_layers", ModelSpec.n_layers)
                    ),
                    d_ff=int(model_doc.get("d_ff", ModelSpec.d_ff)),
                    experts=int(model_doc.get("experts", ModelSpec.experts)),
                    expert_top_k=int(
                        model_doc.get("expert_top_k", ModelSpec.expert_top_k)
                    ),
                    expert_capacity_factor=float(
                        model_doc.get("expert_capacity_factor",
                                      ModelSpec.expert_capacity_factor)
                    ),
                    pipeline_schedule=str(
                        model_doc.get("pipeline_schedule",
                                      ModelSpec.pipeline_schedule)
                    ),
                    layer_pattern=tuple(
                        model_doc.get("layer_pattern", ())),
                    rotary=bool(model_doc.get("rotary", True)),
                    ffn_activation=str(
                        model_doc.get("ffn_activation", "")),
                    router_score=str(model_doc.get("router_score", "")),
                    rope_theta=float(model_doc.get("rope_theta", 0.0)),
                    **{key: bool(model_doc.get(key, False))
                       for key in ModelSpec._PATTERN_BOOLS},
                    **{key: int(model_doc.get(key, 0))
                       for key in ModelSpec._PATTERN_INTS},
                    **{key: float(model_doc.get(key, 0.0))
                       for key in ModelSpec._PATTERN_FLOATS},
                ),
                distributed=DistributedSpec(
                    num_processes=int(
                        dist_doc.get("num_processes",
                                     DistributedSpec.num_processes)
                    ),
                    coordinator_address=str(
                        dist_doc.get("coordinator_address",
                                     DistributedSpec.coordinator_address)
                    ),
                    coordinator_port=int(
                        dist_doc.get("coordinator_port",
                                     DistributedSpec.coordinator_port)
                    ),
                    process_id=int(
                        dist_doc.get("process_id", DistributedSpec.process_id)
                    ),
                ),
                status_port=int(status.get("port", cls.status_port)),
                status_bind=str(status.get("bind", cls.status_bind)),
                status_token=str(status.get("token", cls.status_token)),
                payload=str(payload_doc.get("kind", cls.payload)),
                payload_attention=str(
                    payload_doc.get("attention", cls.payload_attention)
                ),
                payload_serving=str(
                    payload_doc.get("serving", cls.payload_serving)
                ),
                payload_paged_attention=str(
                    payload_doc.get("paged_attention",
                                    cls.payload_paged_attention)
                ),
                serving_slots=int(
                    payload_doc.get("serving_slots", cls.serving_slots)
                ),
                serving_page_size=int(
                    payload_doc.get("serving_page_size",
                                    cls.serving_page_size)
                ),
                serving_pages=int(
                    payload_doc.get("serving_pages", cls.serving_pages)
                ),
                serving_hbm_budget_mb=int(
                    payload_doc.get("serving_hbm_budget_mb",
                                    cls.serving_hbm_budget_mb)
                ),
                serving_page_low_watermark=float(
                    payload_doc.get("serving_page_low_watermark",
                                    cls.serving_page_low_watermark)
                ),
                serving_page_high_watermark=float(
                    payload_doc.get("serving_page_high_watermark",
                                    cls.serving_page_high_watermark)
                ),
                serving_min_bucket=int(
                    payload_doc.get("serving_min_bucket",
                                    cls.serving_min_bucket)
                ),
                serving_kv_dtype=str(
                    payload_doc.get("serving_kv_dtype",
                                    cls.serving_kv_dtype)
                ),
                serving_prefill_chunk=int(
                    payload_doc.get("serving_prefill_chunk",
                                    cls.serving_prefill_chunk)
                ),
                serving_prefix_cache=payload_doc.get(
                    "serving_prefix_cache", cls.serving_prefix_cache
                ),
                serving_prefix_host_mb=int(
                    payload_doc.get("serving_prefix_host_mb",
                                    cls.serving_prefix_host_mb)
                ),
                serving_prefix_persist=payload_doc.get(
                    "serving_prefix_persist", cls.serving_prefix_persist
                ),
                serving_window=_parse_window(
                    payload_doc.get("serving_window", cls.serving_window)
                ),
                serving_window_min=int(
                    payload_doc.get("serving_window_min",
                                    cls.serving_window_min)
                ),
                serving_window_max=int(
                    payload_doc.get("serving_window_max",
                                    cls.serving_window_max)
                ),
                serving_retry_after_s=float(
                    payload_doc.get("serving_retry_after_s",
                                    cls.serving_retry_after_s)
                ),
                serving_recovery_attempts=int(
                    payload_doc.get("serving_recovery_attempts",
                                    cls.serving_recovery_attempts)
                ),
                serving_sched_policy=str(
                    payload_doc.get("serving_sched_policy",
                                    cls.serving_sched_policy)
                ),
                serving_sched_weights=str(
                    payload_doc.get("serving_sched_weights",
                                    cls.serving_sched_weights)
                ),
                serving_sched_max_queue_depth=int(
                    payload_doc.get("serving_sched_max_queue_depth",
                                    cls.serving_sched_max_queue_depth)
                ),
                serving_sched_max_queue_wait_s=float(
                    payload_doc.get("serving_sched_max_queue_wait_s",
                                    cls.serving_sched_max_queue_wait_s)
                ),
                serving_sched_swap_budget_mb=int(
                    payload_doc.get("serving_sched_swap_budget_mb",
                                    cls.serving_sched_swap_budget_mb)
                ),
                serving_trace=_parse_trace(
                    payload_doc.get("serving_trace", cls.serving_trace)
                ),
                serving_debug_locks=payload_doc.get(
                    "serving_debug_locks", cls.serving_debug_locks
                ),
                serving_checkpoint_every=int(
                    payload_doc.get("serving_checkpoint_every",
                                    cls.serving_checkpoint_every)
                ),
                serving_debug_pages=payload_doc.get(
                    "serving_debug_pages", cls.serving_debug_pages
                ),
                serving_slo=payload_doc.get(
                    "serving_slo", cls.serving_slo
                ),
                serving_slo_target=float(
                    payload_doc.get("serving_slo_target",
                                    cls.serving_slo_target)
                ),
                serving_slo_ttft_ms=float(
                    payload_doc.get("serving_slo_ttft_ms",
                                    cls.serving_slo_ttft_ms)
                ),
                serving_slo_itl_ms=float(
                    payload_doc.get("serving_slo_itl_ms",
                                    cls.serving_slo_itl_ms)
                ),
                serving_slo_queue_ms=float(
                    payload_doc.get("serving_slo_queue_ms",
                                    cls.serving_slo_queue_ms)
                ),
                serving_slo_fast_s=float(
                    payload_doc.get("serving_slo_fast_s",
                                    cls.serving_slo_fast_s)
                ),
                serving_slo_slow_s=float(
                    payload_doc.get("serving_slo_slow_s",
                                    cls.serving_slo_slow_s)
                ),
                serving_slo_shed=payload_doc.get(
                    "serving_slo_shed", cls.serving_slo_shed
                ),
                serving_bundle=payload_doc.get(
                    "serving_bundle", cls.serving_bundle
                ),
                serving_occupancy_ring=int(
                    payload_doc.get("serving_occupancy_ring",
                                    cls.serving_occupancy_ring)
                ),
                train_corpus=str(
                    payload_doc.get("corpus", cls.train_corpus)
                ),
                eval_corpus=str(
                    payload_doc.get("eval_corpus", cls.eval_corpus)
                ),
                train_steps=int(payload_doc.get("steps", cls.train_steps)),
                train_batch=int(payload_doc.get("batch", cls.train_batch)),
                train_seq=int(payload_doc.get("seq", cls.train_seq)),
                train_checkpoint_every=int(
                    payload_doc.get("checkpoint_every",
                                    cls.train_checkpoint_every)
                ),
            )
        except (TypeError, ValueError) as e:
            if isinstance(e, RuntimeConfigError):
                raise
            raise RuntimeConfigError(f"wrongly-typed config value: {e}") from e
        cfg.validate()
        return cfg

    def sched_weights_dict(self) -> dict[str, float]:
        """Parse ``serving_sched_weights`` ("class=weight,...") to a dict.

        Raises ``ValueError`` on malformed entries or non-positive
        weights; validate() surfaces that as a RuntimeConfigError and
        workload.py reuses the parsed dict when building the server.
        """
        out: dict[str, float] = {}
        for part in self.serving_sched_weights.split(","):
            part = part.strip()
            if not part:
                continue
            name, sep, val = part.partition("=")
            name = name.strip()
            if not sep or not name:
                raise ValueError(
                    f"expected 'class=weight', got {part!r}"
                )
            weight = float(val.strip())
            if weight <= 0:
                raise ValueError(
                    f"weight for {name!r} must be > 0, got {weight}"
                )
            out[name] = weight
        return out

    def validate(self) -> None:
        if not self.name:
            raise RuntimeConfigError("[runtime] name must be non-empty")
        if self.heartbeat_interval_s <= 0:
            raise RuntimeConfigError("[runtime] heartbeat_interval_s must be > 0")
        if self.expected_chips < 0:
            raise RuntimeConfigError("[tpu] expected_chips must be >= 0")
        # Port 0 = bind an ephemeral port (tests / local verification).
        if not (0 <= self.status_port < 65536):
            raise RuntimeConfigError("[status] port out of range")
        if self.payload not in _VALID_PAYLOADS:
            raise RuntimeConfigError(
                f"[payload] kind must be one of {_VALID_PAYLOADS}, "
                f"got {self.payload!r}"
            )
        if self.payload_attention not in _VALID_ATTENTION:
            raise RuntimeConfigError(
                f"[payload] attention must be one of {_VALID_ATTENTION}, "
                f"got {self.payload_attention!r}"
            )
        if self.payload_serving not in ("", "contiguous", "paged"):
            raise RuntimeConfigError(
                "[payload] serving must be '', 'contiguous', or 'paged', "
                f"got {self.payload_serving!r}"
            )
        if self.payload_paged_attention not in ("", "auto", "kernel",
                                                "gather"):
            raise RuntimeConfigError(
                "[payload] paged_attention must be '', 'auto', "
                f"'kernel', or 'gather', got "
                f"{self.payload_paged_attention!r}"
            )
        if self.serving_slots < 1:
            raise RuntimeConfigError("[payload] serving_slots must be >= 1")
        if self.serving_page_size < 1:
            raise RuntimeConfigError(
                "[payload] serving_page_size must be >= 1"
            )
        if self.serving_pages < 0:
            raise RuntimeConfigError(
                "[payload] serving_pages must be >= 0 (0 = auto-size so "
                "every slot fits a worst-case request)"
            )
        if self.serving_hbm_budget_mb < 0:
            raise RuntimeConfigError(
                "[payload] serving_hbm_budget_mb must be >= 0 "
                "(0 = off; size the pool by serving_pages instead)"
            )
        if self.serving_hbm_budget_mb > 0 and self.serving_pages > 0:
            raise RuntimeConfigError(
                "[payload] serving_hbm_budget_mb and serving_pages are "
                "mutually exclusive — two sources of truth for one "
                "page pool; set one and leave the other 0"
            )
        for name in ("serving_page_low_watermark",
                     "serving_page_high_watermark"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or not 0.0 <= v < 1.0:
                raise RuntimeConfigError(
                    f"[payload] {name} must be a fraction in [0, 1) "
                    "(0 = off)"
                )
        if (self.serving_page_low_watermark
                and self.serving_page_high_watermark
                and self.serving_page_low_watermark
                > self.serving_page_high_watermark):
            raise RuntimeConfigError(
                "[payload] serving_page_low_watermark must be <= "
                "serving_page_high_watermark"
            )
        if self.serving_min_bucket < 0:
            raise RuntimeConfigError(
                "[payload] serving_min_bucket must be >= 0 (0 = off: "
                "the device batch dim is pinned to serving_slots)"
            )
        if self.serving_kv_dtype not in ("", "int8"):
            raise RuntimeConfigError(
                "[payload] serving_kv_dtype must be '' (compute dtype) "
                f"or 'int8', got {self.serving_kv_dtype!r}"
            )
        if self.serving_prefill_chunk < 0:
            raise RuntimeConfigError(
                "[payload] serving_prefill_chunk must be >= 0 "
                "(0 = whole-prompt prefill)"
            )
        if not isinstance(self.serving_prefix_cache, bool):
            raise RuntimeConfigError(
                "[payload] serving_prefix_cache must be a boolean"
            )
        if not isinstance(self.serving_prefix_persist, bool):
            raise RuntimeConfigError(
                "[payload] serving_prefix_persist must be a boolean"
            )
        if self.serving_prefix_host_mb < 0:
            raise RuntimeConfigError(
                "[payload] serving_prefix_host_mb must be >= 0 "
                "(0 disables the host residency tier)"
            )
        if self.serving_window != "auto" and not (
            isinstance(self.serving_window, int)
            and 1 <= self.serving_window <= 1024
        ):
            raise RuntimeConfigError(
                "[payload] serving_window must be in [1, 1024] "
                "(1 = per-step dispatch) or 'auto' (online "
                "controller, SERVING.md rung 26)"
            )
        if not 1 <= self.serving_window_min <= 1024:
            raise RuntimeConfigError(
                "[payload] serving_window_min must be in [1, 1024]"
            )
        if not 1 <= self.serving_window_max <= 1024:
            raise RuntimeConfigError(
                "[payload] serving_window_max must be in [1, 1024]"
            )
        if self.serving_window_min > self.serving_window_max:
            raise RuntimeConfigError(
                "[payload] serving_window_min must be <= "
                "serving_window_max (controller bounds)"
            )
        if self.serving_retry_after_s <= 0:
            raise RuntimeConfigError(
                "[payload] serving_retry_after_s must be > 0 "
                "(seconds a refused client should wait before retrying)"
            )
        if self.serving_recovery_attempts < 0:
            raise RuntimeConfigError(
                "[payload] serving_recovery_attempts must be >= 0 "
                "(0 = no in-process recovery; degrade is terminal)"
            )
        if self.serving_sched_policy not in ("fifo", "strict",
                                             "weighted"):
            raise RuntimeConfigError(
                "[payload] serving_sched_policy must be 'fifo', "
                "'strict' or 'weighted'"
            )
        try:
            self.sched_weights_dict()
        except ValueError as e:
            raise RuntimeConfigError(
                f"[payload] serving_sched_weights: {e}"
            ) from None
        if self.serving_sched_max_queue_depth < 0:
            raise RuntimeConfigError(
                "[payload] serving_sched_max_queue_depth must be >= 0 "
                "(0 = no depth watermark)"
            )
        if self.serving_sched_max_queue_wait_s < 0:
            raise RuntimeConfigError(
                "[payload] serving_sched_max_queue_wait_s must be >= 0 "
                "(0 = no wait watermark)"
            )
        if self.serving_sched_swap_budget_mb < 0:
            raise RuntimeConfigError(
                "[payload] serving_sched_swap_budget_mb must be >= 0 "
                "(0 = preemptive swap off)"
            )
        if isinstance(self.serving_trace, str):
            if self.serving_trace not in ("off", "on"):
                raise RuntimeConfigError(
                    "[payload] serving_trace must be 'off', 'on' or a "
                    f"sample rate in (0, 1], got {self.serving_trace!r}"
                )
        elif not 0.0 < self.serving_trace <= 1.0:
            raise RuntimeConfigError(
                "[payload] serving_trace sample rate must be in "
                f"(0, 1], got {self.serving_trace!r}"
            )
        if not isinstance(self.serving_debug_locks, bool):
            raise RuntimeConfigError(
                "[payload] serving_debug_locks must be a boolean"
            )
        if self.serving_checkpoint_every < 0:
            raise RuntimeConfigError(
                "[payload] serving_checkpoint_every must be >= 0 "
                "(0 = off: no in-flight checkpointing)"
            )
        if not isinstance(self.serving_debug_pages, bool):
            raise RuntimeConfigError(
                "[payload] serving_debug_pages must be a boolean"
            )
        for knob in ("serving_slo", "serving_slo_shed",
                     "serving_bundle"):
            if not isinstance(getattr(self, knob), bool):
                raise RuntimeConfigError(
                    f"[payload] {knob} must be a boolean"
                )
        if not 0.0 < self.serving_slo_target < 1.0:
            raise RuntimeConfigError(
                "[payload] serving_slo_target must be in (0, 1) "
                f"(got {self.serving_slo_target!r}; the error budget "
                "is 1 - target)"
            )
        for knob in ("serving_slo_ttft_ms", "serving_slo_itl_ms",
                     "serving_slo_queue_ms"):
            if getattr(self, knob) <= 0.0:
                raise RuntimeConfigError(
                    f"[payload] {knob} must be > 0 (an objective in "
                    "milliseconds)"
                )
        if not (0.0 < self.serving_slo_fast_s
                <= self.serving_slo_slow_s):
            raise RuntimeConfigError(
                "[payload] serving_slo windows must satisfy "
                "0 < serving_slo_fast_s <= serving_slo_slow_s "
                f"(got fast={self.serving_slo_fast_s!r}, "
                f"slow={self.serving_slo_slow_s!r})"
            )
        if self.serving_slo_shed and not self.serving_slo:
            raise RuntimeConfigError(
                "[payload] serving_slo_shed requires serving_slo = "
                "true (the burn-rate input comes from the SLO engine)"
            )
        if self.serving_occupancy_ring < 0:
            raise RuntimeConfigError(
                "[payload] serving_occupancy_ring must be >= 0 "
                "(0 = off; otherwise the ring depth in samples)"
            )
        if self.model.layer_pattern:
            self._validate_pattern_payload()
        if self.payload == "train" and not self.train_corpus:
            raise RuntimeConfigError(
                "[payload] kind = 'train' requires corpus = '<path>' "
                "(a KVFEED01 token file, typically on the state volume)"
            )
        if self.payload == "eval" and not (self.train_corpus
                                           or self.eval_corpus):
            raise RuntimeConfigError(
                "[payload] kind = 'eval' requires corpus = '<path>' or "
                "eval_corpus = '<path>' (a KVFEED01 token file; "
                "eval_corpus is the held-out split)"
            )
        for field_name in ("train_steps", "train_batch", "train_seq",
                           "train_checkpoint_every"):
            if getattr(self, field_name) <= 0:
                toml_key = field_name.removeprefix("train_")
                raise RuntimeConfigError(
                    f"[payload] {toml_key} must be positive"
                )
        self.mesh.validate()
        self.model.validate()
        self.distributed.validate()

    def _validate_pattern_payload(self) -> None:
        """What cannot run a block with ``[model] layer_pattern``: it
        is written once, in the paged serving path, and its recurrent
        state is a row's whole past in one fixed-size array."""
        if self.payload in ("train", "eval", "transformer-probe",
                            "inference-probe"):
            raise RuntimeConfigError(
                f"[payload] kind = {self.payload!r} cannot run a model "
                "with [model] layer_pattern: the patterned block exists "
                "in the paged serving path only (kind = \"serve\", "
                "serving = \"paged\")")
        if self.payload == "serve" and self.payload_serving != "paged":
            raise RuntimeConfigError(
                "[model] layer_pattern needs [payload] serving = "
                "\"paged\": the contiguous cache has no patterned block")
        window = "window" in self.model.layer_pattern
        if self.serving_prefix_cache:
            raise RuntimeConfigError(
                "[payload] serving_prefix_cache = true cannot serve a "
                "model with [model] layer_pattern: " + (
                    "a shared page that a \"window\" layer has given "
                    "back cannot be attended again" if window else
                    "a recurrent state holds a row's whole prefix in one "
                    "array and cannot be shared by page")
                + "; set serving_prefix_cache = false")
        if window and self.serving_kv_dtype == "int8":
            raise RuntimeConfigError(
                "[payload] serving_kv_dtype = \"int8\" cannot serve a "
                "model with \"window\" layers in [model] layer_pattern: "
                "the window layers' pool is held in the compute dtype "
                "only")


    def _pattern_toml(self) -> str:
        """The patterned block's ``[model]`` keys; nothing for the plain
        block, whose document stays as it was."""
        m = self.model
        if not m.layer_pattern:
            return ""
        kinds = ", ".join(_toml_str(kind) for kind in m.layer_pattern)
        lines = [f"layer_pattern = [{kinds}]",
                 f"rotary = {str(m.rotary).lower()}"]
        if m.ffn_activation:
            lines.append(f"ffn_activation = {_toml_str(m.ffn_activation)}")
        if m.router_score:
            lines.append(f"router_score = {_toml_str(m.router_score)}")
        for key in m._PATTERN_KEYS:
            value = getattr(m, key)
            if key in m._PATTERN_LATER and not value:
                continue
            lines.append(f"{key} = "
                         + (str(value).lower() if isinstance(value, bool)
                            else repr(value)))
        return "\n".join(lines) + "\n"

    def to_toml(self) -> str:
        """Serialize back to TOML (the form written by ``config apply``).

        String values are emitted as TOML basic strings via JSON escaping
        (valid TOML: ``\"``, ``\\``, ``\\uXXXX``), so quotes/backslashes in
        names or paths survive the apply -> re-parse round trip.
        """
        s = _toml_str
        axes = ", ".join(f"{s(name)} = {size}" for name, size in self.mesh.axes)
        return (
            "[runtime]\n"
            f"name = {s(self.name)}\n"
            f"state_dir = {s(self.state_dir)}\n"
            f"checkpoint_dir = {s(self.checkpoint_dir)}\n"
            f"heartbeat_interval_s = {self.heartbeat_interval_s}\n"
            "\n[tpu]\n"
            f"platform = {s(self.expected_platform)}\n"
            f"expected_chips = {self.expected_chips}\n"
            "\n[mesh]\n"
            f"axes = {{ {axes} }}\n"
            "\n[model]\n"
            f"preset = {s(self.model.preset)}\n"
            f"vocab = {self.model.vocab}\n"
            f"d_model = {self.model.d_model}\n"
            f"n_heads = {self.model.n_heads}\n"
            f"n_kv_heads = {self.model.n_kv_heads}\n"
            f"n_layers = {self.model.n_layers}\n"
            f"d_ff = {self.model.d_ff}\n"
            f"experts = {self.model.experts}\n"
            f"expert_top_k = {self.model.expert_top_k}\n"
            f"expert_capacity_factor = {self.model.expert_capacity_factor}\n"
            f"pipeline_schedule = {s(self.model.pipeline_schedule)}\n"
            # stated only where it is set: a document without it is
            # the document it was
            + (f"rope_theta = {self.model.rope_theta!r}\n"
               if self.model.rope_theta else "")
            + self._pattern_toml() +
            "\n[distributed]\n"
            f"num_processes = {self.distributed.num_processes}\n"
            f"coordinator_address = {s(self.distributed.coordinator_address)}\n"
            f"coordinator_port = {self.distributed.coordinator_port}\n"
            f"process_id = {self.distributed.process_id}\n"
            "\n[status]\n"
            f"port = {self.status_port}\n"
            f"bind = {s(self.status_bind)}\n"
            f"token = {s(self.status_token)}\n"
            "\n[payload]\n"
            f"kind = {s(self.payload)}\n"
            f"attention = {s(self.payload_attention)}\n"
            f"serving = {s(self.payload_serving)}\n"
            f"paged_attention = {s(self.payload_paged_attention)}\n"
            f"serving_slots = {self.serving_slots}\n"
            f"serving_page_size = {self.serving_page_size}\n"
            f"serving_pages = {self.serving_pages}\n"
            f"serving_hbm_budget_mb = {self.serving_hbm_budget_mb}\n"
            "serving_page_low_watermark = "
            f"{self.serving_page_low_watermark}\n"
            "serving_page_high_watermark = "
            f"{self.serving_page_high_watermark}\n"
            f"serving_min_bucket = {self.serving_min_bucket}\n"
            f"serving_kv_dtype = {s(self.serving_kv_dtype)}\n"
            f"serving_prefill_chunk = {self.serving_prefill_chunk}\n"
            "serving_prefix_cache = "
            f"{'true' if self.serving_prefix_cache else 'false'}\n"
            f"serving_prefix_host_mb = {self.serving_prefix_host_mb}\n"
            "serving_prefix_persist = "
            f"{'true' if self.serving_prefix_persist else 'false'}\n"
            "serving_window = "
            f"{s(self.serving_window) if isinstance(self.serving_window, str) else self.serving_window}\n"
            f"serving_window_min = {self.serving_window_min}\n"
            f"serving_window_max = {self.serving_window_max}\n"
            f"serving_retry_after_s = {self.serving_retry_after_s}\n"
            f"serving_recovery_attempts = {self.serving_recovery_attempts}\n"
            f"serving_sched_policy = {s(self.serving_sched_policy)}\n"
            f"serving_sched_weights = {s(self.serving_sched_weights)}\n"
            "serving_sched_max_queue_depth = "
            f"{self.serving_sched_max_queue_depth}\n"
            "serving_sched_max_queue_wait_s = "
            f"{self.serving_sched_max_queue_wait_s}\n"
            "serving_sched_swap_budget_mb = "
            f"{self.serving_sched_swap_budget_mb}\n"
            "serving_trace = "
            f"{s(self.serving_trace) if isinstance(self.serving_trace, str) else self.serving_trace}\n"
            "serving_debug_locks = "
            f"{'true' if self.serving_debug_locks else 'false'}\n"
            "serving_checkpoint_every = "
            f"{self.serving_checkpoint_every}\n"
            "serving_debug_pages = "
            f"{'true' if self.serving_debug_pages else 'false'}\n"
            f"serving_slo = {'true' if self.serving_slo else 'false'}\n"
            f"serving_slo_target = {self.serving_slo_target}\n"
            f"serving_slo_ttft_ms = {self.serving_slo_ttft_ms}\n"
            f"serving_slo_itl_ms = {self.serving_slo_itl_ms}\n"
            f"serving_slo_queue_ms = {self.serving_slo_queue_ms}\n"
            f"serving_slo_fast_s = {self.serving_slo_fast_s}\n"
            f"serving_slo_slow_s = {self.serving_slo_slow_s}\n"
            "serving_slo_shed = "
            f"{'true' if self.serving_slo_shed else 'false'}\n"
            "serving_bundle = "
            f"{'true' if self.serving_bundle else 'false'}\n"
            "serving_occupancy_ring = "
            f"{self.serving_occupancy_ring}\n"
            f"corpus = {s(self.train_corpus)}\n"
            f"eval_corpus = {s(self.eval_corpus)}\n"
            f"steps = {self.train_steps}\n"
            f"batch = {self.train_batch}\n"
            f"seq = {self.train_seq}\n"
            f"checkpoint_every = {self.train_checkpoint_every}\n"
        )

    def apply(self, config_path: str = DEFAULT_CONFIG_PATH) -> str:
        """Materialize the validated config — ``iotedge config apply`` analog.

        Writes the canonical TOML to ``config_path`` and creates the state
        directory, so a subsequent runtime boot finds both in place
        (reference: ``_helper.tpl:73-74``).
        """
        self.validate()
        os.makedirs(os.path.dirname(config_path), exist_ok=True)
        os.makedirs(self.state_dir, exist_ok=True)
        tmp = config_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            fh.write(self.to_toml())
        os.replace(tmp, config_path)
        return config_path
