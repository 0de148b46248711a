"""Flagship payload: a compact decoder-only transformer LM, TPU-first.

Design notes (why it looks like this, not like a CUDA/torch port):

* **Params are a flat pytree of stacked arrays.** All layers' weights are
  stacked on a leading layer axis and the forward pass is one
  ``lax.scan`` over that axis — XLA compiles ONE layer body regardless of
  depth, and the layer axis is never sharded.
* **bf16 compute, fp32 master params.** Matmuls (the MXU work) run in
  bfloat16; params and optimizer state stay float32. Serve alone holds
  its matrices in bfloat16, cast once at load (:func:`serving_params`).
* **Static shapes everywhere**; the causal mask is a compile-time constant.
* **Sharding is annotation-only** (see parallel/sharding.py): this file
  contains no collectives — XLA inserts them from the in_shardings.
* **Weight tying**: logits = hidden @ embedding.T, halving embedding HBM.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    # Grouped-query attention: number of K/V heads. 0 means n_heads (MHA).
    # Fewer KV heads shrink the decode-time KV cache by n_heads/n_kv_heads —
    # the HBM-bandwidth lever for inference serving (models/decode.py).
    n_kv_heads: int = 0
    n_layers: int = 8
    d_ff: int = 2048
    max_seq: int = 1024
    dtype: str = "bfloat16"  # compute dtype
    # Rematerialize each layer in backward instead of saving activations
    # (notably the [T, T] attention scores, which otherwise live for every
    # layer at once under lax.scan) — the standard HBM-for-FLOPs trade.
    remat: bool = True
    # What remat may keep: "full" recomputes everything in backward;
    # "dots" saves matmul outputs (jax.checkpoint_policies
    # .dots_with_no_batch_dims_saveable) and recomputes only the cheap
    # elementwise work — less recompute FLOPs for modest extra HBM.
    remat_policy: str = "full"
    # Mixture-of-experts FFN (models/moe.py): 0 = dense. With n_experts
    # set, every layer's FFN becomes E switch-routed experts whose
    # stacked weights shard over an ``expert`` mesh axis — parameter
    # scale-out without per-token FLOP growth. The serving paths
    # (models/decode.py, models/kvcache.py) route per-token without
    # capacity limits; cached decode agrees with the teacher-forced
    # forward pass exactly when training capacity never binds
    # (expert_capacity_factor * expert_top_k >= n_experts guarantees
    # that; the serving boundary warns otherwise — models/moe.py).
    n_experts: int = 0
    # Per-expert slot headroom: capacity = ceil(k*tokens/E * factor);
    # dispatches routed past capacity are dropped (residual carries them).
    expert_capacity_factor: float = 1.25
    # Experts per token: 1 = Switch (gate = raw router prob), 2 = GShard
    # (gates normalized over the pair; first choices take capacity
    # priority over second choices).
    expert_top_k: int = 1
    # Weight of the router's load-balancing aux loss in the training
    # loss (Switch Transformer uses 1e-2).
    moe_aux_weight: float = 0.01
    # Pipeline parallelism (parallel/pipeline.py): 0 = off. With S > 1
    # the layer-stacked params shard their leading L axis over a
    # ``stage`` mesh axis (L/S whole layers per device) and forward runs
    # a GPipe microbatch schedule with ppermute stage hand-offs.
    # Requires a mesh with a ``stage`` axis; currently dense-FFN +
    # local-attention configs only.
    pipeline_stages: int = 0
    # Microbatches per step under pipelining; 0 = one per stage. More
    # microbatches shrink the pipeline bubble (M / (M + S - 1)).
    pipeline_microbatches: int = 0
    # Pipeline backward schedule: "gpipe" (autodiff through the forward
    # schedule + remat — general, composes with MoE/seq-parallel) or
    # "1f1b" (the fused forward+backward schedule with an O(stages)
    # activation stash — dense models, standard attention;
    # parallel/pipeline1f1b.py). Training-only: inference never
    # differentiates, so decode/serve paths ignore it.
    pipeline_schedule: str = "gpipe"
    # Fused cross-entropy readout (ops/xent.py): the training loss skips
    # materializing [B*T, V] logits entirely — blockwise Pallas matmuls
    # with an online logsumexp and an LSE-recompute backward. Measured on
    # v5e the fp32 logits tensor (4.2 GB at the bench shape) and its
    # cotangent dominated the step's HBM traffic. Inference paths
    # (forward/decode) still materialize logits — they need them.
    # Requires vocab % 128 == 0 and batch*seq % 8 == 0; does not compose
    # with tensor-parallel ('model' > 1) meshes yet — the D contraction
    # would need a psum before the online softmax.
    fused_xent: bool = False
    # "naive" materializes [T, T] scores (XLA-fused); "flash" streams K/V
    # blocks through a Pallas kernel with an online softmax (no [T, T] in
    # forward); "ring" shards the sequence over the mesh's ``seq`` axis
    # with ppermute rotation (parallel/ringattention.py); "ulysses"
    # shards the sequence too, but re-shards heads<->sequence with one
    # all-to-all each way and attends locally (parallel/ulysses.py —
    # needs n_heads % (sp * tp) == 0; a ``model`` axis shards heads
    # first). Both sequence modes require passing a mesh with a ``seq``
    # axis to forward(). Flash requires seq to be a multiple of its
    # block size.
    attention: str = "naive"
    # Paged DECODE attention (models/kvcache.py single-query steps and
    # windows): "gather" materializes the per-sequence pool view
    # (pool[tables] — cost scales with the pool CAP); "kernel" streams
    # K/V pages block-table-indexed through a two-phase Pallas kernel —
    # per-step cost scales with each sequence's LIVE length
    # (ops/paged_attention.py; bit-identical to the gather when
    # compiled for the chip, which chip_smoke.py enforces — see
    # kvcache._use_paged_kernel). "auto" picks the kernel on one TPU
    # chip at long-context caps (max_seq >= 2048, 128-token pages) and
    # the gather elsewhere; over several chips see
    # kvcache.settle_paged_attention.
    # Prefill always uses the gather path (multi-query shapes).
    paged_attention: str = "auto"
    # A patterned block (models/hybrid.py; served by the paged path
    # only). ``layer_pattern`` is one period of layer kinds: "attention"
    # (full attention, rotary or not as ``rotary`` says), "window"
    # (attention over the last ``attention_window`` positions, always
    # rotary, with keys and values in a page pool of its own:
    # models/kvcache.py) and at most one recurrent kind, "mamba"
    # (models/ssm.py) or "delta" (models/delta.py), repeated
    # ``n_layers / len(layer_pattern)`` times; () is the block above:
    # every layer rotary attention and a GELU feed-forward. With a
    # pattern every layer's feed-forward is ``n_experts`` routed
    # experts of width ``d_ff`` (``expert_top_k`` a token, gates a
    # softmax over the picked logits) plus, where ``shared_ff`` is set,
    # a shared expert of that width, all gated when ``ffn_gated``
    # (``ffn_activation``: the gate's function, "silu" or "relu").
    # ``router_before_mixer`` routes a layer's tokens on the mixer's
    # normed input and not on the feed-forward's own.
    # ``experts_held`` of the routed experts live here, from index
    # ``expert_first`` on (0 held = all): the layer routes over all
    # ``n_experts`` and returns the held experts' part of the sum.
    # The recurrent layers' sizes are one set of keys for either kind:
    # a mamba layer's state is [heads * head_dim, state] a row, a delta
    # layer's [heads, state, head_dim] (``ssm_state`` its key channels,
    # ``ssm_head_dim`` its value channels; ``ssm_gate_rank`` the width
    # of its two low-rank gates). ``head_dim`` is the attention heads'
    # size where it is not ``d_model / n_heads``; ``attention_gate``
    # gives an attention layer an output gate (``w_gate``) and
    # ``untied_head`` the tree a head of its own (``head``): the layer
    # bodies read both from the tree they are handed.
    # ``dense_layers`` leading layers come before the periods, each with
    # a gated MLP of ``dense_ff`` where the others have experts and with
    # the mixer the pattern, continued backwards, gives its place
    # (leading layer l is of kind ``layer_pattern[(l - dense_layers) %
    # period]``; they are of one kind): ``n_layers`` counts them too.
    # ``router_score`` "sigmoid" scores each expert alone: the picks are
    # the ``expert_top_k`` largest of score plus the tree's
    # ``router_bias`` (where ``router_bias`` says it has one), the gates
    # the picked scores over their sum, times ``router_scale``.
    # ``qk_norm`` gives each head's q and k an RMSNorm of their own
    # (before rotary); ``norm_after`` puts a sublayer's norm on its
    # output, ``x + norm(f(x))``, and none on its input.
    layer_pattern: tuple = ()
    dense_layers: int = 0
    dense_ff: int = 0
    router_score: str = "softmax"
    router_bias: bool = False
    router_scale: float = 1.0
    qk_norm: bool = False
    norm_after: bool = False
    ssm_heads: int = 0      # recurrent heads ...
    ssm_head_dim: int = 0   # ... of this many (value) channels each
    ssm_state: int = 0      # state size N per channel (key channels)
    ssm_conv: int = 4       # causal conv width over the mixer's inputs
    ssm_chunk: int = 256    # the chunk form's block of positions
    ssm_gate_rank: int = 0
    experts_held: int = 0
    expert_first: int = 0
    shared_ff: int = 0
    ffn_gated: bool = False
    ffn_activation: str = "silu"
    router_before_mixer: bool = False
    head_dim: int = 0
    attention_gate: bool = False
    untied_head: bool = False
    # Positions a "window" layer's query sees, its own included (query
    # i attends keys i - attention_window + 1 .. i).
    attention_window: int = 0
    # x = embedding_multiplier * E[tokens]; each residual add takes
    # residual_multiplier * f(norm(x)); attention scores are
    # attention_multiplier * q.k (0 = 1/sqrt(d_head)); logits are
    # divided by logits_scaling.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    rotary: bool = True     # False = no positional encoding at all
    rope_theta: float = 10000.0  # the rotary base, wherever it is applied
    norm_eps: float = 1e-6

    @property
    def periods(self) -> int:
        """Whole periods of ``layer_pattern`` behind the leading dense
        layers."""
        return (self.n_layers - self.dense_layers) // len(self.layer_pattern)

    @property
    def leading_kinds(self) -> tuple:
        """The mixer kind of each leading dense layer: the pattern
        continued backwards from the first period."""
        period = len(self.layer_pattern)
        return tuple(self.layer_pattern[(i - self.dense_layers) % period]
                     for i in range(self.dense_layers))

    def layers_of_kind(self, kind: str) -> int:
        return (self.periods * self.layer_pattern.count(kind)
                + self.leading_kinds.count(kind))

    @property
    def kv_layers(self) -> int:
        """Layers that keep keys and values in the page pool: every
        layer of the plain block, the full attention layers of a
        patterned one (its window layers have a pool of their own)."""
        if not self.layer_pattern:
            return self.n_layers
        return self.layers_of_kind("attention")

    @property
    def window_layers(self) -> int:
        """Layers that keep keys and values in the window layers' pool."""
        if not self.layer_pattern:
            return 0
        return self.layers_of_kind("window")

    @property
    def ssm_layers(self) -> int:
        """Layers that keep a recurrent state per slot."""
        return self.n_layers - self.kv_layers - self.window_layers

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the causal conv runs over: x | B | C, one group."""
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def recurrent_kind(self) -> str:
        """The pattern's recurrent layer kind ("" where it has none)."""
        return next((kind for kind in ("mamba", "delta")
                     if kind in self.layer_pattern), "")

    @property
    def d_head(self) -> int:
        if self.head_dim:
            return self.head_dim
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def param_count(self) -> int:
        """Exact parameter count of the tree init_params builds."""
        d, f, L, v = self.d_model, self.d_ff, self.n_layers, self.vocab
        h, kv, dh = self.n_heads, self.kv_heads, self.d_head
        per_layer = d * (h + 2 * kv) * dh + h * dh * d + 2 * d  # attn + norms
        if self.n_experts:
            per_layer += d * self.n_experts * (1 + 2 * f)  # router + experts
        else:
            per_layer += 2 * d * f  # dense FFN
        return v * d + L * per_layer + d  # embed + layers + final norm

    @property
    def needs_mesh(self) -> bool:
        """True when the concrete mesh is required at trace time: the
        sequence-parallel and pipeline shard_maps, the MoE layer's
        expert-placement ``with_sharding_constraint`` (without which XLA
        may replicate the experts), and the fused cross-entropy kernel
        (its shard_map over the data axis, and the tensor-parallel
        rejection — without the mesh the guard could never fire).
        Callers pass ``mesh`` to :func:`forward`/:func:`make_train_step`
        iff this is set."""
        return (self.attention in ("ring", "ulysses")
                or self.n_experts > 0 or self.pipeline_stages > 1
                or self.fused_xent)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def validate(self) -> None:
        if not self.head_dim and self.d_model % self.n_heads:
            raise ValueError("d_model must be divisible by n_heads")
        if self.n_kv_heads and self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        if self.attention not in ("naive", "flash", "ring", "ulysses"):
            raise ValueError(
                "attention must be 'naive', 'flash', 'ring', or "
                f"'ulysses', got {self.attention!r}"
            )
        if self.paged_attention not in ("auto", "kernel", "gather"):
            raise ValueError(
                "paged_attention must be 'auto', 'kernel', or "
                f"'gather', got {self.paged_attention!r}"
            )
        if self.n_experts < 0:
            raise ValueError("n_experts must be >= 0 (0 = dense FFN)")
        if self.n_experts and self.expert_capacity_factor <= 0:
            raise ValueError("expert_capacity_factor must be > 0")
        if self.layer_pattern:
            self._validate_pattern()
        elif (self.experts_held or self.expert_first or self.shared_ff
              or self.ffn_gated or not self.rotary or self.head_dim
              or self.attention_gate or self.untied_head
              or self.attention_window or self.router_before_mixer
              or self.ffn_activation != "silu"):
            raise ValueError(
                "experts_held, expert_first, shared_ff, ffn_gated, "
                "ffn_activation, router_before_mixer, head_dim, "
                "attention_gate, untied_head, attention_window and "
                "rotary = false belong to a patterned block: set "
                "layer_pattern")
        else:
            stray = [name for name, default in (
                ("dense_layers", 0), ("dense_ff", 0),
                ("router_score", "softmax"), ("router_bias", False),
                ("router_scale", 1.0), ("qk_norm", False),
                ("norm_after", False)) if getattr(self, name) != default]
            if stray:
                raise ValueError(
                    ", ".join(stray) + " belong to a patterned block: "
                    "set layer_pattern")
        if self.rope_theta <= 0:
            raise ValueError("rope_theta, the rotary base, must be > 0")
        if self.n_experts:
            if self.expert_top_k not in (1, 2) and not self.layer_pattern:
                raise ValueError("expert_top_k must be 1 or 2")
            if self.expert_top_k > self.n_experts:
                raise ValueError(
                    f"expert_top_k {self.expert_top_k} needs at least "
                    f"that many experts (n_experts={self.n_experts})"
                )
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got "
                f"{self.remat_policy!r}"
            )
        if self.layer_pattern and self.pipeline_stages > 1:
            raise ValueError(
                "layer_pattern: a patterned block is served by the paged "
                "path on one device; it has no pipeline schedule")
        if self.pipeline_stages < 0:
            raise ValueError("pipeline_stages must be >= 0 (0 = off)")
        if self.pipeline_microbatches < 0:
            raise ValueError(
                "pipeline_microbatches must be >= 0 (0 = one per stage)"
            )
        if (self.pipeline_stages > 1
                and self.n_layers % self.pipeline_stages):
            raise ValueError(
                f"n_layers {self.n_layers} must divide by "
                f"pipeline_stages {self.pipeline_stages}"
            )
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                "pipeline_schedule must be 'gpipe' or '1f1b', got "
                f"{self.pipeline_schedule!r}"
            )
        if self.pipeline_schedule == "1f1b":
            # Config-time refusals (loud at derive/validate, not at the
            # first train step) — parallel/pipeline1f1b.py's docstring
            # carries the reasons.
            if self.n_experts:
                raise ValueError(
                    "pipeline_schedule='1f1b' does not support MoE "
                    "layers (use 'gpipe')"
                )
            if self.attention in ("ring", "ulysses"):
                raise ValueError(
                    "pipeline_schedule='1f1b' does not compose with "
                    "sequence-parallel attention (use 'gpipe')"
                )
            if self.fused_xent:
                raise ValueError(
                    "pipeline_schedule='1f1b' computes its loss head "
                    "inside the pipeline's manual region, where the "
                    "Pallas fused-xent kernel cannot run (use 'gpipe' "
                    "or disable fused_xent)"
                )

    def _validate_pattern(self) -> None:
        kinds = set(self.layer_pattern)
        known = {"mamba", "delta", "attention", "window"}
        if not kinds <= known:
            raise ValueError(
                "layer_pattern holds 'mamba', 'delta', 'attention' and "
                f"'window', got {sorted(kinds - known)}")
        if ("window" in kinds) != (self.attention_window > 0):
            raise ValueError(
                "layer_pattern's 'window' layers and attention_window, "
                "the positions their queries see, go together: "
                f"attention_window = {self.attention_window} with "
                f"{'a' if 'window' in kinds else 'no'} 'window' layer")
        if self.ffn_activation not in ("silu", "relu"):
            raise ValueError(
                "ffn_activation, a gated feed-forward's gate, must be "
                f"'silu' or 'relu', got {self.ffn_activation!r}")
        if self.ffn_activation != "silu" and not self.ffn_gated:
            raise ValueError(
                "ffn_activation names the gate of a gated feed-forward: "
                "set ffn_gated")
        if {"mamba", "delta"} <= kinds:
            raise ValueError(
                "layer_pattern holds one recurrent kind, 'mamba' or "
                "'delta': a slot's state is one array a layer, sized by "
                "the kind")
        if self.dense_layers < 0 or bool(self.dense_layers) != bool(
                self.dense_ff):
            raise ValueError(
                "dense_layers, the leading layers with a gated MLP in "
                "the experts' place, and dense_ff, its width, go "
                f"together: dense_layers = {self.dense_layers} with "
                f"dense_ff = {self.dense_ff}")
        if (self.n_layers - self.dense_layers) % len(self.layer_pattern) \
                or self.n_layers <= self.dense_layers:
            raise ValueError(
                f"n_layers {self.n_layers} must be "
                + (f"dense_layers ({self.dense_layers}) and "
                   if self.dense_layers else "")
                + f"whole periods of "
                f"layer_pattern ({len(self.layer_pattern)} layers)")
        if self.dense_layers and not self.ffn_gated:
            raise ValueError(
                "dense_layers: a leading layer's feed-forward is a gated "
                "MLP; set ffn_gated")
        lead = set(self.leading_kinds)
        if len(lead) > 1 or not lead <= {"attention", "window"}:
            raise ValueError(
                "dense_layers: the leading layers are attention layers "
                "of one kind ('attention' or 'window', by the pattern "
                f"continued backwards), got {list(self.leading_kinds)}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                "router_score must be 'softmax' or 'sigmoid', got "
                f"{self.router_score!r}")
        if self.router_score == "softmax" and (
                self.router_bias or self.router_scale != 1.0):
            raise ValueError(
                "router_bias and router_scale belong to router_score = "
                "'sigmoid': the softmax router's gates are the softmax "
                "over the picked logits")
        if self.router_scale <= 0:
            raise ValueError("router_scale must be > 0")
        if self.qk_norm and not kinds & {"attention", "window"}:
            raise ValueError(
                "qk_norm norms an attention layer's q and k: "
                "layer_pattern holds no 'attention' or 'window' layer")
        if self.recurrent_kind and not (
                self.ssm_heads and self.ssm_head_dim and self.ssm_state
                and self.ssm_conv > 1 and self.ssm_chunk > 0):
            raise ValueError(
                f"layer_pattern has {self.recurrent_kind} layers: "
                "ssm_heads, ssm_head_dim "
                "and ssm_state must be set, ssm_conv > 1, ssm_chunk > 0")
        if "delta" in kinds and self.ssm_gate_rank <= 0:
            raise ValueError(
                "layer_pattern has delta layers: ssm_gate_rank, the "
                "width of their two low-rank gates, must be set")
        if not self.n_experts:
            raise ValueError(
                "layer_pattern: the patterned block's feed-forward is "
                "routed experts; set n_experts")
        held = self.held_experts
        if not (0 <= self.expert_first
                and self.expert_first + held <= self.n_experts):
            raise ValueError(
                f"experts {self.expert_first} to "
                f"{self.expert_first + held - 1} are not among the "
                f"{self.n_experts} routed experts")


# Named model shapes for the runtime's [model] TOML section. One
# definition shared by the payload pipeline (runtime/workload.py), the
# bench, and the driver entry (__graft_entry__.FLAGSHIP): the shape every
# performance number describes must be the shape the product path trains
# and serves. "probe" is the machinery-verification default (deliberately
# tiny); "flagship" is the 41.6M-param bench model. Only shape fields —
# everything execution-related (attention, remat, pipeline, max_seq)
# stays derived from the mesh and the [payload] knobs.
PRESETS: dict[str, dict] = {
    "probe": dict(vocab=512, d_model=128, n_heads=4, n_kv_heads=0,
                  n_layers=2, d_ff=512),
    "flagship": dict(vocab=32000, d_model=512, n_heads=8, n_kv_heads=0,
                     n_layers=8, d_ff=2048),
}


# ``draw * scale`` into the draw's own buffer: the eager product held a
# leaf twice, and two float32 copies of the largest one beside the rest
# of the tree were the device's peak (9.17 of 16 GB at the benchmark
# cell's shapes, PERF.md section 5), above anything serving holds after.
_scaled = jax.jit(lambda draw, scale: draw * scale, donate_argnums=0)


def refuse_pattern(cfg: TransformerConfig, where: str) -> None:
    """The patterned block is written once, in the paged serving path
    (models/hybrid.py): every other path refuses it by the key's name."""
    if cfg.layer_pattern:
        raise ValueError(
            f"[model] layer_pattern is set and {where} has no patterned "
            "block: it is served by serving = \"paged\" on one device "
            "(models/hybrid.py, SERVING.md \"Recurrent state\")")


def init_params(key, cfg: TransformerConfig) -> dict:
    """Initialize the flat, layer-stacked param tree (fp32)."""
    cfg.validate()
    refuse_pattern(cfg, "init_params (hybrid.init_params draws its tree)")
    k_embed, k_qkv, k_out, k_up, k_down = jax.random.split(key, 5)
    d, h, kv, dh, f, layers = (
        cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head, cfg.d_ff,
        cfg.n_layers,
    )

    def normal(k, shape, scale):
        return _scaled(jax.random.normal(k, shape, jnp.float32), scale)

    params = {
        "embedding": normal(k_embed, (cfg.vocab, d), 0.02),
        # Fused projection: [q | k | v] along the output dim; k/v carry
        # cfg.kv_heads heads (== n_heads unless GQA is on).
        "w_qkv": normal(k_qkv, (layers, d, (h + 2 * kv) * dh), d ** -0.5),
        "w_out": normal(k_out, (layers, h * dh, d), (h * dh) ** -0.5),
        "ln_attn": jnp.ones((layers, d), jnp.float32),
        "ln_mlp": jnp.ones((layers, d), jnp.float32),
        "ln_final": jnp.ones((d,), jnp.float32),
    }
    if cfg.n_experts:
        e = cfg.n_experts
        k_router = jax.random.fold_in(k_up, 1)
        params["router"] = normal(k_router, (layers, d, e), d ** -0.5)
        params["w_up_experts"] = normal(k_up, (layers, e, d, f), d ** -0.5)
        params["w_down_experts"] = normal(
            k_down, (layers, e, f, d), f ** -0.5
        )
    else:
        params["w_up"] = normal(k_up, (layers, d, f), d ** -0.5)
        params["w_down"] = normal(k_down, (layers, f, d), f ** -0.5)
    return params


def tied_readout(x, embedding):
    """Weight-tied logits readout: bf16 operands with fp32 accumulation.

    The MXU multiplies in bf16 and accumulates in fp32 natively, so this
    keeps the largest matmul in the model (D x vocab — roughly half its
    FLOPs) at full MXU rate while logits still come out fp32 for a stable
    softmax; a plain fp32 x fp32 matmul here runs at a fraction of the
    bf16 rate. Shared by forward(), contiguous decode, and paged decode:
    the inference probe (runtime/workload.py) asserts those paths agree
    token for token, so they must round identically — one helper makes
    that invariant structural.
    """
    return jnp.dot(
        x, embedding.T.astype(x.dtype), preferred_element_type=jnp.float32
    )


def stacked_layer_params(params: dict, cfg: TransformerConfig) -> tuple:
    """The per-layer param tuple in the order ``_layer`` (and the decode
    paths' layer bodies) unpack it. One definition, switched on
    ``cfg.n_experts``, so training and serving cannot disagree about the
    tuple shape or ordering."""
    if cfg.n_experts:
        return (
            params["w_qkv"], params["w_out"], params["router"],
            params["w_up_experts"], params["w_down_experts"],
            params["ln_attn"], params["ln_mlp"],
        )
    return (
        params["w_qkv"], params["w_out"], params["w_up"], params["w_down"],
        params["ln_attn"], params["ln_mlp"],
    )


# The leaves every serving program casts to ``cfg.dtype`` before their
# first use: the matmul operands (dense and expert forms) and the
# embedding (gathered, and transposed for the tied head). The router is
# read in float32 (moe._route) and so is not among them; the norm gains
# are a few kilobytes that ``_rmsnorm`` casts per program, bit-identical
# either way, and are left as they are.
_COMPUTE_DTYPE_LEAVES = frozenset({
    "embedding", "w_qkv", "w_out", "w_up", "w_down",
    "w_up_experts", "w_down_experts",
    # The patterned block's (models/hybrid.py), nested by layer kind.
    # Its router, the SSM's A_log, dt_bias and D, and every gain stay
    # float32: they are read in float32 by the equations.
    "w_in", "conv_w", "conv_b", "experts_in", "experts_out",
    "shared_in", "shared_out",
    # A delta layer's low-rank gates, an attention layer's output gate
    # and a head of its own.
    "w_low", "w_f2", "w_g2", "w_gate", "head",
})


def serving_params(params: dict, cfg: TransformerConfig) -> dict:
    """The tree serving programs read: ``params`` with the leaves they
    would cast to ``cfg.dtype`` anyway held in that dtype already.

    The layer bodies keep their ``.astype(dtype)``: on a leaf of this
    tree it is nothing, on float32 masters (the trainer, ``eval``, a
    server handed them) it is the same rounding made inside the program,
    every time it runs. A float32 ``cfg.dtype`` returns ``params``
    itself. Works on any subset of the tree's leaves, so a loader can
    hand over one leaf at a time and let each master go as its copy
    exists; a sharded leaf's copy is sharded alike.
    """
    dtype = jnp.dtype(cfg.dtype)
    if dtype == jnp.float32:
        return params
    return {
        name: (serving_params(leaf, cfg) if isinstance(leaf, dict)
               else leaf.astype(dtype) if name in _COMPUTE_DTYPE_LEAVES
               else leaf)
        for name, leaf in params.items()
    }


def _remat_policy(cfg: TransformerConfig):
    """jax.checkpoint policy for cfg.remat_policy (None = save nothing)."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def _rmsnorm(x, gain, eps: float = 1e-6):
    scale = jax.lax.rsqrt(
        jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        + eps
    )
    return (x * scale.astype(x.dtype)) * gain.astype(x.dtype)


def _rotary(x, positions, base: float = 10000.0):
    """Rotary position embedding over the head dim (applied to q and k),
    with frequencies ``base ** (-i / half)`` (``cfg.rope_theta``)."""
    *_, dh = x.shape
    half = dh // 2
    freqs = jnp.exp(
        -jnp.arange(0, half, dtype=jnp.float32) * (jnp.log(base) / half)
    )
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, half]
    cos = jnp.cos(angles).astype(x.dtype)
    sin = jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    # broadcast [T, half] over [B, T, H, half]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )


def split_qkv(cfg: TransformerConfig, qkv):
    """Split a fused [..., (H+2K)*Dh] projection into q/k/v head tensors."""
    *lead, _ = qkv.shape
    h, kv, dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    q = qkv[..., : h * dh].reshape(*lead, h, dh)
    k = qkv[..., h * dh : (h + kv) * dh].reshape(*lead, kv, dh)
    v = qkv[..., (h + kv) * dh :].reshape(*lead, kv, dh)
    return q, k, v


def _layer(cfg: TransformerConfig, x, layer_params, mesh=None,
           constrain_moe: bool = True, seq_manual=None):
    """One pre-norm decoder block. x: [B, T, D] in compute dtype.

    Returns ``(x, aux)`` — ``aux`` is the MoE router's load-balancing
    loss for this layer (0.0 for a dense FFN). ``constrain_moe=False``
    drops the MoE activation sharding constraint: inside the pipeline's
    partial-manual shard_map a NamedSharding over the mesh cannot be
    expressed (manual axes are rejected), and expert placement instead
    rides the expert weights' own sharding through the dispatch/combine
    einsums.

    ``seq_manual = (axis_name, sp)`` means this body is ALREADY inside a
    shard_map whose manual axes include the sequence axis (the pp x sp
    composition, parallel/pipeline.py): ``x`` is a local ``T/sp`` chunk,
    rotary positions offset by the device's chunk index, and ring
    attention calls its per-device body directly — the axis collectives
    (ppermute) resolve against the enclosing manual context instead of
    opening a nested shard_map.
    """
    if cfg.n_experts:
        w_qkv, w_out, router, w_up, w_down, ln_attn, ln_mlp = layer_params
    else:
        w_qkv, w_out, w_up, w_down, ln_attn, ln_mlp = layer_params
    batch, seq, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    dtype = x.dtype

    # Attention.
    normed = _rmsnorm(x, ln_attn)
    qkv = normed @ w_qkv.astype(dtype)  # [B, T, (H+2K)*Dh]
    q, k, v = split_qkv(cfg, qkv)
    positions = jnp.arange(seq)
    if seq_manual is not None:
        # seq here is the LOCAL chunk length; chunks are contiguous in
        # sequence order, so global positions offset by the ring index.
        positions = lax.axis_index(seq_manual[0]) * seq + positions
    q = _rotary(q, positions, cfg.rope_theta)
    k = _rotary(k, positions, cfg.rope_theta)
    if kv != h:
        # GQA at train time: broadcast each KV head over its query group.
        # XLA fuses the broadcast into the batched matmuls — no repeated
        # K/V is materialized in HBM.
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    if seq_manual is not None and cfg.attention == "ring":
        from kvedge_tpu.parallel.ringattention import _ring_attention_local

        attended = _ring_attention_local(
            q, k, v, axis_name=seq_manual[0], sp=seq_manual[1]
        )
        attended = attended.reshape(batch, seq, h * dh)
    elif seq_manual is not None and cfg.attention == "ulysses":
        # Same move that converted ring x stage in round 3: the
        # per-device body runs directly inside the enclosing manual
        # region — lax.all_to_all resolves against a manual axis exactly
        # like ppermute does, so the head scatter/gather needs no nested
        # shard_map. A 'model' axis stays automatic out here too: the
        # all_to_all splits each model shard's local heads over the seq
        # axis (n_heads % (sp*tp), enforced by ulysses_attention's
        # non-pipeline twin and derive_model_config).
        from kvedge_tpu.parallel.ulysses import _ulysses_local

        attended = _ulysses_local(q, k, v, axis_name=seq_manual[0])
        attended = attended.reshape(batch, seq, h * dh)
    elif cfg.attention in ("ring", "ulysses"):
        if mesh is None:
            raise ValueError(
                f"attention={cfg.attention!r} needs a mesh with a 'seq' "
                "axis passed to forward()/make_train_step()"
            )
        if cfg.attention == "ring":
            from kvedge_tpu.parallel.ringattention import ring_attention

            attended = ring_attention(q, k, v, mesh)
        else:
            from kvedge_tpu.parallel.ulysses import ulysses_attention

            attended = ulysses_attention(q, k, v, mesh)
        attended = attended.reshape(batch, seq, h * dh)
    elif cfg.attention == "flash":
        from kvedge_tpu.ops import pallas_interpret
        from kvedge_tpu.ops.attention import flash_attention, pick_block

        # [B, T, H, dh] -> [B*H, T, dh] (head-major programs for the grid).
        def heads_to_programs(x):
            return x.transpose(0, 2, 1, 3).reshape(batch * h, seq, dh)

        attended = flash_attention(
            heads_to_programs(q), heads_to_programs(k), heads_to_programs(v),
            pick_block(seq),
            pallas_interpret(),
        )
        attended = (
            attended.reshape(batch, h, seq, dh)
            .transpose(0, 2, 1, 3)
            .reshape(batch, seq, h * dh)
        )
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (dh ** 0.5)
        causal = jnp.tril(jnp.ones((seq, seq), jnp.bool_))
        scores = jnp.where(causal[None, None], scores, jnp.finfo(dtype).min)
        weights = jax.nn.softmax(
            scores.astype(jnp.float32), axis=-1
        ).astype(dtype)
        attended = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
        attended = attended.reshape(batch, seq, h * dh)
    x = x + attended @ w_out.astype(dtype)

    # MLP — dense, or switch-routed experts (models/moe.py).
    normed = _rmsnorm(x, ln_mlp)
    if cfg.n_experts:
        from kvedge_tpu.models.moe import moe_ffn

        out, aux = moe_ffn(
            normed.reshape(batch * seq, d), router, w_up, w_down,
            capacity_factor=cfg.expert_capacity_factor,
            top_k=cfg.expert_top_k, mesh=mesh if constrain_moe else None,
        )
        x = x + out.reshape(batch, seq, d)
    else:
        up = normed @ w_up.astype(dtype)
        x = x + jax.nn.gelu(up) @ w_down.astype(dtype)
        aux = jnp.zeros((), jnp.float32)
    return x, aux


def forward_hidden(params: dict, tokens, cfg: TransformerConfig,
                   mesh=None):
    """tokens [B, T] int32 -> (hidden [B, T, D] compute-dtype, aux fp32).

    The transformer stack up to and including the final RMSNorm — i.e.
    everything except the readout matmul. Split out so the training loss
    can feed the hidden states straight into the fused cross-entropy
    kernel (ops/xent.py) without logits ever materializing; the inference
    paths apply :func:`tied_readout` on top via :func:`forward_with_aux`.

    ``aux`` is the mean per-layer MoE load-balancing loss (0.0 for dense
    configs). ``mesh`` is only needed for the sequence-parallel attention
    modes (``'ring'``/``'ulysses'``); when given, activations are pinned
    seq-sharded between layers so the LN/MLP work stays sequence-parallel
    too.
    """
    refuse_pattern(cfg, "the trainer's forward pass")
    dtype = jnp.dtype(cfg.dtype)
    embedding = params["embedding"]
    x = embedding[tokens].astype(dtype)  # [B, T, D]

    constrain = None
    if cfg.attention in ("ring", "ulysses") and mesh is not None:
        from kvedge_tpu.parallel.ringattention import sequence_sharding

        sharding = sequence_sharding(mesh)

        def constrain(x):
            return lax.with_sharding_constraint(x, sharding)

        x = constrain(x)

    stacked = stacked_layer_params(params, cfg)

    if cfg.pipeline_stages > 1:
        if mesh is None:
            raise ValueError(
                "pipeline_stages > 1 needs a mesh with a 'stage' axis "
                "passed to forward()/make_train_step()"
            )
        from kvedge_tpu.parallel.pipeline import pipeline_layers

        # The ``expert`` axis (like ``model``) stays automatic inside the
        # pipeline's shard_map; constrain_moe=False because an activation
        # NamedSharding cannot be expressed in that partial-manual
        # context — expert placement propagates from the stacked expert
        # weights' own sharding instead. A ``seq`` axis joins the
        # pipeline's manual axes: the layer body runs seq-local and
        # calls its strategy's per-device body directly — the ring's
        # ppermute fold or ulysses' all_to_all scatter both resolve
        # against the enclosing manual axis (pp x sp).
        sp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("seq", 0)
        seq_manual = (("seq", sp)
                      if cfg.attention in ("ring", "ulysses") and sp
                      else None)
        x, aux = pipeline_layers(
            x, stacked,
            lambda carry, lp: _layer(cfg, carry, lp, mesh,
                                     constrain_moe=False,
                                     seq_manual=seq_manual),
            mesh, n_layers=cfg.n_layers,
            seq_axis="seq" if seq_manual else None,
            n_microbatches=cfg.pipeline_microbatches, remat=cfg.remat,
            remat_policy=_remat_policy(cfg),
        )
        return _rmsnorm(x, params["ln_final"]), aux

    def body(carry, layer_params):
        out, aux = _layer(cfg, carry, layer_params, mesh)
        if constrain is not None:
            out = constrain(out)
        return out, aux

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    x, aux_per_layer = lax.scan(body, x, stacked)
    return _rmsnorm(x, params["ln_final"]), jnp.mean(aux_per_layer)


def forward_with_aux(params: dict, tokens, cfg: TransformerConfig,
                     mesh=None):
    """tokens [B, T] int32 -> (logits [B, T, V] fp32, aux scalar fp32).

    See :func:`forward_hidden` for the mesh/aux semantics; this applies
    the weight-tied readout on top.
    """
    x, aux = forward_hidden(params, tokens, cfg, mesh)
    return tied_readout(x, params["embedding"]), aux


def forward(params: dict, tokens, cfg: TransformerConfig, mesh=None):
    """tokens [B, T] int32 -> logits [B, T, V] (fp32).

    See :func:`forward_with_aux` for the mesh semantics; this wrapper
    drops the MoE aux loss for callers that only want logits.
    """
    logits, _ = forward_with_aux(params, tokens, cfg, mesh)
    return logits


def _fused_xent_loss(params: dict, inputs, targets,
                     cfg: TransformerConfig, mesh=None):
    """Training CE via the Pallas fused readout kernel (ops/xent.py).

    Hidden states go straight into blockwise logsumexp/target-logit
    kernels — the [B, T, V] logits tensor never exists in either pass.
    Mesh handling (``needs_mesh`` guarantees the mesh reaches here
    whenever fused_xent is on):

    * ``model`` axis > 1 — rejected: the D contraction would need a psum
      before the online softmax.
    * ``data`` axis > 1 — the kernel runs under ``shard_map`` over the
      batch rows (embedding replicated); without it XLA cannot partition
      an opaque custom call and would gather the full batch per device.
    * single-device meshes (and mesh=None from non-training callers) run
      the kernel directly.
    """
    from kvedge_tpu.ops import pallas_interpret
    from kvedge_tpu.ops.xent import fused_xent

    interpret = pallas_interpret()
    hidden, aux = forward_hidden(params, inputs, cfg, mesh)
    b, t, d = hidden.shape
    rows = hidden.reshape(b * t, d)
    flat_targets = targets.reshape(b * t)

    axis_sizes = dict(mesh.shape) if mesh is not None else {}
    if axis_sizes.get("model", 1) > 1:
        raise ValueError(
            "fused_xent does not compose with tensor parallelism "
            "('model' axis > 1): the D contraction would need a psum "
            "before the online softmax; disable fused_xent"
        )
    if axis_sizes.get("data", 1) > 1:
        from jax.sharding import PartitionSpec as P

        # check_vma off: pallas_call out_shapes don't declare mesh-axis
        # variance, which the checker would otherwise require.
        per_row = jax.shard_map(
            lambda x, e, tg: fused_xent(x, e, tg, interpret),
            mesh=mesh,
            in_specs=(P("data", None), P(), P("data")),
            out_specs=P("data"),
            check_vma=False,
        )(rows, params["embedding"], flat_targets)
    else:
        per_row = fused_xent(rows, params["embedding"], flat_targets,
                             interpret)
    return jnp.mean(per_row), aux


def loss_fn(params: dict, batch, cfg: TransformerConfig, mesh=None):
    """Next-token cross-entropy. batch [B, T] int32; targets are shifted."""
    inputs = batch[:, :-1]
    targets = batch[:, 1:]
    if cfg.fused_xent:
        ce, aux = _fused_xent_loss(params, inputs, targets, cfg, mesh)
    else:
        logits, aux = forward_with_aux(params, inputs, cfg, mesh)
        # Fused cross-entropy (XLA level): logsumexp(logits) -
        # logits[target] needs only two [B, T] reductions over the vocab
        # axis, instead of materializing a second [B, T, V] fp32
        # log-probs tensor (which at vocab=32000 would be the largest
        # buffer in the step).
        target_logit = jnp.take_along_axis(
            logits, targets[..., None], axis=-1
        )[..., 0]
        ce = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - target_logit)
    if cfg.n_experts:
        # Router load balancing: without it, top-1 routing collapses onto
        # a few experts and the rest never train.
        ce = ce + cfg.moe_aux_weight * aux
    return ce


def make_train_step(cfg: TransformerConfig, optimizer=None, mesh=None):
    """Build (init_opt_state, train_step). Donates params/opt_state buffers.

    ``mesh`` is required for the sequence-parallel attention modes
    (``'ring'``/``'ulysses'`` — their shard_map needs the concrete mesh);
    otherwise sharding stays annotation-only and the mesh argument is
    unused.
    """
    import optax

    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)

    def init_opt_state(params):
        return optimizer.init(params)

    use_1f1b = cfg.pipeline_stages > 1 and cfg.pipeline_schedule == "1f1b"
    if use_1f1b:
        from kvedge_tpu.parallel.pipeline1f1b import (
            pipeline_1f1b_loss_and_grads,
        )

        if mesh is None:
            raise ValueError(
                "pipeline_schedule='1f1b' needs the mesh passed to "
                "make_train_step()"
            )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        if use_1f1b:
            # The fused 1F1B schedule builds the backward itself —
            # autodiff cannot produce a 1F1B schedule from a forward
            # scan (parallel/pipeline1f1b.py).
            loss, grads = pipeline_1f1b_loss_and_grads(
                params, batch, cfg, mesh
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch, cfg, mesh
            )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return init_opt_state, train_step
