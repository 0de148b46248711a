"""Pipeline parallelism: the layer stack sharded over a ``stage`` mesh axis.

The fourth scale-out dimension (after ``data``, ``model``, ``seq``,
``expert`` — the reference has no parallelism of any kind, SURVEY.md §5):
for models too deep for one chip even with tensor/expert sharding, the
layer-stacked parameter arrays shard their leading ``L`` axis over
``stage`` — each device holds ``L/S`` whole layers — and activations flow
stage-to-stage through a GPipe-style microbatch schedule.

TPU-first design:

* **The layer axis is already stacked** for ``lax.scan`` (one compiled
  layer body), so pipelining is just *sharding that axis*: in_specs put
  ``P('stage')`` on dim 0 of every stacked param and each device scans
  its local ``L/S`` slice. No per-stage module surgery.
* **Stage hand-off is one ``ppermute`` hop per schedule step** — neighbor
  traffic that rides ICI, exactly like ring attention's K/V rotation.
* **The schedule is a ``lax.scan`` over ``M + S - 1`` steps** (M
  microbatches, S stages): static trip count, no data-dependent control
  flow. During fill/drain, off-schedule devices compute on garbage —
  the standard SPMD pipeline bubble; wall-clock efficiency is
  ``M / (M + S - 1)``, so more microbatches amortize it.
* **Differentiable end-to-end**: ppermute's transpose is the reverse
  permutation and the final psum's is a broadcast, so ``jax.grad``
  through the whole schedule yields a correct backward without
  hand-written stage logic. This is GPipe, NOT 1F1B: the backward only
  starts after all M forwards, so without remat the live activations
  would grow with M (1F1B's defining property — <= S microbatches in
  flight — does not hold). The schedule instead bounds memory with
  ``remat=True`` (default): each microbatch x stage body checkpoints,
  so the backward recomputes activations and the forward keeps only
  layer inputs — peak live activations stay O(M x mb x T x D) carry
  state, flat in depth. The bubble is GPipe's ``(S-1)/(M+S-1)`` in both
  passes either way. tests/test_pipeline.py pins the memory claim with
  a compiled-HLO peak-memory comparison at M=S vs M=2S.

Composes with ``data`` parallelism (microbatches shard their batch dim on
``data``), with ``model`` tensor parallelism, and with ``expert`` MoE
parallelism: only stage/data go manual in the shard_map, so ``model``
and ``expert`` axes stay *automatic* — XLA keeps Megatron-partitioning
feature dims and partitioning the MoE dispatch/combine einsums (the
expert all-to-alls) inside each stage body. MoE under pipelining has two
semantic shifts, both inherent to microbatching: expert capacity binds
per microbatch (ceil(k*mb_tokens*factor/E) slots per microbatch rather
than one batch-wide pool), and the router's load-balancing statistics
are computed per microbatch and averaged — fill/drain steps, which
compute on garbage, are masked out of that average (see ``step_fn``).
Sequence parallelism composes too (``seq_axis``): the seq axis joins
the manual set and the layer body calls its strategy's per-device body
directly — the ring's ppermute fold or ulysses' all_to_all head
scatter; both collectives resolve against the enclosing manual axis —
see :func:`pipeline_layers`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _stage_specs(n_arrays: int, data_axis: str | None,
                 seq_axis: str | None):
    """in_specs: activations [M, mb, T, D] + n stacked params [L, ...]."""
    act = P(None, data_axis, seq_axis, None)
    return (act, *([P("stage")] * n_arrays))


def pipeline_layers(x, stacked, layer_fn, mesh, *, n_layers: int,
                    stage_axis: str = "stage", data_axis: str = "data",
                    seq_axis: str | None = None,
                    n_microbatches: int = 0, remat: bool = True,
                    remat_policy=None):
    """Run ``n_layers`` stacked layers over ``x``, pipelined over stages.

    x: [B, T, D] (compute dtype); ``stacked``: tuple of layer-stacked
    arrays, each [L, ...]; ``layer_fn(carry, layer_params) ->
    (carry, aux)`` is the single-layer body (already closed over the
    config), where ``aux`` is its scalar auxiliary loss (the MoE router's
    load-balancing term; 0.0 for dense layers). Returns ``(out [B, T, D],
    aux scalar fp32)`` — ``aux`` is the mean over real (non-bubble)
    microbatch×layer evaluations, replicated across the mesh.

    With ``seq_axis``, the activations' T dim additionally shards over
    that axis and the axis joins the manual set — this is how pp×sp
    composes: ring attention cannot NEST a shard_map inside this one,
    but its per-device body only needs ``lax.axis_index(seq_axis)``, so
    the layer body calls ``_ring_attention_local`` directly and the
    ppermute stage hand-offs move ``1/sp`` of the tokens per hop. The
    caller's ``layer_fn`` must already be seq-local (global positions
    from the axis index; see models/transformer.py ``_layer``).
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if stage_axis not in axis_sizes:
        raise ValueError(
            f"mesh has no {stage_axis!r} axis (axes: {sorted(axis_sizes)}) "
            "— pipeline parallelism needs a stage axis"
        )
    stages = axis_sizes[stage_axis]
    if n_layers % stages:
        raise ValueError(
            f"n_layers {n_layers} must divide by the {stage_axis!r} axis "
            f"size {stages} (whole layers per stage)"
        )
    if (any(axis_sizes.get(ax, 1) > 1 for ax in ("model", "expert"))
            and x.dtype == jnp.bfloat16
            and jax.default_backend() == "cpu"):
        # XLA's CPU layout-assignment pass crashes the process ("Invalid
        # binary instruction opcode copy") on bf16 contractions against
        # auto-partitioned operands inside shard_map — a backend compiler
        # bug (observed on jax 0.9.0 / CPU only; hits both the Megatron
        # model axis and the MoE expert axis). Whether the TPU backend
        # compiles the bf16 combination is UNVERIFIED: a multi-chip
        # stage x model mesh cannot exist on this build's single chip,
        # so pp x tp/ep is proven in fp32 (CPU mesh) and bf16 remains an
        # untested claim. A loud error beats a segfault either way.
        raise ValueError(
            "bf16 pipeline x auto-partitioned model/expert axes trip an "
            "XLA CPU-backend compiler crash; use float32 compute "
            "(dtype='float32') when testing these combinations on the "
            "CPU backend"
        )
    batch = x.shape[0]
    micro = n_microbatches or stages
    if batch % micro:
        raise ValueError(
            f"batch {batch} must divide into {micro} microbatches"
        )
    dspec = data_axis if data_axis in axis_sizes else None
    if dspec and (batch // micro) % axis_sizes[data_axis]:
        raise ValueError(
            f"microbatch size {batch // micro} (batch {batch} / {micro} "
            f"microbatches) must divide by the {data_axis!r} axis size "
            f"{axis_sizes[data_axis]}"
        )
    if seq_axis is not None:
        if seq_axis not in axis_sizes:
            raise ValueError(
                f"mesh has no {seq_axis!r} axis (axes: "
                f"{sorted(axis_sizes)}) — pp x sp needs one"
            )
        if x.shape[1] % axis_sizes[seq_axis]:
            raise ValueError(
                f"sequence length {x.shape[1]} must divide by the "
                f"{seq_axis!r} axis size {axis_sizes[seq_axis]}"
            )

    x_mb = x.reshape(micro, batch // micro, *x.shape[1:])  # [M, mb, T, D]

    def local_fn(x_local, *stacked_local):
        # x_local: [M, mb_local, T, D]; stacked_local: [L/S, ...] each.
        stage = lax.axis_index(stage_axis)
        steps = micro + stages - 1
        forward_hop = [(i, i + 1) for i in range(stages - 1)]

        def apply_local_layers(h):
            body_fn = layer_fn
            if remat:
                body_fn = jax.checkpoint(body_fn, policy=remat_policy)
            h, auxes = lax.scan(body_fn, h, stacked_local)
            return h, jnp.mean(auxes)

        # Initial carries must already vary over the stage axis: the loop
        # body mixes in stage-dependent values (axis_index, ppermute), and
        # scan requires carry-in/carry-out types — including varying
        # manual axes — to match (same trick as ringattention.py's
        # initializers).
        zero_stage = stage.astype(x_local.dtype) * 0.0
        state0 = x_local[0] * 0.0 + zero_stage
        outputs0 = x_local * 0.0 + zero_stage
        # The aux accumulator's carry type must already vary over BOTH
        # manual axes (stage from axis_index, data from the input tokens)
        # or scan rejects the carry as type-unstable.
        aux0 = (x_local.ravel()[0].astype(jnp.float32) * 0.0
                + stage.astype(jnp.float32) * 0.0)

        def step_fn(carry, step):
            state, outputs, aux_acc = carry
            # Stage 0 feeds microbatch `step` during the fill phase;
            # later stages consume what the previous stage sent.
            feed = x_local[jnp.clip(step, 0, micro - 1)]
            h = jnp.where(stage == 0, feed, state)
            h, aux_mb = apply_local_layers(h)
            # Stage k computes real work at steps [k, k + micro); the
            # fill/drain bubble steps run on garbage and must not leak
            # into the router statistics.
            real = (step >= stage) & (step < stage + micro)
            aux_acc = aux_acc + jnp.where(real, aux_mb, 0.0)
            # The last stage finishes microbatch `step - (S-1)`.
            out_idx = step - (stages - 1)
            finished = (stage == stages - 1) & (out_idx >= 0)
            outputs = jnp.where(
                finished,
                outputs.at[jnp.clip(out_idx, 0, micro - 1)].set(h),
                outputs,
            )
            state = lax.ppermute(h, stage_axis, forward_hop)
            return (state, outputs, aux_acc), None

        (_, outputs, aux_acc), _ = lax.scan(
            step_fn, (state0, outputs0, aux0), jnp.arange(steps)
        )
        # Only the last stage holds real outputs; zero elsewhere, so one
        # psum over the stage axis replicates them to every stage (its
        # transpose under grad is a cheap broadcast).
        outputs = jnp.where(stage == stages - 1, outputs, 0.0)
        # Each stage accumulated `micro` real per-microbatch aux means
        # over its local layers; the full-depth, all-microbatch mean is
        # the stage-sum divided by micro*stages, then averaged over data
        # shards (each feeds different tokens).
        aux = lax.psum(aux_acc, stage_axis) / (micro * stages)
        if dspec:
            aux = lax.pmean(aux, data_axis)
        if seq_axis is not None:
            # Each seq shard's aux came from its own token chunk.
            aux = lax.pmean(aux, seq_axis)
        return lax.psum(outputs, stage_axis), aux

    # Only the stage (and data, and — for pp x sp — seq) axes go manual;
    # any other mesh axis — notably a Megatron ``model`` axis on the
    # stacked params' feature dims — stays *automatic*: XLA keeps
    # partitioning those dims and inserting the tensor-parallel
    # collectives inside each stage body, so pp composes with tp without
    # the specs having to name it.
    manual = frozenset(
        {stage_axis} | ({data_axis} if dspec else set())
        | ({seq_axis} if seq_axis is not None else set())
    )
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=_stage_specs(len(stacked), dspec, seq_axis),
        out_specs=(P(None, dspec, seq_axis, None), P()),
        axis_names=manual,
    )(x_mb, *stacked)
    return out.reshape(batch, *x.shape[1:]), aux
