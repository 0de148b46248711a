"""Workload payloads: prove real sharded training / serving works.

A step up from the matmul device check:

* ``transformer-probe`` builds the flagship transformer on the configured
  mesh, runs one jitted, dp×tp-sharded train step, and verifies the loss
  is finite and near log(vocab) for random data.
* ``inference-probe`` exercises the serving path instead: GQA prefill +
  KV-cache greedy decode (models/decode.py) cross-checked token-for-token
  against the cache-less forward pass — broken cache plumbing cannot agree
  with teacher forcing.

These are the strongest "the provisioned runtime actually works" signals
the status endpoint can report.
"""

from __future__ import annotations

import collections
import threading
import time

from kvedge_tpu.config.runtime_config import RuntimeConfig
from kvedge_tpu.runtime.devicecheck import DeviceCheckResult, run_device_check

# Deliberately tiny: the probe verifies machinery, not throughput. The
# shape itself is models/transformer.py PRESETS["probe"] — the same table
# the [model] TOML section resolves against — so the probes and an
# unconfigured payload can never drift apart.
PROBE_VOCAB = 512
PROBE_D_MODEL = 128
PROBE_LAYERS = 2
PROBE_SEQ = 64
PROBE_BATCH_PER_DEVICE = 2


class MeshConfigError(ValueError):
    """The operator's mesh cannot run this payload (clear config message)."""


def derive_model_config(cfg: RuntimeConfig, *, seq: int):
    """(TransformerConfig, mesh) for a payload: ``[model]`` x the mesh.

    One derivation shared by the transformer-probe, ``train``, ``eval``,
    and ``serve`` payloads, so every mesh family the probe exercises is a
    mesh family training (and checkpoint-compatible serving) supports.

    The architecture comes from the ``[model]`` TOML section: a preset
    ("probe" by default, "flagship" for the bench model —
    models/transformer.py PRESETS) overridden by any explicitly-set
    field. The mesh then constrains execution:

    * ``seq`` axis -> sequence-parallel attention (ring by default, or
      the strategy named by ``[payload] attention``);
    * ``expert`` axis -> mixture-of-experts FFN sharded over it;
    * ``stage`` axis -> pipelined layer stack; composes with ``model``,
      ``expert``, and ``seq`` (ring or ulysses — the seq axis joins the
      pipeline's manual axes and the strategy's per-device body runs
      inside them);
    * ``model`` axis -> Megatron tensor parallelism (annotation-only).

    Merge discipline: preset-derived values ADAPT to the mesh (head
    count rounds up for ulysses, depth rounds up to a stage multiple,
    expert count follows the expert axis) — the same templated config
    must boot across deployment sizes. Explicitly-set ``[model]`` values
    are authoritative: a mesh they cannot run on raises
    :class:`MeshConfigError`, never a silent adjustment — the operator
    asked for a specific architecture and must get exactly it or a
    clear refusal.
    """
    from kvedge_tpu.models import PRESETS, TransformerConfig
    from kvedge_tpu.parallel import build_mesh

    mesh = build_mesh(cfg.mesh)
    axis_sizes = dict(mesh.shape)
    model_axis = axis_sizes.get("model", 1)
    sp = axis_sizes.get("seq", 1)
    attention = cfg.payload_attention or ("ring" if sp > 1 else "naive")
    if sp > 1 and attention not in ("ring", "ulysses"):
        # The old data x model-only guard existed to keep mesh axes from
        # being SILENTLY ignored; an explicit [payload] attention override
        # must not reopen that hole — a seq axis with local attention
        # would train replicas and report success.
        raise MeshConfigError(
            f"mesh declares a 'seq' axis but [payload] attention = "
            f"{attention!r} would silently ignore it (the axis devices "
            "would hold replicas); use attention = \"ring\"/\"ulysses\" "
            "or drop the seq axis"
        )
    if "seq" not in axis_sizes and attention in ("ring", "ulysses"):
        # Presence, not size: a seq axis that resolves to 1 on a small
        # deployment still exists in the mesh, and the degenerate
        # one-shard ring runs fine — the same templated config must boot
        # across deployment sizes.
        raise MeshConfigError(
            f"[payload] attention = {attention!r} is sequence-parallel "
            "and needs a 'seq' axis in the mesh"
        )
    spec = cfg.model
    if spec.layer_pattern and mesh.devices.size > 1:
        raise MeshConfigError(
            f"[model] layer_pattern cannot run on a mesh of "
            f"{mesh.devices.size} devices {axis_sizes}: the patterned "
            "block has no sharding rules and its expert layer no "
            "exchange; it is served on one device that holds its share "
            "([model] experts_held)")
    base = PRESETS[spec.preset or "probe"]
    n_heads = spec.n_heads or max(base["n_heads"], model_axis)
    group = sp * model_axis
    if attention == "ulysses" and n_heads % group:
        # Ulysses scatters each model shard's heads over the seq axis:
        # heads must divide by sp x tp (parallel/ulysses.py).
        if spec.n_heads:
            raise MeshConfigError(
                f"[model] n_heads = {spec.n_heads} cannot run ulysses "
                f"attention on this mesh: the head count must divide by "
                f"seq x model = {group}"
            )
        n_heads = group * -(-n_heads // group)  # round up, preset-derived
    n_experts_axis = axis_sizes.get("expert", 1)
    if spec.experts:
        n_experts = spec.experts
        if n_experts % n_experts_axis:
            raise MeshConfigError(
                f"[model] experts = {n_experts} must divide by the "
                f"mesh's expert axis ({n_experts_axis}) — each device "
                "holds E/ep whole experts (parallel/sharding.py)"
            )
    else:
        n_experts = n_experts_axis if n_experts_axis > 1 else 0
    if not n_experts and (spec.expert_top_k or spec.expert_capacity_factor):
        # The authoritative-override contract cuts both ways: MoE knobs
        # on a model that resolved dense would be silently dead config.
        raise MeshConfigError(
            "[model] expert_top_k/expert_capacity_factor are set but the "
            "model is dense (no [model] experts and no 'expert' mesh "
            "axis) — set experts = N or drop the MoE knobs"
        )
    stages = axis_sizes.get("stage", 1)
    n_layers = spec.n_layers or base["n_layers"]
    if stages > 1 and n_layers % stages:
        if spec.n_layers:
            raise MeshConfigError(
                f"[model] n_layers = {n_layers} must divide by the "
                f"mesh's stage axis ({stages}) — each stage holds L/S "
                "whole layers"
            )
        n_layers = stages * -(-n_layers // stages)  # round up
    top_k = spec.expert_top_k or 1
    # Default: provably drop-free capacity (factor * top_k >= E): the
    # same derived config feeds train AND serve, and serving routes
    # droplessly — a binding training capacity would make POST /generate
    # silently disagree with the trained model (the
    # warn_if_train_serve_divergence regime). Operators who accept that
    # divergence set [model] expert_capacity_factor themselves.
    capacity = (spec.expert_capacity_factor
                or max(n_experts, 1) / top_k)
    # pp x tp and pp x ep run fp32: bf16 contractions against
    # auto-partitioned model/expert axes crash XLA's CPU backend (see
    # parallel/pipeline.py), and payloads must be portable across the
    # CPU test mesh and real TPUs.
    import jax

    dtype = ("float32"
             if stages > 1 and (model_axis > 1 or n_experts > 1)
             and jax.default_backend() == "cpu"
             else TransformerConfig.dtype)
    tcfg = TransformerConfig(
        vocab=spec.vocab or base["vocab"],
        d_model=spec.d_model or base["d_model"],
        n_heads=n_heads,
        n_kv_heads=spec.n_kv_heads or base["n_kv_heads"],
        n_layers=n_layers,
        d_ff=spec.d_ff or base["d_ff"],
        max_seq=seq,
        dtype=dtype,
        attention=attention,
        n_experts=n_experts,
        expert_top_k=top_k,
        expert_capacity_factor=float(capacity),
        pipeline_stages=stages if stages > 1 else 0,
        pipeline_schedule=spec.pipeline_schedule or "gpipe",
        paged_attention=cfg.payload_paged_attention or "auto",
        layer_pattern=tuple(spec.layer_pattern),
        ssm_heads=spec.ssm_heads,
        ssm_head_dim=spec.ssm_head_dim,
        ssm_state=spec.ssm_state,
        ssm_conv=spec.ssm_conv or TransformerConfig.ssm_conv,
        ssm_chunk=spec.ssm_chunk or TransformerConfig.ssm_chunk,
        ssm_gate_rank=spec.ssm_gate_rank,
        head_dim=spec.head_dim,
        attention_gate=spec.attention_gate,
        untied_head=spec.untied_head,
        experts_held=spec.experts_held,
        expert_first=spec.expert_first,
        shared_ff=spec.shared_ff,
        ffn_gated=spec.ffn_gated,
        ffn_activation=spec.ffn_activation or "silu",
        router_before_mixer=spec.router_before_mixer,
        dense_layers=spec.dense_layers,
        dense_ff=spec.dense_ff,
        router_score=spec.router_score or "softmax",
        router_bias=spec.router_bias,
        router_scale=spec.router_scale or 1.0,
        qk_norm=spec.qk_norm,
        norm_after=spec.norm_after,
        attention_window=spec.attention_window,
        rope_theta=spec.rope_theta or TransformerConfig.rope_theta,
        embedding_multiplier=spec.embedding_multiplier or 1.0,
        residual_multiplier=spec.residual_multiplier or 1.0,
        attention_multiplier=spec.attention_multiplier,
        logits_scaling=spec.logits_scaling or 1.0,
        rotary=spec.rotary,
        norm_eps=spec.norm_eps or TransformerConfig.norm_eps,
    )
    try:
        # Cross-field architecture errors (d_model % n_heads, GQA head
        # divisibility, top_k vs experts) surface as the same clear
        # config-refusal every other bad combination gets.
        tcfg.validate()
    except ValueError as e:
        raise MeshConfigError(f"[model] configuration is invalid: {e}") \
            from e
    return tcfg, mesh


def run_transformer_probe(cfg: RuntimeConfig) -> DeviceCheckResult:
    # The matmul device check runs first: fail fast on visibility problems
    # with a cheaper, clearer error before compiling a model.
    base = run_device_check(cfg)
    if not base.ok:
        return base

    import dataclasses
    import math

    import jax
    import jax.numpy as jnp

    from kvedge_tpu.models import init_params, make_train_step
    from kvedge_tpu.parallel import shard_batch, shard_params

    try:
        tcfg, mesh = derive_model_config(cfg, seq=PROBE_SEQ)
    except MeshConfigError as e:
        # A healthy runtime with an un-runnable mesh combination: surface
        # a clear config message, not a generic "probe failed" traceback.
        return dataclasses.replace(base, ok=False, error=str(e))
    try:
        # Inside the try: an sp-derived head count can make the model
        # config itself invalid (d_model % n_heads), and that must surface
        # as a structured probe failure like every other error here.
        key = jax.random.PRNGKey(0)
        params = shard_params(mesh, init_params(key, tcfg))
        init_opt, train_step = make_train_step(
            tcfg, mesh=mesh if tcfg.needs_mesh else None
        )
        opt_state = init_opt(params)
        batch = shard_batch(
            mesh,
            jax.random.randint(
                key,
                (PROBE_BATCH_PER_DEVICE * base.device_count, PROBE_SEQ + 1),
                0, tcfg.vocab, dtype=jnp.int32,
            ),
        )
        start = time.perf_counter()
        params, opt_state, loss = train_step(params, opt_state, batch)
        loss = float(loss)
        elapsed_ms = (time.perf_counter() - start) * 1000.0
    except Exception as e:
        return dataclasses.replace(
            base, ok=False, error=f"transformer probe failed: {e!r}",
        )

    # Untrained model on random tokens: loss ≈ ln(vocab). Allow a wide band;
    # NaN/inf or wildly-off values mean broken math or sharding.
    expected = math.log(tcfg.vocab)
    if not math.isfinite(loss) or abs(loss - expected) > 0.5 * expected:
        return dataclasses.replace(
            base, ok=False,
            error=f"probe loss {loss:.3f} far from ln(V)={expected:.3f}",
        )
    return dataclasses.replace(
        base, probe_ms=elapsed_ms, probe_checksum=loss,
    )


def run_train_payload(cfg: RuntimeConfig) -> DeviceCheckResult:
    """The "train" payload: resumable training over a corpus on the PVC.

    The full persistence story, live: train ``[payload] steps`` total
    steps over the ``corpus`` token file, checkpointing through the
    state volume. A rescheduled pod restores the latest checkpoint and
    reopens the feeder at exactly that batch (deterministic order), so
    steps count from 0 across ALL pod generations — the payload-level
    analogue of EdgeHub's PVC-backed message state (reference
    ``README.md:88``). A run whose target was already reached reports ok
    immediately.

    On a multi-host slice (``jax.process_count() > 1``) each process
    feeds its own rows of the global batch (sharded feeder offsets) and
    the global array is assembled with
    ``jax.make_array_from_process_local_data``; checkpoints then REQUIRE
    ``[runtime] checkpoint_dir`` on shared storage. A killed slice
    resumes to the same trajectory as an uninterrupted single-process
    run (tests/test_distributed.py).
    """
    base = run_device_check(cfg)
    if not base.ok:
        return base

    import dataclasses
    import functools
    import math

    import jax
    import numpy as np

    from kvedge_tpu.data import open_feeder
    from kvedge_tpu.models import TransformerConfig
    from kvedge_tpu.models.training import run_training
    from kvedge_tpu.parallel import build_mesh, shard_batch, shard_tree
    from kvedge_tpu.runtime import heartbeat
    from kvedge_tpu.runtime.checkpoint import StateCheckpointer

    error, geometry = _feed_geometry(cfg, base, "train")
    if error is not None:
        return error
    local_rows, shard_offset, n_proc = geometry
    # The model derives from the mesh exactly like the probe's (seq axis
    # -> sequence-parallel attention, expert -> MoE, stage -> pipelined
    # layers): every mesh family the probe exercises, training trains.
    try:
        tcfg, mesh = train_model_config(cfg)
    except MeshConfigError as e:
        return dataclasses.replace(base, ok=False, error=str(e))
    feeder = None
    try:
        # Peek the resume point first: the feeder must start at the
        # batch the restored step would consume next.
        with StateCheckpointer(
            cfg.state_dir, checkpoint_dir=cfg.checkpoint_dir
        ) as ckpt:
            resume_step = ckpt.latest_step() or 0
        feeder = open_feeder(
            cfg.train_corpus, batch=local_rows, seq=cfg.train_seq,
            start_batch=resume_step, global_batch=cfg.train_batch,
            shard_offset=shard_offset,
        )
        batches = _global_batches(cfg, tcfg, mesh, feeder, n_proc)

        last_write = 0.0
        # This pod generation's most recent per-step losses: the curve
        # an operator (and chip_smoke.py) reads from /status to tell
        # "trains" from "runs".
        recent = collections.deque(maxlen=64)

        def on_step(step: int, loss: float) -> None:
            # Live progress into /status (and the PVC, so the last known
            # step/loss survives a crash). Best-effort telemetry:
            # throttled off the hot loop (always written on the final
            # step), non-finite losses recorded as null (bare NaN in the
            # persisted JSON would corrupt every later /status body),
            # and a failed write must never abort healthy training.
            nonlocal last_write
            recent.append(round(loss, 6) if math.isfinite(loss) else None)
            now = time.time()
            if step < cfg.train_steps and now - last_write < 1.0:
                return
            last_write = now
            try:
                heartbeat.write_train_progress(cfg.state_dir, {
                    "step": step,
                    "target_steps": cfg.train_steps,
                    "loss": recent[-1],
                    "losses": list(recent),
                    "ts": now,
                })
            except OSError:
                pass

        start = time.perf_counter()
        result = run_training(
            tcfg, cfg.state_dir, num_steps=cfg.train_steps,
            batches=batches, checkpoint_every=cfg.train_checkpoint_every,
            prepare=functools.partial(shard_tree, mesh),
            on_step=on_step, checkpoint_dir=cfg.checkpoint_dir,
            mesh=mesh if tcfg.needs_mesh else None,
        )
        elapsed_ms = (time.perf_counter() - start) * 1000.0
    except Exception as e:
        return dataclasses.replace(
            base, ok=False, error=f"train payload failed: {e!r}",
        )
    finally:
        if feeder is not None:
            feeder.close()
    final_loss = result.losses[-1] if result.losses else float("nan")
    if result.losses and not math.isfinite(final_loss):
        return dataclasses.replace(
            base, ok=False,
            error=f"training diverged: loss {final_loss}",
        )
    return dataclasses.replace(
        base, probe_ms=elapsed_ms,
        probe_checksum=final_loss if result.losses else 0.0,
    )


def train_model_config(cfg: RuntimeConfig):
    """The train payload's model, derived from the runtime config.

    One definition shared by ``train`` and ``serve`` (via
    :func:`derive_model_config`) so the serving payload restores exactly
    the architecture training checkpointed — a drift here would surface
    as an orbax tree-structure mismatch.
    """
    return derive_model_config(cfg, seq=cfg.train_seq)


def _feed_geometry(cfg: RuntimeConfig, base: DeviceCheckResult, kind: str):
    """Shared prechecks + per-host feed geometry for corpus payloads.

    Returns ``(error_result | None, (local_rows, shard_offset, n_proc))``.
    One definition for ``train`` and ``eval`` so the two can never
    disagree on batch/mesh divisibility rules or multi-host requirements
    — a clear message at /status beats an opaque sharding traceback.
    """
    import dataclasses

    import jax

    axis_sizes = dict(zip(base.mesh_axes, base.mesh_shape))
    data_size = axis_sizes.get("data", 1)
    if cfg.train_batch % max(1, data_size):
        return dataclasses.replace(
            base, ok=False,
            error=(
                f"[payload] batch = {cfg.train_batch} must divide by the "
                f"mesh's data axis size ({data_size}) — it is the global "
                "batch, sharded across data-parallel devices"
            ),
        ), None
    n_proc = jax.process_count()
    if n_proc > 1:
        if not cfg.checkpoint_dir:
            return dataclasses.replace(
                base, ok=False,
                error=(
                    f"multi-host {kind} needs [runtime] checkpoint_dir "
                    "on shared storage (a shared-filesystem mount or "
                    "gs://bucket/prefix): per-host PVCs cannot hold a "
                    "slice-wide checkpoint (README 'Multi-host')"
                ),
            ), None
        if cfg.train_batch % n_proc:
            return dataclasses.replace(
                base, ok=False,
                error=(
                    f"[payload] batch = {cfg.train_batch} must divide by "
                    f"the process count ({n_proc}) for per-host feeding"
                ),
            ), None
    local_rows = cfg.train_batch // n_proc
    return None, (local_rows, jax.process_index() * local_rows, n_proc)


def _global_batches(cfg: RuntimeConfig, tcfg, mesh, feeder, n_proc: int):
    """Iterator of sharded global [B, T+1] batches from a (possibly
    host-sharded) feeder. Token ids fold into the payload vocab (% V):
    deterministic, so resume stays exact. Single definition for ``train``
    and ``eval`` — how batches are assembled is part of the resume
    contract and must not fork."""
    import jax

    from kvedge_tpu.parallel import shard_batch

    if n_proc > 1:
        import numpy as np
        from jax.sharding import NamedSharding

        from kvedge_tpu.parallel.sharding import batch_spec

        sharding = NamedSharding(mesh, batch_spec(mesh))
        global_shape = (cfg.train_batch, cfg.train_seq + 1)
        for batch in feeder:
            yield jax.make_array_from_process_local_data(
                sharding, np.asarray(batch) % tcfg.vocab, global_shape
            )
    else:
        for batch in feeder:
            yield shard_batch(mesh, batch % tcfg.vocab)


def _restore_latest_params(cfg: RuntimeConfig, tcfg, mesh=None):
    """(step | None, params) from the latest checkpoint, or the fresh
    deterministic init when the volume has none.

    Shared by ``eval`` and ``serve`` (which reaches it through
    :func:`_restore_serving_params`): the abstract tree MUST mirror
    models/training.py's ``fresh_state`` exactly (params AND optimizer
    state, seed 0) — that is the structure orbax wrote, and drift
    surfaces only as a tree-structure mismatch at restore time, so there
    is exactly one definition of it outside the trainer.

    With ``mesh``, the restore is placement-aware: orbax restores each
    param straight into its ``NamedSharding`` (the same rules training
    sharded it with), so a tp/ep-sharded checkpoint lands distributed —
    never materialized on one device first. Either way the optimizer
    moments are PLACEHOLDER-skipped, not restored-then-discarded: a
    serve pod sized for params + KV pool must not pay 3x params memory
    for Adam state it will never read.
    """
    import jax
    import orbax.checkpoint as ocp

    from kvedge_tpu.models import init_params, make_train_step
    from kvedge_tpu.parallel import abstract_shard_tree, shard_params
    from kvedge_tpu.runtime.checkpoint import StateCheckpointer

    init_opt, _ = make_train_step(tcfg)

    def fresh_state():
        p = init_params(jax.random.PRNGKey(0), tcfg)
        return {"params": p, "opt_state": init_opt(p)}

    abstract = jax.eval_shape(fresh_state)
    if mesh is not None:
        abstract = abstract_shard_tree(mesh, abstract)
    abstract["opt_state"] = jax.tree_util.tree_map(
        lambda _: ocp.PLACEHOLDER, abstract["opt_state"]
    )
    with StateCheckpointer(
        cfg.state_dir, checkpoint_dir=cfg.checkpoint_dir
    ) as ckpt:
        restored = ckpt.restore_latest(abstract, partial=True)
    if restored is not None:
        step, tree = restored
        return step, tree["params"]
    # fresh_state stays abstract — materializing it would allocate the
    # optimizer moments only to discard them.
    params = init_params(jax.random.PRNGKey(0), tcfg)
    return None, params if mesh is None else shard_params(mesh, params)


def _restore_serving_params(cfg: RuntimeConfig, tcfg, mesh=None):
    """``_restore_latest_params`` for serve: (step | None, the tree the
    serving programs read), cast once here so that no program converts
    the float32 masters again each time it runs
    (``transformer.serving_params``). Every serve consumer sits behind
    this: the single-host server and its recovery re-restore, the
    slice's leader and followers, the contiguous ``generate``.

    Cast leaf by leaf, each master dropped as soon as its copy exists
    (and waited for: a dispatch that ran ahead would hold every copy
    beside every master), so the load never holds two trees.
    """
    import jax

    from kvedge_tpu.models import serving_params

    if tcfg.layer_pattern:
        # Served only, so no trainer ever wrote a checkpoint of it: the
        # tree is drawn, leaf by leaf in the serving dtype (a float32
        # tree of the benchmark's configuration would not fit the chip).
        from kvedge_tpu.models import hybrid

        return None, hybrid.init_params(jax.random.PRNGKey(0), tcfg)
    step, masters = _restore_latest_params(cfg, tcfg, mesh=mesh)
    params = {}
    for name in list(masters):
        params.update(jax.block_until_ready(
            serving_params({name: masters.pop(name)}, tcfg)))
    return step, params


def run_eval_payload(cfg: RuntimeConfig) -> DeviceCheckResult:
    """The ``eval`` payload: held-out loss for the checkpointed model.

    The measurement half of the train/eval/serve loop: restores the
    latest checkpoint exactly like ``serve`` does (same derived model,
    same state tree) and computes the mean next-token cross-entropy over
    ``[payload] steps`` deterministic batches of ``corpus`` — no
    gradients, no optimizer, nothing written. The loss lands in
    ``probe_checksum`` (and therefore /status and the heartbeat), so an
    operator can read a checkpoint's quality from the same surface that
    reports everything else.

    Held-out convention: ``[payload] eval_corpus`` names the held-out
    split (produce one with ``kvedge-tpu corpus --holdout``); when it is
    unset, eval falls back to the TRAINING corpus and warns loudly that
    the number is training loss, not held-out loss. The batch order is
    the feeder's deterministic order from batch 0 either way.
    """
    base = run_device_check(cfg)
    if not base.ok:
        return base

    import dataclasses
    import functools
    import math

    import jax

    from kvedge_tpu.data import open_feeder
    from kvedge_tpu.models import loss_fn

    error, geometry = _feed_geometry(cfg, base, "eval")
    if error is not None:
        return error
    local_rows, shard_offset, n_proc = geometry

    feeder = None
    try:
        tcfg, mesh = train_model_config(cfg)
        step, params = _restore_latest_params(cfg, tcfg, mesh=mesh)

        # Pure next-token cross-entropy: zeroing the aux weight drops the
        # MoE router's load-balancing term from the reported number —
        # eval measures model quality, not the training regularizer.
        eval_tcfg = dataclasses.replace(tcfg, moe_aux_weight=0.0)
        eval_loss = jax.jit(functools.partial(
            loss_fn, cfg=eval_tcfg,
            mesh=mesh if tcfg.needs_mesh else None,
        ))
        corpus = cfg.eval_corpus or cfg.train_corpus
        held_out = bool(cfg.eval_corpus)
        if not held_out:
            print(
                "[kvedge-eval] WARNING: no [payload] eval_corpus set — "
                "evaluating on the TRAINING corpus; this number is "
                "training loss, NOT held-out loss (split one with "
                "`kvedge-tpu corpus --holdout`)",
                flush=True,
            )
        feeder = open_feeder(
            corpus, batch=local_rows, seq=cfg.train_seq,
            global_batch=cfg.train_batch, shard_offset=shard_offset,
        )
        batches = _global_batches(cfg, tcfg, mesh, feeder, n_proc)
        start = time.perf_counter()
        total = 0.0
        for _ in range(cfg.train_steps):
            total += float(eval_loss(params, next(batches)))
        mean_loss = total / cfg.train_steps
        elapsed_ms = (time.perf_counter() - start) * 1000.0
        print(
            f"[kvedge-eval] checkpoint_step={step} batches="
            f"{cfg.train_steps} held_out={held_out} loss={mean_loss:.4f} "
            f"ppl={math.exp(min(mean_loss, 30.0)):.2f}",
            flush=True,
        )
    except MeshConfigError as e:
        return dataclasses.replace(base, ok=False, error=str(e))
    except Exception as e:
        return dataclasses.replace(
            base, ok=False, error=f"eval payload failed: {e!r}",
        )
    finally:
        if feeder is not None:
            feeder.close()
    if not math.isfinite(mean_loss):
        return dataclasses.replace(
            base, ok=False, error=f"eval loss is {mean_loss}",
        )
    return dataclasses.replace(
        base, probe_ms=elapsed_ms, probe_checksum=mean_loss,
    )


class _ServeCounters:
    """Request accounting shared by the single-host serve path and the
    multi-host leader — ONE definition of the ``kvedge_serve_*`` counter
    vocabulary and of the exception -> outcome-bucket mapping
    (ValueError -> rejected/400, GenerateUnavailable and retryable
    ServingFailures -> unavailable/503, anything else — including
    terminal ServingFailures like SliceFollowerLost -> errors/500), so
    the two paths can never drift on the /metrics contract."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self.data = {
            "requests_total": 0,
            "completed_total": 0,
            "rejected_total": 0,
            "unavailable_total": 0,
            "errors_total": 0,
            "tokens_generated_total": 0,
            "last_latency_ms": 0.0,
            "latency_ms_sum": 0.0,
        }

    def count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.data[key] += n

    def count_outcome(self, exc: Exception) -> None:
        from kvedge_tpu.runtime.failures import ServingFailure
        from kvedge_tpu.runtime.status import GenerateUnavailable

        if isinstance(exc, GenerateUnavailable):
            self.count("unavailable_total")
        elif isinstance(exc, ServingFailure) and exc.retryable:
            # e.g. PoolPoisoned reaching a streamed request mid-flight
            # (the non-streamed path maps it to GenerateUnavailable
            # before it gets here): the client may retry after the
            # reschedule, so it is unavailability, not a server error.
            self.count("unavailable_total")
        elif isinstance(exc, ValueError):
            self.count("rejected_total")
        else:
            self.count("errors_total")

    def finish(self, start: float) -> None:
        import time

        ms = (time.perf_counter() - start) * 1000.0
        with self._lock:
            self.data["completed_total"] += 1
            self.data["last_latency_ms"] = ms
            self.data["latency_ms_sum"] += ms

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.data)


def _run_multihost_serve(cfg: RuntimeConfig, base, tcfg, mesh):
    """Multi-host ``serve``: leader-serves over the whole slice.

    VERDICT r3 #7. The round-3 refusal existed because N processes would
    each restore and answer /generate independently — N divergent
    replicas behind one Service. The leader-serves architecture fixes
    the coordination problem instead of routing around it:

    * every process restores the checkpoint into the GLOBAL mesh's
      placements (shared ``checkpoint_dir``, orbax reads each process's
      shards — exactly like multi-host train/eval);
    * process 0 (the leader) owns the HTTP endpoint. Followers park in
      a follow loop on ``multihost_utils.broadcast_one_to_all``;
    * per request, the leader broadcasts a fixed-shape header (request
      geometry + sampling controls), then the token rows, and ALL
      processes execute the same jitted ``generate`` on global arrays —
      XLA's collectives span the slice exactly as in training;
    * shutdown broadcasts a stop header; followers exit their loop.

    Requests serialize on the leader (one broadcast conversation at a
    time), which also guarantees every process issues collectives in
    the same order — the multi-controller contract. The K8s Service
    already routes to the leader: the chart's multi-host StatefulSet
    fronts ordinal 0 (the same pod that owns ``jax.distributed``'s
    coordinator), so "HTTP hits process 0" is the deployment's natural
    shape, not an extra router.

    Paged backend: the continuous-batching scheduler stays leader-only
    host state; its DEVICE calls broadcast to the slice via
    ``SlicePagedKVCache`` (runtime/sliceserve.py) — see
    :func:`_run_multihost_paged_serve`.
    """
    import dataclasses
    import threading
    import time as time_mod

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kvedge_tpu.models import generate
    from kvedge_tpu.runtime.status import GenerateUnavailable

    if not cfg.checkpoint_dir:
        raise MeshConfigError(
            "multi-host serve needs [runtime] checkpoint_dir on shared "
            "storage: every process restores the same checkpoint "
            "(README 'Multi-host')"
        )
    restored_step, params = _restore_serving_params(cfg, tcfg, mesh=mesh)
    if cfg.payload_serving == "paged":
        return _run_multihost_paged_serve(
            cfg, base, tcfg, mesh, restored_step, params
        )
    leader = jax.process_index() == 0
    replicated = NamedSharding(mesh, P())
    max_rows = _serve_max_rows(cfg, tcfg)

    def bcast(tree):
        return multihost_utils.broadcast_one_to_all(tree)

    # Header layout (fixed shapes — broadcast requires every process to
    # present identical structures): ints = [op, rows, prompt_len,
    # n_new, sampled, seed], floats = [temperature, top_p]. op 0 = stop.
    def zero_header():
        return (np.zeros(6, np.int64), np.zeros(2, np.float32))

    # One jitted replicator (not per-request — jit caches on function
    # identity): reshard any output so every process can read the full
    # array from its own shards.
    _replicate = jax.jit(lambda x: x, out_shardings=replicated)

    def run_request(ints, floats, tokens_np):
        """Executed by EVERY process with identical inputs — the caller
        must pass the BROADCAST-RETURNED values (broadcast canonicalizes
        dtypes, e.g. int64 -> int32 under default x64-disabled jax; a
        leader computing from its pre-broadcast locals could sample with
        a different seed than the followers)."""
        rows, n_new = int(ints[1]), int(ints[3])
        sampled = bool(ints[4])
        prompt = jax.make_array_from_process_local_data(
            replicated, tokens_np
        )
        sampling = None
        if sampled:
            base_key = jax.random.PRNGKey(int(ints[5]))
            seed_keys = jax.vmap(
                lambda i: jax.random.fold_in(base_key, i)
            )(jnp.arange(rows))
            sampling = (seed_keys, jnp.float32(float(floats[0])),
                        jnp.float32(float(floats[1])))
        out = generate(params, prompt, tcfg, n_new=n_new,
                       sampling=sampling, sampled=sampled)
        return np.asarray(_replicate(out).addressable_data(0))

    if not leader:
        def follow():
            try:
                while True:
                    ints, floats = bcast(zero_header())
                    if int(ints[0]) == 0:
                        return
                    rows, plen = int(ints[1]), int(ints[2])
                    tokens_np = bcast(np.zeros((rows, plen), np.int32))
                    run_request(ints, floats, tokens_np)
            except Exception as e:  # pragma: no cover - slice-fatal
                # Same contract as the paged follower: die loudly so
                # the StatefulSet restarts the slice instead of leaving
                # a healthy-looking pod the leader can never reach.
                print(f"[kvedge-serve] follower loop died: {e!r}",
                      flush=True)
                import os as os_mod

                os_mod._exit(13)

        thread = threading.Thread(target=follow,
                                  name="kvedge-serve-follow", daemon=True)
        thread.start()

        # This pod's own /generate answers 503 pointing at the leader;
        # its real job is the follow loop above. join() lets callers
        # (tests, an orderly pod shutdown) wait for the leader's stop
        # broadcast before exiting — killing the process mid-collective
        # would wedge the slice.
        def follower_fn(doc: dict) -> dict:
            raise GenerateUnavailable(
                f"this pod is follower process {jax.process_index()}; "
                "generation is served by the leader (process 0 — the "
                "Service routes to ordinal 0)"
            )

        follower_fn.stats = lambda: {
            "backend": "multihost-follower",
            "processes": jax.process_count(),
        }
        follower_fn.close = lambda drain=False: None
        follower_fn.join = thread.join
        return dataclasses.replace(
            base, probe_ms=0.0, probe_checksum=0.0,
        ), follower_fn

    lock = threading.Lock()
    stopped = False

    def _serve(doc: dict) -> dict:
        tokens, n_new, temperature, top_p, seed, stream, spec, _, _ = (
            _parse_generate_request(doc, tcfg, max_rows=max_rows,
                                    paged=False)
        )
        if spec:
            raise ValueError(
                "'speculative' is not supported on a multi-host serve "
                "deployment (single-host contiguous only)"
            )
        if not -2 ** 31 <= seed < 2 ** 31:
            # The broadcast canonicalizes the header to int32 (default
            # x64-disabled jax); refuse rather than silently truncate.
            raise ValueError("'seed' must fit in int32")
        arr = np.asarray(tokens, np.int32) % tcfg.vocab
        sampled = temperature > 0.0
        with lock:
            if stopped:
                raise GenerateUnavailable("server is shut down")
            ints = np.array(
                [1, arr.shape[0], arr.shape[1], n_new,
                 1 if sampled else 0, seed], np.int64,
            )
            floats = np.array([temperature, top_p], np.float32)
            # The leader consumes the broadcast RESULTS, exactly like the
            # followers — see run_request's dtype-canonicalization note.
            ints, floats = bcast((ints, floats))
            arr = bcast(arr)
            out = run_request(ints, floats, arr)
        return {
            "tokens": [[int(t) for t in row] for row in out.tolist()],
            "n_new": n_new,
            "restored_step": restored_step,
        }

    counters = _ServeCounters()

    def serve_fn(doc: dict) -> dict:
        counters.count("requests_total")
        start = time_mod.perf_counter()
        try:
            result = _serve(doc)
        except Exception as e:
            counters.count_outcome(e)
            raise
        counters.count("tokens_generated_total",
                       result["n_new"] * len(result["tokens"]))
        counters.finish(start)
        return result

    def serve_stats() -> dict:
        out = counters.snapshot()
        out["backend"] = "multihost-contiguous"
        out["processes"] = jax.process_count()
        return out

    serve_fn.stats = serve_stats

    def close(drain: bool = False) -> None:
        nonlocal stopped
        with lock:
            if stopped:
                return
            stopped = True
            bcast(zero_header())  # op 0: followers exit their loop

    serve_fn.close = close

    # Boot self-check through the REAL broadcast path: proves the whole
    # slice answers before the endpoint goes live (followers are already
    # in their loop — the first collective is the sync point).
    probe_prompt = list(range(1, min(4, tcfg.max_seq - 1) + 1))
    probe_new = min(2, tcfg.max_seq - len(probe_prompt))
    start = time_mod.perf_counter()
    probe = _serve({"tokens": [probe_prompt], "n_new": probe_new})
    elapsed_ms = (time_mod.perf_counter() - start) * 1000.0
    return dataclasses.replace(
        base, probe_ms=elapsed_ms,
        probe_checksum=float(sum(probe["tokens"][0])),
    ), serve_fn


def _serving_page_bytes(cfg, tcfg) -> int:
    """HBM bytes ONE pool page costs: K and V slabs across every layer
    (``[n_layers, page_size, kv_heads * d_head]`` each), plus the two
    fp32 scale slabs an int8 pool carries alongside (kvcache.PagedState
    docstring). This mirrors ``PagedKVCache.__init__``'s allocation
    exactly — the budget arithmetic and the arrays it pays for must
    never drift apart."""
    import jax.numpy as jnp

    page_size = cfg.serving_page_size
    itemsize = (1 if cfg.serving_kv_dtype == "int8"
                else jnp.dtype(tcfg.dtype).itemsize)
    row = tcfg.kv_layers * page_size * tcfg.kv_heads
    per_page = row * tcfg.d_head * itemsize * 2  # K + V
    if cfg.serving_kv_dtype == "int8":
        per_page += row * 4 * 2  # fp32 scale_k + scale_v
    return per_page


def _serving_pool_dims(cfg, tcfg) -> tuple[int, int, int, int]:
    """``(slots, pages, page_size, max_pages_per_seq)`` of the paged
    pool — ONE derivation for the single-host server and the slice
    cache (the two must never size differently). ``serving_pages = 0``
    auto-sizes so every slot can hold a worst-case request — admission
    then only ever waits on slots, never on pages.

    ``serving_hbm_budget_mb`` sizes the pool from a BYTE budget instead
    (mutually exclusive with ``serving_pages`` — config validation
    enforces it): pages = budget // page_bytes, floored. Admission then
    gates on pages, not slots (SERVING.md rung 21), so a budget smaller
    than ``slots`` worst-case requests is a deliberate oversubscription,
    not an error — but a budget too small for even ONE worst-case
    request can never admit anything and fails loudly here."""
    slots, page_size = cfg.serving_slots, cfg.serving_page_size
    mpps = -(-tcfg.max_seq // page_size)
    if cfg.serving_hbm_budget_mb:
        pages = (cfg.serving_hbm_budget_mb * 2**20
                 ) // _serving_page_bytes(cfg, tcfg)
        if pages < mpps:
            raise MeshConfigError(
                f"serving_hbm_budget_mb = {cfg.serving_hbm_budget_mb} "
                f"buys {pages} pages, but one worst-case request needs "
                f"{mpps} (max_seq {tcfg.max_seq} at page size "
                f"{page_size}); raise the budget or shrink max_seq"
            )
    else:
        pages = cfg.serving_pages or slots * mpps
    return slots, pages, page_size, mpps


def _serve_max_rows(cfg, tcfg) -> int:
    """Ingress row ceiling for one ``/generate`` request: 4 waves of
    the pool's WORST-CASE concurrency — the number of full-length
    requests the page budget can actually hold at once, capped at the
    slot count. For auto-sized pools ``pages // mpps == slots``, so
    this reproduces the old ``4 * serving_slots`` ceiling exactly; a
    budget-sized pool that holds fewer worst-case residents than slots
    lowers the ceiling to match what admission can really run."""
    slots, pages, _, mpps = _serving_pool_dims(cfg, tcfg)
    return 4 * max(1, min(slots, pages // mpps))


def _run_multihost_paged_serve(cfg, base, tcfg, mesh, restored_step,
                               params):
    """Cross-host continuous batching: the paged scheduler on a slice.

    The leader runs the UNMODIFIED single-host serving stack —
    ``PagedGenerationServer`` with all its admission, chunked prefill,
    prefix sharing, cancellation, and windowing — over a
    ``SlicePagedKVCache`` whose device seams broadcast each op so every
    process executes the same jitted kernel on global arrays
    (runtime/sliceserve.py has the protocol and its soundness
    argument). Followers replay the op stream; their own /generate
    answers 503 pointing at the leader, exactly like the contiguous
    leader-serves path. Sampling stays leader-local (only the CHOSEN
    tokens enter the op stream), so the cross-backend key schedule
    holds without broadcasting seeds.
    """
    import dataclasses
    import threading

    import jax

    from kvedge_tpu.models.serving import weights_summary
    from kvedge_tpu.runtime.sliceserve import (
        SlicePagedKVCache,
        follow_paged,
    )
    from kvedge_tpu.runtime.status import GenerateUnavailable

    # Constructed identically on EVERY process, at the same point in
    # the collective order (the zeroed global pool is a collective jit
    # execution).
    slots, pages, page_size, mpps = _serving_pool_dims(cfg, tcfg)
    cache = SlicePagedKVCache(
        tcfg, slots=slots, pages=pages, page_size=page_size, mesh=mesh,
        max_pages_per_seq=mpps, kv_dtype=cfg.serving_kv_dtype,
    )

    if jax.process_index() != 0:
        def follow():
            # Bounded rejoin (SERVING.md rung 15): a replay failure no
            # longer kills the pod on the first strike. The follower
            # re-enters follow_paged — its first received op is the
            # leader's reformation barrier SYNC, which restores
            # tables/lengths and puts it back in lockstep. The budget
            # mirrors the leader supervisor's attempt budget; when it
            # is exhausted (or recovery is disabled) the old contract
            # holds: exit non-zero so the StatefulSet restarts the
            # slice — a swallowed replay failure would leave this pod
            # answering /healthz while the leader wedges forever.
            rejoins = max(0, int(cfg.serving_recovery_attempts))
            tries = 0
            while True:
                try:
                    follow_paged(cache, params)
                    return  # leader broadcast STOP: clean end of serve
                except Exception as e:
                    tries += 1
                    if tries > rejoins:  # pragma: no cover - slice-fatal
                        print(f"[kvedge-serve] paged follower died "
                              f"({tries - 1} rejoin(s) spent): {e!r}",
                              flush=True)
                        import os as os_mod

                        os_mod._exit(13)
                    print(f"[kvedge-serve] paged follower dropped from "
                          f"the op stream ({e!r}); rejoining "
                          f"({tries}/{rejoins})", flush=True)

        thread = threading.Thread(
            target=follow, name="kvedge-serve-follow", daemon=True
        )
        thread.start()

        def follower_fn(doc: dict) -> dict:
            raise GenerateUnavailable(
                f"this pod is follower process {jax.process_index()}; "
                "generation is served by the leader (process 0 — the "
                "Service routes to ordinal 0)"
            )

        weights_gb, weights_dtype = weights_summary(params)
        follower_fn.stats = lambda: {
            "backend": "multihost-paged-follower",
            "processes": jax.process_count(),
            # The tree this pod replays the leader's programs over.
            "weights_gb": weights_gb,
            "weights_dtype": weights_dtype,
        }
        follower_fn.close = lambda drain=False: None
        follower_fn.join = thread.join
        return dataclasses.replace(
            base, probe_ms=0.0, probe_checksum=0.0,
        ), follower_fn

    # Follower release rides the server's own close: PagedGenerationServer
    # calls cache.stop() under its lock after the decode loop exits —
    # serialized after every in-flight cache call, and idempotent.
    return _build_serve(
        cfg, base, tcfg, params, restored_step, cache=cache,
        backend="multihost-paged",
    )


def _parse_generate_request(doc: dict, tcfg, *, max_rows: int,
                            paged: bool):
    """Validate a ``POST /generate`` body. ONE definition shared by the
    single-host serve path and the multi-host leader (the two must never
    drift on what a well-formed request is). Returns
    ``(tokens, n_new, temperature, top_p, seed, stream, spec, priority,
    deadline_ms)``; raises ``ValueError`` (the HTTP layer's 400) for
    anything malformed.
    """
    tokens = doc.get("tokens")
    if (not isinstance(tokens, list) or not tokens
            or not all(isinstance(r, list) and r for r in tokens)):
        raise ValueError(
            "body must carry 'tokens': a non-empty list of "
            "non-empty token-id rows"
        )
    if len({len(r) for r in tokens}) != 1:
        raise ValueError("all token rows must have equal length")
    if len(tokens) > max_rows:
        # Both backends need a ceiling: the paged path fans rows out to
        # the bounded worker pool (a burst of thousands of rows would
        # queue, not thread-storm, but the client deserves a clear
        # refusal over an hour-long queue), and the contiguous path
        # jit-compiles one program per batch size (an unbounded compile
        # surface).
        raise ValueError(
            f"request carries {len(tokens)} token rows > the "
            f"runtime's ceiling of {max_rows} (4 x the page pool's "
            "worst-case request capacity); split the request"
        )
    try:
        n_new = int(doc.get("n_new", 16))
    except (TypeError, ValueError):
        raise ValueError("'n_new' must be an integer") from None
    if not 1 <= n_new <= tcfg.max_seq:
        raise ValueError(
            f"'n_new' must be in [1, {tcfg.max_seq}]"
        )
    if len(tokens[0]) + n_new > tcfg.max_seq:
        raise ValueError(
            f"prompt ({len(tokens[0])}) + n_new ({n_new}) exceeds "
            f"the model's max_seq ({tcfg.max_seq})"
        )
    if not all(
        isinstance(t, int) and not isinstance(t, bool)
        for row in tokens for t in row
    ):
        # Explicit check: jnp.asarray would silently TRUNCATE floats
        # (1.9 -> 1) and decode a different prompt than the client sent.
        raise ValueError("token rows must contain integers")
    # Sampling controls: temperature 0 (default) = greedy; > 0 samples
    # through the shared nucleus filter with the deterministic per-row
    # key schedule (seed, row, token) — identical across backends.
    raw_t = doc.get("temperature", 0.0)
    raw_p = doc.get("top_p", 1.0)
    raw_seed = doc.get("seed", 0)
    # Strict types, same discipline as the token check above: bool is an
    # int subclass (true would silently become 1.0 and switch the client
    # to sampling), and a float seed would silently truncate to a seed
    # the client did not send.
    if (not isinstance(raw_t, (int, float))
            or isinstance(raw_t, bool)
            or not isinstance(raw_p, (int, float))
            or isinstance(raw_p, bool)
            or not isinstance(raw_seed, int)
            or isinstance(raw_seed, bool)):
        raise ValueError(
            "'temperature'/'top_p' must be numbers and 'seed' "
            "an integer"
        )
    temperature, top_p, seed = float(raw_t), float(raw_p), raw_seed
    stream = doc.get("stream", False)
    if not isinstance(stream, bool):
        raise ValueError("'stream' must be a boolean")
    if stream and not paged:
        raise ValueError(
            "'stream' requires [payload] serving = \"paged\" — "
            "the contiguous backend decodes the whole request as "
            "one compiled program, so there is nothing to stream"
        )
    if temperature < 0.0:
        raise ValueError("'temperature' must be >= 0")
    if not 0.0 < top_p <= 1.0:
        raise ValueError("'top_p' must be in (0, 1]")
    # Speculative decoding ('speculative': K = draft length): greedy,
    # single-row, contiguous-backend — a latency lever, token-for-token
    # identical to plain greedy decode (models/speculative.py).
    spec = doc.get("speculative", 0)
    if (not isinstance(spec, int) or isinstance(spec, bool)
            or not 0 <= spec <= 16):
        raise ValueError(
            "'speculative' must be an integer draft length in "
            "[0, 16] (0 = off)"
        )
    if spec:
        # Stream check FIRST: on a paged runtime (the only place
        # 'stream' is legal) the composition error is the clearer
        # message; after the paged check it would be unreachable.
        if stream:
            raise ValueError(
                "'speculative' does not compose with 'stream'"
            )
        if paged:
            raise ValueError(
                "per-request 'speculative' runs on the contiguous "
                "backend; the paged backend does not speculate"
            )
        if len(tokens) != 1:
            raise ValueError(
                "'speculative' supports exactly one token row"
            )
        if temperature > 0.0:
            raise ValueError(
                "'speculative' is greedy-only (temperature 0): "
                "drafts verify against the argmax"
            )
    # SLO fields (SERVING.md rung 17): 'priority' names the admission
    # class, 'deadline_ms' bounds how long the request may queue. The
    # paged server validates the class name against its configured set
    # (an unknown class is this same 400 path); the contiguous backend
    # has no admission queue, so the fields are refused there rather
    # than silently ignored.
    priority = doc.get("priority", "interactive")
    if not isinstance(priority, str) or not priority:
        raise ValueError(
            "'priority' must be a non-empty class name "
            "(e.g. 'interactive' or 'batch')"
        )
    deadline_ms = doc.get("deadline_ms")
    if deadline_ms is not None and (
            not isinstance(deadline_ms, int)
            or isinstance(deadline_ms, bool) or deadline_ms < 1):
        raise ValueError("'deadline_ms' must be a positive integer")
    if not paged and ("priority" in doc or deadline_ms is not None):
        raise ValueError(
            "'priority'/'deadline_ms' require [payload] serving = "
            "\"paged\" — the contiguous backend runs one request at a "
            "time with no admission queue to schedule"
        )
    return (tokens, n_new, temperature, top_p, seed, stream, spec,
            priority, deadline_ms)


class _ResumeLog:
    """Bounded per-request delivery log backing client reconnects.

    The durability rung (SERVING.md rung 22) keeps a poisoned pool's
    in-flight requests alive server-side; this is the CLIENT half: the
    serve path records every generated token it hands (or buffers for)
    a request's consumer, keyed by request id, so a client that lost
    its connection can reconnect with its ``X-Request-Id`` and an
    ``emitted_offset`` and receive exactly the tokens it has not seen
    — no duplicates, no gaps — whether the request is still decoding,
    parked in the server's journal across a recovery, or finished.

    Bounded to the ``max_entries`` most recently opened requests; an
    evicted id simply cannot be resumed (the reconnect gets the same
    400 an unknown id gets). Pump threads write and reconnect handlers
    read under one condition variable; records are plain dicts mutated
    only while holding it.
    """

    def __init__(self, max_entries: int = 64):
        self.cond = threading.Condition()
        self.max_entries = int(max_entries)
        self._entries: collections.OrderedDict = collections.OrderedDict()

    def open(self, rid: str, n_rows: int, n_new: int) -> dict:
        """Register ``rid`` (replacing any previous use of the id)."""
        with self.cond:
            rec = {"rows": [[] for _ in range(n_rows)],
                   "live": n_rows, "n_new": n_new,
                   "done": False, "error": None}
            self._entries.pop(rid, None)
            self._entries[rid] = rec
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
            return rec

    def get(self, rid: str) -> dict | None:
        with self.cond:
            return self._entries.get(rid)

    def append(self, rid: str, row: int, token: int) -> None:
        with self.cond:
            rec = self._entries.get(rid)
            if rec is not None:
                rec["rows"][row].append(token)
                self.cond.notify_all()

    def row_done(self, rid: str) -> None:
        """One row finished; the record is done when all rows are."""
        with self.cond:
            rec = self._entries.get(rid)
            if rec is not None:
                rec["live"] -= 1
                if rec["live"] <= 0:
                    rec["done"] = True
                self.cond.notify_all()

    def finish(self, rid: str, error: Exception | None = None) -> None:
        """Mark ``rid`` complete (the first error recorded wins)."""
        with self.cond:
            rec = self._entries.get(rid)
            if rec is not None:
                if error is not None and rec["error"] is None:
                    rec["error"] = error
                rec["done"] = True
                self.cond.notify_all()


def run_serve_payload(cfg: RuntimeConfig):
    """The ``serve`` payload: greedy decode behind ``POST /generate``.

    Closes the loop the state volume exists for: the ``train`` payload
    checkpoints through it, and a later ``serve`` pod restores the
    latest checkpoint (params only — optimizer state is training's
    business) and serves generation requests from it. A fresh volume
    serves the same deterministic init training would start from, so the
    endpoint works before any training has happened.

    Mesh-aware: params restore straight into the configured mesh's
    placements (the same partition rules training used), and decode runs
    under jit with those shardings driving XLA's SPMD partitioner — a
    checkpoint that needed the ``model``/``expert`` axes to train serves
    over them too. On a multi-host slice the payload switches to
    leader-serves (:func:`_run_multihost_serve`): process 0 owns HTTP
    and every decode is an SPMD computation the whole slice joins.

    Returns ``(DeviceCheckResult, serve_fn | None)``; ``serve_fn(doc)``
    implements the request contract::

        {"tokens": [[int, ...], ...], "n_new": int}   ->
        {"tokens": [[prompt + generated], ...], "n_new": N,
         "restored_step": int | null}

    The whole decode loop is one jitted program per (batch, prompt_len,
    n_new) shape (models/decode.py); a lock serializes requests — this
    is the reference-scale single-runtime story, not a batching server.
    """
    base = run_device_check(cfg)
    if not base.ok:
        return base, None

    import dataclasses

    import jax

    try:
        tcfg, mesh = train_model_config(cfg)
        if jax.process_count() > 1:
            # Leader-serves: process 0 owns HTTP; every decode is an
            # SPMD computation the whole slice joins (see
            # _run_multihost_serve). Followers return serve_fn=None —
            # their /generate answers 503 pointing at the leader.
            return _run_multihost_serve(cfg, base, tcfg, mesh)
        # Placement-aware restore: params land sharded over THIS mesh
        # (model/expert/stage axes), so a checkpoint whose model needed
        # tensor parallelism to fit serves over the same axes — decode
        # runs under jit with the input shardings driving XLA's SPMD
        # partitioner, exactly like the train step.
        restored_step, params = _restore_serving_params(cfg, tcfg, mesh=mesh)
        # The recovery supervisor's warm restart re-reads the latest
        # checkpoint (single-host only: a slice restore is a collective
        # the supervisor's thread must not run alone).
        return _build_serve(
            cfg, base, tcfg, params, restored_step,
            restore_params=lambda: _restore_serving_params(
                cfg, tcfg, mesh=mesh
            )[1],
        )
    except MeshConfigError as e:
        # Raised before any server/device state exists: surface the
        # operator-facing config message, not a wrapped traceback.
        return dataclasses.replace(base, ok=False, error=str(e)), None
    except Exception as e:
        return dataclasses.replace(
            base, ok=False, error=f"serve payload failed: {e!r}",
        ), None


def _build_serve(cfg, base, tcfg, params, restored_step, *, cache=None,
                 backend=None, restore_params=None):
    """Build the serve endpoint over restored ``params``.

    The ONE construction of the serving data path, shared by the
    single-host payload (``cache=None`` — it builds its own pool from
    the ``[payload] serving_*`` knobs) and the multi-host paged leader
    (``cache`` = a ``SlicePagedKVCache`` whose device calls span the
    slice; ``backend`` labels the stats). Returns
    ``(DeviceCheckResult, serve_fn)``; on failure, tears down anything
    it created and re-raises for the caller's error mapping.
    """
    import dataclasses
    import threading
    import time as time_mod

    import jax
    import jax.numpy as jnp

    from kvedge_tpu.models import generate
    from kvedge_tpu.runtime.tracing import (
        Tracer, clean_request_id, new_request_id,
    )

    # Row ceiling + worker pool sized from the serving knobs: the
    # serve path must not spawn one thread per row (VERDICT r3 #6 —
    # a burst of wide requests was an unbounded thread surface). The
    # ceiling is page-budget-derived (SERVING.md rung 21), not a bare
    # slot multiple — a budget-sized pool admits what pages allow.
    max_rows = _serve_max_rows(cfg, tcfg)
    # Request-scoped tracing ([payload] serving_trace, SERVING.md rung
    # 18): ONE flight recorder per serving pool, shared by reference
    # with the scheduler, the (slice) cache, the deadline runner and
    # the recovery machinery. None is the off state — every producer
    # guards on it, so off costs one attribute read per seam.
    tracer = Tracer.from_knob(cfg.serving_trace)
    row_pool = None
    paged_server = None
    recovery_sup = None
    resume_log = None
    prefix_path, fp = "", ""
    try:
        if cache is not None or cfg.payload_serving == "paged":
            from kvedge_tpu.models.serving import PagedGenerationServer

            # page_size passed explicitly so the sizing arithmetic and
            # the cache's pages can never drift apart; an injected
            # cache carries its own pool from the SAME derivation.
            slots, pages, page_size, _ = _serving_pool_dims(cfg, tcfg)
            # SLO engine ([payload] serving_slo*, SERVING.md rung 25):
            # objectives travel as one frozen value object; None keeps
            # the engine (and its boundary feed) out of the process.
            slo_objectives = None
            if cfg.serving_slo:
                from kvedge_tpu.runtime.slo import SloObjectives
                slo_objectives = SloObjectives(
                    target=cfg.serving_slo_target,
                    ttft_ms=cfg.serving_slo_ttft_ms,
                    itl_ms=cfg.serving_slo_itl_ms,
                    queue_ms=cfg.serving_slo_queue_ms,
                    fast_window_s=cfg.serving_slo_fast_s,
                    slow_window_s=cfg.serving_slo_slow_s,
                )
            paged_server = PagedGenerationServer(
                params, tcfg, slots=slots, pages=pages,
                page_size=page_size,
                prefill_chunk=cfg.serving_prefill_chunk,
                prefix_cache=cfg.serving_prefix_cache,
                prefix_host_mb=cfg.serving_prefix_host_mb,
                # "auto" hands window choice to the online controller
                # (SERVING.md rung 26) inside the min/max bounds; a
                # static int keeps the operator's cap.
                window=cfg.serving_window,
                window_min=cfg.serving_window_min,
                window_max=cfg.serving_window_max,
                kv_dtype=cfg.serving_kv_dtype,
                cache=cache,
                retry_after_s=cfg.serving_retry_after_s,
                # SLO-aware admission (SERVING.md rung 17): policy +
                # watermarks + host swap budget from the [payload]
                # serving_sched_* knobs; weights pre-parsed so a bad
                # string fails at config validation, not first request.
                sched_policy=cfg.serving_sched_policy,
                sched_weights=cfg.sched_weights_dict(),
                sched_max_queue_depth=cfg.serving_sched_max_queue_depth,
                sched_max_queue_wait_s=(
                    cfg.serving_sched_max_queue_wait_s),
                sched_swap_budget_mb=cfg.serving_sched_swap_budget_mb,
                # Capacity semantics (SERVING.md rung 21): power-of-two
                # compile buckets over the device batch dim, and
                # free-page watermarks feeding the scheduler's shed and
                # resume decisions. An injected cache (the slice path)
                # governs its own bucket — it pins to slots, and the
                # server follows the cache, so min_bucket only reaches
                # the pool this ctor builds itself.
                min_bucket=cfg.serving_min_bucket,
                page_low_watermark=cfg.serving_page_low_watermark,
                page_high_watermark=cfg.serving_page_high_watermark,
                # Multi-host note: revive() after a recovery restarts
                # _loop — the slice cache's reform() dropped its device
                # carry, so the revived pipeline re-enters cleanly from
                # host tokens on every recovery cycle.
                tracer=tracer,
                # Lock-discipline assertions ([payload]
                # serving_debug_locks, SERVING.md rung 19): runtime
                # twin of tools/locklint.py — *_locked calls assert
                # ownership, Condition ops become thread-accurate.
                debug_locks=cfg.serving_debug_locks,
                # Durability (SERVING.md rung 22): boundary checkpoints
                # of in-flight requests into the host journal, and the
                # page-conservation audit at every quiescent boundary.
                checkpoint_every=cfg.serving_checkpoint_every,
                debug_pages=cfg.serving_debug_pages,
                # Observability plane (SERVING.md rung 25): the SLO
                # engine with its knob-gated burn-rate shed input, and
                # the occupancy timeline ring.
                slo=slo_objectives,
                slo_shed=cfg.serving_slo_shed,
                occupancy_ring=cfg.serving_occupancy_ring,
            )
            # Degraded-mode observability: when the pool poisons
            # (runtime/failures.py), persist a post-mortem failure
            # record on the state volume — it survives the reschedule
            # the degradation asks for, boot.snapshot() surfaces it
            # under "last_failure", and the NEXT pod generation's
            # /status shows why its predecessor died.
            if cfg.state_dir:
                from kvedge_tpu.runtime import heartbeat as hb_mod

                state_dir = cfg.state_dir

                def _record_failure(reason, failure):
                    record = {
                        "payload": "serve",
                        "backend": backend or "paged",
                        "type": type(failure).__name__,
                        "reason": reason,
                        "retryable": bool(getattr(failure, "retryable",
                                                  False)),
                    }
                    if tracer is not None:
                        # Flight-recorder tail: the last N trace events
                        # ship INSIDE the post-mortem, so the next pod
                        # generation's /status shows the timeline that
                        # led to the poison, not just the final error.
                        record["trace"] = tracer.last_events()
                    hb_mod.write_failure_record(state_dir, record)
                    if cfg.serving_bundle:
                        # Full post-mortem bundle (rung 25) next to
                        # the failure record: the machine-complete
                        # document — consistent metrics + SLO/burn +
                        # page books + occupancy tail — a dead
                        # replica explains itself with. Best-effort:
                        # a bundle failure must never mask the
                        # failure record above.
                        try:
                            hb_mod.write_flight_bundle(
                                state_dir,
                                paged_server.flight_bundle(),
                            )
                        except Exception:
                            pass

                paged_server.on_degraded = _record_failure
            # Prefix persistence (single-host only: the slice cache's
            # pool is a global array the leader cannot dump alone):
            # warm prefixes from the previous pod generation re-pin at
            # boot, fingerprint-guarded so K/V from other params are
            # ignored; the dump happens at close, below.
            if (cache is None and cfg.serving_prefix_persist
                    and cfg.serving_prefix_cache and cfg.state_dir):
                import os as os_mod

                prefix_path = os_mod.path.join(
                    cfg.state_dir, "prefix-cache.npz"
                )
                fp = (f"step={restored_step} {tcfg.vocab}v "
                      f"{tcfg.d_model}d {tcfg.n_heads}h "
                      f"{tcfg.kv_heads}kv {tcfg.n_layers}L "
                      f"{tcfg.d_ff}ff {tcfg.max_seq}T {tcfg.dtype}")
                n = paged_server.load_prefix_cache(prefix_path, fp)
                if n:
                    print(f"[kvedge-serve] re-pinned {n} prefix-cache "
                          f"entries from {prefix_path}", flush=True)
                # Periodic dumps (VERDICT r4 #10): a SIGKILL'd pod —
                # the reference's own failure story — keeps its warm
                # prefixes, not just a gracefully drained one. The
                # close-time dump below stays as the freshest copy.
                paged_server.start_prefix_persistence(
                    prefix_path, fp, interval=30.0
                )
            # Self-healing (SERVING.md rung 15): the supervisor chains
            # onto on_degraded AFTER the failure-record observer above
            # (attach() preserves it), so a poisoning failure is first
            # recorded, then healed — slice reformation + warm restart
            # with backoff — and only escalates to the terminal 503 /
            # reschedule path when the attempt budget or the crash-loop
            # breaker says in-process recovery is not working.
            if cfg.serving_recovery_attempts > 0:
                from kvedge_tpu.runtime.recovery import (
                    RecoveryPolicy,
                    RecoverySupervisor,
                )

                recovery_sup = RecoverySupervisor(
                    paged_server,
                    policy=RecoveryPolicy(
                        max_attempts=cfg.serving_recovery_attempts,
                    ),
                    state_dir=cfg.state_dir,
                    prefix_path=prefix_path,
                    prefix_fingerprint=fp,
                    restore_params=(restore_params if cache is None
                                    else None),
                ).attach()
            # One shared pool for row priming AND stream pumping, sized
            # 2x slots (only `slots` rows decode concurrently; one
            # primer + one pump each is the useful parallelism). Excess
            # rows queue here instead of spawning threads; progress is
            # guaranteed because decode never depends on a pool worker
            # (tokens buffer in each request's queue regardless).
            import concurrent.futures

            row_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=2 * slots,
                thread_name_prefix="kvedge-serve-row",
            )
            # Reconnect log (rung 22): only when boundary checkpoints
            # are on — without them a disconnect still cancels rows,
            # so there would be nothing durable to resume against.
            if cfg.serving_checkpoint_every > 0:
                resume_log = _ResumeLog()
        lock = threading.Lock()

        def _resume(doc: dict) -> dict:
            """Reconnect path (SERVING.md rung 22): ``X-Request-Id`` +
            ``emitted_offset`` re-attaches to a previously issued
            request and delivers exactly the generated tokens the
            client has not seen. No new work is submitted — tokens
            come from the delivery log the original request's pumps
            keep feeding while the client is gone (a disconnect
            detaches instead of cancelling when checkpointing is on),
            so the stitched sequence is gap-free and duplicate-free
            even across a poison/revive cycle."""
            rid = clean_request_id(doc.get("_request_id"))
            if not rid:
                raise ValueError(
                    "reconnect needs the original request id "
                    "(X-Request-Id header or '_request_id')"
                )
            rec = resume_log.get(rid)
            if rec is None:
                raise ValueError(
                    f"unknown or expired request id {rid!r}: nothing "
                    "to resume (the delivery log keeps the "
                    f"{resume_log.max_entries} most recent requests)"
                )
            n_rows = len(rec["rows"])
            raw = doc.get("emitted_offset")
            offs = raw if isinstance(raw, list) else [raw] * n_rows
            if (len(offs) != n_rows
                    or not all(isinstance(o, int)
                               and not isinstance(o, bool)
                               and 0 <= o <= rec["n_new"]
                               for o in offs)):
                raise ValueError(
                    "'emitted_offset' must be an integer (or one per "
                    f"row, {n_rows} here) in [0, n_new="
                    f"{rec['n_new']}] — the count of generated "
                    "tokens already received for the row"
                )
            stream = doc.get("stream", False)
            if not isinstance(stream, bool):
                raise ValueError("'stream' must be a boolean")
            if not stream:
                # Buffered reconnect: wait out the original request
                # (its submitter is still parked on the server — across
                # a recovery if need be), then hand back the per-row
                # generated suffixes beyond the client's offsets.
                with resume_log.cond:
                    while not rec["done"]:
                        resume_log.cond.wait()
                    if rec["error"] is not None:
                        raise rec["error"]
                    suffix = [list(row[o:])
                              for row, o in zip(rec["rows"], offs)]
                return {"tokens": suffix, "n_new": rec["n_new"],
                        "restored_step": restored_step,
                        "request_id": rid, "resumed_at": list(offs)}

            def replay():
                # Streamed reconnect: drain the log beyond the offsets,
                # then follow it live until the original request's
                # pumps mark the record done. Tokens are read under the
                # log's condition but yielded outside it (the HTTP
                # write must not hold the log against the pumps).
                cursor = list(offs)
                while True:
                    out = []
                    with resume_log.cond:
                        while True:
                            for i in range(n_rows):
                                row = rec["rows"][i]
                                if cursor[i] < len(row):
                                    out.extend(
                                        (i, t)
                                        for t in row[cursor[i]:]
                                    )
                                    cursor[i] = len(row)
                            if out or rec["done"]:
                                done = rec["done"]
                                err = rec["error"]
                                break
                            resume_log.cond.wait()
                    for i, t in out:
                        yield {"row": i, "token": t}
                    if done:
                        if err is not None:
                            raise err
                        yield {
                            "done": True,
                            "tokens": [list(r[o:]) for r, o
                                       in zip(rec["rows"], offs)],
                            "n_new": rec["n_new"],
                            "restored_step": restored_step,
                            "request_id": rid,
                            "resumed_at": list(offs),
                        }
                        return

            return {"_stream": replay(), "request_id": rid}

        def _serve(doc: dict) -> dict:
            if "emitted_offset" in doc:
                # Reconnect, not a new request: every other body field
                # (tokens, sampling, budgets) is pinned by the original
                # submission and must not be re-parsed here.
                if resume_log is None:
                    raise ValueError(
                        "'emitted_offset' reconnect requires the paged "
                        "backend with [payload] "
                        "serving_checkpoint_every > 0"
                    )
                return _resume(doc)
            (tokens, n_new, temperature, top_p, seed, stream, spec,
             priority, deadline_ms) = (
                _parse_generate_request(
                    doc, tcfg, max_rows=max_rows,
                    paged=paged_server is not None,
                )
            )
            # Request ID, minted at ingress (or a sanitized
            # caller-supplied X-Request-Id, injected by the HTTP layer
            # as doc["_request_id"]): echoed in every response and
            # keying this request's span tree in the flight recorder.
            # Minted HERE — not in status.py — so programmatic callers
            # of serve_fn get the same attribution story as HTTP ones.
            rid = (clean_request_id(doc.get("_request_id"))
                   or new_request_id())
            sampled = temperature > 0.0
            base_key = jax.random.PRNGKey(seed) if sampled else None

            def row_sampling(i: int):
                """Row i's sampling triple — ONE definition of the
                cross-backend key schedule (fold_in(base, row))."""
                if not sampled:
                    return None
                return (jax.random.fold_in(base_key, i),
                        jnp.float32(temperature), jnp.float32(top_p))

            if paged_server is not None:
                # Continuous batching: each row is its own request into
                # the shared page pool, submitted CONCURRENTLY so the
                # rows (and any other HTTP handlers' rows) ride the same
                # batched decode step rather than decoding serially.
                from kvedge_tpu.models.serving import (
                    ServerBusy,
                    ServerClosed,
                )
                from kvedge_tpu.runtime.failures import ServingFailure
                from kvedge_tpu.runtime.status import GenerateUnavailable

                def retriable(e: Exception) -> bool:
                    """Conditions a client should retry — against this
                    pod (busy/draining) or its replacement (poisoned
                    pool): 503, not 500."""
                    return (isinstance(e, (ServerBusy, ServerClosed))
                            or (isinstance(e, ServingFailure)
                                and e.retryable))

                def fan_out_rows(n_rows: int, fn) -> None:
                    """Run ``fn(i)`` per row on the shared bounded pool
                    (rows must submit together to ride the same batched
                    decode step; excess rows queue behind the pool's
                    2 x slots workers), then apply the ONE
                    error-priority policy: real faults — including
                    terminal ServingFailures like SliceFollowerLost —
                    surface first (HTTP 500), retriable conditions
                    become GenerateUnavailable (503, with the failure's
                    retry-after hint when it carries one). Shared by
                    the streamed and non-streamed paths so the two can
                    never map the same server condition to different
                    statuses."""
                    errors: list = [None] * n_rows

                    def guarded(i):
                        try:
                            fn(i)
                        except Exception as e:
                            errors[i] = e

                    futures = [
                        row_pool.submit(guarded, i) for i in range(n_rows)
                    ]
                    for f in futures:
                        f.result()
                    for e in errors:
                        if e is not None and not retriable(e):
                            raise e
                    for e in errors:
                        if e is not None:
                            retry_after = getattr(e, "retry_after_s",
                                                  None)
                            hint = ("" if retry_after is None else
                                    f" (retry after ~{retry_after:g}s)")
                            raise GenerateUnavailable(
                                f"{e}{hint}"
                            ) from e

                if stream:
                    import queue as queue_mod

                    prompts = [[t % tcfg.vocab for t in row]
                               for row in tokens]
                    # Prime EVERY row for its first token HERE, before
                    # the handler commits a 200: admission failures
                    # (ServerBusy) must surface as a clean 503 status,
                    # which is impossible once streaming has started.
                    # (Rows beyond the slot count admit as earlier rows
                    # finish; on a timeout the already-admitted rows are
                    # CANCELLED so the 503 frees their slots and pages
                    # at the next decode boundary instead of decoding
                    # out budgets nobody will read.)
                    sources: list = [None] * len(prompts)
                    firsts: list = [None] * len(prompts)

                    def prime(i):
                        src = paged_server.submit_stream(
                            prompts[i], n_new, sampling=row_sampling(i),
                            priority=priority, deadline_ms=deadline_ms,
                            request_id=rid,
                        )
                        firsts[i] = next(src)
                        sources[i] = src

                    try:
                        fan_out_rows(len(prompts), prime)
                    except Exception:
                        for src in sources:
                            if src is not None:
                                src.cancel()
                        raise

                    # The 200 is committed: register the request for
                    # reconnects BEFORE any token leaves, so a client
                    # that dies on the first frame can still resume.
                    if resume_log is not None:
                        resume_log.open(rid, len(prompts), n_new)

                    _ROW_DONE = object()

                    def ndjson():
                        # Rows stream CONCURRENTLY, merged into one
                        # ndjson sequence with per-row attribution: one
                        # pump thread per row feeds a shared queue (the
                        # generators block on the decode loop, so a
                        # single-threaded round-robin would stall every
                        # row behind the slowest).
                        out_q = queue_mod.SimpleQueue()

                        def pump(i):
                            # Pumps feed the reconnect log DIRECTLY —
                            # not via the merger — so a dead merger
                            # (client gone) never stops the log, and a
                            # detached request keeps journaling its
                            # delivery for the eventual reconnect.
                            try:
                                out_q.put((i, firsts[i]))
                                if resume_log is not None:
                                    resume_log.append(rid, i, firsts[i])
                                for token in sources[i]:
                                    out_q.put((i, token))
                                    if resume_log is not None:
                                        resume_log.append(rid, i, token)
                                out_q.put((i, _ROW_DONE))
                                if resume_log is not None:
                                    resume_log.row_done(rid)
                            except Exception as e:
                                out_q.put((i, e))
                                if resume_log is not None:
                                    resume_log.finish(rid, error=e)

                        # Pumps ride the same bounded pool. Rows beyond
                        # the worker count pump after earlier rows
                        # finish — their tokens buffer in the server's
                        # per-request queues meanwhile, so decode never
                        # stalls on pump scheduling.
                        for i in range(len(prompts)):
                            row_pool.submit(pump, i)
                        generated = [[] for _ in prompts]
                        live = len(prompts)
                        try:
                            while live:
                                i, item = out_q.get()
                                if item is _ROW_DONE:
                                    live -= 1
                                    continue
                                if isinstance(item, Exception):
                                    # Attribute the failing row: the HTTP
                                    # layer's final {"error": ...} document
                                    # carries it (status.py), so healthy
                                    # rows' truncation is diagnosable.
                                    item.stream_row = i
                                    raise item
                                generated[i].append(item)
                                yield {"row": i, "token": item}
                        except GeneratorExit:
                            # The HTTP layer closed us: the client is
                            # gone. Without durability, cancel every
                            # row so slots and pages free at the next
                            # decode boundary instead of decoding out
                            # the reserved budgets (models/serving.py
                            # cancel); the pump threads unblock on the
                            # RequestCancelled their streams receive.
                            # With checkpointing on (rung 22) the
                            # disconnect DETACHES instead: the rows
                            # decode on, the pumps keep feeding the
                            # reconnect log, and the client stitches
                            # the stream back with emitted_offset.
                            if resume_log is None:
                                for src in sources:
                                    if src is not None:
                                        src.cancel()
                            raise
                        yield {
                            "done": True,
                            "tokens": [p + g for p, g
                                       in zip(prompts, generated)],
                            "n_new": n_new,
                            "restored_step": restored_step,
                            "request_id": rid,
                        }

                    unwritten = set(range(len(prompts)))

                    def first_written(row: int) -> int:
                        """The handler flushed row ``row``'s first
                        line; returns the rows still to write one."""
                        if row in unwritten:
                            unwritten.discard(row)
                            sources[row].first_written()
                        return len(unwritten)

                    return {"_stream": ndjson(), "request_id": rid,
                            "_first_written": first_written}

                rows: list = [None] * len(tokens)

                def one_row(i):
                    rows[i] = paged_server.submit(
                        [t % tcfg.vocab for t in tokens[i]], n_new,
                        sampling=row_sampling(i),
                        priority=priority, deadline_ms=deadline_ms,
                        request_id=rid,
                    )

                # Buffered requests register for reconnect too: the
                # submitter blocks server-side through a recovery, so
                # a client whose connection died mid-wait re-asks with
                # emitted_offset=0 and collects the finished tokens.
                if resume_log is not None:
                    resume_log.open(rid, len(tokens), n_new)
                try:
                    fan_out_rows(len(tokens), one_row)
                except Exception as e:
                    if resume_log is not None:
                        resume_log.finish(rid, error=e)
                    raise
                if resume_log is not None:
                    for i, row in enumerate(rows):
                        for t in row[len(tokens[i]):]:
                            resume_log.append(rid, i, t)
                    resume_log.finish(rid)
                return {
                    "tokens": rows,
                    "n_new": n_new,
                    "restored_step": restored_step,
                    "request_id": rid,
                }
            prompt = jnp.asarray(tokens, jnp.int32) % tcfg.vocab
            if spec:
                from kvedge_tpu.models import generate_speculative

                with lock:
                    out, rate = generate_speculative(
                        params, prompt, tcfg, n_new=n_new, draft_len=spec
                    )
                return {
                    "tokens": [[int(t) for t in out.tolist()[0]]],
                    "n_new": n_new,
                    "restored_step": restored_step,
                    "request_id": rid,
                    # Observability: mean tokens emitted per verify pass
                    # (1.0 = speculation never paid; draft_len + 1 =
                    # every draft accepted).
                    "accepted_per_step": round(float(rate), 3),
                }
            sampling = None
            if sampled:
                seed_keys = jax.vmap(
                    lambda i: jax.random.fold_in(base_key, i)
                )(jnp.arange(len(tokens)))
                sampling = (seed_keys, jnp.float32(temperature),
                            jnp.float32(top_p))
            with lock:
                out = generate(params, prompt, tcfg, n_new=n_new,
                               sampling=sampling, sampled=sampled)
            return {
                "tokens": [[int(t) for t in row] for row in out.tolist()],
                "n_new": n_new,
                "restored_step": restored_step,
                "request_id": rid,
            }

        # Request accounting around _serve: the serving half of the
        # observability story (/metrics kvedge_serve_* gauges); counter
        # vocabulary and outcome mapping live in _ServeCounters (shared
        # with the multi-host leader).
        counters = _ServeCounters()

        def serve_fn(doc: dict) -> dict:
            counters.count("requests_total")
            start = time_mod.perf_counter()
            try:
                result = _serve(doc)
            except Exception as e:
                counters.count_outcome(e)
                raise
            stream = result.get("_stream")
            if stream is None:
                counters.count("tokens_generated_total",
                               result["n_new"] * len(result["tokens"]))
                counters.finish(start)
                return result

            def counted():
                # Latency for a streamed request = admission to final
                # document; tokens count as they actually go out. A
                # consumer abandoning the iterator mid-stream therefore
                # never records a completion — matching what the client
                # observed. A mid-decode FAILURE is not abandonment: it
                # lands in the same outcome buckets as non-streamed
                # requests (the HTTP status is already committed, but
                # the operator's error counters must still see it).
                try:
                    for item in stream:
                        if "token" in item:
                            counters.count("tokens_generated_total")
                        yield item
                except GeneratorExit:
                    # Closed by the HTTP layer on client disconnect:
                    # propagate so the inner generator cancels its rows.
                    # Still no completion recorded — matching what the
                    # client observed.
                    stream.close()
                    raise
                except Exception as e:
                    counters.count_outcome(e)
                    raise
                counters.finish(start)

            return {**result, "_stream": counted()}

        def serve_stats() -> dict:
            out = counters.snapshot()
            out["backend"] = backend or (
                "paged" if paged_server is not None else "contiguous"
            )
            if backend is not None:
                out["processes"] = jax.process_count()
            if paged_server is not None:
                # Pool occupancy straight from the server (in_flight,
                # free_slots, free_pages, reserved_pages).
                out.update(paged_server.stats())
            if recovery_sup is not None:
                # Recovery-machine gauges/counters (serve_recovering,
                # attempt totals) ride the same snapshot.
                out.update(recovery_sup.stats())
            return out

        serve_fn.stats = serve_stats
        # The live paged server (None on the contiguous backend), for
        # in-process drivers that must see the pool and the programs it
        # runs (chip_smoke.py lowers the decode window it served with).
        serve_fn.server = paged_server
        # Flight-recorder handle for the HTTP layer: boot.py's /trace
        # closure reads this attribute at request time (None = 404,
        # tracing off). Plain reference — survives revive()/reform.
        serve_fn.tracer = tracer
        # Lock-free degraded probe for /healthz (boot.py): reading
        # stats() takes the server lock, which a health check must not
        # depend on; the property is a bare attribute read.
        serve_fn.degraded = (
            (lambda: paged_server.degraded)
            if paged_server is not None else (lambda: None)
        )
        # Lock-free capacity probe for /healthz's recovering payload
        # (satellite of rung 22): pages_free/pages_total/bucket as bare
        # attribute reads — same no-lock contract as `degraded`.
        if paged_server is not None:
            serve_fn.capacity = paged_server.capacity_probe
        # SLO + flight-bundle handles for the HTTP layer (rung 25):
        # boot.py's /slo and /debug/bundle closures call these at
        # request time. None = the route 404s with its knob pointer.
        serve_fn.slo = (
            paged_server.slo_doc
            if paged_server is not None and cfg.serving_slo
            else None
        )
        serve_fn.bundle = (
            paged_server.flight_bundle
            if paged_server is not None and cfg.serving_bundle
            else None
        )
        # Recovery-machine probe for /healthz: while the supervisor is
        # recovering, boot.health_detail reports 503 NON-terminal with
        # a retry-after hint; terminal only after escalation.
        if recovery_sup is not None:
            serve_fn.recovery = recovery_sup.health

        # Self-check: one tiny generation proves the restored params and
        # the decode path actually work before the endpoint goes live.
        # Sized from the model so a small (legal) train_seq cannot fail a
        # servable payload; max_seq == 1 genuinely cannot serve (every
        # request needs prompt + n_new >= 2) and errors out here.
        if tcfg.max_seq < 2:
            raise ValueError(
                f"[payload] seq = {tcfg.max_seq} is too small to serve: "
                "every request needs prompt + n_new >= 2"
            )
        probe_prompt = list(range(1, min(4, tcfg.max_seq - 1) + 1))
        probe_new = min(2, tcfg.max_seq - len(probe_prompt))
        start = time_mod.perf_counter()
        # Through _serve, not the counted wrapper: the boot self-check is
        # not operator traffic, so the kvedge_serve_* counters start at 0.
        probe = _serve({"tokens": [probe_prompt], "n_new": probe_new})
        elapsed_ms = (time_mod.perf_counter() - start) * 1000.0
        # Teardown path: the paged server owns a decode thread and the
        # device-side page pool, plus the bounded row pool; callers
        # (RuntimeHandle.shutdown, test fixtures) release them via
        # serve_fn.close(). drain=True finishes in-flight budgets
        # before stopping (models/serving.py close semantics).
        def _close(drain: bool = False) -> None:
            if recovery_sup is not None:
                # A recovery racing shutdown must not revive a pool the
                # close below is tearing down.
                recovery_sup.stop()
            if paged_server is not None:
                paged_server.close(drain=drain)
                if prefix_path:
                    # AFTER close: a drain's late completions register
                    # prefixes too, and the registry + device pool
                    # survive close (nothing clears them). Best-effort:
                    # a failed dump must not block the shutdown path.
                    try:
                        paged_server.dump_prefix_cache(prefix_path, fp)
                    except Exception as e:
                        print(f"[kvedge-serve] prefix-cache dump "
                              f"failed: {e!r}", flush=True)
            if row_pool is not None:
                # Drain must let QUEUED pumps run: a streamed request
                # wider than the pool still has rows waiting to pump,
                # and cancelling them would leave its ndjson merger
                # blocked on row-done markers that never come. The
                # pumps finish promptly — the drained server has
                # already completed (or poisoned) every stream queue.
                row_pool.shutdown(wait=drain, cancel_futures=not drain)

        serve_fn.close = _close
        return dataclasses.replace(
            base, probe_ms=elapsed_ms,
            probe_checksum=float(sum(probe["tokens"][0])),
        ), serve_fn
    except Exception:
        # paged_server.close() also releases a slice cache's followers
        # (the cache.stop hook); if the failure desynced the broadcast
        # stream the slice is already lost (restart path).
        if recovery_sup is not None:
            recovery_sup.stop()
        if paged_server is not None:
            paged_server.close()
        if row_pool is not None:
            row_pool.shutdown(wait=False, cancel_futures=True)
        raise


# Inference probe: small GQA model, short prompt, a few greedy steps.
PROBE_KV_HEADS = 2
PROBE_PROMPT = 8
PROBE_NEW_TOKENS = 4


def run_inference_probe(cfg: RuntimeConfig) -> DeviceCheckResult:
    """Prove the serving path: cached greedy decode == teacher forcing."""
    base = run_device_check(cfg)
    if not base.ok:
        return base

    import dataclasses
    import time

    import jax
    import jax.numpy as jnp

    from kvedge_tpu.models import (
        TransformerConfig, forward, generate, init_params,
    )

    tcfg = TransformerConfig(
        vocab=PROBE_VOCAB,
        d_model=PROBE_D_MODEL,
        n_heads=4,
        n_kv_heads=PROBE_KV_HEADS,
        n_layers=PROBE_LAYERS,
        d_ff=4 * PROBE_D_MODEL,
        max_seq=PROBE_SEQ,
    )
    try:
        params = init_params(jax.random.PRNGKey(0), tcfg)
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (2, PROBE_PROMPT), 0, tcfg.vocab,
            dtype=jnp.int32,
        )
        start = time.perf_counter()
        out = generate(params, prompt, tcfg, n_new=PROBE_NEW_TOKENS)
        out.block_until_ready()
        elapsed_ms = (time.perf_counter() - start) * 1000.0

        # Cross-check every generated token against the cache-less forward
        # pass — the decode path must reproduce training-time math exactly.
        so_far = prompt
        for _ in range(PROBE_NEW_TOKENS):
            nxt = jnp.argmax(forward(params, so_far, tcfg)[:, -1], axis=-1)
            so_far = jnp.concatenate(
                [so_far, nxt[:, None].astype(jnp.int32)], axis=1
            )
        if not bool(jnp.all(out == so_far)):
            return dataclasses.replace(
                base, ok=False,
                error="inference probe: cached decode disagrees with "
                      "teacher-forced forward pass",
            )
    except Exception as e:
        return dataclasses.replace(
            base, ok=False, error=f"inference probe failed: {e!r}",
        )
    return dataclasses.replace(
        base, probe_ms=elapsed_ms, probe_checksum=float(out.sum()),
    )
