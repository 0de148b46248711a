"""1F1B pipeline schedule: fused forward+backward, O(S) activation stash.

The GPipe schedule (parallel/pipeline.py) is differentiable end-to-end —
``jax.grad`` transposes its scan into all-forwards-then-all-backwards,
which is exactly GPipe's memory shape: the backward needs state for
every one of the M microbatches at once (bounded today by remat to the
O(M) scan carries). 1F1B's defining property — at most O(S) microbatches
in flight — is a property of the *schedule*, and autodiff cannot invent
a schedule; so this module builds the training step's forward AND
backward as ONE explicit schedule and returns ``(loss, grads)``
directly. README future-work item, closed in round 4.

The schedule (full-duplex 1F1B): one ``lax.scan`` over
``T = M + 2S - 1`` ticks; at tick ``t`` stage ``s`` runs

* the FORWARD of microbatch ``i_f = t - s`` (valid while ``0 <= i_f <
  M``) — consuming stage 0's embedded input or the previous stage's
  ppermute'd activation, stashing its input for the backward;
* the BACKWARD of microbatch ``i_b = t - (2S - 1 - s)`` — re-running the
  stage body under ``jax.vjp`` against the stashed input, consuming the
  next stage's ppermute'd cotangent (or, at the last stage, the loss
  head's seed computed one tick earlier), accumulating parameter
  gradients.

In-flight microbatches at stage ``s`` number ``2(S - s) - 1 <= 2S - 1``,
so the input stash is a ``2S``-deep ring buffer indexed ``i mod 2S`` —
collision-free because ``i_f - i_b = 2S - 1 - 2s < 2S``. That is the
1F1B memory claim, made structural: stash depth is a function of S, not
M. (The O(M) arrays that remain — the embedded microbatch inputs and
the stage-0 input cotangents handed back for the embedding's backward —
are data terms every schedule carries.)

SPMD shape discipline: every stage executes every tick's full program
(forward + head + backward) on garbage during its bubble ticks, masked
out of all accumulators — data-dependent control flow would break the
single compiled program. The loss head (final RMSNorm + tied readout +
cross-entropy) therefore runs on every stage and is REAL only on the
last; its cost is one readout matmul per tick, the price of a uniform
program.

Composition: ``data`` joins the manual axes (microbatch rows shard over
it; gradients psum over it — the explicit form of the all-reduce
autodiff inserts for GPipe). ``model`` stays automatic, exactly like
GPipe: the stage body's tensor-parallel matmuls and their transposes
partition inside ``jax.vjp``. MoE, sequence-parallel attention, and the
fused-xent head are refused loudly — the GPipe path keeps those; this
schedule is the memory lever for deep dense stacks.

Gradient parity with ``jax.grad`` of the GPipe path is pinned by
tests/test_pipeline1f1b.py, and the compiled peak-memory win at M = 4S
is asserted there the same way pipeline.py's remat claim is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from kvedge_tpu.models.transformer import (
    _layer,
    _rmsnorm,
    stacked_layer_params,
    tied_readout,
)


def _check_supported(cfg, mesh) -> dict:
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "stage" not in axis_sizes:
        raise ValueError(
            "pipeline_schedule='1f1b' needs a mesh with a 'stage' axis"
        )
    if cfg.n_experts:
        raise ValueError(
            "pipeline_schedule='1f1b' does not support MoE layers yet "
            "(the router aux-loss plumbing lives in the GPipe path; "
            "use pipeline_schedule='gpipe')"
        )
    if cfg.attention in ("ring", "ulysses"):
        raise ValueError(
            "pipeline_schedule='1f1b' does not compose with sequence-"
            "parallel attention yet (pp x sp runs on the GPipe path)"
        )
    if cfg.fused_xent:
        raise ValueError(
            "pipeline_schedule='1f1b' computes its loss head inside the "
            "pipeline's manual region, where the Pallas fused-xent "
            "kernel cannot run; disable fused_xent or use "
            "pipeline_schedule='gpipe'"
        )
    return axis_sizes


def pipeline_1f1b_loss_and_grads(params: dict, batch, cfg, mesh):
    """``(loss, grads)`` for one training batch via the 1F1B schedule.

    ``batch`` [B, T+1] int32 (targets are the shifted inputs, exactly
    :func:`~kvedge_tpu.models.transformer.loss_fn`'s convention);
    ``grads`` matches the ``params`` pytree. The embedding's gradient
    has two contributions — the tied readout inside the loss head
    (accumulated in-schedule at the last stage) and the input lookup
    (computed OUTSIDE the manual region from the schedule's stage-0
    input cotangents, so autodiff handles the scatter-add).
    """
    axis_sizes = _check_supported(cfg, mesh)
    stages = axis_sizes["stage"]
    if cfg.n_layers % stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} must divide by the stage axis "
            f"size {stages}"
        )
    inputs = batch[:, :-1]
    targets = batch[:, 1:]
    b, t = inputs.shape
    micro = cfg.pipeline_microbatches or stages
    if b % micro:
        raise ValueError(f"batch {b} must divide into {micro} microbatches")
    mb = b // micro
    dspec = "data" if axis_sizes.get("data", 1) > 1 else None
    if dspec and mb % axis_sizes["data"]:
        raise ValueError(
            f"microbatch size {mb} must divide by the 'data' axis size "
            f"{axis_sizes['data']}"
        )
    dtype = jnp.dtype(cfg.dtype)
    stacked = stacked_layer_params(params, cfg)

    def embed(embedding, tok):
        return embedding[tok].astype(dtype)

    x_mb, embed_vjp = jax.vjp(
        lambda e: embed(e, inputs.reshape(micro, mb, t)),
        params["embedding"],
    )  # x_mb [M, mb, T, D]
    tgt_mb = targets.reshape(micro, mb, t)
    n_tokens = b * t  # loss normalizer (global batch x seq)

    def local_fn(x_mb, tgt_mb, ln_final, embedding, *stacked_local):
        stage = lax.axis_index("stage")
        ticks = micro + 2 * stages - 1
        depth = 2 * stages
        # Inside the manual region every array is the per-device block:
        # microbatch rows are data-LOCAL (mb / data-axis of them).
        _, mbl, t_loc, _ = x_mb.shape
        fwd_hop = [(i, i + 1) for i in range(stages - 1)]
        bwd_hop = [(i + 1, i) for i in range(stages - 1)]

        def f_stage(stacked_p, x):
            def body(carry, lp):
                out, _ = _layer(cfg, carry, lp, mesh,
                                constrain_moe=False)
                return out, None

            h, _ = lax.scan(body, x, stacked_p)
            return h

        def head(h, lnf, emb, tgt, mask):
            """Loss head: SUM of token cross-entropies for one
            microbatch, times ``mask`` (1.0 only on the last stage's
            valid ticks). The mask multiplies the OUTPUT — not the
            accumulators afterward — because ``lnf``/``emb`` are
            REPLICATED inputs: shard_map's vjp inserts an implicit psum
            over the manual axes into a replicated input's cotangent,
            so any garbage a bubble stage contributed would be mixed in
            BEFORE a post-hoc mask could remove it. Masking the value
            zeroes those cotangent contributions at the source."""
            logits = tied_readout(_rmsnorm(h, lnf), emb)  # [mb, T, V]
            target_logit = jnp.take_along_axis(
                logits, tgt[..., None], axis=-1
            )[..., 0]
            return mask * jnp.sum(
                jax.nn.logsumexp(logits, axis=-1) - target_logit
            )

        # Initial carries must already vary over BOTH manual axes (the
        # tick body mixes in stage- and data-dependent values, and scan
        # requires carry types — including varying manual axes — to
        # match; same trick as pipeline.py / ringattention.py).
        zero = (stage.astype(dtype) * 0
                + x_mb.ravel()[0].astype(dtype) * 0)
        act = jnp.zeros((mbl, t_loc, cfg.d_model), dtype) + zero
        carry0 = (
            act,                                    # fwd_msg
            act,                                    # bwd_msg
            jnp.zeros((depth, mbl, t_loc, cfg.d_model), dtype) + zero,
            jnp.zeros((2, mbl, t_loc, cfg.d_model), dtype) + zero,  # seeds
            # Cotangent accumulators inherit their source's varying
            # axes: the stacked slices vary over stage (p * 0 keeps
            # that marking); the replicated head params' cotangents
            # arrive ALREADY psum'd over the manual axes (implicitly
            # invariant — see ``head``), so their accumulators stay
            # plain (invariant) zeros and need NO psum at the end.
            jax.tree_util.tree_map(lambda p: p * 0, stacked_local),
            jnp.zeros_like(ln_final),
            jnp.zeros_like(embedding),
            jnp.zeros((micro, mbl, t_loc, cfg.d_model), dtype) + zero,
            jnp.float32(0) + zero.astype(jnp.float32),             # loss
        )

        def tick(carry, t_idx):
            (fwd_msg, bwd_msg, stash, seeds, d_stacked, d_lnf, d_emb,
             dx0, loss_acc) = carry
            last = stage == stages - 1

            # ---- forward ------------------------------------------------
            i_f = t_idx - stage
            valid_f = (i_f >= 0) & (i_f < micro)
            i_f_c = jnp.clip(i_f, 0, micro - 1)
            x_in = jnp.where(stage == 0, x_mb[i_f_c], fwd_msg)
            h = f_stage(stacked_local, x_in)
            stash = jnp.where(
                valid_f, stash.at[i_f_c % depth].set(x_in), stash
            )
            # Loss head (real on the last stage's valid ticks only —
            # the mask rides INSIDE head, see its docstring): seeds the
            # backward that starts ONE tick later.
            head_real = last & valid_f
            ce, (dh, dlnf_i, demb_i) = jax.value_and_grad(
                head, argnums=(0, 1, 2)
            )(h, ln_final, embedding, tgt_mb[i_f_c],
              head_real.astype(jnp.float32))
            loss_acc = loss_acc + ce.astype(jnp.float32)
            d_lnf = d_lnf + dlnf_i
            d_emb = d_emb + demb_i
            seeds = jnp.where(
                valid_f, seeds.at[i_f_c % 2].set(dh), seeds
            )

            # ---- backward -----------------------------------------------
            i_b = t_idx - (2 * stages - 1 - stage)
            valid_b = (i_b >= 0) & (i_b < micro)
            i_b_c = jnp.clip(i_b, 0, micro - 1)
            x_saved = stash[i_b_c % depth]
            cot = jnp.where(last, seeds[i_b_c % 2], bwd_msg)
            _, vjp = jax.vjp(f_stage, stacked_local, x_saved)
            dp, dx = vjp(cot)
            d_stacked = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(valid_b, g, 0),
                d_stacked, dp,
            )
            dx0 = jnp.where(
                valid_b & (stage == 0),
                dx0.at[i_b_c].set(dx.astype(dtype)),
                dx0,
            )

            # ---- stage hand-offs ---------------------------------------
            fwd_msg = lax.ppermute(h, "stage", fwd_hop)
            bwd_msg = lax.ppermute(dx, "stage", bwd_hop)
            return (fwd_msg, bwd_msg, stash, seeds, d_stacked, d_lnf,
                    d_emb, dx0, loss_acc), None

        (_, _, _, _, d_stacked, d_lnf, d_emb, dx0, loss_acc), _ = (
            lax.scan(tick, carry0, jnp.arange(ticks))
        )
        # The COTANGENT accumulators are already globally summed: the
        # implicit psum on replicated-input cotangents covered d_lnf /
        # d_emb over every manual axis, and dp over data (its stacked
        # source varies over stage — there is nothing to sum there; one
        # stage's slice is one stage's gradient). Only the VALUE
        # accumulators need explicit reduction: the loss (per-shard
        # token-CE sums) and dx0 (stage 0's rows, zeros elsewhere).
        dx0 = lax.psum(dx0, "stage")
        loss = lax.psum(loss_acc, "stage")
        if dspec:
            loss = lax.psum(loss, dspec)
        return d_stacked, d_lnf, d_emb, dx0, loss

    n_stacked = len(stacked)
    act_spec = P(None, dspec, None, None)
    d_stacked, d_lnf, d_emb_head, dx0, loss_sum = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(act_spec, P(None, dspec, None), P(), P(),
                  *([P("stage")] * n_stacked)),
        out_specs=(tuple([P("stage")] * n_stacked), P(), P(), act_spec,
                   P()),
        axis_names=frozenset({"stage"} | ({dspec} if dspec else set())),
    )(x_mb, tgt_mb, params["ln_final"], params["embedding"], *stacked)

    loss = loss_sum / n_tokens
    # The embedding's input-lookup contribution, via the vjp taken
    # OUTSIDE the manual region (autodiff owns the scatter-add).
    (d_emb_lookup,) = embed_vjp(dx0)
    scale = 1.0 / n_tokens  # head summed raw token CEs; grads follow
    # Stacked grads come back in stacked_layer_params order.
    grads = {name: g * scale
             for name, g in zip(_stacked_names(cfg), d_stacked)}
    grads["ln_final"] = d_lnf * scale
    grads["embedding"] = (d_emb_head * scale
                          + d_emb_lookup.astype(d_emb_head.dtype) * scale)
    return loss, grads


def _stacked_names(cfg) -> tuple:
    """Param names in ``stacked_layer_params`` order (dense configs —
    MoE is refused in :func:`_check_supported`)."""
    return ("w_qkv", "w_out", "w_up", "w_down", "ln_attn", "ln_mlp")
