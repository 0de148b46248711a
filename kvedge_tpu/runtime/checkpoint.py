"""Checkpoint/resume through the PVC-backed state directory.

The reference's whole checkpoint story is "the PVC is the checkpoint":
EdgeHub message state survives rescheduling because the boot disk is
PVC-backed (SURVEY.md §5, reference ``README.md:77,88``) — there is no
application-level checkpoint code at all. kvedge-tpu keeps that property
for the runtime's own state (heartbeats) and adds what a *JAX* payload
actually needs: an orbax-backed layout under ``<state_dir>/checkpoints``
so training state (params, optimizer, step) written through the PVC is
restorable by the next pod generation (SURVEY.md §7 capability 3 calls
for exactly this orbax-compatible layout).
"""

from __future__ import annotations

import os
from typing import Any

CHECKPOINT_SUBDIR = "checkpoints"


def _shape_index(tree: Any) -> dict[str, tuple]:
    """``{"params/embedding": (512, 128), ...}`` for every leaf with a
    shape. Key-path strings normalize container differences — orbax
    metadata renders optax's namedtuples/tuples as dicts of stringified
    indices, so treedef equality is the wrong comparator across that
    boundary; names are stable."""
    import jax

    idx: dict[str, tuple] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        parts = []
        for p in path:
            part = getattr(p, "key", None)
            if part is None:
                part = getattr(p, "name", None)
            if part is None:
                part = getattr(p, "idx", None)
            parts.append(str(part))
        idx["/".join(parts)] = tuple(shape)
    return idx


def _verify_template(abstract_tree: Any, saved_tree: Any, source: str,
                     *, structure_must_match: bool = True) -> None:
    """Raise loudly when the restore template doesn't match the
    checkpoint.

    The mismatch-fails-loudly contract (a serve pod whose [model]
    disagrees with the checkpoint must error, never silently decode a
    different architecture) must not depend on the orbax version doing
    the checking: some releases fulfil a mismatched template from
    whatever the file holds without erroring. ``saved_tree`` is the
    checkpoint's own metadata (pre-restore) or the restored tree
    (post-restore net). Shape checks skip template leaves without a
    ``.shape`` (e.g. PLACEHOLDER markers on partial restores).
    """
    import jax

    want, want_def = jax.tree_util.tree_flatten(abstract_tree)
    got, got_def = jax.tree_util.tree_flatten(saved_tree)
    if want_def != got_def:
        if not structure_must_match:
            # Metadata pre-check: container types differ legitimately
            # (orbax metadata renders tuples as dicts), so match leaves
            # by key path instead of treedef.
            want_idx = _shape_index(abstract_tree)
            got_idx = _shape_index(saved_tree)
            for key in want_idx.keys() & got_idx.keys():
                if want_idx[key] != got_idx[key]:
                    raise ValueError(
                        f"checkpoint shape mismatch against the "
                        f"{source} at {key!r}: template expects "
                        f"{want_idx[key]}, checkpoint holds "
                        f"{got_idx[key]} — the configured model does "
                        "not match the checkpointed one"
                    )
            return
        raise ValueError(
            "checkpoint tree structure mismatch against the "
            f"{source}: the restore template has {want_def}, the "
            f"checkpoint holds {got_def} — the configured model does "
            "not match the checkpointed one"
        )
    for w, g in zip(want, got):
        ws, gs = getattr(w, "shape", None), getattr(g, "shape", None)
        if ws is not None and gs is not None and tuple(ws) != tuple(gs):
            raise ValueError(
                f"checkpoint shape mismatch against the {source}: "
                f"template expects {tuple(ws)}, checkpoint holds "
                f"{tuple(gs)} — the configured model does not match "
                "the checkpointed one"
            )


def resolve_checkpoint_dir(state_dir: str, checkpoint_dir: str = "") -> str:
    """Where checkpoints live for a given state volume + optional override.

    Default (empty override): ``<state_dir>/checkpoints`` on the PVC —
    the single-host layout, where checkpoint durability IS pod-restart
    durability. A multi-host slice needs storage every host can reach
    (per-host PVCs cannot hold a slice-wide sharded checkpoint), so the
    override accepts a shared filesystem path or a remote URI
    (``gs://bucket/prefix`` — orbax resolves URI schemes through
    ``etils.epath``). URIs are passed through untouched; local paths are
    absolutized exactly like the default. Heartbeats and train-progress
    stay on the per-host PVC either way — they are per-pod liveness
    state, not slice state.
    """
    if not checkpoint_dir:
        return os.path.abspath(os.path.join(state_dir, CHECKPOINT_SUBDIR))
    if "://" in checkpoint_dir:
        return checkpoint_dir
    return os.path.abspath(checkpoint_dir)


class StateCheckpointer:
    """Thin orbax CheckpointManager over the state volume.

    Synchronous by design: the runtime's value proposition is that state
    is on the PVC when the pod dies, so every save waits for durability.
    ``checkpoint_dir`` overrides the on-PVC default for shared-storage
    deployments (see :func:`resolve_checkpoint_dir`).
    """

    def __init__(self, state_dir: str, keep: int = 3,
                 checkpoint_dir: str = ""):
        import orbax.checkpoint as ocp

        self._dir = resolve_checkpoint_dir(state_dir, checkpoint_dir)
        self._manager = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(max_to_keep=keep, create=True),
        )
        self._ocp = ocp

    @property
    def directory(self) -> str:
        return self._dir

    def save(self, step: int, tree: Any) -> None:
        self._manager.save(step, args=self._ocp.args.StandardSave(tree))
        self._manager.wait_until_finished()

    def latest_step(self) -> int | None:
        return self._manager.latest_step()

    def _saved_metadata(self, step: int) -> Any | None:
        """Shape metadata of the saved tree, or None when unreadable.

        ``item_metadata`` resolves through the manager's handler
        registry, which a manager that never saved may not have bound
        yet (it then yields an empty tree); the handler-level
        ``metadata()`` reads the step directory directly. Best-effort:
        any failure returns None and the post-restore net still runs.
        """
        import jax

        try:
            meta = self._manager.item_metadata(step)
            if meta is not None and jax.tree_util.tree_leaves(meta):
                return meta
        except Exception:
            pass
        try:
            from etils import epath

            path = epath.Path(self._dir) / str(step) / "default"
            if path.exists():
                return self._ocp.StandardCheckpointHandler().metadata(path)
        except Exception:
            pass
        return None

    def restore_latest(self, abstract_tree: Any = None, *,
                       partial: bool = False) -> tuple[int, Any] | None:
        """(step, tree) of the newest checkpoint, or None on a fresh volume.

        ``abstract_tree`` (e.g. ``jax.eval_shape`` output or a concrete
        template) restores with the correct dtypes/shardings; omitting it
        falls back to orbax's topology inference. With ``partial=True``,
        subtrees of ``abstract_tree`` replaced by ``orbax.checkpoint
        .PLACEHOLDER`` are skipped entirely — never read, never allocated
        (how ``serve``/``eval`` restore params without materializing the
        optimizer moments). Partial restore is only valid on a manager
        that has not saved in this process (orbax binds the handler to
        the first args type it sees).
        """
        step = self._manager.latest_step()
        if step is None:
            return None
        if abstract_tree is not None:
            saved = self._saved_metadata(step)
            if saved is not None:
                _verify_template(abstract_tree, saved,
                                 "checkpoint metadata",
                                 structure_must_match=False)
        if abstract_tree is not None:
            args = (self._ocp.args.PyTreeRestore(abstract_tree) if partial
                    else self._ocp.args.StandardRestore(abstract_tree))
            tree = self._manager.restore(step, args=args)
        else:
            tree = self._manager.restore(step)
        if abstract_tree is not None:
            _verify_template(abstract_tree, tree, "restored tree")
        return step, tree

    def close(self) -> None:
        self._manager.close()

    def __enter__(self) -> "StateCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
