"""Checkpoint abstraction + keep-K manager + storage context.

Mirrors the reference's directory-based Checkpoint
(reference: python/ray/train/_checkpoint.py), CheckpointManager keep-K /
score-attr retention (python/ray/train/_internal/checkpoint_manager.py) and
StorageContext persistence (python/ray/train/_internal/storage.py:358,
persist_current_checkpoint :514). TPU-native addition: `save_pytree` /
`load_pytree` write sharded jax arrays via orbax (one shard per host on a
pod slice) with a numpy fallback.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pickle
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple


class Checkpoint:
    """A directory of files (framework-agnostic), created via
    `Checkpoint.from_directory` (reference: python/ray/train/_checkpoint.py)."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)

    @classmethod
    def from_directory(cls, path: str) -> "Checkpoint":
        return cls(path)

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "Checkpoint":
        d = tempfile.mkdtemp(prefix="rt-ckpt-")
        with open(os.path.join(d, "data.pkl"), "wb") as f:
            pickle.dump(data, f)
        return cls(d)

    def to_dict(self) -> Dict[str, Any]:
        with open(os.path.join(self.path, "data.pkl"), "rb") as f:
            return pickle.load(f)

    def as_directory(self) -> str:
        return self.path

    def __repr__(self):
        return f"Checkpoint({self.path})"


def save_pytree(tree: Any, directory: str) -> None:
    """Saves a jax pytree of (possibly sharded) arrays. Uses orbax when
    available so each host writes only its shards; numpy fallback."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(os.path.abspath(directory), "pytree")
    try:
        import orbax.checkpoint as ocp

        if os.path.exists(path):
            shutil.rmtree(path)
        with ocp.PyTreeCheckpointer() as ckptr:
            ckptr.save(path, tree)
        return
    except Exception:
        # Remove any partial orbax dir so load_pytree doesn't prefer corrupt
        # data over the npz fallback written below.
        shutil.rmtree(path, ignore_errors=True)
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    np.savez(
        os.path.join(directory, "pytree.npz"),
        **{str(i): np.asarray(l) for i, l in enumerate(leaves)},
    )
    with open(os.path.join(directory, "treedef.pkl"), "wb") as f:
        pickle.dump(treedef, f)


def load_pytree(directory: str, like: Any = None) -> Any:
    """Restores a pytree saved by save_pytree. Without `like`, arrays come
    back as numpy (host memory) — device placement is the caller's job,
    which keeps restore topology-independent. With `like` (a pytree of
    arrays with shardings), arrays restore directly onto those shardings."""
    orbax_path = os.path.join(os.path.abspath(directory), "pytree")
    if os.path.exists(orbax_path):
        import jax
        import numpy as np
        import orbax.checkpoint as ocp

        with ocp.PyTreeCheckpointer() as ckptr:
            if like is not None:
                restore_args = ocp.checkpoint_utils.construct_restore_args(like)
                return ckptr.restore(
                    orbax_path, args=ocp.args.PyTreeRestore(item=like, restore_args=restore_args)
                )
            tree_meta = ckptr.metadata(orbax_path).item_metadata
            restore_args = jax.tree_util.tree_map(
                lambda _: ocp.RestoreArgs(restore_type=np.ndarray), tree_meta
            )
            return ckptr.restore(orbax_path, args=ocp.args.PyTreeRestore(restore_args=restore_args))
    import jax
    import numpy as np

    data = np.load(os.path.join(directory, "pytree.npz"))
    with open(os.path.join(directory, "treedef.pkl"), "rb") as f:
        treedef = pickle.load(f)
    leaves = [data[str(i)] for i in range(len(data.files))]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save_aux_state(directory: str, payload: Any) -> None:
    """Pickles host-resident auxiliary training state (optimizer moments,
    RNG keys) alongside a pytree checkpoint. Kept out of save_pytree because
    optax NamedTuple structure does not survive an orbax metadata-restore;
    a resume must continue the same optimizer trajectory. Written via a
    temp file + rename so a crash mid-save cannot leave a truncated file."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "opt_state.pkl")
    with open(path + ".tmp", "wb") as f:
        pickle.dump(payload, f)
    os.replace(path + ".tmp", path)


def load_aux_state(directory: str) -> Optional[Any]:
    """Inverse of save_aux_state; None when the checkpoint predates it or
    the sidecar is unreadable (callers fall back to fresh optimizer state —
    an intact params pytree must stay restorable)."""
    path = os.path.join(directory, "opt_state.pkl")
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            return pickle.load(f)
    except Exception:
        return None


@dataclasses.dataclass
class _TrackedCheckpoint:
    checkpoint: Checkpoint
    index: int
    metrics: Dict[str, Any]


class CheckpointManager:
    """Keep-K retention by score attribute
    (reference: python/ray/train/_internal/checkpoint_manager.py)."""

    def __init__(
        self,
        num_to_keep: Optional[int] = None,
        score_attribute: Optional[str] = None,
        score_order: str = "max",
    ):
        if num_to_keep is not None and num_to_keep <= 0:
            raise ValueError("num_to_keep must be positive or None")
        if score_order not in ("max", "min"):
            raise ValueError("score_order must be 'max' or 'min'")
        self._num_to_keep = num_to_keep
        self._score_attribute = score_attribute
        self._score_order = score_order
        self._checkpoints: List[_TrackedCheckpoint] = []
        self._next_index = 0

    def register(self, checkpoint: Checkpoint, metrics: Dict[str, Any]) -> None:
        self._checkpoints.append(
            _TrackedCheckpoint(checkpoint, self._next_index, dict(metrics or {}))
        )
        self._next_index += 1
        self._evict()

    def _score(self, t: _TrackedCheckpoint) -> Tuple:
        if self._score_attribute and self._score_attribute in t.metrics:
            v = float(t.metrics[self._score_attribute])
            return (v if self._score_order == "max" else -v, t.index)
        return (float("-inf"), t.index)

    def _evict(self) -> None:
        if self._num_to_keep is None:
            return
        while len(self._checkpoints) > self._num_to_keep:
            worst = min(self._checkpoints, key=self._score)
            self._checkpoints.remove(worst)
            shutil.rmtree(worst.checkpoint.path, ignore_errors=True)

    @property
    def best_checkpoint(self) -> Optional[Checkpoint]:
        if not self._checkpoints:
            return None
        return max(self._checkpoints, key=self._score).checkpoint

    @property
    def latest_checkpoint(self) -> Optional[Checkpoint]:
        if not self._checkpoints:
            return None
        return max(self._checkpoints, key=lambda t: t.index).checkpoint

    @property
    def checkpoints(self) -> List[Checkpoint]:
        return [t.checkpoint for t in self._checkpoints]


class StorageContext:
    """Resolves the experiment/trial directory layout and persists worker
    checkpoints into it (reference: python/ray/train/_internal/storage.py:358)."""

    def __init__(self, storage_path: str, experiment_name: str, trial_name: str = ""):
        self.storage_path = os.path.abspath(storage_path)
        self.experiment_name = experiment_name
        self.trial_name = trial_name

    @property
    def experiment_dir(self) -> str:
        return os.path.join(self.storage_path, self.experiment_name)

    @property
    def trial_dir(self) -> str:
        d = self.experiment_dir
        return os.path.join(d, self.trial_name) if self.trial_name else d

    def persist_checkpoint(self, checkpoint: Checkpoint, index: int) -> Checkpoint:
        dest = os.path.join(self.trial_dir, f"checkpoint_{index:06d}")
        if os.path.abspath(checkpoint.path) != dest:
            if os.path.exists(dest):
                shutil.rmtree(dest)
            os.makedirs(os.path.dirname(dest), exist_ok=True)
            shutil.copytree(checkpoint.path, dest)
        return Checkpoint(dest)

    def write_json(self, name: str, payload: Dict[str, Any]) -> None:
        os.makedirs(self.trial_dir, exist_ok=True)
        with open(os.path.join(self.trial_dir, name), "w") as f:
            json.dump(payload, f, indent=2, default=str)
