"""Model server with in-place weight updates (paper §4.2, Fig. 5b).

The LMDeploy analogue: the rollout engine holds one live copy of the
(sharded) parameters; each RL step pushes the trainer's fresh params into
the server **in place** — a device-to-device donation, no file-system IO,
the server never reloads.  ``OfflineWeightStore`` is the Fig. 5a baseline
it replaces: every step saves a checkpoint and the "server" re-loads it
(twice, as the paper observes: once for rollout, once for training).
"""

from __future__ import annotations

import glob
import os
import tempfile
import time
from typing import Any

import jax

from repro.checkpoint.io import load_pytree, save_pytree


class StaleParamsError(RuntimeError):
    """A consumer asked for a param version the server no longer holds.

    ``update_weights`` donates the superseded buffers (and the trainer's
    next step donates the live ones it handed over), so a reference to
    an old version is not merely outdated — reading it can raise
    jax's "Array has been deleted" or silently alias fresh data.  The
    versioned read surface turns that latent hazard into this loud,
    named error at the *request* site instead.
    """


class ModelServer:
    """Keeps the live param pytree + a monotonically increasing version."""

    def __init__(self, params: Any, *, donate: bool = True):
        self._params = params
        self.version = 0
        self.donate = donate
        self.update_seconds = 0.0

    @property
    def params(self):
        return self._params

    def params_versioned(self) -> tuple[int, Any]:
        """One atomic read of ``(version, params)``.

        The pair is what a tick-granular consumer (the async rollout
        producer) must take together: reading ``.params`` and
        ``.version`` separately races with an ``update_weights`` landing
        in between, mis-stamping a whole block of rollouts.
        """
        return self.version, self._params

    def params_at(self, version: int):
        """Version-pinned read: the live params iff ``version`` is
        current, else ``StaleParamsError``.

        The server keeps exactly one version — older buffers were
        donated away — so a consumer that cached a version tag across an
        update cannot get the matching weights back; failing loudly here
        beats a post-donation read deep inside a jitted call.
        """
        if version != self.version:
            raise StaleParamsError(
                f"params version {version} requested but the server "
                f"holds only version {self.version}; older buffers were "
                "donated by update_weights — re-read params_versioned() "
                "instead of caching params across updates")
        return self._params

    def update_weights(self, new_params, *, sync: bool = True) -> int:
        """In-place push (the LMDeploy update API analogue).

        With donation the old buffers are released as the new ones land;
        there is no serialisation and no reload.  ``sync=False`` skips
        the readiness barrier: the version advances immediately and the
        new buffers are consumed through normal jax dataflow — the async
        RL loop uses this so a weight push never stalls the host between
        two pool ticks (``update_seconds`` then measures dispatch only).
        """
        t0 = time.perf_counter()
        if self.donate:
            old = self._params
            self._params = new_params
            del old
        else:
            self._params = jax.tree.map(lambda x: x, new_params)
        if sync:
            jax.block_until_ready(
                jax.tree_util.tree_leaves(self._params)[0])
        self.update_seconds = time.perf_counter() - t0
        self.version += 1
        return self.version


class OfflineWeightStore:
    """Fig. 5a baseline: checkpoint round-trip through the file system."""

    def __init__(self, params: Any, root: str | None = None):
        self.root = root or tempfile.mkdtemp(prefix="dirl_offline_")
        self.version = 0
        self._like = jax.tree.map(lambda x: x, params)
        self.save_seconds = 0.0
        self.load_seconds = 0.0
        self.update_weights(params)

    def _path(self, version: int) -> str:
        return os.path.join(self.root, f"ckpt_{version}.msgpack")

    def update_weights(self, new_params) -> int:
        t0 = time.perf_counter()
        self.version += 1
        save_pytree(self._path(self.version), new_params)
        self.save_seconds = time.perf_counter() - t0
        self._gc(keep=self.version)
        return self.version

    def _gc(self, keep: int) -> None:
        """Delete superseded checkpoints — an online RL run writes one
        per step, which is unbounded disk growth if never reaped."""
        # escaped: a root holding glob metacharacters ("[") must still match
        for p in glob.glob(os.path.join(glob.escape(self.root),
                                        "ckpt_*.msgpack")):
            if p == self._path(keep):
                continue
            try:
                os.remove(p)
            except OSError:
                pass

    @property
    def params(self):
        """Every access loads from storage — the cost Fig. 6 eliminates."""
        t0 = time.perf_counter()
        p = load_pytree(self._path(self.version), self._like)
        jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
        self.load_seconds = time.perf_counter() - t0
        return p
