"""Device-resident coefficient store for the certified regularization path.

The training side certifies a path — L lambda operating points, each with
its own sparsity/quality trade-off. Serving keeps the ENTIRE stacked
``(L, p)`` coefficient array device-resident (replicated locally,
P(model)-feature-sharded on a mesh) so every request picks its lambda at
scoring time with zero host traffic: the scoring step gathers each
nonzero's coefficient from its request's row *on the device*
(``kernels.ops.entry_path_spmv`` over a batch's entry list locally,
``kernels.ops.slab_path_spmv`` over its slabs on a mesh).

Hot-swap: :meth:`PathStore.swap` installs a freshly certified path (a new
``PathResult`` from a background refit, or the next points of a still-
running certification) by building the new device stack first and then
publishing it as one reference assignment. Scoring code takes a
:class:`StoreSnapshot` once per batch, so an in-flight batch keeps scoring
against the coefficients it started with — a batch can never mix two
paths' coefficients — while the next batch sees the new version. The old
stack's device memory is released when the last in-flight batch drops its
snapshot (JAX arrays are immutable; nothing is overwritten in place).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.types import PathResult
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.resilience import InjectedFault, retry_call, take_load_failure, \
    take_swap_failure


@dataclass(frozen=True)
class StoreSnapshot:
    """An immutable view of one published path version.

    ``betas`` is the device-resident ``(L, p_pad)`` stack (feature axis
    zero-padded to the store's alignment); ``lambdas`` stays on host for
    operating-point resolution. Batches resolve lambdas and score against
    ONE snapshot, so a concurrent :meth:`PathStore.swap` can never split a
    batch across versions.
    """

    version: int
    lambdas: np.ndarray          # (L,) descending, host
    betas: jnp.ndarray           # (L, p_pad) device-resident
    p: int                       # original feature count (pre-padding)

    @property
    def num_points(self) -> int:
        return int(self.lambdas.shape[0])

    @property
    def p_pad(self) -> int:
        return int(self.betas.shape[1])

    def index_of(self, lam: float) -> int:
        """Nearest stored lambda in log space (the grid is geometric)."""
        lams = np.maximum(np.asarray(self.lambdas, np.float64), 1e-300)
        return int(np.argmin(np.abs(np.log(lams) - np.log(max(lam, 1e-300)))))

    def indices_of(self, lams) -> np.ndarray:
        """Vectorized :meth:`index_of` for a batch of requested lambdas."""
        grid = np.log(np.maximum(np.asarray(self.lambdas, np.float64),
                                 1e-300))
        q = np.log(np.maximum(np.asarray(lams, np.float64), 1e-300))
        return np.argmin(np.abs(grid[None, :] - q[:, None]),
                         axis=1).astype(np.int32)


class PathStore:
    """Holds the certified path device-resident and versioned.

    ``mesh=None`` keeps the stack on the default device (single-process
    serving); with a mesh the stack lands P(None, "model") — features
    sharded exactly like the training layout's beta, so the scoring
    shard_map pairs each coefficient block with its slab block and only
    psums the (batch,)-sized partial scores. ``tile`` aligns the feature
    padding with the slab partition (``model_dim * tile``), matching
    ``ShardedDesign``'s residency so served scores are bit-identical to
    ``LogisticL1.decision_function`` through the same mesh.
    """

    def __init__(self, result: Optional[PathResult] = None, *, mesh=None,
                 tile: int = 128):
        self.mesh = mesh
        self.tile = tile
        self._snap: Optional[StoreSnapshot] = None
        self._prev: Optional[StoreSnapshot] = None   # last-good fallback
        self._version = 0
        self.quarantined: list = []   # versions rolled back by quarantine()
        if result is not None:
            self.swap(result)

    # -- geometry -----------------------------------------------------------

    @property
    def pad_p_to(self) -> int:
        """Feature-axis alignment: mesh stores pad to model_dim * tile
        (the slab partition unit); local stores don't pad."""
        if self.mesh is None:
            return 1
        return self.mesh.shape["model"] * self.tile

    @property
    def snapshot(self) -> StoreSnapshot:
        if self._snap is None:
            raise ValueError("PathStore is empty — swap() a PathResult in")
        return self._snap

    @property
    def version(self) -> int:
        return self._version

    # -- publish ------------------------------------------------------------

    def swap(self, result: PathResult, *, attempts: int = 3) -> StoreSnapshot:
        """Atomically publish a new path version.

        The new stack is built and placed on device(s) BEFORE the snapshot
        reference flips, so concurrent scorers only ever observe a fully
        materialized version (the flip is one reference assignment —
        atomic under the GIL). In-flight batches holding the previous
        snapshot are unaffected.

        Transient build/placement failures (device OOM races, injected
        chaos faults) are retried with exponential backoff up to
        ``attempts`` times; the store keeps serving the current snapshot
        throughout — a failed swap never leaves it empty or half-built.
        Validation errors (empty path, feature-space mismatch) are not
        retried.
        """
        if len(result) == 0:
            raise ValueError("cannot publish an empty path")
        p = int(result.betas.shape[1])
        snap = self._snap
        if snap is not None and p != snap.p:
            raise ValueError(
                f"new path has p={p} but the store serves p={snap.p} — "
                f"a feature-space change needs a new store"
            )
        return retry_call(lambda: self._publish(result, p),
                          attempts=attempts, base_delay_s=0.01)

    def _publish(self, result: PathResult, p: int) -> StoreSnapshot:
        """One build-then-flip attempt (the retryable unit of :meth:`swap`).

        The ``swap`` span closes at the existing ``block_until_ready``
        sync + reference flip — tracing adds no new device round-trip."""
        with obs_trace.span("swap", points=len(result)):
            if take_swap_failure():
                raise InjectedFault("injected PathStore.swap failure")
            betas = jnp.asarray(result.betas, jnp.float32)
            pad = (-p) % self.pad_p_to
            if pad:
                betas = jnp.pad(betas, ((0, 0), (0, pad)))
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                betas = jax.device_put(
                    betas, NamedSharding(self.mesh, P(None, "model")))
            else:
                betas = jax.device_put(betas)
            betas.block_until_ready()  # fully materialized before publishing
            self._version += 1
            new = StoreSnapshot(version=self._version,
                                lambdas=np.asarray(result.lambdas,
                                                   np.float64),
                                betas=betas, p=p)
            self._prev = self._snap   # keep last-good for quarantine()
            self._snap = new          # the atomic publish
        obs_registry.counter("serve.swaps").inc()
        return new

    # -- rollback -----------------------------------------------------------

    def quarantine(self, version: int) -> bool:
        """Pin the store back to the previous snapshot if ``version`` is
        the one currently published.

        The scorer's non-finite guard calls this when a published version
        produces NaN/Inf scores: the store reverts to the last-good
        snapshot (one reference assignment, same atomicity as swap) and
        records the bad version in :attr:`quarantined`. Returns whether a
        rollback happened — False when ``version`` is already superseded
        (a newer swap won the race) or there is no previous snapshot to
        fall back to.
        """
        if (self._snap is not None and self._snap.version == version
                and self._prev is not None):
            self._snap = self._prev
            self._prev = None         # don't ping-pong back to the bad one
            self.quarantined.append(version)
            return True
        return False

    # -- persistence --------------------------------------------------------

    @classmethod
    def from_checkpoint(cls, directory: str, *, mesh=None, tile: int = 128,
                        attempts: int = 3) -> "PathStore":
        """Fit-once/serve-many: load a ``PathResult.save`` checkpoint and
        publish it (the serving process needs no training code or data).

        The load is retried with backoff (transient filesystem errors and
        injected chaos faults); persistent corruption still surfaces as
        :class:`~repro.checkpoint.CheckpointCorruption` after ``attempts``
        tries, wrapped in ``RetriesExhausted`` with the cause chained.
        """
        def _load() -> PathResult:
            if take_load_failure():
                raise InjectedFault("injected checkpoint-load failure")
            return PathResult.load(directory)

        return cls(retry_call(_load, attempts=attempts, base_delay_s=0.01),
                   mesh=mesh, tile=tile)
