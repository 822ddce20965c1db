"""Batched path scoring: one jitted dispatch per request batch.

The scoring step is ``kernels.ops.slab_path_spmv`` over a
:class:`~repro.serve.ingest.PackedBatch` — the by-feature slab layout the
training kernels consume, request rows playing the example axis, each row
gathering its own operating point from the store's stacked ``(L, p)``
coefficients. Locally that is one jitted call; on a mesh it is the same
``shard_map`` shape as ``core.distributed.make_slab_margins`` (feature
shards run the slab kernel, one psum over ``model`` assembles the scores)
with the beta *stack* left P(model)-sharded in place. Either way exactly
one program launches per batch and only the ``(batch,)`` scores travel to
host.

Because the per-entry coefficient gather feeds the *same* masking/scatter
machinery as ``slab_spmv`` (see ``slab_path_spmv``'s docstring), a batch
whose rows all request lambda ``l`` scores bit-identically to
``LogisticL1.decision_function(design, beta=path[l])`` on the same slabs —
locally and through the mesh.
"""
from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.obs import trace as obs_trace
from repro.resilience import serve_delay
from repro.serve.ingest import PackedBatch
from repro.serve.store import PathStore, StoreSnapshot


class NonFiniteScores(RuntimeError):
    """Every published snapshot the scorer tried produced NaN/Inf scores
    for this batch. Raised only after the store has been pinned back to
    its last-good snapshot (when one existed) and the batch retried — so
    a caller seeing this knows rollback did not help and the *batch*
    itself is suspect."""


@partial(jax.jit, static_argnames=("n_loc",))
def _score_local(rows, vals, lam_idx, betas, *, n_loc: int):
    return kops.slab_path_spmv(rows, vals, lam_idx, betas, n_loc=n_loc)


def make_path_margins(mesh, n_loc: int, model_axis: str = "model"):
    """Sharded batched path scoring ``(row_idx, values, lam_idx, betas) ->
    scores`` — ``core.distributed.make_slab_margins`` with the replicated
    beta vector replaced by the P(model)-sharded ``(L, p_pad)`` stack plus
    a per-row operating-point index. Each (model, data) shard gathers its
    own coefficient block rows and runs the slab kernel; one psum over
    ``model`` assembles the exact scores.

    Deliberately NOT module-cached: a process-lifetime cache here pins the
    mesh (and through jit internals, the last dispatch's arguments —
    i.e. a retired snapshot's beta stack) for as long as the module
    lives. :class:`PathScorer` owns a small per-instance cache instead,
    so dropping the scorer drops the compiled programs and
    ``PathStore.swap`` can actually release the old coefficients."""
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import _data_axes

    daxes = _data_axes(mesh)
    dspec = P(daxes) if daxes else P()

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(model_axis, daxes, None), P(model_axis, daxes, None),
                  dspec, P(None, model_axis)),
        out_specs=dspec,
    )
    def path_margins(row_idx, values, lam_idx, betas):
        rows, vals = row_idx[:, 0, :], values[:, 0, :]
        s_loc = kops.slab_path_spmv(rows, vals, lam_idx, betas,
                                    n_loc=n_loc)
        return jax.lax.psum(s_loc, model_axis)

    return path_margins


class PathScorer:
    """Scores request batches against a :class:`PathStore`.

    Each :meth:`score` call takes ONE store snapshot up front and resolves
    lambdas + scores entirely against it, so a concurrent
    ``PathStore.swap`` can never mix coefficient versions inside a batch;
    the returned version says which path the whole batch was scored with.
    """

    #: distinct (mesh, n_loc) program geometries kept per scorer; a
    #: serving process sees a handful of batch capacities, so eviction
    #: means at worst a recompile, never wrong scores
    _CACHE_MAX = 8

    def __init__(self, store: PathStore):
        self.store = store
        self._margins: "OrderedDict[tuple, object]" = OrderedDict()

    def _margins_for(self, mesh, n_loc: int):
        """Per-instance LRU of compiled sharded scoring programs."""
        key = (mesh, n_loc)
        fn = self._margins.get(key)
        if fn is None:
            fn = make_path_margins(mesh, n_loc)
            self._margins[key] = fn
            while len(self._margins) > self._CACHE_MAX:
                self._margins.popitem(last=False)
        else:
            self._margins.move_to_end(key)
        return fn

    def score(self, batch: PackedBatch,
              lams) -> Tuple[np.ndarray, int]:
        """Score a packed batch; ``lams[i]`` is row i's requested lambda.

        Returns ``(scores, version)``: ``scores`` are the ``(n_live,)``
        margins x_i^T beta_{lam_i} (feed ``jax.nn.sigmoid`` for
        probabilities), ``version`` the store version used for every row.

        Non-finite guard: scores cross to host here anyway (the one
        device->host hop of the serve loop), so they are checked before
        being returned. A snapshot that yields NaN/Inf is quarantined —
        the store pins back to its last-good snapshot and the batch is
        rescored against that — and only if no snapshot survives does
        :class:`NonFiniteScores` escape. Requests never see poison.

        The ``score`` span closes at the existing ``np.asarray`` host
        sync on the scores — tracing adds no extra device->host hop.
        """
        with obs_trace.span("score", rows=int(batch.n_live)) as sp:
            scores, version = self._score(batch, lams)
            sp.set(version=version)
            return scores, version

    def _score(self, batch: PackedBatch,
               lams) -> Tuple[np.ndarray, int]:
        lams = np.asarray(lams, np.float64).reshape(-1)
        if lams.shape[0] != batch.n_live:
            raise ValueError(
                f"{lams.shape[0]} lambdas for {batch.n_live} requests")
        while True:
            snap = self.store.snapshot      # one read per attempt
            if batch.p != snap.p:
                raise ValueError(
                    f"batch hashed to p={batch.p} but the store serves "
                    f"p={snap.p}")
            if batch.p_pad != snap.p_pad:
                raise ValueError(
                    f"batch feature padding {batch.p_pad} != store padding "
                    f"{snap.p_pad} — pack with pad_p_to=store.pad_p_to")
            # lambdas resolve against the snapshot actually scored with
            lam_idx = np.zeros(batch.batch_cap, np.int32)
            if batch.n_live:
                lam_idx[:batch.n_live] = snap.indices_of(lams)
            serve_delay()                   # chaos latency injection point
            scores = np.asarray(self._dispatch(batch, lam_idx, snap))
            live = scores[:batch.n_live]
            if np.all(np.isfinite(live)):
                return live, snap.version
            # rollback-and-retry: each quarantine() retires one version,
            # so the loop is bounded by the (finite) rollback chain
            if not self.store.quarantine(snap.version):
                raise NonFiniteScores(
                    f"non-finite scores from path version {snap.version} "
                    f"and no last-good snapshot left to pin to"
                )

    def _dispatch(self, batch: PackedBatch, lam_idx: np.ndarray,
                  snap: StoreSnapshot):
        mesh = self.store.mesh
        if mesh is None:
            if batch.dp != 1:
                raise ValueError(
                    f"local scoring needs dp=1 slabs, got dp={batch.dp}")
            return _score_local(
                jnp.asarray(batch.row_idx[:, 0, :]),
                jnp.asarray(batch.values[:, 0, :]),
                jnp.asarray(lam_idx), snap.betas, n_loc=batch.batch_cap)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.distributed import _data_axes, _data_extent

        if batch.dp != _data_extent(mesh):
            raise ValueError(
                f"batch dp={batch.dp} != mesh data extent "
                f"{_data_extent(mesh)} — pack with dp=store ddim")
        daxes = _data_axes(mesh)
        slab_sh = NamedSharding(mesh, P("model", daxes, None))
        fn = self._margins_for(mesh, batch.n_loc)
        # request slabs are transient placements, routed through the
        # residency module's sanctioned door (bucket-residency rule)
        from repro.data.residency import put_slab

        rows_dev, vals_dev = put_slab(batch.row_idx, batch.values, slab_sh)
        return fn(
            rows_dev, vals_dev,
            jax.device_put(lam_idx, NamedSharding(mesh, P(daxes))),
            snap.betas)
