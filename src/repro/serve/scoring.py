"""Batched path scoring: one jitted dispatch per request batch.

Locally the scoring step is ``kernels.ops.entry_path_spmv`` over a
:class:`~repro.serve.ingest.PackedBatch`'s entry list: each nonzero
gathers its coefficient from the store's stacked ``(L, p)`` path at its
row's operating point, and each row sums its entries — O(nnz) device
work and host->device bytes per batch, whatever the feature width. On a
mesh it is the same ``shard_map`` shape as
``core.distributed.make_slab_margins`` over the batch's by-feature slabs
(feature shards run ``kernels.ops.slab_path_spmv``, one psum over
``model`` assembles the scores) with the beta *stack* left P(model)-
sharded in place. Either way exactly one program launches per batch and
only the ``(batch,)`` scores travel to host.

A batch whose rows all request lambda ``l`` scores bit-identically to
``LogisticL1.decision_function(design, beta=path[l])`` on the same
batch's slabs through the mesh, and locally on the CPU, where the entry
path's scatter-add sums each row's terms in the slab kernel's order. On
the TPU the scatter's add order is the compiler's, so the local path and
the slab kernel agree to float32 rounding.
"""
from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Tuple

import jax
import numpy as np

from repro.kernels import ops as kops
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.resilience import serve_delay
from repro.serve.ingest import ENTRY_FLOOR_PER_ROW, PackedBatch
from repro.serve.store import PathStore, StoreSnapshot


class NonFiniteScores(RuntimeError):
    """Every published snapshot the scorer tried produced NaN/Inf scores
    for this batch. Raised only after the store has been pinned back to
    its last-good snapshot (when one existed) and the batch retried — so
    a caller seeing this knows rollback did not help and the *batch*
    itself is suspect."""


@jax.jit
def _score_local(entry_row, entry_feat, entry_val, lam_idx, betas):
    return kops.entry_path_spmv(entry_row, entry_feat, entry_val, lam_idx,
                                betas)


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
        sync on the scores — tracing adds no extra device->host hop. Its
        children, each tagged with the batch's ``batch_id``, split it:
        ``put`` places the batch's operands and ``lam_idx`` on the device
        (``bytes`` placed; locally also ``entries``, the live nonzeros,
        and ``slots``, the entry class), ``launch`` enqueues the scoring
        program (and holds any ``compile``), ``fetch`` is that
        ``np.asarray``: the device's run and the copy back. Its self time
        is the snapshot read, lambda resolution and the finite check.
        Locally, a batch whose entry class is above the floor
        (``ENTRY_FLOOR_PER_ROW`` per row) counts on the
        ``serve.entry_class_over_floor`` counter: its shape may compile.
        """
        with obs_trace.span("score", rows=int(batch.n_live),
                            batch=batch.batch_id) as sp:
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
            out = self._dispatch(batch, lam_idx, snap)
            with obs_trace.span("fetch", batch=batch.batch_id):
                scores = np.asarray(out)
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
        """Place the batch on the device and enqueue its scoring program;
        returns the device scores."""
        with obs_trace.span("put", batch=batch.batch_id) as sp:
            fn, args = self._place(batch, lam_idx)
            sp.set(bytes=sum(a.nbytes for a in args))
            if self.store.mesh is None:
                slots = int(batch.entry_row.shape[0])
                sp.set(entries=batch.n_entries, slots=slots)
                if slots > ENTRY_FLOOR_PER_ROW * batch.batch_cap:
                    obs_registry.counter("serve.entry_class_over_floor").inc()
        with obs_trace.span("launch", batch=batch.batch_id):
            return fn(*args, snap.betas)

    def _place(self, batch: PackedBatch, lam_idx: np.ndarray):
        """``(program, device operands)`` of the batch's scoring step:
        locally the entry list, on a mesh the slabs."""
        mesh = self.store.mesh
        if mesh is None:
            # request entries go through the residency module's door, as
            # the mesh branch's slabs do (bucket-residency rule)
            from repro.data.residency import put_entries

            return _score_local, put_entries(
                batch.entry_row, batch.entry_feat, batch.entry_val, lam_idx)
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
        return fn, (rows_dev, vals_dev,
                    jax.device_put(lam_idx, NamedSharding(mesh, P(daxes))))
