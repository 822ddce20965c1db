"""repro.serve — batched online scoring of the certified reg path.

The d-GLMNET training side hands over a typed ``PathResult`` (the whole
certified regularization path); this package serves it:

* :class:`PathStore` — the ``(L, p)`` coefficient stack device-resident
  (replicated locally, P(model)-feature-sharded on a mesh), versioned,
  hot-swappable without dropping in-flight batches;
* :mod:`~repro.serve.ingest` — deterministic hashed sparse-feature
  ingestion packing request batches into flat entry lists (request row,
  feature, value per nonzero: O(nnz), whatever the width), with the
  training kernels' by-feature slabs built from them on demand for the
  mesh branch;
* :class:`RequestBatcher` — accumulate/drain batching with power-of-two
  shape classes, a bounded pending queue (:class:`Overloaded` admission
  control) and per-request deadlines shed at drain;
* :class:`PathScorer` — one jitted dispatch per batch (locally
  ``entry_path_spmv`` over the entry list, on a mesh ``slab_path_spmv``
  over the slabs), each request row picking its own lambda operating
  point on device; scores bit-identical to
  ``LogisticL1.decision_function`` on the CPU and through the mesh, equal
  to float32 rounding on a TPU's local path. Non-finite
  scores quarantine the published version and pin the store back to its
  last-good snapshot (:class:`NonFiniteScores` only if that fails too).

Typed failure surface: :class:`~repro.serve.ingest.InvalidRequest`
(garbage in), :class:`Overloaded` (queue full), :class:`NonFiniteScores`
(poisoned coefficients) — the serve loop counts each instead of dying.

Entry points: ``python -m repro.launch.serve_glm`` (serving),
``python -m repro.launch.chaos_glm`` (fault drills).
"""
from repro.serve.batcher import (  # noqa: F401
    Overloaded,
    RequestBatcher,
    batch_capacity,
)
from repro.serve.ingest import (  # noqa: F401
    InvalidRequest,
    PackedBatch,
    encode_request,
    hash_token,
    k_capacity,
    pack_requests,
)
from repro.serve.scoring import (  # noqa: F401
    NonFiniteScores,
    PathScorer,
    make_path_margins,
)
from repro.serve.store import PathStore, StoreSnapshot  # noqa: F401
