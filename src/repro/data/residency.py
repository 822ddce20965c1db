"""Bucket residency: budgeted device placement of slab work buckets.

The d-GLMNET premise is data too large for one machine, yet until this
module every path solve required the *whole* padded slab layout resident
in device memory — aggregate HBM, not the dataset, was the scale
ceiling. :class:`BucketResidencyManager` makes residency an explicit,
budgeted policy over the mesh-padded work buckets that
``ShardedDesign._mesh_state`` builds:

* **resident** (no budget, or budget >= total slab bytes): every bucket
  is device-put once at construction and pinned for the design's
  lifetime — byte-identical to the pre-manager behavior.
* **streamed** (budget < total slab bytes): buckets live host-side and
  are *double-buffered* through each screened pass — bucket t+1's
  ``device_put`` is dispatched (async on the JAX dispatch stream) before
  bucket t is yielded to its Gram/SpMV work, so the host->device copy
  overlaps compute. A budgeted LRU evicts cold buckets by dropping their
  Python references (XLA frees the buffers once in-flight uses retire;
  an explicit delete would race the async dispatch).

The two modes run the *same op sequence in the same bucket order* — the
manager only changes where buckets live, never the math — which is what
makes streamed solves bit-identical to resident ones.

This module is also the **single home** of slab-bucket
``jax.device_put`` (enforced by the ``bucket-residency`` analysis rule):
transient slab placements outside the managed work buckets (restricted-
solve operands, serve request slabs) go through :func:`put_slab`, and a
serve batch's flat entry list through :func:`put_entries`.

Failure model: every put attempt consults
``repro.resilience.take_prefetch_failure`` and runs under
``retry_call`` — a transient lost bucket is retried with backoff and the
solve proceeds bit-identically; exhaustion surfaces as a typed
``RetriesExhausted`` that the path driver's ``PathProgress`` checkpoints
make resumable (drill: ``repro.launch.chaos_glm --scenario lost-bucket``).

The budget is a residency high-water target for the *managed* buckets:
because puts are dispatched ahead of compute, transiently in-flight
buffers (and unmanaged operands like restricted-solve working sets) can
briefly exceed it.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

import jax

from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.resilience.inject import InjectedFault, take_prefetch_failure
from repro.resilience.retry import retry_call


def put_slab(row_idx, values, sharding=None):
    """Device-put one transient slab pair (the sanctioned door for slab
    placements that are *not* residency-managed work buckets: restricted
    solve operands, serve request slabs). Managed buckets go through
    :class:`BucketResidencyManager` so the budget can see them."""
    if sharding is None:
        return jax.device_put(row_idx), jax.device_put(values)
    return jax.device_put(row_idx, sharding), jax.device_put(values, sharding)


def put_entries(*arrays):
    """Device-put one serve batch's flat entry arrays and lambda indices
    as one batched transfer (the door for request operands that are not
    slabs: O(nnz) bytes, never budgeted buckets). Returns the device
    arrays as a tuple, in order."""
    return tuple(jax.device_put(arrays))


@dataclass
class ResidencyCounters:
    """Mutable telemetry for one manager (all monotone)."""

    hits: int = 0          # get() served from device
    misses: int = 0        # get() had to stream the bucket in
    evictions: int = 0     # LRU drops under budget pressure
    puts: int = 0          # successful host->device bucket puts
    retries: int = 0       # put attempts that failed and were retried
    bytes_h2d: int = 0     # payload bytes moved host->device


class BucketResidencyManager:
    """Budgeted LRU residency over padded slab work buckets.

    ``buckets`` is the tuple of mesh-padded ``(row_idx, values,
    feat_idx)`` triples (host or committed arrays — the manager never
    mutates them); ``sharding`` is the slab ``NamedSharding`` every
    device copy lands in; ``budget_bytes=None`` (or a budget covering
    ``total_bytes``) selects resident mode.

    Streamed mode needs room to double-buffer: the budget must cover the
    largest *adjacent pair* of buckets (:attr:`min_budget_bytes`), else
    construction raises with the number to raise the budget to.
    """

    def __init__(self, buckets, *, sharding=None,
                 budget_bytes: Optional[int] = None,
                 retry_attempts: int = 3, retry_base_s: float = 0.05):
        self.n_buckets = len(buckets)
        self.bucket_bytes: Tuple[int, ...] = tuple(
            int(r.nbytes) + int(v.nbytes) for r, v, _ in buckets)
        self.total_bytes = sum(self.bucket_bytes)
        pairs = [self.bucket_bytes[i] + self.bucket_bytes[i + 1]
                 for i in range(self.n_buckets - 1)]
        self.min_budget_bytes = max(pairs) if pairs else (
            self.bucket_bytes[0] if self.n_buckets else 0)
        self.budget_bytes = None if budget_bytes is None else int(budget_bytes)
        self.streamed = (self.budget_bytes is not None
                         and self.budget_bytes < self.total_bytes)
        self.counters = ResidencyCounters()
        self._feat = tuple(b[2] for b in buckets)
        self._sharding = sharding
        self._retry_attempts = retry_attempts
        self._retry_base_s = retry_base_s
        self._resident: "OrderedDict[int, tuple]" = OrderedDict()
        self._resident_bytes = 0
        self._pinned: set = set()
        self._iterating = False
        if self.streamed:
            if self.budget_bytes < self.min_budget_bytes:
                raise ValueError(
                    f"device_budget_bytes={self.budget_bytes} cannot "
                    f"double-buffer these work buckets: the largest "
                    f"adjacent bucket pair is {self.min_budget_bytes} bytes "
                    f"(of {self.total_bytes} total over {self.n_buckets} "
                    f"buckets) — raise the budget to >= "
                    f"{self.min_budget_bytes}, or drop it to run resident")
            self._host = tuple((r, v) for r, v, _ in buckets)
        else:
            # resident: one put per bucket, pinned for the manager's
            # lifetime; host references dropped (no re-put ever happens)
            self._host = None
            for i, (r, v, _) in enumerate(buckets):
                self._admit(i, self._put(i, r, v))

    # -- device placement --------------------------------------------------

    def _put(self, i: int, r, v):
        """One counted, retried host->device bucket put. The injection
        consult + retry wrapper is what the lost-bucket drill drives."""
        def attempt():
            if take_prefetch_failure():
                raise InjectedFault(
                    f"injected prefetch failure (bucket {i})")
            return put_slab(r, v, self._sharding)

        def count_retry(_k, _err):
            self.counters.retries += 1

        pair = retry_call(attempt, attempts=self._retry_attempts,
                          base_delay_s=self._retry_base_s,
                          retry_on=(RuntimeError,), on_retry=count_retry)
        self.counters.puts += 1
        self.counters.bytes_h2d += self.bucket_bytes[i]
        return pair

    def _admit(self, i: int, pair) -> None:
        self._resident[i] = pair
        self._resident_bytes += self.bucket_bytes[i]

    def _ensure_room(self, need: int, keep) -> None:
        if not self.streamed:
            return
        while self._resident_bytes + need > self.budget_bytes:
            victim = next((j for j in self._resident
                           if j not in self._pinned and j not in keep), None)
            if victim is None:
                raise RuntimeError(
                    f"residency budget {self.budget_bytes} exhausted with "
                    f"every resident bucket pinned — min_budget_bytes="
                    f"{self.min_budget_bytes} should have prevented this")
            # dropping the reference is the eviction: XLA frees the
            # buffers once any in-flight compute on them retires
            self._resident.pop(victim)
            self._resident_bytes -= self.bucket_bytes[victim]
            self.counters.evictions += 1

    # -- access ------------------------------------------------------------

    def get(self, i: int):
        """The device ``(row_idx, values)`` pair for bucket ``i``,
        streaming it in (and evicting LRU cold buckets) on a miss."""
        if not 0 <= i < self.n_buckets:
            raise IndexError(f"bucket {i} out of range [0, {self.n_buckets})")
        pair = self._resident.get(i)
        if pair is not None:
            self._resident.move_to_end(i)
            self.counters.hits += 1
            return pair
        self.counters.misses += 1
        # the span brackets eviction + the (async-dispatch) re-put — on
        # the CPU fake-device mesh that is bookkeeping + memcpy, on a
        # real accelerator it is the h2d dispatch the double buffer hides
        with obs_trace.span("bucket_stream", bucket=i):
            self._ensure_room(self.bucket_bytes[i], keep={i})
            pair = self._put(i, *self._host[i])
        self._admit(i, pair)
        return pair

    def iter_buckets(self) -> Iterator[tuple]:
        """Yield ``(row_idx, values, feat_idx)`` in bucket order, with
        bucket t+1's put dispatched *before* bucket t is yielded to its
        compute — the double buffer that hides the host->device copy
        behind the Gram/SpMV work. Not reentrant (every screened pass
        fully consumes its iteration before the next starts)."""
        if self._iterating:
            raise RuntimeError(
                "bucket iteration is not reentrant — consume the previous "
                "pass before starting another")
        self._iterating = True
        try:
            for i in range(self.n_buckets):
                self._pinned = ({i, i + 1} if i + 1 < self.n_buckets
                                else {i})
                pair = self.get(i)
                if i + 1 < self.n_buckets:
                    self.get(i + 1)       # async prefetch ahead of compute
                yield pair[0], pair[1], self._feat[i]
        finally:
            self._pinned = set()
            self._iterating = False

    # -- telemetry ---------------------------------------------------------

    def register_metrics(self, registry=None, *,
                         name: str = "residency") -> None:
        """Mirror this manager's counters onto a ``repro.obs`` metrics
        registry as a lazy read-only callback. The :class:`ResidencyCounters`
        dataclass stays the single source of truth — ``stats()`` /
        ``residency_stats()`` values are bit-identical whether or not a
        registry is active. No-op when no registry is given or armed."""
        reg = obs_registry.get_registry() if registry is None else registry
        if reg is None:
            return
        reg.register_callback(name, self.stats)

    # -- introspection -----------------------------------------------------

    def resident_indices(self) -> Tuple[int, ...]:
        """Resident bucket ids in LRU order (least recent first)."""
        return tuple(self._resident)

    @property
    def resident_bytes(self) -> int:
        return self._resident_bytes

    def stats(self) -> dict:
        c = self.counters
        access = c.hits + c.misses
        return {
            "streamed": self.streamed,
            "n_buckets": self.n_buckets,
            "budget_bytes": self.budget_bytes,
            "total_bytes": self.total_bytes,
            "resident_bytes": self._resident_bytes,
            "hits": c.hits,
            "misses": c.misses,
            "evictions": c.evictions,
            "puts": c.puts,
            "retries": c.retries,
            "bytes_h2d": c.bytes_h2d,
            "hit_rate": (c.hits / access) if access else 0.0,
        }
