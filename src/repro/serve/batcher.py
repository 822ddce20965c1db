"""Request batching for the serving loop.

``RequestBatcher`` accumulates live requests (hashed-token feature maps +
a requested lambda each) and drains them as one :class:`PackedBatch` per
scoring dispatch. Two shape-bounding rules keep the compiled-program count
small over a serving process's lifetime:

* the batch extent is quantized to power-of-two capacity classes
  (:func:`batch_capacity`) up to ``max_batch``, and each capacity's
  entry list to power-of-two classes floored per row
  (:func:`~repro.serve.ingest.entry_capacity`);
* hashing/encoding happens at ``submit`` time (spreading the host work
  across arrivals), packing at ``drain`` time (one O(nnz) concatenation).

The queue is *bounded*: ``max_pending`` caps admission (``submit`` raises
:class:`Overloaded` instead of growing without limit under a stalled
drainer), and each request carries an optional deadline on an injectable
monotonic clock — expired requests are shed at drain time rather than
scored late. Rejections and sheds are counted in :attr:`RequestBatcher.
stats` so the serve loop can export backpressure telemetry instead of
dying by memory or serving answers nobody is waiting for.

Observability rides the same path without changing it: every request
records its submit timestamp, and the serve loop's :meth:`RequestBatcher.
mark_scored` call (right after the scorer hands back host scores) feeds
a submit->score ``serve.latency_s`` histogram on the active ``repro.obs``
registry, with the live queue depth a lazy callback. The legacy
:attr:`RequestBatcher.stats` dict is bit-identical with or without a
registry — it is mirrored read-only, never rewritten. Under a tracer,
``encode`` carries the request's submit sequence number (``req``);
``drain`` carries the batch's sequence number (``batch``), the first and
last ``req`` it took (the queue is FIFO, so with one submitting thread
it took every queued, unexpired request numbered in between), how many
it took (``drained``) and their summed queue wait on the batcher's clock
(``wait_s``); its child ``pack`` and the scorer's spans carry the same
``batch``.

Lambdas stay raw floats until scoring: ``PathScorer`` resolves them
against the snapshot it scores with, so a hot-swap that re-grids the path
re-resolves naturally instead of serving stale indices.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.serve.ingest import InvalidRequest, PackedBatch, Request, \
    encode_request, pack_requests


class Overloaded(RuntimeError):
    """The batcher's pending queue is at ``max_pending``. Callers should
    shed the request (count it, tell the client to retry) — admission
    control is the bound that keeps a stalled drainer from turning into
    unbounded host memory growth."""


def _check_pow2(name: str, value: int) -> None:
    if value < 1 or (value & (value - 1)):
        raise ValueError(
            f"{name} must be a power of two >= 1 (capacity classes are "
            f"power-of-two so the compiled-shape count stays O(log "
            f"max_batch)), got {value}"
        )


def batch_capacity(b: int, *, b_min: int = 8, b_max: int = 4096) -> int:
    """Power-of-two batch capacity class covering ``b`` rows (clamped to
    ``[b_min, b_max]``) — bounds the distinct scoring-program batch shapes
    to O(log max_batch).

    ``b_min``/``b_max`` must themselves be powers of two: a non-pow2
    floor (say 10) would silently yield 10/20/40/... classes and defeat
    the compiled-shape bound the docstring promises.
    """
    _check_pow2("b_min", b_min)
    _check_pow2("b_max", b_max)
    if b_min > b_max:
        raise ValueError(f"b_min={b_min} exceeds b_max={b_max}")
    cap = b_min
    while cap < min(b, b_max):
        cap *= 2
    return cap


class RequestBatcher:
    """Thread-safe accumulate/drain bridge between request arrival and the
    batched scoring dispatch.

    ``dp``/``pad_p_to`` fix the packed slab geometry (pass the serving
    store's mesh data extent and ``store.pad_p_to``; the defaults are the
    local single-device geometry). ``max_batch`` caps one drain — leftover
    requests stay queued for the next.

    Bounded-queue knobs:

    * ``max_pending`` — admission cap; ``submit`` raises
      :class:`Overloaded` when the queue is full.
    * ``default_ttl_s`` — deadline applied to requests submitted without
      an explicit ``deadline_s`` (``None`` = no deadline).
    * ``clock`` — monotonic time source (injectable so tests and the
      chaos harness can expire requests deterministically).
    """

    def __init__(self, p: int, *, max_batch: int = 256, dp: int = 1,
                 pad_p_to: int = 1, k_min: int = 8,
                 max_pending: int = 4096,
                 default_ttl_s: Optional[float] = None,
                 clock=time.monotonic):
        _check_pow2("max_batch", max_batch)
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.p = p
        self.max_batch = max_batch
        self.dp = dp
        self.pad_p_to = pad_p_to
        self.k_min = k_min
        self.max_pending = max_pending
        self.default_ttl_s = default_ttl_s
        self.clock = clock
        self._lock = threading.Lock()
        # (encoded, lam, expiry-on-self.clock-or-None, submit-ts, req)
        # per pending request; the submit timestamp feeds the submit->score
        # latency histogram and the queue wait, the submit sequence number
        # the trace, and neither is part of the legacy stats surface
        self._pending: List[
            Tuple[Tuple[np.ndarray, np.ndarray], float, Optional[float],
                  float, int]
        ] = []
        self._reqs = itertools.count()
        self._batches = itertools.count()
        self._stats = {"submitted": 0, "rejected_overload": 0,
                       "rejected_invalid": 0, "shed_expired": 0,
                       "drained": 0}
        # submit timestamps of the most recent drain, waiting for the
        # serve loop to confirm the batch was scored (mark_scored)
        self._last_drained_ts: List[float] = []
        self.register_metrics()

    def submit(self, request: Request, lam: float, *,
               deadline_s: Optional[float] = None) -> None:
        """Enqueue one request (hashed + encoded immediately).

        ``deadline_s`` is a time-to-live on the batcher's clock (falls
        back to ``default_ttl_s``); a request still queued past it is shed
        at the next drain. Raises :class:`~repro.serve.ingest.
        InvalidRequest` on garbage input and :class:`Overloaded` when the
        queue is at ``max_pending`` — both counted before raising.
        """
        req = next(self._reqs)
        try:
            with obs_trace.span("encode", req=req):
                enc = encode_request(request, self.p)
            idx = enc[0]
            if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= self.p):
                raise InvalidRequest(
                    f"hashed index out of range [0, {self.p})"
                )
        except InvalidRequest:
            with self._lock:
                self._stats["rejected_invalid"] += 1
            raise
        now = self.clock()
        ttl = self.default_ttl_s if deadline_s is None else deadline_s
        expiry = None if ttl is None else now + float(ttl)
        with self._lock:
            if len(self._pending) >= self.max_pending:
                self._stats["rejected_overload"] += 1
                raise Overloaded(
                    f"pending queue full ({self.max_pending} requests): "
                    f"drain is not keeping up — shed and retry with backoff"
                )
            self._pending.append((enc, float(lam), expiry, now, req))
            self._stats["submitted"] += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._pending)

    @property
    def stats(self) -> dict:
        """Counter snapshot (submitted / rejected_overload /
        rejected_invalid / shed_expired / drained) for telemetry."""
        with self._lock:
            return dict(self._stats)

    def drain(self) -> Tuple[PackedBatch, np.ndarray]:
        """Pack up to ``max_batch`` queued requests into one batch.

        Expired requests (deadline passed on the batcher's clock) are shed
        first — counted, never packed: scoring them would spend a dispatch
        on answers nobody is waiting for. Returns ``(batch, lams)``;
        ``lams[i]`` belongs to batch row ``i``. An empty queue drains to
        an all-padding batch (``n_live == 0``).
        """
        with obs_trace.span("drain") as sp:
            now = self.clock()
            with self._lock:
                live = [e for e in self._pending
                        if e[2] is None or e[2] > now]
                self._stats["shed_expired"] += len(self._pending) - len(live)
                take, self._pending = (live[:self.max_batch],
                                       live[self.max_batch:])
                self._stats["drained"] += len(take)
                self._last_drained_ts = [e[3] for e in take]
                batch_id = next(self._batches)
            encoded = [e[0] for e in take]
            lams = np.asarray([e[1] for e in take], np.float64)
            cap = batch_capacity(max(len(encoded), 1), b_max=self.max_batch)
            cap += (-cap) % max(self.dp, 1)
            with obs_trace.span("pack", batch=batch_id):
                batch = pack_requests(encoded, self.p, batch_cap=cap,
                                      dp=self.dp, pad_p_to=self.pad_p_to,
                                      k_min=self.k_min, batch_id=batch_id)
            sp.set(batch=batch_id, drained=len(take),
                   wait_s=sum((now - e[3] for e in take), 0.0))
            if take:
                sp.set(req=[take[0][4], take[-1][4]])
        return batch, lams

    def mark_scored(self) -> int:
        """Record submit->score latency for the most recently drained
        batch into the ``serve.latency_s`` histogram on the active
        metrics registry. The serve loop calls this right after the
        scorer returns host scores (the existing host sync) — the
        observation costs one clock read per request and is a no-op
        (beyond that) when no registry is active. Returns how many
        requests were marked; calling twice without a new drain is a
        harmless zero."""
        with self._lock:
            ts, self._last_drained_ts = self._last_drained_ts, []
        if not ts:
            return 0
        hist = obs_registry.histogram("serve.latency_s")
        now = self.clock()
        for t in ts:
            hist.observe(now - t)
        return len(ts)

    def register_metrics(self, registry=None) -> None:
        """Mirror the legacy :attr:`stats` dict and the live queue depth
        onto a ``repro.obs`` metrics registry as lazy read-only
        callbacks. ``_stats`` stays the single source of truth — its
        values are bit-identical whether or not a registry is active.
        Called automatically at construction (no-op when no registry is
        armed); call again to attach to a later-activated registry."""
        reg = obs_registry.get_registry() if registry is None else registry
        if reg is None:
            return
        reg.register_callback("serve.batcher", lambda: self.stats)
        reg.register_callback("serve.queue",
                              lambda: {"depth": len(self)})
