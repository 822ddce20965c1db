"""Hashed sparse-feature ingestion: live requests -> per-batch entry lists.

Online traffic arrives as sparse token->value maps over an unbounded
vocabulary; the fitted model lives on a fixed ``p``-dimensional feature
axis. The bridge is the classic hashing trick, made *deterministic* so a
request scores identically across processes and restarts:

* :func:`hash_token` is CRC-32 (not Python's per-process-salted ``hash``),
  so ``token -> index`` is stable across interpreter launches;
* colliding tokens have their values **summed in sorted-token order**
  (:func:`encode_request`), so the collided value is independent of the
  caller's dict insertion order;
* exact-zero values are dropped at encode time — an all-zero request packs
  identically to an empty one (both have no entries and score 0).

:func:`pack_requests` then packs a batch of encoded requests into a flat
**entry list**: three ``(N,)`` arrays holding each nonzero's request row,
hashed feature and value, in request order and ascending by feature
within a request, padded to an entry-capacity class
(:func:`entry_capacity`) with row ``batch_cap`` entries. Packing is a
concatenation — O(nnz), whatever the feature width — and local scoring
is one ``kernels.ops.entry_path_spmv`` dispatch over those entries. The
mesh scoring branch consumes the by-feature ``(p_pad, DP, K)`` slab
layout of the training kernels (paper Table 1, request rows playing the
example axis); :class:`PackedBatch` builds those slabs from the entries
on first access only. Shapes are quantized (power-of-two batch and entry
classes) so a serving process compiles a handful of programs, not one
per batch.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from functools import cached_property
from typing import Iterable, Mapping, Sequence, Tuple, Union

import numpy as np

Request = Union[Mapping[str, float], Iterable[Tuple[str, float]]]


class InvalidRequest(ValueError):
    """A request that can never score correctly: non-finite feature
    values, or hashed indices outside the store's feature axis. Typed (a
    ``ValueError`` subclass, so pre-existing handlers still catch it) so
    the serve loop can count rejections instead of packing garbage."""


def hash_token(token: str, p: int) -> int:
    """Deterministic token -> feature index in [0, p): CRC-32 of the
    UTF-8 bytes, reduced mod ``p``. Stable across processes (unlike
    builtin ``hash``, which is salted per interpreter)."""
    return zlib.crc32(token.encode("utf-8")) % p


def encode_request(request: Request, p: int) -> Tuple[np.ndarray, np.ndarray]:
    """One request -> sorted ``(idx, val)`` arrays on the hashed axis.

    Colliding tokens sum in sorted-token order (determinism under dict
    reordering); exact-zero accumulated values are dropped so empty and
    all-zero requests encode identically (no live slots)."""
    items = request.items() if isinstance(request, Mapping) else request
    acc: dict = {}
    for token, value in sorted(items, key=lambda kv: kv[0]):
        v = float(value)
        if not math.isfinite(v):
            raise InvalidRequest(
                f"non-finite value {v!r} for token {token!r}: refusing to "
                f"encode (a single NaN would poison the whole scoring batch)"
            )
        j = hash_token(token, p)
        acc[j] = acc.get(j, 0.0) + v
    idx = np.asarray(sorted(j for j in acc if acc[j] != 0.0), np.int64)
    val = np.asarray([acc[j] for j in idx], np.float32)
    return idx, val


def k_capacity(k_need: int, *, k_min: int = 8) -> int:
    """Power-of-two slab-capacity class (the serving twin of
    ``data.byfeature.k_class``, with no global K ceiling): bounds the
    number of distinct compiled scoring shapes to O(log K)."""
    cap = max(k_min, 1)
    while cap < k_need:
        cap *= 2
    return cap




#: entry-capacity floor per request row: a batch's entry list holds at
#: least this many entries per row of its capacity, so traffic averaging
#: fewer nonzeros per request (RCV1 stories: 74) meets one entry class
#: per batch capacity, and one compiled scoring program each
ENTRY_FLOOR_PER_ROW = 256


def entry_capacity(nnz: int, batch_cap: int) -> int:
    """Entry-list class of a batch of ``nnz`` nonzeros: the power of two
    covering ``nnz``, floored at ``ENTRY_FLOOR_PER_ROW * batch_cap``. A
    class above the floor is a further scoring shape to compile."""
    return max(k_capacity(nnz, k_min=1), ENTRY_FLOOR_PER_ROW * batch_cap)


@dataclass(frozen=True)
class PackedBatch:
    """A request batch as an entry list, with slabs built on demand.

    ``entry_row``/``entry_feat``/``entry_val`` are ``(N,)`` arrays, one
    entry per nonzero: its request row, hashed feature and value, in
    request order and ascending by feature within a request. The first
    ``n_entries`` are live; the rest pad ``N`` to an
    :func:`entry_capacity` class with row ``batch_cap`` (the sentinel),
    feature 0 and value 0. Rows >= ``n_live`` are padding requests (no
    entries; they score 0 and are trimmed before scores leave the
    scorer). ``batch_id`` is the draining batcher's sequence number, the
    ``batch`` argument of every trace span the batch passes through (-1
    for a batch packed outside a batcher).

    ``row_idx``/``values`` are the same batch as ``(p_pad, DP, K)``
    by-feature slabs whose "examples" are the request rows, split into
    ``DP`` contiguous shards of ``n_loc = batch_cap // DP`` local rows
    (sentinel ``n_loc``) — the operand layout of
    ``core.distributed.make_slab_margins`` and of the mesh scoring step.
    They cost O(p_pad) host memory and are built on first access only.
    """

    entry_row: np.ndarray        # (N,) int32, sentinel batch_cap
    entry_feat: np.ndarray       # (N,) int32
    entry_val: np.ndarray        # (N,) float32
    n_entries: int               # live entries (the first n_entries)
    n_live: int                  # real requests in the batch
    batch_cap: int               # padded batch extent (= DP * n_loc)
    p: int                       # original (unpadded) feature count
    dp: int = 1                  # data shards of the slab layout
    pad_p_to: int = 1            # feature-axis alignment of the slabs
    k_min: int = 8               # floor of the slabs' K class
    batch_id: int = -1

    @property
    def n_loc(self) -> int:
        return self.batch_cap // max(self.dp, 1)

    @property
    def p_pad(self) -> int:
        return self.p + (-self.p) % max(self.pad_p_to, 1)

    @property
    def row_idx(self) -> np.ndarray:
        """``(p_pad, DP, K)`` int32 slab of local request rows."""
        return self._slabs[0]

    @property
    def values(self) -> np.ndarray:
        """``(p_pad, DP, K)`` float32 slab of values."""
        return self._slabs[1]

    @cached_property
    def _slabs(self) -> Tuple[np.ndarray, np.ndarray]:
        """The entries regrouped by (feature, shard), front-packed (live
        slots first, rows ascending within a feature) — the same stable-
        sort construction as ``data.byfeature._regroup_slabs``, so the
        slabs carry the training layout's front-packing invariant."""
        dp, n_loc, n = max(self.dp, 1), self.n_loc, self.n_entries
        rows = self.entry_row[:n].astype(np.int64)
        feats = self.entry_feat[:n].astype(np.int64)
        vals = self.entry_val[:n]
        shard = rows // max(n_loc, 1)
        loc = rows - shard * n_loc
        # rank of each entry within its (feature, shard) group
        group = feats * dp + shard
        counts = np.bincount(group, minlength=self.p * dp)
        order = np.argsort(group, kind="stable")
        starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
        rank = np.arange(order.size) - starts[group[order]]

        k = k_capacity(int(counts.max()) if counts.size else 1,
                       k_min=self.k_min)
        row_idx = np.full((self.p_pad, dp, k), n_loc, np.int32)
        values = np.zeros((self.p_pad, dp, k), np.float32)
        g = group[order]
        row_idx[g // dp, g % dp, rank] = loc[order]
        values[g // dp, g % dp, rank] = vals[order]
        return row_idx, values


def pack_requests(
    encoded: Sequence[Tuple[np.ndarray, np.ndarray]],
    p: int,
    *,
    batch_cap: int = None,
    dp: int = 1,
    pad_p_to: int = 1,
    k_min: int = 8,
    batch_id: int = -1,
) -> PackedBatch:
    """Pack encoded requests into a :class:`PackedBatch`.

    ``batch_cap`` (default: the batch size rounded up to ``dp``) fixes the
    padded request extent; the entry list is padded to
    ``entry_capacity(nnz, batch_cap)``. ``dp``, ``pad_p_to`` (mesh stores
    pass ``model_dim * tile`` so the slab partition lines up with the
    P(model)-sharded coefficient stack) and ``k_min`` (the floor of the
    power-of-two K class) shape the slabs built on demand; ``batch_id``
    tags the batch for tracing. The work is O(nnz): a concatenation of
    the requests' entries, whatever ``p``.
    """
    b = len(encoded)
    if batch_cap is None:
        batch_cap = max(b, 1)
    batch_cap += (-batch_cap) % max(dp, 1)
    if b > batch_cap:
        raise ValueError(f"{b} requests exceed batch_cap={batch_cap}")
    if batch_cap % dp:
        raise ValueError(f"dp={dp} must divide batch_cap={batch_cap}")

    if b:
        feats = np.concatenate([idx for idx, _ in encoded])
        vals = np.concatenate([val for _, val in encoded])
        lengths = [len(idx) for idx, _ in encoded]
    else:
        feats, vals, lengths = np.zeros(0, np.int64), np.zeros(0), []
    if feats.size and (feats.min() < 0 or feats.max() >= p):
        raise InvalidRequest(f"hashed index out of range [0, {p})")

    nnz = int(feats.size)
    n = entry_capacity(nnz, batch_cap)
    entry_row = np.full(n, batch_cap, np.int32)
    entry_row[:nnz] = np.repeat(np.arange(b, dtype=np.int32), lengths)
    entry_feat = np.zeros(n, np.int32)
    entry_feat[:nnz] = feats
    entry_val = np.zeros(n, np.float32)
    entry_val[:nnz] = vals
    return PackedBatch(entry_row=entry_row, entry_feat=entry_feat,
                       entry_val=entry_val, n_entries=nnz, n_live=b,
                       batch_cap=batch_cap, p=p, dp=dp, pad_p_to=pad_p_to,
                       k_min=k_min, batch_id=batch_id)
