"""The paper's "by feature" data layout (§3, Table 1).

d-GLMNET partitions the dataset by features: machine m stores
X_m = {L_j | j in S_m}, L_j = {(i, x_ij) | x_ij != 0}. The paper produces
this with a Map/Reduce pass; here the layout transformation is an explicit,
tested function pair:

* ``to_by_feature`` — CSC-like padded arrays (row_idx (p, K), values (p, K)),
  K = max nnz per feature, sentinel row = n. JAX-friendly fixed shapes; this
  is what lets webspam-scale (16.6M features, 1.2e9 nnz) fit on the mesh
  where a dense X cannot (DESIGN.md §2.3).
* ``densify_tile`` — scatter a tile of features back to a dense (n, F)
  block. The solver hot path no longer uses it (the sparse-native kernel
  suite in ``kernels/sparse_slab.py`` computes tile statistics straight
  from the slabs); it remains the oracle/interop utility.
* text round-trip of the paper's Table-1 line format for interop:
  ``feature_id (example_id:value) (example_id:value) ...``
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, TextIO, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class ByFeature:
    row_idx: jnp.ndarray     # (p, K) int32, sentinel = n for padding
    values: jnp.ndarray      # (p, K) float32
    n: int                   # number of examples

    @property
    def p(self) -> int:
        return self.row_idx.shape[0]

    @property
    def nnz(self) -> int:
        return int((self.row_idx < self.n).sum())

    def gather(self, beta, mask, cap: int):
        """Screened working set as a restricted ByFeature (see
        :func:`gather_features`). Returns ``(bf_sub, beta_sub, idx)``."""
        r, v, b, idx = gather_features(
            self.row_idx, self.values, beta, mask, cap, sentinel=self.n
        )
        return ByFeature(r, v, self.n), b, idx


def to_by_feature(X) -> ByFeature:
    """Dense (n, p) -> by-feature padded CSC (the Reduce step of paper §3)."""
    # one vectorized pass over the feature-major copy: rows come out
    # ascending within each feature, exactly the per-column nonzero order
    Xt = np.ascontiguousarray(np.asarray(X).T)
    p, n = Xt.shape
    feat, rows = np.nonzero(Xt)
    counts = np.bincount(feat, minlength=p)
    k = max(int(counts.max()) if p else 0, 1)
    rank = np.arange(feat.size) - (np.cumsum(counts) - counts)[feat]
    row_idx = np.full((p, k), n, np.int32)
    values = np.zeros((p, k), np.float32)
    row_idx[feat, rank] = rows
    values[feat, rank] = Xt[feat, rows]
    return ByFeature(jnp.asarray(row_idx), jnp.asarray(values), n)


def densify_tile(bf: ByFeature, start: int, width: int) -> jnp.ndarray:
    """Features [start, start+width) -> dense (n, width) block via scatter."""
    rows = jax.lax.dynamic_slice(bf.row_idx, (start, 0), (width, bf.row_idx.shape[1]))
    vals = jax.lax.dynamic_slice(bf.values, (start, 0), (width, bf.values.shape[1]))
    out = jnp.zeros((bf.n + 1, width), jnp.float32)  # +1 row swallows sentinels
    cols = jnp.broadcast_to(jnp.arange(width)[:, None], rows.shape)
    out = out.at[rows.reshape(-1), cols.reshape(-1)].add(vals.reshape(-1))
    return out[: bf.n]


def densify(bf: ByFeature) -> jnp.ndarray:
    return densify_tile(bf, 0, bf.p)


# ---------------------------------------------------------------------------
# Table-1 text format
# ---------------------------------------------------------------------------

def write_table1(bf: ByFeature, fh: TextIO) -> None:
    ri = np.asarray(bf.row_idx)
    vv = np.asarray(bf.values)
    for j in range(bf.p):
        live = ri[j] < bf.n
        cells = " ".join(f"({int(i)}:{float(v):.9g})" for i, v in zip(ri[j][live], vv[j][live]))
        fh.write(f"{j} {cells}\n".rstrip() + "\n")


def read_table1(fh: TextIO, n: int) -> ByFeature:
    """Parse the Table-1 format honoring the leading feature id.

    Lines may arrive in any order (a Map/Reduce shuffle gives no ordering
    guarantee); the feature id — not the line position — decides where a
    feature lands. Ids absent from the file become empty (all-sentinel)
    features; a repeated id keeps the last occurrence.
    """
    feats = {}
    for line in fh:
        parts = line.split()
        if not parts:
            continue
        j = int(parts[0])
        entries = [p.strip("()").split(":") for p in parts[1:]]
        feats[j] = ([int(i) for i, _ in entries], [float(v) for _, v in entries])
    p = max(feats) + 1 if feats else 0
    k = max((len(r) for r, _ in feats.values()), default=1) or 1
    row_idx = np.full((p, k), n, np.int32)
    values = np.zeros((p, k), np.float32)
    for j, (r, v) in feats.items():
        row_idx[j, : len(r)] = r
        values[j, : len(v)] = v
    return ByFeature(jnp.asarray(row_idx), jnp.asarray(values), n)


def partition_features(p: int, num_machines: int) -> Tuple[np.ndarray, ...]:
    """Contiguous feature blocks S_1..S_M (paper's Reduce-side partitioning)."""
    bounds = np.linspace(0, p, num_machines + 1).astype(int)
    return tuple(np.arange(bounds[i], bounds[i + 1]) for i in range(num_machines))


# ---------------------------------------------------------------------------
# Mesh slabs: the (p, DP, K) layout the distributed sparse step consumes
# ---------------------------------------------------------------------------

@dataclass
class SlabBuckets:
    """nnz-bucketed mesh slabs (the ROADMAP "slab rebalancing" layout).

    Power-law feature frequencies (webspam) make a single global slab
    capacity pad every feature to the heaviest one's nnz; here features
    are grouped into capacity classes — ``buckets[i] = (row_idx
    (p_i, DP, K_i), values, feat_idx (p_i,))`` with per-bucket ``K_i`` on
    a power-of-two ladder — so storage is O(sum_i p_i K_i) ~ O(nnz)
    instead of O(p K_max). ``feat_idx`` maps each bucket row back to the
    original feature id; the concatenated bucket order is the *permuted*
    feature axis the screened distributed path works in.

    Invariant: every slab's K axis must be *front-packed* — live slots
    first, sentinels after. ``to_slab_buckets`` guarantees this;
    hand-built instances must too, because consumers trim the K axis
    positionally (``gather_features(..., k_cap)``) and interleaved
    sentinels would silently drop live entries.
    """
    buckets: tuple                 # of (row_idx, values, feat_idx)
    n_loc: int
    p: int                         # original feature count

    @property
    def k_classes(self):
        return tuple(b[0].shape[-1] for b in self.buckets)

    @property
    def feat_order(self) -> np.ndarray:
        """Original feature ids in concatenated bucket order."""
        return np.concatenate([np.asarray(b[2]) for b in self.buckets])

    @property
    def bucket_nbytes(self) -> Tuple[int, ...]:
        """Per-bucket slab payload bytes (row_idx + values; the host-side
        ``feat_idx`` maps are excluded). This is what the residency budget
        (``repro.data.residency``) and any future heavy-feature split
        account in."""
        return tuple(int(r.nbytes) + int(v.nbytes) for r, v, _ in self.buckets)

    @property
    def nbytes(self) -> int:
        """Total slab payload bytes across buckets (sum of
        :attr:`bucket_nbytes`)."""
        return sum(self.bucket_nbytes)


def _regroup_slabs(bf: ByFeature, dp: int):
    """Shared regroup: global rows -> per-shard local rows + per-(feature,
    shard) nnz counts. Fully vectorized (p can be webspam-scale): flatten
    the live entries, key them by (feature, shard), and compute each
    entry's rank within its group from the stable sort of the keys."""
    n_loc = bf.n // dp
    ri = np.asarray(bf.row_idx)
    vv = np.asarray(bf.values)
    p = bf.p
    j_idx, k_idx = np.nonzero(ri < bf.n)
    rows = ri[j_idx, k_idx]
    vals = vv[j_idx, k_idx]
    shard = rows // max(n_loc, 1)
    group = j_idx * dp + shard
    counts = np.bincount(group, minlength=p * dp)
    order = np.argsort(group, kind="stable")
    group_sorted = group[order]
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    rank = np.arange(len(group_sorted)) - starts[group_sorted]
    jj, ss = group_sorted // dp, group_sorted % dp
    loc_rows = (rows - shard * n_loc)[order]
    loc_vals = vals[order]
    return (jj, ss, rank, loc_rows, loc_vals,
            counts.reshape(p, dp), n_loc)


def to_slabs(bf: ByFeature, dp: int):
    """Re-key a by-feature layout for ``dp`` data shards.

    Examples are split into ``dp`` contiguous shards of n_loc = n/dp rows
    each; every feature's entries are regrouped per shard with *local* row
    indices (sentinel n_loc). Returns ``(row_idx (p, dp, K'), values
    (p, dp, K'), n_loc)`` — exactly the operands of
    ``core.distributed.make_dglmnet_step_sparse`` / ``fit_distributed_sparse``
    under sharding P(model, data, None). Entries are front-packed along K
    (live slots first, then sentinels), which is what lets downstream
    consumers trim the K axis to a smaller capacity class.
    """
    if bf.n % dp:
        raise ValueError(
            f"data shard count {dp} must divide n={bf.n} (trim or pad upstream)"
        )
    jj, ss, rank, loc_rows, loc_vals, counts, n_loc = _regroup_slabs(bf, dp)
    p = bf.p
    k = max(1, int(counts.max()) if counts.size else 1)
    row_idx = np.full((p, dp, k), n_loc, np.int32)
    values = np.zeros((p, dp, k), np.float32)
    row_idx[jj, ss, rank] = loc_rows
    values[jj, ss, rank] = loc_vals
    return jnp.asarray(row_idx), jnp.asarray(values), n_loc


def k_class(k_need: int, k_max: int, *, k_min: int = 8) -> int:
    """Round a slab capacity up to its power-of-two class (min ``k_min``,
    capped at ``k_max``). Bounds the number of distinct slab shapes — and
    hence solver retraces — to O(log(K_max)); the feature-axis twin is
    ``core.screening.capacity_bucket``."""
    cap = max(k_min, 1)
    while cap < min(k_need, k_max):
        cap *= 2
    return min(cap, max(k_max, 1))


def to_slab_buckets(bf: ByFeature, dp: int, *, k_min: int = 8) -> SlabBuckets:
    """``to_slabs`` with nnz-bucketed capacities (multiple K classes).

    Features are grouped by their per-shard max nnz into power-of-two
    capacity classes; each class stores its own (p_i, dp, K_i) slab pair
    padded only to K_i. Heavy (power-law head) features no longer inflate
    every slab to the global max: storage drops from O(p K_max) to
    ~O(nnz), and the screened path solves each restricted problem at the
    smallest class that holds its active features. The returned layout
    carries its own byte accounting (:attr:`SlabBuckets.bucket_nbytes` /
    :attr:`SlabBuckets.nbytes`) — the inputs to the device-residency
    budget (``repro.data.residency``).
    """
    if bf.n % dp:
        raise ValueError(
            f"data shard count {dp} must divide n={bf.n} (trim or pad upstream)"
        )
    jj, ss, rank, loc_rows, loc_vals, counts, n_loc = _regroup_slabs(bf, dp)
    p = bf.p
    k_feat = counts.max(axis=1) if p else np.zeros(0, np.int64)  # (p,)
    k_max = max(1, int(k_feat.max()) if p else 1)
    classes = sorted({k_class(int(k), k_max, k_min=k_min) for k in k_feat})
    if not classes:
        classes = [k_class(1, 1, k_min=k_min)]
    # assign every feature the smallest class that holds it
    feat_class = np.searchsorted(np.asarray(classes), k_feat)
    buckets = []
    pos_of_feat = np.zeros(p, np.int64)
    for ci, kc in enumerate(classes):
        feats = np.flatnonzero(feat_class == ci)
        if feats.size == 0:
            continue
        pos_of_feat[feats] = np.arange(feats.size)
        row_idx = np.full((feats.size, dp, kc), n_loc, np.int32)
        values = np.zeros((feats.size, dp, kc), np.float32)
        sel = feat_class[jj] == ci
        row_idx[pos_of_feat[jj[sel]], ss[sel], rank[sel]] = loc_rows[sel]
        values[pos_of_feat[jj[sel]], ss[sel], rank[sel]] = loc_vals[sel]
        buckets.append((jnp.asarray(row_idx), jnp.asarray(values),
                        feats.astype(np.int64)))
    return SlabBuckets(buckets=tuple(buckets), n_loc=n_loc, p=p)


def _trim_k(arr, k_cap: int, fill):
    """Slice (or pad) the trailing slab-capacity axis to ``k_cap``. Safe
    because slab entries are front-packed (live slots first)."""
    k = arr.shape[-1]
    if k_cap >= k:
        pad = [(0, 0)] * (arr.ndim - 1) + [(0, k_cap - k)]
        return jnp.pad(arr, pad, constant_values=fill) if k_cap > k else arr
    return jax.lax.slice_in_dim(arr, 0, k_cap, axis=arr.ndim - 1)


def gather_features(row_idx, values, beta, mask, cap: int, *, sentinel: int,
                    k_cap: Optional[int] = None):
    """Feature-axis gather of the screened working set into slab form.

    ``row_idx``/``values`` are feature-major — ``(p, K)`` (single ByFeature)
    or ``(p, DP, K)`` (mesh slabs); selection happens on axis 0 only, so the
    restricted problem stays in slab form end-to-end (no densification).
    Returns ``(row_idx_sub, values_sub, beta_sub, idx)`` with ``idx`` of
    shape ``(cap,)`` carrying sentinel ``p`` for padding; padded features are
    all-sentinel/zero slabs, so their coordinates provably stay at zero and
    the restricted solve equals the masked full solve. On a mesh this gather
    *is* the active-set reshard: the working set's slabs land back in a
    capacity-bucketed P(model) layout.

    ``k_cap`` additionally trims the slab-capacity axis to the active
    set's own class (front-packed entries make the slice exact): a solve
    whose working set holds only light features stops paying the heavy
    (power-law head) features' global K — the second half of the ROADMAP
    slab-rebalancing item, and what drops restricted solves into the
    sparse-native kernel regime.
    """
    from repro.core.screening import pack_indices

    idx = pack_indices(mask, cap)
    row_idx_sub = jnp.take(row_idx, idx, axis=0, mode="fill",
                           fill_value=sentinel)
    values_sub = jnp.take(values, idx, axis=0, mode="fill", fill_value=0.0)
    beta_sub = jnp.take(beta, idx, mode="fill", fill_value=0.0)
    if k_cap is not None:
        row_idx_sub = _trim_k(row_idx_sub, k_cap, sentinel)
        values_sub = _trim_k(values_sub, k_cap, 0.0)
    return row_idx_sub, values_sub, beta_sub, idx


def take_buckets_iter(buckets, n_loc: int, idx, k_cap: int):
    """Core of :func:`take_features_buckets` over *any* iterable of
    ``(row_idx, values, ...)`` buckets.

    Resident tuples and the streamed iteration of
    :class:`repro.data.residency.BucketResidencyManager` feed the exact
    same op sequence through here — same bucket order, same
    take/trim/where-combine — which is what keeps streamed gathers
    bit-identical to resident ones.
    """
    rows_sub = vals_sub = None
    off = 0
    for bucket in buckets:
        r_b, v_b = bucket[0], bucket[1]
        p_b = r_b.shape[0]
        ok = jnp.logical_and(idx >= off, idx < off + p_b)
        li = jnp.where(ok, idx - off, p_b)
        rb = jnp.take(r_b, li, axis=0, mode="fill", fill_value=n_loc)
        vb = jnp.take(v_b, li, axis=0, mode="fill", fill_value=0.0)
        rb = _trim_k(rb, k_cap, n_loc)
        vb = _trim_k(vb, k_cap, 0.0)
        if rows_sub is None:
            rows_sub, vals_sub = rb, vb
        else:
            sel = ok[:, None, None]
            rows_sub = jnp.where(sel, rb, rows_sub)
            vals_sub = jnp.where(sel, vb, vals_sub)
        off += p_b
    return rows_sub, vals_sub


def take_features_buckets(slabs: "SlabBuckets", idx, k_cap: int):
    """Explicit-index feature take over an nnz-bucketed layout.

    ``idx`` holds concatenated-bucket-axis positions (sentinel >= the
    concatenated extent marks padding). Each bucket is taken with the
    indices remapped into its own range (out-of-range -> all-sentinel
    fill) and trimmed/padded to ``k_cap``; since every index lands in
    exactly one bucket, a where-combine assembles a single
    (len(idx), DP, k_cap) slab pair.
    """
    return take_buckets_iter(slabs.buckets, slabs.n_loc, idx, k_cap)


def gather_features_buckets(slabs: "SlabBuckets", beta, mask, cap: int,
                            k_cap: int):
    """:func:`gather_features` over an nnz-bucketed layout.

    ``mask``/``beta`` live on the concatenated (bucket-permuted, padded)
    feature axis. The packed working-set indices are taken bucket-by-bucket
    (:func:`take_features_buckets`) into the single restricted (cap, DP,
    k_cap) slab pair the solver consumes.
    """
    from repro.core.screening import pack_indices

    idx = pack_indices(mask, cap)
    beta_sub = jnp.take(beta, idx, mode="fill", fill_value=0.0)
    rows_sub, vals_sub = take_features_buckets(slabs, idx, k_cap)
    return rows_sub, vals_sub, beta_sub, idx


def scatter_features(beta_sub, idx, p: int):
    """Inverse of :func:`gather_features`: restricted solution -> full beta.
    The coefficient scatter is layout-agnostic, so this is exactly the dense
    column scatter."""
    from repro.core.screening import scatter_columns

    return scatter_columns(beta_sub, idx, p)
