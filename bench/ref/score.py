"""Plain reference scorer for hashed documents.

Each document's tokens hash to feature ids by CRC-32 of their UTF-8 bytes
modulo ``p``; tokens that collide add their values. A document's score at
path point ``l`` is ``sum_j beta[l, j] v_j``, summed in float64. It imports
nothing of the program.

A served score ``s`` is judged by its gap ``|s - ref| / sum_j |beta[l, j]
v_j|`` (0 where the document touches no coefficient and ``s == ref ==
0``); the widest gap over the documents is the reading. With
``round_bf16`` the reference itself is computed from coefficients and
values rounded to bfloat16 and its gap taken against the float64 one: the
control.
"""
from __future__ import annotations

import zlib

import numpy as np


def encode(doc: dict, p: int):
    """Feature ids and summed values of one document."""
    idx = np.fromiter((zlib.crc32(t.encode("utf-8")) % p for t in doc),
                      np.int64, len(doc))
    vals = np.fromiter(doc.values(), np.float64, len(doc))
    uniq, inv = np.unique(idx, return_inverse=True)
    return uniq, np.bincount(inv, weights=vals)


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float64."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def widest_gap(docs, lam_idx, served, betas, *, round_bf16: bool = False
               ) -> float:
    """Largest relative gap of ``served`` (or of the bfloat16 control)
    from the float64 reference over ``docs``."""
    p = betas.shape[1]
    worst = 0.0
    for doc, l, s in zip(docs, lam_idx, served):
        idx, vals = encode(doc, p)
        coef = betas[int(l), idx].astype(np.float64)
        ref = float(np.dot(coef, vals))
        scale = float(np.dot(np.abs(coef), np.abs(vals)))
        if round_bf16:
            s = float(np.dot(_bf16(coef), _bf16(vals)))
        gap = abs(float(s) - ref)
        worst = max(worst, gap / scale if scale > 0 else
                    (0.0 if gap == 0 else float("inf")))
    return worst
