"""Whole path's share of the chip's peak, in %.

The work is counted from the configuration's sizes alone, so it is the
same whatever implements the path: ``path_len + 1`` full passes over the
design (the lambda_max pass and one KKT certificate per point), each
reading every live byte and doing two operations per nonzero. The least
time the chip could take is the larger of operations over peak FLOP/s and
bytes over HBM bandwidth; it is divided by the wall time of a path timed
with the profiler off.
"""


def read(facts):
    cfg, gen, pk = facts["config"], facts["gen"], facts["peaks"]
    passes = int(cfg["path_len"]) + 1
    t_min = max(2.0 * gen.nnz(cfg) * passes / pk["flops_per_s"],
                gen.live_bytes(cfg) * passes / pk["hbm_bytes_per_s"])
    return 100.0 * t_min / facts["path_s"]
