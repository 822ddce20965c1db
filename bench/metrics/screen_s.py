"""Self time of the screening spans in one path, in s: ``lambda_grid``,
``screen_round`` and ``kkt_check`` (``core.screening`` passes and the
host syncs that close them)."""
from bench.spans import total_self


def read(facts):
    return total_self(facts["spans"],
                      ("lambda_grid", "screen_round", "kkt_check"))
