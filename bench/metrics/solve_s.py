"""Self time of the ``restricted_solve`` spans in one path, in s."""
from bench.spans import total_self


def read(facts):
    return total_self(facts["spans"], ("restricted_solve",))
