"""Mean ``score`` span per batch, in ms: host->device copy of the packed
slab, ``slab_path_spmv`` and the fetch of the scores (``serve.scoring``)."""
from bench.spans import mean_ms


def read(facts):
    return mean_ms(facts["spans"], "score")
