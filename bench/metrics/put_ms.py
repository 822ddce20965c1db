"""Mean ``put`` span per scored batch, in ms: placing the packed slabs and
the lambda indices on the device, inside ``score`` (``serve.scoring``)."""
from bench.spans import mean_ms


def read(facts):
    return mean_ms(facts["spans"], "put")
