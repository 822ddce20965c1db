"""Mean ``encode`` span per request, in ms (hashing at submit,
``serve.ingest``)."""
from bench.spans import mean_ms


def read(facts):
    return mean_ms(facts["spans"], "encode")
