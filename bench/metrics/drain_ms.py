"""Mean ``drain`` span per batch that drained requests, in ms
(``serve.batcher``, which packs the batch with ``pack_requests``)."""
from bench.spans import mean_ms


def read(facts):
    return mean_ms(facts["spans"], "drain", live_only=True)
