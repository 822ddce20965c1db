"""Mean ``fetch`` span per scored batch, in ms: the wait for the scores and
their copy to the host, inside ``score`` (``serve.scoring``)."""
from bench.spans import mean_ms


def read(facts):
    return mean_ms(facts["spans"], "fetch")
