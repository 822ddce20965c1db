"""95th percentile of due time -> score over the traced window's
requests, in ms (a miss counts at the top)."""


def read(facts):
    return facts["serve_p95_ms"]
