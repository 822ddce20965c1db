"""Mean wait of a request in the batcher's queue, submit to drain, in ms:
the ``drain`` spans' summed ``wait_s`` over their summed ``drained``
(``serve.batcher``)."""


def read(facts):
    drains = [sp["args"] for sp in facts["spans"]
              if sp["name"] == "drain" and "wait_s" in sp["args"]]
    n = sum(a["drained"] for a in drains)
    return 1e3 * sum(a["wait_s"] for a in drains) / n if n else None
