"""Mean bytes placed on the device per scored batch, in MB (1e6 bytes): the
``put`` spans' ``bytes``, the packed slabs and lambda indices
(``serve.scoring``)."""


def read(facts):
    sizes = [sp["args"]["bytes"] for sp in facts["spans"]
             if sp["name"] == "put" and "bytes" in sp["args"]]
    return sum(sizes) / len(sizes) / 1e6 if sizes else None
