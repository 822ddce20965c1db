"""Mean ``pack`` span per drain that drained requests, in ms:
``pack_requests`` filling the batch's slabs inside ``drain``
(``serve.batcher``)."""


def read(facts):
    spans = facts["spans"]
    live = {sp["sid"] for sp in spans
            if sp["name"] == "drain" and sp["args"].get("drained")}
    durs = [sp["dur"] for sp in spans
            if sp["name"] == "pack" and sp["parent"] in live]
    return 1e3 * sum(durs) / len(durs) if durs else None
