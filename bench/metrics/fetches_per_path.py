"""Calls through ``repro.core.engine.device_get`` during one path."""


def read(facts):
    return float(facts["fetches"])
