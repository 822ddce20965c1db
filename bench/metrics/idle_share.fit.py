"""Share of one traced path's wall time with no operation on the device,
in %."""


def read(facts):
    t = facts["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
