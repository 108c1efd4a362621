"""Share of the traced serving window in which no program ran on the
device: 1 - (union of device busy intervals) / (traced window)."""


def read(inp):
    t = inp["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
