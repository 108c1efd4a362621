"""Share of the window's requests answered from the placement cache,
from the service's own counters (``counts["cache"]``)."""


def read(inp):
    return 100.0 * inp["cache_hits"] / inp["requests"]
