"""Device seconds per PPO iteration of the list-scheduling simulator: the programs that
chipbench/layers.json maps to the ``simulator`` layer, summed over the
traced iteration.  Nothing to read where no such program ran."""


def read(inp):
    s = inp["trace"]["layer_s"].get("simulator")
    return None if s is None else s / inp["iterations"]
