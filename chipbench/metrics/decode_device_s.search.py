"""Device seconds per PPO iteration of autoregressive decode (the segment scans): the programs that
chipbench/layers.json maps to the ``decode`` layer, summed over the
traced iteration.  Nothing to read where no such program ran."""


def read(inp):
    s = inp["trace"]["layer_s"].get("decode")
    return None if s is None else s / inp["iterations"]
