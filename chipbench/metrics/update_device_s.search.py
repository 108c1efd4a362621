"""Device seconds per PPO iteration of the teacher-forced passes, the update and Adam: the programs that
chipbench/layers.json maps to the ``update`` layer, summed over the
traced iteration.  Nothing to read where no such program ran."""


def read(inp):
    s = inp["trace"]["layer_s"].get("update")
    return None if s is None else s / inp["iterations"]
