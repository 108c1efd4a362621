"""XLA backend compiles during the measured window (every program, eager
operations included); a warm cell reads 0."""


def read(inp):
    return inp["compiles_in_window"]
