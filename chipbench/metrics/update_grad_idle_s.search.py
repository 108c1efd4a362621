"""Seconds per traced PPO iteration in which the device sat idle while
the program's ``ppo.update.grad`` span was the innermost one open: host
dispatch of the segmented ``value_and_grad``.  Nothing to read where the
program has no such span."""


def read(inp):
    d = [t for name, t in inp["trace"]["idle_gaps"]
         if name == "ppo.update.grad"]
    return sum(d) / inp["iterations"] if d else None
