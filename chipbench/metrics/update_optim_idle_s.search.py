"""Seconds per traced PPO iteration in which the device sat idle while
the program's ``ppo.update.optim`` span was the innermost one open: host
dispatch of the gradient sanitize, global-norm clip and Adam step.
Nothing to read where the program has no such span."""


def read(inp):
    d = [t for name, t in inp["trace"]["idle_gaps"]
         if name == "ppo.update.optim"]
    return sum(d) / inp["iterations"] if d else None
