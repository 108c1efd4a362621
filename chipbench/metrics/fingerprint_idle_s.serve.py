"""Seconds of the traced serving window in which the device sat idle
while the program's ``serve.fingerprint`` span was the innermost one
open: the WL fingerprint and canonical order of a request's graph and
its topology's fingerprint, host work with nothing queued behind it.
Nothing to read where the program has no such span."""


def read(inp):
    d = [t for name, t in inp["trace"]["idle_gaps"]
         if name == "serve.fingerprint"]
    return sum(d) if d else None
