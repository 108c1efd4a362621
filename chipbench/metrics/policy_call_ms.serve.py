"""Mean wall duration of the program's ``serve.batch`` spans in the
window: one batched GNN and autoregressive decode call, ended by the
host copy of its placements inside the span.  Nothing to read where no
batch ran."""


def read(inp):
    d = inp["batch_span_s"]
    return 1e3 * sum(d) / len(d) if d else None
