"""Model FLOP utilization of serving: the FLOPs of the GNN and the
autoregressive decodes of the requests whose policy call ran in the
traced window, at their own node counts (backfill rows and padding do
not count), over the traced window's wall time times the chip's bf16
peak.  Nothing to read when no policy call ran in it."""


def read(inp):
    if not inp["flops"]:
        return None
    t = inp["trace"]
    return 100.0 * inp["flops"] / (t["window_s"] * inp["peak_flops"])
