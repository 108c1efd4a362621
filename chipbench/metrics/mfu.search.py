"""Model FLOP utilization of a PPO iteration: the FLOPs one iteration
requires (GNN, autoregressive decode, teacher-forced re-score, the
update's forward and backward; counted from shapes at the real node
count by chipbench/flops.py) over the traced iteration's wall time times
the chip's bf16 peak."""


def read(inp):
    t = inp["trace"]
    return 100.0 * inp["flops"] / (t["window_s"] * inp["peak_flops"])
