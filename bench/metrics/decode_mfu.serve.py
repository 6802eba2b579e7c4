"""Whole decode step's share of the chip's bf16 peak: 2 FLOPs per weight a
token uses in a matmul (every layer's matrices and the head) plus the
attention's 4 * layers * heads * head_dim * context per token, times tokens
per second of the window, over the peak."""


def read(m):
    x = m["run"].extra
    return 100.0 * x["flops"] / m["window_s"] / m["peaks"]["bf16_flop_per_s"]
