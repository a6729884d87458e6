"""Model step: model FLOPs of every prompt and output token the model
consumed in the window (``bench/work.py``: 2 x matmul weights, tied LM head
included, plus attention over each token's context) over window seconds x
chips x peak bf16 FLOP/s."""


def read(run):
    if run.cell.kind != "model" or run.peak is None:
        return None
    flops = sum(run.work.span_flops(run.cfg, s, n) for st in run.steps for s, n in st.fed)
    return 100.0 * flops / (run.window_s * run.chips * run.peak["bf16_flops"])
