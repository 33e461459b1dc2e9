"""Model FLOP/s of the traced window over the chip's bf16 peak: 2 per
matmul weight per token processed (prefill tokens, and the decode steps
the slots needed) plus attention over the positions needed, divided by
the traced window's length times the peak."""
from bench.harness.readings import decode_work


def read(run):
    if not run.trace or not run.blocks and not run.prefills:
        return None
    flops = sum(f for f, _ in decode_work(run)) + sum(
        run.sizes.prefill(s)[0] for s in run.prefills)
    return 100.0 * flops / (run.trace["window_s"] * run.peak["bf16_flops"])
