"""Whole forward: the operations Algorithm 1 needs for every candidate
answered inside the window (``chipbench/flops.py``), over the window's
seconds times the chip's peak, in %.  The program computes in float32 at the
chip's default matmul precision; the peak is the published bfloat16 figure."""


def read(ctx):
    if not ctx.flops_answered:
        return None
    return 100.0 * ctx.flops_answered / (ctx.seconds * ctx.peaks["flops_per_s"])
