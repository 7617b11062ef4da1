"""kernels/seg_gather: the least time the chip could take for every
segment gather/scatter call in the traced sub-window (the larger of its
operations over peak FLOP/s and its bytes over HBM bandwidth, from the call
shapes, ``chipbench/flops.py``) over the kernels' device time, in %.

On the refine paths these are the only Mosaic kernels (``mp_sweep``,
``mp_update`` and ``banked_mlp`` run only under ``use_pallas=True``); the
trace names each by its HLO instruction, which carries its shapes."""

from chipbench import flops as F
from chipbench import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    spent = least = 0.0
    for evs in ctx.trace.ops.values():
        for e in evs:
            if not ctx.trace.lo <= e.start < ctx.trace.hi:
                continue
            call = T.custom_call_shapes(e.name)
            counts = F.seg_gather(*call) if call else None
            if counts is None:
                continue
            spent += e.end - e.start
            least += max(counts["flops"] / ctx.peaks["flops_per_s"],
                         counts["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent if spent > 0 else None
