"""Operations and bytes from shapes: the GNN forward and the ``seg_gather``
kernels.

The forward's count is the work the paper's Algorithm 1 needs for a request's
*real* operators and hosts, whatever implements it: no padding to the
program's fixed graph size, no banding slack, no empty depth levels.  A
request for one structure needs its stage-0 encoders once, and stages 1-3
and the readout once per candidate.  Matrix products count 2 operations per
multiply-add; in a sum of node states each summand counts one operation per
element; bias adds and activations are not counted.  Everything is per ensemble member.
"""

from __future__ import annotations

from typing import Dict, Optional

from chipbench import structures as S


def _mlp(d_in: int, hidden: int, d_out: int, layers: int) -> int:
    sizes = [d_in] + [hidden] * (layers - 1) + [d_out]
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def encoder_flops(struct: Dict, model: Dict) -> int:
    """Stage 0 of one structure, one member."""
    h, n, m = model["hidden"], len(struct["ops"]), len(struct["hosts"])
    return n * _mlp(39, h, h, model["enc_layers"]) + m * _mlp(4, h, h, model["enc_layers"])


def candidate_flops(struct: Dict, model: Dict) -> int:
    """Stages 1-3 and the readout for one candidate placement, one member."""
    h, n, m = model["hidden"], len(struct["ops"]), len(struct["hosts"])
    upd = _mlp(2 * h, h, h, model["update_layers"])
    par = S.parents(struct)
    f = n * h + m * upd  # stage 1: operator states summed into hosts, host updates
    f += n * upd  # stage 2 operator updates
    for ps in par:  # stage 3: each operator with parents
        if ps:
            f += len(ps) * h + upd
    f += (n + m) * h + _mlp(h, h, 1, model["readout_layers"])  # readout
    return f


def request_flops(struct: Dict, model: Dict, n_candidates: int, members: int) -> int:
    """One answered request: encoders once, every candidate, every member."""
    return members * (encoder_flops(struct, model) + n_candidates * candidate_flops(struct, model))


# -- seg_gather ---------------------------------------------------------------------


def _n(shape) -> int:
    out = 1
    for d in shape:
        out *= d
    return out


def seg_gather(out: tuple, operands: list) -> Optional[Dict[str, int]]:
    """Operations and bytes of one ``kernels/seg_gather`` call from its
    shapes: ``out`` and ``operands`` are (dtype, shape) pairs, leading batch
    axes (members, rows) included.  None for a call of another kernel.

    * gather-sum ``out[.., r] = sum_p w[.., r, p] * h[.., idx[.., r, p]]``:
      operands (h float, idx int, w float), 2 operations per output element
      and parent;
    * segment-sum ``out[.., s] = sum_{r: seg[.., r] == s} x[.., r]``:
      operands (x float, seg int), one operation per element of ``x``.

    Bytes are every operand read once and the output written once."""
    kinds = tuple("int" if dt.startswith("s") or dt.startswith("u") else "float" for dt, _ in operands)
    if kinds == ("float", "int", "float"):
        flops = 2 * _n(out[1]) * operands[1][1][-1]
    elif kinds == ("float", "int"):
        flops = _n(operands[0][1])
    else:
        return None
    size = {"f32": 4, "s32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1}
    nbytes = sum(size[dt] * _n(sh) for dt, sh in operands) + size[out[0]] * _n(out[1])
    return {"flops": flops, "bytes": nbytes}
