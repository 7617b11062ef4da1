"""``chipbench/flops.py`` against counts made by hand."""

from _chipbench_tiny import ROOT  # noqa: F401

from chipbench import flops as F

MODEL = {"hidden": 64, "enc_layers": 2, "update_layers": 2, "readout_layers": 2}
H = 64
UPD = 2 * (128 * H + H * H)  # one 2-layer update MLP, 128 -> 64 -> 64
OUT = 2 * (H * H + H * 1)  # readout 64 -> 64 -> 1
ENC_OP = 2 * (39 * H + H * H)
ENC_HW = 2 * (4 * H + H * H)


def _hosts(m):
    return [{"cpu": 100.0, "ram_mb": 1000.0, "bandwidth_mbps": 100.0, "latency_ms": 1.0}] * m


LINEAR = {"ops": [{"type": "source"}, {"type": "filter"}, {"type": "sink"}],
          "edges": [[0, 1], [1, 2]], "hosts": _hosts(3)}
# three sources, two filters, two joins, an aggregation, a sink
THREE_WAY = {"ops": [{"type": "source"}, {"type": "filter"}, {"type": "source"}, {"type": "source"},
                     {"type": "filter"}, {"type": "join"}, {"type": "join"}, {"type": "aggregate"},
                     {"type": "sink"}],
             "edges": [[0, 1], [3, 4], [1, 5], [2, 5], [5, 6], [4, 6], [6, 7], [7, 8]],
             "hosts": _hosts(5)}


def test_linear_chain_by_hand():
    n, m = 3, 3
    per_cand = (n * H + m * UPD) + n * UPD + 2 * (1 * H + UPD) + (n + m) * H + OUT
    assert F.candidate_flops(LINEAR, MODEL) == per_cand
    assert F.encoder_flops(LINEAR, MODEL) == n * ENC_OP + m * ENC_HW
    assert F.request_flops(LINEAR, MODEL, 5, 9) == 9 * (n * ENC_OP + m * ENC_HW + 5 * per_cand)


def test_three_way_join_by_hand():
    n, m = 9, 5
    # stage 3: two filters (1 parent), two joins (2 parents), agg and sink (1 parent)
    stage3 = 2 * (H + UPD) + 2 * (2 * H + UPD) + 2 * (H + UPD)
    per_cand = (n * H + m * UPD) + n * UPD + stage3 + (n + m) * H + OUT
    assert F.candidate_flops(THREE_WAY, MODEL) == per_cand
    # 5 host and 9 operator updates in stages 1-2, 6 in stage 3; 9 + 8 + 14 summands
    assert per_cand == 20 * UPD + (9 + 8 + 14) * H + OUT


def test_seg_gather_counts_from_a_traced_call():
    from chipbench import trace as T

    # a gather-sum as the chip's trace names it: 9 members, 32 rows, 8 slots, 1 parent
    hlo = ("%vmap__.8 = f32[9,32,8,64]{3,2,1,0:T(8,128)S(1)} custom-call(f32[9,32,8,64]"
           "{3,2,1,0:T(8,128)S(1)} %a, s32[32,8,1]{2,1,0:T(8,128)S(1)} %b, f32[32,8,1]"
           "{2,1,0:T(8,128)S(1)} %c), custom_call_target=\"tpu_custom_call\", x={}")
    call = T.custom_call_shapes(hlo)
    assert call == (("f32", (9, 32, 8, 64)),
                    [("f32", (9, 32, 8, 64)), ("s32", (32, 8, 1)), ("f32", (32, 8, 1))])
    h = 9 * 32 * 8 * 64
    assert F.seg_gather(*call) == {"flops": 2 * h * 1, "bytes": 4 * h + 8 * 32 * 8 + 4 * h}
    # a segment-sum: x (9, 32, 8, 64) into 5 hosts
    seg = (("f32", (9, 32, 5, 64)), [("f32", (9, 32, 8, 64)), ("s32", (32, 8))])
    assert F.seg_gather(*seg) == {"flops": h, "bytes": 4 * h + 4 * 32 * 8 + 4 * 9 * 32 * 5 * 64}
    assert F.seg_gather(("f32", (4, 4)), [("f32", (4, 4))]) is None
    assert T.custom_call_shapes("%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p)") is None
