"""Structures keep their shapes across seeds, candidates obey the paper's
placement rules, and the reference's featurization agrees with the program's."""

import json

import numpy as np
import pytest
from _chipbench_tiny import ROOT

from chipbench import reference as R
from chipbench import structures as S


def _config(name):
    # by file: costream-dspbench has no cell in BENCHMARK.json yet
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("config_name", ["costream-synth", "costream-dspbench"])
def test_seeds_change_values_not_shapes(config_name):
    config = _config(config_name)
    a = S.make_pool(config, 16, 7, seed=1)
    b = S.make_pool(config, 16, 7, seed=2**31 + 1)
    shape = lambda p: [([o["type"] for o in s["ops"]], s["edges"], len(s["hosts"])) for s in p]  # noqa: E731
    assert shape(a) == shape(b)
    assert a != b
    assert max(len(s["ops"]) for s in a) <= 12 and max(len(s["hosts"]) for s in a) <= 8


@pytest.mark.parametrize("config_name", ["costream-synth", "costream-dspbench"])
def test_candidates_obey_the_placement_rules(config_name):
    from repro.placement.enumerate import batch_validity_mask

    config = _config(config_name)
    rng = np.random.default_rng(3)
    for s in S.make_pool(config, 8, 11, seed=5):
        a = S.sample_candidates(s, config["hardware"], 200, rng)
        q, c = S.to_program(s)
        assert a.shape == (200, len(s["ops"]))
        assert batch_validity_mask(q, c, a).all()


@pytest.mark.parametrize("config_name", ["costream-synth", "costream-dspbench"])
def test_reference_features_match_the_programs(config_name):
    from repro.core.graph import build_graph_skeleton, slot_index

    config = _config(config_name)
    for s in S.make_pool(config, 8, 13, seed=9):
        q, c = S.to_program(s)
        g = build_graph_skeleton(q, c)
        x_op, types, x_hw = R.featurize(s)
        slots = slot_index(q)
        np.testing.assert_allclose(np.asarray(g.op_x)[slots], x_op, rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(g.op_type)[slots], types)
        np.testing.assert_allclose(np.asarray(g.hw_x)[: len(s["hosts"])], x_hw, rtol=1e-6)
