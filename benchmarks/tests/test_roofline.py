"""The functions that count the algorithm's operations and bytes."""

import pytest

import reference
import roofline
from run import tree_counts


def three_leaf_tree():
    """root(100 rows) -> [leaf0: 30] , split1(70) -> [leaf1: 60, leaf2: 10]"""
    return reference.parse_tree({"tree_structure": {
        "split_index": 0, "split_feature": 0, "threshold": 0.0,
        "split_gain": 1.0, "decision_type": "<=", "internal_value": 0.0,
        "internal_weight": 25.0, "internal_count": 100,
        "left_child": {"leaf_index": 0, "leaf_value": -0.1,
                       "leaf_weight": 7.5, "leaf_count": 30},
        "right_child": {
            "split_index": 1, "split_feature": 1, "threshold": 1.0,
            "split_gain": 0.5, "decision_type": "<=", "internal_value": 0.0,
            "internal_weight": 17.5, "internal_count": 70,
            "left_child": {"leaf_index": 1, "leaf_value": 0.05,
                           "leaf_weight": 15.0, "leaf_count": 60},
            "right_child": {"leaf_index": 2, "leaf_value": 0.3,
                            "leaf_weight": 2.5, "leaf_count": 10}}}})


def test_necessary_bytes_three_leaves():
    counts = tree_counts(three_leaf_tree())
    assert counts == (100, [(30, 70), (60, 10)])
    # root 100 rows + smaller children 30 and 10, each row 28 bins + g + h
    assert roofline.necessary_bytes(counts, features=28) == 140 * (28 + 8)


def test_hist_pass_work_counts_one_product():
    ops, byts = roofline.hist_pass_work(rows=1000, features=28, bins=64,
                                        slots=64)
    assert ops == 2 * 1000 * 28 * 64 * 3 * 64
    assert byts == 1000 * (28 + 12 + 4) + 4 * 64 * 28 * 64 * 3


def test_hist_call_shapes_from_hlo_text():
    text = ("%hist_leaves_pallas.8 = f32[1,192,1792]{2,1,0:T(8,128)S(1)} "
            "custom-call(f32[1,1792]{1,0} %fusion.61, u8[2000384,28]{1,0} "
            "%pad, f32[3,2000384]{1,0} %p1, s32[1,2000384]{1,0} %b), "
            'custom_call_target="tpu_custom_call"')
    assert roofline.hist_call_shapes(text, bins=64) == {
        "rows": 2000384, "features": 28, "slots": 64}
    assert roofline.hist_call_shapes("%fusion.1 = f32[3] fusion()", 64) is None
    assert roofline.hist_call_shapes("(f32[1,24,2048]{2,1,0:T(8,128)})", 64) \
        == {"features": 32, "slots": 8}
    # the calls of a five-block pass writing slices of one result buffer:
    # each still sums one block of 32 features
    assert roofline.hist_call_shapes("(f32[5,192,2048]{2,1,0:T(8,128)})", 64) \
        == {"features": 32, "slots": 64}


def test_unknown_device_is_an_error():
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9")
