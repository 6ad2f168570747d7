"""The capture reduction, on the repo's recorded capture and against the program's
own reduction, which it was copied from."""

import os

import pytest

from perfbench.harness import capture


@pytest.fixture
def recorded(repo_root):
    return os.path.join(repo_root, "tests", "data", "recorded_capture")


def test_reduction_agrees_with_the_original_on_the_recorded_capture(recorded):
    from sheeprl_tpu.obs.xprof import analyze_capture

    theirs = analyze_capture(recorded)
    mine = capture.reduce(capture.load_trace_json(recorded))
    assert mine["busy_s"] == pytest.approx(theirs["busy_seconds"], abs=1e-6)
    assert mine["window_s"] - mine["busy_s"] == pytest.approx(theirs["idle_seconds"], abs=1e-6)
    for category, seconds in theirs["categories"].items():
        assert mine["categories"][category] == pytest.approx(seconds, abs=1e-6)
    assert mine["device_ops"][0][0].startswith("dot") and len(mine["device_ops"]) <= 10
    assert sum(s for _, s in mine["idle_gaps"]) == pytest.approx(theirs["idle_seconds"], abs=1e-6)


def test_classification_is_the_originals_plus_tpu_output_fusions():
    from sheeprl_tpu.obs.xprof import classify_op

    names = ["all-reduce.3", "dot.6", "loop_fusion.12", "copy.4", "while.1", "infeed.2",
             "convolution_add_fusion.30", "reduce-scatter.1", "fusion.2375", "concatenate.1"]
    assert [capture.classify_op(n) for n in names] == [classify_op(n) for n in names]
    text = "%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%fused_computation"
    assert capture.classify_op("fusion.8", text) == "mxu"
    assert capture.classify_op("fusion.8", text.replace("kOutput", "kLoop")) == "elementwise"


def test_a_window_clips_ops_and_an_empty_capture_reads_nothing():
    c = capture.Capture(ops={"d0": [capture.Op("dot.1", "dot.1", "", 0.0, 1.0),
                                    capture.Op("while.2", "while.2", "", 2.0, 2.0),
                                    capture.Op("fusion.3", "fusion.3", "", 2.5, 1.0)]},
                        host_spans=[("perfbench.train_call", 1.0, 2.1)])
    r = capture.reduce(c, window=(0.5, 5.0))
    assert r["busy_s"] == pytest.approx(2.5) and r["window_s"] == pytest.approx(4.5)
    assert r["leaf_op_s"] == pytest.approx(1.5)  # the while spans its body: not counted twice
    assert dict(r["device_ops"]) == {"dot.1": pytest.approx(0.5), "fusion.3": pytest.approx(1.0)}
    gaps = dict(r["idle_gaps"])
    assert any(k.startswith("train_call:after_dot_before_while") for k in gaps)  # 1.0 to 2.0: inside the call
    assert any(k.startswith("env_act:after_while_before_window") for k in gaps)
    assert capture.reduce(capture.Capture()) is None
    assert capture.module_mean_s(None, "train_step") is None
    reduced = {"module_s": {"jit_train_step": [0.3, 0.1], "jit_other": [9.0]}}
    assert capture.module_mean_s(reduced, "train_step") == pytest.approx(0.2)
