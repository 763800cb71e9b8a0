"""What decides `correct`, at sizes a test run can hold (the files' tiny
`rehearsal` sizes, on the CPU): the control comes out not correct, and a run
of the harness with the timed path broken underneath comes out not correct.
The chip readings the limits were set from are in PERF.md."""
import json

import numpy as np
import pytest

from benchmark import compare, manifest, run


def _last_json(text, word):
    lines = [ln for ln in text.splitlines() if ln.startswith(word)]
    return json.loads(lines[-1][len(word):])


def _rehearse(capsys, workload, seed=5, seconds=2):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0", "--rehearse"])
    assert rc == 0
    return _last_json(capsys.readouterr().out, "REHEARSAL ")


# ------------------------------------------------------------ sound runs
@pytest.mark.parametrize("workload", ["opt-1.3b-fit-s1024", "opt-1.3b-serve-chat"])
def test_a_sound_run_is_correct(capsys, parked, workload):
    out = _rehearse(capsys, workload, seed=2 ** 31 + 77)
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"] and all(
        row["value"] <= row["limit"] for row in out["compared"].values())
    # the numbers compared come last in the line, each beside its limit
    assert list(out)[-1] == "compared"


# ------------------------------------------------------- training: control
def _fit_readings(workload, seed, **fault):
    cell = manifest.Cell(workload, rehearse=True)
    gen = cell.generator()
    built = gen.prepare(cell, seed, 1)
    ref = gen.reference_readings(built, cell, keep_first=True)
    other = gen.reference_readings(built, cell, against=ref.pop("first_grad"),
                                   **fault)
    return cell, compare.training(other, ref, other["grad_cos_gap"])[0]


def test_the_fp8_control_fails_the_training_cell():
    cell, values = _fit_readings("opt-1.3b-fit-s1024", 7, quant="fp8")
    assert cell.config["control_precision"] == "fp8"
    rows, ok = compare.judge(values, cell.limits)
    assert not ok, rows
    # by the direction of the first gradient, and by three times its limit:
    # the gaps of norms part fp8 from bfloat16 by a factor of two (PERF.md)
    assert values["grad_cos_gap_median_leaf"] > \
        3 * cell.limits["grad_cos_gap_median_leaf"]


def test_half_the_batch_left_out_fails_the_training_cell():
    cell, values = _fit_readings("opt-1.3b-fit-s1024", 7, keep_one_in=2)
    rows, ok = compare.judge(values, cell.limits)
    assert not ok, rows


def test_a_state_left_unchanged_reads_one():
    cell = manifest.Cell("opt-1.3b-fit-s1024", rehearse=True)
    gen = cell.generator()
    ref = gen.reference_readings(gen.prepare(cell, 7, 1), cell)
    still = {"loss": [ref["loss"][0]] * 3,
             "grad_norm": {k: 0.0 for k in ref["grad_norm"]},
             "delta_norm": {k: 0.0 for k in ref["delta_norm"]}}
    values = compare.training(still, ref, {k: 1.0 for k in ref["grad_norm"]})[0]
    assert values["grad_norm_worst_leaf"] == pytest.approx(1.0)
    assert values["grad_cos_gap_median_leaf"] == 1.0
    assert values["param_change_worst_leaf"] == pytest.approx(1.0)
    assert values["grad_norm_median_leaf"] > 0.5
    assert not compare.judge(values, cell.limits)[1]


def test_the_loss_read_from_the_step_outputs_is_the_metrics_cross_entropy():
    """`loss_reading: outputs` (a configuration whose `ce` metric is too
    coarse): the same number as mx.metric.CrossEntropy, reduced in float64."""
    import mxtpu as mx
    gen = manifest.Cell("resnet50-fit-b256", rehearse=True).generator()
    rng = np.random.default_rng(11)
    prob = rng.dirichlet(np.ones(16), size=8).astype(np.float32)
    labels = rng.integers(0, 16, size=8).astype(np.float32)

    class Mod:
        def get_outputs(self):
            return [mx.nd.array(prob)]

    metric = mx.metric.CrossEntropy()
    metric.update([mx.nd.array(labels)], [mx.nd.array(prob)])
    assert gen._ce_of_outputs(Mod(), labels) == pytest.approx(
        metric.get()[1], rel=1e-6)


# ------------------------------------- the harness over a broken timed path
def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    from mxtpu.ops import optimizer_ops
    monkeypatch.setattr(optimizer_ops, "_adam_update",
                        lambda a, weight, grad, mean, var: (weight, mean, var))
    out = _rehearse(capsys, "opt-1.3b-fit-s1024")
    assert out["correct"] is False
    assert out["compared"]["grad_norm_worst_leaf"]["value"] == pytest.approx(1.0)
    assert out["compared"]["grad_cos_gap_median_leaf"]["value"] == 1.0


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp
    import mxtpu as mx
    cell = manifest.Cell("opt-1.3b-fit-s1024", rehearse=True)
    gen = cell.generator()
    real_build = gen.build

    def build(cell_, seed, chips):
        built = real_build(cell_, seed, chips)
        rows, half = built["batch"], built["batch"] // 2

        def halved(x):      # the program's rows: the first half, twice
            y = x.reshape(rows, -1)
            return jnp.concatenate([y[:half], y[:half]]).reshape(x.shape)

        batch = built["batch_obj"]
        batch.data = [mx.nd.NDArray(halved(built["drawn"][n]))
                      for n, _, _ in built["data_desc"]]
        batch.label = [mx.nd.NDArray(halved(built["drawn"][n]))
                       for n, _, _ in built["label_desc"]]
        return built            # the reference follows the whole batch

    monkeypatch.setattr(gen, "build", build)
    monkeypatch.setattr(manifest.Cell, "generator", lambda self: gen)
    out = _rehearse(capsys, "opt-1.3b-fit-s1024")
    assert out["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch,
                                                              parked):
    from mxtpu.serving.decode.session import DecodeSession
    real = DecodeSession._sample
    calls = {"n": 0}

    def wrong_every_fifth(self, row, seq):
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            return int(np.argmin(row))
        return real(self, row, seq)

    monkeypatch.setattr(DecodeSession, "_sample", wrong_every_fifth)
    out = _rehearse(capsys, "opt-1.3b-serve-chat", seconds=3)
    assert out["correct"] is False
    gap = out["compared"]["served_logit_gap"]
    assert gap["value"] > 10 * gap["limit"]


def test_the_fp8_control_fails_the_serving_cell(parked):
    cell = manifest.Cell("opt-1.3b-serve-chat", rehearse=True)
    gen = cell.generator()
    rng = np.random.default_rng(3)
    sample = [([int(t) for t in rng.integers(0, cell.config["vocab_size"], 30)],
               [int(t) for t in rng.integers(0, cell.config["vocab_size"], 8)])]
    _, control_gap, n = gen.logit_gaps(cell, 3, sample, quant="fp8")
    assert n == 8
    assert control_gap > cell.limits["served_logit_gap"]


def test_a_compile_inside_the_window_or_a_failed_request_is_not_correct():
    rows, ok = compare.judge({"served_logit_gap": 0.0}, {"served_logit_gap": 0.1})
    assert ok and rows == [("served_logit_gap", 0.0, 0.1)]
    assert not compare.judge({}, {"served_logit_gap": 0.1})[1]
    assert not compare.judge({"served_logit_gap": float("nan")},
                             {"served_logit_gap": 0.1})[1]
