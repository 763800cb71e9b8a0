"""`olmo-hybrid-7b-fit-s2048` at the files' tiny `rehearsal` sizes on the
CPU: the Symbol against the plain reference, what decides `correct` (a sound
run passes; the fp8 control, half of the batch left out and the state reset
at every chunk boundary each fail), the vocabulary's share, and the counts of
operations and bytes."""
import json
import os

import numpy as np
import pytest

from benchmark import compare, manifest, run

CELL = "olmo-hybrid-7b-fit-s2048"
BENCH = os.path.dirname(manifest.__file__)


def _last_json(text, word):
    lines = [ln for ln in text.splitlines() if ln.startswith(word)]
    return json.loads(lines[-1][len(word):])


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL, rehearse=True)


@pytest.fixture(scope="module")
def sound(cell):
    """The sound reference's readings on one seed, and what a fault is
    held against (its first gradient)."""
    gen = cell.generator()
    built = gen.prepare(cell, 7, 1)
    ref = gen.reference_readings(built, cell, keep_first=True)
    return gen, built, ref, ref.pop("first_grad")


# ------------------------------------------------------------- the model
def test_the_symbols_logits_are_the_references(cell):
    import jax
    import jax.numpy as jnp
    import mxtpu as mx
    from benchmark import weights
    cfg = dict(cell.config, dtype=None)     # float32: the model, not rounding
    program, reference = cell.config_module("program"), \
        cell.config_module("reference")
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 128))
    params = weights.make(11, reference.param_specs(cfg), round_to="float32")
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(reference.forward(
            params, jnp.asarray(ids, jnp.int32), cfg), axis=-1)
        sym = program.symbol(cfg, cell.traffic)
        args = {k: mx.nd.NDArray(v) for k, v in params.items()}
        args["data"] = mx.nd.array(ids.astype(np.float32))
        args["softmax_label"] = mx.nd.zeros((256,))
        ex = sym.bind(mx.cpu(), args)
        got = ex.forward(is_train=False)[0].asnumpy()
    assert sorted(set(sym.list_arguments()) - {"data", "softmax_label"}) == \
        sorted(params)
    np.testing.assert_allclose(got, np.asarray(want).reshape(256, -1),
                               rtol=2e-3, atol=1e-7)


def test_a_sound_run_is_correct(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 28), "--seconds",
                   "2", "--trace", "0", "--rehearse"])
    assert rc == 0
    out = _last_json(capsys.readouterr().out, "REHEARSAL ")
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert set(out["metrics"]) == {"train_throughput", "setup_s"}
    assert list(out)[-1] == "compared"


# ------------------------------------------- the control and the faults
def _judged(cell, sound, **fault):
    gen, built, ref, first = sound
    other = gen.reference_readings(built, cell, against=first, **fault)
    values = compare.training(other, ref, other["grad_cos_gap"])[0]
    return values, compare.judge(values, cell.limits)


def test_the_fp8_control_is_not_correct(cell, sound):
    assert cell.config["control_precision"] == "fp8"
    values, (rows, ok) = _judged(cell, sound, quant="fp8")
    assert not ok, rows
    assert values["grad_cos_gap_median_leaf"] > \
        2 * cell.limits["grad_cos_gap_median_leaf"]


def test_half_of_the_batch_left_out_is_not_correct(cell, sound):
    _, (rows, ok) = _judged(cell, sound, keep_one_in=2)
    assert not ok, rows


def test_the_state_reset_at_every_chunk_boundary_is_not_correct(cell):
    from benchmark.tools import fit_fault_readings
    values, ok = fit_fault_readings.readings(cell, 7, "chunk_reset")
    assert not ok, values
    # the first chunk of a row is untouched, the second starts from nothing
    assert values["grad_cos_gap_median_leaf"] > \
        cell.limits["grad_cos_gap_median_leaf"]


def test_the_reset_moves_nothing_inside_the_first_chunk():
    import jax.numpy as jnp
    from benchmark.references import olmo_hybrid as ref
    rng = np.random.default_rng(3)
    q, k = rng.normal(size=(2, 128, 2, 8)).astype(np.float32)
    v = rng.normal(size=(128, 2, 16)).astype(np.float32)
    g = -rng.uniform(size=(128, 2)).astype(np.float32) * 0.1
    beta = rng.uniform(size=(128, 2)).astype(np.float32) * 2
    whole = ref.delta_rule(*map(jnp.asarray, (q, k, v, g, beta)))
    cut = ref.delta_rule(*map(jnp.asarray, (q, k, v, g, beta)),
                         reset_every=64)
    np.testing.assert_array_equal(whole[:64], cut[:64])
    assert float(jnp.max(jnp.abs(whole[64:] - cut[64:]))) > 1e-2


# ------------------------------------------------------------- the share
def test_the_vocabulary_slices_add_up_to_the_uncut_model():
    """At a small size: the logits of the eight slices of the head,
    concatenated, are the uncut reference's, and a slice's loss is the
    cross-entropy over the slice."""
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.references import common, olmo_hybrid as ref
    cfg = manifest.Cell(CELL, rehearse=True).config
    v, share = cfg["vocab_size"], cfg["vocab_size"] // 8
    params = weights.make(5, ref.param_specs(cfg), round_to="float32")
    rng = np.random.default_rng(1)
    local = rng.integers(0, share, (1, 128))
    labels = rng.integers(0, share, (128,))
    with jax.default_matmul_precision("highest"):
        for mine in (0, 5):     # which slice the ids are drawn from
            uncut = ref.forward(params, jnp.asarray(local + mine * share),
                                cfg)[0]
            parts = []
            for s in range(8):
                cut = dict(params)
                cut["tok_emb_weight"] = params["tok_emb_weight"][
                    mine * share:(mine + 1) * share]
                cut["lm_head_weight"] = params["lm_head_weight"][
                    s * share:(s + 1) * share]
                parts.append(ref.forward(cut, jnp.asarray(local),
                                         dict(cfg, vocab_size=share))[0])
            np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), uncut,
                                       rtol=1e-5, atol=1e-6)
            assert uncut.shape == (128, v)
            got = common.ce_sum(parts[mine], jnp.asarray(labels))[0]
            mine_logits = uncut[:, mine * share:(mine + 1) * share]
            want = -jnp.sum(jax.nn.log_softmax(mine_logits, axis=-1)[
                jnp.arange(128), labels])
            assert float(got) == pytest.approx(float(want), rel=1e-5)


# --------------------------------------------------- operations and bytes
def _full_config():
    return manifest.read_json(os.path.join(
        BENCH, "configs", "olmo-hybrid-7b-train", "config.json"))


def _flops():
    return manifest.load_module(os.path.join(
        BENCH, "configs", "olmo-hybrid-7b-train", "flops.py"), "flops_olmo")


def test_flops_and_bytes_by_hand():
    cfg, f = _full_config(), _flops()
    d, ff, v, h, dk, dv = 3840, 11008, 12544, 30, 96, 192
    ffn = 2 * 3 * d * ff
    linear = 2 * (2 * d * h * dk + 2 * d * h * dv + h * dv * d + 2 * d * h) \
        + 2 * 4 * h * (2 * dk + dv) + 6 * dk * dv * h
    full = 2 * 4 * d * d + 4 * d * 2049 / 2
    assert f.forward_flops_per_token(cfg, 2048) == \
        3 * (linear + ffn) + (full + ffn) + 2 * v * d
    per_token = f.train_flops_per_item(cfg, {"seq_len": 2048})
    assert per_token == 3 * f.forward_flops_per_token(cfg, 2048)
    # ISSUE 28 reckoned "roughly 5.3 GFLOP a token" from the matmuls alone
    assert per_token == pytest.approx(5.4e9, rel=0.03)
    traffic = {"seq_len": 2048}
    flops, bytes_ = f.delta_rule(cfg, traffic, 4)
    assert flops == 4 * 2048 * 30 * 6 * 96 * 192
    assert bytes_ == 4 * 2048 * 30 * ((96 + 96 + 192 + 192) * 2 + 4 + 2)
    bflops, bbytes = f.delta_rule_bwd(cfg, traffic, 4)
    assert bflops == 2 * flops
    assert bbytes == 4 * 2048 * 30 * (2 * (96 + 96 + 192) * 2 + 192 * 2
                                      + 4 + 2 + 8)
    # about 96 flops a byte against the v5e's 240: the bytes bound it
    assert flops / bytes_ == pytest.approx(95.5, rel=0.01)
    assert flops / 197e12 < bytes_ / 819e9
    assert bflops / 197e12 < bbytes / 819e9


def test_the_flash_kernels_flops_and_bytes_by_hand():
    """Causal attention as written, B 4, H 30, T 2048, head 128: two
    products a query-key pair forward, five backward; the flops bound both
    on a v5e."""
    cfg, f = _full_config(), _flops()
    traffic = {"seq_len": 2048}
    pairs = 4 * 30 * 2048 * 2049 // 2
    elems, rows = 4 * 30 * 2048 * 128, 4 * 30 * 2048
    flops, bytes_ = f.flash_fwd(cfg, traffic, 4)
    assert flops == 4 * 128 * pairs
    assert bytes_ == 4 * elems * 2 + rows * 4
    bflops, bbytes = f.flash_bwd(cfg, traffic, 4)
    assert bflops == 10 * 128 * pairs
    assert bbytes == 7 * elems * 2 + 2 * rows * 4
    assert flops / 197e12 > bytes_ / 819e9
    assert bflops / 197e12 > bbytes / 819e9
    assert flops / 197e12 == pytest.approx(0.654e-3, rel=0.01)
    # the full-attention layer's share of train_flops_per_item counts the
    # same pairs: forward 4 d (T+1)/2 a token
    assert flops / (4 * 2048) == 4 * 3840 * 2049 / 2


def test_flops_against_xla_cost_analysis():
    """XLA's count of the reference's matmuls at a small size: one row
    through one full-attention layer, one linear layer's projections and the
    head. Its `while` loops (the token recurrence) it counts once, so the
    recurrence is left out of both sides."""
    import jax
    import jax.numpy as jnp
    from benchmark.references import olmo_hybrid as ref
    f = _flops()
    t = 64
    cfg = dict(_full_config(), hidden_size=128, intermediate_size=512,
               num_attention_heads=2, vocab_size=1024, num_hidden_layers=2,
               linear_num_key_heads=2, linear_num_value_heads=2,
               linear_key_head_dim=32, linear_value_head_dim=64,
               layer_types=["full_attention", "linear_attention"])
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s, _ in ref.param_specs(cfg)}
    tokens = jax.ShapeDtypeStruct((t,), jnp.int32)
    cost = jax.jit(lambda p, x: ref.row_logits(p, x, cfg, remat=False)) \
        .lower(shapes, tokens).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    h, dk, dv = 2, 32, 64
    recurrence = 6 * dk * dv * h
    # XLA counts full (not causal) attention; the recurrence's loop body it
    # counts for one token
    full_attention = 4 * cfg["hidden_size"] * (t - 1) / 2
    mine = t * (f.forward_flops_per_token(cfg, t) - recurrence
                + full_attention)
    assert cost["flops"] == pytest.approx(mine, rel=0.1)


# ------------------------------------------------- the per-layer readers
def _made_up_trace():
    """One step program of 100 ms with three forward calls of 8 ms and three
    backward calls of 9 ms of the delta rule, two flash calls and a fusion
    that only reads a kernel's output."""
    from benchmark import trace
    ms = 1_000_000
    ops, t = [], 10 * ms
    for i in range(3):
        ops.append(("%%mxtpu_delta_rule_fwd.%d = (bf16[120,2048,192]{2,1,0}, "
                    "f32[120,32,96,192]{3,2,1,0}) custom-call(%%bitcast.5), "
                    "custom_call_target=\"tpu_custom_call\"" % i, t, t + 8 * ms))
        t += 8 * ms
        ops.append(("%%fusion.%d = bf16[4,2048,5760]{2,1,0} fusion("
                    "%%mxtpu_delta_rule_fwd.%d), kind=kLoop" % (i, i),
                    t, t + 1 * ms))
        t += 1 * ms
    for name in ("mxtpu_flash_fwd.1", "mxtpu_flash_bwd.1"):
        ops.append(("%%%s = bf16[120,2048,128]{2,1,0} custom-call(%%x), "
                    "custom_call_target=\"tpu_custom_call\"" % name,
                    t, t + 2 * ms))
        t += 2 * ms
    for i in range(3):
        ops.append(("%%mxtpu_delta_rule_bwd.%d = (bf16[120,2048,96]{2,1,0}) "
                    "custom-call(%%bitcast.8), custom_call_target="
                    "\"tpu_custom_call\"" % i, t, t + 9 * ms))
        t += 9 * ms
    devices = {"/device:TPU:0": {
        "ops": ops,
        "modules": [("jit_mxtpu_fused_step(1)", 10 * ms, 110 * ms)]}}
    return trace.Trace(devices, [(trace.WINDOW_SPAN, 0, 200 * ms)])


def test_the_delta_rule_readers_find_the_kernels_by_name_and_no_others():
    cell = manifest.Cell(CELL)
    facts = {"trace": _made_up_trace(), "cell": cell, "config": cell.config,
             "traffic": cell.traffic, "batch_per_chip": 4,
             "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    f = _flops()
    fwd = f.delta_rule(cell.config, cell.traffic, 4)[1] / 819e9
    bwd = f.delta_rule_bwd(cell.config, cell.traffic, 4)[1] / 819e9
    got = cell.reader("delta_rule_roofline").read(facts)
    assert got == pytest.approx(100 * (fwd + bwd) / (8e-3 + 9e-3))
    assert 5 < got < 7          # by bytes: 0.35 + 0.66 ms against 17 ms
    # the fusions that read a kernel's output and the flash calls are not
    # the delta rule's: 3 x (8 + 9) ms of a 100 ms step
    assert cell.reader("delta_rule_device_share").read(facts) == \
        pytest.approx(51.0)
    # a trace without the kernels (the parent's): nothing, and no error
    bare = dict(facts, trace=type(facts["trace"])(
        {"/device:TPU:0": {"ops": [("%fusion.1 = f32[8]{0} fusion(%x)", 0, 5)],
                           "modules": [("jit_step(1)", 0, 5)]}}, []))
    assert cell.reader("delta_rule_roofline").read(bare) is None
    assert cell.reader("delta_rule_device_share").read(bare) is None


def test_the_flash_readers_take_each_kernel_by_its_name():
    """`mxtpu_flash_fwd_roofline` / `mxtpu_flash_bwd_roofline`: one call of
    2 ms each in the made-up trace beside six Mosaic calls of the delta
    rule, which `flash_fwd_roofline`'s pattern would take too."""
    cell = manifest.Cell(CELL)
    facts = {"trace": _made_up_trace(), "cell": cell, "config": cell.config,
             "traffic": cell.traffic, "batch_per_chip": 4,
             "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    f = _flops()
    for name, need in (("mxtpu_flash_fwd_roofline", f.flash_fwd),
                       ("mxtpu_flash_bwd_roofline", f.flash_bwd)):
        got = cell.reader(name).read(facts)
        assert got == pytest.approx(
            100 * need(cell.config, cell.traffic, 4)[0] / 197e12 / 2e-3)
        assert 0 < got < 100
    # a program from before the kernels were named (the parent's Mosaic
    # calls are `branch_0_fun.N`), and a configuration that counts no
    # flash_bwd: nothing, and no error
    unnamed = type(facts["trace"])(
        {"/device:TPU:0": {"ops": [(
            "%branch_0_fun.3 = bf16[128,1024,64]{2,1,0} custom-call(%x), "
            "custom_call_target=\"tpu_custom_call\"", 0, 5)],
            "modules": [("jit_mxtpu_fused_step(1)", 0, 5)]}}, [])
    opt = manifest.Cell("opt-1.3b-fit-s1024")
    for name in ("mxtpu_flash_fwd_roofline", "mxtpu_flash_bwd_roofline"):
        assert cell.reader(name).read(dict(facts, trace=unnamed)) is None
        assert cell.reader(name).read(dict(facts, trace=None)) is None
    assert cell.reader("mxtpu_flash_bwd_roofline").read(
        dict(facts, cell=opt, config=opt.config, traffic=opt.traffic)) is None


def test_the_state_reader_reads_the_programs_gauge():
    from mxtpu import telemetry
    cell = manifest.Cell(CELL)
    reader = cell.reader("delta_rule_state_saved_gb")
    telemetry.gauge("delta_rule_state_saved_bytes").set(0)
    assert reader.read({"config": cell.config}) is None
    one_call = 4 * 30 * 32 * 96 * 192 * 4
    telemetry.gauge("delta_rule_state_saved_bytes").set(one_call)
    assert reader.read({"config": cell.config}) == \
        pytest.approx(3 * one_call / 1e9)      # three linear layers held
    assert reader.read({}) is None
    assert reader.read({"config": {"num_layers": 50}}) is None


def test_the_cell_reports_what_its_entry_lists():
    cell = manifest.Cell(CELL)
    due = [m["name"] for m in cell.per_layer()]
    for name in ("delta_rule_roofline", "delta_rule_device_share",
                 "delta_rule_state_saved_gb", "mxtpu_flash_fwd_roofline",
                 "mxtpu_flash_bwd_roofline", "mfu.train", "step_device_ms",
                 "device_idle_share.train", "hbm_peak_gb.train",
                 "fit_host_wait_share", "idle_unnamed_share", "compile_s",
                 "window_compiles"):
        assert name in due, name
    assert "flash_fwd_roofline" not in due
    for other in ("opt-1.3b-fit-s1024", "resnet50-fit-b256"):
        theirs = [m["name"] for m in manifest.Cell(other).per_layer()]
        assert not [n for n in theirs
                    if n.startswith(("delta_rule", "mxtpu_flash"))]
    cfg = cell.config
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == \
        ["linear_attention"] * 3 + ["full_attention"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["rms_norm_eps"]) == (3840, 30, 11008, 96, 192, 4, 1e-6)
    assert sorted(cfg["published"]) == ["num_hidden_layers", "vocab_size"]
