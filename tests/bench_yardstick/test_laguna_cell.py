"""`laguna-s-2.1-fit-s4096` at the files' tiny `rehearsal` sizes on the CPU:
the Symbol against the plain reference, what decides `correct` (sound runs
pass on three seeds; the fp8 control, half of the batch left out, the window
left out and the routed weights normalised over the held experts each
fail), the counts of operations and bytes, and the readers of what the cell
adds."""
import json
import os

import numpy as np
import pytest

from benchmark import compare, manifest, run

CELL = "laguna-s-2.1-fit-s4096"
BENCH = os.path.dirname(manifest.__file__)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


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
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 64))
    params = weights.make(11, reference.param_specs(cfg), round_to="float32")
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(reference.forward(
            params, jnp.asarray(ids, jnp.int32), cfg), axis=-1)
        sym = program.symbol(cfg, cell.traffic)
        args = {k: mx.nd.NDArray(v) for k, v in params.items()}
        args["data"] = mx.nd.array(ids.astype(np.float32))
        args["softmax_label"] = mx.nd.zeros((128,))
        ex = sym.bind(mx.cpu(), args)
        outs = ex.forward(is_train=False)
    assert sorted(set(sym.list_arguments()) - {"data", "softmax_label"}) == \
        sorted(params)
    np.testing.assert_allclose(outs[0].asnumpy(),
                               np.asarray(want).reshape(128, -1),
                               rtol=2e-3, atol=1e-7)
    # the loads beside the loss: what the reference's router sends here
    for i, load in zip((1, 2, 3, 4), outs[1:]):
        assert load.shape == (4,) and 0 < load.asnumpy().sum() <= 128 * 3


def test_a_sound_run_is_correct(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 32), "--seconds",
                   "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    out = _last_json(capsys.readouterr().out, "REHEARSAL ")
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert set(out["metrics"]) == {"train_throughput", "setup_s"}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("seed", [3, 3300032105])
def test_the_programs_first_steps_are_the_references(cell, seed):
    """With the seed above, three seeds of the whole rehearsal model through
    `Module.fit`'s fused step against the reference under the cell's
    rehearsal limits (what benchmark/tools/fit_readings.py does)."""
    import contextlib
    gen = cell.generator()
    built = gen.build(cell, seed, 1)
    it = gen.DeviceBatchIter(built["batch_obj"], built["pdata"],
                             built["plabel"],
                             lambda n: contextlib.nullcontext())
    prog = gen.first_steps(built, it)
    assert built["mod"]._fused is not None
    ref = gen.reference_readings(built, cell, against=prog.pop("first_grad"))
    values = compare.training(prog, ref, ref["grad_cos_gap"])[0]
    rows, ok = compare.judge(values, cell.limits)
    assert ok, rows


# ------------------------------------------- the control and the faults
def _judged(cell, sound, fault=None, **kw):
    from benchmark.references import common
    gen, built, ref, first = sound
    if fault is None:
        other = gen.reference_readings(built, cell, against=first, **kw)
    else:
        mod, cfg = built["reference"], cell.config
        rows = mod.split_rows(*(built["drawn"][n] for n in built["names"]))
        other = common.follow(
            mod.block_loss(cfg, None, fault=fault), built["make_params"],
            rows, dict(built["opt"]), cfg["param_dtypes"], steps=3,
            items_per_row=built["items_per_row"], against=first)
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


@pytest.mark.parametrize("fault", ["no_window", "held_norm"])
def test_this_architectures_own_faults_are_not_correct(cell, sound, fault):
    """The window left out of the `sliding_attention` layers; the routed
    weights normalised over the experts held here in place of all chosen:
    each in the reference's copy, each judged not correct."""
    _, (rows, ok) = _judged(cell, sound, fault=fault)
    assert not ok, rows


def test_the_faults_move_what_they_should():
    import jax
    import jax.numpy as jnp
    from benchmark.references import laguna as ref
    cfg = manifest.Cell(CELL, rehearse=True).config
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    w_r = jnp.asarray(rng.normal(size=(16, 64)) * 0.3, jnp.float32)
    with jax.default_matmul_precision("highest"):
        w, i = ref.route(x, w_r, cfg)
        w_bad, i_bad = ref.route(x, w_r, cfg, fault="held_norm")
    assert np.array_equal(np.asarray(i), np.asarray(i_bad))
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    here = (np.asarray(i) < 4)
    some = here.any(-1)
    np.testing.assert_allclose(
        (np.asarray(w_bad) * here).sum(-1)[some], 2.5, rtol=1e-5)
    assert (np.asarray(w_bad)[here] >= np.asarray(w)[here]).all()
    # the window: a query in the first 8 positions sees the same keys
    qkv = tuple(jnp.asarray(rng.normal(size=s), jnp.float32)
                for s in ((3, 64, 16), (64, 16), (64, 16)))
    inside = ref._attend(qkv, 8, lambda a: a)
    without = ref._attend(qkv, 0, lambda a: a)
    np.testing.assert_allclose(inside[:, :8], without[:, :8], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(inside[:, 8:] - without[:, 8:]))) > 1e-2


# --------------------------------------------------- operations and bytes
def _full_config():
    return manifest.read_json(os.path.join(
        BENCH, "configs", "laguna-s-2.1-train", "config.json"))


def _flops():
    return manifest.load_module(os.path.join(
        BENCH, "configs", "laguna-s-2.1-train", "flops.py"), "flops_laguna")


def test_flops_by_hand_and_the_issues_shares():
    cfg, f = _full_config(), _flops()
    d, dh, g, v = 3072, 128, 8, 12544
    full = 2 * (d * 48 * dh * 2 + 2 * d * g * dh + d * 48)
    window = 2 * (d * 72 * dh * 2 + 2 * d * g * dh + d * 72)
    keys_full = 4097 / 2
    keys_window = (512 * 513 / 2 + (4096 - 512) * 512) / 4096
    assert keys_window == sum(min(t + 1, 512) for t in range(4096)) / 4096
    scores = 2 * 4 * 48 * dh * keys_full + 3 * 4 * 72 * dh * keys_window
    dense, head = 6 * d * 12288, 2 * v * d
    shared, router, routed = 6 * d * 1024, 2 * d * 256, 0.3125 * 6 * d * 1024
    assert f.pairs_per_token_expected(cfg) == 0.3125
    total = 2 * full + 3 * window + scores + dense + head + 4 * (
        shared + router + routed)
    assert f.forward_flops_per_token(cfg, 4096) == pytest.approx(total, rel=1e-12)
    assert f.train_flops_per_item(cfg, {"seq_len": 4096}) == \
        pytest.approx(3 * total, rel=1e-12)
    # ISSUE 32: attention 63% (matrices and gates 50, scores 14), the dense
    # FFN 20%, the head 7%, the expert layers 9.5% (6.8 + 0.6 + 2.1)
    share = lambda x: 100 * x / total  # noqa: E731
    assert share(2 * full + 3 * window) == pytest.approx(50, abs=0.5)
    assert share(scores) == pytest.approx(14, abs=0.5)
    assert share(dense) == pytest.approx(20, abs=0.5)
    assert share(head) == pytest.approx(7, abs=0.2)
    assert share(4 * shared) == pytest.approx(6.8, abs=0.1)
    assert share(4 * router) == pytest.approx(0.6, abs=0.05)
    assert share(4 * routed) == pytest.approx(2.1, abs=0.05)
    # visiting every causal block in a window layer: 4 x 9216 x (2048 - 480)
    # more a token a layer, three times over, some 15% of the step
    more = 4 * 72 * dh * (keys_full - keys_window)
    assert more == pytest.approx(58e6, rel=0.01)
    assert 3 * more / total == pytest.approx(0.155, abs=0.005)


def test_the_kernels_flops_and_bytes_by_hand():
    cfg, f = _full_config(), _flops()
    traffic = {"seq_len": 4096}
    t, dh = 4096, 128
    for kind, heads, keys, fwd, bwd in (
            ("full", 48, 4097 / 2, f.flash_fwd, f.flash_bwd),
            ("window", 72, (512 * 513 / 2 + 3584 * 512) / 4096,
             f.flash_win_fwd, f.flash_win_bwd)):
        pairs = 2 * heads * t * keys * dh
        per_q, per_kv, rows = 2 * heads * t * dh, 2 * 8 * t * dh, 2 * heads * t
        flops, bytes_ = fwd(cfg, traffic, 2)
        assert flops == pytest.approx(4 * pairs, rel=1e-12), kind
        assert bytes_ == (2 * per_q + 2 * per_kv) * 2 + rows * 4
        bflops, bbytes = bwd(cfg, traffic, 2)
        assert bflops == pytest.approx(10 * pairs, rel=1e-12)
        assert bbytes == (3 * per_q + 4 * per_kv) * 2 + 2 * rows * 4
        assert flops / 197e12 > bytes_ / 819e9      # the flops bound both
    # a window call needs under a quarter of what the same heads would
    # under the causal mask alone
    assert f.flash_win_fwd(cfg, traffic, 2)[0] / (
        4 * 2 * 72 * t * 4097 / 2 * dh) == pytest.approx(0.234, abs=0.002)
    flops, bytes_ = f.moe_gmm(cfg, traffic, 2, 2560)
    assert flops == 3 * 2560 * 6 * 3072 * 1024
    assert bytes_ == 3 * (2560 * (2 * 3072 + 3 * 1024) * 2
                          + 3 * 8 * 3072 * 1024 * 2)
    # what 8 experts over every token would cost: 3.7 TFLOP a layer
    assert f.moe_gmm(cfg, traffic, 2, 8192 * 8)[0] == pytest.approx(
        3.7e12, rel=0.01)


def test_flops_against_xla_cost_analysis():
    """XLA's count of the reference at a small size, one row without
    recomputation. XLA counts full (not causal, not windowed) attention and
    the reference computes every held expert over every token, so both are
    put on the reference's footing."""
    import jax
    import jax.numpy as jnp
    from benchmark.references import laguna as ref
    f = _flops()
    t = 64
    cfg = dict(_full_config(), hidden_size=128, head_dim=32,
               num_key_value_heads=2, intermediate_size=512,
               num_attention_heads_per_layer=[4, 6], sliding_window=16,
               layer_types=["full_attention", "sliding_attention"],
               mlp_layer_types=["dense", "sparse"], num_hidden_layers=2,
               vocab_size=1024, router_num_experts=16, num_experts=4,
               num_experts_per_tok=3, moe_intermediate_size=64,
               shared_expert_intermediate_size=64)
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s, _ in ref.param_specs(cfg)}
    tokens = jax.ShapeDtypeStruct((t,), jnp.int32)
    cost = jax.jit(lambda p, x: ref.row_logits(p, x, cfg, remat=False)) \
        .lower(shapes, tokens).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    d, dh, fe = 128, 32, 64
    masked = (4 * 4 * dh * (t - (t + 1) / 2)
              + 4 * 6 * dh * (t - f._keys_per_query(t, 16)))
    # the reference's scan over the 4 held experts is a loop XLA counts once
    experts = (1 - f.pairs_per_token_expected(cfg)) * 6 * d * fe
    mine = t * (f.forward_flops_per_token(cfg, t) + masked + experts)
    assert cost["flops"] == pytest.approx(mine, rel=0.1)


# ------------------------------------------------- the per-layer readers
def _made_up_trace():
    """Two step programs of 100 ms, each with a full and a window kernel
    pair, two dispatch loops with three grouped products inside each, and a
    fusion that only reads a kernel's output."""
    from benchmark import trace
    ms = 1_000_000
    ops, modules = [], []
    for step in range(2):
        t0 = t = (10 + 100 * step) * ms
        modules.append(("jit_mxtpu_fused_step(1)", t0, t0 + 100 * ms))
        for name, dur in (("mxtpu_flash_fwd", 3), ("mxtpu_flash_win_fwd", 2),
                          ("mxtpu_flash_win_bwd", 5), ("mxtpu_flash_bwd", 7)):
            ops.append(("%%%s.1 = (bf16[96,4096,128]{2,1,0}) custom-call(%%x), "
                        "custom_call_target=\"tpu_custom_call\"" % name,
                        t, t + dur * ms))
            t += dur * ms
        ops.append(("%fusion.7 = bf16[2,4096,9216]{2,1,0} fusion("
                    "%mxtpu_flash_win_fwd.1), kind=kLoop", t, t + 1 * ms))
        t += 1 * ms
        for loop in range(2):
            ops.append(("%%while.%d = (s32[], f32[8192,3072]{1,0}) while(%%t)"
                        ", condition=%%c, body=%%b" % loop, t, t + 4 * ms))
            for k in range(3):
                ops.append((
                    "%%ragged-dot-none.%d = f32[4096,1024]{1,0} custom-call("
                    "%%a, %%b), custom_call_target=\"tpu_custom_call\""
                    % (3 * loop + k), t + k * ms, t + (k + 1) * ms))
            ops.append(("%ragged-dot-metadata = (s32[9]{0}) custom-call(%gs)",
                        t + 3 * ms, t + 3 * ms + 1000))
            t += 4 * ms
    devices = {"/device:TPU:0": {"ops": ops, "modules": modules}}
    return trace.Trace(devices, [(trace.WINDOW_SPAN, 0, 300 * ms)])


def _facts(cell=None):
    cell = cell or manifest.Cell(CELL)
    return {"trace": _made_up_trace(), "cell": cell, "config": cell.config,
            "traffic": cell.traffic, "batch_per_chip": 2,
            "items_per_step": 8192, "peaks": PEAKS}


def test_the_window_readers_take_each_kernel_by_its_name():
    cell, f = manifest.Cell(CELL), _flops()
    facts = _facts(cell)
    for name, need, ms in (("flash_win_fwd_roofline", f.flash_win_fwd, 2),
                           ("flash_win_bwd_roofline", f.flash_win_bwd, 5),
                           ("mxtpu_flash_fwd_roofline", f.flash_fwd, 3),
                           ("mxtpu_flash_bwd_roofline", f.flash_bwd, 7)):
        got = cell.reader(name).read(facts)
        assert got == pytest.approx(
            100 * need(cell.config, cell.traffic, 2)[0] / 197e12 / (ms * 1e-3))
        assert 0 < got < 100
    # the four kernels, 17 ms of a 100 ms step; the fusion is not theirs
    assert cell.reader("attention_device_share").read(facts) == \
        pytest.approx(17.0)
    # the two loops, 8 ms of a 100 ms step
    assert cell.reader("moe_device_share").read(facts) == pytest.approx(8.0)


def test_the_expert_readers_read_the_programs_counters():
    from mxtpu import telemetry
    cell, f = manifest.Cell(CELL), _flops()
    facts = _facts(cell)
    telemetry.gauge("moe_load_max_over_mean").set(0)
    assert cell.reader("moe_load_max_over_mean").read(facts) is None
    telemetry.gauge("moe_load_max_over_mean").set(1.17)
    assert cell.reader("moe_load_max_over_mean").read(facts) == 1.17
    # counters only count up: bring their totals to 5 pairs in 16 tokens
    pairs, tokens = (telemetry.counter(c) for c in ("moe_pairs_routed",
                                                    "moe_tokens_seen"))
    total = 16 * (10 ** 6 + int(tokens.value))
    pairs.inc(total * 5 // 16 - int(pairs.value))
    tokens.inc(total - int(tokens.value))
    assert cell.reader("moe_pairs_per_token").read(facts) == 0.3125
    flops, bytes_ = f.moe_gmm(cell.config, cell.traffic, 2, 2560)
    least = 4 * max(flops / 197e12, bytes_ / 819e9)
    # six grouped products of 1 ms a step; the metadata calls are not counted
    assert cell.reader("moe_gmm_roofline").read(facts) == \
        pytest.approx(100 * least / 6e-3, rel=1e-3)


def test_a_program_without_what_this_pr_adds_reads_nothing():
    """The parent's trace (no window kernel, no loop, no grouped product)
    and the other cells: nothing, and no error."""
    from benchmark import trace
    cell = manifest.Cell(CELL)
    bare = trace.Trace(
        {"/device:TPU:0": {"ops": [("%fusion.1 = f32[8]{0} fusion(%x)", 0, 5)],
                           "modules": [("jit_step(1)", 0, 5)]}}, [])
    names = ("flash_win_fwd_roofline", "flash_win_bwd_roofline",
             "moe_gmm_roofline", "moe_device_share", "attention_device_share")
    for name in names:
        assert cell.reader(name).read(dict(_facts(cell), trace=bare)) is None
        assert cell.reader(name).read(dict(_facts(cell), trace=None)) is None
        assert cell.reader(name).read({}) is None
    opt = manifest.Cell("opt-1.3b-fit-s1024")
    other = dict(_facts(cell), cell=opt, config=opt.config,
                 traffic=opt.traffic)
    for name in ("flash_win_fwd_roofline", "flash_win_bwd_roofline",
                 "moe_gmm_roofline"):
        assert cell.reader(name).read(other) is None


def test_the_cell_reports_what_its_entry_lists():
    cell = manifest.Cell(CELL)
    due = [m["name"] for m in cell.per_layer()]
    for name in ("flash_win_fwd_roofline", "flash_win_bwd_roofline",
                 "moe_gmm_roofline", "moe_device_share",
                 "attention_device_share", "moe_pairs_per_token",
                 "moe_load_max_over_mean", "mxtpu_flash_fwd_roofline",
                 "mxtpu_flash_bwd_roofline", "mfu.train", "step_device_ms",
                 "device_idle_share.train", "hbm_peak_gb.train",
                 "fit_host_wait_share", "idle_unnamed_share", "compile_s",
                 "window_compiles"):
        assert name in due, name
    assert "flash_fwd_roofline" not in due and "delta_rule_roofline" not in due
    for other in ("opt-1.3b-fit-s1024", "resnet50-fit-b256",
                  "olmo-hybrid-7b-fit-s2048"):
        theirs = [m["name"] for m in manifest.Cell(other).per_layer()]
        assert not [n for n in theirs if n.startswith(("flash_win", "moe_"))
                    or n == "attention_device_share"]
    bench = manifest.read_json(os.path.join(os.path.dirname(BENCH),
                                            "BENCHMARK.json"))
    row = [c for c in bench["configs"] if c["name"] == "laguna-s-2.1-train"][0]
    assert row["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cell.row["chips"] == 1 and cell.row["traffic"] == "tokens-s4096-b2"
    assert (cell.traffic["batch_per_chip"], cell.traffic["seq_len"]) == (2, 4096)


def test_the_configuration_keeps_every_published_width():
    cfg = _full_config()
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["rms_norm_eps"],
            cfg["moe_routed_scaling_factor"]) == (
                3072, 128, 8, 12288, 1024, 1024, 10, 512, 1e-6, 2.5)
    n = cfg["num_hidden_layers"]
    assert n == 5 and len(cfg["layer_types"]) == 48
    assert cfg["layer_types"][:n] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_attention_heads_per_layer"][:n] == [48, 72, 72, 72, 48]
    assert cfg["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    # the router stays 256 wide with 10 a token; 8 experts are held
    assert (cfg["router_num_experts"], cfg["num_experts"], cfg["experts_held"],
            cfg["expert_offset"]) == (256, 8, 8, 0)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert sorted(cfg["reduced_why"]) == sorted(cfg["published"])
    assert "32 chips" in cfg["deployment"]
    for key in ("block_order", "qk_norm", "gate", "router", "shared_expert"):
        assert key in cfg["assumed"], key
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["partial_rotary_factor"]) == ("yarn", 500000, 128, 0.5)
    from benchmark.references import laguna as ref
    params = sum(int(np.prod(s)) for _, s, _ in ref.param_specs(cfg))
    assert params == pytest.approx(811e6, rel=0.001)
