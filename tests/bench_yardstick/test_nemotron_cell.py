"""`nemotron-twotower-30b-fit-s4096` at the files' tiny `rehearsal` sizes on
the CPU: the Symbol against the plain reference, what decides `correct`
(sound runs pass on three seeds; the fp8 control, half of the batch left
out, the decay left out and the routed weights normalised over the held
experts each fail), the counts of operations and bytes, and the readers of
what the cell adds."""
import json
import os

import numpy as np
import pytest

from benchmark import compare, manifest, run

CELL = "nemotron-twotower-30b-fit-s4096"
CONFIG = "nemotron-twotower-30b-train"
BENCH = os.path.dirname(manifest.__file__)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    picks = reference.route_choices(params, jnp.asarray(ids, jnp.int32), cfg)
    assert len(outs) == 3       # MEMEM*, the rehearsal's depth: two E layers
    for pick, load in zip(picks, outs[1:]):
        want_load = [(np.asarray(pick) == e).sum() for e in range(4)]
        assert load.asnumpy().tolist() == want_load and sum(want_load) > 0


def test_a_sound_run_is_correct(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 34),
                   "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    out = _last_json(capsys.readouterr().out, "REHEARSAL ")
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert set(out["metrics"]) == {"train_throughput", "setup_s"}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("seed", [3, 3300034105])
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


@pytest.mark.parametrize("fault", ["no_decay", "held_norm"])
def test_this_architectures_own_faults_are_not_correct(cell, sound, fault):
    """exp(dt A) replaced by 1 in the Mamba-2 layers; the routed weights
    normalised over the experts held here in place of all chosen: each in
    the reference's copy, each judged not correct."""
    _, (rows, ok) = _judged(cell, sound, fault=fault)
    assert not ok, rows


def test_the_faults_move_what_they_should():
    import jax
    import jax.numpy as jnp
    from benchmark.references import nemotron_h as ref
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
    # the decay: with a = None the state is the running sum of the writes,
    # so the first token reads the same and a later one does not
    xs = jnp.asarray(rng.normal(size=(32, 2, 4)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 1.0, size=(32, 2)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(32, 2, 8)), jnp.float32)
              for _ in range(2))
    with jax.default_matmul_precision("highest"):
        sound = ref._recurrence(xs, dt, -jnp.ones((2,)), bm, cm, lambda a: a)
        kept = ref._recurrence(xs, dt, None, bm, cm, lambda a: a)
        summed = jnp.einsum(
            "thn,thp->thnp", bm, dt[..., None] * xs).cumsum(0)
        want = jnp.einsum("thnp,thn->thp", summed, cm)
    np.testing.assert_allclose(kept, want, rtol=1e-4, atol=1e-4)
    # the first token's state is its own write, decayed or not
    np.testing.assert_allclose(
        sound[0], kept[0], rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(sound[8:] - kept[8:]))) > 1e-2


# --------------------------------------------------- operations and bytes
def _full_config():
    return manifest.read_json(os.path.join(
        BENCH, "configs", CONFIG, "config.json"))


def _flops():
    return manifest.load_module(os.path.join(
        BENCH, "configs", CONFIG, "flops.py"), "flops_nemotron")


def test_flops_by_hand_and_the_issues_shares():
    cfg, f = _full_config(), _flops()
    d, v = 2688, 16384
    in_proj = 2 * d * (4096 + 6144 + 64)
    out_proj = 2 * 4096 * d
    conv, scan = 2 * 4 * 6144, 5 * 128 * 64 * 64
    mamba = in_proj + out_proj + conv + scan
    shared, router, routed = 4 * d * 3712, 2 * d * 128, 0.375 * 4 * d * 1856
    assert f.pairs_per_token_expected(cfg) == 0.375
    attention = (2 * d * (4096 + 2 * 256) + 2 * 4096 * d
                 + 4 * 32 * 128 * 4097 / 2)
    head = 2 * v * d
    total = 4 * mamba + 4 * (shared + router + routed) + attention + head
    assert f.forward_flops_per_token(cfg, 4096) == pytest.approx(
        total, rel=1e-12)
    assert f.train_flops_per_item(cfg, {"seq_len": 4096}) == \
        pytest.approx(3 * total, rel=1e-12)
    # ISSUE 34: 681 M flops a token forward, 16.7 TFLOP a step; the Mamba-2
    # layers 47% (projections 45.5, scan and convolution 1.5), the expert
    # layers 28% (shared 23.4, routed 4.4, router 0.4), the head 13%, the
    # attention layer 12%
    assert total == pytest.approx(681e6, rel=0.001)
    assert 3 * total * 8192 == pytest.approx(16.7e12, rel=0.005)
    share = lambda x: 100 * x / total  # noqa: E731
    assert share(4 * mamba) == pytest.approx(47, abs=0.5)
    assert share(4 * (in_proj + out_proj)) == pytest.approx(45.5, abs=0.1)
    assert share(4 * (conv + scan)) == pytest.approx(1.5, abs=0.1)
    assert share(4 * shared) == pytest.approx(23.4, abs=0.1)
    assert share(4 * routed) == pytest.approx(4.4, abs=0.1)
    assert share(4 * router) == pytest.approx(0.4, abs=0.05)
    assert share(head) == pytest.approx(13, abs=0.1)
    assert share(attention) == pytest.approx(12, abs=0.3)


def test_the_kernels_flops_and_bytes_by_hand():
    cfg, f = _full_config(), _flops()
    traffic = {"seq_len": 4096}
    tokens = 2 * 4096
    flops, bytes_ = f.ssd(cfg, traffic, 2)
    assert flops == tokens * 64 * 5 * 128 * 64
    # x and y per head, dt in float32, B and C once a group of 8 heads
    assert bytes_ == tokens * (2 * 4096 * 2 + 64 * 4 + 2 * 8 * 128 * 2)
    bflops, bbytes = f.ssd_bwd(cfg, traffic, 2)
    assert bflops == 2 * flops
    assert bbytes == tokens * (4 * 4096 * 2 + 3 * 64 * 4 + 4 * 8 * 128 * 2)
    for fl, by in ((flops, bytes_), (bflops, bbytes)):
        assert by / 819e9 > fl / 197e12            # the bytes bound both
        assert fl / by == pytest.approx(126, abs=1)
    t, dh, heads = 4096, 128, 32
    pairs = 2 * heads * t * 4097 / 2 * dh
    per_q, per_kv, rows = 2 * heads * t * dh, 2 * 2 * t * dh, 2 * heads * t
    flops, bytes_ = f.flash_fwd(cfg, traffic, 2)
    assert flops == pytest.approx(4 * pairs, rel=1e-12)
    assert bytes_ == (2 * per_q + 2 * per_kv) * 2 + rows * 4
    bflops, bbytes = f.flash_bwd(cfg, traffic, 2)
    assert bflops == pytest.approx(10 * pairs, rel=1e-12)
    assert bbytes == (3 * per_q + 4 * per_kv) * 2 + 2 * rows * 4
    assert flops / 197e12 > bytes_ / 819e9         # the flops bound these
    # two products a pair, three passes; 6 x 8 / 128 of 8,192 tokens
    flops, bytes_ = f.moe_gmm(cfg, traffic, 2, 3072)
    assert flops == 3 * 3072 * 4 * 2688 * 1856
    assert bytes_ == 3 * (3072 * (2 * 2688 + 2 * 1856) * 2
                          + 2 * 8 * 2688 * 1856 * 2)


def test_flops_against_xla_cost_analysis():
    """XLA's count of the reference at a small size, one row without
    recomputation. XLA counts full (not causal) attention, every held
    expert over every token (a loop it counts once), the convolution's and
    the recurrence's elementwise products one by one, and a `lax.scan` over
    tokens once: so the scan is left out of both sides and the rest is put
    on the reference's footing."""
    import jax
    import jax.numpy as jnp
    from benchmark.references import nemotron_h as ref
    f = _flops()
    t = 64
    cfg = dict(_full_config(), hidden_size=128, head_dim=32,
               num_attention_heads=4, num_key_value_heads=2,
               mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
               ssm_state_size=16, hybrid_override_pattern="ME*",
               num_hidden_layers=3, vocab_size=1024, router_num_experts=16,
               n_routed_experts=4, num_experts_per_tok=3,
               moe_intermediate_size=64,
               moe_shared_expert_intermediate_size=128)
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s, _ in ref.param_specs(cfg)}
    tokens = jax.ShapeDtypeStruct((t,), jnp.int32)
    cost = jax.jit(lambda p, x: ref.row_logits(p, x, cfg, remat=False)) \
        .lower(shapes, tokens).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    d, fe = 128, 64
    masked = 4 * 4 * 32 * (t - (t + 1) / 2)
    experts = (1 - f.pairs_per_token_expected(cfg)) * 4 * d * fe
    scan = 5 * 16 * 16 * 4
    mine = t * (f.forward_flops_per_token(cfg, t) + masked + experts - scan)
    assert cost["flops"] == pytest.approx(mine, rel=0.1)


# ------------------------------------------------- the per-layer readers
def _made_up_trace():
    """Two step programs of 100 ms, each with four scan forwards and four
    backwards, a flash pair, two dispatch loops with two grouped products
    inside each, and a fusion that only reads a kernel's output."""
    from benchmark import trace
    ms = 1_000_000
    ops, modules = [], []
    for step in range(2):
        t0 = t = (10 + 100 * step) * ms
        modules.append(("jit_mxtpu_fused_step(1)", t0, t0 + 100 * ms))
        for name, dur, calls in (("mxtpu_ssd_fwd", 1, 4),
                                 ("mxtpu_ssd_bwd", 2, 4),
                                 ("mxtpu_flash_fwd", 3, 1),
                                 ("mxtpu_flash_bwd", 7, 1)):
            for call in range(calls):
                ops.append(("%%%s.%d = (bf16[2,4096,4096]{2,1,0}) custom-call("
                            "%%x), custom_call_target=\"tpu_custom_call\""
                            % (name, call), t, t + dur * ms))
                t += dur * ms
        ops.append(("%fusion.7 = bf16[2,4096,4096]{2,1,0} fusion("
                    "%mxtpu_ssd_fwd.1), kind=kLoop", t, t + 1 * ms))
        t += 1 * ms
        for loop in range(2):
            ops.append(("%%while.%d = (s32[], f32[8192,2688]{1,0}) while(%%t)"
                        ", condition=%%c, body=%%b" % loop, t, t + 4 * ms))
            for k in range(2):
                ops.append((
                    "%%ragged-dot-none.%d = f32[4096,1856]{1,0} custom-call("
                    "%%a, %%b), custom_call_target=\"tpu_custom_call\""
                    % (2 * loop + k), t + k * ms, t + (k + 1) * ms))
            t += 4 * ms
    devices = {"/device:TPU:0": {"ops": ops, "modules": modules}}
    return trace.Trace(devices, [(trace.WINDOW_SPAN, 0, 300 * ms)])


def _facts(cell=None):
    cell = cell or manifest.Cell(CELL)
    return {"trace": _made_up_trace(), "cell": cell, "config": cell.config,
            "traffic": cell.traffic, "batch_per_chip": 2,
            "items_per_step": 8192, "peaks": PEAKS}


def test_the_scans_readers_take_each_kernel_by_its_name():
    from mxtpu import telemetry
    from mxtpu.ops import ssd
    assert (ssd.FWD_KERNEL_NAME, ssd.BWD_KERNEL_NAME) == \
        ("mxtpu_ssd_fwd", "mxtpu_ssd_bwd")
    cell, f = manifest.Cell(CELL), _flops()
    facts = _facts(cell)
    least = sum(need(cell.config, cell.traffic, 2)[1] / 819e9
                for need in (f.ssd, f.ssd_bwd))
    got = cell.reader("ssd_roofline").read(facts)
    # one forward of 1 ms and one backward of 2 ms, the mean call of each
    assert got == pytest.approx(100 * least / 3e-3) and 0 < got < 100
    # 4 x 1 + 4 x 2 ms of a 100 ms step; the fusion is not the kernels'
    assert cell.reader("ssd_device_share").read(facts) == pytest.approx(12.0)
    # the flash pair alone is attention's; the two loops the experts'
    assert cell.reader("attention_device_share").read(facts) == \
        pytest.approx(10.0)
    assert cell.reader("moe_device_share").read(facts) == pytest.approx(8.0)
    for name, need, ms in (("mxtpu_flash_fwd_roofline", f.flash_fwd, 3),
                           ("mxtpu_flash_bwd_roofline", f.flash_bwd, 7)):
        assert cell.reader(name).read(facts) == pytest.approx(
            100 * need(cell.config, cell.traffic, 2)[0] / 197e12 / (ms * 1e-3))
    # the gauge is one differentiated call's; the cell holds four M layers
    telemetry.gauge("ssd_state_saved_bytes").set(0)
    assert cell.reader("ssd_state_saved_gb").read(facts) is None
    per_call = 2 * 64 * 32 * 128 * 64 * 4
    telemetry.gauge("ssd_state_saved_bytes").set(per_call)
    assert cell.reader("ssd_state_saved_gb").read(facts) == \
        pytest.approx(4 * per_call / 1e9)
    assert 4 * per_call / 1e9 == pytest.approx(0.537, abs=0.001)


def test_the_expert_readers_read_the_relu2_layers_counters():
    from mxtpu import telemetry
    cell, f = manifest.Cell(CELL), _flops()
    facts = _facts(cell)
    telemetry.gauge("moe_load_max_over_mean").set(1.25)
    assert cell.reader("moe_load_max_over_mean").read(facts) == 1.25
    # counters only count up: bring their totals to 3 pairs in 8 tokens
    pairs, tokens = (telemetry.counter(c) for c in ("moe_pairs_routed",
                                                    "moe_tokens_seen"))
    total = 8 * (10 ** 6 + int(tokens.value))
    pairs.inc(total * 3 // 8 - int(pairs.value))
    tokens.inc(total - int(tokens.value))
    assert cell.reader("moe_pairs_per_token").read(facts) == 0.375
    flops, bytes_ = f.moe_gmm(cell.config, cell.traffic, 2, 3072)
    least = 4 * max(flops / 197e12, bytes_ / 819e9)
    # four grouped products of 1 ms a step
    assert cell.reader("moe_gmm_roofline").read(facts) == \
        pytest.approx(100 * least / 4e-3, rel=1e-3)


def test_a_program_without_what_this_pr_adds_reads_nothing():
    """The parent's trace (no scan kernel) and the other cells: nothing,
    and no error."""
    from benchmark import trace
    cell = manifest.Cell(CELL)
    bare = trace.Trace(
        {"/device:TPU:0": {"ops": [("%fusion.1 = f32[8]{0} fusion(%x)", 0, 5)],
                           "modules": [("jit_step(1)", 0, 5)]}}, [])
    for name in ("ssd_roofline", "ssd_device_share"):
        assert cell.reader(name).read(dict(_facts(cell), trace=bare)) is None
        assert cell.reader(name).read(dict(_facts(cell), trace=None)) is None
        assert cell.reader(name).read({}) is None
    assert cell.reader("ssd_state_saved_gb").read({}) is None
    for other in ("olmo-hybrid-7b-fit-s2048", "laguna-s-2.1-fit-s4096"):
        theirs = manifest.Cell(other)
        facts = dict(_facts(cell), cell=theirs, config=theirs.config,
                     traffic=theirs.traffic)
        assert cell.reader("ssd_roofline").read(facts) is None
        assert cell.reader("ssd_state_saved_gb").read(facts) is None


def test_the_cell_reports_what_its_entry_lists():
    cell = manifest.Cell(CELL)
    due = [m["name"] for m in cell.per_layer()]
    for name in ("ssd_roofline", "ssd_device_share", "ssd_state_saved_gb",
                 "moe_gmm_roofline", "moe_device_share",
                 "attention_device_share", "moe_pairs_per_token",
                 "moe_load_max_over_mean", "mxtpu_flash_fwd_roofline",
                 "mxtpu_flash_bwd_roofline", "mfu.train", "step_device_ms",
                 "device_idle_share.train", "hbm_peak_gb.train",
                 "fit_host_wait_share", "idle_unnamed_share", "compile_s",
                 "window_compiles"):
        assert name in due, name
    assert not [n for n in due if n.startswith(("flash_win", "delta_rule"))
                or n == "flash_fwd_roofline"]
    for other in ("opt-1.3b-fit-s1024", "resnet50-fit-b256",
                  "olmo-hybrid-7b-fit-s2048", "laguna-s-2.1-fit-s4096"):
        theirs = [m["name"] for m in manifest.Cell(other).per_layer()]
        assert not [n for n in theirs if n.startswith("ssd_")]
    bench = manifest.read_json(os.path.join(os.path.dirname(BENCH),
                                            "BENCHMARK.json"))
    row = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert row["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "ssd_roofline", "ssd_device_share", "ssd_state_saved_gb"]
    assert cell.row["chips"] == 1 and cell.row["traffic"] == "tokens-s4096-b2"
    assert (cell.traffic["batch_per_chip"],
            cell.traffic["seq_len"]) == (2, 4096)
    limits = manifest.read_json(os.path.join(BENCH, "cells", CELL + ".json"))
    for name in limits["limits"]:
        assert "upper" in limits["readings"][name], name


def test_the_configuration_keeps_every_published_width():
    cfg = _full_config()
    reduced = {"num_hidden_layers": 9, "n_routed_experts": 8,
               "vocab_size": 16384}
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            rows = [json.loads(line) for line in fh]
        row = [r for r in rows if r["source_url"] == cfg["source"]][0]
        assert row["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"
        for key, value in row["config"].items():
            assert cfg[key] == reduced.get(key, value), key
        assert cfg["published"] == {k: row["config"][k] for k in reduced}
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
            cfg["chunk_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["layer_norm_epsilon"], cfg["mlp_hidden_act"]) == (
                2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 6, 2.5,
                1e-5, "relu2")
    n = cfg["num_hidden_layers"]
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == 52 and pattern[:n] == "MEMEM*EME"
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == \
        (23, 23, 6)
    assert cfg["mlp_layer_types"] == [
        "sparse" if c == "E" else "none" for c in pattern]
    assert cfg["layer_types"] == [
        {"M": "mamba2", "*": "full_attention", "E": "none"}[c]
        for c in pattern]
    # the router stays 128 wide with 6 a token; 8 experts are held
    assert (cfg["router_num_experts"], cfg["n_routed_experts"],
            cfg["experts_held"], cfg["expert_offset"]) == (128, 8, 8, 0)
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "n_routed_experts": 128, "vocab_size": 131072}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert sorted(cfg["reduced_why"]) == sorted(cfg["published"])
    assert "16 chips" in cfg["deployment"] and "1/16" in cfg["deployment"]
    for word in ("denoising tower", "adaLN", "cross-tower", "diffusion"):
        assert word in cfg["left_out"], word
    for key in ("block_order", "positions", "attention", "mamba2", "router",
                "experts", "init", "optimizer"):
        assert key in cfg["assumed"], key
    stated = cfg["param_dtypes"]
    assert {k for k, v in stated.items() if v == "float32"} == \
        {"tok_emb_weight"} | {"l%d_router_weight" % i for i in (1, 3, 6, 8)} \
        | {"l%d_%s" % (i, name) for i in (0, 2, 4, 7)
           for name in ("A_log", "dt_bias", "D")}
    from benchmark.references import nemotron_h as ref
    params = sum(int(np.prod(s)) for _, s, _ in ref.param_specs(cfg))
    assert params == pytest.approx(667e6, rel=0.001)
