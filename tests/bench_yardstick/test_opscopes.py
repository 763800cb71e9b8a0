"""benchmark/opscopes.py: the fused step's operations, every instant counted
once, joined with the scope table the program keeps; and the seven readers
built on it, on a case worked out by hand."""
import os

import pytest

from benchmark import manifest, opscopes, trace

ALL = ["opt-1.3b-fit-s1024", "resnet50-fit-b256", "olmo-hybrid-7b-fit-s2048",
       "laguna-s-2.1-fit-s4096", "nemotron-twotower-30b-fit-s4096"]
LM = [c for c in ALL if c != "resnet50-fit-b256"]
READERS = {
    "step_scoped_share": ("higher", ALL),
    "attention_block_device_share": ("lower", LM),
    "expert_block_device_share": ("lower", ALL[3:]),
    "mixer_block_device_share": ("lower", [ALL[2], ALL[4]]),
    "head_block_device_share": ("lower", LM),
    "batchnorm_device_share": ("lower", ["resnet50-fit-b256"]),
    "update_device_share": ("lower", ALL),
}

TABLE = {
    "fusion.1": ("l0_q", "FullyConnected", "attention", "forward", False),
    "mxtpu_flash_fwd.2": ("l0_attn", "_contrib_FlashAttention", "attention",
                          "forward", False),
    "while.3": ("l0_experts", "_contrib_MoEExperts", "experts", "backward",
                False),
    "ragged-dot.4": ("l0_experts", "_contrib_MoEExperts", "experts",
                     "backward", False),
    "fusion.5": ("l0_ssd", "_contrib_SSDScan", "mamba2", "backward", True),
    "fusion.6": ("lm_head", "FullyConnected", "head", "update", False),
    "fusion.7": ("bn0", "BatchNorm", "stem", "forward", False),
    "fusion.8": ("l0_q", "FullyConnected", "attention", "update", False),
    "copy.9": ("", "", "", "unscoped", False),
}


def hlo(name, kind="fusion"):
    return "%%%s = bf16[8,8]{1,0} %s(%%p.1), kind=kLoop" % (name, kind)


def made_up():
    """Two whole runs of the step in a window of 1000 ns, [100, 400] and
    [500, 800], and one cut by its end. A run, from its start: fusion.1
    0-40, the attention kernel 40-100, the loop 100-200 with its grouped
    product 120-180 inside, fusion.5 200-230, fusion.6 230-250, fusion.7
    250-260, fusion.8 260-270, a copy 270-280, an operation nobody has
    heard of 280-290 (10 idle). Busy 290 ns a run."""
    run = [("fusion.1", 0, 40), ("mxtpu_flash_fwd.2", 40, 100),
           ("while.3", 100, 200), ("ragged-dot.4", 120, 180),
           ("fusion.5", 200, 230), ("fusion.6", 230, 250),
           ("fusion.7", 250, 260), ("fusion.8", 260, 270),
           ("copy.9", 270, 280), ("fusion.99", 280, 290)]
    ops, modules = [], []
    for at in (100, 500, 900):
        modules.append(("jit_mxtpu_fused_step(77)", at, at + 300))
        ops += [(hlo(n, "custom-call" if n.startswith("mxtpu_") else
                     "fusion"), s + at, e + at) for n, s, e in run]
    ops.append((hlo("fusion.1"), 420, 480))     # another program's
    modules.append(("jit_mxtpu_metric_accum(5)", 410, 490))
    return trace.Trace({"/device:TPU:0": {"ops": ops, "modules": modules}},
                       [(trace.WINDOW_SPAN, 0, 1000)])


def test_exclusive_time_adds_to_the_busy_time():
    events = [("while", 0, 100), ("a", 10, 30), ("b", 30, 50),
              ("cond", 60, 90), ("branch", 65, 85),
              ("overlaps", 95, 120), ("alone", 200, 210)]
    got = opscopes.exclusive(events)
    assert got == {"while": 25, "a": 20, "b": 20, "cond": 10, "branch": 20,
                   "overlaps": 25, "alone": 10}
    busy = trace.total(trace.union((s, e) for _, s, e in events))
    assert sum(got.values()) == busy == 130


def test_whole_runs_of_the_step_inside_the_window():
    acc, runs, runs_ns = opscopes.step_ops(made_up())
    assert runs == 2 and runs_ns == 600
    assert sum(acc.values()) == 2 * 290
    assert acc[hlo("while.3")] == 2 * 40 and acc[hlo("ragged-dot.4")] == 120
    assert acc[hlo("fusion.1")] == 2 * 40       # not the other program's
    assert opscopes.step_ops(trace.Trace({}, [])) is None
    assert opscopes.step_ops(None) is None


@pytest.mark.parametrize("name,want", [
    ("step_scoped_share", 100 * 270 / 290),
    ("attention_block_device_share", 100 * 60 / 290),   # not the projection
    ("expert_block_device_share", 100 * 100 / 290),     # loop and body
    ("mixer_block_device_share", 100 * 30 / 290),
    ("head_block_device_share", 100 * 20 / 290),        # the head's update
    ("batchnorm_device_share", 100 * 10 / 290),
    ("update_device_share", 100 * 30 / 290),
])
def test_each_reader_on_the_made_up_case(name, want, monkeypatch):
    monkeypatch.setattr(opscopes, "scope_table", lambda: TABLE)
    reader = manifest.load_module(
        os.path.join(manifest.HERE, "metrics", name + ".py"), "reader_" + name)
    facts = {"trace": made_up()}
    assert reader.read(facts) == pytest.approx(want)
    # an entry of the cells it can be read in, with its reader's file
    bench = manifest.read_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": READERS[name][0],
                     "source": "device_trace", "layer": "step programs",
                     "moves": "train_throughput",
                     "workloads": READERS[name][1]}
    # a program that keeps no table (this PR's parent), and a run without a
    # device plane (a CPU rehearsal), leave the metric out
    monkeypatch.setattr(opscopes, "scope_table", lambda: None)
    assert reader.read({"trace": made_up()}) is None
    monkeypatch.setattr(opscopes, "scope_table", lambda: TABLE)
    assert reader.read({"trace": trace.Trace({}, [])}) is None
    assert reader.read({"trace": None}) is None


def test_the_shares_add_to_a_hundred():
    sc = opscopes.build(made_up(), TABLE)
    blocks = {}
    for _, ns, scope, _ in sc.rows:
        key = scope[2] if scope[3] != "unscoped" else "unscoped"
        blocks[key] = blocks.get(key, 0) + ns
    assert sum(blocks.values()) == sc.total_ns == 580
    assert sum(sc.share(lambda n, o, b, p, want=want:
                        (b if p != "unscoped" else "unscoped") == want)
               for want in blocks) == pytest.approx(100)
    line = opscopes.summary(sc)
    assert "\n" not in line and line.startswith("runs=2 runs_ns=600 ops_ns=580")
    assert "mixed_share=%.4f" % (100 * 60 / 580) in line
    assert "unscoped=%.4f" % (100 * 40 / 580) in line
    assert "experts/_contrib_MoEExperts/backward=200" in line
    assert "unscoped_top copy.9=20 fusion.99=20" in line
    assert "attention/_contrib_FlashAttention=120" in line
    assert "custom_calls_by_name mxtpu_flash_fwd=120" in line
    # the blocks' shares in the line, with unscoped, add to a hundred
    shares = line.split("blocks ")[1].split(" rows ")[0].split()
    assert sum(float(s.split("=")[1]) for s in shares) == pytest.approx(
        100, abs=1e-3)


def test_a_process_without_the_table_reads_nothing(monkeypatch):
    """The join, as the parent's process answers it: a ProgramRecord that
    has no `op_scopes`."""
    from mxtpu import diagnostics as diag
    monkeypatch.delattr(diag.ProgramRecord, "op_scopes")
    assert opscopes.scope_table() is None
    facts = {"trace": made_up()}
    assert opscopes.load(facts) is None and facts["op_scopes"] is None
