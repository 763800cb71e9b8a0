"""The scope table of a compiled step (mxtpu.diagnostics.opscopes,
ProgramRecord.op_scopes) and its reader in mx.profiler: HLO instruction ->
graph node, operator, model block and phase; device time by node from a
trace's operations."""
import contextlib
import gc
import re

import jax
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import diagnostics as diag
from mxtpu import profiler
from mxtpu.diagnostics import opscopes
from mxtpu.diagnostics.programs import ProgramRecord, _LOCK, _RECORDS
from mxtpu.models import decoder, resnet, transformer

STEP = "jit_mxtpu_fused_step"
OP = "jit(mxtpu_fused_step)/"

# a fusion of two nodes around a matmul, one of two nodes without one, a
# loop with a body, a conditional with two branches, a recomputed forward
# and two copies XLA made itself, one between instructions of one node
HLO = """HloModule jit_mxtpu_fused_step, is_scheduled=true, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_computation.1 (param_0.1: f32[8,8], param_1.2: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %param_1.2 = f32[8,8]{1,0} parameter(1)
  %dot.3 = f32[8,8]{1,0} dot(%param_0.1, %param_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(mxtpu_fused_step)/transpose(jvp(fc1))/dot_general" source_file="a.py" source_line=3}
  ROOT %multiply.4 = f32[8,8]{1,0} multiply(%dot.3, %param_1.2), metadata={op_name="jit(mxtpu_fused_step)/mxtpu.update/fc2_weight/mul"}
}

%fused_computation.2 (param_0.5: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %param_0.5 = f32[8,8]{1,0} parameter(0)
  %add.6 = f32[8,8]{1,0} add(%param_0.5, %param_0.5), metadata={op_name="jit(mxtpu_fused_step)/jvp(fc1)/add"}
  %maximum.7 = f32[8,8]{1,0} maximum(%add.6, %param_0.5), metadata={op_name="jit(mxtpu_fused_step)/jvp(relu1)/max"}
  ROOT %tuple.8 = (f32[8,8]{1,0}, /*index=1*/f32[8,8]{1,0}) tuple(%add.6, %maximum.7)
}

%fused_computation.3 (param_0.34: f32[8,8]) -> f32[8,8] {
  %param_0.34 = f32[8,8]{0,1} parameter(0)
  ROOT %copy.35 = f32[8,8]{1,0} copy(%param_0.34)
}

%body.9 (arg.10: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg.10 = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte.11 = f32[8,8]{1,0} get-tuple-element(%arg.10), index=1
  %ragged-dot.12 = f32[8,8]{1,0} ragged-dot(%gte.11, %gte.11), metadata={op_name="jit(mxtpu_fused_step)/transpose(jvp(moe))/while/body/ragged_dot_general"}
  ROOT %tuple.13 = (s32[], f32[8,8]{1,0}) tuple(%gte.11, %ragged-dot.12)
}

%cond.14 (arg.15: (s32[], f32[8,8])) -> pred[] {
  %arg.15 = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %lt.16 = pred[] constant(true), metadata={op_name="jit(mxtpu_fused_step)/transpose(jvp(moe))/while/cond/lt"}
}

%branch_a.17 (x.18: f32[8,8]) -> f32[8,8] {
  %x.18 = f32[8,8]{1,0} parameter(0)
  ROOT %mxtpu_flash_fwd.19 = f32[8,8]{1,0} custom-call(%x.18), custom_call_target="tpu_custom_call", metadata={op_name="jit(mxtpu_fused_step)/jvp(attn)/jit(_forward)/cond/branch_0_fun/mxtpu_flash_fwd"}
}

%branch_b.20 (x.21: f32[8,8]) -> f32[8,8] {
  %x.21 = f32[8,8]{1,0} parameter(0)
  ROOT %negate.22 = f32[8,8]{1,0} negate(%x.21), metadata={op_name="jit(mxtpu_fused_step)/jvp(attn)/jit(_forward)/cond/branch_1_fun/neg"}
}

ENTRY %main.23 (p.24: f32[8,8]) -> f32[8,8] {
  %p.24 = f32[8,8]{1,0} parameter(0), metadata={op_name="params['fc1_weight']"}
  %fusion.1 = f32[8,8]{1,0} fusion(%p.24, %p.24), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(mxtpu_fused_step)/mxtpu.update/fc2_weight/mul"}
  %fusion.2 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(%p.24), kind=kLoop, calls=%fused_computation.2
  %while.25 = (s32[], f32[8,8]{1,0}) while(%fusion.2), condition=%cond.14, body=%body.9, metadata={op_name="jit(mxtpu_fused_step)/transpose(jvp(moe))/while"}
  %conditional.26 = f32[8,8]{1,0} conditional(%p.24, %p.24, %p.24), branch_computations={%branch_a.17, %branch_b.20}, metadata={op_name="jit(mxtpu_fused_step)/jvp(attn)/jit(_forward)/cond"}
  %tanh.27 = f32[8,8]{1,0} tanh(%p.24), metadata={op_name="jit(mxtpu_fused_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/relu1/tanh"}
  %reduce.28 = f32[] reduce(%p.24, %p.24), dimensions={0,1}, to_apply=%cond.14, metadata={op_name="jit(mxtpu_fused_step)/mxtpu.health/reduce_sum"}
  %sub.29 = f32[8,8]{1,0} subtract(%p.24, %p.24), metadata={op_name="jit(mxtpu_fused_step)/mxtpu.head_grad/sub"}
  %copy.30 = f32[8,8]{0,1} copy(%conditional.26)
  %copy.31 = f32[8,8]{0,1} copy(%fusion.1)
  %maximum.32 = f32[8,8]{1,0} maximum(%copy.31, %copy.31), metadata={op_name="jit(mxtpu_fused_step)/jvp(relu1)/max"}
  %fusion.3 = f32[8,8]{1,0} fusion(%copy.31), kind=kLoop, calls=%fused_computation.3
  ROOT %tuple.33 = (f32[8,8]{0,1}, f32[8,8]{1,0}) tuple(%copy.30, %maximum.32)
}
"""

SCOPES = {"nodes": {"fc1": ("FullyConnected", "ffn"),
                    "relu1": ("Activation", "ffn"),
                    "fc2": ("FullyConnected", "head"),
                    "moe": ("_contrib_MoEExperts", "experts"),
                    "attn": ("_contrib_FlashAttention", "attention")},
          "params": {"fc1_weight": "fc1", "fc2_weight": "fc2"}}


def test_the_parser_and_the_table_on_a_hand_written_module():
    module = opscopes.parse(HLO)
    assert module.name == STEP and module.entry == "main.23"
    assert set(module.computations) == {
        "fused_computation.1", "fused_computation.2", "fused_computation.3",
        "body.9", "cond.14",
        "branch_a.17", "branch_b.20", "main.23"}
    by_name = {ins.name: ins for _, ins in module.instructions()}
    assert by_name["while.25"].called == ("cond.14", "body.9")
    assert by_name["conditional.26"].called == ("branch_a.17", "branch_b.20")
    assert by_name["fusion.2"].opcode == "fusion"
    assert by_name["fusion.2"].shape == "(f32[8,8]{1,0}, f32[8,8]{1,0})"
    assert by_name["tuple.8"].root and by_name["tuple.8"].opcode == "tuple"
    assert by_name["p.24"].op_name == "params['fc1_weight']"

    table = opscopes.build_table(module, SCOPES)
    assert len(table) == len(by_name)
    # the matmul decides, not the root; two nodes inside
    assert table["fusion.1"] == ("fc1", "FullyConnected", "ffn", "backward",
                                 True)
    # no matmul, a root XLA made: the fusion's own metadata is empty, so
    # the first scoped instruction inside; mixed
    assert table["fusion.2"] == ("fc1", "FullyConnected", "ffn", "forward",
                                 True)
    # the loop, its body's instructions, a branch's
    assert table["while.25"][:4] == ("moe", "_contrib_MoEExperts", "experts",
                                     "backward")
    assert table["ragged-dot.12"] == table["while.25"]
    assert table["mxtpu_flash_fwd.19"] == (
        "attn", "_contrib_FlashAttention", "attention", "forward", False)
    assert table["negate.22"] == table["conditional.26"]
    # a recomputed forward is backward time
    assert table["tanh.27"] == ("relu1", "Activation", "ffn", "backward",
                                False)
    # the step's own scopes; an update carries its consumer's node and block
    assert table["multiply.4"] == ("fc2", "FullyConnected", "head", "update",
                                   False)
    assert table["reduce.28"][0::3] == (opscopes.HEALTH, "update")
    assert table["sub.29"][0::3] == (opscopes.HEAD_GRAD, "backward")
    assert by_name["conditional.26"].operands == ("p.24", "p.24", "p.24")
    assert by_name["while.25"].operands == ("fusion.2",)
    # no metadata: the node its scoped neighbours agree on (the producer's
    # here; its phase is the latest of its producers'), or, between two
    # nodes, none
    assert table["copy.30"] == table["conditional.26"]
    assert table["copy.31"] is opscopes.UNSCOPED
    # a fusion of nothing scoped, reached across the copy: the producer's
    assert table["copy.35"] is opscopes.UNSCOPED
    assert table["fusion.3"] == ("fc1", "FullyConnected", "ffn", "backward",
                                 False)
    assert table["p.24"] is opscopes.UNSCOPED
    assert opscopes.instruction_name(
        "%fusion.61 = bf16[8,8]{1,0} fusion(%a), kind=kLoop") == "fusion.61"
    # without a Symbol's scopes the first element that is no shell is a node
    assert opscopes.classify(OP + "transpose(jvp(l3_attn))/mul")[::3] == (
        "l3_attn", "backward")


def test_a_nodes_scope_is_unique_to_it():
    class Node:
        def __init__(self, name, op="FullyConnected", var=False):
            self.name, self.is_variable = name, var
            self.op = type("Op", (), {"name": op})

    topo = [Node("data", var=True), Node("fc"), Node("", "Activation"),
            Node("fc"), Node("", "Activation")]
    got = opscopes.node_scopes(topo)
    assert [got[id(n)] for n in topo[1:]] == [
        "fc", "Activation.2", "fc.3", "Activation.4"]


# ------------------------------------------- the families' fused steps
class OneBatch(mx.io.DataIter):
    """One batch of token ids, labels flattened as the LM symbols take them
    (or of images)."""

    def __init__(self, data, label):
        super().__init__()
        self.batch_size = data.shape[0]
        self.provide_data = [mx.io.DataDesc("data", data.shape)]
        self.provide_label = [mx.io.DataDesc("softmax_label", label.shape)]
        self._batch = mx.io.DataBatch(
            data=[mx.nd.array(data)], label=[mx.nd.array(label)], pad=0,
            index=None, provide_data=self.provide_data,
            provide_label=self.provide_label)
        self._out = False

    def reset(self):
        self._out = False

    def next(self):
        if self._out:
            raise StopIteration
        self._out = True
        return self._batch


def _tokens(vocab, seq, batch=2):
    ids = np.random.default_rng(0).integers(0, vocab, size=(batch, seq + 1))
    return OneBatch(ids[:, :-1].astype(np.float32),
                    ids[:, 1:].reshape(-1).astype(np.float32))


_MOE = dict(num_experts=8, top_k=2, experts_held=4, hidden=16,
            shared_hidden=16)


def _opt():
    return transformer.get_symbol(64, 32, num_layers=1, num_heads=2,
                                  d_model=32), _tokens(64, 32)


def _hybrid():
    return decoder.get_symbol(
        64, 64, [decoder.LINEAR], num_heads=2, d_model=32, d_ff=64,
        linear_key_dim=8, linear_value_dim=16), _tokens(64, 64)


def _laguna():
    return decoder.get_laguna_symbol(
        64, 32, [decoder.FULL], num_heads=[4], num_kv_heads=2, head_dim=16,
        d_model=32, d_ff=64, mlp_layer_types=["sparse"], window=8,
        rope={decoder.FULL: {"rotary_dims": 16, "rope_type": "default",
                             "theta": 1e4}},
        moe=dict(_MOE)), _tokens(64, 32)


def _nemotron():
    return decoder.get_nemotron_h_symbol(
        64, 64, "ME", d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
        mamba=dict(num_heads=4, head_dim=8, n_groups=2, state_size=8,
                   chunk=32),
        moe=dict(_MOE, activation="relu2")), _tokens(64, 64)


FAMILIES = {
    "opt": (_opt, {"embed", "attention", "ffn", "head"}),
    "hybrid": (_hybrid, {"embed", "delta_rule", "ffn", "head"}),
    "laguna": (_laguna, {"embed", "attention", "ffn", "experts", "head"}),
    "nemotron": (_nemotron, {"embed", "mamba2", "experts", "ffn", "head"}),
}


def _one_fused_step(build):
    """(the step's record, what its Symbol says of its nodes, its HLO
    text) after one step of `Module.fit` with the health rows armed; the
    Module is gone when it returns."""
    with mx.name.NameManager():
        sym, it = build()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd", health=True,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Normal(0.02),
            eval_metric=mx.metric.create("ce"))
    assert mod._fused is not None, "the fused step did not arm"
    rec = diag.latest_record(name=STEP)
    want = opscopes.symbol_scopes(mod._fused._graph_symbol)
    text = rec.hlo_text()
    assert text is not None
    del mod, sym, it
    gc.collect()
    # the executable is gone; what the table is made of is not
    assert rec.hlo_text() is None and rec._hlo is not None
    return rec, want, text


@pytest.fixture(scope="module")
def steps():
    built = {}

    def get(family):
        if family not in built:
            built[family] = _one_fused_step(FAMILIES[family][0])
        return built[family]

    return get


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_familys_fused_step_has_every_block_and_phase(family, steps):
    rec, want, _ = steps(family)
    table = rec.op_scopes()
    assert rec._hlo is None and rec.op_scopes() is table
    scoped = [s for s in table.values() if s.phase != "unscoped"]
    assert {s.block for s in scoped if s.node in want["nodes"]} == \
        FAMILIES[family][1]
    assert {s.phase for s in scoped} == {"forward", "backward", "update"}
    for s in scoped:
        if s.node in want["nodes"]:
            assert (s.operator, s.block) == want["nodes"][s.node]
    # an update carries the node and the block of its parameter's consumer
    updates = {s.node: s for s in scoped if s.phase == "update"}
    assert updates["lm_head"].block == "head"
    assert updates["lm_head"].operator == "FullyConnected"
    assert updates["tok_emb"].block == "embed"
    if family in ("laguna", "nemotron"):
        ups = {s.operator for s in updates.values() if s.block == "experts"}
        assert {"_contrib_MoEExperts", "_contrib_MoERouter"} <= ups
    # the health rows have a scope of their own
    assert opscopes.HEALTH in updates


def test_resnets_nodes_carry_their_stage():
    sym = resnet.get_symbol(num_classes=10, num_layers=50,
                            image_shape=(3, 224, 224))
    nodes = opscopes.symbol_scopes(sym)["nodes"]
    assert {b for _, b in nodes.values()} == {
        "stem", "stage1", "stage2", "stage3", "stage4", "head"}
    assert nodes["conv0"] == ("Convolution", "stem")
    assert nodes["stage3_unit2_bn1"] == ("BatchNorm", "stage3")
    assert nodes["fc1"] == ("FullyConnected", "head")


_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")


def _less_metadata(text):
    """An HLO text without its instructions' metadata and without the
    tables of files, functions and stack frames that metadata points to."""
    head, _, rest = text.partition("\n\n")
    body = rest[re.search(r"^(%|ENTRY)", rest, re.M).start():]
    return _METADATA.sub("", head + "\n\n" + body)


def test_scopes_and_attributes_leave_the_compiled_step_as_it_is(monkeypatch,
                                                                steps):
    """The step's optimised HLO, metadata stripped, is byte for byte what
    it is without the step's own scopes and without the `__block__`
    attributes: they are metadata."""
    _, want, text = steps("opt")
    assert OP + "mxtpu.update/l0_q_weight/" in text
    assert OP + "mxtpu.health/" in text
    assert want["nodes"]["l0_attn"] == ("_contrib_FlashAttention",
                                        "attention")
    named = jax.named_scope

    def no_own_scopes(name):
        return contextlib.nullcontext() if name.startswith("mxtpu.") \
            else named(name)

    monkeypatch.setattr(jax, "named_scope", no_own_scopes)
    monkeypatch.setattr(mx.AttrScope, "__init__",
                        lambda self, **kw: setattr(self, "_attrs", {}))
    _, bare, without = _one_fused_step(_opt)
    assert "mxtpu." not in without and "op_name=" in without
    assert bare["nodes"]["l0_attn"] == ("_contrib_FlashAttention", "")
    assert _less_metadata(without) == _less_metadata(text)


def test_the_executors_programs_keep_a_table_and_imperative_ones_none():
    with mx.AttrScope(block="mlp"):
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                    name="fc_scoped")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 8))
    exe.forward(is_train=False)
    table = diag.latest_record(kind="fwd_eval").op_scopes()
    assert ("fc_scoped", "FullyConnected", "mlp", "forward") in {
        s[:4] for s in table.values()}
    mx.nd.ones((2, 2)) + 1
    assert all(r.op_scopes() is None for r in _RECORDS
               if not r.kind.startswith(("fwd", "fused", "vjp")))


# --------------------------------------------------- the profiler's table
def _made_up_record(table, name="jit_mxtpu_made_up_step"):
    rec = ProgramRecord("fused_step", "test", 0.0)
    rec.name, rec._table = name, table
    with _LOCK:
        _RECORDS.append(rec)
    return rec


def test_exclusive_time_goes_to_the_innermost_operation():
    events = [("while", 0, 100), ("body.a", 10, 30), ("body.b", 30, 50),
              ("cond", 60, 90), ("branch", 65, 85), ("late", 95, 120),
              ("alone", 200, 210), ("empty", 205, 205)]
    got = profiler.exclusive_ns(events)
    assert got == {"while": 100 - 20 - 20 - 30 - 5, "body.a": 20,
                   "body.b": 20, "cond": 10, "branch": 20, "late": 25,
                   "alone": 10}
    assert sum(got.values()) == 120 + 10    # the union's length


def test_dumps_prints_device_time_by_node_from_a_made_up_trace(monkeypatch):
    program = "jit_mxtpu_made_up_step"
    table = opscopes.build_table(HLO, SCOPES)
    _made_up_record(table, program)
    text = "%%%s = f32[8,8]{1,0} fusion(%%p.24), kind=kLoop"
    one_run = [(text % "fusion.1", 10, 40), (text % "fusion.2", 40, 49),
               ("%while.25 = (s32[], f32[8,8]{1,0}) while(%fusion.2)", 50, 90),
               (text % "ragged-dot.12", 55, 85),
               (text % "multiply.4", 90, 95), (text % "copy.31", 95, 100)]
    ops = [(n, s + at, e + at) for at in (0, 1000) for n, s, e in one_run]
    devices = {"/device:TPU:0": {
        "ops": ops + [("%other = f32[] add()", 500, 600)],
        "modules": [(program + "(123)", 0, 100), (program + "(123)", 1000,
                                                  1100),
                    ("jit_mxtpu_arena_view(9)", 450, 650)]}}
    monkeypatch.setattr(profiler, "_newest_xplane", lambda: "made up")
    monkeypatch.setattr(profiler, "device_events", lambda path: devices)
    profiler.clear()
    profiler._state["jax_trace"] = True     # as `set_state('run')` leaves it
    profiler.set_state("stop")
    rows = [r for r in profiler.device_rows() if r["program"] == program]
    by_node = {r["node"]: r for r in rows}
    assert [r["node"] for r in rows] == ["moe", "fc1", "fc2", "unscoped"]
    assert by_node["fc1"]["backward_ms"] == pytest.approx(30e-6)
    assert by_node["fc1"]["forward_ms"] == pytest.approx(9e-6)
    assert by_node["moe"]["backward_ms"] == pytest.approx(40e-6)
    assert by_node["fc2"]["update_ms"] == pytest.approx(5e-6)
    assert by_node["unscoped"]["total_ms"] == pytest.approx(5e-6)
    assert by_node["moe"]["runs"] == 2 and by_node["moe"]["block"] == "experts"
    # the rows add to the program's operations' time in the trace, a run
    assert sum(r["total_ms"] for r in rows) == pytest.approx(89e-6)
    out = profiler.dumps()
    device, spans = out.split("\n\n")
    assert device.startswith("Device time by graph node")
    assert program + ": 2 run(s)" in device
    assert re.search(r"\nfc1 +FullyConnected +ffn +2 ", device)
    assert spans.startswith("Host spans")
    # a program without a table has no rows; reset drops the reading
    assert "arena_view" not in out
    profiler.dumps(reset=True)
    assert profiler.device_rows() == []
