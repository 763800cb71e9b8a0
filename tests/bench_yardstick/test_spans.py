"""benchmark/spans.py: the program's ring spans brought onto the trace's
clock by the nestings the fit cells have, and the five readers built on it,
each on a case worked out by hand; then once on the trace recorded on a TPU
v5e with spans laid over it."""
import os

import pytest

from benchmark import manifest, spans, trace

RECORDED = os.path.join(os.path.dirname(trace.__file__), "tests", "data",
                        "recorded.xplane.pb")
WALL = 1_790_000_000_000_000_000      # wall - trace of the made-up runs, ns
READERS = ("fit_input_share", "input_starved_share",
           "dispatch_exposed_share", "fit_self_share", "idle_unnamed_share")


def row(name, s, e, span_id, parent_id, thread=7, off=WALL):
    """A ring record whose span lies at [s, e] on the trace's clock."""
    return {"seq": span_id, "name": name, "category": "module",
            "t0_ns": s + off, "t1_ns": e + off, "span_id": span_id,
            "parent_id": parent_id, "trace_id": 1, "thread": thread,
            "tags": None}


def made_up():
    """A window of 1000 ns: the chip runs [100, 400] and [500, 900], so it
    is idle [0, 100], [400, 500], [900, 1000] (300 ns). The host: `fit`
    [12, 996] > `fit.epoch` [20, 990] > input [30, 60], step [60, 120],
    input [130, 150], pace [150, 420], step [420, 470], input [480, 496],
    metric_sync [520, 940]."""
    ops = [("%fusion.1 = bf16[8]{0} fusion()", 100, 400),
           ("%fusion.2 = bf16[8]{0} fusion()", 500, 900)]
    tr = trace.Trace({"/device:TPU:0": {"ops": ops, "modules": []}},
                     [(trace.WINDOW_SPAN, 0, 1000), ("bench.fit", 10, 998),
                      ("bench.input_next", 34, 56),
                      ("bench.input_next", 133, 147),
                      ("bench.input_next", 484, 492)])
    rows = [row("fit", 900, 980, 90, 0),           # an earlier call: not it
            row("fit.input", 905, 915, 91, 90),
            row("executor.forward", 300, 320, 50, 0, thread=8),
            row("fit", 12, 996, 1, 0),
            row("fit.epoch", 20, 990, 2, 1),
            row("fit.input", 30, 60, 3, 2), row("fit.step", 60, 120, 4, 2),
            row("fit.input", 130, 150, 5, 2), row("fit.pace", 150, 420, 6, 2),
            row("fit.step", 420, 470, 7, 2), row("fit.input", 480, 496, 8, 2),
            row("fit.metric_sync", 520, 940, 9, 2)]
    # the earlier call lies before this one on the wall clock
    for r in rows[:2]:
        r["t0_ns"] -= 5000
        r["t1_ns"] -= 5000
    rows.sort(key=lambda r: r["t1_ns"])
    return tr, rows


def test_offset_from_nestings():
    # bench.fit [10, 998] holds fit [12, 996]: -2 <= d <= 2 around WALL
    fit = [((WALL + 12, WALL + 996), (10, 998))]
    off = spans.offset(fit, [])
    assert off.drift == 0 and set(off.widths()) == {4}
    assert off.to_trace(WALL + 500) == 500
    # fit.input [30, 60] holds bench.input_next [34, 56]: -4 <= d <= 4;
    # [130, 150] holds [133, 147]: -3 <= d <= 3; together with the above
    off = spans.offset(fit, [((WALL + 30, WALL + 60), (34, 56)),
                             ((WALL + 131, WALL + 151), (133, 147))])
    assert off.drift == 0 and set(off.widths()) == {4}   # one sits 1 late
    off = spans.offset([], [((WALL + 30, WALL + 60), (34, 56)),
                            ((WALL + 135, WALL + 155), (133, 147))])
    assert off.drift == 0 and set(off.widths()) == {2}   # 5 late: d in [2, 4]
    assert off.to_trace(WALL + 103) == 100


def test_offset_of_clocks_that_part():
    """The wall clock gains 20 ppm on the trace's: 80 us over 4 s, where a
    pair leaves 10 us. No one offset fits; the limits near an instant do."""
    gain = 20e-6
    pairs = []
    for k in range(41):
        s = k * 100_000_000                  # a step every 100 ms
        d = WALL + int(gain * s)
        pairs.append(((s - 5_000 + d, s + 9_000 + d), (s, s + 4_000)))
    fit = [((pairs[0][0][0] - 900_000, pairs[-1][0][1] + 400_000),
            (-950_000, pairs[-1][1][1] + 450_000))]
    assert min(spans.Offset(fit, pairs).widths()) < 0
    assert min(spans.Offset(fit, pairs, 5e-6).widths()) < 0
    off = spans.offset(fit, pairs)
    assert off.drift == 50e-6 and 0 <= min(off.widths())
    assert max(off.widths()) <= 10_100       # at fit's ends, 0.9 ms off
    # a span's end halfway between two pairs lands within a pair's slack
    for at in (50_000_000, 2_050_000_000, 3_950_000_000):
        assert abs(off.to_trace(at + WALL + int(gain * at)) - at) <= 5_000
    # a pair the host was held up in (3 ms) is bounded by its neighbours
    (w0, w1), inner = pairs[20]
    held = pairs[:20] + [((w0 - 3_000_000, w1), inner)] + pairs[21:]
    off = spans.offset(fit, held)
    assert off.drift == 50e-6 and max(off.widths()) <= 20_000


def test_spans_on_the_trace_clock_and_the_sums():
    tr, rows = made_up()
    sp = spans.build(tr, rows, capacity=4096)
    assert sp.width_ns == 4 and sp.window == (0, 1000)
    assert sp.fit_cover == pytest.approx(984 / 988)
    # only the last call's spans, only its thread
    assert sorted(n for n, _, _, _, _ in sp.rows) == sorted(
        ["fit", "fit.epoch"] + ["fit.input"] * 3 + ["fit.step"] * 2
        + ["fit.pace", "fit.metric_sync"])
    assert sp.named("fit.input") == [(30, 60), (130, 150), (480, 496)]
    assert sp.covered_ns("fit.input") == 66
    assert sp.covered_ns("fit.epoch") == 970
    # fit.epoch is its children and its own time
    kids = sum(sp.covered_ns(p) for p in spans.PHASES)
    assert kids == 66 + 110 + 270 + 420
    assert sp.self_ns("fit.epoch") == 970 - kids == 104
    assert sp.self_ns("fit") == 984 - 970
    # the chip's idle time is its parts by phase and the unnamed part
    assert sp.idle == [(0, 100), (400, 500), (900, 1000)]
    by_phase = {p: sp.idle_in_ns(p) for p in spans.PHASES}
    assert by_phase == {"fit.input": 30 + 16, "fit.step": 40 + 50,
                        "fit.pace": 20, "fit.metric_sync": 40,
                        "fit.callbacks": 0, "fit.eval": 0}
    assert sp.idle_unnamed_ns() == 30 + 14 + 60
    assert sum(by_phase.values()) + sp.idle_unnamed_ns() == sp.idle_ns() == 300
    line = spans.summary(sp)
    assert "offset_width_ns=4 offset_drift_ppm=0 " in line and "idle_unnamed_ns=104" in line


@pytest.mark.parametrize("name,want", [
    ("fit_input_share", 6.6),            # 66 of 1000 ns
    ("input_starved_share", 4.6),        # idle and in fit.input: 30 + 16
    ("dispatch_exposed_share", 9.0),     # idle and in fit.step: 40 + 50
    ("fit_self_share", 10.4),            # fit.epoch less its children
    ("idle_unnamed_share", 100 * 104 / 300),
])
def test_each_reader_on_the_made_up_case(name, want):
    tr, rows = made_up()
    facts = {"trace": tr, "program_spans": spans.build(tr, rows)}
    reader = manifest.load_module(
        os.path.join(manifest.HERE, "metrics", name + ".py"), "reader_" + name)
    assert reader.read(facts) == pytest.approx(want)
    # a program without the spans (this PR's parent) leaves the metric out
    assert reader.read({"trace": tr, "program_spans": None}) is None


def test_readers_are_entries_of_both_fit_cells():
    bench = manifest.read_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    mine = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    assert bench["per_layer"][-5:] == mine          # appended, in this order
    for m in mine:
        assert m["source"] == "program_span" and m["unit"] == "%"
        assert m["moves"] == "train_throughput" and m["better"] == "lower"
        assert m["workloads"] == ["opt-1.3b-fit-s1024", "resnet50-fit-b256"]


def test_nothing_to_read():
    tr, rows = made_up()
    assert spans.build(None, rows) is None
    assert spans.build(tr, []) is None
    # the parent's ring: microsecond floats, and no `fit` span
    old = [{"name": "fit.step", "t0_us": 1.0, "t1_us": 2.0, "thread": 7}]
    assert spans.build(tr, old) is None
    assert spans.build(tr, [r for r in rows if r["name"] != "fit"]) is None
    assert spans.build(tr, [r for r in rows
                            if r["name"] != "fit.input"]) is None
    no_bench = trace.Trace(tr.devices, [(trace.WINDOW_SPAN, 0, 1000)])
    assert spans.build(no_bench, rows) is None
    # no chip in the trace (a CPU rehearsal): spans, but no idle time
    sp = spans.build(trace.Trace({}, tr.spans), rows)
    assert sp.idle is None and sp.idle_in_ns("fit.step") is None
    assert sp.share(sp.idle_unnamed_ns()) is None
    assert sp.share(sp.covered_ns("fit.input")) == pytest.approx(6.6)


def test_an_empty_or_wide_interval_raises_with_its_numbers():
    tr, rows = made_up()
    late = [dict(r, t0_ns=r["t0_ns"] + 9, t1_ns=r["t1_ns"] + 9)
            if r["span_id"] == 5 else r for r in rows]
    with pytest.raises(ValueError, match=r"3 `fit.input` spans, 3 "
                       r"`bench.input_next`; with the clocks parting by 500 "
                       r"ppm at most, 4 nestings bound wall - trace to "
                       r"nothing: limits cross by 4 ns"):
        spans.build(tr, late)
    longer = [dict(r, t0_ns=r["t0_ns"] - 5, t1_ns=r["t1_ns"] + 5)
              if r["span_id"] == 1 else r for r in rows]
    with pytest.raises(ValueError, match=r"`fit` 994 ns in `bench.fit` 988 "
                       r"ns, .* limits cross by 6 ns"):
        spans.build(tr, longer)
    # without the inputs' pairs only `bench.fit` bounds it: too wide
    wide = trace.Trace(tr.devices, [
        (trace.WINDOW_SPAN, 0, 10**9), ("bench.fit", 10, 10**9 - 2),
        ("bench.input_next", 34, 56)])
    with pytest.raises(ValueError, match=r"3 `fit.input` spans, 1 "
                       r"`bench.input_next`; with the clocks parting by 0 "
                       r"ppm at most, 1 nestings bound wall - trace to "
                       r"\d+ ns, over 200000"):
        spans.build(wide, rows)


def test_a_ring_that_no_longer_reaches_the_window_raises():
    tr, rows = made_up()
    mine = [r for r in rows if r["span_id"] < 50]        # the last call alone
    assert spans.build(tr, mine, capacity=len(mine) + 1) is not None
    tail = [r for r in mine if r["name"] != "fit.epoch"
            and not (r["name"] == "fit.input" and r["span_id"] == 3)]
    tail.sort(key=lambda r: r["t0_ns"] if r["name"] != "fit" else 10**30)
    with pytest.raises(ValueError, match="the ring's 7 slots reach back to 60 ns, the window opens at 0"):
        spans.build(trace.Trace(tr.devices, [
            (trace.WINDOW_SPAN, 0, 1000), ("bench.fit", 10, 998),
            ("bench.input_next", 133, 147), ("bench.input_next", 484, 492)]),
            tail, capacity=len(tail))


def test_on_the_recorded_trace_with_spans_laid_over_it():
    """The recorded trace has six 4 ms sleeps inside `bench.input_next`, each
    followed by three steps under a `bench.fit`. Laid over it: one call of
    fit whose `fit.input` spans hold the sleeps and whose `fit.step` spans
    lie where the recorded `bench.fit` spans were."""
    rec = trace.Trace.from_file(RECORDED)
    lo, hi = rec.window
    sleeps = sorted(s for s in rec.spans if s[0] == "bench.input_next")
    groups = sorted(s for s in rec.spans if s[0] == "bench.fit")
    first, last = sleeps[0][1], groups[-1][2]
    tr = trace.Trace(rec.devices, [(trace.WINDOW_SPAN, lo, hi),
                                   ("bench.fit", first - 3000, last + 3000)]
                     + sleeps)
    rows = [row("fit", first - 2000, last + 2000, 1, 0),
            row("fit.epoch", first - 1500, last + 1500, 2, 1)]
    for k, (_, s, e) in enumerate(sleeps):
        rows.append(row("fit.input", s - 700, e + 900, 10 + k, 2))
    for k, (_, s, e) in enumerate(groups):
        rows.append(row("fit.step", s, e, 20 + k, 2))
    sp = spans.build(tr, rows, capacity=4096)
    assert sp.width_ns == 1600            # the inputs' slack, 700 + 900
    busy = rec.busy_s() * 1e9
    assert sp.idle_ns() == pytest.approx(hi - lo - busy)
    # the chip ran each group's steps ~1 ms before the host's annotation of
    # the group began (the device's events lead the host's in this trace),
    # so most of its idle time is under the sleeps, as `idle_gaps` reads it
    starved = sp.idle_in_ns("fit.input")
    exposed = sp.idle_in_ns("fit.step")
    assert starved + exposed + sp.idle_unnamed_ns() == sp.idle_ns()
    assert 0.70 < starved / sp.idle_ns() < 0.85
    assert 0.15 < exposed / sp.idle_ns() < 0.30
    assert sp.idle_unnamed_ns() < 0.01 * sp.idle_ns()
    facts = {"trace": tr, "program_spans": sp}
    idle_share = manifest.load_module(os.path.join(
        manifest.HERE, "metrics", "device_idle_share.train.py"), "idle_reader")
    by_phase = sum(sp.share(sp.idle_in_ns(p)) for p in spans.PHASES)
    assert by_phase + sp.share(sp.idle_unnamed_ns()) == pytest.approx(
        idle_share.read(facts))
