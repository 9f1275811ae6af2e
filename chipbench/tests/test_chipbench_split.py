"""The tick split from inside the program (``chipbench/split.py``), on
synthetic timelines whose answers are worked out by hand: nested ops
give self time, the scopes sum to ``step_ms``, a phase's idle time is
its span less the step's executions, and a program without the spans
or scopes yields nothing to read."""

import re

import pytest

from chipbench import split, trace
from chipbench.trace import Event, Timeline

P = "jit(serve_decode_chunk)/while/body/closed_call/"
DEV = "/device:TPU:0"
UNSCOPED = re.compile(r"(%s)/" % "|".join(split.SCOPES))   # the parent's paths


def op(name, start, end):
    return Event(f"%{name} = bf16[16,1,2560]{{2,1,0}} fusion(bf16[16,1,2560] %p)",
                 start, end)


def hlo_of(ops):
    """The program's HLO text: each op's line with its op_name."""
    return "\n".join(f"  %{n} = bf16[16,1,2560]{{2,1,0}} fusion(%p), "
                     f'metadata={{op_name="{p}"}}' for n, _, _, p in ops)


def tick(i, t0, device, ops, phases=True):
    """One tick from t0 (seconds): harness span [t0, t0 + 0.1], engine
    phases schedule [t0+1ms, t0+10ms], fetch [.., t0+90ms], sample
    [.., t0+99ms]; the step's execution over ``device`` (offsets)."""
    host = [Event("chipbench.tick", t0, t0 + 0.100, {"tick": i})]
    if phases:
        host += [Event("serve.tick", t0 + 0.001, t0 + 0.099, {"tick": i}),
                 Event("serve.schedule", t0 + 0.001, t0 + 0.010),
                 Event("serve.fetch", t0 + 0.010, t0 + 0.090,
                       {"chunk": 1, "live": 2}),
                 Event("serve.sample", t0 + 0.090, t0 + 0.099)]
    mods = [Event("jit_serve_decode_chunk(7)", t0 + device[0], t0 + device[1])]
    evs = [op(n, t0 + s, t0 + e) for n, s, e, _ in ops]
    return host, mods, evs


# offsets in seconds inside one tick's execution [8 ms, 85 ms]
OPS = [
    ("cache_fusion", 0.0085, 0.0095, P + "cache/dynamic_slice"),
    ("while.3", 0.010, 0.080, "jit(serve_decode_chunk)/while"),
    ("attn_fusion", 0.012, 0.030, P + "attention/dot_general"),
    ("while.4", 0.030, 0.060, P + "ssd/while"),
    ("ssd_fusion", 0.035, 0.050, P + "ssd/while/body/mul"),
    ("mlp_fusion", 0.060, 0.070, P + "mlp/dot_general"),
    ("head_fusion", 0.080, 0.084, "jit(serve_decode_chunk)/head/dot_general"),
]
# self times, ms: while.3 70 - (18 + 30 + 10) = 12; while.4 30 - 15 = 15
WANT = {"cache": 1.0, "attention": 18.0, "ssd": 30.0, "mlp": 10.0,
        "head": 4.0}
STEP = 77.0
HLO = split.hlo_paths([hlo_of(OPS)])


def timeline(ticks, window=(0.0, 10.0)):
    host = [Event("chipbench.window", *window)]
    mods, ops = [], []
    for h, m, o in ticks:
        host += h
        mods += m
        ops += o
    return Timeline(window, {DEV: ops}, {DEV: mods}, host)


def test_nested_ops_give_self_time_not_double_counts():
    evs = [Event(n, s, e) for n, s, e, _ in OPS]
    own = dict(zip([e.name for e in evs], split.self_times(evs)))
    assert own["while.3"] == pytest.approx(0.012)
    assert own["while.4"] == pytest.approx(0.015)
    assert own["ssd_fusion"] == pytest.approx(0.015)
    naive = sum(e.end - e.start for e in evs)
    assert naive == pytest.approx(0.148)                # nested, twice over
    assert sum(own.values()) == pytest.approx(0.075)    # within the 77 ms run


def test_scopes_sum_to_step_ms_and_match_its_reader():
    tl = timeline([tick(0, 1.0, (0.008, 0.085), OPS),
                   tick(1, 2.0, (0.008, 0.085), OPS)])
    rows = split.tick_rows(tl, HLO)
    assert [r["tick"] for r in rows] == [0, 1]
    m = split.means(rows)
    for sc, v in WANT.items():
        assert m[f"step_ms.{sc}"] == pytest.approx(v)
    assert m["step_ms.unscoped"] == pytest.approx(STEP - sum(WANT.values()))
    parts = sum(m[f"step_ms.{s}"] for s in split.SCOPES + ("unscoped",))
    assert parts == pytest.approx(m["step_ms"], abs=1e-9)
    per = trace.per_span_device_s(tl, "chipbench.tick", split.PROGRAM)
    assert m["step_ms"] == pytest.approx(1e3 * sum(per.values()) / len(per))
    # the remainder: while.3's own 12 ms, and 2 ms of the run with no op
    top = split.unscoped_ops(tl, HLO)
    assert [(n, round(v, 9)) for n, _, v in top] == [("while.3", 12.0),
                                                     ("(no op)", 2.0)]


def test_phase_idle_is_the_span_less_the_executions():
    tl = timeline([tick(0, 1.0, (0.008, 0.085), OPS)])
    (row,) = split.tick_rows(tl, HLO)
    # schedule [1, 10] ms overlaps the run [8, 85] by 2 ms; fetch [10, 90]
    # by 75 ms; sample [90, 99] not at all
    assert row["host_ms_per_tick.schedule"] == pytest.approx(7.0)
    assert row["host_ms_per_tick.fetch"] == pytest.approx(5.0)
    assert row["host_ms_per_tick.sample"] == pytest.approx(9.0)
    assert row["host_ms"] == pytest.approx(100.0 - STEP)
    # the phases leave out only the harness lines around step(): 2 ms
    phases = sum(row[f"host_ms_per_tick.{p}"]
                 for p in ("schedule", "fetch", "sample"))
    assert row["host_ms"] - phases == pytest.approx(2.0)


def test_ticks_cut_by_the_window_or_without_device_time_are_left_out():
    tl = timeline([tick(0, 1.0, (0.008, 0.085), OPS),
                   tick(1, 2.0, (0.0, 0.0), []),
                   tick(2, 9.95, (0.008, 0.085), OPS)])
    assert [r["tick"] for r in split.tick_rows(tl, HLO)] == [0]


def test_nothing_to_read_without_the_programs_spans_or_scopes():
    bare = timeline([tick(0, 1.0, (0.008, 0.085), OPS, phases=False)])
    parent = split.hlo_paths([UNSCOPED.sub("", hlo_of(OPS))])
    m = split.means(split.tick_rows(bare, parent))
    assert m["step_ms"] == pytest.approx(STEP)
    assert not [k for k in m if k.startswith(("step_ms.", "host_ms_per_tick."))]
    # a scope with no op (no SSD in a dense model) reads nothing
    dense = [o for o in OPS if "ssd" not in o[3]]
    m = split.means(split.tick_rows(timeline([tick(0, 1.0, (0.008, 0.085),
                                                   dense)]), HLO))
    assert "step_ms.ssd" not in m and m["step_ms.attention"] > 0
    assert split.means([]) == {}


def test_scopes_from_hlo_text_by_name_and_shape():
    assert split.scope_of(P + "attention/dot_general") == "attention"
    assert split.scope_of(P + "ssd/while/body/mul") == "ssd"
    assert split.scope_of("jit(serve_decode_chunk)/while") is None
    assert split.instr_key(
        "%while = (s32[]{:T(128)}, bf16[2,4]{1,0}) while((s32[]) %t)") \
        == ("while", "(s32[]{:T(128)}, bf16[2,4]{1,0})")
    # the two tick shapes' programs: fusion.7 is the MLP in one and the
    # attention in the other, told apart by the result shape
    decode = "\n".join([
        "ENTRY %main {",
        "  %fusion.7 = bf16[2,1,4]{2,1,0} fusion(%p), kind=kLoop, metadata="
        '{op_name="' + P + 'mlp/dot_general" stack_frame_id=3}',
        "  %copy.1 = bf16[2,1,4]{2,1,0} copy(%p)",
        "  ROOT %while.3 = (s32[]) while(%t), condition=%c, body=%b, "
        'metadata={op_name="jit(serve_decode_chunk)/while"}',
        "}"])
    chunk = decode.replace("2,1,4", "2,16,4").replace("mlp/", "attention/")
    hlo = split.hlo_paths([decode, chunk])
    ops = [Event("%fusion.7 = bf16[2,1,4]{2,1,0} fusion(bf16[2,1,4] %p)", 0, 1),
           Event("%fusion.7 = bf16[2,16,4]{2,1,0} fusion(bf16[2,16,4] %p)", 1, 2),
           Event("%while.3 = (s32[]) while((s32[]) %t)", 2, 4),
           Event("%copy.1 = bf16[2,1,4]{2,1,0} copy(bf16[2,1,4] %p)", 2, 3),
           Event("%fusion.9 = f32[] fusion(f32[] %q)", 4, 5)]
    assert split.op_scopes(ops, hlo) == ["mlp", "attention", None, None, None]
    # a name the programs disagree on, with no shape to tell, reads no scope
    assert hlo["fusion.7"] is None


def test_keep_timeline_holds_what_the_run_loads_and_restores_the_loader():
    load = trace.load
    box = []
    with split.keep_timeline(box):
        assert trace.load is not load
    assert trace.load is load and box == []
