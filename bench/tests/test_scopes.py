"""Attribution by the program's own names (``tracescope.py``), the two
readers of the program's records, and ``attribute.py`` at the test size;
on hand-made event lists and on a small trace recorded on a TPU v5 lite
by ``record_scoped_trace.py``."""
import json
import os

import pytest

import tracefile
import tracescope
from fedrun import WINDOW_SPAN
from spec import BENCH, load_module

SCOPED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "scoped")
ms = 1e6  # events in ns

HLO = """\
HloModule jit_group, is_scheduled=true

FileNames
1 "zo.py"

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(group)/while/body/zo.perturb/add" stack_frame_id=2}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(group)/while/body/zo.perturb/add" stack_frame_id=2}
  %dot.2 = f32[8]{0} dot(%fusion.1, %x), metadata={op_name="jit(group)/zo.update/zo.forward/dot_general"}
  %copy.3 = f32[8]{0} copy(%dot.2)
  %copy.5 = f32[8]{0} copy(%x)
  %add.6 = f32[8]{0} add(%copy.5, %x), metadata={op_name="jit(group)/zo.perturb/add"}
  ROOT %while.4 = f32[8]{0} while(%copy.3), condition=%c, body=%b, metadata={op_name="jit(group)/while"}
}
"""


def test_hlo_index():
    idx = tracescope.hlo_index(HLO)
    assert idx["fusion.1 f32[8]"] == (
        "jit(group)/while/body/zo.perturb/add", "zo.perturb", False)
    assert idx["add.1 f32[8]"][1] == "zo.perturb"   # inside a fusion
    assert idx["x f32[8]"] == ("x", tracescope.OTHER, False)
    assert idx["dot.2 f32[8]"][1] == "zo.forward"   # the innermost scope
    assert idx["while.4 f32[8]"][1] == tracescope.OTHER
    assert idx["copy.5 f32[8]"] == ("", "zo.perturb", True)   # its user's
    assert idx["copy.3 f32[8]"] == ("", tracescope.OTHER, False)
    assert "x" in tracescope.Prefixes("x.", "x")
    assert "fl.round" not in tracescope.Prefixes("mask.", "bench.")


def _trace():
    return {
        "host": [(WINDOW_SPAN, 0, 100 * ms),
                 ("fl.round", 0, 90 * ms), ("fl.group", 1 * ms, 2 * ms),
                 ("fl.uplink", 52 * ms, 60 * ms),
                 ("fl.replay", 60 * ms, 70 * ms),
                 ("fl.update", 70 * ms, 88 * ms)],
        "devices": {"/device:TPU:0": {
            "modules": [("jit_group(7)", 5 * ms, 50 * ms),
                        ("jit_replay(2)", 62 * ms, 64 * ms)],
            "ops": [("%while.4 = f32[8]{0} while(...)", 5 * ms, 45 * ms),
                    ("%fusion.1 = f32[8]{0} fusion(...)", 10 * ms, 20 * ms),
                    ("%dot.2 = f32[8]{0} dot(...)", 20 * ms, 35 * ms),
                    ("%copy.5 = f32[8]{0} copy(...)", 35 * ms, 40 * ms),
                    ("%copy.3 = f32[8]{0} copy(...)", 45 * ms, 48 * ms),
                    ("%custom.9 = f32[8]{0} custom-call(...)", 48 * ms,
                     49 * ms),
                    # another compile's copy.3: same name, other type
                    ("%copy.3 = f32[4]{0} copy(...)", 49 * ms, 50 * ms),
                    ("%fusion.1 = f32[8]{0} fusion(...)", 62 * ms, 64 * ms),
                    ]}}}


def test_scope_times_by_hand():
    r = tracescope.scope_times(_trace(), WINDOW_SPAN, HLO)
    sc = r["scopes_s"]
    # fusion.1 (not the replay's: it lies outside jit_group's module
    # event), and copy.5 by its user's scope
    assert sc["zo.perturb"] == pytest.approx(0.015)
    assert r["inherited_s"] == {"zo.perturb": pytest.approx(0.005)}
    assert sc["zo.forward"] == pytest.approx(0.015)
    assert sc["zo.sample"] == sc["zo.update"] == 0.0
    # while.4's own 10 ms, copy.3 (no metadata), custom.9 and the
    # mismatched copy.3 (not in the text)
    assert sc[tracescope.OTHER] == pytest.approx(0.015)
    assert [k for k, _, _ in r["top_other"]] == [
        "while.4 f32[8]", "copy.3 f32[8]", "custom.9 f32[8]",
        "copy.3 f32[4]"]
    assert r["ops_s"] == pytest.approx(0.045)
    assert r["program_s"] == pytest.approx(0.045)
    assert r["joined_share"] == pytest.approx(43 / 45)


def test_idle_by_span_by_hand():
    r = tracescope.idle_by_span(_trace(), WINDOW_SPAN)
    idle = r["idle_s"]
    # gaps: [0,5] in fl.round (the group span closed at 2 ms), [50,62]
    # midpoint 56 in fl.uplink, [64,100]: midpoint 82 in fl.update
    assert idle["fl.round"] == pytest.approx(0.005)
    assert idle["fl.uplink"] == pytest.approx(0.012)
    assert idle["fl.update"] == pytest.approx(0.036)
    assert r["idle_total_s"] == pytest.approx(0.053)
    assert r["named_share"] == pytest.approx(1.0)
    r = tracescope.idle_by_span(_trace(), WINDOW_SPAN, prefixes=("mask.",))
    assert r["named_share"] == 0.0


def test_empty_trace_gives_nothing():
    empty = {"host": [], "devices": {}}
    assert tracescope.scope_times(empty, WINDOW_SPAN, HLO) == {}
    assert tracescope.idle_by_span(empty, WINDOW_SPAN) == {}


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(SCOPED, "scoped.xplane.pb")
    if not os.path.exists(path):
        pytest.fail("the recorded trace is missing; make it on a TPU with "
                    "record_scoped_trace.py")
    with open(os.path.join(SCOPED, "scoped.json")) as f:
        meta = json.load(f)
    with open(os.path.join(SCOPED, "scoped.hlo.txt")) as f:
        hlo = f.read()
    trace = tracefile.load(SCOPED, tracescope.Prefixes(
        "bench.", *tracescope.PROGRAM_SPANS))
    return trace, hlo, meta


def test_recorded_scope_times(recorded):
    trace, hlo, meta = recorded
    r = tracescope.scope_times(trace, WINDOW_SPAN, hlo)
    sc = r["scopes_s"]
    assert r["joined_share"] >= 0.95
    # four 2048^3 matmuls a round: the forward takes most of the program
    assert sc["zo.forward"] > 0.5 * r["program_s"]
    assert sc["zo.perturb"] > 0 and sc["zo.update"] > 0
    assert sum(sc.values()) == pytest.approx(r["ops_s"])
    assert r["ops_s"] == pytest.approx(r["program_s"], rel=0.05)
    programs = tracefile.reduce(trace, WINDOW_SPAN)["programs"]
    assert r["program_s"] == pytest.approx(programs["jit_group"], rel=1e-6)


def test_recorded_idle_by_span(recorded):
    trace, _, meta = recorded
    names = {n for n, *_ in trace["host"]}
    assert {"fl.round", "fl.group", "fl.group_wait", "fl.uplink",
            "fl.update"} <= names
    r = tracescope.idle_by_span(trace, WINDOW_SPAN)
    # every round sleeps inside fl.uplink while the device waits; the
    # device clock leads the host's by about a millisecond, so a gap's
    # edges, and no more, may fall in the neighbouring spans
    assert r["idle_s"]["fl.uplink"] >= 0.9 * sum(meta["sleeps_s"])
    assert r["named_share"] >= 0.9
    red = tracefile.reduce(trace, WINDOW_SPAN)
    assert r["idle_total_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "test_reader_" + name)


def test_readers_of_the_program_records():
    import jax
    import jax.numpy as jnp

    from repro import obs
    obs.reset()
    with obs.span("mask.to_host"):
        pass
    with obs.span("mask.topk"):
        pass
    with obs.span("fl.round", round=0):
        jax.jit(lambda x: x * 3.0 + 2.0)(jnp.ones(3)).block_until_ready()
    with obs.span("fl.round", round=1):
        jax.jit(lambda x: x * 5.0 - 1.0)(jnp.ones(3)).block_until_ready()
    rec = obs.export()
    topk = _reader("mask_topk_s").read({})
    assert topk == pytest.approx(sum(
        (s["t1_ns"] - s["t0_ns"]) * 1e-9 for s in rec["spans"][:2]))
    # one window round: the compiles of round 0 count, round 1's do not
    compile_s = _reader("setup_compile_s").read(
        {"window": {"round_s": [0.1]}})
    first = [c for c in rec["compiles"] if c["round"] == 0]
    assert 0 < compile_s <= sum(c["seconds"] for c in first) + 1e-9
    assert _reader("setup_compile_s").read(
        {"window": {"round_s": [0.1, 0.1]}}) is None
    obs.reset()


def test_readers_without_the_program_records(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_obs(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "repro" and fromlist and "obs" in fromlist:
            raise ImportError("cannot import name 'obs'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_obs)
    assert _reader("mask_topk_s").read({}) is None
    assert _reader("setup_compile_s").read(
        {"window": {"round_s": [0.1]}}) is None


def test_attribute_at_the_test_size(tmp_path, capsys):
    import attribute
    import tiny
    root, bench = tiny.make_root(str(tmp_path / "root"))
    out = tmp_path / "attr.json"
    rc = attribute.main(["--workload", tiny.CELL, "--seed", "5",
                         "--seconds", "0.2", "--out", str(out)],
                        root=root, bench=bench, require_tpu=False,
                        cache_dir=str(tmp_path / "cache"))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = line["metrics"]
    assert m["mask_topk_s"] > 0 and m["setup_compile_s"] > 0
    # the CPU's trace has no device plane: the device numbers are absent
    assert m["zo_perturb_device_ms"] is None
    assert line["profiler"]["span_cost_us"] > 0
    full = json.loads(out.read_text())
    assert {"fl.round", "fl.group", "fl.update"} <= set(
        full["obs"]["spans_s"])
