"""The phase reduction (``phases.py``) on hand-made planes, on small traces
recorded on a TPU v5e (``record_trace.py``, ``record_phase_trace.py``),
and the tool end to end on the CPU at a tiny size."""

import os

import jax
import jax.numpy as jnp
import pytest

import phases
import profile_reduce as pr
import run
import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
SPANS = ("engine.execute", "session.wait", "session.fetch", "session.record")


def _planes():
    host = [("bench.window", 0, 100 * MS), ("bench.call", 0, 60 * MS),
            ("engine.execute", 0, 60 * MS), ("session.wait", 0, 40 * MS),
            ("session.fetch", 40 * MS, 15 * MS),
            ("session.record", 55 * MS, 5 * MS),
            ("bench.pace", 60 * MS, 40 * MS), ("not.ours", 0, 100 * MS)]
    modules = [("jit_fused(123)", 5 * MS, 30 * MS),
               ("jit_slice(45)", 52 * MS, 3 * MS)]
    # the sweep's while holds its body's ops
    ops = [("%while.1 = (f32[4]) while(...)", 5 * MS, 30 * MS),
           ("%sort.3 = f32[4] sort(...)", 6 * MS, 10 * MS),
           ("%fusion.4 = f32[4] fusion(...)", 16 * MS, 10 * MS),
           ("%fusion.5 = f32[4] fusion(...)", 26 * MS, 7 * MS),
           ("%copy.7 = f32[4] copy(...)", 33 * MS, 2 * MS),
           ("%slice.1 = f32[4] slice(...)", 52 * MS, 3 * MS)]
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])]


SCOPES = {"jit_fused": [{"while.1": None, "sort.3": "repro.scan.select",
                         "fusion.4": "repro.scan.distance",
                         "fusion.5": "repro.scan.select"}]}


def test_phase_time_is_self_time_inside_the_window():
    r = phases.reduce_phases(_planes(), SCOPES, SPANS)
    got = dict(r["device_phases"])
    assert got == pytest.approx({
        "repro.scan.select": 0.017,  # sort.3 + fusion.5
        "repro.scan.distance": 0.010,
        "unscoped": 0.004,  # the while's own 1 ms + the other module's slice
        "unattributed": 0.002,  # copy.7: not in its module's text
    })
    assert [p for p, _ in r["device_phases"]][0] == "repro.scan.select"
    assert sum(got.values()) == pytest.approx(r["busy_s"])
    assert r["busy_s"] == pytest.approx(0.033)
    assert r["device_ops"][0] == ["%while.1", pytest.approx(0.030),
                                  "unscoped"]
    assert ["%sort.3", pytest.approx(0.010), "repro.scan.select"] in \
        r["device_ops"]
    assert phases.scoped_share_pct(r) == pytest.approx(100 * 27 / 33)


def test_gaps_are_named_by_the_innermost_program_span():
    r = phases.reduce_phases(_planes(), SCOPES, SPANS)
    # [0, 5] in session.wait, [35, 52] in session.fetch, [55, 100] pacing
    assert r["idle_gaps"] == [["bench.pace", pytest.approx(0.045)],
                              ["session.fetch", pytest.approx(0.017)],
                              ["session.wait", pytest.approx(0.005)]]
    assert dict(r["idle_by_annotation"])["session.fetch"] == \
        pytest.approx(0.017)
    # without the span names the same gaps read as the harness's call
    plain = phases.reduce_phases(_planes(), SCOPES)
    assert [n for n, _ in plain["idle_gaps"]] == [
        "bench.pace", "bench.call", "bench.call"]


def test_programs_sharing_a_module_name_must_agree():
    two = {"m": [{"a": "repro.scan.select", "b": "repro.merge"},
                 {"a": "repro.scan.select", "b": "repro.lookup"}]}
    assert phases.phase_of(two, "m", "a") == "repro.scan.select"
    assert phases.phase_of(two, "m", "b") == phases.UNATTRIBUTED
    assert phases.phase_of(two, "m", "c") == phases.UNATTRIBUTED
    assert phases.phase_of(two, "other", "a") == phases.UNSCOPED
    assert phases.phase_of(two, None, "a") == phases.UNSCOPED


def test_op_scopes_reads_compiled_text():
    def body(i, c):
        with jax.named_scope("repro.scan.distance"):
            d = c @ c.T
        with jax.named_scope("repro.scan.select"):
            v, _ = jax.lax.top_k(d, 2)
        return c + v.sum()

    f = jax.jit(lambda x: jax.lax.fori_loop(0, 3, body, x))
    text = f.lower(jnp.ones((4, 4))).compile().as_text()
    ((module, (table,)),) = phases.op_scopes([text]).items()
    assert module == "jit__lambda"
    assert {"repro.scan.distance", "repro.scan.select"} <= set(
        table.values())
    assert any(name.startswith("while") and scope is None
               for name, scope in table.items())
    assert phases.scope_of("jit(f)/while/body/repro.scan.select/top_k") \
        == "repro.scan.select"
    assert phases.scope_of("jit(f)/repro.merge/repro.scan.select/sort") \
        == "repro.scan.select"
    assert phases.scope_of("jit(f)/while") is None


def test_recorded_v5e_trace_reads_as_before():
    """The harness's own reduction of its recorded trace is unchanged, and
    with no program text every op is unscoped and the phases sum to the
    busy time."""
    planes = pr.load_planes(os.path.join(DATA, "reduce_trace.xplane.pb"))
    r = pr.reduce_planes(planes)
    assert r.busy_s == pytest.approx(0.000180074, rel=1e-12)
    assert r.window_s == pytest.approx(0.019693679, rel=1e-12)
    assert r.device_ops == [["%fusion", pytest.approx(0.00018004)],
                            ["%copy-start", pytest.approx(2.7e-08)],
                            ["%copy-done", pytest.approx(7e-09)]]
    p = phases.reduce_phases(planes, {})
    assert p["busy_s"] == pytest.approx(r.busy_s)
    assert p["device_phases"] == [["unscoped", pytest.approx(r.busy_s)]]


def test_recorded_v5e_phase_trace():
    """A v5e trace of a program with repro.* scopes inside a fori_loop,
    called under enabled obs.Tracer spans: the op names of the trace are
    the instructions of the program's compiled text, the scopes hold the
    device time, and the host's sleep reads as its span."""
    planes = pr.load_planes(os.path.join(DATA, "phase_trace.xplane.pb"))
    with open(os.path.join(DATA, "phase_trace.hlo.txt")) as f:
        scopes = phases.op_scopes([f.read()])
    r = phases.reduce_phases(planes, scopes, SPANS)
    got = dict(r["device_phases"])
    assert "unattributed" not in got
    assert got["repro.scan.distance"] > 0 and got["repro.scan.select"] > 0
    assert phases.scoped_share_pct(r) > 90
    assert sum(got.values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["idle_gaps"][0][0] == "session.record"


def test_tool_runs_a_cell_on_the_cpu(tmp_path):
    root = tiny.make_root(tmp_path)
    out = tmp_path / "out"
    r = phases.measure(run.Spec("copydays-sift.batch", root=root), tiny.SEED,
                       0.3, 1, require_tpu=False, out=str(out))
    assert len(r["ms_per_image_tracer_off"]) == 1
    assert len(r["ms_per_image_tracer_on"]) == 1
    assert r["host_ms_per_call"] > 0
    assert list(r["ms_per_call"]) == ["session.pad", "session.dispatch",
                                      "session.wait", "session.fetch",
                                      "session.record"]
    assert r["device"]["platform"] == "cpu"
    off, on = r["slowest_call_per_window"]
    assert not off["tracer"] and off["call_ms"] > 0
    assert on["tracer"] and on["call_ms"] >= on["session.wait"] > 0
    assert "device_phases" not in r  # no TPU plane on the CPU
    assert sorted(os.listdir(out)) == [
        f"copydays-sift.batch.{tiny.SEED}.hlo0.txt",
        f"copydays-sift.batch.{tiny.SEED}.json"]
