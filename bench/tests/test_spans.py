"""The reduction of the program's own tracing (``spans.py``): engine spans
against device idle time on hand-made spans, request stamps on a hand-made
run, the decoding of the device ops' ``tf_op`` paths, and the numbers of a
small trace recorded on a TPU v5e (``bench/data/selfcheck-spans.*``)."""

import json
from pathlib import Path

import pytest

import names
import spans
import xplane
from record import Req, Run

DATA = Path(__file__).resolve().parents[1] / "data"


def _program():
    return [
        (1.0, 4.0, "engine.step", {"tick": 0}),
        (1.0, 1.5, "engine.admit", {"admitted": 1}),
        (1.5, 3.5, "engine.decode", {"rows": 2}),
        (2.0, 3.5, "engine.decode.wait", {}),
        (6.0, 9.0, "engine.step", {"tick": 1}),
        (6.0, 7.0, "engine.prefill", {"tokens": 16}),
    ]


def _trace():
    tr = xplane.Trace()
    tr.host = [(0.0, 10.0, names.WINDOW_SPAN)]
    tr.ops = [[(1.0, 2.0, "a"), (1.5, 3.0, "b"), (5.0, 6.0, "a"), (9.5, 11.0, "c")]]
    return tr


def test_segments_label_the_innermost_span():
    assert spans.segments(_program(), 0.0, 10.0) == [
        (0.0, 1.0, spans.OUTSIDE_STEP),
        (1.0, 1.5, "engine.admit"),
        (1.5, 2.0, "engine.decode"),
        (2.0, 3.5, "engine.decode.wait"),
        (3.5, 4.0, "engine.step"),
        (4.0, 6.0, spans.OUTSIDE_STEP),
        (6.0, 7.0, "engine.prefill"),
        (7.0, 9.0, "engine.step"),
        (9.0, 10.0, spans.OUTSIDE_STEP),
    ]
    # clipped to the window
    assert spans.segments(_program(), 2.5, 6.5)[0] == (2.5, 3.5, "engine.decode.wait")
    assert spans.segments(_program(), 2.5, 6.5)[-1] == (6.0, 6.5, "engine.prefill")


def test_idle_by_span_splits_every_gap():
    tr = _trace()
    lo, hi = tr.window
    idle = spans.idle_by_span(tr.ops[0], _program(), lo, hi)
    # gaps (0, 1), (3, 5), (6, 9.5)
    assert idle == pytest.approx({
        spans.OUTSIDE_STEP: 2.5, "engine.decode.wait": 0.5,
        "engine.step": 2.5, "engine.prefill": 1.0,
    })
    assert sum(idle.values()) == pytest.approx(hi - lo - xplane.busy_s(tr))
    assert spans.host_idle_pct(idle, hi - lo) == pytest.approx(40.0)
    assert spans.step_ms(_program(), lo, hi) == pytest.approx(3000.0)


def test_no_step_span_reads_none(capsys):
    tr = _trace()
    lo, hi = tr.window
    program = [e for e in _program() if e[2] != "engine.step"]
    assert spans.step_ms(program, lo, hi) is None
    assert spans.idle_by_span(tr.ops[0], program, lo, hi) is None
    assert spans.host_idle_pct(None, hi - lo) is None
    assert "no engine.step span" in capsys.readouterr().err


def _run(requests):
    return Run(
        conf={}, mix={}, peaks={}, seconds=10.0, setup_s=1.0, w0=10.0, w1=20.0,
        requests=requests, steps=0, compiles=[], suspensions=0, pool_peak=0.0,
        decode_calls=[], prefill_calls=[],
    )


def test_light_ttft_split_and_prefill():
    reqs = [
        Req("w0", "chat", "light", "window", 10.0, (1,), 2, stamps=[10.5, 10.6]),
        Req("w1", "chat", "light", "window", 11.0, (1,), 2, stamps=[11.5]),
        Req("w2", "docs", "heavy", "window", 12.0, (1,), 2, stamps=[19.0]),
        Req("m0", "chat", "light", "warm", 1.0, (1,), 2, stamps=[1.5]),
    ]
    stamps = {
        "w0": (10.1, 10.2, 10.45),
        "w1": (11.0, None, None),  # never admitted: left out
        "w2": (12.0, 12.0, 18.9),
        "m0": (1.0, 1.1, 1.2),
    }
    run = _run(reqs)
    assert spans.light_ttft_split(run, stamps) == pytest.approx({
        "due_to_submit": 0.1, "submit_to_admit": 0.1,
        "admit_to_first": 0.25, "first_to_client": 0.05,
    })
    assert spans.light_prefill_ms(run, stamps) == pytest.approx(250.0)


def test_light_prefill_without_stamps_reads_none(capsys):
    run = _run([Req("w0", "chat", "light", "window", 10.0, (1,), 2, stamps=[11.0])])
    assert spans.light_prefill_ms(run, {}) is None
    assert "stamps" in capsys.readouterr().err


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(_paged_step)/layers/while/body/closed_call/pool_view/gather:", "layers/pool_view"),
    ("jit(_paged_step)/layers/while/body/closed_call/attention/"
     "jit(paged_decode_attention)/paged_decode_attention/pallas_call:", "layers/attention"),
    ("jit(_paged_step)/layers/while:", "layers"),
    ("jit(<lambda>)/prefill/while/body/closed_call/ffn/moe_ffn/dot_general:", "prefill/ffn"),
    ("jit(scatter)/scatter:", ""),
    ("", ""),
])
def test_scope_of(tf_op, scope):
    assert spans.scope_of(tf_op) == scope


def test_device_by_scope_on_hand_made_ops():
    ops = [
        (0.0, 1.0, "fusion.1", "jit(_paged_step)/layers/while/body/closed_call/pool_view/gather:"),
        (1.0, 2.0, "copy.3", ""),
        (2.0, 2.5, "while.4", "jit(_paged_step)/layers/while:"),
        (3.0, 4.0, "paged_decode_attention.6",
         "jit(_paged_step)/layers/while/body/closed_call/attention/pallas_call:"),
        (5.0, 6.0, "scatter.1", "jit(scatter)/scatter:"),
        (7.0, 7.5, "copy.9", ""),
    ]
    modules = [(0.0, 4.0, "jit__paged_step(12)"), (5.0, 6.0, "jit_scatter(3)")]
    got = spans.device_by_scope((ops, modules), 0.5, 10.0)
    assert got == pytest.approx({
        "jit__paged_step/layers/pool_view": 0.5, "jit__paged_step": 1.0,
        "jit__paged_step/layers/attention": 1.0, "jit_scatter": 1.0,
        "(no module)": 0.5,
    })
    top = spans.device_by_scope((ops, modules), 0.0, 10.0, n=2)
    assert list(top.values()) == [1.0, 1.0]
    assert "jit__paged_step/layers/pool_view/fusion.1" in top


def test_decoded_ops_match_profile_data():
    """The protobuf decoding reads the same op and program events as
    ``jax.profiler.ProfileData`` (to its nanosecond rounding)."""
    path = DATA / "selfcheck.xplane.pb.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    tr = xplane.read(path)
    ops, modules = spans.read_scoped_ops(path)
    assert [o[2] for o in ops] == [o[2] for o in tr.ops[0]]
    assert [m[2] for m in modules] == [m[2] for m in tr.modules[0]]
    for mine, theirs in ((ops, tr.ops[0]), (modules, tr.modules[0])):
        for x, y in zip(mine, theirs):
            assert x[0] == pytest.approx(y[0], abs=2e-9)
            assert x[1] == pytest.approx(y[1], abs=2e-9)
    paths = [o[3] for o in ops]
    assert sum(p.startswith("jit(_paged_step)/") for p in paths) > 0


def test_recorded_spans_trace():
    path = DATA / "selfcheck-spans.xplane.pb.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    want = json.loads((DATA / "selfcheck-spans.json").read_text())
    tr = xplane.read(path)
    lo, hi = tr.window
    program = spans.read_program(path)
    assert hi - lo == pytest.approx(want["window_s"], rel=1e-9)
    # against what the run itself counted: one engine.step span per step
    assert len(spans.steps(program, lo, hi)) == want["steps"] > 0
    assert spans.step_ms(program, lo, hi) == pytest.approx(want["step_ms"], rel=1e-9)
    idle = spans.idle_by_span(tr.ops[0], program, lo, hi)
    assert idle == pytest.approx(want["idle_by_span"], rel=1e-9)
    idle_s = (hi - lo) - sum(b - a for a, b in xplane.union(tr.ops[0], lo, hi))
    assert sum(idle.values()) == pytest.approx(idle_s, rel=0.01)
    host = spans.host_idle_pct(idle, hi - lo)
    assert host == pytest.approx(want["host_idle_pct"], rel=1e-9)
    assert host <= 100.0 * (1.0 - xplane.busy_s(tr) / (hi - lo))
    scoped = spans.read_scoped_ops(path)
    by_scope = spans.device_by_scope(scoped, lo, hi)
    assert by_scope == pytest.approx(want["device_by_scope"], rel=1e-9)
    for key in ("jit__paged_step/layers/attention", "jit__paged_step/layers/pool_view",
                "jit__paged_step/layers/kv_write"):
        assert by_scope.get(key, 0.0) > 0.0, key
    assert all(want["checks"].values()), want["checks"]
