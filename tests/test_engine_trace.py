"""The engine's own tracing: ``engine.*`` host spans on the JAX profiler's
clock, and the per-request host-clock stamps behind ``RequestOutcome``'s
seconds.

A MURS run under KV pressure (suspensions and resumes) is traced
for its first ticks and read back from the ``.xplane.pb`` with
``jax.profiler.ProfileData``; the same run without the profiler must serve
the same tokens.
"""

from pathlib import Path

import jax
import pytest

from repro.configs import ARCHS
from repro.models import init_model
from repro.sched import MursConfig
from repro.serve import EngineConfig, Request, ServingEngine
from repro.serve.kv_cache import kv_bytes_per_token

TRACED_TICKS = 12
MAX_TICKS = 400

#: phase spans and the span each one nests in
PARENT = {
    "engine.admit": "engine.step",
    "engine.prefill": "engine.step",
    "engine.decode": "engine.step",
    "engine.policy": "engine.step",
    "engine.tiers": "engine.step",
    "engine.prefill.install": "engine.prefill",
    "engine.prefill.chunk": "engine.prefill",
    "engine.prefill.prefix_hit": "engine.prefill",
    "engine.prefill.first_token": "engine.prefill",
    "engine.decode.plan": "engine.decode",
    "engine.decode.put": "engine.decode",
    "engine.decode.launch": "engine.decode",
    "engine.decode.wait": "engine.decode",
    "engine.decode.bookkeep": "engine.decode",
}


def _requests():
    # the pool holds 80 tokens, so MURS suspends and resumes
    reqs = [Request(f"A{i}", "A", list(range(10, 18)), 40) for i in range(3)]
    reqs += [Request(f"B{i}", "B", list(range(30, 34)), 6) for i in range(4)]
    return reqs


def _engine(cfg, params):
    eng = ServingEngine(
        cfg, params,
        EngineConfig(n_slots=4, max_seq=64,
                     hbm_capacity_bytes=kv_bytes_per_token(cfg) * 80,
                     scheduler=MursConfig(period=1.0)),
    )
    for r in _requests():
        eng.submit(r)
    return eng


def _run(eng, ticks, suspended):
    for _ in range(ticks):
        if not eng.has_pending:
            return
        eng.step()
        suspended.update(
            rid for rid, r in eng.requests.items() if r.state == "suspended"
        )


def _engine_spans(logdir):
    """(start ns, end ns, name, args) of every ``engine.*`` host event."""
    path = next(Path(logdir).rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    args = {k: v for k, v in e.stats}
                    out.append((e.start_ns, e.end_ns, e.name, args))
    return sorted(out)


@pytest.fixture(scope="module")
def small_model():
    cfg = ARCHS["internlm2-1.8b"].smoke()
    return cfg, init_model(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def traced(small_model, tmp_path_factory):
    """The run with its first ticks traced: (engine, spans, suspended)."""
    eng = _engine(*small_model)
    logdir = tmp_path_factory.mktemp("engine_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    suspended = set()
    with jax.profiler.trace(str(logdir), profiler_options=opts):
        _run(eng, TRACED_TICKS, suspended)
    _run(eng, MAX_TICKS, suspended)
    assert not eng.has_pending
    return eng, _engine_spans(logdir), suspended


def test_every_step_holds_its_phases(traced):
    _, spans, _ = traced
    steps = [s for s in spans if s[2] == "engine.step"]
    assert [s[3]["tick"] for s in steps] == list(range(TRACED_TICKS))
    for a, b, name, _ in spans:
        if name == "engine.step":
            continue
        assert name in PARENT, name
        parents = [p for p in spans
                   if p[2] == PARENT[name] and p[0] <= a and b <= p[1]]
        assert len(parents) == 1, (name, a, b)
    for a, b, _, _ in steps:
        inside = [s[2] for s in spans if a <= s[0] and s[1] <= b]
        for phase in ("engine.admit", "engine.prefill", "engine.decode",
                      "engine.tiers"):
            assert inside.count(phase) == 1, (phase, inside)


def test_phase_spans_carry_counts(traced):
    eng, spans, _ = traced
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s[3])
    assert sum(a["admitted"] for a in by["engine.admit"]) >= 4
    assert sum(a["tokens"] for a in by["engine.prefill"]) > 0
    rows = [a["rows"] for a in by["engine.decode"]]
    plan_rows = [a["rows"] for a in by["engine.decode.plan"] if "rows" in a]
    assert max(rows) > 0 and sorted(plan_rows) == sorted(r for r in rows if r)
    assert all(a["width"] > 0 and a["kv_tokens"] > a["rows"]
               for a in by["engine.decode.plan"] if "rows" in a)
    assert len(by["engine.decode.wait"]) == len(plan_rows)
    assert sum(a["suspended"] for a in by.get("engine.policy", [])) <= eng.suspensions


def test_prefill_spans_carry_the_request(traced):
    eng, spans, _ = traced
    install = [s[3] for s in spans if s[2] == "engine.prefill.install"]
    assert install
    for args in install:
        # a resumed request replays its prompt and the tokens it had
        req = eng.requests[args["rid"]]
        assert len(req.prompt) <= args["tokens"]
        assert args["tokens"] < len(req.prompt) + len(req.generated)
    firsts = {s[3]["rid"] for s in spans if s[2] == "engine.prefill.first_token"}
    hits = {s[3]["rid"] for s in spans if s[2] == "engine.prefill.prefix_hit"}
    assert firsts and firsts <= set(eng.requests)
    assert hits <= set(eng.requests)


def test_tokens_equal_with_the_profiler_off(traced, small_model):
    eng, _, _ = traced
    ref = _engine(*small_model)
    _run(ref, MAX_TICKS, set())
    assert not ref.has_pending
    assert {k: r.generated for k, r in ref.requests.items()} == {
        k: r.generated for k, r in eng.requests.items()
    }


def test_stamps_are_ordered(traced):
    eng, _, suspended = traced
    resumed = suspended & {k for k, r in eng.requests.items() if r.state == "done"}
    assert resumed, "the run should suspend and later finish a request"
    for r in eng.requests.values():
        assert r.state == "done"
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_finish, r.request_id


def test_outcome_seconds_agree_with_the_stamps(traced):
    eng, _, _ = traced
    outcomes = eng.report().outcomes
    assert len(outcomes) == len(eng.requests)
    for o in outcomes:
        r = eng.requests[o.request_id]
        assert o.queue_s == pytest.approx(r.t_admit - r.t_submit, abs=1e-9)
        assert o.ttft_s == pytest.approx(r.t_first - r.t_submit, abs=1e-9)
        assert o.latency_s == pytest.approx(r.t_finish - r.t_submit, abs=1e-9)
        assert 0.0 <= o.queue_s <= o.ttft_s <= o.latency_s


def test_unstamped_outcome_seconds_are_none(small_model):
    eng = ServingEngine(
        small_model[0], small_model[1],
        EngineConfig(n_slots=1, max_seq=64, hbm_capacity_bytes=1e12),
    )
    eng.submit(Request("q", "T", [1, 2, 3], 4))
    (o,) = eng.report().outcomes
    assert o.outcome == "unfinished"
    assert o.queue_s is None and o.ttft_s is None and o.latency_s is None
