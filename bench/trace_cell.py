#!/usr/bin/env python3
"""Make one cell's traced run (``run.py --trace 1``) and reduce the
program's own tracing in it (``spans.py``): the engine's spans, the
device ops' named scopes and the requests' host-clock stamps.

    python3 bench/trace_cell.py --workload internlm2.docs --seed 7 \
        --seconds 51 [--trace 0] [--keep cell.xplane.pb.gz]

run.py's result line comes first, as run.py prints it.  The last line of
standard output is this script's JSON: ``step_ms``, ``host_idle_pct``,
``light_prefill_ms``, ``idle_by_span``, ``device_by_scope`` and the top
ops by program and scope, the light requests' TTFT split, the traced
run's end-to-end readings, the seconds ``stop_trace`` took, the cost of
one engine span with no profiler active, and ``checks``: the readings
held against each other and against what the run itself counted.
With ``--trace 0`` the run is untraced and the line holds only what the
request stamps give, the end-to-end readings and the span cost.

run.py does not read the program's tracing yet; this script wraps its
``Tracer``, ``Run`` and ``build_engine`` from outside to get at it.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import names  # noqa: E402
import run as R  # noqa: E402
import spans  # noqa: E402
import xplane  # noqa: E402

#: enter/exit pairs timed for the cost of a span with no profiler active
OFF_PAIRS = 100_000


class SpanTracer(R.Tracer):
    """``run.Tracer`` that also counts the engine's steps inside the
    traced window, times ``stop_trace`` and keeps the trace file."""

    #: the engine being traced (set by :func:`install`)
    engine = None
    #: the last tracer made
    last = None

    def __init__(self, start: float, stop: float):
        super().__init__(start, stop)
        self.tick0 = self.steps = self.stop_s = self.kept = None
        SpanTracer.last = self

    def tick(self, now: float) -> None:
        opening = self.lo is None
        super().tick(now)
        if opening and self.lo is not None:
            self.tick0 = self.engine.tick

    def close(self) -> None:
        if self.lo is None or self.hi is not None:
            return
        self.steps = self.engine.tick - self.tick0
        t = time.perf_counter()
        super().close()
        self.stop_s = time.perf_counter() - t

    def keep(self) -> Path:
        """Copy the trace file out of the directory ``summary`` removes."""
        if self.kept is None:
            src = next(Path(self.dir).rglob("*.xplane.pb"))
            fd, name = tempfile.mkstemp(suffix=".xplane.pb")
            with open(fd, "wb") as out, open(src, "rb") as f:
                shutil.copyfileobj(f, out)
            self.kept = Path(name)
        return self.kept

    def summary(self):
        self.keep()
        return super().summary()


def install():
    """Wrap run.py's ``Tracer``, ``Run`` and ``build_engine``; returns the
    list the runs made are appended to."""
    runs = []

    class CapturedRun(R.Run):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            runs.append(self)

    build = R.build_engine

    def build_engine(*a, **k):
        SpanTracer.engine = build(*a, **k)
        return SpanTracer.engine

    R.Tracer, R.Run, R.build_engine = SpanTracer, CapturedRun, build_engine
    return runs


def span_off_us() -> dict:
    """Microseconds per engine span with no profiler active: plain, with
    keyword arguments, and with arguments set at exit."""
    from jax.profiler import TraceAnnotation

    out = {}
    t = time.perf_counter()
    for _ in range(OFF_PAIRS):
        with TraceAnnotation("engine.decode.put"):
            pass
    out["plain"] = (time.perf_counter() - t) / OFF_PAIRS * 1e6
    t = time.perf_counter()
    for i in range(OFF_PAIRS):
        with TraceAnnotation("engine.step", tick=i):
            pass
    out["args"] = (time.perf_counter() - t) / OFF_PAIRS * 1e6
    t = time.perf_counter()
    for i in range(OFF_PAIRS):
        with TraceAnnotation("engine.decode") as span:
            span.set_metadata(rows=i)
    out["set_metadata"] = (time.perf_counter() - t) / OFF_PAIRS * 1e6
    return out


def reduce(path, *, steps_run: int) -> dict:
    """The program-tracing readings of one trace file, and the checks
    that need only the trace and the run's count of steps in it."""
    tr = xplane.read(path)
    lo, hi = tr.window
    window_s = hi - lo
    program = spans.read_program(path)
    ops0 = tr.ops[0] if tr.ops else []
    idle = spans.idle_by_span(ops0, program, lo, hi)
    busy0 = sum(b - a for a, b in xplane.union(ops0, lo, hi))
    step_spans = spans.steps(program, lo, hi)
    in_steps = sum(min(b, hi) - a for a, b, _, _ in step_spans)
    out = {
        "window_s": window_s,
        "busy_s": xplane.busy_s(tr),
        "device_idle_pct": 100.0 * (1.0 - xplane.busy_s(tr) / window_s),
        "step_ms": spans.step_ms(program, lo, hi),
        "host_idle_pct": spans.host_idle_pct(idle, window_s),
        "idle_by_span": idle,
        "step_spans": len(step_spans),
        "spans_per_step": (
            sum(1 for e in program if lo <= e[0] <= hi) / max(len(step_spans), 1)
        ),
    }
    scoped = spans.read_scoped_ops(path)
    if scoped is not None:
        out["device_by_scope"] = spans.device_by_scope(scoped, lo, hi)
        out["top_ops_by_scope"] = spans.device_by_scope(scoped, lo, hi, n=12)
    idle_sum = sum(idle.values()) if idle else float("nan")
    matched = {k: xplane.device_time(tr.modules, p)[1] for k, p in names.MODULES.items()}
    matched["kernel"] = xplane.device_time(tr.ops, names.KERNEL_PAGED_DECODE)[1]
    out["pattern_events"] = matched
    out["checks"] = {
        "step_spans_equal_steps": len(step_spans) == steps_run,
        "host_idle_le_device_idle": (
            out["host_idle_pct"] is not None
            and out["host_idle_pct"] <= out["device_idle_pct"] + 1e-9
        ),
        "idle_by_span_sums_to_idle": abs(idle_sum - (window_s - busy0))
        <= 0.01 * (window_s - busy0),
        "steps_and_outside_cover_window": idle is not None and abs(
            in_steps + idle.get(spans.OUTSIDE_STEP, 0.0) - window_s
        ) <= 0.02 * window_s,
        "patterns_match": all(n > 0 for n in matched.values()),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1,
                    help="0: an untraced run, read for its request stamps only")
    ap.add_argument("--keep", default=None,
                    help="write the trace here, gzipped")
    args = ap.parse_args(argv)
    runs = install()
    rc = R.main(["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", str(args.trace)])
    tracer = SpanTracer.last
    if rc != 0 or not runs or (args.trace and (tracer is None or tracer.kept is None)):
        return rc or 1
    run, engine = runs[-1], SpanTracer.engine
    out = {}
    if args.trace:
        out = reduce(tracer.kept, steps_run=tracer.steps)
        out["steps"] = tracer.steps
        out["stop_trace_s"] = tracer.stop_s
    stamps = {rid: (q.t_submit, q.t_admit, q.t_first)
              for rid, q in engine.requests.items()}
    out["light_ttft_split_s"] = spans.light_ttft_split(run, stamps)
    out["light_prefill_ms"] = spans.light_prefill_ms(run, stamps)
    for m in ("light_ttft_p50_s", "itl_p99_s"):
        out[m] = R._load_module(BENCH / "metrics" / f"{m}.py").read(run)
    out["span_off_us"] = span_off_us()
    if args.trace:
        out["step_off_us"] = out["span_off_us"]["args"] * out["spans_per_step"]
        if args.keep:
            Path(args.keep).parent.mkdir(parents=True, exist_ok=True)
            Path(args.keep).write_bytes(gzip.compress(tracer.kept.read_bytes()))
        tracer.kept.unlink()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
