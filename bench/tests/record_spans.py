#!/usr/bin/env python3
"""Record the small trace that ``test_spans.py`` checks the reduction of
the program's own tracing on.

    python3 bench/tests/record_spans.py      # on a TPU

``record_trace.py``'s recipe: a miniature model (``data/trace-tiny.json``)
under the ``tiny-docs`` mix for two seconds through the harness's own
client, the second of them traced.  Writes
``bench/data/selfcheck-spans.xplane.pb.gz`` with ``selfcheck-spans.json``:
the reduction's readings (``trace_cell.reduce``) and the engine steps the
run itself made inside the traced window, which the test holds the
reduction to.
"""

import gzip
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402

import run as R  # noqa: E402
import trace_cell  # noqa: E402
import traffic  # noqa: E402

OUT = HERE.parent / "data"


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU", file=sys.stderr)
        return 2
    from repro.launch import enable_compile_cache

    enable_compile_cache()
    conf = json.loads((HERE / "data" / "trace-tiny.json").read_text())
    mix = json.loads((HERE / "data" / "tiny-docs.json").read_text())
    cfg, params, engine, probe, ref, warmed = R.prepare(conf, mix, 5)
    client = R.Client(engine)
    arrivals = traffic.schedule(mix, 5, 2.0, cfg.vocab, "window")
    t0 = time.perf_counter()
    trace_cell.SpanTracer.engine = engine
    client.hook = tracer = trace_cell.SpanTracer(t0 + 0.5, t0 + 1.5)
    client.serve(arrivals, "window", t0, t0 + 2.0)
    tracer.close()
    pb = tracer.keep()
    OUT.mkdir(exist_ok=True)
    path = OUT / "selfcheck-spans.xplane.pb.gz"
    path.write_bytes(gzip.compress(pb.read_bytes()))
    pb.unlink()
    want = trace_cell.reduce(path, steps_run=tracer.steps)
    want["steps"] = tracer.steps
    (OUT / "selfcheck-spans.json").write_text(json.dumps(want, indent=1) + "\n")
    print(json.dumps(want))
    return 0 if all(want["checks"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
