"""Reduction of the program's own tracing in a profiler trace: the
engine's ``engine.*`` host spans (``ServingEngine.step`` and its phases,
with their arguments), the device ops' named scopes, and the requests'
host-clock stamps.

Times are in seconds on the trace's clock, as in ``xplane``; the traced
window ``[lo, hi]`` is ``xplane.Trace.window``.  A reading with nothing to
read returns None and says why on standard error; it never reads 0.

The scope names below are the program's (``jax.named_scope`` in
``models/transformer.py``, ``models/blocks.py`` and ``serve/engine.py``).
"""

from __future__ import annotations

import gzip
import statistics
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import xplane

#: prefix of the engine's host spans, and the span of one ``step()``
PREFIX = "engine."
STEP = "engine.step"
#: label of time under no ``engine.step`` span
OUTSIDE_STEP = "outside_step"
#: the program's named scopes, outermost first in an op's path
SCOPES = ("prefill", "chunk_scan", "layers", "kv_write", "pool_view",
          "attention", "wo", "ffn", "moe_ffn")

Event = Tuple[float, float, str, Dict[str, Any]]  # (start, end, name, args)


def _say(msg: str) -> None:
    print(f"spans: {msg}", file=sys.stderr)


def _read_bytes(path) -> bytes:
    data = Path(path).read_bytes()
    return gzip.decompress(data) if str(path).endswith(".gz") else data


# ------------------------------------------------------------- host spans
def read_program(path) -> List[Event]:
    """The ``engine.*`` host events of an ``.xplane.pb`` (or its gzip),
    with their arguments, sorted by start."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(_read_bytes(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    args = {k: v for k, v in e.stats}
                    out.append((s, s + e.duration_ns * 1e-9, e.name, args))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def steps(program: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """The ``engine.step`` spans that start inside ``[lo, hi]``."""
    return [e for e in program if e[2] == STEP and lo <= e[0] <= hi]


def step_ms(program: Sequence[Event], lo: float, hi: float) -> Optional[float]:
    """Mean duration of the ``engine.step`` spans that start in the
    window, in ms."""
    st = steps(program, lo, hi)
    if not st:
        _say(f"no {STEP} span in the traced window")
        return None
    return 1e3 * sum(b - a for a, b, _, _ in st) / len(st)


def segments(program: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut into consecutive pieces, each labelled with the
    innermost engine span over it, or ``OUTSIDE_STEP``.  The engine's
    spans nest (one thread), so a stack of open spans gives the label."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []  # (end, name) of open spans
    t = lo

    def until(to: float) -> None:
        nonlocal t
        to = min(to, hi)
        if to > t:
            out.append((t, to, stack[-1][1] if stack else OUTSIDE_STEP))
            t = to

    for a, b, name, _ in program:
        while stack and stack[-1][0] <= a:
            until(stack[-1][0])
            stack.pop()
        until(a)
        stack.append((b, name))
    while stack:
        until(stack[-1][0])
        stack.pop()
    until(hi)
    return out


def idle_intervals(ops0: Sequence[xplane.Span], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The gaps in ``[lo, hi]`` with no op on a device."""
    gaps, t = [], lo
    for a, b in xplane.union(ops0, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_by_span(ops0: Sequence[xplane.Span], program: Sequence[Event],
                 lo: float, hi: float) -> Optional[Dict[str, float]]:
    """Device-idle seconds of the window under each innermost engine
    span, and under no step (``OUTSIDE_STEP``); they sum to the window's
    idle time.  ``ops0``: one device's op spans."""
    if not steps(program, lo, hi):
        _say(f"no {STEP} span in the traced window: idle_by_span left out")
        return None
    out: Dict[str, float] = {}
    segs = segments(program, lo, hi)
    i = 0
    for g0, g1 in idle_intervals(ops0, lo, hi):
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            a, b, name = segs[j]
            d = min(b, g1) - max(a, g0)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            j += 1
    return out


def host_idle_pct(idle: Optional[Dict[str, float]], window_s: float) -> Optional[float]:
    """Share of the window in which the device idles while the host is
    inside ``engine.step``, in percent."""
    if idle is None or window_s <= 0.0:
        return None
    return 100.0 * (sum(idle.values()) - idle.get(OUTSIDE_STEP, 0.0)) / window_s


# ---------------------------------------------------- request host stamps
def light_ttft_split(run, stamps: Dict[str, Tuple]) -> Optional[Dict[str, float]]:
    """Medians, over the light requests due in the window that hold every
    stamp, of the parts of their TTFT in seconds: due → submit (the
    client's lateness), submit → admit, admit → first token, first token
    → the client's stamp.  ``stamps``: request id → the engine's
    ``(t_submit, t_admit, t_first)``."""
    parts: Dict[str, List[float]] = {
        "due_to_submit": [], "submit_to_admit": [], "admit_to_first": [],
        "first_to_client": [],
    }
    for r in run.due_in_window("light"):
        t = stamps.get(r.rid)
        if t is None or None in t or not r.stamps:
            continue
        sub, adm, first = t
        for k, v in zip(parts, (sub - r.due, adm - sub, first - adm, r.stamps[0] - first)):
            parts[k].append(v)
    if not parts["admit_to_first"]:
        _say("no light request due in the window holds the engine's stamps")
        return None
    return {k: statistics.median(v) for k, v in parts.items()}


def light_prefill_ms(run, stamps: Dict[str, Tuple]) -> Optional[float]:
    """Median over the light requests due in the window of first token −
    admission (the engine's stamps), in ms."""
    split = light_ttft_split(run, stamps)
    return None if split is None else 1e3 * split["admit_to_first"]


# ---------------------------------------------- device ops by named scope
def _varint(buf, i: int) -> Tuple[int, int]:
    v = s = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << s
        s += 7
        if b < 0x80:
            return v, i


def _fields(buf):
    """(field number, value) of a protobuf message: an int for varints,
    a memoryview for length-delimited fields, raw bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wt in (1, 5):
            size = 8 if wt == 1 else 4
            v, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wt} not supported")
        yield f, v


def _map_values(entry) -> Tuple[int, Any]:
    key, val = 0, None
    for f, v in _fields(entry):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


# field numbers of tensorflow/tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TS_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_MD_NAME, _MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _device_plane(data: bytes):
    """Fields of the first device plane, as ``xplane.read`` orders them."""
    for f, plane in _fields(memoryview(data)):
        if f != _SPACE_PLANES:
            continue
        for pf, v in _fields(plane):
            if pf == _PLANE_NAME:
                if xplane._DEVICE_PLANE.match(bytes(v).decode()):
                    return plane
                break
    return None


def read_scoped_ops(path) -> Optional[Tuple[List[Tuple[float, float, str, str]], List[xplane.Span]]]:
    """Device 0's op events as ``(start, end, op name, tf_op path)`` and
    its program events, read from the XSpace protobuf itself:
    ``jax.profiler.ProfileData`` does not expose the event metadata's
    ``tf_op`` stat.  None (and why, on standard error) when the trace has
    no device plane or no ``tf_op`` stat."""
    plane = _device_plane(_read_bytes(path))
    if plane is None:
        _say("no device plane in the trace")
        return None
    lines, event_md, stat_names = {}, {}, {}
    for f, v in _fields(plane):
        if f == _PLANE_LINES:
            line = dict(_fields(v))
            name = bytes(line.get(_LINE_NAME, b"")).decode()
            lines[name] = v
        elif f == _PLANE_EVENT_MD:
            k, md = _map_values(v)
            event_md[k] = md
        elif f == _PLANE_STAT_MD:
            k, md = _map_values(v)
            stat_names[k] = bytes(dict(_fields(md)).get(_MD_NAME, b"")).decode()
    tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    if tf_op is None or "XLA Ops" not in lines:
        _say("the trace's device plane has no tf_op stat or no XLA Ops line")
        return None
    names: Dict[int, Tuple[str, str]] = {}
    for k, md in event_md.items():
        name, path_ = "", ""
        for f, v in _fields(md):
            if f == _MD_NAME:
                name = bytes(v).decode().split(" = ", 1)[0].lstrip("%")
            elif f == _MD_STATS:
                st = dict(_fields(v))
                if st.get(_STAT_MD_ID) == tf_op:
                    if _STAT_STR in st:
                        path_ = bytes(st[_STAT_STR]).decode()
                    elif _STAT_REF in st:
                        path_ = stat_names.get(st[_STAT_REF], "")
        names[k] = (name, path_)

    def events(line_buf):
        ts = 0
        evs = []
        for f, v in _fields(line_buf):
            if f == _LINE_TS_NS:
                ts = v
            elif f == _LINE_EVENTS:
                evs.append(v)
        for ev in evs:
            e = dict(_fields(ev))
            s = ts * 1e-9 + e.get(_EVENT_OFFSET_PS, 0) * 1e-12
            yield s, s + e.get(_EVENT_DURATION_PS, 0) * 1e-12, e.get(_EVENT_MD_ID, 0)

    ops = [(a, b, *names.get(m, ("", ""))) for a, b, m in events(lines["XLA Ops"])]
    modules = []
    if "XLA Modules" in lines:
        for a, b, m in events(lines["XLA Modules"]):
            # a module event's metadata name is "<module>(<program id>)"
            modules.append((a, b, names.get(m, ("", ""))[0]))
    return ops, modules


def scope_of(tf_op: str) -> str:
    """The program's named scopes in an op's ``tf_op`` path, outermost
    first and at most two deep (``layers/pool_view``); "" for none."""
    found = [c for c in tf_op.split(":")[0].split("/")[1:] if c in SCOPES]
    return "/".join(found[:2])


def _module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def device_by_scope(scoped, lo: float, hi: float, n: int = 0) -> Dict[str, float]:
    """Device seconds in the window per ``<module>/<scope>``: each op is
    given the program whose event on the device holds its start, and the
    named scopes of its ``tf_op`` path.  With ``n``, the ``n`` op names
    with the most time instead, each as ``<module>/<scope>/<op>``.
    Containers (a layer scan's loop) are left out, as in
    ``xplane.top_ops``."""
    ops, modules = scoped
    modules = sorted(modules)
    out: Dict[str, float] = {}
    j = 0
    for a, b, name, tf_op in sorted(ops):
        if name.split(".")[0] in xplane._CONTAINERS:
            continue
        d = min(b, hi) - max(a, lo)
        if d <= 0:
            continue
        while j < len(modules) and modules[j][1] < a:
            j += 1
        mod = (_module_name(modules[j][2])
               if j < len(modules) and modules[j][0] <= a else "(no module)")
        key = "/".join(p for p in (mod, scope_of(tf_op)) if p)
        if n:
            key = f"{key}/{name}"
        out[key] = out.get(key, 0.0) + d
    rows = sorted(out.items(), key=lambda kv: -kv[1])
    return dict(rows[:n] if n else rows)
