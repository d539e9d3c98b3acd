"""Device time by named scope, and idle time by program span, in a trace.

The program puts ``jax.named_scope``s on its layers (``wave_search`` and
``wave_commit`` in ``construct.wave_core``; ``coarse_pass`` and ``expand``
in ``core.search``).  A scope lands in each device op's HLO ``op_name``,
which the TPU profiler keeps as the op's ``tf_op`` stat, for example
``jit(search)/coarse_pass/jit(search)/expand/while/body/gather:``.
``jax.profiler.ProfileData`` hands out each event's own stats only, and the
``tf_op`` sits in the plane's event metadata, so ``tf_ops`` decodes it from
the ``.xplane.pb`` with a small protobuf reader (no TensorFlow), and
``attach`` sets it on the ops of a ``bench.trace.Trace`` as
``Event.stats["tf_op"]``, matched op for op by position and name.

``scope_runs``/``scope_seconds`` then give the device self time of one
module's ops under a scope (a ``while`` op, which has no ``tf_op`` of its
own on the TPU, under its loop's: ``op_paths``).  A scope that matches nothing raises
``KeyError``, as everywhere in ``bench/trace.py``: the per-layer reader
reports nothing on a program without the scope.

The program's ``obs`` spans (``index/search``, ``build/wave``, ...) are
profiler ``TraceAnnotation``s too; ``idle_by_span`` puts the window's idle
device time down to the innermost one open across each gap.

    python bench/scopes.py <trace dir>   # scope and idle-by-span summary
"""

from __future__ import annotations

import argparse
import bisect
import os
import re
import sys

if __package__ in (None, ""):  # run as a script: make ``bench`` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace as trace_lib  # noqa: E402

#: the program's own span names (``repro.obs`` vocabulary) on the host plane
PROGRAM_SPAN = re.compile(r"^(index|build|serve|router)/")

# ---------------------------------------------------------------------------
# the ops' tf_op, from the XPlane protobuf (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field (fixed-width ones skipped)."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i : i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} in an XPlane")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def tf_ops(data: bytes) -> dict:
    """Device plane name -> [(op name, tf_op or None)], one per event of its
    ``XLA Ops`` line, in the file's order.

    XSpace: planes = 1.  XPlane: name = 2, lines = 3, event_metadata = 4
    (map id -> XEventMetadata: name = 2, stats = 5), stat_metadata = 5 (map
    id -> XStatMetadata: name = 2).  XLine: name = 2, events = 4.  XEvent:
    metadata_id = 1.  XStat: metadata_id = 1, str_value = 5, ref_value = 7
    (the id of a stat metadata whose name is the string)."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        raw = {2: [], 3: [], 4: [], 5: []}
        for g, v in _fields(plane):
            if g in raw:
                raw[g].append(v)
        names = [_text(v) for v in raw[2]]
        if not names or not trace_lib._DEVICE.match(names[0]):
            continue
        stat_names, ev_meta = {}, {}
        for g in (5, 4):
            for v in raw[g]:
                entry = dict(_fields(v))  # a map entry: key = 1, value = 2
                meta = list(_fields(entry.get(2, b"")))
                if g == 5:
                    stat_names[entry.get(1, 0)] = next(
                        (_text(x) for h, x in meta if h == 2), "")
                else:
                    ev_meta[entry.get(1, 0)] = meta
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        ops = {}
        for mid, meta in ev_meta.items():
            op, tf_op = "", None
            for h, x in meta:
                if h == 2:
                    op = _text(x)
                elif h == 5:
                    stat = list(_fields(x))
                    if any(k == 1 and v in tf_op_ids for k, v in stat):
                        for k, v in stat:
                            if k == 5:
                                tf_op = _text(v)
                            elif k == 7:
                                tf_op = stat_names.get(v)
            ops[mid] = (op, tf_op)
        for line in raw[3]:
            fields = list(_fields(line))
            if any(g == 2 and _text(v) == "XLA Ops" for g, v in fields):
                out[names[0]] = [
                    ops.get(next((x for h, x in _fields(v) if h == 1), 0), ("", None))
                    for g, v in fields if g == 4
                ]
    return out


def attach(trace: trace_lib.Trace, path: str) -> trace_lib.Trace:
    """Set ``stats["tf_op"]`` on the trace's device ops from the
    ``.xplane.pb`` at ``path`` (or the trace directory holding it), matched
    by position.  A plane whose ops do not line up with the file's, op for
    op by name, raises ``KeyError``: nothing is guessed."""
    if os.path.isdir(path):
        path = trace_lib.find_xplane(path)
    with open(path, "rb") as f:
        decoded = tf_ops(f.read())
    for plane, events in trace.ops.items():
        got = decoded.get(plane, [])
        if len(got) != len(events) or any(d[0] != e.name for d, e in zip(got, events)):
            raise KeyError(f"the XLA Ops of {plane} in {path} do not line up "
                           "with the trace's, op for op")
        for e, (_, tf_op) in zip(events, got):
            if tf_op is not None:
                e.stats["tf_op"] = tf_op
    return trace


def run_trace_dir(argv=None) -> str:
    """The trace directory of the ``bench/run.py`` run in progress, from its
    command line (``--workload``, ``--seed``, ``--trace-dir``), as ``run``
    names it."""
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--trace-dir", default=None)
    args, _ = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = args.trace_dir or os.path.join(root, ".bench_trace")
    return os.path.join(base, f"{args.workload}.{args.seed}")


def scoped(ctx) -> trace_lib.Trace:
    """A per-layer reader's trace with the ops' ``tf_op``s attached (once)."""
    trace = ctx.trace
    if not getattr(trace, "tf_ops_attached", False):
        path = run_trace_dir()
        if not os.path.isdir(path):
            raise KeyError(f"no trace directory {path} to read tf_ops from")
        attach(trace, path)
        trace.tf_ops_attached = True
    return trace


# ---------------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------------


def _nest(events) -> list:
    """[[event, device time net of the ops nested inside it, index of the op
    it nests in or None]] in start order: a ``while`` op spans its body's
    ops on the same line (the nesting rule of ``trace.self_times``)."""
    out: list = []
    stack: list = []  # indexes of the ops still open
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and out[stack[-1]][0].end <= e.start:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            out[parent][1] -= e.dur
        out.append([e, e.dur, parent])
        stack.append(len(out) - 1)
    return out


def _segments(tf_op: str) -> list:
    """``jit(search)/expand/while/body/gather:`` -> its path's segments."""
    return tf_op.rsplit(":", 1)[0].split("/") if tf_op else []


def op_paths(events) -> list:
    """[[event, own time, scope path]] in start order (``_nest``).  The
    path is the op's ``tf_op``.  A TPU ``while`` op carries none; it takes
    the path its nested ops share, cut after its last ``while`` segment (the
    loop the named scope was put around), so that its own time, the loop's
    control, counts under the loop's scope.  Any other op without a
    ``tf_op`` (a copy the compiler added) has the path ``""``."""
    rows = _nest(events)
    shared: dict = {}  # index -> the path segments its nested ops share
    for e, _, parent in rows:
        if parent is None:
            continue
        segs = _segments(str(e.stats.get("tf_op", "")))
        have = shared.setdefault(parent, segs)
        n = 0
        while n < min(len(have), len(segs)) and have[n] == segs[n]:
            n += 1
        shared[parent] = have[:n]
    out = []
    for i, (e, own, _) in enumerate(rows):
        path = str(e.stats.get("tf_op", ""))
        common = shared.get(i, [])
        if not path and "while" in common:
            path = "/".join(common[: len(common) - common[::-1].index("while")])
        out.append([e, own, path])
    return out


def in_scope(tf_op: str, scope: str, exclude=()) -> bool:
    """Whether an op's ``tf_op`` path holds the segment ``scope`` and none of
    ``exclude``: ``jit(search)/coarse_pass/jit(search)/expand/while:`` is in
    ``expand``, and is not once ``coarse_pass`` is excluded."""
    segments = _segments(tf_op)
    return scope in segments and not any(x in segments for x in exclude)


def _runs(trace: trace_lib.Trace, module: str):
    """(device, window run of ``module``, its ops) for each run."""
    for dev, mods in trace.modules.items():
        ops = sorted(trace.ops.get(dev, []), key=lambda e: e.start)
        starts = [e.start for e in ops]
        for run in trace_lib._in_window(trace, mods):
            if trace_lib.module_name(run.name) == module:
                lo = bisect.bisect_left(starts, run.start)
                hi = bisect.bisect_right(starts, run.end)
                yield dev, run, [e for e in ops[lo:hi] if e.end <= run.end]


def scope_runs(trace: trace_lib.Trace, module: str, scope: str, exclude=()) -> list:
    """[[run, seconds]] for each window run of the XLA module ``module`` (jit
    name, as ``trace.module_events``), in time order: the self time of the
    device ops inside the run whose path (``op_paths``) is ``in_scope``.
    No such op in any run raises ``KeyError``."""
    out, found = [], False
    for _, run, ops in _runs(trace, module):
        ns = 0
        for _, own, path in op_paths(ops):
            if in_scope(path, scope, exclude):
                ns, found = ns + own, True
        out.append([run, ns / 1e9])
    if not found:
        raise KeyError(f"no device op under scope {scope!r} in {module!r} in the window")
    return sorted(out, key=lambda r: r[0].start)


def scope_seconds(trace: trace_lib.Trace, module: str, scope: str, exclude=()) -> float:
    """Device self time of the window's ops of ``module`` under ``scope``."""
    return sum(secs for _, secs in scope_runs(trace, module, scope, exclude))


def module_scopes(trace: trace_lib.Trace, module: str, scopes) -> dict:
    """Device seconds of ``module``'s window runs by the first of ``scopes``
    (a scope of its own, or a (scope, exclude) pair) each op falls under,
    with ``"unscoped"`` for the ops under none and ``"idle"`` for the time of
    the runs that no op covers, and ``"runs"``, their number."""
    out = {"runs": 0, "idle": 0.0, "unscoped": 0.0}
    for _, run, ops in _runs(trace, module):
        out["runs"] += 1
        busy = sum(b - a for a, b in trace_lib.busy_intervals(ops, run.start, run.end))
        out["idle"] += (run.dur - busy) / 1e9
        for _, own, path in op_paths(ops):
            for s in scopes:
                name, exclude = (s, ()) if isinstance(s, str) else s
                if in_scope(path, name, exclude):
                    out[name] = out.get(name, 0.0) + own / 1e9
                    break
            else:
                out["unscoped"] += own / 1e9
    return out


# ---------------------------------------------------------------------------
# idle time by program span
# ---------------------------------------------------------------------------


def idle_by_span(trace: trace_lib.Trace, device: str | None = None) -> list:
    """[[span, idle seconds, gaps]] over every idle gap of the window, each
    put down to the innermost program span (``PROGRAM_SPAN``) open across
    the gap's middle (``"no program span"`` where none is), most first."""
    if not trace.ops:
        raise KeyError("no TPU device plane with an 'XLA Ops' line")
    device = device or trace.devices[0]
    lo, hi = trace_lib.window(trace)
    gaps, t = [], lo
    for a, b in trace_lib.busy_intervals(trace.ops[device], lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted((e for e in trace.host if PROGRAM_SPAN.match(e.name)),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    tot: dict = {}
    for a, b in gaps:
        mid = (a + b) // 2
        cover = [e for e in spans[: bisect.bisect_right(starts, mid)] if e.end >= mid]
        name = min(cover, key=lambda e: e.dur).name if cover else "no program span"
        secs, n = tot.get(name, (0.0, 0))
        tot[name] = (secs + (b - a) / 1e9, n + 1)
    return [[k, s, n] for k, (s, n) in sorted(tot.items(), key=lambda kv: -kv[1][0])]


#: the scopes the summary splits each module by, in order of precedence
SUMMARY = {
    "jit_wave_core": ["wave_search", "wave_commit"],
    "jit_search": ["coarse_pass", ("expand", ("coarse_pass",))],
}


def summary(trace: trace_lib.Trace) -> str:
    lines = []
    for module, scopes in SUMMARY.items():
        split = module_scopes(trace, module, scopes)
        if split["runs"]:
            per = {k: round(v / split["runs"] * 1e3, 3) for k, v in split.items() if k != "runs"}
            lines.append(f"{module}: {split['runs']} runs, ms per run {per}")
    try:
        lines.append("idle by program span [span, s, gaps]:")
        lines.extend(f"  {row}" for row in idle_by_span(trace))
    except KeyError as e:
        lines.append(f"no window reading: {e}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(summary(attach(trace_lib.load(sys.argv[1]), sys.argv[1])))
