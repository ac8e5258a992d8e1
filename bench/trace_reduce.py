"""From a profiler trace to numbers: the one reduction every PR's trace goes through.

``events_of`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
nothing but JAX) into plain events: plane, line, name, start and duration in
nanoseconds, and for the program's own ``kvmini.*`` host spans the arguments
they were opened with (``args``; no other event keeps any: the device's
operations are tens of thousands and need none). ``reduce`` works on those
events alone, so that it can be checked on the small recorded traces under
``bench/testdata/``.

What a TPU trace holds (read by hand first, PR 25): one plane a device,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per executed
program: ``jit_decode(...)``, ``jit_prefill(...)``, ``jit_first(...)``) and
``XLA Ops`` (every operation inside them, nested: a ``%while`` spans its
body); one plane ``/host:CPU`` whose thread lines carry the program's
``jax.profiler.TraceAnnotation`` spans (``kvmini.decode_dispatch``,
``kvmini.decode_retire``, ``kvmini.emit`` with the experts its sweep touched,
``kvmini.prefill`` with its piece's real tokens; docs/TRACING.md). All planes
share one clock.

- busy: the union of the intervals in which an operation ran on the device
  (``XLA Ops``), averaged over the device planes; the window is the span from
  the first to the last event of the trace.
- a program's step time: the median duration of its ``XLA Modules`` events.
- ``breakdown.device_ops``: the ten operations with most *self* time (their
  own duration less that of the operations nested in them), named
  ``<program>/<operation>``.
- ``breakdown.idle_gaps``: the longest stretches with no operation on the
  device, each named by the host span that covers most of it, or
  ``other-host``.
- ``spans``: what the program's ``kvmini.*`` spans counted in the SAME seconds
  the device times come from: for each span name, how many began inside the
  device's window (its first to its last event) and the sum of each numeric
  argument over them. A reader that divides a count by a device time takes
  both from here (``bench/metrics.span_sums``), never the count from the 45 s
  window's counters: the slice holds more or fewer live rows than the window's
  mean, and a time that follows the count then reads a share that is off by
  that ratio, over 100% on a quiet slice (PR 48). This is the one place that
  says what "inside the slice" means.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
import shutil
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPANS = ("kvmini.decode_dispatch", "kvmini.decode_retire")
SPAN_PREFIX = "kvmini."
_LAYOUT = re.compile(r"\{[^{}]*\}")


def events_of(trace_dir) -> list[dict]:
    import jax

    paths = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb")))
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        own = plane.name.startswith("/host:")
        for line in plane.lines:
            for ev in line.events:
                event = {"plane": plane.name, "line": line.name, "name": ev.name,
                         "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns)}
                if own and ev.name.startswith(SPAN_PREFIX) and (args := dict(ev.stats)):
                    event["args"] = args
                events.append(event)
    return events


def load_events(path) -> list[dict]:
    """A recorded head (``bench/testdata/``): the events as ``events_of`` gave
    them, with ``args`` on the spans that had any, or on none (the older heads)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def short_name(name: str) -> str:
    """An operation's name without layouts, cut to what says what it is."""
    return _LAYOUT.sub("", name)[:96]


def program_of(name: str) -> str:
    """``jit_decode(7988...)`` -> ``jit_decode``."""
    return name.split("(", 1)[0]


def self_times(ops: list[dict]) -> list[float]:
    """Each event's duration less the time of the events nested directly in
    it (events of one line nest properly)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start_ns"], -ops[i]["dur_ns"]))
    own = [e["dur_ns"] for e in ops]
    stack: list[int] = []
    for i in order:
        start, end = ops[i]["start_ns"], ops[i]["start_ns"] + ops[i]["dur_ns"]
        while stack and ops[stack[-1]]["start_ns"] + ops[stack[-1]]["dur_ns"] <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]]["start_ns"] + ops[stack[-1]]["dur_ns"]:
            own[stack[-1]] -= ops[i]["dur_ns"]
        stack.append(i)
    return own


def span_counts(events: list[dict]) -> dict:
    """``{name: {"count": n, "sums": {argument: sum}}}`` over the program's
    ``kvmini.*`` host spans that BEGIN between the device's first event and its
    last: the seconds every device time of the trace comes from. Sums and not
    the spans, so that what is kept of a trace stays small; an argument that is
    no number (a request's id) is not summed."""
    device = [e for e in events if e["plane"].startswith("/device:")]
    if not device:
        return {}
    lo = min(e["start_ns"] for e in device)
    hi = max(e["start_ns"] + e["dur_ns"] for e in device)
    out: dict[str, dict] = {}
    for e in events:
        if (e["plane"].startswith("/host:") and e["name"].startswith(SPAN_PREFIX)
                and lo <= e["start_ns"] <= hi):
            span = out.setdefault(e["name"], {"count": 0, "sums": {}})
            span["count"] += 1
            for key, value in (e.get("args") or {}).items():
                if isinstance(value, (int, float)) and not isinstance(value, bool):
                    span["sums"][key] = span["sums"].get(key, 0) + value
    return out


def reduce(events: list[dict]) -> dict:
    """``busy_s``, ``window_s``, each program's step times (``programs``),
    seconds and calls by operation (``ops``), what the program's spans counted
    in those seconds (``spans``) and the ``breakdown`` of a traced window.
    Empty (``busy_s`` 0) when the trace holds no device plane."""
    device_planes = sorted({e["plane"] for e in events if e["plane"].startswith("/device:")})
    if not events or not device_planes:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {}, "ops": {}, "spans": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    t_lo = min(e["start_ns"] for e in events)
    t_hi = max(e["start_ns"] + e["dur_ns"] for e in events)
    window_ns = t_hi - t_lo
    host = [e for e in events if e["plane"].startswith("/host:") and e["name"] in HOST_SPANS]

    busy_ns, programs, op_total, op_self, op_count, gaps = 0.0, {}, {}, {}, {}, []
    for plane in device_planes:
        ops = [e for e in events if e["plane"] == plane and e["line"] == OPS_LINE]
        mods = sorted((e for e in events if e["plane"] == plane and e["line"] == MODULES_LINE),
                      key=lambda e: e["start_ns"])
        busy = union([(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops])
        busy_ns += sum(b - a for a, b in busy)
        for m in mods:
            programs.setdefault(program_of(m["name"]), []).append(m["dur_ns"])
        # which program an operation belongs to: the module running at its start
        starts = [m["start_ns"] for m in mods]
        for e, own in zip(ops, self_times(ops)):
            k = bisect.bisect_right(starts, e["start_ns"]) - 1
            prog = program_of(mods[k]["name"]) if k >= 0 and e["start_ns"] < (
                mods[k]["start_ns"] + mods[k]["dur_ns"]) else "?"
            key = f"{prog}/{short_name(e['name'])}"
            op_total[key] = op_total.get(key, 0.0) + e["dur_ns"]
            op_count[key] = op_count.get(key, 0) + 1
            op_self[key] = op_self.get(key, 0.0) + own
        edges = [(t_lo, t_lo)] + busy + [(t_hi, t_hi)]
        gaps += [(b0, a1) for (_, b0), (a1, _) in zip(edges, edges[1:]) if a1 > b0]

    def covering(a: float, b: float) -> str:
        cover: dict[str, float] = {}
        for h in host:
            lo, hi = max(a, h["start_ns"]), min(b, h["start_ns"] + h["dur_ns"])
            if hi > lo:
                cover[h["name"]] = cover.get(h["name"], 0.0) + hi - lo
        best = max(cover, key=cover.get) if cover else None
        return best if best and cover[best] >= 0.5 * (b - a) else "other-host"

    gaps.sort(key=lambda g: g[0] - g[1])
    n_dev = len(device_planes)
    return {
        "busy_s": busy_ns / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "programs": {p: sorted(d / 1e9 for d in durs) for p, durs in programs.items()},
        "ops": {k: {"seconds": v / 1e9, "calls": op_count[k]} for k, v in op_total.items()},
        "spans": span_counts(events),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in
                           sorted(op_self.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[covering(a, b), (b - a) / 1e9] for a, b in gaps[:5]],
        },
    }


def reduce_dir(trace_dir) -> dict:
    """Reduce the trace under ``trace_dir`` and drop the raw files (tens of MB)."""
    events = events_of(trace_dir)
    shutil.rmtree(Path(trace_dir) / "plugins", ignore_errors=True)
    return reduce(events)
