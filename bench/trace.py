"""Reduce a JAX profiler trace (``.xplane.pb``) of a measured window to
what the per-layer metrics read: device busy time, device time per XLA
program and per op, and the idle gaps labelled by the harness's host
spans.

Two steps, so that the second can be checked on a recorded event list:

* ``extract`` reads a ``ProfileData``: for every device plane
  (``/device:TPU:<n>``) the executions of its XLA programs (line ``XLA
  Modules``, names without the run suffix: ``jit_decode_fn(12)`` ->
  ``jit_decode_fn``) and its ops (line ``XLA Ops``), each op with its short
  name (the HLO instruction's name, without the text after ``=``) and the
  program it runs in; and the harness's own
  ``jax.profiler.TraceAnnotation`` spans on the host plane (names with a
  prefix, ``bench.`` in ``run.py``; ``<prefix>trace`` bounds the window);
* ``reduce_events`` turns those lists into the numbers.  Ops nest (a
  ``while`` op spans the ops of its body), so an op's time in the
  breakdown is its self time, and busy time is the union of intervals.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(path: str) -> str | None:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def short_name(name: str) -> str:
    return name.split(" = ", 1)[0].strip().lstrip("%")


def extract(pd, span_prefix: str = "bench.") -> dict:
    """``{"spans": [[name, start_ns, end_ns]], "devices": [{"modules":
    [[program, start_ns, end_ns]], "ops": [[op, program, start_ns,
    end_ns]]} per device]}``."""
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append([ev.name[len(span_prefix):],
                                      ev.start_ns, ev.end_ns])
        elif DEVICE_PLANE.match(plane.name):
            mods = []
            ops = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = sorted((ev.start_ns, ev.end_ns,
                                   ev.name.split("(")[0])
                                  for ev in line.events)
                elif line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.end_ns, ev.name,
                            dict(ev.stats).get("hlo_module", ""))
                           for ev in line.events]
            starts = [m[0] for m in mods]
            out = []
            for s, e, name, stat_mod in ops:
                i = bisect.bisect_right(starts, s) - 1
                mod = (mods[i][2] if i >= 0 and s < mods[i][1]
                       else str(stat_mod).split("(")[0])
                out.append([short_name(name), mod, s, e])
            devices.append({"modules": [[n, s, e] for s, e, n in mods],
                            "ops": out})
    return {"spans": spans, "devices": devices}


def _union(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _label(spans, s, e) -> str:
    """The harness span (other than the window's) that covers most of the
    gap ``[s, e)``; ``harness`` where none does."""
    best, best_cover = "harness", 0
    for name, a, b in spans:
        if name == "trace":
            continue
        cover = min(b, e) - max(a, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def _self_times(ops, w0, w1):
    """Each op's time in ``[w0, w1)`` less the time of the ops nested in
    it (on one device's line, an op that starts inside another and ends
    by its end is its child)."""
    out = collections.Counter()
    stack = []                                  # [key, start, end, self]

    def close(top):
        out[top[0]] += top[3] / 1e9

    for name, mod, s, e in sorted(
            (op for op in ops if op[3] > w0 and op[2] < w1),
            key=lambda op: (op[2], -op[3])):
        s, e = max(s, w0), min(e, w1)
        while stack and s >= stack[-1][2]:
            close(stack.pop())
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([f"{mod}/{name}", s, e, e - s])
    for top in stack:
        close(top)
    return out


def reduce_events(ev: dict) -> dict | None:
    """The traced window's numbers; None if no device plane was traced."""
    devices = [d for d in ev["devices"] if d["ops"]]
    if not devices:
        return None
    spans = ev["spans"]
    win = [(s, e) for n, s, e in spans if n == "trace"]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(op[2] for d in devices for op in d["ops"])
        w1 = max(op[3] for d in devices for op in d["ops"])
    busy_ns = 0
    merged0 = []
    for i, d in enumerate(devices):
        b, merged = _union((max(op[2], w0), min(op[3], w1))
                           for op in d["ops"] if op[3] > w0 and op[2] < w1)
        busy_ns += b
        if i == 0:
            merged0 = merged
    first = devices[0]
    mod_s = collections.Counter()
    for name, s, e in first["modules"]:
        if e > w0 and s < w1:
            mod_s[name] += (min(e, w1) - max(s, w0)) / 1e9
    op_s = _self_times(first["ops"], w0, w1)
    gaps = []
    prev = w0
    for s, e in merged0 + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    idle = collections.Counter()
    longest = []
    for s, e in gaps:
        label = _label(spans, s, e)
        idle[label] += (e - s) / 1e9
        longest.append([f"{label}@{(s - w0) / 1e9:.3f}s", (e - s) / 1e9])
    longest.sort(key=lambda x: -x[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / len(devices) / 1e9,
        "devices": len(devices),
        "modules": dict(mod_s),
        "ops": dict(op_s),
        "idle_by_span": dict(idle),
        "breakdown": {
            "device_ops": [[n, s] for n, s in op_s.most_common(10)],
            "idle_gaps": longest[:10],
        },
    }


def reduce_dir(path, span_prefix: str = "bench.") -> dict | None:
    from jax.profiler import ProfileData
    f = find_xplane(str(path))
    if f is None:
        return None
    return reduce_events(extract(ProfileData.from_file(f), span_prefix))
