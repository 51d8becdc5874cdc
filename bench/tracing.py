#!/usr/bin/env python3
"""The serving engine's own tracing, read beside the device trace.

    python3 bench/tracing.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--tracer clock|default]

The engine marks its phases with ``engine.*`` profiler spans and stamps
requests through a ``repro.runtime.trace.Tracer``, and its forwards carry
``jax.named_scope``s (``SCOPES``).  This module reads them:

* ``scope_maps(engine)``: every op of every program the engine serves with
  (``PagedEngine.programs``), found in the compiled program's text, and
  the scope it runs under (``scope_label``);
* ``reduce_profile``: one pass over a profile, giving what
  ``bench.trace.reduce_events`` gives plus device self time by program and
  scope (``ops_by_scope``), idle time by the innermost host span that
  covers it (``idle_by_innermost``), and the host/device clock offset;
* ``READERS``: per-layer metrics that read these, each ``read(rec)`` on a
  run record as ``bench/metrics`` readers do, where ``rec`` also holds
  ``tracer`` (the tracer's records on the window's clock) and each request
  its ``rid``;
* ``main``: one run of a cell with the engine's clock tracer (or the
  default, ``--tracer default``, for the tracer's cost), printing the
  end-to-end metrics, these metrics and the slowest steps' phase splits.
  It skips the correctness comparison of ``bench/run.py``.

``bench/run.py`` does not call this module.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import pathlib
import re
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np              # noqa: E402

from bench.trace import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,  # noqa: E402
                         find_xplane, reduce_events, short_name)

SCOPES = ("layers", "kv_write", "kv_gather", "attn", "mlp", "lm_head")
# The scan's own ops: ``layers/while/body/dynamic_update_slice`` and the
# like, emitted by the loop itself and not by the layer's body (whose ops
# carry a call, ``.../while/body/closed_call/...``).
SCAN_PARTS = {"while", "body", "cond"}
# A compiled module's instruction, or a profile's op event name:
# ``%fusion.3 = bf16[1,64]{1,0} fusion(...), ...``.
INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


# ---------------------------------------------------------------------------
# Ops and their scopes
# ---------------------------------------------------------------------------


def scope_label(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an op's ``op_name`` path; ops of
    ``layers`` outside every inner scope read ``layers`` when the scan
    itself emits them (slicing each layer's inputs, stacking its outputs)
    and ``block`` when a layer's body does (norms, projections); ``""``
    outside every scope."""
    parts = op_name.split("/")
    at = [i for i, p in enumerate(parts) if p in SCOPES]
    if not at:
        return ""
    i = at[-1]
    if parts[i] != "layers":
        return parts[i]
    return "layers" if set(parts[i + 1:-1]) <= SCAN_PARTS else "block"


def op_key(text: str) -> tuple[str, str | None]:
    """(name, result type) of an instruction as compiled text or a
    profile's op event writes it; the type is None for a bare name."""
    m = INSTR.match(text)
    return (m.group(1), m.group(2)) if m else (short_name(text), None)


def scope_map(texts) -> dict:
    """Labels of the ops of one program from the text of each of its
    compiled variants: ``{"key": {(name, type): label}, "name": {name:
    label, or None where variants disagree}}``."""
    by_key, by_name = {}, {}
    for text in texts:
        for line in text.splitlines():
            m = INSTR.match(line)
            if not m:
                continue
            on = OP_NAME.search(line)
            label = scope_label(on.group(1)) if on else ""
            by_key[(m.group(1), m.group(2))] = label
            prev = by_name.get(m.group(1), label)
            by_name[m.group(1)] = label if prev == label else None
    return {"key": by_key, "name": by_name}


def label_of(smap: dict | None, text: str) -> str | None:
    """An op's label from its program's map; None if unknown."""
    if smap is None:
        return None
    name, typ = op_key(text)
    if typ is not None and (name, typ) in smap["key"]:
        return smap["key"][(name, typ)]
    return smap["name"].get(name)


def scope_maps(engine) -> dict:
    """``{program: scope_map}`` for every program the engine serves with,
    under the name a profile gives it (``jit_decode_fn``,
    ``jit_prefill_fn`` for every chunk width)."""
    texts = collections.defaultdict(list)
    for name, (fn, args) in engine.programs().items():
        texts["jit_" + name.split("@")[0]].append(
            fn.lower(*args).compile().as_text())
    return {prog: scope_map(t) for prog, t in texts.items()}


# ---------------------------------------------------------------------------
# One pass over a profile
# ---------------------------------------------------------------------------


def profile_events(pd, prefixes=("bench.", "engine.")) -> dict:
    """Host spans ``[[prefixed name, start_ns, end_ns]]`` of the given
    prefixes, and the first device plane's ``modules`` and ``ops``
    (``[[op event name, program, start_ns, end_ns]]``, the name in full)."""
    spans, mods, ops = [], [], []
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefixes):
                        spans.append([ev.name, ev.start_ns, ev.end_ns])
        elif DEVICE_PLANE.match(plane.name) and device is None:
            device = plane
    if device is not None:
        raw = []
        for line in device.lines:
            if line.name == MODULES_LINE:
                mods = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                              for ev in line.events)
            elif line.name == OPS_LINE:
                raw = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
        starts = [m[0] for m in mods]
        for name, s, e in raw:
            i = bisect.bisect_right(starts, s) - 1
            ops.append([name, mods[i][2] if i >= 0 and s < mods[i][1] else "",
                        s, e])
    return {"spans": spans, "modules": [[n, s, e] for s, e, n in mods],
            "ops": ops}


def busy_intervals(ops, w0, w1) -> list[list[int]]:
    merged = []
    for s, e in sorted((max(o[2], w0), min(o[3], w1)) for o in ops
                       if o[3] > w0 and o[2] < w1):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def innermost_timeline(spans) -> list[tuple[int, int, str]]:
    """``(start, end, name)`` pieces of the host's timeline, each with the
    innermost span open over it (the open span that opened last)."""
    bounds = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)] +
                    [(e, 0, i) for i, (_, _, e) in enumerate(spans)])
    out, open_, prev = [], [], None
    for t, is_start, i in bounds:
        if open_ and prev is not None and t > prev:
            out.append((prev, t, spans[open_[-1]][0]))
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
        prev = t
    return out


def gap_parts(gaps, spans) -> list[collections.Counter]:
    """For each of the ``gaps`` (sorted host-clock ``[s, e)``), its
    seconds by the innermost span that covers each part of it; ``none``
    where no span does.  A gap can be split, e.g. ``engine.emit``,
    ``bench.record``, ``engine.admit``."""
    pieces = innermost_timeline(spans)
    out = []
    j = 0
    for s, e in gaps:
        parts = collections.Counter()
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                parts[name] += d / 1e9
                covered += d
            k += 1
        if e - s > covered:
            parts["none"] += (e - s - covered) / 1e9
        out.append(parts)
    return out


def host_device_offset(modules, spans, program="jit_decode_fn",
                       span="engine.decode") -> float | None:
    """Least (start of a ``program`` execution - start of the nearest
    ``span``) in seconds: how far the device's clock reads ahead of the
    host's (negative) or the least dispatch latency (positive)."""
    starts = sorted(sp[1] for sp in spans if sp[0] == span)
    best = None
    for name, m, _ in modules:
        if name != program or not starts:
            continue
        i = bisect.bisect_left(starts, m)
        near = min((starts[j] for j in (i - 1, i) if 0 <= j < len(starts)),
                   key=lambda t: abs(m - t))
        d = (m - near) / 1e9
        best = d if best is None else min(best, d)
    return best


def reduce_profile(pd, maps: dict) -> dict | None:
    """``bench.trace.reduce_events`` of the profile's traced slice, plus
    ``ops_by_scope`` (``{program: {label: self seconds}}``, ``?`` where
    the maps do not know an op), ``offset_s`` (``host_device_offset``),
    ``idle_by_innermost`` and the longest ``idle_gaps``, each labelled by
    its largest part (device times shifted by the offset when it is
    negative)."""
    ev = profile_events(pd)
    if not ev["ops"]:
        return None
    bench = [[n[len("bench."):], s, e] for n, s, e in ev["spans"]
             if n.startswith("bench.")]
    plain = {"spans": bench, "devices": [{
        "modules": ev["modules"],
        "ops": [[short_name(n), m, s, e] for n, m, s, e in ev["ops"]]}]}
    out = reduce_events(plain)
    labels = {}
    for n, m, _, _ in ev["ops"]:
        if (m, n) not in labels:
            labels[(m, n)] = label_of(maps.get(m), n) or "?"
    labelled = dict(plain, devices=[{
        "modules": ev["modules"],
        "ops": [[labels[(m, n)], m, s, e] for n, m, s, e in ev["ops"]]}])
    by = collections.defaultdict(dict)
    for key, sec in reduce_events(labelled)["ops"].items():
        prog, label = key.rsplit("/", 1)
        by[prog][label] = sec
    out["ops_by_scope"] = dict(by)
    win = [(s, e) for n, s, e in bench if n == "trace"]
    w0, w1 = win[0] if win else (min(o[2] for o in ev["ops"]),
                                 max(o[3] for o in ev["ops"]))
    host = [sp for sp in ev["spans"] if sp[0] != "bench.trace"]
    off = host_device_offset(ev["modules"], host)
    out["offset_s"] = off
    shift = int(-off * 1e9) if off is not None and off < 0 else 0
    gaps, prev = [], w0
    for s, e in busy_intervals(ev["ops"], w0, w1) + [[w1, w1]]:
        if s > prev:
            gaps.append((prev + shift, s + shift))
        prev = max(prev, e)
    parts = gap_parts(gaps, host)
    out["idle_by_innermost"] = dict(sum(parts, collections.Counter()))
    # The longest gaps, each labelled by its largest part.
    out["idle_gaps"] = sorted(
        ([f"{p.most_common(1)[0][0]}@{(s - shift - w0) / 1e9:.3f}s",
          (e - s) / 1e9] for (s, e), p in zip(gaps, parts) if p),
        key=lambda g: -g[1])[:10]
    return out


# ---------------------------------------------------------------------------
# Per-layer metrics that read the tracer and the scopes
# ---------------------------------------------------------------------------


def tracer_record(tracer, t0: float) -> dict:
    """The tracer's records with times on the window's clock (seconds
    after ``t0``, the window's opening on the tracer's clock)."""
    r = tracer.records()
    r["steps"] = [dict(st, t=st["t"] - t0) for st in r["steps"]]
    r["events"] = [[rid, ev, t - t0] for rid, ev, t in r["events"]]
    return r


def request_phases(rec: dict) -> list[dict]:
    """For each request whose first token reached the harness in the
    window: ``ttft`` (first token time - due), ``lag`` (sent - due),
    ``submit`` (submit - sent), ``queue`` (admit - submit), ``prefill``
    (first_token - admit) and ``hold`` (first token time - first_token),
    in seconds: they sum to ``ttft``."""
    tr = rec.get("tracer")
    if not tr:
        return []
    ev = collections.defaultdict(dict)
    for rid, name, t in tr["events"]:
        ev[rid].setdefault(name, t)      # a resumed request's first admit
    out = []
    for r in rec["requests"]:
        e = ev.get(r.get("rid"), {})
        if (not r["times"] or not 0 <= r["times"][0] <= rec["window_s"]
                or not {"submit", "admit", "first_token"} <= set(e)):
            continue
        t = r["times"][0]
        out.append({"ttft": t - r["due"], "lag": r["sent"] - r["due"],
                    "submit": e["submit"] - r["sent"],
                    "queue": e["admit"] - e["submit"],
                    "prefill": e["first_token"] - e["admit"],
                    "hold": t - e["first_token"]})
    return out


def _median_ms(rec, key):
    v = [p[key] for p in request_phases(rec)]
    return 1e3 * float(np.median(v)) if v else None


def first_token_hold_p50_ms(rec):
    """Scheduler: median time a sampled first token waits for the rest of
    its tick (the decode step) before the caller sees it (ms)."""
    return _median_ms(rec, "hold")


def prefill_wait_p50_ms(rec):
    """Scheduler: median time from admission to the first token (ms)."""
    return _median_ms(rec, "prefill")


def step_host_ms(rec):
    """Scheduler: mean over the window's steps of the step less its
    ``prefill_wait`` and ``readback``: host time the chip may wait on
    (ms)."""
    tr = rec.get("tracer")
    steps = [st for st in (tr or {}).get("steps", [])
             if 0 <= st["t"] <= rec["window_s"]]
    if not steps:
        return None
    return 1e3 * float(np.mean([
        st["s"] - st["phases"].get("prefill_wait", 0.0)
        - st["phases"].get("readback", 0.0) for st in steps]))


def _scoped_ms(rec, program, labels, counter):
    tr = rec.get("trace")
    by = (tr or {}).get("ops_by_scope", {}).get(program)
    n = by and tr["counters"].get(counter)
    if not n:
        return None
    return 1e3 * sum(by.get(lab, 0.0) for lab in labels) / n


def decode_cache_ms(rec):
    """Model step: device time per decode step (traced slice) of the ops
    under ``kv_write`` and the layer scan's own slicing and stacking of
    the cache (ms)."""
    return _scoped_ms(rec, "jit_decode_fn", ("kv_write", "layers"),
                      "decode_steps")


def prefill_attn_ms(rec):
    """Kernels: device time per prefill chunk (traced slice) of the ops
    under ``kv_gather`` and ``attn`` (ms)."""
    return _scoped_ms(rec, "jit_prefill_fn", ("kv_gather", "attn"),
                      "prefill_chunks")


READERS = {f.__name__: f for f in (
    first_token_hold_p50_ms, prefill_wait_p50_ms, step_host_ms,
    decode_cache_ms, prefill_attn_ms)}


# ---------------------------------------------------------------------------
# One run with the engine's tracer
# ---------------------------------------------------------------------------


def run(cell, seed: int, seconds: float, trace: bool, clock: bool = True,
        require_tpu: bool = True) -> dict:
    """One run of ``cell`` as ``bench/run.py`` makes it, with the engine
    given ``Tracer(SystemClock())`` (``clock``) or its default tracer, and
    no correctness comparison.  Returns the end-to-end metrics, the
    ``READERS``' values, the traced slice's reduction and the tracer's
    slowest steps."""
    import jax
    from bench import run as R
    from bench.archs import family, jax_seed
    from bench.traffic import make_schedule
    from repro.runtime import SystemClock, Tracer
    from repro.serving import PagedEngine

    device = R.device_info(cell.chips, require_tpu)
    R.enable_cache()
    model = family(cell.conf).model
    cfg = model.program_config(cell.conf)
    params = model.make_params(cell.conf, seed)
    tracer = Tracer(SystemClock()) if clock else None
    engine = PagedEngine(cfg, params, R.serve_config(cell), tracer=tracer)
    engine.begin(jax_seed(seed))
    schedule = make_schedule(cell.mix, seed, cfg.vocab)
    R.warm_up(engine, cell, cfg.vocab, seed)
    if trace:
        shutil.rmtree(R.TRACE_DIR, ignore_errors=True)
    d = R.drive(engine, schedule, seconds,
                R.trace_slice(seconds) if trace else None)
    t0 = d["opened"]["t0"]
    jax.block_until_ready(engine.caches)
    before = d["opened"]["counters"]
    counters = {k: engine.counters[k] - before.get(k, 0)
                for k in engine.counters}
    rec = R.window_record(d["sent"], d["unsent"], seconds, counters, engine,
                          t0 - R.T0)
    for r, s in zip(rec["requests"], d["sent"]):
        r["rid"] = s.req.rid
    if clock:
        rec["tracer"] = tracer_record(tracer, t0)
    if trace:
        from jax.profiler import ProfileData
        f = find_xplane(str(R.TRACE_DIR))
        maps = scope_maps(engine)
        rec["trace"] = (reduce_profile(ProfileData.from_file(f), maps)
                        if f else None)
        shutil.rmtree(R.TRACE_DIR, ignore_errors=True)
        if rec["trace"] is not None:
            a, b = d["traced"]["after"], d["traced"]["before"]
            rec["trace"]["counters"] = {k: a[k] - b.get(k, 0) for k in a}
    out = {"device": device, "metrics": {}}
    for m in cell.metrics["end_to_end"]:
        v = R.read_metric(m["name"], rec)
        if v is not None:
            out["metrics"][m["name"]] = v
    for name, read in READERS.items():
        v = read(rec)
        if v is not None:
            out["metrics"][name] = v
    out["requests"] = request_phases(rec)
    if out["requests"]:
        out["queue_wait_p50_ms"] = 1e3 * float(np.median(
            [p["queue"] for p in out["requests"]]))
    if clock:
        # the window's own: the warm-up's compiling steps would lead
        out["slow_steps"] = sorted(
            (st for st in rec["tracer"]["steps"] if 0 <= st["t"] <= seconds),
            key=lambda st: -st["s"])[:8]
    if rec.get("trace"):
        tr = rec["trace"]
        out["trace"] = {k: tr[k] for k in (
            "window_s", "busy_s", "modules", "idle_by_span",
            "idle_by_innermost", "idle_gaps", "offset_s", "ops_by_scope",
            "breakdown", "counters")}
    return out


def main(argv=None) -> int:
    from bench import run as R
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--tracer", choices=["clock", "default"],
                    default="clock")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  clock=args.tracer == "clock")
    except R.NoChip as e:
        R.say(f"FAIL: {e}")
        return 2
    for st in out.get("slow_steps", []):
        R.say(f"slow step at {st['t']:.3f}s: {st['s']:.3f}s = " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(st["phases"].items(),
                                              key=lambda kv: -kv[1])))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
