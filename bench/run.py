#!/usr/bin/env python3
"""Benchmark harness: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is data found by name: the configuration file
(``bench/configs/<config>.json``), whose ``arch_family`` names the
package that builds its model, holds its plain reference and counts its
work (``bench/archs/<arch_family>/``), the traffic mix
(``bench/traffic/<traffic>.json``, whose ``loop`` names its generator),
the cell's correctness limit (``bench/limits/<cell>.json``) and one reader
per metric (``bench/metrics/<metric>.py``).

A run builds the model with random weights made on the device from the
seed, warms up the prefill-chunk and decode shapes of the cell's traffic,
drives the paged engine's ``submit``/``step`` with the traffic for
``--seconds``, compares the served tokens with the family's plain
reference, and prints one JSON line last on standard output.
``--trace 0`` reports the cell's end-to-end metrics on the harness's
clock; ``--trace 1`` traces the window with the JAX profiler and reports
the per-layer metrics.  A run on anything but a TPU, or on fewer
chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()           # process start, for setup_s

import argparse                 # noqa: E402
import dataclasses              # noqa: E402
import gc                       # noqa: E402
import importlib                # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import pathlib                  # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax                      # noqa: E402
import numpy as np              # noqa: E402

from bench.archs import family, jax_seed  # noqa: E402

BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
SPAN = "bench."                 # prefix of the harness's host spans
# Traced runs read this many seconds from the window's middle: the
# profiler's buffer holds about 10 s of this program's device ops.
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict                   # configuration file
    mix: dict                    # traffic mix file
    limits: dict                 # correctness limits of this cell
    metrics: dict                # {"end_to_end": [...], "per_layer": [...]}


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_file: pathlib.Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    bench = _load_json(bench_file)
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in {bench_file}") from None
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=w["chips"],
                conf=_load_json(ROOT / conf["file"]),
                mix=_load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_load_json(BENCH / "limits" / f"{name}.json"),
                metrics={k: [m for m in bench[k] if mine(m)]
                         for k in ("end_to_end", "per_layer")})


def read_metric(name: str, rec: dict):
    """The metric's own reader, ``bench/metrics/<name>.py:read(rec)``;
    None where it finds nothing to read."""
    return importlib.import_module(f"bench.metrics.{name}").read(rec)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(chips, len(devs))}


def enable_cache() -> None:
    """Persistent compilation cache at the fixed ``.jax_cache/`` of the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def serve_config(cell: Cell):
    from repro.serving import ServeConfig
    e = cell.mix["engine"]
    return ServeConfig(max_len=e["max_len"], max_slots=e["max_slots"],
                       prefill_chunk=e["prefill_chunk"],
                       page_size=cell.conf["page_size"],
                       pool_blocks=cell.conf["pool_blocks"],
                       cache_dtype=cell.conf["kv_cache_dtype"],
                       temperature=0.0, fused_decode=True)


def warm_up(engine, cell: Cell, vocab: int, seed: int) -> None:
    """One request per prefill-chunk shape the traffic can produce (a
    prompt's last chunk is bucket-padded to 16, 32, ... tokens), each
    decoding one step: every prefill, decode and sampling program the
    window runs is compiled (or loaded from the cache) here."""
    from repro.serving import Request
    bucket = engine.scfg.prefill_bucket
    rng = np.random.default_rng([seed, 99])
    reqs = [Request(prompt=rng.integers(0, vocab, n, dtype=np.int32),
                    max_new_tokens=2)
            for n in range(bucket, engine.scfg.resolved_chunk() + 1, bucket)]
    for r in reqs:
        engine.submit(r)
    while engine.pending():
        engine.step()
    jax.block_until_ready(engine.caches)


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class Sent:
    spec: object                # bench.traffic.Spec
    req: object                 # repro.serving.Request
    due: float                  # seconds after the window opened
    sent: float
    times: list                 # when each output token reached the host
    done: bool = False


def trace_slice(seconds: float) -> tuple[float, float]:
    """Start and length of a window's traced slice: ``TRACE_SECONDS`` (or
    the whole of a shorter window) from the window's middle, where the
    load is steady."""
    length = min(seconds, TRACE_SECONDS)
    return max(0.0, min(seconds / 2, seconds - length)), length


def drive(engine, schedule, seconds: float,
          traced_slice: tuple[float, float] | None = None) -> dict:
    """Drive ``engine`` with ``schedule`` for ``seconds`` after the window
    opens; with ``traced_slice`` (start, length), trace that slice of the
    window with the JAX profiler.  In a closed loop each client's first
    request is sent, and every one the pool admits is prefilled, before
    the window opens: the window starts in steady decode instead of with
    every client's prompt at once.

    Returns the requests sent, the specs that came due in the window but
    were never sent, the window's opening (``time.monotonic`` and the
    engine's counters), the counters where the traced slice began and
    ended, and the wall time of every step in the window."""
    from repro.serving import Request
    Ann = jax.profiler.TraceAnnotation
    sent: list[Sent] = []
    live: list[Sent] = []
    clock = {"t0": time.monotonic()}

    def now():
        return time.monotonic() - clock["t0"]

    def send(spec, due):
        with Ann(SPAN + "submit"):
            req = engine.submit(Request(prompt=spec.prompt,
                                        max_new_tokens=spec.max_new))
        s = Sent(spec=spec, req=req, due=due, sent=now(), times=[])
        sent.append(s)
        live.append(s)

    def step():
        t_step = time.monotonic()
        with Ann(SPAN + "step"):
            engine.step()
        steps.append((t_step - clock["t0"], time.monotonic() - t_step))
        t = now()
        with Ann(SPAN + "record"):
            for s in list(live):
                s.times.extend([t] * (len(s.req.generated) - len(s.times)))
                if s.req.finished_step >= 0:
                    s.done = True
                    live.remove(s)
                    if closed:
                        q = queues[s.spec.client]
                        if q and t < seconds:
                            send(q.pop(), t)

    steps: list[tuple[float, float]] = []
    closed = schedule.loop == "closed"
    if closed:
        queues = [list(reversed(c)) for c in schedule.clients]
        for q in queues:
            send(q.pop(), 0.0)
        step()                          # admits what the pool holds
        while any(st is not None and not st.prefilled()
                  for st in engine.slots):
            step()
        jax.block_until_ready(engine.caches)
    else:
        arrivals = list(reversed(schedule.arrivals))
    traced = {}

    def begin_slice():
        jax.block_until_ready(engine.caches)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        traced["span"] = Ann(SPAN + "trace")
        traced["span"].__enter__()
        traced["before"] = dict(engine.counters)

    def end_slice():
        # The profiler keeps recording until the window has closed: writing
        # a trace out takes seconds, which would stall the window.  Device
        # events past the slice are not read (its buffer keeps the first
        # ones).
        jax.block_until_ready(engine.caches)
        traced.pop("span").__exit__(None, None, None)
        traced["after"] = dict(engine.counters)

    opened = {"counters": dict(engine.counters)}
    engine.pool.peak_live_blocks = engine.pool.live_blocks()
    shift = time.monotonic() - clock["t0"]
    clock["t0"] += shift
    opened["t0"] = clock["t0"]
    for s in sent:                      # warm requests: before the window
        s.due = s.sent = s.sent - shift
        s.times = [t - shift for t in s.times]
    steps.clear()
    while True:
        t = now()
        if traced_slice and "before" not in traced and t >= traced_slice[0]:
            begin_slice()
        if "span" in traced and t >= sum(traced_slice):
            end_slice()
        if t >= seconds:
            break
        if not closed:
            while arrivals and arrivals[-1].due <= t:
                spec = arrivals.pop()
                send(spec, spec.due)
            if not engine.pending():
                if not arrivals:
                    break
                wake = min(seconds, arrivals[-1].due)
                if traced_slice and "before" not in traced:
                    wake = min(wake, traced_slice[0])
                with Ann(SPAN + "wait"):
                    time.sleep(max(0.0, wake - now()))
                continue
        elif not engine.pending() and not any(queues):
            break                       # every client's work is done
        step()
    if "span" in traced:
        end_slice()
    if "before" in traced:
        jax.profiler.stop_trace()
    unsent = [] if closed else [a for a in arrivals if a.due < seconds]
    return {"sent": sent, "unsent": unsent, "traced": traced,
            "opened": opened, "steps": steps}


# ---------------------------------------------------------------------------
# Correctness: served tokens against the plain reference
# ---------------------------------------------------------------------------


def sample_for_check(sent: list[Sent], check: dict, seed: int) -> list[Sent]:
    """Requests whose served tokens are compared: the one with the most
    served tokens, then others drawn from the seed until ``min_tokens``
    served tokens or ``max_requests`` requests."""
    have = [s for s in sent if s.req.generated]
    if not have:
        return []
    have.sort(key=lambda s: (len(s.req.generated), len(s.spec.prompt)),
              reverse=True)
    picked, rest = [have[0]], have[1:]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    for i in order:
        if (len(picked) >= check["max_requests"] or
                sum(len(s.req.generated) for s in picked)
                >= check["min_tokens"]):
            break
        picked.append(rest[i])
    return picked


def reference_shape(cell: Cell) -> tuple[int, int]:
    t_pad = -(-cell.mix["engine"]["max_len"] // 256) * 256
    return t_pad, cell.mix["output_tokens"]["max"]


def served_gaps(cell: Cell, params, picked: list[Sent],
                control: str | None = None) -> dict:
    """The widest gap by which a compared token lies below the float32
    reference's best logit at its position.  The compared tokens are the
    served ones; with ``control`` (a reference mode, ``float8``) they are
    the tokens that the control puts first at the same positions of the
    same prompts and served tokens, in the served tokens' place."""
    logits_at = family(cell.conf).reference.logits_at
    t_pad, n_out = reference_shape(cell)
    widest = 0.0
    tokens = 0
    for s in picked:
        served = np.asarray(s.req.generated, np.int32)
        prompt = np.asarray(s.spec.prompt, np.int32)
        ref = logits_at(cell.conf, params, prompt, served, t_pad, n_out)
        top = served if control is None else logits_at(
            cell.conf, params, prompt, served, t_pad, n_out,
            mode=control).argmax(-1)
        gap = ref.max(axis=-1) - ref[np.arange(len(served)), top]
        widest = max(widest, float(gap.max()))
        tokens += len(served)
    return {"widest": widest, "tokens": tokens, "requests": len(picked)}


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown: dict | None = None
                ) -> dict:
    """The result, printed as the last line of standard output: the five
    keys every run gives, ``breakdown`` in a traced run, and last
    ``checks``, each number compared with its limit."""
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def window_record(sent, unsent, seconds, counters, engine, setup_s) -> dict:
    return {
        "window_s": seconds,
        "setup_s": setup_s,
        "requests": [{"due": s.due, "sent": s.sent, "times": list(s.times)}
                     for s in sent],
        "unsent_due": [a.due for a in unsent],
        "counters": counters,
        "pool": {"peak_live_blocks": engine.pool.peak_live_blocks,
                 "pool_blocks": engine.layout.pool_blocks},
    }


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, control: str | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict.  With
    ``control`` (``calibrate.py --control``) the control's tokens are
    compared in the served tokens' place, and the run has to come out not
    correct."""
    from bench.traffic import make_schedule
    from repro.serving import PagedEngine

    device = device_info(cell.chips, require_tpu)
    enable_cache()
    model = family(cell.conf).model
    cfg = model.program_config(cell.conf)
    params = model.make_params(cell.conf, seed)
    engine = PagedEngine(cfg, params, serve_config(cell))
    engine.begin(jax_seed(seed))
    schedule = make_schedule(cell.mix, seed, cfg.vocab)
    warm_up(engine, cell, cfg.vocab, seed)
    compiles = _count_compiles()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    d = drive(engine, schedule, seconds,
              trace_slice(seconds) if trace else None)
    sent, unsent = d["sent"], d["unsent"]
    setup_s = d["opened"]["t0"] - T0
    jax.block_until_ready(engine.caches)
    in_window = compiles()
    t_window = time.monotonic()
    before = d["opened"]["counters"]
    counters = {k: engine.counters[k] - before.get(k, 0)
                for k in engine.counters}
    rec = window_record(sent, unsent, seconds, counters, engine, setup_s)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    rec["memory_peak_bytes"] = device["memory_peak_bytes"]
    rec["conf"] = cell.conf
    if device["platform"] == "tpu":
        from bench.peaks import peaks_for
        rec["peaks"] = peaks_for(device["kind"])
    if trace:
        from bench.trace import reduce_dir
        rec["trace"] = reduce_dir(TRACE_DIR, SPAN)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if rec["trace"] is not None:
            a, b = d["traced"]["after"], d["traced"]["before"]
            rec["trace"]["counters"] = {k: a[k] - b.get(k, 0) for k in a}
        rec["planes"] = model.plane_stats(engine, seed)
        if rec["trace"] is not None:
            device["busy_s"] = rec["trace"]["busy_s"]
            device["window_s"] = rec["trace"]["window_s"]

    bad = [s for s in sent if len(s.req.generated) > s.spec.max_new
           or any(not 0 <= t < cfg.vocab for t in s.req.generated)]
    del engine
    gc.collect()

    t_ref = time.monotonic()
    picked = sample_for_check(sent, cell.mix["check"], seed)
    check = served_gaps(cell, params, picked, control)
    say(f"times: setup {setup_s:.1f}s, window {seconds}s, after the "
        f"window {t_ref - t_window:.1f}s, reference "
        f"{time.monotonic() - t_ref:.1f}s")
    gap = check["widest"]
    limit = cell.limits["served_logit_gap"]
    correct = bool(picked) and not bad and gap <= limit

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        v = read_metric(m["name"], rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    say(f"window: {len(sent)} sent, {len(unsent)} due but unsent, "
        f"{sum(bool(s.times) for s in sent)} started, "
        f"{sum(s.done for s in sent)} finished, "
        f"{sum(len(s.times) for s in sent)} tokens, counters "
        f"{ {k: v for k, v in counters.items() if v} }, compiles in "
        f"window {in_window}")
    longest = sorted(d["steps"], key=lambda x: -x[1])[:3]
    say(f"steps: {len(d['steps'])} in the window, median "
        f"{np.median([x[1] for x in d['steps']] or [0]):.3f}s, longest "
        + ", ".join(f"{dt:.3f}s at {t:.1f}s" for t, dt in longest))
    say(f"compared {check['tokens']} "
        f"{'served' if control is None else control + ' control'} tokens "
        f"of {check['requests']} requests with the float32 reference; "
        f"{len(bad)} malformed")
    say(f"check served_logit_gap={gap!r} limit={limit!r}")
    return result_line(
        correct, attempted=len(sent) + len(unsent),
        failed=len(bad) + counters.get("requests_shed", 0),
        metrics=metrics, device=device,
        checks={"served_logit_gap": {"value": gap, "limit": limit}},
        breakdown=rec["trace"]["breakdown"]
        if trace and rec.get("trace") else None)


def _count_compiles():
    """Counts XLA compilations from now on; the returned function reads
    the count."""
    n = [0]

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return lambda: n[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        say(f"FAIL: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
