#!/usr/bin/env python3
"""The program's own host spans (``repro.*``, from ``repro.obs.span``)
in a profiler trace, and the splits of the assessment tick and of the
training step that they give.

- per span name: the count, the total seconds and the self seconds (the
  total minus the part its child program spans cover on the same host
  line), each clipped to the window;
- the device's idle gaps, labelled by the innermost host span of either
  family that covers the gap's middle: a benchmark span as
  ``chipbench/trace.py`` labels it (``host:engine.chunk``), a program
  span by its full name (``host:repro.accel.wait``);
- per assessment tick: ``policy`` (self time of ``repro.sim.*`` and
  ``repro.core.*``), ``prep`` (self time of the ``repro.accel`` methods,
  ``refresh``, ``upload`` and ``launch``), ``wait`` and ``fetch``; the
  four add up to the ``repro.sim.tick`` total;
- per committed training step: the self time of ``repro.runtime.gather``,
  ``repro.runtime.reduce`` and ``repro.runtime.apply``, and the mean
  ``repro.runtime.bino_tick``.

Events are the tuples of ``chipbench/trace.py``; :func:`load_events`
keeps the host threads apart (the profiler names every Python thread's
line ``python``) and the device's lines as they are.

As a command, from the root of a checkout, it runs one cell's set-up and
one traced window (no check) and prints these numbers as one JSON line,
also written to ``chipbench_out/spans/<workload>-<seed>.json``:

    python3 chipbench/spans.py --workload <cell> --seed <n> --seconds <s>

On a tree whose program has no spans, the spans and splits come out
empty and the counters ``None``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import trace as tr  # noqa: E402

PREFIX = "repro."
BENCH_PREFIX = tr.SPAN_PREFIX
OUT_DIR = ROOT / "chipbench_out" / "spans"

# Span names as the program writes them (without the prefix).
TICK = "sim.tick"
POLICY = ("sim.", "core.")
WAIT = "accel.wait"
FETCH = "accel.fetch"


def line_key(plane: str, line: str, k: int) -> str:
    """A host line is named ``<name>#<k>``, ``k`` its place in its plane,
    so that two threads stay apart; a device's lines keep their names
    (``XLA Ops``), which ``trace.reduce_events`` reads."""
    return line if plane.startswith("/device:") else f"{line}#{k}"


def load_events(path: str) -> List[tr.Event]:
    """As ``trace.load_events``, with the lines named by
    :func:`line_key`."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: List[tr.Event] = []
    for plane in data.planes:
        for k, line in enumerate(plane.lines):
            key = line_key(plane.name, line.name, k)
            for ev in line.events:
                out.append((plane.name, key, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def window_of(events: Sequence[tr.Event]) -> Optional[Tuple[float, float]]:
    """The benchmark's ``chipbench.window`` span, if the trace has one."""
    for _plane, _line, name, start, dur in events:
        if name == BENCH_PREFIX + "window":
            return start, start + dur
    return None


def _clipped(s: float, e: float, lo: float, hi: float) -> float:
    c = tr._clip(s, e, lo, hi)
    return (c[1] - c[0]) if c is not None else 0.0


def program_spans(events: Sequence[tr.Event],
                  window: Tuple[float, float]) -> Dict[str, dict]:
    """``{name: {"count", "total_s", "self_s", "parents"}}`` of the
    program's spans (names without ``repro.``) that overlap the window.
    Spans of one host line nest by call, so a span's children are the
    program spans it holds on its line; ``parents`` names the spans that
    held it (``""`` for none)."""
    lo, hi = window
    by_line: Dict[tuple, list] = defaultdict(list)
    for plane, line, name, start, dur in events:
        if name.startswith(PREFIX):
            by_line[(plane, line)].append(
                (start, start + dur, name[len(PREFIX):]))
    out: Dict[str, dict] = {}
    for spans in by_line.values():
        # Parents before their children: by start, the longer first.
        spans.sort(key=lambda sp: (sp[0], -sp[1]))
        child_s = [0.0] * len(spans)
        parent = [""] * len(spans)
        stack: List[int] = []
        for i, (s, e, _name) in enumerate(spans):
            while stack and spans[stack[-1]][1] <= s:
                stack.pop()
            if stack and e <= spans[stack[-1]][1]:
                child_s[stack[-1]] += _clipped(s, e, lo, hi)
                parent[i] = spans[stack[-1]][2]
            stack.append(i)
        for (s, e, name), inner, up in zip(spans, child_s, parent):
            total = _clipped(s, e, lo, hi)
            if total <= 0.0:
                continue
            rec = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0, "parents": set()})
            rec["count"] += 1
            rec["total_s"] += total / 1e9
            rec["self_s"] += (total - inner) / 1e9
            rec["parents"].add(up)
    for rec in out.values():
        rec["parents"] = sorted(rec["parents"])
    return out


def idle_gaps(events: Sequence[tr.Event], window: Tuple[float, float],
              top: int = 10) -> List[list]:
    """The device's longest idle gaps in the window (as
    ``trace.reduce_events`` finds them), each labelled by the innermost
    host span of either family that covers its middle."""
    lo, hi = window
    ops: Dict[str, list] = defaultdict(list)
    mods: Dict[str, list] = defaultdict(list)
    host: List[Tuple[float, float, str]] = []
    for plane, line, name, start, dur in events:
        if plane.startswith("/device:"):
            if line == tr.OPS_LINE:
                ops[plane].append((start, start + dur))
            elif line == tr.MODULES_LINE:
                mods[plane].append((start, start + dur))
        elif name.startswith(PREFIX):
            host.append((start, start + dur, "host:" + name))
        elif name.startswith(BENCH_PREFIX) \
                and name != BENCH_PREFIX + "window":
            host.append((start, start + dur,
                         "host:" + name[len(BENCH_PREFIX):]))
    for plane, ivs in mods.items():
        ops.setdefault(plane, list(ivs))
    gaps: List[Tuple[float, float]] = []
    for ivs in ops.values():
        m = tr.merged(c for c in (tr._clip(s, e, lo, hi) for s, e in ivs)
                      if c is not None)
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    def label(s: float, e: float) -> str:
        mid = (s + e) / 2
        cover = [sp for sp in host if sp[0] <= mid <= sp[1]]
        if not cover:
            return "host:outside-spans"
        return min(cover, key=lambda sp: sp[1] - sp[0])[2]

    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(s, e), (e - s) / 1e9] for s, e in gaps[:top]]


def _self(spans: Dict[str, dict], pick) -> float:
    return sum(v["self_s"] for k, v in spans.items() if pick(k))


def tick_split(spans: Dict[str, dict], ticks: int
               ) -> Optional[Dict[str, float]]:
    """Milliseconds per assessment tick in the policy's host logic, the
    device path's preparation (mirror, upload, dispatch), the wait for
    the device and the copies back; they add up to ``tick_ms``."""
    if TICK not in spans or not ticks:
        return None
    out = {
        "policy_ms": _self(spans, lambda k: k.startswith(POLICY)),
        "prep_ms": _self(spans, lambda k: k.startswith("accel.")
                         and k not in (WAIT, FETCH)),
        "wait_ms": _self(spans, lambda k: k == WAIT),
        "fetch_ms": _self(spans, lambda k: k == FETCH),
        "tick_ms": spans[TICK]["total_s"],
    }
    return {k: v / ticks * 1e3 for k, v in out.items()}


def step_split(spans: Dict[str, dict], steps: int
               ) -> Optional[Dict[str, float]]:
    """Milliseconds per committed step the coordinator gathers
    gradients (its bino ticks apart), reduces them and applies the
    update, and the mean bino tick."""
    if "runtime.gather" not in spans or not steps:
        return None
    tick = spans.get("runtime.bino_tick")

    def per_step(name):
        return spans.get(name, {}).get("self_s", 0.0) / steps * 1e3

    return {"gather_ms": per_step("runtime.gather"),
            "reduce_ms": per_step("runtime.reduce"),
            "apply_ms": per_step("runtime.apply"),
            "bino_tick_ms": (tick["total_s"] / tick["count"] * 1e3
                             if tick else None)}


# ---------------------------------------------------------------------------
# The program's counters, read around the window
# ---------------------------------------------------------------------------
def counters(driver: str, state) -> Dict[str, Optional[float]]:
    """``fetch_bytes`` of the fleet's device backend; the training
    coordinator's ``detect_silence_s`` observations (count, sum)."""
    if driver == "fleet":
        return {"fetch_bytes": getattr(state.backend.inner, "fetch_bytes",
                                       None)}
    snap = state.trainer.coord.metrics.snapshot()
    return {"detect_n": snap.get("detect_silence_s_n", 0),
            "detect_sum": snap.get("detect_silence_s_sum", 0.0)}


def counter_metrics(driver: str, c0: dict, c1: dict, run_counters: dict
                    ) -> Dict[str, Optional[float]]:
    """``download_mb`` per tick of the window; the window's mean
    ``detect_s``."""
    if driver == "fleet":
        ticks = run_counters.get("ticks")
        if c1["fetch_bytes"] is None or not ticks:
            return {"download_mb": None}
        return {"download_mb": (c1["fetch_bytes"] - c0["fetch_bytes"])
                / ticks / 1e6}
    n = c1["detect_n"] - c0["detect_n"]
    return {"detect_s": (c1["detect_sum"] - c0["detect_sum"]) / n
            if n else None}


# ---------------------------------------------------------------------------
def traced_window(driver_name: str, driver, run, state, seconds: float,
                  traffic: dict, log_dir: Path) -> dict:
    """The driver's window under the profiler (as ``run.py`` traces it),
    and everything above, read from its trace and the counters."""
    import jax
    from chipbench import harness
    c0 = counters(driver_name, state)
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    with harness.span("window"):
        driver.window(run, state, seconds, traffic)
    jax.profiler.stop_trace()
    c1 = counters(driver_name, state)
    events = load_events(tr.find_xplane(str(log_dir)))
    shutil.rmtree(log_dir, ignore_errors=True)
    window = window_of(events)
    summary = tr.reduce_events(events, window=window)
    spans = program_spans(events, window)
    rc = run.counters
    out = {"window_s": summary["window_s"],
           "device_idle_share": summary["idle_share"],
           "spans": spans,
           "rate_per_s": {k: v["count"] / summary["window_s"]
                          for k, v in spans.items()},
           "idle_gaps": idle_gaps(events, window),
           "counters": counter_metrics(driver_name, c0, c1, rc)}
    if driver_name == "fleet":
        out["ticks"] = rc["ticks"]
        out["tick_ms_host_clock"] = (rc["assess_wall_s"] / rc["ticks"]
                                     * 1e3 if rc["ticks"] else None)
        out["sim_rate"] = run.e2e["sim_rate"]
        out["split"] = tick_split(spans, rc["ticks"])
    else:
        out["steps"] = rc["steps"]
        out["tokens_per_s"] = run.e2e["tokens_per_s"]
        out["split"] = step_split(spans, rc["steps"])
    return out


def main(argv=None, *, require_chip: bool = True, tweak=None,
         cache: bool = True, out_dir: Path = OUT_DIR) -> int:
    """``require_chip``, ``tweak``, ``cache`` and ``out_dir`` are the CPU
    tests' seams, as in ``run.py``."""
    from chipbench import harness
    from chipbench import run as R
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        R._program_path()
        manifest = harness.load_manifest(ROOT)
        cell, config, traffic = harness.find_cell(manifest, args.workload,
                                                  ROOT)
        if tweak is not None:
            config, traffic = tweak(config, traffic)
        if cache:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.CACHE_DIR)
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        device = R._device(int(cell["chips"]), require_chip)
    except (R.NoResult, harness.ManifestError, FileNotFoundError) as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable
    if cache:
        R.CACHE_DIR.mkdir(exist_ok=True)
        enable()
    run = harness.Run(args.workload, args.seed, args.seconds, True)
    run.device_kind = device["kind"]
    run.config = config
    driver = harness.load_driver(traffic["driver"])
    state = driver.setup(run, config, traffic)
    setup_s = time.perf_counter() - T_START
    out = {"workload": args.workload, "seed": args.seed,
           "setup_s": setup_s, "device": device}
    out.update(traced_window(
        traffic["driver"], driver, run, state, args.seconds, traffic,
        out_dir / f"trace-{args.workload}-{args.seed}"))
    driver.release(state)
    line = json.dumps(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{args.workload}-{args.seed}.json").write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
