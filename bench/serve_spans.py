"""The program's own ``serve.*`` spans in a profiler trace, and the device's
idle time split by what the host was doing in it.

The serving engine enters a ``jax.profiler.TraceAnnotation`` named
``serve.<phase>`` around each phase of its loop (``repro.serve.trace``), so
a traced run has them on the profiler's host plane, beside the device's
events. This module charges every instant of each device-idle interval (the gaps of
the busy union inside the ``bench.window`` span, as ``trace_reduce.reduce``
finds them) to the innermost serve span open on the host at that instant,
and groups the spans into four parts:

  sched     the scheduler's own work: every serve span not named below
  dispatch  ``serve.dispatch.<kind>``: dispatching one compiled step
  fetch     ``serve.fetch_tokens``: the one blocking device-to-host fetch
  outside   no serve span open: the load generator and the harness

The four parts sum to the window less its busy time. A program without
these spans gives no split, and the readers then return None.

The device's times are first put on the host's clock (``clock_shift``):
the profiler syncs the two clocks, and in two traces of six on one TPU v5e
it placed the device's events about 1.4 ms early, which moved that much of
each iteration from dispatch and the scheduler into the fetch.

The readers find the trace where ``run.py`` keeps it while they read,
``.bench_cache/trace/<cell>/``, and read it only if it was written after
the window's trace began; a trace kept anywhere else they do not see. To
read a trace or a recorded fixture, with each long gap labelled:

    python3 bench/serve_spans.py <file.xplane.pb | events.json.gz>
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import gzip
import json
import math
import os
import pathlib
import statistics
import sys
import time
from typing import Dict, List, Optional, Tuple

import trace_reduce

TRACES = pathlib.Path(__file__).resolve().parents[1] / ".bench_cache/trace"
PARTS = ("sched", "dispatch", "fetch", "outside")
SCHED = ("iteration", "schedule", "policy", "submit", "commit",
         "decode_inputs", "prefill_chunk", "cow_copy", "swap_wait")
DISPATCH = ("decode", "prefill_chunk", "decode_dense", "prefill_dense",
            "prefill_slot")
GROUP = {**{f"serve.{n}": "sched" for n in SCHED},
         **{f"serve.dispatch.{k}": "dispatch" for k in DISPATCH},
         "serve.fetch_tokens": "fetch"}
DECODE_PROGRAM = "jit_decode_step"
# a program that starts this soon after the device's previous one waited for
# the device, not for its dispatch (on one TPU v5e: 3 us after, against 2.5 ms
# or more for a program that found the device idle)
QUEUED_NS = 100e3
Span = trace_reduce.Span


def extract(path: str) -> Dict:
    """``trace_reduce.extract``'s events, plus the host's serve spans under
    ``serve``."""
    from jax.profiler import ProfileData
    events = trace_reduce.extract(path)
    serve = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        serve.append((ev.name, float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns)))
    events["serve"] = serve
    return events


def _window(events: Dict, devs: List[Dict]) -> Tuple[float, float]:
    windows = [(s, e) for n, s, e in events["host"] if n == "bench.window"]
    if windows:
        return min(s for s, _ in windows), max(e for _, e in windows)
    allev = [x for d in devs for x in d["modules"] + d["ops"]]
    return min(x[1] for x in allev), max(x[2] for x in allev)


def _idle(dev: Dict, w0: float, w1: float, shift: float = 0.0
          ) -> List[Tuple[float, float]]:
    """One device's idle intervals in the window, as ``reduce`` finds them,
    its times moved by ``shift``."""
    busy = trace_reduce._union(trace_reduce._clip(
        [(s + shift, e + shift) for _, s, e in dev["modules"] + dev["ops"]],
        w0, w1))
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def innermost(spans: List[Span]) -> List[Span]:
    """The timeline cut into pieces, each named by the innermost span open
    over it; time under no span is left out. Spans nest on one thread; a
    child that outlasts its parent is cut at the parent's end."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []
    t = 0.0
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            out.append((top, t, end))
            t = end
        if stack:
            out.append((stack[-1][0], t, s))
            e = min(e, stack[-1][1])
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        out.append((top, t, end))
        t = end
    return [p for p in out if p[2] > p[1]]


def _cover(pieces: List[Span], starts: List[float], a: float, b: float
           ) -> Dict[str, float]:
    """ns of [a, b] under each innermost span name."""
    got: Dict[str, float] = collections.Counter()
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(pieces) and pieces[i][1] < b:
        name, s, e = pieces[i]
        c = min(b, e) - max(a, s)
        if c > 0:
            got[name] += c
        i += 1
    return got


def _pairs(programs: List[Tuple], dispatches: List[Tuple[float, float]]
           ) -> List[Tuple]:
    """Each decode program paired with its dispatch: the k-th program with
    the (k - c)-th dispatch, c 1 where the trace opened while a program
    dispatched before it ran, else 0, whichever pairs programs with the
    nearer dispatch ends."""
    best: List[Tuple] = []
    cost = math.inf
    for c in (0, 1):
        pairs = [(p, dispatches[k - c]) for k, p in enumerate(programs)
                 if 0 <= k - c < len(dispatches)]
        if pairs:
            got = statistics.median(abs(p[0] - d[1]) for p, d in pairs)
            if got < cost:
                best, cost = pairs, got
    return best


def clock_shift(dev: Dict, serve: List[Span]) -> Optional[float]:
    """ns to add to one device's times to put them on the host's clock.

    On a synced clock the median decode program that found the device idle
    starts after its dispatch has returned, and the median decode program
    ends before the token fetch after its dispatch returns (on one TPU v5e:
    0.2 ms after, 1.0 ms before). Where the first starts earlier, the
    device's times move later until it does not; where the second ends
    later, earlier. 0 where both hold, and where no decode program was
    paired; None where no shift makes both hold."""
    programs, busy_to = [], -math.inf
    for s, e, n in sorted((s, e, n) for n, s, e in dev["modules"]):
        if n.split("(")[0] == DECODE_PROGRAM:
            programs.append((s, e, s - busy_to < QUEUED_NS))
        busy_to = max(busy_to, e)
    dispatches = sorted((s, e) for n, s, e in serve
                        if n == "serve.dispatch.decode")
    fetches = sorted((s, e) for n, s, e in serve
                     if n == "serve.fetch_tokens")
    pairs = _pairs(programs, dispatches)
    f_starts = [s for s, _ in fetches]
    starts = [d[1] - p[0] for p, d in pairs if not p[2]]
    ends = []
    for p, d in pairs:
        i = bisect.bisect_left(f_starts, d[1])
        if i < len(fetches):
            ends.append(fetches[i][1] - p[1])
    lo = statistics.median(starts) if starts else -math.inf
    hi = statistics.median(ends) if ends else math.inf
    if lo > hi:
        return None
    return min(max(0.0, lo), hi)


@dataclasses.dataclass
class Split:
    window_s: float
    idle_s: Dict[str, float]        # per part, averaged over the devices
    iterations: int                 # serve.iteration spans begun in the window
    host_step_s: List[float]        # each iteration less its token fetches
    shift_s: List[float]            # each device's clock_shift
    idle: List[Tuple[float, float]]  # idle intervals, on the host's clock

    def idle_ms(self, which: str) -> float:
        """Idle ms an iteration charged to the part ``which``."""
        return 1000.0 * self.idle_s[which] / self.iterations

    def host_step_ms(self) -> float:
        return 1000.0 * sum(self.host_step_s) / len(self.host_step_s)


def split(events: Dict) -> Optional[Split]:
    """Split extracted events' device idle time by serve span; None where
    the trace has no device op or no ``serve.iteration`` in the window, or
    where a device's clock cannot be put on the host's."""
    devs = [d for d in events["devices"] if d["modules"] or d["ops"]]
    serve = events.get("serve", [])
    if not devs or not serve:
        return None
    w0, w1 = _window(events, devs)
    iters = [(s, e) for n, s, e in serve
             if n == "serve.iteration" and w0 <= s < w1]
    if not iters:
        return None
    shifts, idle = [], []
    for d in devs:
        shift = clock_shift(d, serve)
        if shift is None:
            return None
        shifts.append(shift / 1e9)
        idle += _idle(d, w0, w1, shift)
    pieces = innermost(serve)
    starts = [p[1] for p in pieces]
    idle_ns = dict.fromkeys(PARTS, 0.0)
    for a, b in idle:
        got = _cover(pieces, starts, a, b)
        for name, c in got.items():
            idle_ns[GROUP.get(name, "sched")] += c
        idle_ns["outside"] += (b - a) - sum(got.values())
    fetches = sorted((s, e) for n, s, e in serve if n == "serve.fetch_tokens")
    f_starts = [s for s, _ in fetches]
    host_step = []
    for s, e in iters:
        i, j = (bisect.bisect_left(f_starts, s),
                bisect.bisect_left(f_starts, e))
        host_step.append(((e - s) - sum(min(fe, e) - fs
                                        for fs, fe in fetches[i:j])) / 1e9)
    return Split(window_s=(w1 - w0) / 1e9,
                 idle_s={k: v / 1e9 / len(devs) for k, v in idle_ns.items()},
                 iterations=len(iters), host_step_s=host_step,
                 shift_s=shifts, idle=idle)


def label_gaps(events: Dict, s: Split, n: int = 10
               ) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each labelled by the serve span covering
    most of it, else by the harness span covering most of it (as
    ``trace_reduce`` labels them), else ``outside``."""
    pieces = innermost(events["serve"])
    starts = [p[1] for p in pieces]
    harness = innermost([x for x in events["host"] if x[0] != "bench.window"])
    h_starts = [p[1] for p in harness]
    gaps = []
    for a, b in sorted(s.idle, key=lambda g: g[0] - g[1])[:n]:
        got = (_cover(pieces, starts, a, b)
               or _cover(harness, h_starts, a, b))
        gaps.append((max(got, key=got.get) if got else "outside",
                     (b - a) / 1e9))
    return gaps


@functools.lru_cache(maxsize=1)
def _split_file(path: str, mtime: float) -> Optional[Split]:
    return split(extract(path))


def of(w) -> Optional[Split]:
    """The split of a traced window's trace, read once for all readers;
    None where ``run.py``'s trace of the cell is missing or older than the
    window's trace."""
    from harness import CLOCK
    if w.trace is None:
        return None
    path = trace_reduce.find_trace(TRACES / w.name)
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if mtime < time.time() - (CLOCK() - w.trace_t0):
        return None
    return _split_file(path, mtime)


def main(argv: List[str]) -> int:
    path = argv[0]
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            events = json.load(f)
    else:
        events = extract(path)
    s = split(events)
    if s is None:
        print("no serve spans and device ops in this trace, or clocks that "
              "no shift reconciles", file=sys.stderr)
        return 1
    print(json.dumps({
        "window_s": s.window_s, "iterations": s.iterations,
        "clock_shift_ms": [1000.0 * x for x in s.shift_s],
        "idle_ms_per_iteration": {p: s.idle_ms(p) for p in PARTS},
        "host_step_ms": s.host_step_ms(),
        "idle_gaps": label_gaps(events, s)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
