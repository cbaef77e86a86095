"""From a profiler trace to device busy time, programs, kernels and gaps.

Read with nothing but JAX (``jax.profiler.ProfileData``). On a TPU the trace
has one plane per chip (``/device:TPU:<n>``) with an ``XLA Modules`` line
(one event per program execution, named ``jit_<function>(<fingerprint>)``)
and an ``XLA Ops`` line (one event per HLO op, named by its HLO text). The
host plane (``/host:CPU``) holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans and the program's ``serve.*`` spans
(``serve_spans.py``) on the same clock.

A Pallas kernel is an op ``%<name>.<n> = ... custom-call(...),
custom_call_target="tpu_custom_call"``, named by the ``name`` the program
gives it (``%paged_decode_attention.7``). Device time and calls are kept per
kernel name, so a reader of a new kernel's roofline needs no change here. A
program is a decode program if it runs the paged decode attention kernel, a
prefill program if it runs the paged prefill one (``PROGRAM_KIND``).

``extract`` turns a trace into plain lists of (name, start, end) events,
which is also the form of the recorded fixture in ``tests/bench``;
``reduce`` turns those into a :class:`Reduction`.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

KERNEL = 'custom_call_target="tpu_custom_call"'
_NUMBERED = re.compile(r"\.\d+$")
PROGRAM_KIND = {"paged_decode_attention": "decode",
                "paged_prefill_attention": "prefill"}
HOST_SPANS = ("bench.window", "wait_arrival", "Engine.submit",
              "Engine.step")
Span = Tuple[str, float, float]        # name, start ns, end ns


def extract(path: str) -> Dict:
    """Events of an ``.xplane.pb`` trace: per device its modules and ops,
    the benchmark's host spans, and the program's ``serve.*`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, serve = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" not in lines:
                continue
            dev = {"name": plane.name, "modules": [], "ops": []}
            for key, line in (("modules", "XLA Modules"), ("ops", "XLA Ops")):
                for ev in lines[line].events if line in lines else ():
                    dev[key].append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    into = (host if ev.name in HOST_SPANS else serve
                            if ev.name.startswith("serve.") else None)
                    if into is not None:
                        into.append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
    return {"devices": devices, "host": host, "serve": serve}


def find_trace(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def kernel_name(op_name: str) -> Optional[str]:
    """The name of a Pallas kernel's op (``%paged_decode_attention.7 = ...``
    -> ``paged_decode_attention``); None for any other op."""
    if KERNEL not in op_name:
        return None
    return _NUMBERED.sub("", op_name.split(" = ")[0].lstrip("%"))


def _union(spans: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(spans, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


def window(events: Dict, devs: List[Dict]) -> Tuple[float, float]:
    """The ``bench.window`` span, else the span of the devices' events."""
    windows = [(s, e) for n, s, e in events["host"] if n == "bench.window"]
    if windows:
        return min(s for s, _ in windows), max(e for _, e in windows)
    allev = [x for d in devs for x in d["modules"] + d["ops"]]
    return min(x[1] for x in allev), max(x[2] for x in allev)


def busy(dev: Dict, w0: float, w1: float, shift: float = 0.0
         ) -> List[Tuple[float, float]]:
    """The union of one device's events in the window, its times moved by
    ``shift``."""
    return _union(_clip([(s + shift, e + shift) for _, s, e in
                         dev["modules"] + dev["ops"]], w0, w1))


def idle(busy_: List[Tuple[float, float]], w0: float, w1: float
         ) -> List[Tuple[float, float]]:
    """The window's intervals outside a busy union."""
    edges = [w0] + [x for s, e in busy_ for x in (s, e)] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over the devices
    decode_steps_s: List[float]         # each decode program execution
    prefill_programs_s: float           # all prefill program executions
    kernel_s: Dict[str, float]          # summed device time per kernel name
    kernel_calls: Dict[str, int]
    op_self_s: Dict[str, float]         # self time per op, by label
    gaps: List[Tuple[str, float]]       # idle gaps, labelled, longest first
    split: Optional["serve_spans.Split"] = None   # idle by serve span

    def breakdown(self) -> Dict:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def _label(op_name: str, module: str, kind: str) -> str:
    head = op_name.split(" = ")[0]
    shape = op_name.split(" = ")[1].split(" ")[0] if " = " in op_name else ""
    return f"{module}[{kind}] {head} {shape}"[:160]


def reduce(events: Dict) -> Optional[Reduction]:
    """Reduce extracted events; None where no device op was traced."""
    devs = [d for d in events["devices"] if d["modules"] or d["ops"]]
    if not devs:
        return None
    w0, w1 = window(events, devs)
    busy_total = 0.0
    decode_steps: List[float] = []
    prefill_s = 0.0
    kernel_s: Dict[str, float] = collections.Counter()
    kernel_calls: Dict[str, int] = collections.Counter()
    op_self: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[float, float]] = []
    for d in devs:
        mods = sorted(d["modules"], key=lambda x: x[1])
        ops = sorted(d["ops"], key=lambda x: (x[1], -x[2]))
        starts = [m[1] for m in mods]

        def module_of(t):
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][0] if i >= 0 and t < mods[i][2] else "-"

        kinds: Dict[str, str] = {}
        for name, s, e in ops:
            k = PROGRAM_KIND.get(kernel_name(name))
            if k:
                kinds.setdefault(module_of(s), k)
        for name, s, e in ops:
            k = kernel_name(name)
            if k and w0 <= s < w1:
                kernel_s[k] += (min(e, w1) - s) / 1e9
                kernel_calls[k] += 1
        for name, s, e in mods:
            if s >= w0 and e <= w1:
                if kinds.get(name) == "decode":
                    decode_steps.append((e - s) / 1e9)
                elif kinds.get(name) == "prefill":
                    prefill_s += (e - s) / 1e9
        # self time: an op's time not covered by ops nested inside it
        stack: List[List] = []
        for name, s, e in _clip_named(ops, w0, w1):
            while stack and stack[-1][2] <= s:
                _pop(stack, op_self)
            if stack:
                stack[-1][3] -= min(e, stack[-1][2]) - s
            mod = module_of(s)
            stack.append([_label(name, mod.split("(")[0],
                                 kinds.get(mod, "other")), s, e, e - s])
        while stack:
            _pop(stack, op_self)
        union = busy(d, w0, w1)
        busy_total += sum(e - s for s, e in union) / 1e9
        gaps += idle(union, w0, w1)
    # labelled by the program's serve spans on the host's clock where it has
    # them, else by the harness's spans on the device's
    import serve_spans      # it builds on this module's window and busy
    split = serve_spans.split(events)
    labelled = serve_spans.label_gaps(events, split.idle if split else gaps)
    return Reduction(
        window_s=(w1 - w0) / 1e9, busy_s=busy_total / len(devs),
        decode_steps_s=decode_steps, prefill_programs_s=prefill_s,
        kernel_s=dict(kernel_s), kernel_calls=dict(kernel_calls),
        op_self_s={k: v / 1e9 for k, v in op_self.items()}, gaps=labelled,
        split=split)


def _clip_named(ops, w0, w1):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in ops
            if e > w0 and s < w1]


def _pop(stack, op_self):
    label, _, _, self_ns = stack.pop()
    op_self[label] += max(0.0, self_ns)


def reduce_dir(trace_dir) -> Optional[Reduction]:
    path = find_trace(trace_dir)
    return reduce(extract(path)) if path else None
