"""One run of one cell: set-up, a measured window, the check, the result.

Everything that belongs to one cell, configuration, traffic mix or metric is
a file of its own, found by name:

  BENCHMARK.json                the cells, the metrics, their bounds
  bench/workloads/<cell>.json   configuration, mix, load, engine, check
  bench/configs/<config>.json   the model's published config as run; its
                                ``block`` names the two files below
  bench/blocks/<block>.py       the block as the program runs it: its
                                ``program_config``, weight ``layout`` and
                                ``layer_flops``
  bench/reference/<block>.py    the block's float32 reference
  bench/traffic/<mix>.json      the mix's parameters (traffic/generator.py)
  bench/metrics/<name>.py       an end-to-end metric's reader
  bench/layer_metrics/<name>.py a per-layer metric's reader

A reader is ``read(w: Window) -> float | None``; ``None`` leaves the metric
out of the result line.

The system under test is the program's ``repro.serve.engine.Engine``, driven
through ``submit`` and ``step`` only. Set-up makes the weights from the
seed, then warms up, on an engine of the cell's shape, every program the
window can dispatch: the decode step and each prefill chunk size from 1 to
the token budget (requests whose prompts sum to the budget complete in one
iteration, each as one chunk). A fresh engine then takes the traffic: an
open loop is run for ``lead_s`` before the window opens, a closed loop until
every slot decodes. ``setup_s`` is the time from process start to then.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import time
from types import ModuleType
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import model
import trace_reduce
from reference import check
from traffic import generator

from repro.serve.cache import CacheConfig
from repro.serve.engine import Engine, EngineConfig, Request

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CLOCK = time.perf_counter      # the scheduler's clock too (Request.t_*)
TRACE_S = 10.0


def load(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    """What one cell needs, read from its files."""
    cell: Dict
    conf: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    peaks: Dict
    block: ModuleType           # bench/blocks/<block>.py
    ref: ModuleType             # bench/reference/<block>.py
    chips: int = 1


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _module(path: pathlib.Path, name: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod         # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


def block_of(conf_path: pathlib.Path, conf: Dict) -> tuple:
    """The modules of the block the configuration file names: its program
    side and its reference, beside the file under ``bench/``."""
    if "block" not in conf:
        raise ValueError(f"{conf_path}: names no \"block\"")
    bench = conf_path.parent.parent
    mods = []
    for kind in ("blocks", "reference"):
        path = bench / kind / f"{conf['block']}.py"
        if not path.is_file():
            raise ValueError(f"{conf_path}: its block {conf['block']!r} has "
                             f"no {kind}/{path.name} in {bench}")
        mods.append(_module(path, f"bench_{kind}_{conf['block']}"))
    return tuple(mods)


def spec(name: str, root: pathlib.Path = ROOT) -> Spec:
    bench = load(root / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[name]
    cell = load(root / "bench" / "workloads" / f"{name}.json")
    if (cell["config"], cell["traffic"]) != (entry["config"],
                                             entry["traffic"]):
        raise ValueError(f"{name}: its file and BENCHMARK.json disagree on "
                         "configuration or traffic")
    conf_path = root / "bench" / "configs" / f"{cell['config']}.json"
    conf = load(conf_path)
    block, ref = block_of(conf_path, conf)
    return Spec(
        cell=cell, conf=conf,
        mix=load(root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        peaks=load(root / "bench" / "peaks.json"), block=block, ref=ref,
        chips=entry["chips"])


class CompileCounter:
    """Programs the process needed, from JAX's monitoring events (the
    pattern of ``chip_smoke.py``): JAX times every program it obtains as a
    backend compile, whether it compiled it or loaded it from the persistent
    cache, and counts the loads apart as cache hits."""

    def __init__(self):
        self.programs = self.hits = 0
        self.obtain_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.obtain_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiles(self) -> int:
        return self.programs - self.hits


@dataclasses.dataclass
class Record:
    """One request as served: the benchmark's own view of it."""
    req: Request
    due: float = 0.0            # host clock at which it was due (open loop)
    t_submit: float = 0.0
    t_done: float = math.inf


@dataclasses.dataclass
class Window:
    """What a reader sees once the window has closed."""
    name: str
    conf: Dict
    cell: Dict
    peaks: Dict
    t0: float
    t1: float
    open_loop: bool
    records: List[Record]
    stats0: Dict
    stats1: Dict
    page_share: List[float]      # used / total pages after each step
    admit_lat: List[float]       # queue latency of admissions in the window
    setup_programs: int
    window_programs: int
    steps: List[tuple]           # (t_begin, t_end, new iter_log entries)
    trace: Optional[trace_reduce.Reduction] = None
    trace_t0: float = 0.0
    trace_t1: float = 0.0
    device_kind: str = ""
    block: Optional[ModuleType] = None     # for the work count

    def peak(self) -> Dict:
        """The device kind's peaks; a kind not in peaks.json is an error."""
        try:
            return self.peaks["devices"][self.device_kind]
        except KeyError:
            raise ValueError(f"no peaks for device kind {self.device_kind!r}"
                             " in bench/peaks.json") from None

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if self.t0 <= r.due < self.t1]

    def ttfts(self) -> List[float]:
        """Seconds from due to first token of every request due in the
        window; one with no first token by the close counts its time so
        far."""
        out = []
        for r in self.due_in_window():
            t = r.req.t_first
            out.append((t if 0.0 < t <= self.t1 else self.t1) - r.due)
        return out

    def gaps(self) -> List[float]:
        """Every inter-token gap whose later token landed in the window."""
        out = []
        for r in self.records:
            ts = r.req.t_tokens or []
            out += [b - a for a, b in zip(ts, ts[1:])
                    if self.t0 < b <= self.t1]
        return out

    def emitted(self, t0: float, t1: float) -> int:
        return sum(sum(1 for t in (r.req.t_tokens or []) if t0 < t <= t1)
                   for r in self.records)

    def delta(self, key: str) -> float:
        return self.stats1.get(key, 0) - self.stats0.get(key, 0)

    def work(self, t0: float, t1: float):
        """Work the algorithm needed for what the engine did in [t0, t1]:
        decode tokens by the time they landed, prefill chunks by the step
        that dispatched them."""
        import costs
        contexts = []
        for r in self.records:
            ts, L = r.req.t_tokens or [], len(r.req.prompt)
            contexts += [L + i for i, t in enumerate(ts)
                         if i >= 1 and t0 < t <= t1]
        chunks = [(start, size) for tb, te, log in self.steps
                  if t0 <= tb < t1 for e in log
                  for _, start, size in e["chunks"]]
        shape = costs.Shape.of(self.conf, self.block.layer_flops(self.conf))
        return costs.count(shape, contexts, chunks, self.emitted(t0, t1))


def quantile(values: List[float], p: float) -> Optional[float]:
    """Percentile ``p`` with linear interpolation; None for no values."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), p))


def _reader(kind: str, name: str) -> Callable:
    return _module(BENCH / kind / f"{name}.py", f"bench_{kind}_{name}"
                   .replace("-", "_").replace(".", "_")).read


def engine_config(cell: Dict) -> EngineConfig:
    e = cell["engine"]
    return EngineConfig(
        n_slots=e["n_slots"], max_seq=e["max_seq"], chunked=True,
        token_budget=e.get("token_budget"),
        cache=CacheConfig(paged=True, page_tokens=e["page_tokens"],
                          n_pages=e.get("n_pages"), prefix=e["prefix"],
                          prefix_pages=e.get("prefix_pages")))


def _drain(eng: Engine) -> None:
    while not eng.idle:
        eng.step()


def warm_up(cfg, params, econf: EngineConfig, vocab: int) -> None:
    """Run, on a throwaway engine, every program the window can dispatch."""
    eng = Engine(cfg, params, config=econf)
    budget = eng.token_budget
    rng = np.random.default_rng(0)
    ids = iter(range(10 ** 9))

    def wave(lengths, max_new=2):
        for n in lengths:
            eng.submit(Request(seq_id=next(ids), max_new=max_new,
                               prompt=rng.integers(0, vocab, n)
                               .astype(np.int32)))
        _drain(eng)

    # each wave completes in one iteration, one chunk per request, so the
    # two sizes s and budget - s compile in one go
    for s in range(0, budget // 2 + 1):
        wave([n for n in (s, budget - s) if n > 0])
    if econf.cache.prefix:
        # copy-on-write of a shared partial page: a prompt, its exact
        # re-arrival, and a longer prompt that shares it mid-page
        base = rng.integers(0, vocab, 40).astype(np.int32)
        for prompt in (base, base, np.concatenate([base, base[:9]])):
            eng.submit(Request(seq_id=next(ids), prompt=prompt, max_new=3))
            _drain(eng)
    # the one host transfer per iteration concatenates the iteration's ids:
    # k chunk completions, with and without a decode batch
    vec = jnp.zeros((econf.n_slots,), jnp.int32)
    one = jnp.zeros((), jnp.int32)
    for k in range(1, 2 * econf.n_slots + 2):
        for tail in ([], [vec]):
            flats = [jnp.ravel(a) for a in [one] * k + tail]
            np.asarray(jnp.concatenate(flats) if len(flats) > 1
                       else flats[0])
    del eng
    gc.collect()


class Feeder:
    """Offers the cell's load to one engine and keeps the records."""

    def __init__(self, eng: Engine, spec_: Spec, seed: int, seconds: float,
                 annotate: bool):
        self.eng, self.spec = eng, spec_
        self.load = spec_.cell["load"]
        self.records: Dict[int, Record] = {}
        self.page_share: List[float] = []
        self.steps: List[tuple] = []
        self.refused = 0
        self.n_pages = eng.pool.alloc.n_pages
        self.annotate = annotate
        self._ids = iter(range(10 ** 9))
        vocab = spec_.conf["vocab_size"]
        if self.load["kind"] == "open":
            horizon = self.load["lead_s"] + seconds
            self.schedule = generator.open_loop(
                spec_.mix, self.load["session_rate_per_s"], horizon, vocab,
                seed)
            self.next = 0
        else:
            self.queues = generator.closed_loop(
                spec_.mix, self.load["clients"],
                self.load["requests_per_client"], vocab, seed)
            self.client_of: Dict[int, int] = {}

    def _span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def submit(self, r: generator.Req, due: float,
               client: Optional[int] = None) -> None:
        sid = next(self._ids)
        req = Request(seq_id=sid, prompt=r.prompt, max_new=r.max_new)
        with self._span("Engine.submit"):
            ok = self.eng.submit(req)
        if not ok:
            self.refused += 1
            return
        self.records[sid] = Record(req=req, due=due, t_submit=req.t_submit)
        if client is not None:
            self.client_of[sid] = client

    def step(self, sample_pages: bool) -> None:
        n_log = len(self.eng.stats["iter_log"])
        tb = CLOCK()
        with self._span("Engine.step"):
            done = self.eng.step()
        te = CLOCK()
        self.steps.append((tb, te, self.eng.stats["iter_log"][n_log:]))
        if sample_pages:
            self.page_share.append(
                self.eng.metrics.gauge("used_pages").value / self.n_pages)
        for req in done:
            rec = self.records[req.seq_id]
            rec.t_done = te
            if self.load["kind"] == "closed":
                c = self.client_of[req.seq_id]
                if self.queues[c]:
                    self.submit(self.queues[c].pop(0), te, c)

    # -- open loop ---------------------------------------------------------
    def prime(self) -> None:
        """Serve the schedule's primers (session -1: each system prompt
        once) one after another, before the arrival clock starts."""
        for r in [r for r in self.schedule if r.session < 0]:
            self.submit(r, CLOCK())
            while not self.eng.idle:
                self.step(sample_pages=False)
        self.schedule = [r for r in self.schedule if r.session >= 0]

    def run_open(self, t_start: float, t_end: float, measure_from: float):
        """Serve the schedule (time 0 = ``t_start``) until ``t_end``."""
        sched = self.schedule
        while True:
            now = CLOCK()
            if now >= t_end:
                return
            while self.next < len(sched) and \
                    t_start + sched[self.next].due_s <= now:
                r = sched[self.next]
                self.submit(r, t_start + r.due_s)
                self.next += 1
            if self.eng.idle:
                due = (t_start + sched[self.next].due_s
                       if self.next < len(sched) else t_end)
                with self._span("wait_arrival"):
                    time.sleep(max(0.0, min(due, t_end) - CLOCK()))
                continue
            self.step(sample_pages=now >= measure_from)

    # -- closed loop -------------------------------------------------------
    def start_closed(self) -> None:
        """Bring every slot to decoding: the first ``n_slots`` clients send
        one after another, each once the one before has its prompt in; the
        rest then send and wait in the queue."""
        n_slots = self.spec.cell["engine"]["n_slots"]
        for c, q in enumerate(self.queues):
            self.submit(q.pop(0), CLOCK(), c)
            while c < n_slots and (len(self.eng.mailbox)
                                   or self.eng.prefilling):
                self.step(sample_pages=False)
        while len(self.eng.active) < n_slots:
            self.step(sample_pages=False)

    def run_closed(self, t_end: float) -> None:
        while CLOCK() < t_end and not self.eng.idle:
            self.step(sample_pages=True)


def _stats(eng: Engine) -> Dict:
    return {k: v for k, v in eng.stats.items() if isinstance(v, (int, float))}


def sample(records: List[Record], seed: int, n: int) -> List[Record]:
    """The requests the check reads: the finished request with the most
    served tokens, then finished ones drawn from the seed; where fewer than
    ``n`` finished, the unfinished ones that served the most tokens."""
    n_out = lambda r: len(r.req.tokens_out or ())
    done = [r for r in records if r.req.done and n_out(r)]
    open_ = sorted((r for r in records if not r.req.done and n_out(r)),
                   key=lambda r: -n_out(r))
    if not done:
        return open_[:n]
    longest = max(done, key=n_out)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed + 1)
    drawn = [rest[i] for i in rng.permutation(len(rest))[:max(0, n - 1)]]
    return ([longest] + drawn + open_)[:n]


def correctness(spec_: Spec, params, records: List[Record], seed: int,
                n_check: int) -> Dict:
    """The widest gap of a sample of served tokens below the reference's
    best (see reference/check.py), with the sample drawn from the seed."""
    pick = sample(records, seed, n_check)
    if not pick:
        return {"tokens": 0, "requests": 0, "worst_gap_sigma": math.inf}
    samples = [{"prompt": r.req.prompt.tolist(),
                "served": list(r.req.tokens_out)} for r in pick]
    gaps = check.served_gaps(params, spec_.ref, spec_.ref.Dims.of(spec_.conf),
                             spec_.cell["engine"]["max_seq"], samples)
    allg = np.concatenate(gaps)
    return {"tokens": int(allg.size), "requests": len(pick),
            "worst_gap_sigma": float(allg.max()) if allg.size else math.inf}


def setup(spec_: Spec, seed: int, say, t_process: float) -> tuple:
    """The program's config, the weights from ``seed`` and the engine
    config, with every program the window can dispatch warmed up."""
    cell, conf = spec_.cell, spec_.conf
    cfg = spec_.block.program_config(conf)
    generator.validate(spec_.mix, cell["engine"]["max_seq"])
    params = model.make_params(spec_.block.layout(conf), seed)
    jax.block_until_ready(params)
    say(f"weights made at {CLOCK() - t_process:.3f} s")
    econf = engine_config(cell)
    warm_up(cfg, params, econf, conf["vocab_size"])
    return cfg, params, econf


def measure(spec_: Spec, cfg, params, econf, seed: int, seconds: float,
            counter: CompileCounter, t_process: float,
            trace_dir: Optional[pathlib.Path] = None) -> tuple:
    """Offer the cell's load to a fresh engine and measure one window.
    Returns (setup_s, window, failed, device), with the engine freed."""
    cell = spec_.cell
    eng = Engine(cfg, params, config=econf)
    drv = Feeder(eng, spec_, seed, seconds, annotate=trace_dir is not None)
    open_loop = cell["load"]["kind"] == "open"
    if open_loop:
        drv.prime()
        t_sched = CLOCK()
        drv.run_open(t_sched, t_sched + cell["load"]["lead_s"],
                     measure_from=math.inf)
    else:
        drv.start_closed()
    t0 = CLOCK()
    setup_s = t0 - t_process
    setup_programs = counter.programs
    stats0 = _stats(eng)
    n_adm0 = len(eng.stats["queue_lat_s"])
    t1 = t0 + seconds
    serve = ((lambda until: drv.run_open(t_sched, until, measure_from=t0))
             if open_loop else drv.run_closed)
    # the traced run traces the window's last TRACE_S seconds only: a trace
    # of every op is large, and stopping it takes seconds
    t_trace = t1 - min(TRACE_S, seconds / 2) if trace_dir else t1
    serve(t_trace)
    trace_t0 = trace_t1 = 0.0
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        trace_t0 = CLOCK()
        with jax.profiler.TraceAnnotation("bench.window"):
            serve(t1)
            jax.block_until_ready(eng.pool.pages)
        trace_t1 = CLOCK()
        jax.profiler.stop_trace()
    stats1 = _stats(eng)
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    win = Window(name=cell["name"], conf=spec_.conf, cell=cell,
                 peaks=spec_.peaks, t0=t0, t1=t1, open_loop=open_loop,
                 records=sorted(drv.records.values(),
                                key=lambda r: r.req.seq_id),
                 stats0=stats0, stats1=stats1, page_share=drv.page_share,
                 admit_lat=list(eng.stats["queue_lat_s"][n_adm0:]),
                 setup_programs=setup_programs,
                 window_programs=counter.programs - setup_programs,
                 steps=drv.steps, trace_t0=trace_t0, trace_t1=trace_t1,
                 device_kind=dev.device_kind, block=spec_.block)
    failed = stats1.get("rejected", 0) + drv.refused
    del eng, drv, serve     # the engine and its pool, before any reference
    gc.collect()
    if trace_dir is not None:
        win.trace = trace_reduce.reduce_dir(trace_dir)
        device["busy_s"] = win.trace.busy_s if win.trace else 0.0
        device["window_s"] = (win.trace.window_s if win.trace
                              else trace_t1 - trace_t0)
    return setup_s, win, failed, device


def attempted(win: Window) -> int:
    """Open loop: requests due in the window. Closed loop: requests in
    flight at some time in the window."""
    if win.open_loop:
        return len(win.due_in_window())
    return sum(1 for r in win.records if r.t_submit < win.t1
               and r.t_done >= win.t0)


def run(spec_: Spec, seed: int, seconds: float, trace: bool,
        t_process: float, trace_dir: Optional[pathlib.Path] = None,
        log=sys.stderr) -> Dict:
    """One run; returns the result line's object."""
    say = lambda msg: print(f"[bench] {msg}", file=log, flush=True)
    counter = CompileCounter()
    cfg, params, econf = setup(spec_, seed, say, t_process)
    say(f"set-up programs ready at {CLOCK() - t_process:.3f} s: "
        f"{counter.programs} programs in {counter.obtain_s:.3f} s, "
        f"{counter.compiles} compiled, {counter.hits} from the cache")
    setup_s, win, failed, device = measure(
        spec_, cfg, params, econf, seed, seconds, counter, t_process,
        trace_dir if trace else None)
    say(f"window {win.t0 - t_process:.3f}..{win.t1 - t_process:.3f} s; "
        f"{len(win.steps)} steps in all, {win.window_programs} programs "
        "needed in the window")
    metrics: Dict[str, Dict] = {}
    for m in (spec_.per_layer if trace else spec_.end_to_end):
        kind = "layer_metrics" if trace else "metrics"
        v = (setup_s if m["name"] == "setup_s"
             else _reader(kind, m["name"])(win))
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t_ref = CLOCK()
    chk = correctness(spec_, params, win.records, seed,
                      spec_.cell["check"]["requests"])
    limit = spec_.cell["check"]["worst_gap_sigma"]
    emitted = win.emitted(win.t0, win.t1)
    say(f"reference over {chk['requests']} requests, {chk['tokens']} served "
        f"tokens, in {CLOCK() - t_ref:.3f} s")
    say(f"{attempted(win)} requests attempted in the window, {emitted} "
        f"tokens emitted, {sum(1 for r in win.records if not r.req.done)} "
        "requests open at the close")
    compared = {
        "worst_gap_sigma": {"value": chk["worst_gap_sigma"], "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = (chk["worst_gap_sigma"] <= limit and failed == 0
               and emitted >= 1 and chk["tokens"] > 0)
    result = {"correct": bool(correct), "attempted": attempted(win),
              "failed": int(failed), "metrics": metrics, "device": device}
    if win.trace is not None:
        result["breakdown"] = win.trace.breakdown()
    for k, v in compared.items():
        print(f"compared {k} {v['value']} limit {v['limit']}", file=log,
              flush=True)
    result["compared"] = compared
    return result
