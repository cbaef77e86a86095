"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) says what the requests look like; the
cell (``bench/workloads/<cell>.json``) says how they are offered: an open
loop of sessions arriving at a fixed rate, or a closed loop of clients that
each send their next request when the last one ends.

Every random size is drawn by strata: for n draws the generator takes the n
quantiles (i + 1/2) / n of the stated distribution and the seed only orders
them. Each seed thus offers the same multiset of lengths, gaps and turn
counts in another order, with other token ids, so runs with different seeds
do the same amount of work.

Mix keys (all lengths in tokens):

  system_prompts  {"count", "length": dist, "zipf_s", "primed"}  shared
                  prefixes (optional); each session picks one with Zipf
                  weights 1/k^s, the k-th most popular having the k-th
                  longest of the length strata for every seed. With
                  "primed", the schedule starts with each sent once with one
                  output token (session -1), which the harness serves one
                  after another before the arrival clock starts, so that the
                  prefix cache holds them, as at a front end that has run
                  for a while
  turns           {"lo", "hi"}  turns per session, uniform (default 1..1)
  turn_gap_s      dist  seconds from a turn's due time to the next one's
  prompt_len      dist  the user message (a turn) or the whole prompt
  max_new         dist  tokens to generate

A dist is {"dist": "uniform" | "loguniform" | "lognormal", "lo", "hi"} over
whole numbers, with "median" and "sigma" for lognormal (clipped to [lo,
hi]; with "truncate", lengths outside it are dropped instead), or
{"dist": "uniform_real", "lo", "hi"} over real numbers. Turn k's prompt is
the system prompt, then each earlier turn's message and a seeded stand-in
for its answer of that turn's ``max_new`` tokens, then turn k's message: it
extends turn k-1's prompt, and it is due whether or not turn k-1 finished.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Req:
    """One request: its prompt, its output length, and (open loop) the
    second, from the start of the schedule, at which it is due."""
    prompt: np.ndarray
    max_new: int
    due_s: float = 0.0
    session: int = 0
    turn: int = 0


def strata(rng: np.random.Generator, n: int, spec: Dict) -> np.ndarray:
    """n draws of ``spec``: its quantiles at (i + 1/2) / n, in seeded order."""
    u = (np.arange(n) + 0.5) / n
    kind, lo, hi = spec["dist"], spec.get("lo"), spec.get("hi")
    if kind == "uniform":
        v = lo + np.floor(u * (hi - lo + 1))
    elif kind == "loguniform":
        v = np.round(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo))))
    elif kind == "lognormal":
        med, sigma = spec["median"], spec["sigma"]
        if spec.get("truncate"):
            # lengths outside [lo, hi] are dropped, not piled at the ends
            a, b = (_NORMAL.cdf(math.log(x / med) / sigma) for x in (lo, hi))
            u = a + u * (b - a)
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        v = np.round(med * np.exp(sigma * z))
        v = np.clip(v, lo, hi)
    elif kind == "uniform_real":
        v = lo + u * (hi - lo)
    elif kind == "exponential":
        v = -np.log1p(-u) * spec["mean"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if kind not in ("exponential", "uniform_real"):
        v = np.clip(v, lo, hi).astype(np.int64)
    return rng.permutation(v)


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of n picks go to each of k items under weights 1/i^s, by
    largest remainder, so the counts are the same for every seed."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, int(n)).astype(np.int32)


def sessions(mix: Dict, n_sessions: int, vocab: int,
             rng: np.random.Generator) -> Tuple[List[np.ndarray],
                                                List[List[Req]]]:
    """The mix's system prompts, most popular first, and n sessions, each a
    list of its turns (prompt, max_new, the turn's offset from the session's
    start in ``due_s``)."""
    t = mix.get("turns", {"lo": 1, "hi": 1})
    n_turns = strata(rng, n_sessions,
                     {"dist": "uniform", "lo": t["lo"], "hi": t["hi"]})
    total = int(n_turns.sum())
    msg = strata(rng, total, mix["prompt_len"])
    out_len = strata(rng, total, mix["max_new"])
    gaps = (strata(rng, total, mix["turn_gap_s"]).astype(float)
            if "turn_gap_s" in mix else np.zeros(total))
    systems: List[np.ndarray] = []
    pick = np.full(n_sessions, -1)
    sp = mix.get("system_prompts")
    if sp:
        lens = np.sort(strata(rng, sp["count"], sp["length"]))[::-1]
        systems = [_tokens(rng, n, vocab) for n in lens]
        counts = zipf_counts(n_sessions, sp["count"], sp.get("zipf_s", 1.0))
        pick = rng.permutation(np.repeat(np.arange(sp["count"]), counts))
    out, i = [], 0
    for s in range(n_sessions):
        history = [systems[pick[s]]] if pick[s] >= 0 else []
        turns, offset = [], 0.0
        for k in range(int(n_turns[s])):
            user = _tokens(rng, msg[i], vocab)
            prompt = np.concatenate(history + [user])
            turns.append(Req(prompt=prompt, max_new=int(out_len[i]),
                             due_s=offset, session=s, turn=k))
            history += [user, _tokens(rng, out_len[i], vocab)]
            offset += float(gaps[i])
            i += 1
        out.append(turns)
    return systems, out


def open_loop(mix: Dict, rate_per_s: float, horizon_s: float, vocab: int,
              seed: int) -> List[Req]:
    """Sessions arriving as a Poisson process at ``rate_per_s`` over
    ``horizon_s`` seconds; returns every turn, sorted by due time."""
    rng = np.random.default_rng(seed)
    n = max(1, int(math.ceil(rate_per_s * horizon_s)))
    gaps = strata(rng, n, {"dist": "exponential", "mean": 1.0 / rate_per_s})
    starts = np.cumsum(gaps) - gaps[0]
    systems, by_session = sessions(mix, n, vocab, rng)
    primed = mix.get("system_prompts", {}).get("primed", False)
    reqs = [Req(prompt=p, max_new=1, session=-1) for p in systems] \
        if primed else []
    for start, turns in zip(starts, by_session):
        for r in turns:
            r.due_s += float(start)
            reqs.append(r)
    reqs.sort(key=lambda r: r.due_s)
    return reqs


def closed_loop(mix: Dict, clients: int, per_client: int, vocab: int,
                seed: int) -> List[List[Req]]:
    """``clients`` queues of ``per_client`` single-turn requests each."""
    rng = np.random.default_rng(seed)
    flat = [t[0] for t in sessions(mix, clients * per_client, vocab, rng)[1]]
    return [flat[c::clients] for c in range(clients)]


def longest_prompt(mix: Dict) -> int:
    """The longest prompt the mix can make (sizes the engine's max_seq)."""
    sp = mix.get("system_prompts")
    t = mix.get("turns", {"hi": 1})["hi"]
    return ((sp["length"]["hi"] if sp else 0)
            + (t - 1) * (mix["prompt_len"]["hi"] + mix["max_new"]["hi"])
            + mix["prompt_len"]["hi"])


def validate(mix: Dict, max_seq: Optional[int] = None) -> None:
    """Refuse a mix whose requests could not all be served by an engine of
    ``max_seq`` positions (prompt + output + the engine's two spare rows)."""
    if max_seq is not None:
        need = longest_prompt(mix) + mix["max_new"]["hi"] + 2
        if need > max_seq:
            raise ValueError(f"the mix needs max_seq >= {need}, the cell "
                             f"gives {max_seq}")
