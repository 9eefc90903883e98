"""One general generator of open-loop traffic, driven by a mix's data file.

A mix (``traffic/<name>.json``) describes sessions:

* ``turns``: requests per session (a length distribution);
* ``think_s``: the gap between a session's turns (needed when a session
  can have more than one turn);
* ``prefix``: what a session's prompts start with — nothing (``null``),
  one of a ``pool`` of shared prefixes picked by Zipf popularity, or a
  prefix of the session's own (``"share": "session"``);
* ``prompt``: the distinct part of each prompt, after the prefix;
* ``output``: the tokens to generate (greedy, no end token).

* ``arrivals``: how sessions arrive, ``{"process": <name>, ...}``; the
  process is the file ``arrivals/<name>.py``, found by name, whose
  ``gaps(rng, n, mean_s, params)`` gives the gaps of a block of ``n``
  sessions.  A new process is a new file.

The cell fixes the rate of *requests*; sessions arrive at that rate over
the mean turn count.

Sizes (turns, prefix and prompt lengths, outputs, think times) are drawn
stratified in blocks of ``block`` sessions: each block holds the values
at the quantiles ``(i + 0.5) / block`` of the distribution, in an order
drawn from the seed.  So every seed offers the same sizes, in another
order, and with other token ids.

A mix sets ``order_seed`` where the seed's order would change the work
in a window (a document session costs ten times a follow-up question; a
burst of long answers holds the slots): the order, and the arrivals, are
then drawn from it, the same for every run, and the run's seed picks
only the token ids.
"""

from __future__ import annotations

import importlib.util
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Any, Callable, Dict, List, Optional

import numpy as np


@dataclass
class Arrival:
    rid: int
    due: float           # seconds after the traffic starts
    prompt: List[int]
    max_new: int
    session: int
    prefix_len: int


def quantile(dist: Dict[str, Any], q: float) -> float:
    """The value of ``dist`` at quantile ``q`` (0 < q < 1)."""
    kind = dist["dist"]
    if kind == "const":
        return dist["value"]
    if kind == "uniform":            # whole numbers lo..hi, both included
        lo, hi = int(dist["lo"]), int(dist["hi"])
        return lo + min(int(q * (hi - lo + 1)), hi - lo)
    if kind == "lognormal":          # rounded, then clipped to [lo, hi]
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(q))
        return int(min(max(round(v), dist["lo"]), dist["hi"]))
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-q)
    raise ValueError(f"unknown distribution {kind!r}")


def strata(dist: Dict[str, Any], n: int) -> List[float]:
    """The ``n`` stratified values of one block, in quantile order."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def zipf_strata(count: int, s: float, n: int) -> List[int]:
    """``n`` picks among ``count`` items of Zipf popularity ``1/(k+1)^s``,
    stratified like :func:`strata`."""
    w = np.array([1.0 / (k + 1) ** s for k in range(count)])
    cdf = np.cumsum(w / w.sum())
    return [int(min(np.searchsorted(cdf, (i + 0.5) / n), count - 1))
            for i in range(n)]


def arrival_process(name: str) -> Callable[..., List[float]]:
    """The ``gaps`` function of ``arrivals/<name>.py``."""
    path = Path(__file__).resolve().parent / "arrivals" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no arrival process {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_arrivals_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.gaps


def mean_turns(mix: Dict[str, Any]) -> float:
    return float(np.mean(strata(mix["turns"], mix["block"])))


def generate(mix: Dict[str, Any], *, rate: float, seed: int,
             horizon_s: float, vocab: int,
             max_len: Optional[int] = None) -> List[Arrival]:
    """Requests due in ``[0, horizon_s)``, sorted by due time.

    ``rate`` is in requests per second.  Token ids are drawn from
    ``[1, vocab)``.  Raises if a request would not fit ``max_len``."""
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(mix["order_seed"]) if "order_seed" in mix
             else rng)
    block = int(mix["block"])
    session_rate = rate / mean_turns(mix)
    arrivals = dict(mix["arrivals"])
    session_gaps = arrival_process(arrivals.pop("process"))
    prefix = mix.get("prefix")
    pool: List[List[int]] = []
    if prefix is not None and prefix["share"] == "pool":
        lens = [int(v) for v in strata(prefix["len"], prefix["count"])]
        order.shuffle(lens)
        pool = [rng.integers(1, vocab, n).tolist() for n in lens]

    def shuffled(values):
        values = list(values)
        order.shuffle(values)
        return values

    out: List[Dict[str, Any]] = []
    t, session = 0.0, 0
    while t < horizon_s:
        gaps = session_gaps(order, block, 1.0 / session_rate, arrivals)
        turns = shuffled(strata(mix["turns"], block))
        picks = (shuffled(zipf_strata(prefix["count"],
                                      prefix["popularity"]["zipf_s"], block))
                 if pool else [None] * block)
        own = (shuffled(strata(prefix["len"], block))
               if prefix is not None and prefix["share"] == "session"
               else [0] * block)
        for gap, nturns, pick, own_len in zip(gaps, turns, picks, own):
            t += gap
            if t >= horizon_s:
                break
            head = (pool[pick] if pick is not None
                    else rng.integers(1, vocab, int(own_len)).tolist())
            out.append({"due": t, "session": session, "head": head,
                        "turns": int(nturns)})
            session += 1

    # turns: the prompt and output sizes of all requests, stratified over
    # blocks in session order, and think times between a session's turns
    n_req = sum(s["turns"] for s in out)
    sizes = []
    for lo in range(0, n_req, block):
        sizes += list(zip(shuffled(strata(mix["prompt"], block)),
                          shuffled(strata(mix["output"], block))))
    thinks = []
    if "think_s" in mix:
        for lo in range(0, n_req, block):
            thinks += shuffled(strata(mix["think_s"], block))
    reqs, k = [], 0
    for s in out:
        due = s["due"]
        for turn in range(s["turns"]):
            if turn:
                due += thinks[k]
            plen, olen = (int(v) for v in sizes[k])
            k += 1
            if due >= horizon_s:
                continue
            prompt = s["head"] + rng.integers(1, vocab, plen).tolist()
            if max_len is not None and len(prompt) + olen > max_len:
                raise ValueError(
                    f"mix makes a request of {len(prompt)} prompt + {olen} "
                    f"output tokens, over max_len {max_len}")
            reqs.append(Arrival(rid=-1, due=due, prompt=prompt, max_new=olen,
                                session=s["session"],
                                prefix_len=len(s["head"])))
    reqs.sort(key=lambda a: (a.due, a.session))
    for i, a in enumerate(reqs):
        a.rid = i
    return reqs
