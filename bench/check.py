"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the engine is freed, a sample of the
requests the run served — drawn from the seed, and always with the
longest of them — is run through ``reference.py`` at float32, prompt and
served tokens together.  A request still running when the run ends is
sampled with the tokens drained so far: a drained token is delivered
and final, and a cell whose answers take longer than its window would
otherwise check only its shortest requests.  For every served token the
reference says how far its logit lies below the best one at that
position (0 where the served token is the reference's argmax).  Greedy decoding in bfloat16
picks a near-tie's other side now and then, so the gap is small but not
0; a token altered where it is produced, a cache that lost what was
written, or arithmetic in a lower precision open it wider.

The numbers compared and their limits are in the cell's file, under
``check``: ``max_gap``, the widest gap over the sample, and
``mean_gap``, the mean over every served token of the sample.  How each
limit was set is in ``PERF.md``.

With ``control``, the fp8 control is put in the program's place: at the
same positions, the gap of the token the control puts first is judged
against the same limits, and ``correct`` is the control's verdict (it
has to come out false).  The program's own readings are still printed.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np


def sample(served: List[Any], n: int, seed: int) -> List[Any]:
    """``n`` requests drawn from the seed, the longest first."""
    if not served:
        return []
    longest = max(served, key=lambda s: (len(s.req.prompt)
                                         + len(s.req.out_tokens),
                                         s.arrival.rid))
    rest = sorted((s for s in served if s is not longest),
                  key=lambda s: s.arrival.rid)
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check_served(weights: Dict[str, Any], cell: Any, served: List[Any],
                 seed: int, control: bool = False) -> Dict[str, Any]:
    from reference import served_gaps
    conf = cell.settings["check"]
    model = cell.config["model"]
    t = time.perf_counter()
    picked = sample(served, conf["requests"], seed)
    gaps, ctl = [], []
    for s in picked:
        g = served_gaps(weights, model, s.req.prompt, s.req.out_tokens,
                        control=control)
        gaps.append(g["gap"])
        if control:
            ctl.append(g["control_gap"])
    readings: Dict[str, Any] = {"requests": len(picked),
                                "tokens": int(sum(len(g) for g in gaps))}
    if gaps:
        allg = np.concatenate(gaps)
        readings.update(max_gap=float(allg.max()),
                        mean_gap=float(allg.mean()),
                        argmax_share=float(np.mean(allg == 0.0)))
    if ctl:
        allc = np.concatenate(ctl)
        readings.update(control_max_gap=float(allc.max()),
                        control_mean_gap=float(allc.mean()),
                        control_argmax_share=float(np.mean(allc == 0.0)))
    print(f"check: {readings} in {time.perf_counter() - t:.3f} s",
          flush=True)
    judged = ({k[len("control_"):]: v for k, v in readings.items()
               if k.startswith("control_")} if control else readings)
    numbers = {"sampled_tokens": {"value": readings["tokens"],
                                  "limit": conf["min_tokens"]}}
    for name, limit in conf["limits"].items():
        numbers[name] = {"value": judged.get(name), "limit": limit}
    correct = readings["tokens"] >= conf["min_tokens"] and all(
        v["value"] is not None and v["value"] <= v["limit"]
        for k, v in numbers.items() if k != "sampled_tokens")
    return {"correct": bool(correct), "numbers": numbers,
            "readings": readings}
