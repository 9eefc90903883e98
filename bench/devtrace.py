"""Reduce a JAX profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.  Its
device planes (``/device:TPU:<n>``) carry an ``XLA Ops`` line: one
event per HLO op run on the chip, with a start and a duration in
nanoseconds on the same clock as the host planes.  The window is the
host span the harness opens and closes around the measured steps
(``bench.trace``); every device interval is clipped to it.

* ``busy_s``: union of the op intervals inside the window, averaged
  over the chips traced;
* ``time_of(match)``: summed clipped duration of the ops ``match``
  accepts (a kernel), averaged over the chips;
* ``breakdown()``: the ten device ops that took most time (grouped by
  op kind and result shape, loops that hold other ops left out) and the
  ten longest kinds of idle gap, named by the innermost host span that
  covers each gap's middle.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from stats import union_length

CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float

    @property
    def label(self) -> str:
        return op_label(self.name)


@dataclass
class DeviceTrace:
    window: Tuple[float, float]
    ops: List[List[Op]]                     # per chip
    host: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clipped(self, ops: List[Op]) -> List[Tuple[float, float, Op]]:
        lo, hi = self.window
        out = []
        for o in ops:
            s, e = max(o.start_ns, lo), min(o.end_ns, hi)
            if e > s:
                out.append((s, e, o))
        return out

    @property
    def busy_s(self) -> float:
        per_chip = [union_length([(s, e) for s, e, _ in self._clipped(ops)])
                    for ops in self.ops]
        return sum(per_chip) / len(per_chip) / 1e9

    def time_of(self, match: Callable[[Op], bool]) -> float:
        per_chip = [sum(e - s for s, e, o in self._clipped(ops) if match(o))
                    for ops in self.ops]
        return sum(per_chip) / len(per_chip) / 1e9

    def idle_gaps(self) -> List[Tuple[float, float]]:
        lo, hi = self.window
        spans = sorted((s, e) for s, e, _ in self._clipped(self.ops[0]))
        gaps, cur = [], lo
        for s, e in spans:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        return gaps

    def host_at(self, t: float) -> str:
        """The innermost host span covering ``t``."""
        best: Optional[Tuple[float, str]] = None
        for s, e, name in self.host:
            if s <= t <= e and (best is None or e - s < best[0]):
                best = (e - s, name)
        return best[1] if best else "no host span"

    def breakdown(self) -> Dict[str, List[List]]:
        ops: Dict[str, float] = {}
        for s, e, o in self._clipped(self.ops[0]):
            if o.label.split(" ")[0] not in CONTAINERS:
                ops[o.label] = ops.get(o.label, 0.0) + (e - s) / 1e9
        gaps: Dict[str, float] = {}
        for s, e in self.idle_gaps():
            k = self.host_at((s + e) / 2)
            gaps[k] = gaps.get(k, 0.0) + (e - s) / 1e9
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


_HLO = re.compile(r"%?([A-Za-z_\-]+?)(?:\.\d+)? = (\S+?)(?:\{[^}]*\})? ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(name: str) -> str:
    """``"%fusion.12 = bf16[32,8192]{1,0} fusion(...)"`` -> ``"fusion
    bf16[32,8192]"``; a custom call is named by its target."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    kind, shape = m.group(1), m.group(2)
    t = _TARGET.search(name)
    if t:
        kind = f"{kind}:{t.group(1)}"
    return f"{kind} {shape}"[:120]


def load(path: Path, window_span: str) -> DeviceTrace:
    import jax
    files = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {path}")
    pd = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    chips: List[List[Op]] = []
    host: List[Tuple[float, float, str]] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips.append([Op(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                  for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == window_span:
                        window = span[:2]
                    else:
                        host.append(span)
    if window is None:
        raise ValueError(f"trace holds no {window_span!r} host span")
    if not chips:
        raise ValueError("trace holds no device op line")
    return DeviceTrace(window=window, ops=chips, host=host)


def reduce_trace(path: Path, window_span: str) -> DeviceTrace:
    t = load(path, window_span)
    if t.busy_s <= 0:
        raise ValueError("no operation ran on the device in the window")
    return t
