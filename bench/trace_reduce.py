"""From a JAX profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``load`` keeps, per TPU device, the executions of each compiled program
(the ``XLA Modules`` line) and of each operation (``XLA Ops``), and the
host spans that name what the benchmark or the program was doing
(``bench:*``, ``phase:*``). ``reduce`` clips all of it to the measured
window (the ``bench:window`` span) and gives:

- ``busy_s``: the union of operation intervals on a device (loops and
  calls left out: they enclose the operations), averaged over
  devices; ``window_s`` the window's length;
- ``module_s(pattern)``: the device time of the programs whose name
  matches, averaged over devices;
- ``collective_exposed_s``: the time a collective runs on a device while
  no other operation does, averaged over devices;
- ``top_ops``: the ten operations that took most device time;
- ``idle_gaps``: device idle time, by the innermost host span open when
  each gap began, the ten largest.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # name, start s, end s

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter")
HOST_SPAN = re.compile(r"^(bench|phase):")
#: control flow that encloses other operations: not work of its own
CONTAINER = re.compile(r"^%?(while|conditional|call)\b")
WINDOW = "bench:window"


@dataclasses.dataclass
class Raw:
    """device id -> {"modules": [...], "ops": [...]}, and host spans."""

    devices: Dict[int, Dict[str, List[Event]]]
    host: List[Event]


def load(path: str) -> Raw:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)),
                                   {"modules": [], "ops": []})
            for line in plane.lines:
                kind = {"XLA Modules": "modules",
                        "XLA Ops": "ops"}.get(line.name)
                if kind == "modules":
                    d[kind].extend((e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                                   for e in line.events)
                elif kind == "ops":
                    # "%fusion.12 = f32[...] fusion(...)" -> "%fusion.12"
                    d[kind].extend(
                        (e.name.split(" = ", 1)[0], e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                        if not CONTAINER.match(e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if HOST_SPAN.match(e.name))
    return Raw(devices, host)


def union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(iv: Sequence[Interval]) -> float:
    return sum(e - s for s, e in iv)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (merged) intervals ``a`` that no interval of the
    (merged) ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _short(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    modules: Dict[int, List[Event]]
    collective_exposed_s: float
    collective_s: float
    top_ops: List[List]
    idle_gaps: List[List]

    def module_s(self, pattern: str) -> Optional[float]:
        """Device seconds of the programs matching ``pattern``, averaged
        over devices; None where no such program ran."""
        rx = re.compile(pattern)
        per = [sum(e - s for n, s, e in mods if rx.search(n))
               for mods in self.modules.values()]
        if not any(per):
            return None
        return sum(per) / len(per)

    def module_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return max((sum(1 for n, _, _ in mods if rx.search(n))
                    for mods in self.modules.values()), default=0)


def _innermost(host: List[Event], starts: List[float], t: float) -> str:
    """Name of the latest-starting host span open at ``t``."""
    i = bisect.bisect_right(starts, t)
    best = None
    for n, s, e in reversed(host[:i]):
        if e > t:
            best = n
            break
        if t - s > 600:
            break
    return best or "no span"


def reduce(raw: Raw, window: Optional[Interval] = None) -> Summary:
    host = sorted(raw.host, key=lambda ev: ev[1])
    if window is None:
        spans = [(s, e) for n, s, e in host if n == WINDOW]
        if spans:
            window = max(spans, key=lambda iv: iv[1] - iv[0])
        else:
            ops = [ev for d in raw.devices.values() for ev in d["ops"]]
            window = (min(s for _, s, _ in ops), max(e for _, _, e in ops))
    lo, hi = window
    starts = [s for _, s, _ in host]
    busy, exposed, coll = [], [], []
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    modules = {}
    for dev, d in sorted(raw.devices.items()):
        ops = clip(d["ops"], lo, hi)
        mods = sorted(clip(d["modules"], lo, hi), key=lambda ev: ev[1])
        modules[dev] = mods
        mstarts = [s for _, s, _ in mods]
        b = union([(s, e) for _, s, e in ops])
        busy.append(length(b))
        c = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n)])
        other = union([(s, e) for n, s, e in ops
                       if not COLLECTIVE.search(n)])
        coll.append(length(c))
        exposed.append(length(subtract(c, other)))
        for n, s, e in ops:
            i = bisect.bisect_right(mstarts, s) - 1
            mod = _short(mods[i][0]) if i >= 0 and mods[i][2] >= s \
                else "?"
            op_time[f"{mod}/{n}"] += e - s
        for s, e in subtract([(lo, hi)], b):
            gap_time[_innermost(host, starts, s)] += e - s
    n = max(len(raw.devices), 1)

    def top(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return Summary(window_s=hi - lo, busy_s=sum(busy) / n, n_devices=n,
                   modules=modules, collective_exposed_s=sum(exposed) / n,
                   collective_s=sum(coll) / n, top_ops=top(op_time),
                   idle_gaps=top(gap_time))
