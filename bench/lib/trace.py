"""Reduction of a profiler trace to intervals, and of intervals to times.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: device
operations (the ``XLA Ops`` line of each ``/device:`` plane), device
programs (``XLA Modules``) and the benchmark's own host spans (events named
``bench.*``).  Each operation is tagged with the program whose interval
holds it.  Everything after ``load`` works on plain :class:`Ev` lists, so
a recorded trace (``save_json`` / ``load_json``) checks it on the CPU.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Ev:
    name: str
    start: float          # seconds
    end: float
    module: str = ""
    device: int = 0

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: List[Ev]
    modules: List[Ev]
    spans: List[Ev]
    n_devices: int = 1


def _tag_modules(ops: List[Ev], modules: List[Ev]) -> None:
    by_dev: Dict[int, List[Ev]] = {}
    for m in modules:
        by_dev.setdefault(m.device, []).append(m)
    for lst in by_dev.values():
        lst.sort(key=lambda e: e.start)
    starts = {d: [m.start for m in lst] for d, lst in by_dev.items()}
    for op in ops:
        lst = by_dev.get(op.device, [])
        i = bisect.bisect_right(starts.get(op.device, []), op.start) - 1
        if i >= 0 and lst[i].end >= op.start:
            op.module = lst[i].name


def op_name(text: str) -> str:
    """An op event's name is its HLO instruction (``%fusion.6 = bf16[..]
    fusion(...)``); keep ``fusion.6``."""
    m = re.match(r"%?([^\s=]+)", text)
    return m.group(1) if m else text


def strip_id(name: str) -> str:
    """``jit_tick_paged(1234)`` -> ``jit_tick_paged``; ``fusion.12`` ->
    ``fusion``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"\.\d+$", "", name)


#: ops that hold other ops (their time is their children's)
CONTAINERS = ("while", "conditional", "call")


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, spans = [], [], []
    devs = set()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            m = re.search(r"(\d+)$", plane.name)
            dev = int(m.group(1)) if m else 0
            for line in plane.lines:
                dst = (ops if line.name == OPS_LINE else
                       modules if line.name == MODULES_LINE else None)
                if dst is None:
                    continue
                devs.add(dev)
                for e in line.events:
                    s = e.start_ns * 1e-9
                    name = op_name(e.name) if dst is ops else e.name
                    dst.append(Ev(name, s, s + e.duration_ns * 1e-9,
                                  device=dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append(Ev(e.name, s, s + e.duration_ns * 1e-9))
    _tag_modules(ops, modules)
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans, max(1, len(devs)))


def describe(trace_dir: str, top: int = 40) -> list:
    """Every plane and line of a raw trace with its most frequent event
    names: what to read by hand before writing patterns against it."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names: Dict[str, int] = {}
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
            common = sorted(names.items(), key=lambda x: -x[1])[:top]
            out.append({"plane": plane.name, "line": line.name,
                        "events": sum(names.values()), "names": common})
    return out


def save_json(tr: Trace, path: str) -> None:
    doc = {k: [dataclasses.asdict(e) for e in getattr(tr, k)]
           for k in ("ops", "modules", "spans")}
    doc["n_devices"] = tr.n_devices
    with open(path, "w") as f:
        json.dump(doc, f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        doc = json.load(f)
    return Trace(*([Ev(**e) for e in doc[k]]
                   for k in ("ops", "modules", "spans")),
                 n_devices=doc.get("n_devices", 1))


# ------------------------------------------------------------- intervals

def union(ivs: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: Sequence[Tuple[float, float]], a: float, b: float
            ) -> float:
    """Length of ``[a, b]`` that the merged intervals cover."""
    tot = 0.0
    i = bisect.bisect_right([x[1] for x in merged], a)
    for s, e in merged[i:]:
        if s >= b:
            break
        tot += max(0.0, min(e, b) - max(s, a))
    return tot


def busy(tr: Trace, a: float, b: float) -> float:
    """Seconds inside ``[a, b]`` in which an operation ran, averaged over
    the devices."""
    tot = 0.0
    for dev in sorted({e.device for e in tr.ops}) or [0]:
        merged = union((e.start, e.end) for e in tr.ops if e.device == dev)
        tot += covered(merged, a, b)
    return tot / max(1, tr.n_devices)


def select(evs: Iterable[Ev], name_re: str = "", module_re: str = "",
           a: float = float("-inf"), b: float = float("inf")) -> List[Ev]:
    """Events whose name and program match the patterns and that start
    inside ``[a, b]``."""
    nr = re.compile(name_re) if name_re else None
    mr = re.compile(module_re) if module_re else None
    return [e for e in evs
            if a <= e.start <= b
            and (nr is None or nr.search(e.name))
            and (mr is None or mr.search(e.module))]


def total(evs: Iterable[Ev]) -> float:
    return sum(e.dur for e in evs)


def idle_gaps(tr: Trace, a: float, b: float, top: int = 10
              ) -> List[Tuple[str, float]]:
    """The longest device-idle gaps inside ``[a, b]`` (first device), each
    named by the host span that holds its midpoint."""
    dev0 = min((e.device for e in tr.ops), default=0)
    merged = union((e.start, e.end) for e in tr.ops if e.device == dev0)
    gaps, cur = [], a
    for s, e in merged:
        if e <= a:
            continue
        if s >= b:
            break
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < b:
        gaps.append((cur, b))
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        name = "outside_spans"
        for sp in tr.spans:
            if sp.start <= mid <= sp.end:
                name = sp.name
        out.append((name, e - s))
    out.sort(key=lambda x: -x[1])
    return out[:top]


def top_ops(tr: Trace, a: float, b: float, top: int = 10
            ) -> List[Tuple[str, float]]:
    """Device time by operation (program/op, numeric ids stripped, loops
    left out as their bodies are counted), largest first, averaged over
    devices."""
    acc: Dict[str, float] = {}
    for e in tr.ops:
        if a <= e.start <= b and strip_id(e.name) not in CONTAINERS:
            k = f"{strip_id(e.module)}/{strip_id(e.name)}"
            acc[k] = acc.get(k, 0.0) + e.dur
    items = sorted(acc.items(), key=lambda x: -x[1])[:top]
    return [(k, v / max(1, tr.n_devices)) for k, v in items]
