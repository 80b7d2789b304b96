"""A profiler trace, reduced to what the per-layer readers need.

``Trace`` holds, for each chip, the operations the device ran (the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane of the profiler's
xplane), the harness's own host spans (``chipbench.*`` annotations), and
the traced window: the ``chipbench.window`` span. Times are nanoseconds on
the profiler's common clock.

``Trace.save``/``Trace.load`` keep the same content as JSON, which is how
the recorded trace the tests read is kept.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Callable, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW = "chipbench.window"


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: List[List[Op]]                 # per chip, ordered by start
    spans: List[Op]                         # host chipbench.* spans
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, match: Callable[[Op], bool]) -> List[List[Op]]:
        """Per chip, the operations ``match`` accepts, inside the window."""
        lo, hi = self.window
        return [[o for o in dev if match(o) and o.end > lo and o.start < hi]
                for dev in self.devices]

    def busy_s(self) -> List[float]:
        """Per chip, seconds in the window during which any op ran."""
        return [union_ns(dev, self.window) * 1e-9 for dev in self.devices]

    def save(self, path: str) -> None:
        data = {"window": list(self.window),
                "spans": [dataclasses.astuple(s) for s in self.spans],
                "devices": [[dataclasses.astuple(o) for o in dev]
                            for dev in self.devices]}
        with gzip.open(path, "wt") as f:
            json.dump(data, f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        return cls(devices=[[Op(*o) for o in dev] for dev in data["devices"]],
                   spans=[Op(*s) for s in data["spans"]],
                   window=tuple(data["window"]))


def union_ns(ops: Iterable[Op], window: Tuple[float, float]) -> float:
    """Length of the union of the ops' intervals, clipped to the window."""
    lo, hi = window
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(o.start, lo), min(o.end, hi)) for o in ops):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(ops: List[Op], window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The intervals of the window in which no op of ``ops`` ran."""
    lo, hi = window
    gaps, edge = [], lo
    for o in sorted(ops, key=lambda o: o.start):
        if o.start > edge:
            gaps.append((edge, min(o.start, hi)))
        edge = max(edge, o.end)
        if edge >= hi:
            break
    if edge < hi:
        gaps.append((edge, hi))
    return [(s, e) for s, e in gaps if e > s]


def from_profile_dir(path: str) -> Trace:
    """Read the one ``*.xplane.pb`` the profiler wrote under ``path``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {path}: {files}")
    return from_profile(ProfileData.from_file(files[0]))


def from_profile(pd) -> Trace:
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend(Op(ev.name, ev.start_ns, ev.end_ns)
                           for ev in line.events)
            devices[int(m.group(1))] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Op(ev.name, ev.start_ns, ev.end_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(windows)}")
    return Trace(devices=[devices[k] for k in sorted(devices)],
                 spans=sorted(spans, key=lambda s: s.start),
                 window=(windows[0].start, windows[0].end))


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def self_times(ops: List[Op], window: Tuple[float, float]) -> dict:
    """Seconds per op name inside the window, less the time of the ops
    nested inside it on the same line (a while loop's body ops)."""
    lo, hi = window
    out: dict = {}
    stack: list = []                      # [end, name] of enclosing ops
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        s, e = max(o.start, lo), min(o.end, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            stack.pop()
        name = short_name(o.name)
        if stack:
            out[stack[-1][1]] -= (e - s) * 1e-9
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
        stack.append([e, name])
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most self time, averaged over chips, and
    the longest idle gaps of chip 0, each named by the innermost harness
    span that covers its middle."""
    n = max(1, len(trace.devices))
    by_name: dict = {}
    for dev in trace.devices:
        for k, v in self_times(dev, trace.window).items():
            by_name[k] = by_name.get(k, 0.0) + v / n
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(trace.devices[0], trace.window) if trace.devices else []
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        cover = [sp for sp in trace.spans if sp.start <= mid <= sp.end]
        label = (min(cover, key=lambda sp: sp.dur).name if cover
                 else "outside harness spans")
        named.append([label, (e - s) * 1e-9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
