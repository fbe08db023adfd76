"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

The trace is first flattened into plain events (``load``), so the
arithmetic below runs the same on a trace read from the chip and on the
small recorded trace the tests keep:

    {"plane": str, "line": str, "name": str, "ts": ns, "dur": ns}

The chip names each device op by its whole HLO text.  Device events are
those of planes named ``/device:TPU:<n>`` on their op line; host events
are the annotations of the host planes (``TraceAnnotation`` spans of the
harness and of the program's dispatch labels).
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops",)
Interval = Tuple[float, float]


def load(profile_dir: str) -> List[dict]:
    """Flatten the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        host = plane.name.startswith("/host:")
        if not (dev or host):
            continue
        for line in plane.lines:
            if dev and line.name not in OP_LINES:
                continue
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "ts": float(ev.start_ns),
                            "dur": float(ev.duration_ns)})
    return out


def device_events(events: Iterable[dict]) -> List[dict]:
    return [e for e in events if DEVICE_PLANE.match(e["plane"])
            and e["line"] in OP_LINES]


def host_events(events: Iterable[dict]) -> List[dict]:
    return [e for e in events if e["plane"].startswith("/host:")]


def clip(iv: Interval, t0: float, t1: float) -> Optional[Interval]:
    a, b = max(iv[0], t0), min(iv[1], t1)
    return (a, b) if b > a else None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals (sorted by start)."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(events: Iterable[dict], t0: float, t1: float) -> List[Interval]:
    """Union of device-op intervals inside ``[t0, t1)``, per chip merged
    (one chip per run here)."""
    ivs = []
    for e in device_events(events):
        c = clip((e["ts"], e["ts"] + e["dur"]), t0, t1)
        if c:
            ivs.append(c)
    return union(ivs)


def total(ivs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in ivs)


_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.clone(?:\.\d+)?)?"
                    r"(?: =|$)")


def instruction(e: dict) -> str:
    """The op's HLO instruction name without its numeric suffix: the
    chip prints each op as ``%<name>.<n> = <type> <opcode>(...)``, and a
    Pallas kernel's instruction is named after its jitted wrapper
    (``gptq_matmul``, ``paged_attention_quant``, ...)."""
    m = _INSTR.match(e["name"])
    return m.group(1) if m else e["name"]


def matches(e: dict, kernels: Sequence[str]) -> bool:
    return instruction(e) in kernels


def kernel_ns(events: Iterable[dict], kernels: Sequence[str], t0: float,
              t1: float) -> float:
    """Device time of the ops that name one of ``kernels`` (clipped to
    the window)."""
    s = 0.0
    for e in device_events(events):
        if matches(e, kernels):
            c = clip((e["ts"], e["ts"] + e["dur"]), t0, t1)
            if c:
                s += c[1] - c[0]
    return s


CONTAINERS = ("while", "conditional", "call")


def top_ops(events: Iterable[dict], t0: float, t1: float,
            n: int = 10) -> List[list]:
    """The device ops that took most time in the window, as
    ``[name, seconds]``; control-flow ops, whose events span the ops of
    their bodies, are left out."""
    acc: Dict[str, float] = defaultdict(float)
    for e in device_events(events):
        if instruction(e) in CONTAINERS:
            continue
        c = clip((e["ts"], e["ts"] + e["dur"]), t0, t1)
        if c:
            acc[instruction(e)] += c[1] - c[0]
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def gaps(busy_ivs: Sequence[Interval], t0: float, t1: float
         ) -> List[Interval]:
    """The idle intervals of the window between busy intervals."""
    out, t = [], t0
    for a, b in busy_ivs:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t1 > t:
        out.append((t, t1))
    return out


def idle_by_host(events: Sequence[dict], busy_ivs: Sequence[Interval],
                 t0: float, t1: float, labels: Sequence[str],
                 n: int = 10) -> List[list]:
    """Idle device time labelled with what the host was doing: each gap
    goes to the innermost host annotation (of those whose name starts
    with one of ``labels``) covering its midpoint; returns
    ``[label, seconds]`` summed per label.  A sweep over the midpoints in
    time order with a heap of the open annotations keyed by their end."""
    host = sorted((e for e in host_events(events)
                   if any(e["name"].startswith(lb) for lb in labels)),
                  key=lambda e: e["ts"])
    acc: Dict[str, float] = defaultdict(float)
    open_: List[tuple] = []
    i = 0
    for a, b in gaps(busy_ivs, t0, t1):
        t = (a + b) / 2
        while i < len(host) and host[i]["ts"] <= t:
            e = host[i]
            heapq.heappush(open_, (e["ts"] + e["dur"], e["dur"], i))
            i += 1
        while open_ and open_[0][0] <= t:
            heapq.heappop(open_)
        label = (host[min(open_, key=lambda x: x[1])[2]]["name"]
                 if open_ else "no host span")
        acc[label] += b - a
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def host_window(events: Sequence[dict], name: str) -> Optional[Interval]:
    """The extent of the host annotation ``name`` (the traced window)."""
    for e in host_events(events):
        if e["name"] == name:
            return e["ts"], e["ts"] + e["dur"]
    return None


def count(events: Sequence[dict], name: str, t0: float, t1: float) -> int:
    """Host annotations called ``name`` that start inside the window."""
    return sum(1 for e in host_events(events)
               if e["name"] == name and t0 <= e["ts"] < t1)
