"""From a profiler trace to device busy time, op times and idle gaps.

`load` reads the `.xplane.pb` a traced run wrote into a plain record,
{"device": [[op, start_ns, dur_ns], ...], "modules": [[program, start_ns,
dur_ns], ...], "host": [[span, start_ns, dur_ns], ...]}: the XLA ops and
program executions of the device planes, and the host spans the
benchmark annotated.  `reduce` works on that record alone, so it is
tested on a small recorded one.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# ops that only hold other ops: they count toward busy time, not op time
CONTAINER = re.compile(r"^(while|conditional|call)\b")
_NAME = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def short(op: str) -> str:
    """'%copy.95 = bf16[2,3]{1,0} copy(...)' -> 'copy.95 bf16[2,3] copy'."""
    m = _NAME.match(op)
    if not m:
        return op[:120]
    rest = op[m.end():]
    shape = "(tuple)" if rest.startswith("(") else re.split(r"[{ ]", rest)[0]
    code = _OPCODE.search(rest)
    return f"{m.group(1)} {shape} {code.group(1) if code else '?'}"


def load(trace_dir: str, spans: Iterable[str]) -> Dict[str, list]:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    keep = set(spans)
    rec = {"device": [], "modules": [], "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rec["device"] += [[short(e.name), e.start_ns,
                                       e.duration_ns] for e in line.events]
                elif line.name == MODULES_LINE:
                    rec["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rec["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events if e.name in keep]
    return rec


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _span_at(host, t: float) -> str:
    """The innermost annotated host span holding time t."""
    best, best_start = "host_other", None
    for name, s, d in host:
        if s <= t <= s + d and (best_start is None or s >= best_start):
            best, best_start = name, s
    return best


def reduce(rec: Dict[str, list], top: int = 10,
           since: Optional[float] = None) -> dict:
    """Over the device record from `since` (ns; default its first op) to
    its last op: window_s, busy_s (union of op intervals), op_s (seconds
    per leaf op), top_ops, and the `top` longest idle gaps between ops,
    each named by the host span it fell in."""
    dev = [e for e in rec["device"] if since is None or e[1] >= since]
    if not dev:
        return {"busy_s": 0.0, "window_s": 0.0, "op_s": {}, "top_ops": [],
                "gaps": []}
    busy = _union([(s, s + d) for _, s, d in dev])
    op_s: Dict[str, float] = defaultdict(float)
    for name, _, d in dev:
        if not CONTAINER.match(name.split(" ")[-1]):
            op_s[name] += d * 1e-9
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_span_at(rec["host"], (a + b) / 2), (b - a) * 1e-9]
             for a, b in gaps[:top]]
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(e - s for s, e in busy) * 1e-9,
            "window_s": (busy[-1][1] - busy[0][0]) * 1e-9,
            "op_s": dict(op_s), "top_ops": [[k, v] for k, v in top_ops],
            "gaps": named}


def op_seconds(red: dict, pattern: str) -> float:
    """Seconds of every op whose short name matches `pattern` (a regex)."""
    rx = re.compile(pattern)
    return sum(v for k, v in red["op_s"].items() if rx.search(k))


def runs_of(rec: Dict[str, list], pattern: str) -> List[Tuple[float, float]]:
    """(start_ns, end_ns) of each execution of the programs matching
    `pattern`, in order."""
    rx = re.compile(pattern)
    return sorted((s, s + d) for n, s, d in rec["modules"] if rx.search(n))
