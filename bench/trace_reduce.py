"""Reduce a profiler trace (``.xplane.pb``) of a window to the numbers the
per-layer metrics read.

The four routed programs of ``ShardedEngine`` are all jitted from a local
function named ``fn``, so the device trace cannot tell them apart by
program name.  Each program run (an event of a device's "XLA Modules"
line) is classified by the operations that ran inside it:

* the slab-update kernel: the update program;
* the fused CDF gather or the probe kernel: the query program;
* a sort or top-k without those kernels: the top-n program;
* none of these: the maintain program (when its decay branch runs it
  holds the odd-even kernel, never the slab-update one).

Programs with a name of their own keep it (``jit__counter_stack``, ...).
Busy time is the union of the intervals in which an operation ran on a
device; kernel and collective times are sums of their operations'
durations.  Times are per device, averaged over the devices traced.
"""

from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

KERNELS = ("oddeven_pallas", "slab_update_pallas", "probe_find_pallas",
           "cdf_query_fused_pallas", "cdf_query_pallas", "draft_walk_pallas")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "collective-permute",
               "reduce-scatter")
HOST_CALLS = ("bench.observe", "bench.topn", "bench.query")
_SUFFIX = re.compile(r"[.:]\d+$")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def instruction(op: str) -> str:
    """HLO instruction name of an op event (the trace names an op by its
    whole HLO text, ``%fusion.8 = s32[...] fusion(...)``)."""
    m = _INSTRUCTION.match(op)
    return m.group(1) if m else op


def base_name(name: str) -> str:
    return _SUFFIX.sub("", instruction(name))


def _device_planes(pd):
    pat = re.compile(r"^/device:TPU:\d+$")
    return [p for p in pd.planes if pat.match(p.name)]


def _lines(plane):
    by = {ln.name: ln for ln in plane.lines}
    return by.get("XLA Modules"), by.get("XLA Ops")


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events] if line is not None else []


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def classify(module: str, ops) -> str:
    names = {base_name(o) for o in ops}
    if not module.startswith("jit_fn"):
        return module.split("(")[0]
    if "slab_update_pallas" in names:
        return "update"
    if names & {"cdf_query_fused_pallas", "cdf_query_pallas",
                 "probe_find_pallas"}:
        return "query"
    if any(n.startswith("sort") or "topk" in n.lower() for n in names):
        return "topn"
    return "maintain"


def _host_calls(pd):
    calls = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_CALLS:
                    calls.append((e.name, float(e.start_ns),
                                  float(e.start_ns + e.duration_ns)))
    return calls


def _gap_label(mid, calls):
    active = {n for n, s, e in calls if s <= mid <= e}
    for n in HOST_CALLS:
        if n in active:
            return n.replace("bench.", "host in ")
    return "host with no call in flight"


def reduce_profile(pd, window_s=None) -> dict:
    planes = _device_planes(pd)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    nd = len(planes)
    programs = collections.defaultdict(lambda: [0.0, 0.0])
    kernels = collections.defaultdict(lambda: [0.0, 0.0])
    collectives = collections.defaultdict(float)
    op_time = collections.defaultdict(float)
    busy_total, lo, hi = 0.0, None, None
    first_busy = None
    for plane in planes:
        mods, ops_line = _lines(plane)
        ops = _events(ops_line)
        modules = _events(mods)
        ops.sort(key=lambda x: x[1])
        starts = [o[1] for o in ops]
        for name, s, e in modules:
            i = bisect.bisect_left(starts, s)
            j = bisect.bisect_right(starts, e)
            inside = ops[i:j]
            kind = classify(name, [o[0] for o in inside])
            programs[kind][0] += 1
            programs[kind][1] += (e - s) * 1e-9
            for oname, os_, oe in inside:
                b = base_name(oname)
                if any(b.startswith(c) for c in COLLECTIVES):
                    collectives[kind] += (oe - os_) * 1e-9
        for oname, s, e in ops:
            b = base_name(oname)
            if b in KERNELS:
                kernels[b][0] += 1
                kernels[b][1] += (e - s) * 1e-9
            op_time[oname[:120]] += (e - s) * 1e-9
        spans = _union([(s, e) for _, s, e in (ops or modules)])
        busy_total += sum(e - s for s, e in spans) * 1e-9
        if spans:
            lo = spans[0][0] if lo is None else min(lo, spans[0][0])
            hi = spans[-1][1] if hi is None else max(hi, spans[-1][1])
        if first_busy is None:
            first_busy = spans
    extent = ((hi - lo) * 1e-9) if lo is not None else 0.0
    window = float(window_s) if window_s else extent
    calls = _host_calls(pd)
    gaps = collections.defaultdict(float)
    spans = first_busy or []
    for (s0, e0), (s1, _) in zip(spans, spans[1:]):
        gaps[_gap_label((e0 + s1) / 2, calls)] += (s1 - e0) * 1e-9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": nd,
        "busy_s": busy_total / nd,
        "window_s": window,
        "programs": {k: {"runs": v[0] / nd, "device_s": v[1] / nd}
                     for k, v in programs.items()},
        "kernels": {k: {"runs": v[0] / nd, "device_s": v[1] / nd}
                    for k, v in kernels.items()},
        "collectives": {k: v / nd for k, v in collectives.items()},
        "breakdown": {
            "device_ops": [[k, v / nd] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:10]]},
    }


def reduce_file(path, window_s=None) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), window_s)


def reduce_dir(directory, window_s=None) -> dict:
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(files[-1], window_s)
