"""From a profiler trace (.xplane.pb) to numbers. Kept with the benchmark so
that every PR computes the same number in the same way.

A trace is reduced to plain tuples first (`load`), so that the arithmetic
below works on, and is tested on, lists of (name, start_ns, duration_ns):

  busy        union of the intervals in which an operation runs on a chip
  collective  union of all-reduce / all-gather / reduce-scatter /
              collective-permute / all-to-all events, and the part of it
              during which no other operation runs on that chip (exposed)
  mosaic      sum of the Pallas/Mosaic custom calls' durations
  idle gaps   the complement of busy inside the window, each gap named by
              the benchmark's own host span (`bench.*` TraceAnnotation)
              that covers its middle
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"            # one event per executed HLO op
ASYNC_LINE = "Async XLA Ops"    # one event per async pair, start to done
HOST_SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast",
               "ragged-all-to-all")
CONTAINERS = ("while", "conditional", "call")   # their bodies' ops follow

# On a TPU an op event's name is the HLO instruction's whole text:
#   %fusion.2 = bf16[16,1024]{1,0:T(8,128)(2,1)} fusion(bf16[...] %p), kind=..
# so an op is classified by its OPCODE (the first lower-case word followed
# by "(" after the "="; layouts only hold T( and S(), never by searching
# the text, which also names the operands.
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"(?<![A-Za-z0-9_])([a-z][a-z0-9\-]*)\(")


def parse_op(text: str) -> dict:
    """{"name", "opcode", "type"} of a device event's name. A name that is
    not HLO text (older profilers, hand-made events) is taken as
    `<opcode>.<n>`."""
    m = _HLO.match(text)
    om = _OPCODE.search(m["rest"]) if m else None
    if not om:
        return {"name": text.lstrip("%"), "opcode": op_family(text),
                "type": ""}
    return {"name": m["name"], "opcode": om.group(1),
            "type": m["rest"][:om.start()].strip()}


def is_collective(op: dict) -> bool:
    base = re.sub(r"-(start|done)$", "", op["opcode"])
    return base in COLLECTIVES


def is_mosaic(op: dict, text: str) -> bool:
    """A Pallas/Mosaic kernel: custom-call with target tpu_custom_call."""
    if op["opcode"] == "tpu_custom_call":
        return True
    return op["opcode"] == "custom-call" and (
        "custom_call_target" not in text or "tpu_custom_call" in text)


# ------------------------------------------------------------------ loading
def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> dict:
    """{"devices": {chip: {"ops": [(name, start_ns, dur_ns), ...],
                           "async": [...]}},
        "host": [(name, start_ns, dur_ns), ...]   # bench.* spans only
        "lines": {plane: {line: n_events}}}        # what the file held"""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host, lines = {}, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines[plane.name] = {}
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            lines[plane.name][line.name] = len(evs)
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                chip = devices.setdefault(int(m.group(1)),
                                          {"ops": [], "async": []})
                chip["ops" if line.name == OPS_LINE else "async"] = evs
            elif not m:
                host.extend(e for e in evs
                            if e[0].startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "host": host, "lines": lines}


# --------------------------------------------------------------- intervals
def merge(intervals) -> list:
    """Sorted disjoint [start, end) covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged) -> float:
    return float(sum(e - s for s, e in merged))


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(merged, lo: float, hi: float) -> list:
    return intersect(merged, [[lo, hi]])


def _iv(events):
    return [(s, s + d) for _, s, d in events]


# ----------------------------------------------------------------- metrics
def window_of(trace: dict) -> tuple:
    """(start_ns, end_ns): the `bench.window` host span where the trace has
    it, else the extent of the device operations."""
    for name, s, d in trace["host"]:
        if name == HOST_SPAN_PREFIX + "window":
            return s, s + d
    evs = [e for d in trace["devices"].values() for e in d["ops"]]
    return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def op_family(name: str) -> str:
    """`fusion.123` -> `fusion`; `%all-reduce-start.7` -> `all-reduce-start`:
    instances of one HLO op family add up under one name."""
    return re.sub(r"[.:]\d+$", "", name.lstrip("%"))


def reduce(trace: dict, top: int = 10) -> dict:
    """Every device number the benchmark reports, from one loaded trace.
    Times in seconds, averaged over the chips that have an ops line."""
    if not any(d["ops"] for d in trace["devices"].values()):
        return {}
    lo, hi = window_of(trace)
    window_s = (hi - lo) / 1e9

    def inside(events):
        return [e for e in events if e[1] + e[2] > lo and e[1] < hi]

    per_chip = []
    for chip, lines in sorted(trace["devices"].items()):
        events = [(e, parse_op(e[0])) for e in inside(lines["ops"])]
        # a collective's time on the wire: the op itself where it is
        # synchronous, the start-to-done pair where it is asynchronous
        coll = [e for e, op in events if is_collective(op)] + [
            e for e in inside(lines.get("async", []))
            if is_collective(parse_op(e[0]))]
        other = [e for e, op in events if not is_collective(op)
                 and op["opcode"] not in CONTAINERS]
        mosaic = [e for e, op in events if is_mosaic(op, e[0])]
        busy = clip(merge(_iv([e for e, _ in events])), lo, hi)
        coll_u = clip(merge(_iv(coll)), lo, hi)
        other_u = clip(merge(_iv(other)), lo, hi)
        per_chip.append({
            "chip": chip, "busy": busy,
            "busy_s": total(busy) / 1e9,
            "collective_s": total(coll_u) / 1e9,
            "collective_exposed_s":
                (total(coll_u) - total(intersect(coll_u, other_u))) / 1e9,
            "mosaic_s": sum(d for _, _, d in mosaic) / 1e9,
            "mosaic_calls": len(mosaic),
            "events": events,
        })
    n = len(per_chip)
    out = {"window_s": window_s, "chips": n,
           "window_ns": (lo, hi)}
    for key in ("busy_s", "collective_s", "collective_exposed_s",
                "mosaic_s"):
        out[key] = sum(c[key] for c in per_chip) / n
    out["mosaic_calls"] = sum(c["mosaic_calls"] for c in per_chip) / n
    out["busy_by_chip"] = {c["chip"]: c["busy"] for c in per_chip}

    # the operations that took most time, instances of one op family with
    # one result type added up (containers left out: their bodies follow)
    fam = defaultdict(lambda: [0.0, 0])
    for c in per_chip:
        for (_, _, d), op in c["events"]:
            if op["opcode"] in CONTAINERS:
                continue
            label = op["opcode"] if op["opcode"] == op_family(op["name"]) \
                else f"{op['opcode']}:{op_family(op['name'])}"
            f = fam[(label + " " + op["type"][:40]).strip()]
            f[0] += d / 1e9 / n
            f[1] += 1
    ops = sorted(fam.items(), key=lambda kv: -kv[1][0])[:top]
    out["breakdown"] = {
        "device_ops": [[f"{name} x{round(cnt / n)}", secs]
                       for name, (secs, cnt) in ops],
        "idle_gaps": idle_gaps(per_chip[0]["busy"], trace["host"], lo, hi,
                               top),
    }
    return out


def idle_gaps(busy, host_spans, lo: float, hi: float, top: int = 10) -> list:
    """Idle seconds on one chip, summed by the host span the gap's middle
    falls in (the shortest covering `bench.*` span), and the longest
    single gap; at most `top` entries."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(s, s + d, name) for name, s, d in host_spans
             if name != HOST_SPAN_PREFIX + "window"]
    by = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(b - a, name) for a, b, name in spans if a <= mid < b]
        by["host_in_" + min(cover)[1] if cover
           else "host_outside_bench_spans"] += (e - s) / 1e9
    out = sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])
    out = out[:top - 1]
    if gaps:
        out.append(["longest_single_gap",
                    max(e - s for s, e in gaps) / 1e9])
    return out
