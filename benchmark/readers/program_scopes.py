"""Device time by what the PROGRAM names: `jax.named_scope`s (they reach each
op's `tf_op` stat in the trace's event metadata, inside `transpose(jvp(`
too) and the Pallas kernels' `name` (the Mosaic call's instruction name).

A metric's file says what it reads in `field`:

  {"scopes": [...], "order": n}     share of the window, in %, of the ops
        booked to this group. The files of this reader that list `scopes`
        are one family: each op is booked once, to the group of the lowest
        `order` that has one of its scopes among the op's path components,
        so a new group is one new file and the shares still add up to the
        busy share.
  {"unscoped": true}                share of the ops no group of the family
        books: how complete the naming is.
  {"kernels": [...]}                ms per traced step of the Mosaic calls
        the program named so (`pl.pallas_call(name=...)`: the name is on
        the call's scope path, and XLA names the instruction after it).

The rule of benchmark/trace_reduce.py holds: ops that overlap the window
count whole, containers (`while`, `conditional`, `call`) are left out since
their bodies' ops follow, chips are averaged. None where the run has no
device trace, and where the program names none of what the files list (a
program from before the names existed).
"""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

from benchmark import trace_reduce, xplane_meta

LAYER_METRICS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "layer_metrics")
_PART = re.compile(r"[^/()]+|[/()]")
TOP = 4     # ops logged per metric


def scope_path(tf_op: str) -> tuple:
    """The components of an op's name path that a `jax.named_scope` can
    have put there. `jit(step)/transpose(jvp(attn))/jit(_var)/reduce_sum:`
    gives ("attn",): transforms wrap the component that follows them and
    are unwrapped, the names of jitted functions and the primitive at the
    end are no scopes."""
    parts = _PART.findall((tf_op or "").rstrip(":"))
    path, wrappers = [], []
    for i, part in enumerate(parts):
        if part in ("/", "("):
            continue
        if part == ")":
            if wrappers:
                wrappers.pop()
        elif parts[i + 1:i + 2] == ["("]:
            wrappers.append(part)
        else:
            # jit(<fn>) holds a function's name, no scope
            path.append(None if "jit" in wrappers else part)
    return tuple(part for part in path[:-1] if part)


def family(metrics_dir: str = LAYER_METRICS) -> list:
    """[(metric name, scopes)] of this reader's scope groups, in the order
    an op is offered to them."""
    groups = []
    for path in sorted(glob.glob(os.path.join(metrics_dir, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        field = spec.get("field")
        if spec.get("reader") == "program_scopes" and "scopes" in field:
            groups.append((int(field["order"]), spec["name"],
                           frozenset(field["scopes"])))
    orders = [g[0] for g in groups]
    if len(set(orders)) != len(orders):
        raise ValueError(f"program_scopes: two scope groups of "
                         f"{metrics_dir} share an `order`: {sorted(groups)}")
    return [(name, scopes) for _, name, scopes in sorted(groups)]


def group_of(path: tuple, groups: list):
    """The metric an op with this scope path is booked to, or None."""
    for name, scopes in groups:
        if not scopes.isdisjoint(path):
            return name
    return None


def op_table(trace: dict, metadata: dict, window_ns, groups: list) -> list:
    """One row per executed op in the window, containers left out:
    {"chip", "dur_ns", "op" (trace_reduce.parse_op), "mosaic", "path"
    (scope_path), "group" (group_of), "tf_op", "source"}. `metadata` maps a
    chip to its plane's event metadata."""
    lo, hi = window_ns
    rows = []
    for chip, lines in sorted(trace["devices"].items()):
        meta = metadata.get(chip, {})
        for text, start, dur in lines["ops"]:
            if start + dur <= lo or start >= hi:
                continue
            op = trace_reduce.parse_op(text)
            if op["opcode"] in trace_reduce.CONTAINERS:
                continue
            stats = meta.get(text, {})
            path = scope_path(stats.get("tf_op"))
            rows.append({"chip": chip, "dur_ns": dur, "op": op,
                         "mosaic": trace_reduce.is_mosaic(op, text),
                         "path": path, "group": group_of(path, groups),
                         "tf_op": stats.get("tf_op") or "",
                         "source": stats.get("source") or ""})
    return rows


def load_table(path: str, window_ns, groups: list = None) -> list:
    trace = trace_reduce.load(path)
    metadata = {}
    for plane in xplane_meta.read_planes(path):
        m = trace_reduce.DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        chip = int(m.group(1))
        if trace["devices"].get(chip, {}).get("ops") and not any(
                "tf_op" in s for s in plane["event_metadata"].values()):
            raise RuntimeError(
                f"program_scopes: no event metadata of {plane['name']} in "
                f"{path} carries a `tf_op` stat: the profiler's format "
                f"changed, and no scope can be read")
        metadata[chip] = plane["event_metadata"]
    return op_table(trace, metadata, window_ns,
                    family() if groups is None else groups)


def select(rows: list, metric: dict):
    """The rows the metric's file asks for, or None where the program
    names nothing of what the files list."""
    field = metric["field"]
    if "kernels" in field:
        names = set(field["kernels"])
        picked = [r for r in rows if r["mosaic"] and names & (
            set(r["path"]) | {trace_reduce.op_family(r["op"]["name"])})]
        return picked or None
    if not any(r["group"] for r in rows):
        return None
    group = None if field.get("unscoped") else metric["name"]
    return [r for r in rows if r["group"] == group]


def _log_top(log, metric, picked, chips, steps):
    fam = defaultdict(float)
    for r in picked:
        op = r["op"]
        where = r["source"].rsplit("/", 1)[-1] or "no source"
        fam[(f"{op['opcode']}:{trace_reduce.op_family(op['name'])} "
             f"{op['type'][:32]}", r["tf_op"][-60:], where)] += r["dur_ns"]
    per = 1e6 * chips * max(steps, 1)
    top = sorted(fam.items(), key=lambda kv: -kv[1])[:TOP]
    log(f"[scopes] {metric['name']}: " + "; ".join(
        f"{ns / per:.3f} ms/step {name} <{tf_op}> {where}"
        for (name, tf_op, where), ns in top))


def read(metric: dict, obs: dict):
    red = obs.get("trace")
    if not red:
        return None
    if "program_scopes" not in obs:       # once per run, for every metric
        obs["program_scopes"] = load_table(
            trace_reduce.find_xplane(obs["trace_dir"]), red["window_ns"])
    picked = select(obs["program_scopes"], metric)
    if picked is None:
        return None
    chips, steps = red["chips"], obs.get("traced_steps") or 0
    _log_top(obs.get("log") or (lambda *a: None), metric, picked, chips,
             steps)
    ns = sum(r["dur_ns"] for r in picked) / chips
    if "kernels" in metric["field"]:
        return ns / 1e6 / steps if steps else None
    return 100.0 * ns / 1e9 / red["window_s"]
