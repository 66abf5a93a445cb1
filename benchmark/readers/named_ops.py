"""Device time of ops the PROGRAM names that are no group of the
`program_scopes` family, and rooflines over them.

The op table is program_scopes' own (`load_table`, one per run, shared
through `obs["program_scopes"]`): one row per executed op in the traced
window with its `jax.named_scope` path, its family group and whether it is
a Mosaic call. An op is NAMED by a metric's file where the family books it
to no group and its scope path holds one of the file's `scopes`, or it is a
Mosaic call whose whole instruction name (instances `.1`, `.2` folded) is
among the file's `kernels` (XLA's own grouped-matmul calls carry no scope).
Listed scopes are siblings of the family's in the program, never inside
them, so with the family's shares these add up to the busy share.

A metric's file says what it reads in `field`:

  {"scopes": [...], "kernels": [...]}      share of the window, in %, of
        the ops named so (either list may be left out).
  {"unnamed": true, "scopes": [...], "kernels": [...]}   share of the ops
        that neither the family nor the lists name: how complete the
        naming is in a program that has these scopes.
  {"roofline": "<obs key>", "scopes" / "kernels": [...]}   the least seconds
        a step needs for the cost {"flops", "bytes"} the kind put into
        `obs` under that key (flops.roofline_seconds against peaks.json),
        over the seconds a traced step spends in the ops named so, in %
        (here an op counts whichever group books its share: the flash
        calls sit under `attn`).

None where the run has no device trace, where the program names nothing
of what the file lists (a program from before the names existed), and for
a roofline whose cost the kind did not give.
"""
from __future__ import annotations

from benchmark import flops, trace_reduce
from benchmark.readers import program_scopes


def named(row: dict, field: dict, any_group: bool = False) -> bool:
    """`any_group`: also an op the family books (a roofline counts a
    kernel's time whoever's share it is in; a share never does)."""
    if row["group"] and not any_group:
        return False
    if not set(field.get("scopes", ())).isdisjoint(row["path"]):
        return True
    return bool(row["mosaic"]) and trace_reduce.op_family(
        row["op"]["name"]) in set(field.get("kernels", ()))


def select(rows: list, field: dict):
    """The rows the field asks for, or None where nothing in the trace
    carries a listed name."""
    hit = [r for r in rows if named(r, field, "roofline" in field)]
    if not hit:
        return None
    if field.get("unnamed"):
        return [r for r in rows if not r["group"] and not named(r, field)]
    return hit


def read(metric: dict, obs: dict):
    red = obs.get("trace")
    if not red:
        return None
    if "program_scopes" not in obs:       # once per run, for every metric
        obs["program_scopes"] = program_scopes.load_table(
            trace_reduce.find_xplane(obs["trace_dir"]), red["window_ns"])
    field = metric["field"]
    picked = select(obs["program_scopes"], field)
    if picked is None:
        return None
    log = obs.get("log") or (lambda *a: None)
    chips, steps = red["chips"], obs.get("traced_steps") or 0
    program_scopes._log_top(log, metric, picked, chips, steps)
    seconds = sum(r["dur_ns"] for r in picked) / chips / 1e9
    if "roofline" not in field:
        return 100.0 * seconds / red["window_s"]
    cost = obs.get(field["roofline"])
    if not cost or not steps or seconds <= 0:
        return None
    least, bound = flops.roofline_seconds(
        cost, flops.peaks(obs["device"]["kind"]))
    log(f"[trace] {metric['name']}: per step {cost['flops'] / 1e12:.3f} "
        f"TFLOP, {cost['bytes'] / 1e9:.3f} GB, least {least * 1e3:.3f} ms "
        f"({bound}-bound); measured {seconds / steps * 1e3:.3f} ms")
    return 100.0 * least / (seconds / steps)
