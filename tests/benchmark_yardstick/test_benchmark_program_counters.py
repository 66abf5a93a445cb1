"""The reader of the program's own counter families (`program_counters`):
on hand-made registry children with known sums, on a program without the
family, on the program's own compile and gap counters; and the three
set-up metrics it reads."""
import json
import os

import pytest

from benchmark import manifest as mf
from benchmark.readers import program_counters

NEW = ["setup_step_trace_s", "setup_step_backend_s", "setup_before_model_s"]


def _metric(name):
    with open(os.path.join(mf.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _family(name):
    """A fresh `{phase, span}` counter family with known children."""
    from paddle_tpu.observability import get_registry

    fam = get_registry().counter(name, labels=("phase", "span"))
    for (phase, span), v in {("trace", "a"): 1.0, ("lower", "a"): 0.25,
                             ("backend", "a"): 4.0, ("trace", "b"): 8.0,
                             ("cache_load", "a"): 0.5}.items():
        fam.labels(phase=phase, span=span).value = v
    return fam


@pytest.mark.parametrize("match,want", [
    ({"phase": ["trace", "lower"], "span": ["a"]}, 1.25),
    ({"phase": ["backend"], "span": ["a"]}, 4.0),
    ({"phase": ["trace"]}, 9.0),
    ({"span": ["a", "b"]}, 13.75),
    ({}, 13.75),
])
def test_the_sum_of_the_children_that_match(match, want):
    _family("t37_hand_made_total")
    metric = {"name": "m", "field": {"family": "t37_hand_made_total",
                                     "match": match}}
    assert program_counters.read(metric, {}) == pytest.approx(want)


def test_one_counters_line_a_family_with_every_child():
    _family("t37_logged_total")
    lines = []
    obs = {"log": lines.append}
    for match in ({"span": ["a"]}, {"span": ["b"]}):
        program_counters.read({"name": "m", "field": {
            "family": "t37_logged_total", "match": match}}, obs)
    assert len(lines) == 1 and lines[0].startswith(
        "[counters] t37_logged_total: ")
    for child in ("phase=trace,span=a 1.0000", "phase=trace,span=b 8.0000",
                  "phase=cache_load,span=a 0.5000"):
        assert child in lines[0]


def test_nothing_to_read_is_none():
    """A program from before the family (the parent of PR 37) and a
    family without the child: the line leaves the metric out."""
    _family("t37_partial_total")
    assert program_counters.read({"name": "m", "field": {
        "family": "t37_no_such_family_total", "match": {}}}, {}) is None
    assert program_counters.read({"name": "m", "field": {
        "family": "t37_partial_total",
        "match": {"span": ["jit_step.first_call"]}}}, {}) is None


def test_the_three_metrics_read_the_programs_own_counters():
    """A compile inside `jit_step.first_call` and a gap before `model_init`
    are what the three files read, through the program's own span stream."""
    import time

    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import RecordEvent

    before = {n: program_counters.read(_metric(n), {}) or 0.0 for n in NEW}
    with RecordEvent("t37_import"):
        pass
    time.sleep(0.001)
    with RecordEvent("model_init"):
        pass
    with RecordEvent("jit_step"):
        with RecordEvent("jit_step.first_call"):
            jax.jit(lambda x: jnp.cos(x) * 3)(jnp.ones(37))
    grew = {n: program_counters.read(_metric(n), {}) - before[n]
            for n in NEW}
    assert all(v > 0 for v in grew.values()), grew


def test_the_three_metric_files_and_their_entries(manifest):
    # looked up by name: a position asserted breaks with the next entry
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        spec = _metric(name)
        assert set(spec) == {"name", "layer", "unit", "moves", "reader",
                             "field", "what"}
        assert spec["name"] == name and spec["reader"] == "program_counters"
        assert spec["layer"] == "Compile and cache" and spec["unit"] == "s"
        assert spec["moves"] == "setup_s"
        field = spec["field"]
        assert set(field) == {"family", "match"}
        assert all(isinstance(v, list) and v for v in field["match"].values())
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": "Compile and cache",
            "moves": "setup_s"}      # no `workloads`: every cell reports it
    assert _metric(NEW[0])["field"]["match"] == {
        "phase": ["trace", "lower"], "span": ["jit_step.first_call"]}
    assert _metric(NEW[1])["field"]["match"] == {
        "phase": ["backend"], "span": ["jit_step.first_call"]}
    assert _metric(NEW[2])["field"] == {
        "family": "host_outside_seconds_total",
        "match": {"before": ["model_init"]}}
