"""A sum over one of the program's own counter families in the metrics
registry (paddle_tpu/observability/metrics.py). A metric's file says which
family and which of its children in `field`:

  {"family": "<name>", "match": {"<label>": ["<value>", ...], ...}}

The value is the sum of every child whose every listed label takes one of
its listed values. The first metric to read a family logs all of its
children on one `[counters]` line, so a traced run prints the whole map
(compile seconds by phase and span, the caller's seconds between spans).
Reported like `compile_s`, on any device. None where the program has no
such family or no such child (a program from before they existed).
"""


def read(metric: dict, obs: dict):
    from paddle_tpu.observability import get_registry

    spec = metric["field"]
    family = get_registry().get(spec["family"])
    if family is None:
        return None
    children = [(labels, child.value) for labels, child in family.items()]
    logged = obs.setdefault("program_counters_logged", set())
    if spec["family"] not in logged:
        logged.add(spec["family"])
        (obs.get("log") or (lambda *a: None))(
            f"[counters] {spec['family']}: " + "; ".join(
                ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
                + f" {value:.4f}" for labels, value in children))
    match = spec["match"]
    hits = [value for labels, value in children
            if all(labels.get(k) in vs for k, vs in match.items())]
    return sum(hits) if hits else None
