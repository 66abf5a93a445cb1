"""Set-up phases from the program's own counters: the registry family
`host_span_seconds_total{span}`, to which every `profiler.RecordEvent` of
the program adds its duration (paddle_tpu/observability/host_spans.py). A
metric's file lists the spans it adds up in `field`: {"spans": [...]}.
Reported like `compile_s`, on any device. None where the program has no
such family or none of the spans (a program from before they existed)."""


def read(metric: dict, obs: dict):
    from paddle_tpu.observability import get_registry

    family = get_registry().get("host_span_seconds_total")
    if family is None:
        return None
    seconds = {labels["span"]: child.value for labels, child in family.items()}
    spans = metric["field"]["spans"]
    if not any(s in seconds for s in spans):
        return None
    (obs.get("log") or (lambda *a: None))(
        f"[phases] {metric['name']}: " + ", ".join(
            f"{s} {seconds.get(s, 0.0):.3f} s" for s in spans))
    return sum(seconds.get(s, 0.0) for s in spans)
