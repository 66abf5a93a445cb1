"""Numbers from the device trace of the traced slice, which the harness
reduces once per run (`obs["trace"]`, benchmark/trace_reduce.py)."""
from benchmark import flops


def read(metric: dict, obs: dict):
    red = obs.get("trace")
    if not red:
        return None
    field = metric["field"]
    w = red["window_s"]
    if field == "device_idle_share":
        return 100.0 * (1.0 - red["busy_s"] / w)
    if field == "mosaic_time_share":
        return 100.0 * red["mosaic_s"] / w
    if field == "flash_roofline_share":
        shape, steps = obs.get("flash"), obs.get("traced_steps")
        if not shape or not steps or red["mosaic_s"] <= 0:
            return None
        # per chip: the batch is split over the data-parallel ranks and the
        # heads over the tensor-parallel ones, so a chip does 1/chips of it
        cost = flops.flash_causal_train_cost(**shape)
        cost = {k: v / obs["chips"] for k, v in cost.items()}
        least, bound = flops.roofline_seconds(
            cost, flops.peaks(obs["device"]["kind"]))
        (obs.get("log") or (lambda *a: None))(
            f"[trace] flash fwd+bwd per step per chip: "
            f"{cost['flops'] / 1e12:.3f} TFLOP, {cost['bytes'] / 1e9:.3f} GB"
            f", least {least * 1e3:.3f} ms ({bound}-bound); measured "
            f"{red['mosaic_s'] / steps * 1e3:.3f} ms")
        return 100.0 * least / (red["mosaic_s"] / steps)
    raise KeyError(f"device_trace has no field {field!r}")
