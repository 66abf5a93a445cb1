"""A number the kind took inside the window and put into `obs` under the
key the metric's `field` names: from the program's own counters read at the
window's two ends, or from the device at the window's end. None where the
kind gave none."""


def read(metric: dict, obs: dict):
    value = obs.get(metric["field"])
    return None if value is None else float(value)
