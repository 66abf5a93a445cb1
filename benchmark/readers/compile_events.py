"""jax.monitoring's compile events, split at the window's start by the
harness (`obs["compile"]`)."""


def read(metric: dict, obs: dict):
    comp = obs.get("compile")
    if not comp:
        return None
    return comp[metric["field"]]
