"""Host-clock numbers of the train kind's chunks: `obs["chunk_seconds"]`
are the kept chunks (profiler off), each k steps long."""
from benchmark import flops, stats


def read(metric: dict, obs: dict):
    chunks = obs.get("chunk_seconds")
    if not chunks:
        return None
    field = metric["field"]
    k = obs["chunk_steps"]
    if field == "step_ms_p50":
        return stats.percentile(chunks, 50) / k * 1e3
    if field == "stall_share":
        return stats.stall_share(chunks)
    if field == "mfu":
        tok_s_chip = stats.chunk_rate(
            chunks, obs["tokens_per_step"] * k) / obs["chips"]
        peak = flops.peaks(obs["device"]["kind"])["bf16_flops_per_s"]
        return 100.0 * tok_s_chip * obs["flops_per_token"] / peak
    raise KeyError(f"chunk_timer has no field {field!r}")
