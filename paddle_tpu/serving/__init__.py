"""paddle_tpu.serving — continuous-batching inference serving runtime.

ROADMAP item 1: the repo trains at scale; this package makes it SERVE.
Layers (each its own module, composable without the others):

  scheduler.py   admission-controlled request queue (open-loop arrivals
                 get backpressure at submit; drained requests re-admit
                 at the head — zero lost)
  kv_cache.py    paged/blocked KV cache: fixed-size blocks + free list +
                 per-sequence block tables, at-rest int8/fp8 blockwise
                 quantization through grad_comm's codec seam
                 (``_block_kernel_ops`` — pallas kernels under
                 ``FLAGS_kernel_autotune`` on TPU); refcounted prefix
                 sharing (chain-hash index, copy-on-write, LRU over
                 refcount-0 blocks) + reserve/rollback scratch
  model.py       GPTForCausalLM -> jitted prefill/decode/extend split
                 with zero-copy parameter sharing across replicas;
                 ``truncated(n)`` derives a self-draft model
  sampler.py     batched jitted top-k/top-p/temperature sampling over
                 per-request counter-based RNG streams (greedy = the
                 temperature=0 fast path)
  engine.py      the continuous-batching step loop (batch re-formed
                 every step; no head-of-line blocking), prefix-cached
                 admission, and lossless speculative decoding
  replica.py     N replicas behind the queue with watchdog +
                 ReplicaGuard eviction and drain-and-re-admit

Observability: ``serve_requests_total{outcome=}``, ``serve_queue_depth``,
``serve_request_latency_ms`` (p50/p95/p99 via ``Histogram.quantile``),
``serve_batch_occupancy{replica=}``, ``serve_kv_blocks_in_use{replica=}``,
``serve_replica_evictions_total{reason=}``,
``serve_prefix_cache_{hit,miss}_tokens_total``,
``serve_spec_accepted_per_step{replica=}``, plus a ``/serving`` section
on the telemetry exposition endpoint while a ``ReplicaSet`` is running.

Speed: not measured. No cell of ``BENCHMARK.json`` serves yet (the KV
pool lives on the host: ROADMAP B3); the counts this runtime owes (bytes a
quantized block holds, zero lost requests under eviction, prefix hits,
lossless speculation) are held by ``tests/test_serving*.py``.
"""
from .engine import ReplicaBootBudgetExceeded, ServingEngine
from .kv_cache import BlockTable, KVBlockPool, KVCacheOOM, KV_CODECS
from .model import GPTDecodeModel, bucket_pow2
from .replica import ReplicaSet, StandbyReplica
from .sampler import BatchSampler, SamplingParams, default_sampler
from .scheduler import OUTCOMES, RequestQueue, ServeRequest

__all__ = [
    "ServingEngine", "ReplicaBootBudgetExceeded", "KVBlockPool",
    "BlockTable", "KVCacheOOM",
    "KV_CODECS", "GPTDecodeModel", "bucket_pow2", "ReplicaSet",
    "StandbyReplica", "RequestQueue", "ServeRequest", "OUTCOMES",
    "BatchSampler", "SamplingParams", "default_sampler",
]
