"""Paged/blocked KV cache for the continuous-batching serving runtime.

The decode-side analog of grad_comm's bucketed gradient store: KV state
lives in fixed-size *blocks* of ``block_tokens`` tokens allocated from a
shared pool with a free list, and each sequence owns a *block table*
(ordered block ids + token count) instead of a contiguous buffer — so a
finishing sequence returns its blocks immediately and a new admission
reuses them, with zero compaction (the paged-attention allocation model).

At-rest quantization reuses the PR-8 EQuARX blockwise codecs verbatim:
one fp32 abs-max scale per ``quant_block`` elements, encoded/decoded
through ``grad_comm._block_kernel_ops()`` — the same seam the collectives
ride, so the pallas codec kernels (ops/pallas/codec.py) apply under
``FLAGS_kernel_autotune`` on TPU targets and the pure-jnp pair stays the
reference everywhere else. Each appended token is quantized exactly once
(scales aligned to token boundaries: ``quant_block`` must divide the
per-token element count), so a token's at-rest bits never change after
the write — which makes an incrementally-maintained dequantized working
copy bit-identical to a fresh :meth:`KVBlockPool.gather` (the engine
relies on this; ``tests/test_serving.py`` pins it).

``append`` returns the *dequantized read-back* of what was stored, never
the input: attention must see exactly the at-rest bits, or the quantized
cache's accuracy story would be fiction.

Prefix cache (PR 16). Blocks additionally carry a refcount and an
optional set of *index keys* — chain hashes of the token prefix whose KV
the block's leading rows hold (``h_i = H(h_{i-1} || chunk_i)``, so a key
names the FULL path from token 0, not just the chunk). Admission walks a
prompt's chain through the index and, on hits, maps the matched blocks
into the new table read-only (``refcount += 1``; they become the table's
leading ``n_shared`` entries) so a shared prefix is prefilled exactly
once. Sharing is copy-on-write: the first ``append`` whose frontier
lands inside a shared block copies the matched rows' at-rest bits
(payload + scales — bit-identical, no re-quantization) into a block
reserved for that purpose at admission (``cow_spare``), so a sequence
appending past a shared prefix can never mutate bytes another sequence
reads, and never needs a block it didn't reserve. Freed blocks that
carry index keys retire to an LRU of refcount-0 *cached* blocks instead
of the free list; the allocator evicts from that LRU (dropping the keys)
only when the free list runs dry. ``free_blocks`` therefore counts free
AND cached blocks — both are allocatable — and ``blocks_in_use`` counts
only blocks some live table references.

Speculative decoding rides ``reserve``/``rollback``: ``reserve`` grows a
table past its admission reservation for draft-token scratch, and
``rollback`` unwinds rejected tokens, returning every block beyond
``max(base_blocks, blocks_needed(n_tokens))`` — the same no-leak
discipline the PR-14 drain path exercises, pinned under chaos eviction.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["KVBlockPool", "BlockTable", "KVCacheOOM", "KV_CODECS"]

KV_CODECS = ("fp32", "int8_block", "fp8_block")


class KVCacheOOM(RuntimeError):
    """The pool has no free block for a requested allocation."""


@dataclass
class BlockTable:
    """Per-sequence view into the pool: ordered block ids + token count.

    ``n_shared`` leading blocks are mapped read-only from the prefix
    cache (refcounted; ``append`` never writes them in place — COW).
    ``cow_spare`` is the block reserved at admission for that COW when
    the last shared block is only partially matched. ``base_blocks`` is
    the admission reservation size — ``rollback`` never shrinks the
    table below it (the never-OOM-mid-flight guarantee).
    """

    block_ids: List[int] = field(default_factory=list)
    n_tokens: int = 0
    n_shared: int = 0
    cow_spare: Optional[int] = None
    base_blocks: int = 0

    def capacity(self, block_tokens: int) -> int:
        return len(self.block_ids) * block_tokens


def _chain_key(prev: bytes, tokens: np.ndarray) -> bytes:
    """h_i = H(h_{i-1} || tokens): a key names the whole token path."""
    return hashlib.sha1(
        prev + np.ascontiguousarray(tokens, np.int32).tobytes()).digest()


class KVBlockPool:
    """Fixed-size KV block pool with a free list, refcounted prefix
    sharing, and blockwise codecs.

    One pool per serving replica. ``elems_per_token`` is the flattened
    per-token KV payload (layers x {k,v} x heads x head_dim); callers
    append/gather ``[tokens, elems_per_token]`` fp32 matrices and the
    pool handles block placement and the at-rest codec.
    """

    def __init__(self, n_blocks: int, block_tokens: int,
                 elems_per_token: int, codec: str = "fp32",
                 quant_block: Optional[int] = None):
        from ..distributed import grad_comm

        if codec not in KV_CODECS:
            raise ValueError(f"codec must be one of {KV_CODECS}, got {codec!r}")
        self.n_blocks = int(n_blocks)
        self.block_tokens = int(block_tokens)
        self.elems_per_token = int(elems_per_token)
        self.codec = codec
        if codec != "fp32":
            qb = int(quant_block or min(self.elems_per_token, 1024))
            if self.elems_per_token % qb:
                raise ValueError(
                    f"quant_block ({qb}) must divide elems_per_token "
                    f"({self.elems_per_token}) so every append stays "
                    f"scale-aligned (tokens quantize exactly once)")
            self.quant_block = qb
            self._scales_per_token = self.elems_per_token // qb
        else:
            self.quant_block = 0
            self._scales_per_token = 0
        shape = (self.n_blocks, self.block_tokens, self.elems_per_token)
        if codec == "fp32":
            self._payload = np.zeros(shape, np.float32)
            self._scales = None
        else:
            wire = np.int8 if codec == "int8_block" else grad_comm._FP8_WIRE
            self._payload = np.zeros(shape, wire)
            self._scales = np.zeros(
                (self.n_blocks,
                 self.block_tokens * self._scales_per_token), np.float32)
        self._free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        # prefix cache state: per-block refcounts, chain-hash index
        # (key -> (block, matched rows)), per-block registered keys, and
        # the LRU of refcount-0 blocks still holding indexed content
        self._ref: List[int] = [0] * self.n_blocks
        self._index: Dict[bytes, Tuple[int, int]] = {}
        self._block_keys: List[List[bytes]] = [[] for _ in range(self.n_blocks)]
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.prefix_evictions = 0
        self._lock = threading.Lock()

    # ------------------------------------------------------------ allocator
    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: truly free plus cached (evictable LRU)."""
        return len(self._free) + len(self._lru)

    @property
    def blocks_in_use(self) -> int:
        """Blocks referenced by at least one live table."""
        return self.n_blocks - self.free_blocks

    @property
    def cached_blocks(self) -> int:
        """Refcount-0 blocks retained only for prefix reuse."""
        return len(self._lru)

    def blocks_needed(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_tokens)

    def _take_block_locked(self) -> int:
        """A writable block: free list first, then evict the LRU cached
        block (its index keys drop — the cache trades history for room)."""
        if self._free:
            return self._free.pop()
        if self._lru:
            bi, _ = self._lru.popitem(last=False)
            for key in self._block_keys[bi]:
                if self._index.get(key, (None,))[0] == bi:
                    del self._index[key]
            self._block_keys[bi] = []
            self.prefix_evictions += 1
            return bi
        raise KVCacheOOM(
            f"no free or evictable block "
            f"(pool of {self.n_blocks} x {self.block_tokens} tokens)")

    def _release_locked(self, bi: int):
        self._ref[bi] -= 1
        if self._ref[bi] < 0:
            raise AssertionError(f"block {bi} refcount underflow")
        if self._ref[bi] == 0:
            if self._block_keys[bi]:
                self._lru[bi] = None
                self._lru.move_to_end(bi)
            else:
                self._free.append(bi)

    def _match_locked(self, prefix: np.ndarray
                      ) -> Tuple[List[int], Optional[Tuple[int, int]], int]:
        """Walk ``prefix`` through the chain index. Returns (full-block
        ids, optional (block, rows) partial tail hit, matched tokens)."""
        bt = self.block_tokens
        full: List[int] = []
        h = b""
        t = 0
        while t + bt <= len(prefix):
            key = _chain_key(h, prefix[t:t + bt])
            ent = self._index.get(key)
            if ent is None:
                break
            full.append(ent[0])
            h = key
            t += bt
        partial = None
        rem = len(prefix) - t
        for length in range(min(rem, bt - 1), 0, -1):
            ent = self._index.get(_chain_key(h, prefix[t:t + length]))
            if ent is not None:
                partial = (ent[0], length)
                break
        matched = t + (partial[1] if partial else 0)
        return full, partial, matched

    def probe_prefix(self, prefix_tokens) -> int:
        """Longest cached-prefix match in tokens (no allocation)."""
        prefix = np.asarray(prefix_tokens, np.int32)
        with self._lock:
            return self._match_locked(prefix)[2]

    def alloc_table(self, n_tokens: int,
                    prefix_tokens=None) -> BlockTable:
        """Allocate blocks covering ``n_tokens`` tokens up front (the
        engine reserves a sequence's full context budget at admission so
        decode can never OOM mid-flight).

        With ``prefix_tokens`` (the prompt prefix eligible for reuse —
        the engine caps it at ``n_prompt - 1`` so at least one token is
        always prefilled for logits), matched cached blocks become the
        table's leading shared entries and ``table.n_tokens`` starts at
        the matched length; only ``blocks_needed - full_shared`` fresh
        blocks are drawn (shared blocks count once in the reservation),
        plus one COW spare when the last match is partial.
        """
        need = self.blocks_needed(n_tokens)
        with self._lock:
            full: List[int] = []
            partial = None
            matched = 0
            if prefix_tokens is not None and len(prefix_tokens):
                prefix = np.asarray(prefix_tokens, np.int32)
                full, partial, matched = self._match_locked(prefix)
            n_shared = len(full) + (1 if partial else 0)
            fresh = need - len(full) - (1 if partial else 0)
            spare = 1 if partial else 0
            shared_ids = full + ([partial[0]] if partial else [])
            in_lru_shared = sum(1 for bi in shared_ids if bi in self._lru)
            if fresh + spare > self.free_blocks - in_lru_shared:
                raise KVCacheOOM(
                    f"need {fresh + spare} blocks beyond {n_shared} shared, "
                    f"{self.free_blocks - in_lru_shared} allocatable "
                    f"(pool of {self.n_blocks} x {self.block_tokens} tokens)")
            for bi in shared_ids:
                self._ref[bi] += 1
                self._lru.pop(bi, None)
            ids = shared_ids + [self._take_block_locked()
                                for _ in range(fresh)]
            for bi in ids[n_shared:]:
                self._ref[bi] += 1
            spare_id = None
            if spare:
                spare_id = self._take_block_locked()
                self._ref[spare_id] += 1
        return BlockTable(block_ids=ids, n_tokens=matched,
                          n_shared=n_shared, cow_spare=spare_id,
                          base_blocks=len(ids))

    def free_table(self, table: BlockTable):
        with self._lock:
            for bi in table.block_ids:
                self._release_locked(bi)
            if table.cow_spare is not None:
                self._release_locked(table.cow_spare)
        table.block_ids = []
        table.n_tokens = 0
        table.n_shared = 0
        table.cow_spare = None

    # --------------------------------------------------------- prefix index
    def register_prefix(self, table: BlockTable, prompt_tokens):
        """Index ``table``'s blocks under the chain keys of
        ``prompt_tokens`` so later admissions can share them. Every
        complete ``block_tokens`` chunk gets its full-chain key, and
        every block additionally gets keys for each proper prefix of its
        chunk (partial-tail matches stop anywhere). Rows being indexed
        are already immutable: appends only ever write at the frontier,
        which sits at or past ``len(prompt_tokens)`` when the engine
        calls this. First writer wins on key collisions (identical
        content — the chain hash covers the whole path)."""
        tokens = np.asarray(prompt_tokens, np.int32)
        bt = self.block_tokens
        with self._lock:
            if table.n_tokens < len(tokens):
                raise ValueError("register_prefix before the prompt's KV "
                                 "was appended")
            h = b""
            for start in range(0, len(tokens), bt):
                chunk = tokens[start:start + bt]
                bi = table.block_ids[start // bt]
                for length in range(1, len(chunk) + 1):
                    key = _chain_key(h, chunk[:length])
                    if key not in self._index:
                        self._index[key] = (bi, length)
                        self._block_keys[bi].append(key)
                if len(chunk) < bt:
                    break
                h = _chain_key(h, chunk)

    # ------------------------------------------------- speculative scratch
    def reserve(self, table: BlockTable, extra_tokens: int):
        """Grow the table so ``n_tokens + extra_tokens`` fit — draft-token
        scratch beyond the admission reservation. No-op when capacity
        already covers it; raises :class:`KVCacheOOM` (table unchanged)
        when the pool cannot back the growth."""
        need = self.blocks_needed(table.n_tokens + int(extra_tokens))
        with self._lock:
            grow = need - len(table.block_ids)
            if grow <= 0:
                return
            if grow > self.free_blocks:
                raise KVCacheOOM(
                    f"reserve wants {grow} blocks, "
                    f"{self.free_blocks} allocatable")
            for _ in range(grow):
                bi = self._take_block_locked()
                self._ref[bi] += 1
                table.block_ids.append(bi)

    def rollback(self, table: BlockTable, n_tokens: int):
        """Unwind the last ``n_tokens`` appended tokens (rejected draft
        positions). Stale at-rest rows need no scrubbing — reads are
        bounded by ``table.n_tokens`` and the next append overwrites —
        but every block beyond ``max(base_blocks, blocks_needed)``
        returns to the pool immediately: reserve/rollback must never
        leak. ``rollback(table, 0)`` unwinds no tokens but still trims
        excess reserved blocks — the cancel path for an unused
        :meth:`reserve`."""
        n = int(n_tokens)
        if n < 0 or n > table.n_tokens:
            raise ValueError(f"rollback of {n} from {table.n_tokens} tokens")
        with self._lock:
            table.n_tokens -= n
            keep = max(table.base_blocks,
                       self.blocks_needed(table.n_tokens))
            while len(table.block_ids) > keep:
                self._release_locked(table.block_ids.pop())

    # ---------------------------------------------------------------- codec
    def _encode_chunk(self, chunk: np.ndarray):
        """fp32 [t, ept] -> (payload [t, ept] wire-dtype, scales or None,
        dequantized read-back [t, ept] fp32)."""
        from ..distributed import grad_comm

        if self.codec == "fp32":
            stored = np.ascontiguousarray(chunk, np.float32)
            return stored, None, stored
        flat = chunk.reshape(-1)
        qb = self.quant_block
        absmax = grad_comm.block_absmax(flat, qb)
        scales = grad_comm.block_scales(absmax, self.codec)
        enc, dec = grad_comm._block_kernel_ops()
        q = enc(flat, scales, qb, self.codec)
        deq = np.asarray(dec(q, scales, 1, np.float32, flat.size),
                         np.float32).reshape(chunk.shape)
        wire = self._payload.dtype
        payload = np.asarray(q, dtype=wire).reshape(chunk.shape)
        return payload, np.asarray(scales, np.float32), deq

    def _decode_rows(self, payload: np.ndarray, scales) -> np.ndarray:
        """wire [t, ept] (+scales) -> fp32 [t, ept]."""
        from ..distributed import grad_comm

        if self.codec == "fp32":
            return np.array(payload, np.float32)
        qb = self.quant_block
        carrier = (payload.astype(np.int32) if self.codec == "int8_block"
                   else payload.astype(np.float32))
        _enc, dec = grad_comm._block_kernel_ops()
        numel = payload.size
        out = dec(carrier.reshape(-1, qb), np.asarray(scales, np.float32),
                  1, np.float32, numel)
        return np.asarray(out, np.float32).reshape(payload.shape)

    def _cow_locked(self, table: BlockTable, idx: int, rows: int):
        """Copy-on-write of shared block ``table.block_ids[idx]``: move
        its first ``rows`` at-rest rows (payload + scales — the exact
        bits, no re-quantization) into the admission-reserved spare and
        swap it into the table. The shared original keeps its index
        entries and refcount with the other readers."""
        if idx != table.n_shared - 1:
            raise AssertionError(
                "COW frontier must be the last shared block "
                f"(idx {idx}, n_shared {table.n_shared})")
        old = table.block_ids[idx]
        if table.cow_spare is not None:
            new = table.cow_spare
            table.cow_spare = None
        else:  # defensive: reservation should always have provided one
            new = self._take_block_locked()
            self._ref[new] += 1
        if rows:
            self._payload[new, :rows] = self._payload[old, :rows]
            if self._scales is not None:
                spt = self._scales_per_token
                self._scales[new, :rows * spt] = \
                    self._scales[old, :rows * spt]
        table.block_ids[idx] = new
        table.n_shared = idx
        self._release_locked(old)

    # ------------------------------------------------------------------- io
    def append(self, table: BlockTable, kv: np.ndarray) -> np.ndarray:
        """Append ``kv`` [t, elems_per_token] fp32 rows to the sequence.
        Returns the dequantized at-rest read-back of the same rows (what
        attention must consume). The table must already hold enough
        blocks (``alloc_table``/``reserve`` reserved them). A frontier
        inside a shared block triggers copy-on-write first — shared
        bytes are never mutated."""
        kv = np.asarray(kv, np.float32)
        if kv.ndim != 2 or kv.shape[1] != self.elems_per_token:
            raise ValueError(
                f"append wants [t, {self.elems_per_token}], got {kv.shape}")
        t = kv.shape[0]
        if table.n_tokens + t > table.capacity(self.block_tokens):
            raise KVCacheOOM(
                f"table holds {table.capacity(self.block_tokens)} tokens, "
                f"append to {table.n_tokens + t} exceeds the reservation")
        out = np.empty_like(kv)
        done = 0
        with self._lock:
            while done < t:
                pos = table.n_tokens + done
                idx = pos // self.block_tokens
                off = pos % self.block_tokens
                if idx < table.n_shared:
                    self._cow_locked(table, idx, off)
                bi = table.block_ids[idx]
                take = min(t - done, self.block_tokens - off)
                chunk = kv[done:done + take]
                payload, scales, deq = self._encode_chunk(chunk)
                self._payload[bi, off:off + take] = payload
                if scales is not None:
                    spt = self._scales_per_token
                    self._scales[bi, off * spt:(off + take) * spt] = scales
                out[done:done + take] = deq
                done += take
            table.n_tokens += t
        return out

    def gather(self, table: BlockTable) -> np.ndarray:
        """Dequantize the sequence's full KV prefix -> fp32
        [n_tokens, elems_per_token]."""
        out = np.empty((table.n_tokens, self.elems_per_token), np.float32)
        with self._lock:
            done = 0
            for bi in table.block_ids:
                if done >= table.n_tokens:
                    break
                take = min(self.block_tokens, table.n_tokens - done)
                scales = (None if self._scales is None else
                          self._scales[bi, :take * self._scales_per_token])
                out[done:done + take] = self._decode_rows(
                    self._payload[bi, :take], scales)
                done += take
        return out

    # ----------------------------------------------------------- accounting
    def block_bytes(self) -> int:
        """At-rest bytes of ONE block: payload + its scale slice."""
        b = self.block_tokens * self.elems_per_token * \
            self._payload.dtype.itemsize
        if self._scales is not None:
            b += self.block_tokens * self._scales_per_token * 4
        return b

    def bytes_in_use(self) -> int:
        """At-rest bytes of every allocated block (allocation granularity —
        what the pool actually holds, reservation included)."""
        return self.blocks_in_use * self.block_bytes()

    def fp32_equiv_bytes(self) -> int:
        """What the same allocation would hold un-quantized."""
        return (self.blocks_in_use * self.block_tokens *
                self.elems_per_token * 4)

    def stats(self) -> dict:
        return {
            "codec": self.codec,
            "n_blocks": self.n_blocks,
            "block_tokens": self.block_tokens,
            "blocks_in_use": self.blocks_in_use,
            "free_blocks": self.free_blocks,
            "cached_blocks": self.cached_blocks,
            "prefix_evictions": self.prefix_evictions,
            "bytes_in_use": self.bytes_in_use(),
            "fp32_equiv_bytes": self.fp32_equiv_bytes(),
        }
