"""pathway_tpu.kvcache — paged KV-cache management for batched decoding.

Round-7 subsystem (see ARCHITECTURE.md "Round-7: paged KV serving"): the
dense per-instance `[1, T_max]` KV buffer in models/decoder.py pinned the
serving path to one sequence at a time.  Here the cache is a managed,
shared resource — a fixed HBM block pool (block_pool.py) addressed through
per-sequence block tables, with hash-chained prefix sharing
(prefix_cache.py), a paged attention op with a pure-JAX gather reference
path and a Pallas kernel (paged_attention.py), and a continuous-batching
generation engine (engine.py) that admits new sequences into in-flight
decode batches at step boundaries and preempts-with-recompute when the
pool is exhausted.

Round-8 (ARCHITECTURE.md "Round-8: Ragged fused-step decode") makes one
engine step one device program over a ragged mixed batch: prompts stream
in as block-aligned chunks through the token-packed fused step
(models/decoder.paged_mixed_step) instead of per-admission whole-bucket
prefills, greedy argmax runs inside the jitted step (only [B] int32 ids
cross to host per round), and the Pallas kernel's grid is length-aware
(blocks past a row's context are neither DMA'd nor computed).

Round-9 (ARCHITECTURE.md "Round-9: Tensor-parallel paged decode") shards
the whole serving path over a (dp=1, tp=N) device mesh: the pool's K/V
arrays split on the head axis (n_kv_heads/tp per shard — N x aggregate
KV HBM, so N x more live sequences at fixed model size), every step
program runs under shard_map with Megatron column/row-parallel
projections and ONE psum per layer pair, and sampling stays device-side
(greedy argmax fused into the sharded vocab head as an exact two-stage
reduction — no replicated [B, vocab] gather ever materializes).
``PagedDecodeEngine(tp=...)``; tp=1 degenerates to the exact
single-device programs.

The engine<->cache contract is backend.py (``CacheBackend`` +
``make_backend``), with five kinds behind it: ``"paged"`` (block_pool.py
``BlockPool``: K/V blocks for every layer), ``"hybrid"`` (hybrid.py
``HybridCache``: K/V blocks for a model's attention layers and a conv
slot a sequence beside them), ``"windowed"`` (windowed.py
``WindowedCache``: K/V blocks for a model's full-attention layers and a
second pool, freed behind the window, for its sliding-window layers),
``"latent_state"`` (hybrid.py ``StateCache``: a latent pool of one array
for a model's latent-attention layers and, under one slot a sequence, the
conv inputs and the f32 matrix states of its delta-rule layers) and
``"kv_state"`` (hybrid.py ``KVStateCache``: that state cache with a plain
K/V pool, keys and values of a model's full-attention layers, where the
latent pool stood).  Which kind an engine builds, and which
step programs it runs, its block family says (models/families.py).

Round-18 (ARCHITECTURE.md "Round-18: Speculative decoding") breaks the
step's serial token dependence: a cheap drafter (speculative.py — a
zero-HBM n-gram/prefix-hash drafter or a separately-planned draft MODEL)
proposes up to K tokens per row, ONE ragged verify dispatch checks them
all through the mixed-step kernel (C = k+1 queries/row), and the greedy
accept rule keeps output TOKEN-IDENTICAL to non-speculative decode.
Unlike the Round-10 chain, speculative rounds stay multi-token while
arrivals are pending; ``PagedDecodeEngine(speculative=...)``, with
``"auto"`` reading the cost store's measured ``pw.spec_tier`` prior.

Kernel shape follows Ragged Paged Attention (arxiv 2604.15464); the
managed-resource framing follows arxiv 2603.09555.
"""

from .backend import CacheBackend, UnsupportedCacheOp, make_backend
from .block_pool import BlockPool, PoolExhausted, SequenceState
from .engine import EngineHungError, PagedDecodeEngine, resolve_tp
from .hybrid import HybridCache, KVStateCache, StateCache
from .paged_attention import paged_attention, paged_attention_reference
from .prefix_cache import PrefixCache
from .speculative import (Drafter, DraftModelDrafter, NGramDrafter,
                          SpecController, SpecResourceError)
from .tiering import SessionStore
from .windowed import WindowedCache

__all__ = [
    "Drafter",
    "DraftModelDrafter",
    "NGramDrafter",
    "SpecController",
    "SpecResourceError",
    "SessionStore",
    "BlockPool",
    "CacheBackend",
    "EngineHungError",
    "HybridCache",
    "KVStateCache",
    "StateCache",
    "PoolExhausted",
    "SequenceState",
    "PrefixCache",
    "PagedDecodeEngine",
    "UnsupportedCacheOp",
    "WindowedCache",
    "make_backend",
    "resolve_tp",
    "paged_attention",
    "paged_attention_reference",
]
