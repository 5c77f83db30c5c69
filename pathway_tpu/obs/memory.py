"""HBM ledger + pre-flight fit checks for the paged decode engine.

A PagedDecodeEngine configuration that cannot fit HBM used to OOM at
first dispatch — after compile, mid-request, with a driver error that
names no knob.  Round-14 accounts for the three HBM consumers UP FRONT
(in the spirit of "Memory Safe Computations with XLA", arxiv
2206.14148) so an unfittable ``(num_blocks, chain_steps, max_batch)``
is rejected at CONSTRUCTION with the budget and the largest fitting
alternative named:

- **params**: the decoder weights, per tensor-parallel shard;
- **KV pool**: BlockPool's stacked K/V arrays — the same per-shard
  formula as PR 4's ``shard_hbm_bytes`` gauge; for a hybrid block family
  over the attention layers and K/V heads only, with the conv slot arena
  beside it (``conv_bytes``); for a family with sliding-window layers over
  its full-attention layers only, with the window layers' pool, sized by
  the batch and the window, beside it (``window_bytes``); for a family with
  latent-attention and delta-rule layers the latent pool (one array, in the
  latent layers only), with the conv inputs (``conv_bytes``) and the matrix
  states (``state_bytes``, f32) of the slot arena beside it;
- **step temps**: the transient working set of the largest step
  program.  When the program registry (obs/profiler.py) already holds
  a MEASURED ``memory_analysis()`` temp watermark for the engine's
  programs, that is used; otherwise an analytic estimate covering the
  reference path's gathered K/V copy, the score matrix, the packed
  activation stream and the logits head.

The budget resolves from (in order) an explicit argument, the
``PW_HBM_BUDGET_BYTES`` env, or the device's ``memory_stats()`` limit
on a TPU backend (a TPU that reports no limit raises).  On the CPU backend
no budget is known: ``hbm_plan`` still reports the ledger but ``fits`` is
not enforced.
"""

from __future__ import annotations

import dataclasses
import os


def resolve_budget(explicit: int | None = None) -> tuple[int | None, str]:
    """(budget_bytes | None, source)."""
    if explicit:
        return int(explicit), "explicit"
    env = os.environ.get("PW_HBM_BUDGET_BYTES")
    if env:
        try:
            return int(float(env)), "env:PW_HBM_BUDGET_BYTES"
        except ValueError:
            pass
    import jax

    if jax.default_backend() == "tpu":
        # on the chip the budget is never "unenforced": a device that
        # does not report its limit is an error, not a free pass
        lim = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        if not lim:
            raise RuntimeError(
                "TPU backend reports no memory_stats()['bytes_limit']; "
                "pass hbm_budget_bytes or set PW_HBM_BUDGET_BYTES"
            )
        return int(lim), "device:memory_stats"
    return None, "none"


def _dtype_itemsize(dtype) -> int:
    import numpy as np

    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        # jax dtypes like bfloat16 that numpy cannot parse directly
        return int(getattr(dtype, "itemsize", None)
                   or getattr(dtype, "dtype", np.dtype("float32")).itemsize)


def _params_bytes(cfg, params, tp: int, itemsize: int) -> int:
    """Per-shard parameter bytes: exact when the live pytree is given
    (its leaves may already be sharded jax arrays — global sizes divided
    by tp approximate the per-shard slice; replicated biases are noise
    at this scale), analytic from the config otherwise.  Each leaf is
    billed at its OWN dtype width — an int8 decode plan's quantized
    weights count one byte each (Round-17), so the ledger the engine
    builds from its dispatch pytree reflects the true quantized
    footprint, not the f32 checkpoint's."""
    if params is not None:
        try:
            import jax

            total = sum(
                l.size * _dtype_itemsize(l.dtype)
                for l in jax.tree_util.tree_leaves(params)
                if hasattr(l, "size")
            )
            return int(total // max(tp, 1))
        except Exception:  # noqa: BLE001 - fall through to analytic
            pass
    if hasattr(cfg, "param_count"):  # a family that counts its own
        return int(cfg.param_count() * itemsize // max(tp, 1))
    d, v, ff, ln = cfg.d_model, cfg.vocab_size, cfg.d_ff, cfg.n_layers
    n = v * d + cfg.max_len * d + ln * (4 * d * d + 2 * d * ff + 9 * d) \
        + 2 * d
    return int(n * itemsize // max(tp, 1))


def kv_pool_bytes(cfg, *, num_blocks: int, block_size: int, tp: int,
                  itemsize: int) -> int:
    """K + V bytes held by EACH shard — BlockPool.per_shard_bytes
    computed from the configuration before the pool exists."""
    if hasattr(cfg, "latent_lanes"):
        # a latent pool (kvcache/hybrid.py StateCache): one array, a stored
        # row key and value both, in the latent-attention layers only
        return len(cfg.mla_layers) * num_blocks * block_size \
            * cfg.latent_lanes * itemsize
    layers, kv_heads, hd = _kv_geometry(cfg)
    heads = max(kv_heads // max(tp, 1), 1)
    return layers * num_blocks * block_size * heads * _kv_lanes(cfg, hd) \
        * itemsize


def _kv_lanes(cfg, hd: int) -> int:
    """A K row's and a V row's lanes a head, together: twice ``head_dim``,
    or a V head of the width the family gives its cache beside it."""
    return hd + (_arenas(cfg, 0).get("v_head_dim") or hd)


def _kv_geometry(cfg) -> tuple:
    """(layers whose K/V the block pool keeps, K/V heads, head_dim): all
    layers and all heads for the plain decoder; a hybrid family keeps K/V
    in its attention layers only, a family with sliding-window layers
    keeps its full-attention layers' there (the window layers' pool:
    :func:`window_pool_bytes`); both have fewer K/V heads than query
    heads."""
    hd = getattr(cfg, "head_dim", cfg.d_model // cfg.n_heads)
    pooled = getattr(cfg, "attn_layers", getattr(cfg, "full_layers", None))
    return (cfg.n_layers if pooled is None else len(pooled),
            getattr(cfg, "n_kv_heads", cfg.n_heads), hd)


def _arenas(cfg, max_batch_size: int) -> dict:
    """What the family hands its cache (models/families.py
    ``cache_kwargs``): the one place a slot arena's geometry is written."""
    from ..models.families import step_family

    return step_family(cfg).cache_kwargs(cfg, max_batch_size, 0)


def conv_arena_bytes(cfg, *, max_batch_size: int, itemsize: int) -> int:
    """The conv slot arena of a hybrid family (kvcache/hybrid.py): the
    carried inputs (``conv_taps`` vectors of ``conv_width``, two where the
    family does not say) a conv layer for every batch row and the null
    slot.  0 for a family without conv layers."""
    kw = _arenas(cfg, max_batch_size)
    if "conv_layers" not in kw:
        return 0
    return kw["conv_layers"] * (kw["conv_slots"] + 1) \
        * kw.get("conv_taps", 2) * kw["conv_width"] * itemsize


def state_arena_bytes(cfg, *, max_batch_size: int) -> int:
    """The matrix-state arena of a family with delta-rule layers
    (kvcache/hybrid.py StateCache): ``heads x dk x dv`` in f32 a layer for
    every batch row and the null slot.  0 for a family without such
    layers."""
    kw = _arenas(cfg, max_batch_size)
    if "state_heads" not in kw:
        return 0
    return kw["conv_layers"] * (kw["conv_slots"] + 1) * kw["state_heads"] \
        * kw["state_dk"] * kw["state_dv"] * 4


def window_pool_bytes(cfg, *, max_batch_size: int, block_size: int,
                      round_tokens: int, itemsize: int) -> int:
    """The sliding-window layers' K/V pool of a windowed family
    (kvcache/windowed.py): sized exactly by the batch, the window and the
    most a round adds to a sequence, not by ``num_blocks``.  0 for a
    family without window layers."""
    layers = len(getattr(cfg, "window_layers", ()))
    if not layers:
        return 0
    from ..kvcache.windowed import window_pool_blocks

    _l, kv_heads, hd = _kv_geometry(cfg)
    # a window pool of its own K/V heads, each pool at its own width
    kv_heads = _arenas(cfg, max_batch_size).get("window_heads") or kv_heads
    blocks = window_pool_blocks(cfg.sliding_window, round_tokens, block_size,
                                max_batch_size)
    return layers * blocks * block_size * kv_heads * _kv_lanes(cfg, hd) \
        * itemsize


def _temp_bytes(cfg, *, num_blocks: int, block_size: int,
                max_batch_size: int, chain_steps: int, prefill_chunk: int,
                tp: int, itemsize: int, reference_attn: bool) -> int:
    """Analytic transient working set of the LARGEST step program (the
    ragged mixed step, or the chained program when its scan carries
    dominate).  Used when the registry has no measured watermark yet —
    construction time, before anything compiled."""
    B = max_batch_size
    C = max(prefill_chunk, 1)
    T = B + C
    d = cfg.d_model
    # a latent family folds every head on a stored row of latent_lanes
    hd = getattr(cfg, "latent_lanes", None) \
        or getattr(cfg, "head_dim", d // cfg.n_heads)
    heads = max(cfg.n_heads // max(tp, 1), 1)
    vocab = cfg.vocab_size // max(tp, 1)
    # a sequence's table can span at most the pool (minus the null block)
    nb_seq = min(-(-cfg.max_len // block_size),
                 max(num_blocks - 1, 1))
    ctx = nb_seq * block_size
    # the reference gather path materializes the gathered K/V copy
    # (B, NB*BS, H, hd) x2 per layer plus the (B, H, C, NB*BS) scores;
    # the Pallas kernel keeps both in VMEM (≈0 HBM temps)
    gather = (
        2 * B * ctx * heads * hd * itemsize + B * heads * C * ctx * 4
        if reference_attn else 0
    )
    # packed stream residuals (a family of expert layers only: no d_ff)
    acts = 6 * T * max(d, getattr(cfg, "d_ff", 0)) * itemsize
    # the rows' queries gathered (B, C, H, hd) for the ragged kernel, its
    # output the same (at a V head's width), each with a folded copy
    acts += 2 * B * C * heads * _kv_lanes(cfg, hd) * itemsize
    from ..models.families import step_family

    scan = getattr(step_family(cfg), "scan_items", None)
    if scan is not None:
        # the chunked scan's work items: five operands and the output,
        # every row wasting less than one item of the scan's chunk
        chunk, lanes = scan(cfg)
        acts += 6 * (T // chunk + B) * chunk * lanes * 4
    if getattr(cfg, "n_experts", 0):
        # routed pairs laid out by expert in whole row tiles (16 to 128
        # rows, the kernel's own rule): the gathered inputs, the experts'
        # hidden rows and their outputs
        from ..ops.moe import row_tile

        rows = T * cfg.top_k \
            + row_tile(T, cfg.top_k, cfg.n_experts) * cfg.n_experts
        acts += rows * (2 * d + cfg.d_ff_expert) * itemsize
    logits = B * vocab * 4  # f32 head output
    chain = B * max(chain_steps, 1) * 4 * 2  # [B, K] ids carry + stack
    return int(gather + acts + logits + chain)


@dataclasses.dataclass
class HbmPlan:
    """The ledger for one engine configuration.  ``fits`` is only
    meaningful when ``budget_bytes`` resolved; ``fits_with`` re-plans
    with overrides (the pre-flight what-if the auto-planner queries)."""

    params_bytes: int
    kv_bytes: int
    temp_bytes: int
    temp_source: str
    budget_bytes: int | None
    budget_source: str
    num_blocks: int
    block_size: int
    max_batch_size: int
    chain_steps: int
    prefill_chunk: int
    tp: int
    # a hybrid family's conv slot arena (0 elsewhere): fixed by the batch,
    # not by num_blocks
    conv_bytes: int = 0
    # a windowed family's pool of the sliding-window layers (0 elsewhere):
    # fixed by the batch and the window, not by num_blocks
    window_bytes: int = 0
    # a delta-rule family's matrix-state arena (0 elsewhere): fixed by the
    # batch, not by num_blocks
    state_bytes: int = 0
    _replan: "object" = dataclasses.field(default=None, repr=False)

    @property
    def total_bytes(self) -> int:
        return self.params_bytes + self.kv_bytes + self.conv_bytes \
            + self.window_bytes + self.state_bytes + self.temp_bytes

    @property
    def fits(self) -> bool:
        return self.budget_bytes is None or \
            self.total_bytes <= self.budget_bytes

    @property
    def per_block_bytes(self) -> int:
        return self.kv_bytes // max(self.num_blocks, 1)

    def fits_with(self, *, num_blocks: int | None = None,
                  chain_steps: int | None = None,
                  max_batch_size: int | None = None) -> bool:
        """Would ``(num_blocks, chain_steps, max_batch)`` fit the same
        budget? — the check PagedDecodeEngine runs before allocating."""
        return self.with_(
            num_blocks=num_blocks, chain_steps=chain_steps,
            max_batch_size=max_batch_size,
        ).fits

    def with_(self, *, num_blocks: int | None = None,
              chain_steps: int | None = None,
              max_batch_size: int | None = None) -> "HbmPlan":
        return self._replan(
            num_blocks=(self.num_blocks if num_blocks is None
                        else int(num_blocks)),
            chain_steps=(self.chain_steps if chain_steps is None
                         else int(chain_steps)),
            max_batch_size=(self.max_batch_size if max_batch_size is None
                            else int(max_batch_size)),
        )

    def max_fitting_num_blocks(self) -> int | None:
        """Largest ``num_blocks`` that fits at the current chain/batch
        (temp depends weakly on num_blocks through the max table span,
        so the closed form is verified and walked down if needed)."""
        if self.budget_bytes is None:
            return self.num_blocks
        per_block = max(self.per_block_bytes, 1)
        nb = (self.budget_bytes - self.params_bytes - self.conv_bytes
              - self.window_bytes - self.state_bytes
              - self.temp_bytes) // per_block
        nb = min(int(nb), self.num_blocks)
        while nb >= 2 and not self.with_(num_blocks=nb).fits:
            nb -= max(nb // 8, 1)
        return nb if nb >= 2 else None

    def largest_fitting(self) -> dict | None:
        """The largest fitting alternative the rejection message names:
        first shrink ``num_blocks``; if even a minimal pool cannot fit,
        shrink ``max_batch_size`` then ``chain_steps`` too."""
        nb = self.max_fitting_num_blocks()
        if nb is not None:
            return {"num_blocks": nb, "chain_steps": self.chain_steps,
                    "max_batch_size": self.max_batch_size,
                    "total_bytes": self.with_(num_blocks=nb).total_bytes}
        for batch in (self.max_batch_size // 2, 2, 1):
            if batch < 1:
                continue
            for k in (self.chain_steps, 1):
                alt = self.with_(max_batch_size=batch, chain_steps=k)
                nb = alt.max_fitting_num_blocks()
                if nb is not None:
                    return {"num_blocks": nb, "chain_steps": k,
                            "max_batch_size": batch,
                            "total_bytes":
                                alt.with_(num_blocks=nb).total_bytes}
        return None

    def reject_message(self) -> str:
        mb = 1024 * 1024
        alt = self.largest_fitting()
        alt_txt = (
            f"largest fitting alternative: num_blocks={alt['num_blocks']} "
            f"(chain_steps={alt['chain_steps']}, "
            f"max_batch_size={alt['max_batch_size']}) at "
            f"{alt['total_bytes'] / mb:.1f}MB"
            if alt else
            "no (num_blocks, chain_steps, max_batch) configuration fits"
        )
        return (
            f"engine configuration cannot fit HBM: params "
            f"{self.params_bytes / mb:.1f}MB + KV pool "
            f"{self.kv_bytes / mb:.1f}MB ({self.num_blocks} blocks x "
            f"{self.block_size} tokens, tp={self.tp}) + conv arena "
            f"{self.conv_bytes / mb:.1f}MB + window pool "
            f"{self.window_bytes / mb:.1f}MB + state arena "
            f"{self.state_bytes / mb:.1f}MB + step temps "
            f"{self.temp_bytes / mb:.1f}MB ({self.temp_source}) = "
            f"{self.total_bytes / mb:.1f}MB > HBM budget "
            f"{self.budget_bytes / mb:.1f}MB ({self.budget_source}); "
            f"{alt_txt}"
        )

    def as_dict(self) -> dict:
        return {
            "params_bytes": self.params_bytes,
            "kv_bytes": self.kv_bytes,
            "conv_bytes": self.conv_bytes,
            "window_bytes": self.window_bytes,
            "state_bytes": self.state_bytes,
            "temp_bytes": self.temp_bytes,
            "temp_source": self.temp_source,
            "total_bytes": self.total_bytes,
            "budget_bytes": self.budget_bytes,
            "budget_source": self.budget_source,
            "fits": self.fits,
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "max_batch_size": self.max_batch_size,
            "chain_steps": self.chain_steps,
            "prefill_chunk": self.prefill_chunk,
            "tp": self.tp,
        }


def hbm_plan(cfg, *, num_blocks: int, block_size: int,
             max_batch_size: int = 8, chain_steps: int = 8,
             prefill_chunk: int | None = None, tp: int = 1, dtype=None,
             params=None, budget_bytes: int | None = None,
             reference_attn: bool = True) -> HbmPlan:
    """Build the HBM ledger for one engine configuration.

    ``params`` (the live pytree) makes the weights term exact;
    ``dtype`` defaults to float32.  The temp watermark prefers a
    MEASURED ``memory_analysis()`` value from the program registry when
    one is already cached (a warmed engine re-planning), else the
    analytic estimate."""
    import numpy as np

    itemsize = _dtype_itemsize(dtype) if dtype is not None \
        else np.dtype("float32").itemsize
    budget, budget_source = resolve_budget(budget_bytes)
    pchunk = int(prefill_chunk) if prefill_chunk else 2 * int(block_size)
    pb = _params_bytes(cfg, params, tp, itemsize)

    def _measured_temp(num_blocks: int) -> int | None:
        """Registry watermark restricted to THIS geometry: the step
        programs' buckets carry the pool shape, so another model's (or
        pool size's) measured temps never inflate this fit check."""
        try:
            from . import profiler as _profiler

            hd = cfg.d_model // cfg.n_heads
            # the pool array's GLOBAL shape: BlockPool allocates full
            # n_heads even under tp (sharding splits the head axis but
            # jax arrays — and so the bucket labels — report global dims)
            pool_sig = (
                f"[{cfg.n_layers},{num_blocks},{int(block_size)},"
                f"{cfg.n_heads},{hd}]"
            )
            return _profiler.registry().max_temp_bytes(
                prefix="pw.", bucket_contains=pool_sig,
            )
        except Exception:  # noqa: BLE001
            return None

    def _build(*, num_blocks: int, chain_steps: int,
               max_batch_size: int) -> HbmPlan:
        measured = _measured_temp(num_blocks)
        kv = kv_pool_bytes(cfg, num_blocks=num_blocks,
                           block_size=int(block_size), tp=tp,
                           itemsize=itemsize)
        analytic = _temp_bytes(
            cfg, num_blocks=num_blocks, block_size=int(block_size),
            max_batch_size=max_batch_size, chain_steps=chain_steps,
            prefill_chunk=pchunk, tp=tp, itemsize=itemsize,
            reference_attn=reference_attn,
        )
        temp, source = (
            (max(measured, analytic), "measured+analytic")
            if measured else (analytic, "analytic")
        )
        plan = HbmPlan(
            params_bytes=pb, kv_bytes=kv, temp_bytes=temp,
            temp_source=source, budget_bytes=budget,
            budget_source=budget_source, num_blocks=int(num_blocks),
            block_size=int(block_size),
            max_batch_size=int(max_batch_size),
            chain_steps=int(chain_steps), prefill_chunk=pchunk, tp=tp,
            conv_bytes=conv_arena_bytes(
                cfg, max_batch_size=int(max_batch_size), itemsize=itemsize),
            window_bytes=window_pool_bytes(
                cfg, max_batch_size=int(max_batch_size),
                block_size=int(block_size),
                round_tokens=max(-(-pchunk // int(block_size))
                                 * int(block_size), int(chain_steps)),
                itemsize=itemsize),
            state_bytes=state_arena_bytes(
                cfg, max_batch_size=int(max_batch_size)),
        )
        plan._replan = _build
        return plan

    return _build(num_blocks=int(num_blocks),
                  chain_steps=max(1, int(chain_steps)),
                  max_batch_size=int(max_batch_size))


# documented fallback shapes for hosts where NO HBM budget resolves (the
# CPU fallback with no env override): with nothing to fit against, the
# what-if ladder has no signal, so the choice degrades to these — the
# same shapes the engine hand-set before Round-17
ENGINE_DEFAULTS = {
    "num_blocks": 256, "block_size": 16,
    "max_batch_size": 8, "chain_steps": 8,
}

_BATCH_LADDER = (16, 8, 4, 2, 1)
_CHAIN_LADDER = (16, 8, 4, 1)
# prefill chunk rungs (tokens; rounded up to whole blocks).  The top and
# the ridge share below are fixed from one sweep of 64 / 128 / 256 / 512 in
# serve_closed16 and lfm2_closed16 (PERF.md section 6, PR 34)
# and checked by one of 128 / 256 / 512 in phi4flash_closed16_longshort, the
# first cell whose bill has rows-only layers (PERF.md section 6)
_CHUNK_LADDER = (512, 256, 128, 64, 32)
# the share of the step's streaming time its matmuls may fill: at 1.0 a
# step of max_batch_size rows and a chunk of tokens (:func:`step_flops`)
# sits on the chip's ridge
_CHUNK_RIDGE_SHARE = 1.0


def resolve_roof(explicit: dict | None = None) -> tuple[dict | None, str]:
    """(``{"peak": FLOP/s, "membw": bytes/s}`` | None, source): the roof a
    prefill chunk is sized against.  An explicit one (a described chip),
    else on a TPU backend the published row of the device's kind
    (``obs/profiler._roofline``); a kind that is not listed, and every
    other backend, resolve none: the CPU's roof is a probe, and nothing is
    measured at engine build."""
    if explicit:
        return {"peak": float(explicit["peak"]),
                "membw": float(explicit["membw"])}, "explicit"
    import jax

    if jax.default_backend() != "tpu":
        return None, "none"
    from .profiler import _roofline

    roof = _roofline()
    if roof["peak"] and roof["membw"]:
        return {"peak": roof["peak"], "membw": roof["membw"]}, roof["source"]
    return None, roof["source"]


def step_flops(cfg, params, *, rows: int, tokens: int, tp: int = 1) -> int:
    """Matmul FLOPs of a step of ``rows`` rows and ``tokens`` prompt tokens,
    per shard, from the decode plan's own leaves as the ledger bills them:
    2 an element of every leaf of two axes or more; a routed expert layer
    (three axes, as many leading entries as the configuration holds
    experts) at the share of its experts a token is routed to.  Every leaf
    is billed on the rows; on the tokens, all but the leaves of the layers
    the configuration's ``rows_only_layers`` names (``params["layers"][i]``:
    a cross-decoder the mixed program runs over the rows' emitting tokens
    alone).  With none declared, ``(rows + tokens) x`` the bill of one
    token.  Without the pytree, twice the ledger's parameter count a row
    or token."""
    if params is None:  # a byte a parameter
        return (rows + tokens) * 2 * _params_bytes(cfg, None, tp, 1)
    import jax

    # the experts whose matrices the plan holds (a share of an
    # expert-parallel deployment holds fewer than the router chooses among)
    held = getattr(cfg, "held_experts", getattr(cfg, "n_experts", 0))
    share = getattr(cfg, "top_k", 0) / max(getattr(cfg, "n_experts", held), 1)

    def bill(tree) -> float:
        flops = 0.0
        for leaf in jax.tree_util.tree_leaves(tree):
            shape = getattr(leaf, "shape", ())
            if len(shape) < 2:
                continue
            routed = held and len(shape) == 3 and shape[0] == held
            flops += 2.0 * leaf.size * (share if routed else 1.0)
        return flops

    every = bill(params)
    rows_only = bill([params["layers"][i]
                      for i in getattr(cfg, "rows_only_layers", ())])
    tp = max(tp, 1)
    return rows * int(every // tp) + tokens * int((every - rows_only) // tp)


def choose_engine_config(cfg, *, params=None, tp: int = 1, dtype=None,
                         budget_bytes: int | None = None,
                         reference_attn: bool = True,
                         prefill_chunk: int | None = None,
                         num_blocks: int | None = None,
                         block_size: int | None = None,
                         max_batch_size: int | None = None,
                         chain_steps: int | None = None,
                         seq_buckets=None, roof: dict | None = None) -> dict:
    """Pick the engine shapes the caller left as ``None`` from HBM-ledger
    what-ifs (:meth:`HbmPlan.fits_with`) instead of hand-set defaults
    (Round-17).  Explicit values are honored verbatim — only the Nones
    are chosen.  The rule, in order, of the five shapes:

    - ``block_size``: the pool granularity every kernel/chunk rule is
      tiled for — not a fit question; 16 unless overridden.
    - ``max_batch_size``: the widest rung of (16, 8, 4, 2, 1) whose
      ledger fits with a one-sequence pool (batch width costs step
      temps and logits rows, not pool blocks).
    - ``chain_steps``: the longest rung of (16, 8, 4, 1) still fitting
      at that batch (the chain term is bytes-cheap: a [B, K] ids carry).
    - ``num_blocks``: full coverage — every batch row able to span
      ``cfg.max_len`` (plus the null block) — when that fits, else the
      ledger's ``max_fitting_num_blocks`` at the chosen batch/chain.
    - ``prefill_chunk``: chosen LAST, at the three shapes above and a
      two-block chunk, so that a wider chunk never costs a block of the
      pool or a rung of batch or chain.  The widest rung of (512, 256,
      128, 64, 32), in whole blocks, for which (1) the ledger still fits,
      with the mixed program's temporaries and a windowed family's window
      pool at that chunk, and the pool at full coverage; (2) the rung is
      no wider than the prompt cap (the largest of ``seq_buckets``, or
      ``cfg.max_len``); (3) the step stays on the bytes side of the chip's
      ridge: :func:`step_flops` of ``max_batch_size`` rows and the rung's
      tokens (every leaf on the rows; the layers a configuration runs over
      its rows' emitting tokens alone, its ``rows_only_layers``, on the
      rows only) over the roof's peak is no more than the bytes a step
      streams (the ledger's ``params_bytes``) over its bandwidth, so the
      chunk rides on weights the step reads anyway, and a plan of fewer
      bytes (int8, bf16) gets a narrower chunk than an f32 one of the same
      shapes.
      Where no budget or no roof resolves (:func:`resolve_roof`: the CPU),
      or no rung passes, two blocks, reported as a default.

    With no budget resolvable the ladder has no signal and the choice
    falls back to :data:`ENGINE_DEFAULTS` (reported as such).

    Returns a dict of the five resolved ints plus ``plan`` (a FRESH
    ledger built from the final values — the re-constructibility
    guarantee: anyone re-running ``hbm_plan`` with these numbers gets
    the same fitting verdict), ``chosen`` (which names were auto-picked),
    ``source`` and ``chunk_source`` (how the chunk was arrived at).  Raises
    ``ValueError`` when a budget resolves but no configuration fits,
    mirroring the construction rejection path."""
    chosen = [name for name, v in (
        ("num_blocks", num_blocks), ("block_size", block_size),
        ("max_batch_size", max_batch_size), ("chain_steps", chain_steps),
        ("prefill_chunk", prefill_chunk),
    ) if v is None]
    bs = int(block_size) if block_size else ENGINE_DEFAULTS["block_size"]
    budget, budget_source = resolve_budget(budget_bytes)

    def ledger(nb: int, k: int, b: int, chunk: int | None = None) -> HbmPlan:
        return hbm_plan(
            cfg, num_blocks=nb, block_size=bs, max_batch_size=b,
            chain_steps=k, prefill_chunk=chunk or prefill_chunk, tp=tp,
            dtype=dtype, params=params, budget_bytes=budget_bytes,
            reference_attn=reference_attn,
        )

    def result(plan: HbmPlan, chunk_source: str, source: str) -> dict:
        return {
            "num_blocks": plan.num_blocks, "block_size": bs,
            "max_batch_size": plan.max_batch_size,
            "chain_steps": plan.chain_steps,
            "prefill_chunk": plan.prefill_chunk, "plan": plan,
            "chosen": chosen, "source": source, "chunk_source": chunk_source,
        }

    if budget is None:
        nb = int(num_blocks) if num_blocks else \
            ENGINE_DEFAULTS["num_blocks"]
        b = int(max_batch_size) if max_batch_size else \
            ENGINE_DEFAULTS["max_batch_size"]
        k = max(1, int(chain_steps) if chain_steps else
                ENGINE_DEFAULTS["chain_steps"])
        return result(
            ledger(nb, k, b),  # an unset chunk is two blocks there
            "explicit" if prefill_chunk
            else "default: two blocks (no HBM budget resolved)",
            "defaults (no HBM budget resolved)")

    blocks_per_seq = -(-cfg.max_len // bs)
    min_nb = blocks_per_seq + 1  # one full-length sequence + null block
    if max_batch_size is None:
        max_batch_size = next(
            (b for b in _BATCH_LADDER if ledger(min_nb, 1, b).fits), 1
        )
    b = int(max_batch_size)
    if chain_steps is None:
        chain_steps = next(
            (k for k in _CHAIN_LADDER if ledger(min_nb, k, b).fits), 1
        )
    k = max(1, int(chain_steps))
    covered = True
    if num_blocks is None:
        want = b * blocks_per_seq + 1
        probe = ledger(want, k, b)
        if probe.fits:
            num_blocks = want
        else:
            covered = False
            num_blocks = probe.max_fitting_num_blocks()
            if num_blocks is None or num_blocks < 2:
                raise ValueError(probe.reject_message())
    nb = int(num_blocks)
    if prefill_chunk:
        chunk, chunk_source = int(prefill_chunk), "explicit"
    else:
        chunk, chunk_source = _choose_chunk(
            cfg, ledger(nb, k, b), lambda c: ledger(nb, k, b, c).fits,
            params=params, covered=covered, seq_buckets=seq_buckets,
            roof=roof)
    final = ledger(nb, k, b, chunk)
    if set(chosen) - {"prefill_chunk"} and not final.fits:
        # an auto-chosen shape must never need the clamp/reject path —
        # the what-ifs above already proved it against the same ledger
        # (a chosen chunk alone decides nothing: it is two blocks, as an
        # unset one always was, unless the ledger fits at a wider rung)
        raise AssertionError(
            "auto-chosen engine config failed its own re-constructed "
            "fit check: " + final.reject_message()
        )
    return result(final, chunk_source,
                  f"hbm_plan.fits_with what-ifs ({budget_source})")


def _choose_chunk(cfg, plan: HbmPlan, fits, *, params, covered: bool,
                  seq_buckets, roof: dict | None) -> tuple[int, str]:
    """The ``prefill_chunk`` rule of :func:`choose_engine_config` at the
    shapes of ``plan`` (built at a two-block chunk): ``(tokens, how)``.
    ``fits(chunk)``: the ledger's verdict at these shapes and that chunk;
    ``covered``: the pool spans every row's full length."""
    bs = plan.block_size
    two_blocks = 2 * bs
    roof, roof_source = resolve_roof(roof)
    if roof is None:
        return two_blocks, f"default: two blocks (no device roof: " \
            f"{roof_source})"
    if not covered:
        return two_blocks, "default: two blocks (the pool is short of " \
            "full coverage: no bytes to spare for a wider step)"
    cap = min(max(seq_buckets), cfg.max_len) if seq_buckets \
        else cfg.max_len
    b, peak = plan.max_batch_size, roof["peak"]

    def step_s(rows: int, tokens: int) -> float:
        return step_flops(cfg, params, rows=rows, tokens=tokens,
                          tp=plan.tp) / peak

    row_s, token_s = step_s(1, 0), step_s(0, 1)
    # the rows-only layers billed on the batch's rows alone, where declared
    bill = f"{b} + " if row_s == token_s else f"{b} rows x " \
        f"{row_s * 1e6:.2f} us + "
    stream_s = _CHUNK_RIDGE_SHARE * plan.params_bytes / roof["membw"]
    for rung in _CHUNK_LADDER:
        rung = -(-rung // bs) * bs
        if rung <= two_blocks:
            break
        if rung <= cap and fits(rung) and step_s(b, rung) <= stream_s:
            return rung, (
                f"ridge: {bill}{rung} tokens x {token_s * 1e6:.2f} us of "
                f"matmuls <= {stream_s * 1e3:.2f} ms of streamed weights "
                f"({roof_source}); prompt cap {cap}; ledger fits")
    return two_blocks, "default: two blocks (no wider rung passes the " \
        "ledger, the prompt cap and the ridge)"
