"""Drives one run of ``run.py`` for the ``lfm2_moe`` cell with a fault
planted in the program underneath, and prints what ``correct`` came to.

    python3 benchmark/tests/drive_lfm2.py <fault|none> <run.py arguments ...> [--measure]

Without ``--measure`` a rehearsal (toy widths, any platform); with it the
cell's own size on the chip, which is how the upper readings of the
configuration's limits were taken.  Each fault is a way an ``lfm2_moe``
step can be subtly wrong while every request still completes:

- ``top_k_less_one``: one expert layer (the third) keeps one expert fewer
  than ``num_experts_per_tok`` (top-3 in place of top-4), the rest
  renormalised;
- ``not_renormalised``: the chosen experts' weights are the raw scores;
- ``conv_state_dropped``: a sequence's conv state is not carried over one
  chunk boundary - the one before its prompt's last chunk;
- ``rope_off_by_one``: the keys a prefill chunk writes are rotated for the
  position after their own;
- ``token_altered_once``: one served token altered once, mid-window
  (``drive.py``'s).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


N_EXPERT_LAYERS = 0  # of the configuration as it will run; set by main()


def _route_patched(change) -> None:
    """``ops.moe.route`` with ``change(call index, experts, weights,
    scores, kwargs) -> weights``.  A step program's trace calls ``route``
    once an expert layer, in layer order."""
    from pathway_tpu.ops import moe

    route, calls = moe.route, {"n": 0}

    def patched(h, wg, bias, **kw):
        experts, weights, scores = route(h, wg, bias, **kw)
        weights = change(calls["n"], experts, weights, scores, kw)
        calls["n"] += 1
        return experts, weights, scores

    moe.route = patched


def top_k_less_one() -> None:
    import jax.numpy as jnp

    def change(call, experts, weights, scores, kw):
        if call % N_EXPERT_LAYERS != 2:
            return weights
        # route() orders the chosen by selection score: the last is the
        # one a top-(k-1) would have left out
        kept = jnp.take_along_axis(scores, experts, axis=1).at[:, -1].set(0.0)
        if kw.get("norm_topk", True):
            kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-6)
        return kept * kw.get("scale", 1.0)

    _route_patched(change)


def not_renormalised() -> None:
    import jax.numpy as jnp

    def change(call, experts, weights, scores, kw):
        return jnp.take_along_axis(scores, experts, axis=1) \
            * kw.get("scale", 1.0)

    _route_patched(change)


def conv_state_dropped() -> None:
    from pathway_tpu.kvcache.engine import PagedDecodeEngine

    build = PagedDecodeEngine._build_mixed

    def dropped(self, reserved, chunks, ph):
        step = build(self, reserved, chunks, ph)
        for act, _row, filled in step[2]:
            if filled >= 0 and act.n_filled > 0 \
                    and filled == len(act.tokens):
                slot = self.pool.slot(act.seq_id)
                self.pool.conv = self.pool.conv.at[:, slot].set(0)
        return step

    PagedDecodeEngine._build_mixed = dropped


def rope_off_by_one() -> None:
    from pathway_tpu.models import lfm2

    rope, mixed = lfm2._rope, lfm2.hybrid_mixed_step
    state = {"mixed": False, "calls": 0}

    def shifted(x, positions, theta):
        state["calls"] += 1
        # _forward rotates q, then k, in every attention layer
        if state["mixed"] and state["calls"] % 2 == 0:
            positions = positions + 1
        return rope(x, positions, theta)

    def mixed_step(*args, **kw):
        state["mixed"], state["calls"] = True, 0
        try:
            return mixed(*args, **kw)
        finally:
            state["mixed"] = False

    lfm2._rope = shifted
    lfm2.hybrid_mixed_step = mixed_step


def token_altered_once() -> None:
    import drive

    drive.token_altered_once()


FAULTS = {"none": lambda: None, "top_k_less_one": top_k_less_one,
          "not_renormalised": not_renormalised,
          "conv_state_dropped": conv_state_dropped,
          "rope_off_by_one": rope_off_by_one,
          "token_altered_once": token_altered_once}


def main() -> int:
    from benchmark import run

    rest = sys.argv[2:]
    measure = "--measure" in rest
    if measure:
        rest.remove("--measure")
    else:
        rest = rest + ["--rehearse"]
    # the expert layers of the configuration as it will run, for the fault
    # that names one of them
    config = run.load_json(run.HERE, "configs", "lfm2-8b-a1b-serve.json")
    if not measure:
        config = run.merged(config, config["rehearse"])
    global N_EXPERT_LAYERS
    N_EXPERT_LAYERS = config["num_hidden_layers"] \
        - config["num_dense_layers"]
    FAULTS[sys.argv[1]]()
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
