"""Drives one run of ``run.py`` for the ``afmoe`` cell with a fault planted
in the program underneath, and prints what ``correct`` came to.

    python3 benchmark/tests/drive_afmoe.py <fault|none> <run.py arguments ...> [--measure]

Without ``--measure`` a rehearsal (toy widths, any platform); with it the
cell's own size on the chip, which is how the upper readings of the
configuration's limits were taken.  Each fault is a way an ``afmoe`` step
can be subtly wrong while every request still completes:

- ``window_ignored``: the sliding-window layers attend their whole
  context (through tables whose entries behind the window are gone);
- ``window_block_freed_early``: a window block goes back to its free list
  one block before the last query that sees it has run;
- ``rope_on_full_layers``: the full-attention layers rotate q and k too;
- ``gate_left_out``: the attention output is not gated;
- ``shared_expert_left_out``: the routed sum alone;
- ``top_k_less_one``: one expert layer (the third) keeps one expert fewer
  than ``num_experts_per_tok``, the rest renormalised (``drive_lfm2``'s);
- ``route_scale_left_out``: the routed weights without ``route_scale``;
- ``token_altered_once``: one served token altered once, mid-window
  (``drive.py``'s).
"""

from __future__ import annotations

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import drive_lfm2  # noqa: E402 - the router's seam and drive.py's fault


def window_ignored() -> None:
    pa = importlib.import_module("pathway_tpu.kvcache.paged_attention")

    ragged, append = pa.paged_attention, pa.paged_append_attend
    pa.paged_attention = lambda *a, window=None, **kw: ragged(*a, **kw)
    pa.paged_append_attend = lambda *a, window=None, **kw: append(*a, **kw)


def window_block_freed_early() -> None:
    from pathway_tpu.kvcache.windowed import WindowedCache

    dead = WindowedCache.dead_blocks
    WindowedCache.dead_blocks = lambda self, nxt: dead(
        self, nxt + self.block_size)


def rope_on_full_layers() -> None:
    from pathway_tpu.models import afmoe

    rotary = afmoe._rotary
    afmoe._rotary = lambda kind, *a: rotary(afmoe.SLIDING, *a)


def gate_left_out() -> None:
    from pathway_tpu.models import afmoe

    afmoe._gated = lambda a, gate, dtype: a.astype(dtype)


def shared_expert_left_out() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import afmoe

    swiglu = afmoe._swiglu

    def routed_only(lay, h):
        y = swiglu(lay, h)
        # the shared expert is the one SwiGLU handed a dict of its own
        return jnp.zeros_like(y) if set(lay) == {"w1", "w3", "w2"} else y

    afmoe._swiglu = routed_only


def route_scale_left_out() -> None:
    drive_lfm2._route_patched(
        lambda call, experts, weights, scores, kw:
        weights / kw.get("scale", 1.0))


FAULTS = {"none": lambda: None, "window_ignored": window_ignored,
          "window_block_freed_early": window_block_freed_early,
          "rope_on_full_layers": rope_on_full_layers,
          "gate_left_out": gate_left_out,
          "shared_expert_left_out": shared_expert_left_out,
          "top_k_less_one": drive_lfm2.top_k_less_one,
          "route_scale_left_out": route_scale_left_out,
          "token_altered_once": drive_lfm2.token_altered_once}


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
    config = run.load_json(run.HERE, "configs", "trinity-mini-serve.json")
    if not measure:
        config = run.merged(config, config["rehearse"])
    drive_lfm2.N_EXPERT_LAYERS = config["num_hidden_layers"] \
        - config["num_dense_layers"]
    FAULTS[sys.argv[1]]()
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
