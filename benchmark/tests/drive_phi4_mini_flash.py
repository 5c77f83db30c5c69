"""Drives one run of ``run.py`` for the ``phi4_mini_flash`` cell with a
fault planted in the program underneath, and prints what ``correct`` came
to.

    python3 benchmark/tests/drive_phi4_mini_flash.py <fault|none> <run.py arguments ...> [--measure]

Without ``--measure`` a rehearsal (toy widths, any platform); with it the
cell's own size on the chip, which is how the upper readings of the
configuration's limits were taken.  Each fault is a way a
``phi4_mini_flash`` step can be subtly wrong while every request still
completes:

- ``bf16_scan_state``: every scan state rounded to bf16 after each
  layer's update (the configuration's variant ``bf16_state``);
- ``conv_not_carried``: the Mamba layers' conv inputs not carried from a
  step to the next (each run starts its convolution from zeros, as a fresh
  sequence's first does);
- ``second_map_dropped``: the differential layers without ``lam`` times
  their second map;
- ``memory_from_layer_14``: the memory units read the scan output of the
  Mamba layer two below the published one (14 of 32);
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

import drive_lfm2  # noqa: E402 - drive.py's fault


def _model():
    from pathway_tpu.models import phi4_mini_flash

    return phi4_mini_flash


def bf16_scan_state() -> None:
    from benchmark.systems import serve_phi4_mini_flash

    serve_phi4_mini_flash.round_scan_states_in_program("bfloat16")


def conv_not_carried() -> None:
    m = _model()
    inputs = m._mamba_inputs
    m._mamba_inputs = lambda lay, cfg, h, conv_l, *rest: inputs(
        lay, cfg, h, conv_l * 0, *rest)


def second_map_dropped() -> None:
    m = _model()
    diff = m._differential
    m._differential = lambda a, lay, *rest: diff(
        a.at[:, a.shape[1] // 2:].set(0), lay, *rest)


def memory_from_layer_14() -> None:
    _model().Phi4MiniFlashConfig.memory_layer = property(
        lambda cfg: cfg.n_layers // 2 - 2)


FAULTS = {"none": lambda: None, "bf16_scan_state": bf16_scan_state,
          "conv_not_carried": conv_not_carried,
          "second_map_dropped": second_map_dropped,
          "memory_from_layer_14": memory_from_layer_14,
          "token_altered_once": drive_lfm2.token_altered_once}


def main() -> int:
    from benchmark import run

    rest = sys.argv[2:]
    if "--measure" in rest:
        rest.remove("--measure")
    else:
        rest = rest + ["--rehearse"]
    FAULTS[sys.argv[1]]()
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
