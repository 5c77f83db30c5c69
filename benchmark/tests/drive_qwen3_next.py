"""Drives one run of ``run.py`` for the ``qwen3_next`` cell with a fault
planted in the program underneath, and prints what ``correct`` came to.

    python3 benchmark/tests/drive_qwen3_next.py <fault|none> <run.py arguments ...> [--measure]

Without ``--measure`` a rehearsal (toy widths, any platform); with it the
cell's own size on the chip, which is how the upper readings of the
configuration's limits were taken.  Each fault is a way a ``qwen3_next``
step can be subtly wrong while every request still completes:

- ``rotary_on_the_whole_head``: rotary on all of a head's values, not on
  its leading ``rotary_dim``;
- ``attention_gate_left_out``: the attention output without its sigmoid
  gate;
- ``decay_left_out``: the DeltaNet state does not decay (``exp(g) = 1``);
- ``scales_not_zero_centred``: the norm scales read as ``w``, not ``1 + w``;
- ``shared_gate_left_out``: the shared expert without its sigmoid gate;
- ``sigmoid_router``: sigmoid scores in the router where a softmax over
  all the logits belongs;
- ``key_head_modulo``: value head ``h`` reads key head ``h % Hk``, not
  ``h // (Hv / Hk)``;
- ``token_altered_once``: one served token altered once, mid-window
  (``drive.py``'s).

A delta-rule state kept in bf16 is not planted here: it is the
configuration's variant ``bf16_state`` (``run.py --variant``), the control
of ``state_gap``.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import drive_lfm2  # noqa: E402 - drive.py's fault


def rotary_on_the_whole_head() -> None:
    from pathway_tpu.models import qwen3_next as m

    rope = m._partial_rope
    m._partial_rope = lambda x, positions, theta, rot: rope(
        x, positions, theta, x.shape[-1])


def attention_gate_left_out() -> None:
    from pathway_tpu.models import qwen3_next as m

    m._gated = lambda a, gate, dtype: a.astype(dtype)


def decay_left_out() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import qwen3_next as m

    decay = m._log_decay
    m._log_decay = lambda *a: jnp.zeros_like(decay(*a))


def scales_not_zero_centred() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import qwen3_next as m

    m._scale = lambda w: w.astype(jnp.float32)


def shared_gate_left_out() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import qwen3_next as m

    gate = m._shared_gate
    m._shared_gate = lambda *a: jnp.ones_like(gate(*a))


def sigmoid_router() -> None:
    from pathway_tpu.ops import moe

    route = moe.route

    def sigmoid(h, wg, bias, **kw):
        kw.pop("score", None)
        return route(h, wg, bias, **kw)

    moe.route = sigmoid


def key_head_modulo() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import qwen3_next as m

    m._key_heads = lambda x, rep: jnp.tile(x, (1, rep, 1))


FAULTS = {"none": lambda: None,
          "rotary_on_the_whole_head": rotary_on_the_whole_head,
          "attention_gate_left_out": attention_gate_left_out,
          "decay_left_out": decay_left_out,
          "scales_not_zero_centred": scales_not_zero_centred,
          "shared_gate_left_out": shared_gate_left_out,
          "sigmoid_router": sigmoid_router,
          "key_head_modulo": key_head_modulo,
          "token_altered_once": drive_lfm2.token_altered_once}


def main() -> int:
    from benchmark import run

    rest = sys.argv[2:]
    measure = "--measure" in rest
    if measure:
        rest.remove("--measure")
    else:
        rest = rest + ["--rehearse"]
    FAULTS[sys.argv[1]]()
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
