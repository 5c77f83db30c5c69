"""Drives one run of ``run.py`` for the ``kimi_linear`` cell with a fault
planted in the program underneath, and prints what ``correct`` came to.

    python3 benchmark/tests/drive_kimi_linear.py <fault|none> <run.py arguments ...> [--measure]

Without ``--measure`` a rehearsal (toy widths, any platform); with it the
cell's own size on the chip, which is how the upper readings of the
configuration's limits were taken.  Each fault is a way a ``kimi_linear``
step can be subtly wrong while every request still completes:

- ``decay_left_out``: the KDA state does not decay (``g = 0``);
- ``beta_left_out``: the delta rule's step size is 1;
- ``slot_not_reset``: a sequence's first chunk starts from what the slot's
  last owner left (a finished, longer sequence's state) and not from zero;
- ``padding_in_state``: a chunk's padded tokens are not masked out of the
  chunked scan's work items;
- ``conv_state_late``: the conv inputs a row leaves in its slot are one
  token late (those before its last token, not its last token's);
- ``k_r_left_out``: the latent scores leave out the shared ``k_r`` part;
- ``held_range_shifted``: the expert layer takes its held experts for the
  range one expert further on;
- ``shared_expert_left_out``: the routed sum alone;
- ``route_scale_left_out``: the routed weights without
  ``routed_scaling_factor``;
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

import drive_lfm2  # noqa: E402 - the router's seam and drive.py's fault


def decay_left_out() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import kimi_linear as m

    decay = m._log_decay
    m._log_decay = lambda *a: jnp.zeros_like(decay(*a))


def beta_left_out() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import kimi_linear as m

    beta = m._beta
    m._beta = lambda *a: jnp.ones_like(beta(*a))


def slot_not_reset() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import kimi_linear as m

    m._fresh_rows = lambda row_start: jnp.zeros(row_start.shape, bool)


def padding_in_state() -> None:
    from pathway_tpu.ops import kda

    kda._mask_padding = lambda y, ok: y


def conv_state_late() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import kimi_linear as m

    m._carried = lambda prev, u: jnp.stack(prev, axis=1)


def k_r_left_out() -> None:
    from pathway_tpu.models import kimi_linear as m

    absorbed = m._absorbed_query

    def without(qh, w_kb, nope, pad):
        q = absorbed(qh, w_kb, nope, pad)
        r = w_kb.shape[2]
        return q.at[..., r:].set(0)

    m._absorbed_query = without


def held_range_shifted() -> None:
    from pathway_tpu.ops import moe

    ffn = moe.expert_ffn

    def shifted(*a, first_expert=None, **kw):
        return ffn(*a, first_expert=None if first_expert is None
                   else first_expert + 1, **kw)

    moe.expert_ffn = shifted


def shared_expert_left_out() -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import kimi_linear as m

    swiglu = m._swiglu

    def routed_only(lay, h):
        y = swiglu(lay, h)
        # the shared expert is the one SwiGLU handed a dict of its own
        return jnp.zeros_like(y) if set(lay) == {"w1", "w3", "w2"} else y

    m._swiglu = routed_only


def route_scale_left_out() -> None:
    drive_lfm2._route_patched(
        lambda call, experts, weights, scores, kw:
        weights / kw.get("scale", 1.0))


FAULTS = {"none": lambda: None, "decay_left_out": decay_left_out,
          "beta_left_out": beta_left_out, "slot_not_reset": slot_not_reset,
          "padding_in_state": padding_in_state,
          "conv_state_late": conv_state_late, "k_r_left_out": k_r_left_out,
          "held_range_shifted": held_range_shifted,
          "shared_expert_left_out": shared_expert_left_out,
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
    FAULTS[sys.argv[1]]()
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
