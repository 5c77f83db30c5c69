"""Drives one run of ``run.py`` for the ``mimo_v2_flash`` cell with a fault
planted in the program underneath, and prints what ``correct`` came to.

    python3 benchmark/tests/drive_mimo_v2_flash.py <fault|none> <run.py arguments ...> [--measure]

Without ``--measure`` a rehearsal (toy widths, any platform); with it the
cell's own size on the chip, which is how the upper readings of the
configuration's limits were taken.  Each fault is a way a ``mimo_v2_flash``
step can be subtly wrong while every request still completes (each is a
reading of the published keys the configuration's ``assumed`` rules out):

- ``sink_left_out``: the sliding layers' softmax without its sink;
- ``sink_on_full_layers``: the full layers get a sink too, the one a layer
  would learn for the same share of a context ``max_len / window`` times as
  long (the next sliding layer's, plus ``log(max_len / window)``);
- ``window_127`` / ``window_129``: the window one key short, one key long;
- ``sliding_heads_by_16``: the sliding layers' query heads grouped as the
  full layers' are (head ``h`` on K/V head ``h // 16``: half of the K/V
  heads unread);
- ``rotary_on_all_192``: rotate-half over the whole head;
- ``thetas_swapped``: ``rope_theta`` on the sliding layers, ``swa_rope_theta``
  on the full ones;
- ``value_scale_left_out``: v as projected;
- ``scores_over_sqrt_128``: scores over the root of ``v_head_dim``;
- ``v_at_k_stride``: a V head read where a K head of the same number would
  start in its row (stride 192 over a row of 128 a head, wrapping);
- ``bias_in_the_weights``: the router's bias in the weights, not in the
  choice only (at the cell's size only ``router_weight_gap`` sees it: the
  chip holds a sixteenth of a token's experts);
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

import drive_lfm2  # noqa: E402 - drive.py's fault

CONFIG: dict = {}  # the configuration as it will run; set by main()


def _paged():
    return importlib.import_module("pathway_tpu.kvcache.paged_attention")


def _attention_patched(change) -> None:
    """The two attention entry points with ``change(kwargs) -> kwargs`` on a
    call's keywords (``window`` / ``sinks`` say which layer kind it is)."""
    pa = _paged()
    ragged, append = pa.paged_attention, pa.paged_append_attend
    pa.paged_attention = lambda *a, **kw: ragged(*a, **change(kw))
    pa.paged_append_attend = lambda *a, **kw: append(*a, **change(kw))


def sink_left_out() -> None:
    _attention_patched(lambda kw: {k: v for k, v in kw.items()
                                   if k != "sinks"})


def sink_on_full_layers() -> None:
    import numpy as np

    from pathway_tpu.models import mimo_v2_flash as m

    forward = m._forward
    shift = float(np.log(CONFIG["serve"]["max_len"]
                         / CONFIG["sliding_window"]))

    todo: list = []  # the sinks of the layers a trace has yet to attend

    def with_sinks(params, cfg, *args, **kw):
        later = [lay.get("sinks") for lay in params["layers"]]
        for i in range(len(later) - 2, -1, -1):  # the next sliding layer's
            later[i] = later[i] if later[i] is not None else later[i + 1]
        todo[:] = [s + shift for s in later]
        return forward(params, cfg, *args, **kw)

    m._forward = with_sinks
    pa = _paged()
    ragged, append = pa.paged_attention, pa.paged_append_attend

    def sunk(fn):
        def call(*a, **kw):
            sinks = todo.pop(0)  # _forward attends once a layer, in order
            return fn(*a, **kw) if "sinks" in kw \
                else fn(*a, **dict(kw, sinks=sinks))
        return call

    pa.paged_attention, pa.paged_append_attend = sunk(ragged), sunk(append)


def window_off_by(one: int):
    return lambda: _attention_patched(
        lambda kw: dict(kw, window=kw["window"] + one)
        if kw.get("window") is not None else kw)


def _sliding_kv_patched(change) -> None:
    """``change(k (T, KV, hd), v (T, KV, hd_v)) -> (k, v)`` on the new rows
    of the sliding layers (those of ``swa_num_key_value_heads`` heads), in
    the writer and in the fused append."""
    pa = _paged()
    write, append = pa.paged_write_rows, pa.paged_append_attend
    kv = CONFIG["swa_num_key_value_heads"]

    def written(k_pool, v_pool, sb, so, k_rows, v_rows, **kw):
        if k_rows.shape[1] == kv:
            k_rows, v_rows = change(k_rows, v_rows)
        return write(k_pool, v_pool, sb, so, k_rows, v_rows, **kw)

    def appended(q, k_new, v_new, *a, **kw):
        if k_new.shape[1] == kv:
            k_new, v_new = change(k_new, v_new)
        return append(q, k_new, v_new, *a, **kw)

    pa.paged_write_rows, pa.paged_append_attend = written, appended


def sliding_heads_by_16() -> None:
    import numpy as np

    ratio = CONFIG["swa_num_key_value_heads"] // CONFIG["num_key_value_heads"]
    heads = np.arange(CONFIG["swa_num_key_value_heads"]) // ratio
    _sliding_kv_patched(lambda k, v: (k[:, heads], v[:, heads]))


def v_at_k_stride() -> None:
    import numpy as np

    hd, hv = CONFIG["head_dim"], CONFIG["v_head_dim"]

    def moved(k, v):
        T, KV = v.shape[:2]
        lanes = (np.arange(KV)[:, None] * hd + np.arange(hv)[None, :]) \
            % (KV * hv)
        return k, v.reshape(T, KV * hv)[:, lanes]

    _sliding_kv_patched(moved)


def rotary_on_all_192() -> None:
    from pathway_tpu.models import mimo_v2_flash as m
    from pathway_tpu.models.lfm2 import _rope

    m._partial_rope = lambda x, positions, theta, rot: _rope(
        x, positions, theta)


def thetas_swapped() -> None:
    from pathway_tpu.models import mimo_v2_flash as m

    rope = m._partial_rope
    other = {float(CONFIG["rope_theta"]): float(CONFIG["swa_rope_theta"]),
             float(CONFIG["swa_rope_theta"]): float(CONFIG["rope_theta"])}
    m._partial_rope = lambda x, positions, theta, rot: rope(
        x, positions, other[float(theta)], rot)


def value_scale_left_out() -> None:
    from pathway_tpu.models import mimo_v2_flash as m

    m._values = lambda v, scale: v


def scores_over_sqrt_128() -> None:
    import numpy as np

    pa = _paged()
    ragged, append = pa.paged_attention, pa.paged_append_attend
    up = float(np.sqrt(CONFIG["head_dim"] / CONFIG["v_head_dim"]))

    def scaled(fn):
        return lambda q, *a, **kw: fn((q * up).astype(q.dtype), *a, **kw)

    pa.paged_attention, pa.paged_append_attend = scaled(ragged), \
        scaled(append)


def bias_in_the_weights() -> None:
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    route = moe.route

    def patched(h, wg, bias, **kw):
        experts, _w, scores = route(h, wg, bias, **kw)
        w = jnp.take_along_axis(scores + bias.astype(jnp.float32), experts,
                                axis=1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True)
                 + kw.get("renorm_eps", 1e-6))
        return experts, w * kw.get("scale", 1.0), scores

    moe.route = patched


FAULTS = {"none": lambda: None, "sink_left_out": sink_left_out,
          "sink_on_full_layers": sink_on_full_layers,
          "window_127": window_off_by(-1), "window_129": window_off_by(1),
          "sliding_heads_by_16": sliding_heads_by_16,
          "rotary_on_all_192": rotary_on_all_192,
          "thetas_swapped": thetas_swapped,
          "value_scale_left_out": value_scale_left_out,
          "scores_over_sqrt_128": scores_over_sqrt_128,
          "v_at_k_stride": v_at_k_stride,
          "bias_in_the_weights": bias_in_the_weights,
          "token_altered_once": drive_lfm2.token_altered_once}


def main() -> int:
    from benchmark import run

    rest = sys.argv[2:]
    measure = "--measure" in rest
    if measure:
        rest.remove("--measure")
    else:
        rest = rest + ["--rehearse"]
    config = run.load_json(run.HERE, "configs", "mimo-v2-flash-serve.json")
    CONFIG.update(config if measure else run.merged(config,
                                                    config["rehearse"]))
    FAULTS[sys.argv[1]]()
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
