"""Reader ``span_stat``: a statistic of the program's own flight-recorder
spans (``pathway_tpu.obs.recorder().snapshot()``), read as the engine's
counters are: what the program says of itself, never a yardstick.

Two forms.  With ``p``: the ``p``-th percentile, times ``scale``, of the
summed duration per request (trace id) of the spans named in ``spans``,
over the requests whose first token fell inside the window
(``engine.request``'s start plus its ``ttft_s``); spans that carry the
attribute named by ``without`` (a re-admission's) do not count.  With
``num`` / ``den``: ``scale * sum(num) / sum(den)`` of two attributes over
the spans named in ``spans`` that started inside the window and whose
attributes equal ``where``.

No reading: a program without such spans or attributes; fewer than
``min_count`` samples; nothing to divide by; or a ring that has evicted
spans of the window (more spans recorded than the ring holds, and the
oldest one kept finished after the window's start)."""

from __future__ import annotations

from benchmark.readers.client_percentile import percentile


def evicted_in_window(ring: list, n_recorded: int, window: tuple) -> bool:
    return n_recorded > len(ring) and (not ring
                                       or ring[0].t1 >= window[0])


def per_request(params: dict, ring: list, window: tuple) -> list:
    """Summed seconds of the named spans, per request of the window."""
    t0, t1 = window
    first = {}
    for s in ring:
        if s.name == "engine.request" and "ttft_s" in (s.attrs or {}):
            first[s.trace_id] = s.t0 + s.attrs["ttft_s"]
    names, without = set(params["spans"]), params.get("without")
    total: dict = {}
    for s in ring:
        if s.name in names and t0 <= first.get(s.trace_id, t0 - 1) <= t1 \
                and not (without and (s.attrs or {}).get(without)):
            total[s.trace_id] = total.get(s.trace_id, 0.0) + (s.t1 - s.t0)
    return list(total.values())


def ratio(params: dict, ring: list, window: tuple) -> float | None:
    names, where = set(params["spans"]), params.get("where", {})
    num = den = 0.0
    for s in ring:
        a = s.attrs or {}
        if s.name in names and window[0] <= s.t0 <= window[1] \
                and all(a.get(k) == v for k, v in where.items()) \
                and params["num"] in a and params["den"] in a:
            num += a[params["num"]]
            den += a[params["den"]]
    return params.get("scale", 1.0) * num / den if den else None


def from_ring(params: dict, ring: list, n_recorded: int,
              window: tuple) -> float | None:
    if evicted_in_window(ring, n_recorded, window):
        return None
    if "p" not in params:
        return ratio(params, ring, window)
    v = per_request(params, ring, window)
    if len(v) < params.get("min_count", 1):
        return None
    return params.get("scale", 1.0) * percentile(v, params["p"])


def read(params: dict, run) -> float | None:
    from pathway_tpu.obs import recorder

    rec = recorder()
    return from_ring(params, rec.snapshot(), rec.n_recorded, run.window)
