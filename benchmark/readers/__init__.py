"""Found by name from the data files; see benchmark/README.md."""

from __future__ import annotations


def work_between(run, params: dict, a: float, b: float) -> tuple:
    """What the system computed between ``a`` and ``b`` (the host's clock),
    from what the clients received: the contexts of the tokens decoded
    (``decode_events``: when delivered, context) and, of every prompt in
    prefill then (``prefill_events``: due, first token, length), its length
    with the share of its prefill that fell between the two."""
    dec = [c for t, c in run.events.get(params["decode_events"], ())
           if a <= t <= b]
    pre = []
    for s, e, p in run.events.get(params["prefill_events"], ()):
        share = (min(e, b) - max(s, a)) / (e - s)
        if share > 0:
            pre.append((p, share))
    return dec, pre
