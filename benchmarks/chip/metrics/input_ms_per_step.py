"""Host time spent inside ``host.input`` annotations (the program's
synthetic stream, ``data/synthetic.py``, called by ``Trainer._batch``) in
the traced window, per step."""


def read(ctx):
    lo, hi = ctx["red"]["window_ns"]
    spans = [h for h in ctx["trace"]["host"] if h["name"] == "host.input"
             and lo <= h["start"] < hi]
    if not spans:
        return None
    return sum(h["end"] - h["start"] for h in spans) * 1e-6 / ctx["n_steps"]
