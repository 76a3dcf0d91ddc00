"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 1 - (union of device op intervals / window)."""


def read(ctx):
    red = ctx["red"]
    lo, hi = red["window_ns"]
    busy = sum(red["busy_ns"].values()) / len(red["busy_ns"])
    return 100.0 * (1.0 - busy / (hi - lo))
