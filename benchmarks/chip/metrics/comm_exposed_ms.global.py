"""Device time of collective ops in the global steps during which no other
op runs on that device, in ms per global step, averaged over the chips.
Nothing to read (no collective op in a global step) gives None."""
from chip import tracered


def read(ctx):
    return tracered.exposed_ms(ctx["red"], "global")
