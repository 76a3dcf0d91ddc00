"""Device time of collective ops in the gossip steps during which no other
op runs on that device, in ms per gossip step, averaged over the chips.
Nothing to read (no collective op in a gossip step) gives None."""
from chip import tracered


def read(ctx):
    return tracered.exposed_ms(ctx["red"], "gossip")
