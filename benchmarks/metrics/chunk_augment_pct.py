"""Share of a launch's device time that prepares the images: the byte images
bitcast out of the gathered rows' words (`prep/pixels`, with the relayouts of
the whole block the compiler puts in front of the bitcast, which the
program's table reads as that scope where it has the rule) and each update's
random shift (pad, crop, the conversion to float: `update/augment`), over all
operations of the launch (harness/scopes.py). Only a program that brackets
them has the scopes; any other gives nothing to read. Not in it: the slices
of an update's 256 images out of the scanned block inside the loop, which
carry no name and read `update`."""

from harness import scopes


def read(run):
    return scopes.pct(run, ("update/augment", "prep/pixels")) or None
