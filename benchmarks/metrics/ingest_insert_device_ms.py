"""Device time of one ring insert, median over the traced launches of the
insert program that took most device time (the trace's `XLA Modules` line
names them `jit_ring_insert...`: replay/device.py)."""

from harness import inside


def read(run):
    found = inside.insert_launches(run)
    return 1000.0 * max(found, key=lambda v: v["total_s"])["median_s"] if found else None
