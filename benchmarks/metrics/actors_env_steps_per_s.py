"""The actors' rate, env_steps_per_s, as a per-layer metric: for the cells
where it spreads too far from run to run to carry a bound (Humanoid: 11-18%,
since how soon the learning policy falls decides the episode lengths;
PERF.md, section 2). Recorded on every PR, deciding none."""

from . import env_steps_per_s


def read(run):
    return env_steps_per_s.read(run)
