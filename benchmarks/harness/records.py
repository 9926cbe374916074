"""From the trainer's own `"train"` records to rates and window means.

The trainer writes a `"train"` record about once a second, right after it
has read the newest chunk's metrics back from the device, so the
`learner_steps` of a record counts chunks the device has finished. Each
record carries the counts since the run began (`step`, `learner_steps`)
and, for the host phases, means and call counts since the previous record.
A window is the records between an opening and a closing record; rates are
count differences over the harness's own clock between the two (window.py).
"""

import json


def parse_lines(lines):
    """JSON records from text lines; a torn last line is skipped."""
    out = []
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    return out


def rate(open_rec, close_rec, key, seconds):
    """Delta of the count `key` between two records, per second of the
    harness's clock between them."""
    if seconds <= 0:
        raise ValueError("window has no length")
    return (close_rec[key] - open_rec[key]) / seconds


def phase_mean_ms(window_records, phase):
    """Mean ms per call of a host phase over the window: each record gives
    the mean (`t_<phase>_ms`) and the calls (`n_<phase>`) of its interval.
    None where the phase never ran."""
    total_ms, calls = 0.0, 0
    for r in window_records:
        n = r.get(f"n_{phase}", 0)
        if n:
            total_ms += r[f"t_{phase}_ms"] * n
            calls += n
    return total_ms / calls if calls else None


def phase_calls(window_records, phase):
    return sum(r.get(f"n_{phase}", 0) for r in window_records)
