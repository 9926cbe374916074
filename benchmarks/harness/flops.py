"""A launch against the chip's roofline, from the operations and bytes its
updates need.

The count itself belongs to the algorithm and sits beside its reference:
`reference/<algo>.py: work(env, hp)` gives {"flops", "row_bytes"} of one
update and the {"state_bytes"} a launch reads and writes once, from the
shapes alone (reference/common.py says what is counted and what is not).

The share built from it can not pass 100% by construction: the count holds
only operations the algorithm needs, each once, and the time it is divided
by is the whole device time of the launch.
"""


def roofline_pct(need, updates, device_seconds, peaks):
    """(share in %, which bound) of one launch of `updates` updates, each
    needing `need` (a reference's `work`), that took `device_seconds` on the
    device."""
    t_flops = updates * need["flops"] / peaks["flops_per_s"]
    t_bytes = (need["state_bytes"] + updates * need["row_bytes"]) / peaks["bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / device_seconds, bound
