"""Share of the window's wall time the learner thread spent inside `refresh`
and `sync` once their drains had ended (records: `t_refresh_ms`, `t_sync_ms`
less `t_refresh_drain_ms`, `t_sync_drain_ms`, each times its calls): the idle
share the two read-backs cost over the whole window, by the host's clock, to
hold beside what the short trace reads under `idle.d2h_pct` and
`idle.publish_pct`."""

from harness import timeline


def read(run):
    host = [timeline.host_ms(run["window"], phase) for phase in timeline.DRAINED]
    if None in host:
        return None
    return 100.0 * sum(host) / (1000.0 * run["window_s"])
