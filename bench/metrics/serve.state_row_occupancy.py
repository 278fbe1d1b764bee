"""Live recurrent-state rows over the state pool's rows, summed over
the ticks of the untraced window (the engine's ``cb.state_rows{live,
total}`` counters): how full the pool the admission budget guards is
kept."""


def read(rec):
    if not rec.get("state_rows_total"):
        return None
    return 100.0 * rec["state_rows_live"] / rec["state_rows_total"]
