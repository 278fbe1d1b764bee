"""Real vertices over padded slots (levels T times width M) of every
batch the window ran: an exact count of the scheduler's padding."""


def read(rec):
    if not rec.get("slots"):
        return None
    return 100.0 * rec["vertices"] / rec["slots"]
