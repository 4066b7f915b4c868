"""Device 0's idle time in the traced window, in ms per client visit
(clients of each launch). The window is whole launches back to back, so
this is the host's share between the programs of the plan interpreter:
the warmup's and each visit's schedule upload, dispatch and loss sync."""


def read(rec):
    tr = rec["window"].get("trace")
    if not tr:
        return None
    visits = len(rec["window"]["units"]) * rec["traffic"]["clients"]
    return 1e3 * sum(s for _, s in tr["idle_gaps"]) / visits
