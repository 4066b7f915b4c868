"""Device time of the pool procedure around the steps (the program's
`pool.create`, `pool.average` and `pool.append` scopes), in ms per pool
slot: clients x pool_size of each launch in the traced window
(`bench/scopes.py`)."""
from bench import scopes


def read(rec):
    t = rec["traffic"]
    slots = len(rec["window"]["units"]) * t["clients"] * t["pool_size"]
    return scopes.per_unit(
        rec, ["pool.create", "pool.average", "pool.append"], slots)
