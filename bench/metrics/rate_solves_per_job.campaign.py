"""Rate-resolution calls per finished job: an exact count of the calls
into ``phase_worst_loads`` from the engines."""


def read(rec):
    if not rec["calls"].get("rate") or not rec["jobs"]:
        return None
    return rec["calls"]["rate"] / rec["jobs"]
