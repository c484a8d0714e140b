"""Share of the window spent inside ``phase_worst_loads`` as the lane
engine and the v2 engine call it (rate resolution)."""


def read(rec):
    if not rec["calls"].get("rate"):
        return None
    return 100.0 * rec["seconds"]["rate"] / rec["window_s"]
