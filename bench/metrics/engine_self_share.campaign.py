"""Share of the window left to the event engine itself: 1 minus the rate
-resolution and placement shares (in the lane engine, placement is part of
the engine)."""


def read(rec):
    if not rec["window_s"]:
        return None
    inside = sum(rec["seconds"].get(k, 0.0) for k in ("rate", "place"))
    return 100.0 * (1.0 - inside / rec["window_s"])
