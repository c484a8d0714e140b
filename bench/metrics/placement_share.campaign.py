"""Share of the window spent inside the strategies' ``place`` (placement,
the vClos ILP and OCS rewiring included).  Silent where the lane engine
places jobs itself and no strategy ``place`` is called."""


def read(rec):
    if not rec["calls"].get("place"):
        return None
    return 100.0 * rec["seconds"]["place"] / rec["window_s"]
