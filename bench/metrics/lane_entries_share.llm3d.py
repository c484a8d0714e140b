"""Share of the window in the lane engine's link-entry build of placed
jobs: the program's ``lanes.entries`` span total over the window, in
percent (``repro.obs``, on over the window of a traced run)."""


def read(rec):
    spans = (rec.get("obs") or {}).get("spans", {})
    if "lanes.entries" not in spans or not rec["window_s"]:
        return None
    return 100.0 * spans["lanes.entries"]["total_s"] / rec["window_s"]
