"""Cross-leaf flows per placed job: the program's ``flows.dp`` plus
``flows.pp`` counters (data-parallel ring and pipeline stage-boundary
flows whose ends sit under different leafs) over the jobs the window
placed and finished (``repro.obs``, on over the window of a traced run)."""


def read(rec):
    counters = (rec.get("obs") or {}).get("counters", {})
    if "flows.dp" not in counters or not rec["jobs"]:
        return None
    return (counters["flows.dp"] + counters.get("flows.pp", 0)) / rec["jobs"]
