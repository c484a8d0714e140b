"""1 minus the device's busy seconds (union of XLA op intervals on the TPU
planes of the profiler trace) over the traced window, in percent."""


def read(rec):
    if "idle_share" not in rec:
        return None
    return 100.0 * rec["idle_share"]
