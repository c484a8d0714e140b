"""Rate-resolution solves that ran on the chip inside the window's units
(the program's ``/repro/phase_max/call`` monitoring event) per finished
job."""


def read(rec):
    if not rec["jobs"]:
        return None
    return rec["kernel_calls"] / rec["jobs"]
