"""Share of the roofline reached by the dense scan, over the device-busy
time of the traced window (``work.py`` counts the work)."""

import work


def read(run):
    return work.scan_roofline(run)
