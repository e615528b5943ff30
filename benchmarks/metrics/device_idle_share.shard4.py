"""Share of the traced window in which no op ran on a chip, in the
four-chip mesh cell: 1 - (union of device op and program intervals) /
window, averaged over the chips."""


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
