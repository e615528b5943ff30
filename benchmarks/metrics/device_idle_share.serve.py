"""Share of the traced window in which no op ran on the device, in the
open-loop serving cell: 1 - (union of device op intervals) / window."""


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
