"""A chip's busy time per key answered in the traced window of the
four-chip mesh cell (microseconds): the mean busy time over the chips,
each of which works on every key, over the keys answered."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("answered"):
        return None
    return 1e6 * tr["busy_s"] / record["answered"]
