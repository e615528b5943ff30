"""Device busy time per key answered in the traced window (microseconds):
the device program's own cost of one DPF, idle time left out."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("answered"):
        return None
    return 1e6 * tr["busy_s"] / record["answered"]
