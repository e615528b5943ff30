"""Host time the serving engine spends per submit (milliseconds): its
``admit``, ``pack`` and ``dispatch`` spans (admission, decode and pad,
the enqueue of the device program), summed over the window and divided
by the number of ``submit`` spans.  Waits on the device are left out."""

PARTS = ("admit", "pack", "dispatch")


def read(record):
    spans = record.get("spans") or {}
    submits = spans.get("submit", {}).get("count", 0)
    if not submits:
        return None
    host_s = sum(spans[p]["total_s"] for p in PARTS if p in spans)
    return 1e3 * host_s / submits
