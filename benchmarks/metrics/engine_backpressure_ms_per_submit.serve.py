"""Time work waited in the serving engine for a free slot of its
in-flight window (milliseconds per submit): the program's
``backpressure`` spans, summed over the window, divided by the number of
``submit`` spans, those that did not wait included."""


def read(record):
    spans = record.get("spans") or {}
    submits = spans.get("submit", {}).get("count", 0)
    if not submits:
        return None
    waited_s = spans.get("backpressure", {}).get("total_s", 0.0)
    return 1e3 * waited_s / submits
