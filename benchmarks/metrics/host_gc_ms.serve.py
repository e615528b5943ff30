"""Time the host spent in full (generation 2) garbage collections in the
traced window (milliseconds): the program's ``gc`` spans, summed.  0.0
when spans were recorded and none of them is a ``gc`` span; ``None``
when the record holds no spans."""


def read(record):
    spans = record.get("spans")
    if not spans:
        return None
    return 1e3 * spans.get("gc", {}).get("total_s", 0.0)
