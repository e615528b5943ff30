"""How late the benchmark's client submitted, 95th percentile
(milliseconds): actual submit minus scheduled arrival, per request."""

import numpy as np


def read(record):
    late = record.get("client_late_s")
    if not late:
        return None
    return float(np.percentile(np.asarray(late) * 1e3, 95))
