"""Share of the key slots the serving engine dispatched in the window
that were padding (``EngineCounters``: padded / (padded + real))."""


def read(record):
    eng = record.get("engine")
    if not eng:
        return None
    slots = eng["padded_queries"] + eng["queries_submitted"]
    if not slots:
        return None
    return 100.0 * eng["padded_queries"] / slots
