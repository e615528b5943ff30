"""Host time of one ``DPF.eval_tpu`` call outside its wait on the device
(milliseconds): the program's ``eval_tpu`` spans, less their
``eval_tpu.fetch`` children (the blocking copy back, which holds the
wait on the device), summed over the window and divided by the number
of calls.  What is left is the key decode, knob resolution, the enqueue
with its host-to-device copy, the pad and the concatenation.  ``None``
where the program records no ``eval_tpu`` span."""


def read(record):
    spans = record.get("spans") or {}
    calls = spans.get("eval_tpu", {}).get("count", 0)
    if not calls:
        return None
    fetch_s = spans.get("eval_tpu.fetch", {}).get("total_s", 0.0)
    return 1e3 * (spans["eval_tpu"]["total_s"] - fetch_s) / calls
