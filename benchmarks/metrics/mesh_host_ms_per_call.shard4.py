"""Host time of one ``ShardedDPFServer.eval`` call outside its wait on
the chips (milliseconds): the program's ``mesh_eval`` spans, less their
``mesh_eval.fetch`` children (the blocking copy back, which holds the
wait on the devices), summed over the window and divided by the number
of calls: the key decode, knob resolution and the enqueue of the mesh
program.  ``None`` where the program records no ``mesh_eval`` span."""


def read(record):
    spans = record.get("spans") or {}
    calls = spans.get("mesh_eval", {}).get("count", 0)
    if not calls:
        return None
    fetch_s = spans.get("mesh_eval.fetch", {}).get("total_s", 0.0)
    return 1e3 * (spans["mesh_eval"]["total_s"] - fetch_s) / calls
