"""One run of one benchmark cell, driven by the data beside this file.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
metrics.  Everything that belongs to one of them sits in a file of its
own, found by name:

* ``configs/<config>.json``: the deployment (table, PRF, the
  guarantees) and how its answers are checked: ``check.recover`` and
  ``check.reference`` answers sampled, and ``check.reference_on``, where
  the plain reference runs: absent or ``"host"``, NumPy on the host;
  ``"devices"``, ``jax.numpy`` on the cell's devices
  (``reference_devices.py``), for tables too large for the host walk;
* ``traffic/<mix>.json``: the parameters of one mix and the name of
  the driver in ``drivers/`` that plays them;
* ``metrics/<metric>.py``: a reader, ``read(record)``, of one per-layer
  metric from the traced run's record (``None`` when it finds nothing);
* ``prfs/<prf>.py``: the reference's PRF that a configuration names.

A driver module exposes ``setup(ctx) -> state``, ``window(state,
seconds, annotate) -> Window``, ``server1(state, idx) -> shares`` and
``release(state)``.  The harness times set-up, runs the window (under
the profiler with ``--trace 1``), reads the device's memory peak, has
the program compute server 1's shares of a sample outside the window,
frees the program, and only then runs the plain reference
(``reference.py``, block by block) over a second sample.  The numbers
compared, each with its limit, go last, on standard error and in the
result line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KEYGEN_THREADS = 4
REFERENCE_THREADS = 4
TABLE_BLOCK_BYTES = 256 << 20
REFERENCE_PLACES = ("host", "devices")


def use_program_defaults() -> None:
    """Before JAX starts: the program's own defaults run (no tuning
    entries from elsewhere on the machine), JAX's compile cache sits at
    a fixed path inside this checkout with no size limit, and the TPU
    runtime writes no log files.

    A size limit set on the machine turns on JAX's LRU eviction, whose
    bookkeeping failed to store the batch program on one chip host
    (every run then compiled it again in set-up); one checkout holds
    one cell's few programs, so the cache needs no eviction."""
    os.environ["DPF_TPU_TUNE_CACHE"] = "0"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".jax_compile_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- lookup

def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: str

    def driver(self):
        return load_module(os.path.join(
            self.root, "benchmarks", "drivers",
            self.traffic["driver"] + ".py"),
            "bench_driver_" + self.traffic["driver"])

    def reader(self, metric: str):
        return load_module(os.path.join(
            self.root, "benchmarks", "metrics", metric + ".py"),
            "bench_metric_" + metric.replace(".", "_"))


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def find_cell(name: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json" % name)
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "benchmarks", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer,
                root)


# ---------------------------------------------------------------- device

def look_for_chip(chips: int, root: str = ROOT) -> dict:
    """The device as JAX reports it; exits when it is no TPU, when there
    are fewer chips than the cell needs, or when the peaks table does
    not know the device."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit("no TPU: the JAX backend is %r" % devs[0].platform)
    if len(devs) < chips:
        raise SystemExit("the cell needs %d chips, JAX finds %d"
                         % (chips, len(devs)))
    peaks = load_json(os.path.join(root, "benchmarks", "peaks.json"))
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        raise SystemExit("device kind %r is not in benchmarks/peaks.json"
                         % kind)
    return {"platform": devs[0].platform, "kind": kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileEvents:
    """Counts JAX's compile and compilation-cache events, and Python's
    full (generation 2) garbage collections with the seconds they took,
    so the run can say how many fell inside the measured window."""

    NAMES = {"/jax/core/compile/backend_compile_duration": "compiles",
             "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        from jax import monitoring
        self.counts = {v: 0 for v in self.NAMES.values()}
        self.counts.update(full_gcs=0, full_gc_s=0.0)
        self._gc_t0 = None
        monitoring.register_event_listener(self._on)
        monitoring.register_event_duration_secs_listener(self._on_dur)
        gc.callbacks.append(self._on_gc)

    def _on(self, event, **kw):
        if event in self.NAMES:
            self.counts[self.NAMES[event]] += 1

    def _on_dur(self, event, duration, **kw):
        self._on(event)

    def _on_gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.counts["full_gcs"] += 1
            self.counts["full_gc_s"] += time.perf_counter() - self._gc_t0

    def snapshot(self) -> dict:
        return dict(self.counts)


# ----------------------------------------------------------------- inputs

def rng_for(seed: int, *tag: int) -> np.random.Generator:
    """Independent streams drawn from the run's seed (any whole number)."""
    s = seed & ((1 << 128) - 1)
    return np.random.default_rng([s & ((1 << 64) - 1), s >> 64, *tag])


def make_table(config: dict, seed: int) -> np.ndarray:
    """The [2^log2_rows, entry_words] int32 table, drawn from the seed in
    row blocks of about ``TABLE_BLOCK_BYTES`` of int64 draws into one
    int32 array: one generator drawn in blocks gives the stream a single
    draw gives, so the table does not depend on the block size."""
    rng = rng_for(seed, 0)
    n, e = 1 << config["log2_rows"], config["entry_words"]
    table = np.empty((n, e), np.int32)
    step = max(1, TABLE_BLOCK_BYTES // (8 * e))
    for lo in range(0, n, step):
        hi = min(n, lo + step)
        table[lo:hi] = rng.integers(-2 ** 31, 2 ** 31, (hi - lo, e),
                                    dtype=np.int64)
    return table


def mint_keys(dpf, n: int, count: int, seed: int, tag: int):
    """``count`` key pairs for uniform rows, drawn from the seed through
    the client entry point ``DPF.gen_batch``, in a few threads.
    Returns (rows, server 0 keys, server 1 keys) as NumPy arrays."""
    rng = rng_for(seed, tag)
    rows = rng.integers(0, n, count)
    seeds = [rng.bytes(16) for _ in range(count)]
    step = -(-count // KEYGEN_THREADS)
    parts = [(rows[i:i + step], seeds[i:i + step])
             for i in range(0, count, step)]

    def gen(part):
        a, b = dpf.gen_batch(part[0], n, seeds=part[1])
        return np.asarray(a, np.int32), np.asarray(b, np.int32)

    with ThreadPoolExecutor(KEYGEN_THREADS) as ex:
        out = list(ex.map(gen, parts))
    return (rows, np.concatenate([a for a, _ in out]),
            np.concatenate([b for _, b in out]))


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's data, the seed, and the table."""
    config: dict
    traffic: dict
    seed: int
    seconds: float
    table: np.ndarray
    t0: float

    def mark(self, label: str) -> None:
        """Say on standard error how far set-up has come."""
        log("setup %s at %.3f s" % (label, time.monotonic() - self.t0))

    @property
    def prf_id(self) -> int:
        """The program's number for the configuration's PRF."""
        return self.config["prf_id"]

    def keys(self, dpf, count: int, tag: int):
        return mint_keys(dpf, self.table.shape[0], count, self.seed, tag)


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns.

    ``served`` are indices into ``rows``/``keys0``/``keys1`` (the
    driver's key pool) of the answers the window produced, with those
    answers in ``shares`` ([len(served), E] int32)."""
    end_to_end: dict
    attempted: int
    failed: int
    served: np.ndarray
    shares: np.ndarray
    record: dict


# -------------------------------------------------------------- tracing

@contextlib.contextmanager
def no_annotation(name):
    yield


def profiled(trace_dir: str):
    """(start, stop) of a profiler capture without Python call events."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0

    def start():
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    return start, jax.profiler.stop_trace


def span_totals(events: list) -> dict:
    out = {}
    for e in events:
        d = out.setdefault(e["name"], {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0})
        d["count"] += 1
        d["total_s"] += e["dur_us"] / 1e6
        d["self_s"] += e["self_us"] / 1e6
    return out


# ---------------------------------------------------------------- checks

LIMITS = {"rows_not_recovered": 0, "reference_mismatch_rows": 0}


def compare(cell: Cell, state, drv, win: Window, seed: int, table,
            control: bool = False) -> tuple:
    """The numbers compared, and with ``control`` the control's.

    * ``rows_not_recovered``: of a seeded sample of the window's answers
      (``check.recover`` of them), those whose server 0 share minus the
      program's server 1 share is not the table row, exactly.
    * ``reference_mismatch_rows``: of a second seeded sample
      (``check.reference``), those whose share differs from the plain
      reference's at all.
    Both limits are 0: the deployment promises exact recovery.

    The control is the plain reference contracted in float32, put in the
    program's place as server 0: its shares of the same keys replace the
    window's, and the same code reads the same two numbers from them."""
    chk = cell.config["check"]
    rng = rng_for(seed, 7)
    served = win.served
    pick_rec = rng.choice(len(served), min(chk["recover"], len(served)),
                          replace=False)
    pick_ref = rng.choice(len(served), min(chk["reference"], len(served)),
                          replace=False)
    s1 = drv.server1(state, served[pick_rec])
    drv.release(state)
    keys0 = state.keys0
    want = reference_shares(keys0[served[pick_ref]], table, cell)
    truth = table[state.rows[served[pick_rec]]]

    def numbers(shares):
        got = (shares[pick_rec].astype(np.int64) - s1).astype(np.int32)
        return {"rows_not_recovered": int((got != truth).any(axis=1).sum()),
                "reference_mismatch_rows": int(
                    (shares[pick_ref] != want).any(axis=1).sum())}

    nums = numbers(win.shares)
    ctrl = None
    if control:
        shares = win.shares.copy()
        both = np.union1d(pick_rec, pick_ref)
        shares[both] = reference_shares(keys0[served[both]], table, cell,
                                        "float32")
        ctrl = numbers(shares)
    return nums, ctrl


def passes(nums: dict) -> bool:
    return all(v <= LIMITS[k] for k, v in nums.items())


def reference_place(config: dict) -> str:
    """Where the configuration's plain reference runs; exits, before any
    set-up, on a place it does not know or a PRF that cannot run
    there."""
    from benchmarks import reference
    place = config["check"].get("reference_on", "host")
    if place not in REFERENCE_PLACES:
        raise SystemExit("check.reference_on %r: not one of %s"
                         % (place, ", ".join(REFERENCE_PLACES)))
    if place == "devices" and not reference.runs_under(config["prf"],
                                                       "jax.numpy"):
        raise SystemExit("check.reference_on 'devices': the reference's "
                         "PRF %r has no jax.numpy path (prfs/%s.py)"
                         % (config["prf"], config["prf"]))
    return place


def reference_shares(keys, table, cell: Cell, contraction="exact"):
    """The plain reference's shares of ``keys``: on the host in a few
    threads, or on the cell's devices."""
    prf = cell.config["prf"]
    if len(keys) == 0:
        return np.zeros((0, table.shape[1]), np.int32)
    if reference_place(cell.config) == "devices":
        import jax
        from benchmarks import reference_devices
        return reference_devices.share(keys, table, prf, contraction,
                                       jax.devices()[:cell.chips])
    from benchmarks import reference
    parts = np.array_split(np.arange(len(keys)),
                           min(REFERENCE_THREADS, len(keys)))
    with ThreadPoolExecutor(len(parts)) as ex:
        outs = list(ex.map(lambda p: reference.share(
            keys[p], table, prf, contraction), parts))
    return np.concatenate(outs)


# ------------------------------------------------------------------- run

def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t0: float, root: str = ROOT, look=look_for_chip,
             control: bool = False, keep: str | None = None,
             events: CompileEvents | None = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict."""
    cell = find_cell(name, root)
    reference_place(cell.config)
    device = look(cell.chips, root)
    events = events or CompileEvents()
    table = make_table(cell.config, seed)
    ctx = Context(cell.config, cell.traffic, seed, seconds, table, t0)
    ctx.mark("device and table")
    drv = cell.driver()
    state = drv.setup(ctx)
    setup_s = time.monotonic() - t0
    before = events.snapshot()
    record = {}
    if trace:
        from dpf_tpu.obs import tracer
        from benchmarks import tracereduce
        import jax
        with tempfile.TemporaryDirectory() as d:
            start, stop = profiled(d)
            spans = tracer.enable(capacity=1 << 18)
            spans.clear()
            start()
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    win = drv.window(state, seconds,
                                     jax.profiler.TraceAnnotation)
            finally:
                stop()
                tracer.disable()
            compact = tracereduce.load(d)
        if keep:
            os.makedirs(keep, exist_ok=True)
            tracereduce.save(compact, os.path.join(keep, "trace.json.gz"))
        record["trace"] = tracereduce.reduce(compact)
        record["spans"] = span_totals(spans.events())
    else:
        win = drv.window(state, seconds, no_annotation)
    in_window = {k: v - before[k] for k, v in events.snapshot().items()}
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    record.update(win.record, in_window=in_window)
    nums, ctrl = compare(cell, state, drv, win, seed, table, control)
    log("setup_s %.4f" % setup_s)
    log("in_window %s" % json.dumps(in_window))
    out = {"correct": passes(nums),
           "attempted": win.attempted, "failed": win.failed}
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    else:
        values = dict(win.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError("driver %r does not measure %r"
                                   % (cell.traffic["driver"], m["name"]))
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if trace:
        out["breakdown"] = {"device_ops": record["trace"]["top_ops"],
                            "idle_gaps": record["trace"]["top_gaps"]}
    if keep:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, "record.json"), "w") as f:
            json.dump({"cell": name, "record": record,
                       "metrics": metrics}, f)
    if ctrl is not None:
        out["control"] = {"correct": passes(ctrl),
                          "compared": {k: {"value": v, "limit": LIMITS[k]}
                                       for k, v in ctrl.items()}}
    out["compared"] = {k: {"value": v, "limit": LIMITS[k]}
                       for k, v in nums.items()}
    return out


def print_result(out: dict) -> None:
    for k, v in out["compared"].items():
        log("compared %s %s limit %s" % (k, v["value"], v["limit"]))
    print(json.dumps(out), flush=True)
