"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic mix in ``bench/traffic/<traffic>.json`` and each per-layer metric's
reader in ``bench/metrics/<metric>.py``. A later change adds a cell, a mix,
a configuration or a metric by adding files and entries.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from . import datagen, flops, reference, trace as tracelib
from .peaks import peak

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# -- the cell, found by name ----------------------------------------------------
@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    bench_dir: str = BENCH_DIR


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, checkout: str = CHECKOUT) -> Cell:
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(checkout, conf["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(checkout, spec["paths"][0])
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, config=config, traffic=traffic, chips=w["chips"],
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                bench_dir=bench_dir)


def metric_reader(bench_dir: str, name: str):
    """The ``read(window)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- what the per-layer readers see ---------------------------------------------
@dataclass
class Window:
    """The measured window, as per-layer metric readers see it."""

    chips: int
    batch_sizes: list                # micro-batches applied in the window
    batch_latencies: list            # apply seconds (GraphServer counter)
    batch_full_latencies: list       # apply + commit capture + publish
    compiles: int                    # backend compiles in the window
    trace: dict | None = None        # trace.reduce() of a traced run
    flops: float | None = None       # required operations in the window
    peak_flops: float | None = None


# -- the run ----------------------------------------------------------------------
@dataclass
class WarmPolicy:
    """Warm-up ends once a stretch of ``stable_s`` seconds and at least
    ``stable_batches`` micro-batches has passed with no backend compile and
    no cap-ladder retry, after at least ``min_s`` seconds. After ``max_s``
    the window opens anyway, and its compiles are counted.

    No bounded warm-up sees every shape: on Arxiv traffic about one
    micro-batch in several hundred touches so few adjacency slots that
    the engine's mirror refresh takes a bucket one size smaller than all
    the others, a compile of some 7 ms that many 30 s windows hold. A
    warm-up long enough to see it would cost more set-up than it saves."""

    min_s: float = 4.0
    stable_s: float = 3.0
    stable_batches: int = 100
    max_s: float = 240.0


@dataclass
class _Compiles:
    times: list = field(default_factory=list)

    def __call__(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t < hi for t in self.times)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, warm: WarmPolicy = WarmPolicy(), hooks=None,
        control: str | None = None, keep: str | None = None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``hooks(session, server)`` (tests only) may replace parts of the timed
    path, to show that the check catches a broken one. ``control`` (a
    ``reference.forward`` precision, ``control.py`` only) puts the
    reference at that precision in the program's place for the check:
    ``correct`` and ``checks`` are then the control's, and the program's
    own readings are under ``program``. ``keep`` names an ``.npz`` file
    that receives the check's inputs and outputs (``control.py
    --witness``)."""
    import jax

    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, seed=seed, seconds=seconds, trace=trace,
                    t_start=t_start, warm=warm, hooks=hooks,
                    compiles=compiles, control=control, keep=keep)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run(cell, *, seed, seconds, trace, t_start, warm, hooks, compiles,
         control, keep):
    import jax

    from repro.api import InferenceSession
    from repro.core.graph import DynamicGraph
    from repro.core.workloads import make_workload
    from repro.serve import GraphServer, TenantConfig
    from repro.utils import use_compile_cache

    from .load import Load
    from .traffic import Tape

    cfg, traffic = cell.config, cell.traffic
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    stamps = {}

    # -- set-up ---------------------------------------------------------------
    ss_graph, ss_inputs, ss_tape, ss_load = datagen.seeds(seed, 4)
    t = time.perf_counter()
    snapshot, holdout = datagen.make_graph(cfg, ss_graph)
    stamps["graph"] = time.perf_counter() - t
    t = time.perf_counter()
    x_dev, params_dev = datagen.make_inputs(cfg, ss_inputs)
    x0 = np.asarray(x_dev)
    params_ref = [{k: np.array(v) for k, v in p.items()} for p in params_dev]
    del x_dev
    stamps["inputs"] = time.perf_counter() - t
    t = time.perf_counter()
    workload = make_workload(cfg["workload"], n_layers=cfg["n_layers"],
                             d_in=cfg["d_in"], d_hidden=cfg["d_hidden"],
                             n_classes=cfg["n_classes"])
    graph = DynamicGraph(cfg["n"], *snapshot)
    stamps["graph_store"] = time.perf_counter() - t
    t = time.perf_counter()
    session = InferenceSession.bootstrap(workload, params_dev, x0, graph,
                                         engine="device")
    stamps["bootstrap"] = time.perf_counter() - t
    t = time.perf_counter()
    tape = Tape(cfg["n"], snapshot, holdout, x0, traffic, ss_tape)
    tape.prefill(int(traffic.get("prefill_updates", 0)))
    base = snapshot if trace else None    # the FLOP count's starting graph
    del snapshot, holdout
    stamps["tape"] = time.perf_counter() - t
    tenants = [TenantConfig(name, staleness="wait", wait_timeout_s=600.0)
               for name in tape.names]
    server = GraphServer(session, tenants=tenants,
                         max_batch=int(traffic["max_batch"]),
                         capacity=int(traffic["capacity"]),
                         overload=traffic["overload"])
    applied: list = []                    # every batch apply_one took
    span = _spans(session, server, applied) if trace else None
    if hooks:
        hooks(session, server)
    load = Load(server, tape, traffic, ss_load, span=span)
    drawn0 = len(tape.log)
    # The prefilled tape is most of the process's tracked objects (one per
    # update) and belongs to the generator, not to the server: left in the
    # collector's oldest generation, a full collection walks all of it (a
    # quarter second per 700k objects) and stalls the server wherever it
    # falls. Set-up's objects go to the permanent generation.
    gc.collect()
    gc.freeze()
    server.start()
    load.start()

    # -- warm-up on the cell's own traffic ------------------------------------
    t_warm = time.perf_counter()
    engine = session.engine.impl
    last = (len(compiles.times), engine.retries)
    t_last, b_last = t_warm, len(server.batch_sizes)
    while True:
        time.sleep(0.25)
        _check_load(load)
        now = time.perf_counter()
        cur = (len(compiles.times), engine.retries)
        if cur != last:
            last, t_last, b_last = cur, now, len(server.batch_sizes)
            continue
        settled = (now - t_last >= warm.stable_s
                   and len(server.batch_sizes) - b_last >= warm.stable_batches
                   and now - t_warm >= warm.min_s)
        if settled or now - t_warm >= warm.max_s:
            break
    stamps["warmup"] = time.perf_counter() - t_warm
    if not settled:
        log(f"warm-up: not settled after {warm.max_s} s; the window counts "
            f"its compiles")

    # -- the window -------------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    b0, pub0 = len(server.batch_sizes), server.published_updates
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    with (jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN) if trace
          else contextlib.nullcontext()):
        time.sleep(max(seconds - (time.perf_counter() - t_open), 0.0))
        t_close = time.perf_counter()
    b1, pub1 = len(server.batch_sizes), server.published_updates
    if trace:
        jax.profiler.stop_trace()
    load.stop()
    server.drain()
    gc.unfreeze()
    _check_load(load)

    # -- what the window did ----------------------------------------------------
    window_s = t_close - t_open
    attempted = sum(size for sent, size in load.chunks
                    if t_open <= sent < t_close)
    stats = server.metrics()
    unpublished = sum(s["submitted"] - s["committed"]
                      for s in stats["tenants"].values())
    failed = load.refused * tape.chunk + unpublished
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    win = Window(chips=cell.chips,
                 batch_sizes=list(server.batch_sizes[b0:b1]),
                 batch_latencies=list(server.batch_latencies[b0:b1]),
                 batch_full_latencies=list(server.batch_full_latencies[b0:b1]),
                 compiles=compiles.between(t_open, t_close))
    e2e = {"updates_per_s": (pub1 - pub0) / window_s, "setup_s": setup_s}
    _report_lateness(load, t_open, t_close)
    log(f"set-up: {json.dumps({k: round(v, 3) for k, v in stamps.items()})}"
        f" compile cache {cache}")
    log(f"window: {window_s:.3f} s, {b1 - b0} micro-batches, "
        f"{pub1 - pub0} updates published, {win.compiles} compiles; "
        f"the tape drew "
        f"{len(tape.log)} chunks, {len(tape.log) - drawn0} after set-up")

    # -- the check: published snapshot and engine state vs the reference -------
    snap = server.query(tape.names[0], np.arange(cfg["n"]), min_seq=0).values
    server.stop()
    state = session.sync()
    got_H, got_S = list(state.H), list(state.S)
    # free the program's device state before the reference runs
    session.engine = server.session = load.server = None
    del session, server, engine
    gc.collect()
    src, dst = tape.final_edges()
    x_final = tape.final_features()
    fam, agg = datagen.family(cfg), datagen.aggregator(cfg)
    t = time.perf_counter()
    ref_H, ref_S = reference.forward(fam, agg, params_ref, x_final, src, dst)
    checks = compare(cfg, got_H, got_S, snap, ref_H, ref_S)
    log(f"reference: {time.perf_counter() - t:.3f} s")
    if keep:
        _keep(keep, params_ref, x_final, src, dst, snap, got_H, got_S,
              ref_H, ref_S)
    program = None
    if control:
        program = {k: c["value"] for k, c in checks.items()}
        log(f"program: {json.dumps(program)}")
        low_H, low_S = reference.forward(fam, agg, params_ref, x_final, src,
                                         dst, precision=control)
        checks = compare(cfg, low_H, low_S, low_H[-1], ref_H, ref_S)
        del low_H, low_S
    correct = bool(all(c["value"] <= c["limit"] for c in checks.values())
                   and failed == 0)

    # -- per-layer metrics (traced run) -----------------------------------------
    out = {"correct": correct, "attempted": int(attempted),
           "failed": int(failed)}
    if program is not None:
        out["program"] = program
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    if trace:
        reduced = tracelib.reduce(tracelib.load(trace_dir))
        _rmtree(trace_dir)
        win.trace = reduced
        win.peak_flops = peak(dev.device_kind, cfg["peak"]) \
            if dev.platform == "tpu" else None
        win.flops = flops.window_flops(
            flops.Graph(cfg["n"], *base), applied, b0, b1, family=fam,
            dims=datagen.dims(cfg))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(cell.bench_dir, m["name"])(win)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    else:
        out["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    log(f"check failed_updates: {failed} limit 0")
    out["checks"] = dict(checks, failed_updates={"value": int(failed),
                                                 "limit": 0})
    return out


def compare(cfg: dict, got_H, got_S, snap, ref_H, ref_S) -> dict:
    """The compared numbers, each with its limit from the configuration:
    ``state_gap`` over every layer's H and S of the engine and
    ``snapshot_gap`` over the published final layer (``reference.gap``)."""
    limits = cfg["limits"]
    state = max(max(reference.gap(got_H[l], ref_H[l]),
                    reference.gap(got_S[l], ref_S[l]))
                for l in range(1, len(ref_H)))
    return {"state_gap": {"value": state, "limit": limits["state_gap"]},
            "snapshot_gap": {"value": reference.gap(snap, ref_H[-1]),
                             "limit": limits["snapshot_gap"]}}


def _report_lateness(load, t_open, t_close) -> None:
    """How late the query schedule ran; the submits follow no schedule."""
    late = np.array([start - due for due, start, _ in load.queries
                     if t_open <= due < t_close]) * 1e3
    msg = "generator: closed loop, submits back to back; "
    if late.size:
        msg += (f"{late.size} snapshot queries in the window, start lateness "
                f"p50 {np.percentile(late, 50):.3f} ms, p95 "
                f"{np.percentile(late, 95):.3f} ms, max {late.max():.3f} ms")
    else:
        msg += "no snapshot query in the window"
    log(msg)


def _keep(path, params, x, src, dst, snap, got_H, got_S, ref_H, ref_S):
    arrays = {"x": x, "src": src, "dst": dst, "snap": snap}
    for l, p in enumerate(params):
        arrays.update({f"p{l}.{k}": v for k, v in p.items()})
    for l in range(1, len(ref_H)):
        arrays.update({f"got_H{l}": got_H[l], f"got_S{l}": got_S[l],
                       f"ref_H{l}": ref_H[l], f"ref_S{l}": ref_S[l]})
    np.savez(path, **arrays)


def _check_load(load) -> None:
    if load.errors:
        raise RuntimeError("load generator failed") from load.errors[0]


def _spans(session, server, applied: list):
    """Benchmark-side host spans around the calls into each layer: the
    session's ``apply_one``, the server's per-micro-batch work and its
    publish (wrapped on these instances only). ``applied`` receives each
    batch ``apply_one`` is given, in order, for the FLOP count."""
    import jax

    def wrap(obj, attr, name, record=None):
        fn = getattr(obj, attr)

        def traced(*a, **kw):
            if record is not None:
                record.append(a[0])
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        setattr(obj, attr, traced)

    wrap(session, "apply_one", "bench.apply_one", applied)
    wrap(server, "_apply_chunk", "bench.micro_batch")
    wrap(server, "_publish", "bench.publish")
    return jax.profiler.TraceAnnotation


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)
