"""One run of one cell: set-up, the measured window, the traced window,
the check, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name:

* `BENCHMARK.json` names the cell, its configuration and its mix;
* the configuration's `file` is JSON whose `kind` names the deployment
  module `chipbench/deploy/<kind>.py`;
* the mix is `chipbench/traffic/<traffic>.json`, whose `loop` must equal
  that `kind`;
* each per-layer metric is read by `chipbench/metrics/<name>.py`, whose
  `read(view)` returns a number or None (nothing to read).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import check, stats, tracing
from .deploy import common
from .savelog import SaveLog

PKG = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PKG)
#: stores, traces: removed at the start and the end of every run
WORK_DIR = os.path.join(ROOT, ".chipbench_work")
#: saves of the sampled stream issued after the window: the checked pair,
#: then one more, so that the pair's later commit is not the last one
SAMPLED_SAVES = 3


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(bench: Dict[str, Any], workload: str,
              root: str = ROOT) -> CellSpec:
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _json(os.path.join(root, entry["file"]))
    mix = traffic(cell["traffic"])
    if mix["loop"] != cfg["kind"]:
        raise ValueError(f"traffic {cell['traffic']!r} drives a "
                         f"{mix['loop']!r} loop, configuration "
                         f"{cell['config']!r} is a {cfg['kind']!r}")
    return CellSpec(
        name=workload, chips=cell["chips"], cfg=cfg, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def traffic(name: str) -> Dict[str, Any]:
    return _json(os.path.join(PKG, "traffic", f"{name}.json"))


def deployment_class(kind: str):
    return importlib.import_module(f"{__package__}.deploy.{kind}").Deployment


def metric_reader(name: str) -> Callable[[Any], Optional[float]]:
    path = os.path.join(PKG, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _json(os.path.join(PKG, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def dir_bytes(root: str) -> int:
    n = 0
    for base, _, files in os.walk(root):
        for f in files:
            try:
                n += os.stat(os.path.join(base, f)).st_size
            except FileNotFoundError:      # a tmp file renamed meanwhile
                pass
    return n


def devices(chips: int, require_tpu: bool = True) -> List[Any]:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips, JAX found {len(devs)}")
    return devs


def use_compile_cache() -> str:
    """The program's persistent compilation cache, at its fixed path;
    every program of a run goes to it, small ones too."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class _CompileCounter:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.n = 0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class View:
    """What a per-layer metric reader sees."""

    saves: List[Any]            # committed window saves (SaveRecord)
    stalls: List[float]         # stall of every save issued in the window
    trace: Optional[tracing.TraceSummary]
    peaks: Dict[str, Any]
    window_s: float


@dataclasses.dataclass
class Outcome:
    result: Dict[str, Any]
    numbers: Dict[str, float]
    info: List[str]


def _no_span(_name: str):
    return contextlib.nullcontext()


def run(spec: CellSpec, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        plant: Optional[Callable[[SaveLog], Any]] = None,
        work_dir: str = WORK_DIR) -> Outcome:
    """One run.  `plant` (never used by a benchmark run) breaks the save
    path before set-up (`chipbench.faults`) and returns its undo."""
    devs = devices(spec.chips, require_tpu)
    rng = np.random.default_rng(common.child_seed(seed, common.SAMPLE))
    annotate = tracing.annotation if trace else _no_span
    log = SaveLog(spec.cfg["deployment"]["chunk_bytes"], "", annotate)
    dep = deployment_class(spec.cfg["kind"])(spec.cfg, spec.mix, seed,
                                             work_dir, log, annotate)
    log.sample = dep.streams[int(rng.integers(len(dep.streams)))]
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    undo = plant(log) if plant is not None else None
    try:
        return _measure(spec, seconds, trace, t_start, devs, work_dir, rng,
                        annotate, log, dep)
    finally:
        if undo is not None:
            undo()
        shutil.rmtree(work_dir, ignore_errors=True)


def _measure(spec, seconds, trace, t_start, devs, work_dir, rng, annotate,
             log, dep):
    import jax
    dev = devs[0]
    dep.setup()
    setup_s = time.perf_counter() - t_start

    counter = _CompileCounter()
    store_before = dir_bytes(dep.store_dir)
    trace_dir = os.path.join(work_dir, "trace")
    with contextlib.ExitStack() as traced:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced.callback(jax.profiler.stop_trace)
            traced.enter_context(tracing.layer_spans())
        n_compiles = counter.n
        steps0 = dep.steps
        log.in_window = True
        with annotate(tracing.WINDOW_SPAN):
            w0 = time.perf_counter()
            deadline = w0 + seconds
            # whole save cycles: a closing round without a save would
            # shift the time per step by the parity of the count
            while time.perf_counter() < deadline or dep.steps % dep.cycle:
                dep.round()
            window_s = time.perf_counter() - w0
        log.in_window = False
        steps = dep.steps - steps0
    compiles_in_window = counter.n - n_compiles
    dep.drain()
    stored = dir_bytes(dep.store_dir) - store_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    # the reference's checksums of the sampled saves, after the window
    # and the peak: the traffic goes on through the same calls
    log.sampling = True
    while log.n_sampled < SAMPLED_SAVES:
        dep.round()
    log.sampling = False
    dep.drain()
    logical = dep.logical_bytes()
    traffic_faults = dep.traffic_faults()
    dep.release()
    log.fetch_checksums()

    window = log.window()
    committed = [r for r in window if r.stats is not None]
    info = [f"set-up {setup_s:.6f} s; window {window_s:.6f} s, {steps} "
            f"steps, {len(window)} saves issued, {len(committed)} "
            f"committed, {compiles_in_window} compiles in the window",
            f"store: {stored} bytes added in the window; snapshot "
            f"{logical} logical bytes; traffic faults {traffic_faults}"]
    info += [f"save {r.tid} {r.stream}: stall {r.stall_s:.6f} s, commit "
             f"{r.commit_s if r.commit_s is not None else float('nan'):.6f}"
             f" s, {r.stats['n_dirty_chunks'] if r.stats else '-'}/"
             f"{r.stats['n_chunks'] if r.stats else '-'} chunks dirty"
             for r in window]

    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if not trace:
        values = {
            "setup_s": setup_s,
            "step_ms": window_s / steps * 1e3,
            "stall_ms": stats.mean([r.stall_s for r in window]) * 1e3,
            "commit_ms": stats.mean([r.commit_s for r in committed]) * 1e3,
            "stored_bytes_ratio": stats.ratio(
                stored, len(committed) * logical),
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}
    else:
        summary = tracing.reduce_trace(tracing.load_trace(trace_dir))
        view = View(saves=committed, stalls=[r.stall_s for r in window],
                    trace=summary, peaks=peaks(dev.device_kind),
                    window_s=window_s)
        metrics = {}
        for m in spec.per_layer:
            v = metric_reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.device_ops,
                     "idle_gaps": summary.idle_gaps}
        info.append(f"trace: busy {summary.busy_s:.6f} s of "
                    f"{summary.window_s:.6f} s, save ops "
                    f"{summary.save_busy_s:.6f} s")

    timings: Dict[str, float] = {}
    try:
        numbers = check.compare(log, dep, rng, timings)
    except Exception as exc:            # a check that cannot run fails
        info.append(f"check failed to run: {type(exc).__name__}: {exc}")
        numbers = {"check_error": 1}
    finally:
        dep.close_reader()
    info.append("check: " + ", ".join(f"{k} {v:.3f} s"
                                      for k, v in timings.items()))
    numbers["traffic_faults"] = traffic_faults
    result: Dict[str, Any] = {
        "correct": check.verdict(numbers), "attempted": len(window),
        "failed": len(window) - len(committed), "metrics": metrics,
        "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # the numbers compared, each with its limit, come last
    result["checks"] = {k: {"value": v, "limit": check.LIMITS.get(k, 0)}
                        for k, v in numbers.items()}
    return Outcome(result=result, numbers=numbers, info=info)
