"""One run of one cell: build, warm up, measure, trace, judge, report.

`run` is what ``bench/run.py`` calls.  The order is fixed: the window
first, then the device's peak memory, then the program's state is dropped
and the reference judges what the window decided, and only then are the
metrics read, so that no reference work lands in the window or in
``setup_s`` and none sets the memory peak.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Optional

import numpy as np

from harness import loads, manifest, stats, tracefile
from harness import traffic as tr


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _device(jax, chips: int, require_chip: bool):
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs[:chips]


def _memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _traffic_inputs(cell, sut, seed: int, seconds: float) -> tuple:
    """(texts, due times in s or None, warm-up texts) of the traffic mix."""
    spec = cell.traffic
    n_warm = int(spec.get("warm_rounds", 8)) * int(
        cell.config["batching"]["max_batch"]) + 1
    due = None
    if spec["loop"] == "open_loop":
        arr = spec["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        due = tr.poisson_arrivals(tr.rng_for(seed, tr.STREAM_ARRIVALS),
                                  float(arr["rate_per_s"]), seconds)
        n = len(due)
    else:
        n = int(spec["text_pool"])
    texts = tr.texts(sut.catalog, spec["text"], seed, n + n_warm)
    return texts[:n], due, texts[n:]


def _runtime_flags(cfg: dict) -> None:
    """Add the configuration's ``runtime.libtpu_init_args`` to
    ``LIBTPU_INIT_ARGS``.  The TPU runtime reads them when JAX first
    touches the chip, which comes after this."""
    args = cfg.get("runtime", {}).get("libtpu_init_args", [])
    have = os.environ.get("LIBTPU_INIT_ARGS", "").split()
    if args:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            have + [a for a in args if a not in have])


def _prepare(cell, seed: int, trace: bool, require_chip: bool) -> tuple:
    """(devices, system module, system under test, compile counter,
    cache directory): the set-up shared by a run and a sweep."""
    _runtime_flags(cell.config)
    import jax

    devs = _device(jax, cell.chips, require_chip)
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = loads.CompileCounter()
    if trace:
        from repro.obs import trace as obs_trace

        obs_trace.enable_jax_annotations()
    b = manifest.system(cell.bench_dir, cell.config["system"])
    sut = b.build(cell, seed, tracing=trace)
    if require_chip and not sut.use_kernels():
        raise NoChip("the engine left the kernel path")
    return devs, b, sut, counter, cache_dir


def sweep(workload: str, seed: int, rates: list, seconds: float, *,
          require_chip: bool = True, root: str = manifest.ROOT_DIR,
          log=None) -> list:
    """Knee sweep of an open-loop cell: one build and warm-up, one window
    at the first rate that is thrown away (``discarded``: the gateway's
    first window after its warm-up), then one window per rate on the same
    gateway.  Per rate: p50 and p99 of the latency from due time, the
    share shed or expired, and how far the last answer came after the
    window closed (a growing backlog)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell = manifest.resolve(workload, root)
    devs, b, sut, counter, _ = _prepare(cell, seed, False, require_chip)
    out = []
    for k, rate in enumerate([rates[0]] + list(rates)):
        spec = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"],
                                                rate_per_s=float(rate)))
        cell_k = dataclasses.replace(cell, traffic=spec)
        texts, due, warm = _traffic_inputs(cell_k, sut, seed + k, seconds)
        w = loads.run_gateway(sut, spec, texts, due, seconds, warm, counter)
        lat = [s.t_done - s.t_due for s in w.samples
               if s.result is not None and not s.result.shed
               and not s.result.expired]
        bad = sum(1 for s in w.samples
                  if s.result is None or s.result.shed or s.result.expired)
        half = [s.t_done - s.t_due for s in w.samples
                if s.t_due >= 0.5 * (w.t0 + w.t1) and s.t_done is not None]
        row = dict(rate=rate, requests=len(w.samples),
                   p50_ms=stats.percentile(lat, 50),
                   p99_ms=stats.percentile(lat, 99),
                   p99_second_half_ms=stats.percentile(half, 99),
                   failed_share=bad / max(len(w.samples), 1),
                   drain_lag_ms=w.drained_ms - w.t1,
                   flushes=w.frontend_flushes, compiles=w.compiles,
                   discarded=k == 0)
        log(f"sweep {row}")
        out.append(row)
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_chip: bool = True,
        root: str = manifest.ROOT_DIR, control: bool = False,
        log=None) -> dict:
    """Run the cell once.  Returns ``result`` (the last line's object),
    ``checks`` (name -> [value, limit]) and, with ``control=True``, the
    control's readings under ``control``."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    t_run = time.monotonic()
    cell = manifest.resolve(workload, root)
    devs, b, sut, counter, cache_dir = _prepare(cell, seed, trace,
                                                require_chip)
    t_traffic = time.monotonic()
    texts, due, warm = _traffic_inputs(cell, sut, seed, seconds)
    sut.setup_phases.update(start_s=t_run - t_start,
                            prepare_s=t_traffic - t_run,
                            traffic_s=time.monotonic() - t_traffic)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        capture = (lambda: tracefile.capture(log_dir)) if trace else None
        window = loads.run_gateway(sut, cell.traffic, texts, due, seconds,
                                   warm, counter, trace_window=capture)
        events = tracefile.load(log_dir) if trace else None
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    setup_s = (window.t0 - 1000.0 * t_start) / 1000.0
    mem = _memory_peak(devs)
    calls = b.window_calls(sut, window)
    phases = (window.phases0, window.phases1)
    env = sut.release()
    gc.collect()

    t_ref = time.monotonic()
    ref = b.reference(cell, sut, "exact")
    reading = b.judge(cell, sut, window, env, ref)
    ctrl = None
    if control:
        ctrl = b.judge(cell, sut, window, env, ref,
                       control=b.reference(cell, sut, b.CONTROL))
    ref_s = time.monotonic() - t_ref

    samples = window.samples
    missing = sum(1 for s in samples if s.result is None)
    failed = sum(1 for s in samples
                 if s.result is not None and (s.result.shed or s.result.expired))
    values = dict(reading, missing=reading["missing"] + missing)
    checks = {k: [values[k], lim] for k, lim in cell.config["checks"].items()
              if k != "about"}
    correct = bool(reading["checked"] > 0 and all(
        v <= lim for v, lim in checks.values()))

    reduced = tracefile.reduce(events) if events is not None else None
    notes: list = []
    ctx = types.SimpleNamespace(
        cell=cell, seconds=seconds, window=window, samples=samples,
        setup_s=setup_s, phases=phases, calls=calls, reference=ref,
        trace=reduced,
        peaks=lambda: manifest.peaks(cell.bench_dir, devs[0].device_kind),
        counts=lambda k: manifest.counts(cell.bench_dir, k),
        note=notes.append,
    )
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        v = manifest.metric_reader(cell.bench_dir, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    lateness = np.asarray([s.t_fire - s.t_due for s in samples]) if samples else np.zeros(1)
    log(f"cell {workload} seed {seed}: {len(samples)} requests, "
        f"{window.frontend_flushes} flushes ({window.n_flushes - window.first_flush} "
        f"recorded), {counter.total} programs compiled or loaded "
        f"({counter.hits} from the cache, {counter.seconds:.3f} s), "
        f"{window.compiles} compiles in "
        f"the window, generator late p50 {np.percentile(lateness, 50):.3f} ms "
        f"p99 {np.percentile(lateness, 99):.3f} ms max {lateness.max():.3f} ms, "
        f"cache {cache_dir}, reference {ref_s:.1f} s, "
        f"{reading['checked']} decisions judged, "
        f"{reading['off_candidate']} off the candidates, worst {reading['worst']}; "
        f"set-up {setup_s:.3f} s of which "
        + ", ".join(f"{k} {v:.3f}" for k, v in sut.setup_phases.items()))
    for n in notes:
        log(n)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {
        "correct": correct,
        "attempted": len(samples),
        "failed": failed + missing,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    out = {"result": result, "checks": checks, "reading": reading,
           "compiles": window.compiles}
    if ctrl is not None:
        out["control"] = ctrl
    return out
