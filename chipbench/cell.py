"""One run of one cell: build, warm up, measure, check, reduce.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration's file (``configs/<name>.json``), the traffic mix's file
(``traffic/<name>.json``) whose ``kind`` names its generator module
(``traffic/<kind>.py``), and one reader module per metric
(``metrics/<name up to the first dot>.py``, whose ``read(ctx)`` returns
the number or None).  A new cell, mix or metric is new files and entries.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import types

import numpy as np

from chipbench import harness as harness_lib, peaks as peaks_lib, stats, \
    tracing, weights, work
from chipbench.harness import clock
from chipbench.reference import check as check_lib
from chipbench.traffic.packets import META_WORDS, PacketSource

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")


class NoChip(RuntimeError):
    """The machine lacks the platform or the chips the cell asks for."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return _json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(os.path.join(HERE, "traffic", f"{name}.json"))


def metrics_for(spec: dict, wl: str) -> tuple[list, list]:
    """The cell's end-to-end and per-layer metric entries."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or wl in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if wl in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


def reader(name: str):
    base = name.split(".")[0]
    return _module(os.path.join(HERE, "metrics", f"{base}.py"),
                   f"chipbench.metrics.{base}")


def setup_jax() -> None:
    """Persistent compilation cache at a fixed path in the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices(chips: int, rehearse: bool):
    """Every device JAX finds; the cell runs on the first ``chips``.  A
    cell on more than one chip needs exactly that many in sight: the
    program's multi-chip path (``fanout="shard_map"``) spreads its queues
    over every device it finds, and has no argument that names them."""
    import jax
    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    if chips > 1 and len(devs) != chips:
        raise NoChip(f"cell runs on {chips} chips and the program spreads "
                     f"its queues over all it finds; JAX found {len(devs)}")
    return devs


def placement(chips) -> dict:
    """The runtime's arguments that put a cell on its chips: on more than
    one, the queues split over them by ``shard_map``; on one, none, so the
    program keeps its own default."""
    return {"fanout": "shard_map"} if len(chips) > 1 else {}


def _served_models(ticks, seqs, source, epochs, num_slots) -> np.ndarray:
    """Index of the model each packet's slot held at its tick: the
    resident model ``k`` of slot ``k``, or the last epoch applied to that
    slot before the tick."""
    slot = np.clip(source.attributes(seqs)[0].astype(np.int64), 0,
                   num_slots - 1)
    model = slot.copy()
    for k in range(num_slots):
        ep = [e for e in epochs if e.slot == k and e.applied_tick is not None]
        if not ep:
            continue
        at = np.array([e.applied_tick for e in ep])
        mods = np.array([e.model for e in ep])
        sel = slot == k
        idx = np.searchsorted(at, ticks[sel], side="left")
        model[sel] = np.where(idx > 0, mods[np.maximum(idx - 1, 0)], k)
    return model


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_proc: float, t_devices: float | None = None,
             rehearse: bool = False, interpret: bool = False,
             control: bool = False, events=None,
             mix_overrides: dict | None = None) -> dict:
    """Run cell ``name`` once; returns everything the printers need.
    ``mix_overrides`` changes traffic parameters (the knee sweep's rate)."""
    import jax
    spec = load_spec()
    wl = workload(spec, name)
    cfg = config(spec, wl["config"])
    mix = dict(traffic(wl["traffic"]), **(mix_overrides or {}))
    devs = devices(wl["chips"], rehearse)
    chips = devs[:wl["chips"]]
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.dataplane import DataplaneRuntime
    driver_mod = _module(os.path.join(HERE, "traffic", f"{mix['kind']}.py"),
                         f"chipbench.traffic.{mix['kind']}")
    events = events or harness_lib.CompileEvents()
    full_gc = harness_lib.FullCollections()
    marks = [("start", t_proc)]
    if t_devices is not None:
        marks.append(("jax_and_chip", t_devices))
    marks.append(("program_import", clock()))

    bank = weights.bank(cfg, seed)
    jax.block_until_ready(bank)
    n_swap = mix.get("swap_models", 0)
    swap_params = weights.swap_models(cfg, seed, n_swap)
    resident = []
    if n_swap:
        host = jax.device_get(bank)
        resident = [{k: v[i] for k, v in host.items()}
                    for i in range(cfg["slots"])]
    source = PacketSource(slots=cfg["slots"], flows=mix["flows"],
                          monitor_share=mix["monitor_share"], seed=seed,
                          rss_buckets=cfg["rss_buckets"])
    marks.append(("inputs", clock()))
    kw = dict(placement(chips), **({"backend": "pallas"} if interpret else {}))
    rt = DataplaneRuntime(bank, num_queues=cfg["queues"],
                          ring_capacity=cfg["ring_capacity"], **kw)
    h = harness_lib.Harness(rt, annotate=trace)
    driver = driver_mod.Driver(h, source, cfg, mix, seed=seed,
                               swap_params=swap_params,
                               resident_params=resident)
    marks.append(("runtime", clock()))
    driver.warm_up()
    del bank
    marks.append(("warm_up", clock()))
    setup_s = clock() - t_proc
    setup_parts = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    h.start()
    if trace:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            t0, t1 = driver.window(seconds)
    else:
        t0, t1 = driver.window(seconds)
    h.drain()
    t_drained = clock()
    reduced = None
    trace_s = None
    if trace:
        jax.profiler.stop_trace()
        reduced = tracing.reduce(tracing.find(TRACE_DIR),
                                 device_ids=[d.id for d in chips])
        trace_s = clock() - t_drained
    compiles = events.between(t0, t_drained)
    gc_pauses = full_gc.between(t0, t_drained)

    mem = [d.memory_stats() for d in chips]
    peak_bytes = max((m or {}).get("peak_bytes_in_use", 0) for m in mem) \
        if any(mem) else None
    ids = {e.epoch_id for e in h.epochs}
    apply_us = [r.apply_us for r in rt.control.log
                if r.epoch in ids and r.apply_us is not None]
    seqs, slots, verdicts, actions, ticks, times = h.served()
    dropped = h.dropped_seqs()
    offered = h.offered
    spans = {k: tuple(v) for k, v in h.spans.items()}
    in_window = times <= t1
    timed = seqs < offered
    due = driver.due(seqs[timed])
    latency_us = None if due is None else (times[timed] - due) * 1e6
    late_us = (np.concatenate(h.late) * 1e6) if h.late else None
    swap_us = [(e.returned_t - e.submit_t) * 1e6 for e in h.epochs
               if e.returned_t is not None]
    submit_us = list(h.submit_us)
    tick_work = h.tick_work(t0, t1)
    backlog = list(getattr(driver, "backlog", []))
    models = _served_models(ticks, seqs, source, h.epochs, cfg["slots"])
    epochs = list(h.epochs)
    h.rt = None
    del rt, h, driver
    gc.collect()

    all_models = weights.models(cfg, seed, weights.BANK_STREAM, cfg["slots"])
    if n_swap:
        extra = weights.models(cfg, seed, weights.SWAP_STREAM, n_swap)
        all_models = {k: np.concatenate([np.asarray(all_models[k]),
                                         np.asarray(extra[k])])
                      for k in all_models}
    served = check_lib.Served(seqs=seqs, slots=slots, verdicts=verdicts,
                              actions=actions, models=models)
    t_check = clock()
    readings = check_lib.judge(served, offered=offered, dropped=dropped,
                               source=source, models=all_models,
                               num_slots=cfg["slots"], meta_words=META_WORDS)
    check_s = clock() - t_check
    control_readings = None
    if control:
        ctrl = check_lib.control_served(served, source=source,
                                        models=all_models,
                                        num_slots=cfg["slots"],
                                        meta_words=META_WORDS)
        control_readings = check_lib.judge(
            ctrl, offered=offered, dropped=dropped, source=source,
            models=all_models, num_slots=cfg["slots"], meta_words=META_WORDS)
    correct = (readings["checked"] > 0 and all(
        readings[k] <= lim for k, lim in check_lib.LIMITS.items()))

    dev = chips[0]
    peaks = None if rehearse else peaks_lib.for_kind(dev.device_kind)
    ctx = types.SimpleNamespace(
        cfg=cfg, traffic=mix, workload=wl, setup_s=setup_s, window=(t0, t1),
        seconds=t1 - t0, spans=spans, offered=offered,
        retired_in_window=int(np.sum(in_window & timed)),
        latency_us=latency_us, late_us=late_us, swap_us=swap_us,
        submit_us=submit_us, apply_us=apply_us, epochs=epochs,
        trace=reduced, tick_work=tick_work, peaks=peaks, work=work,
        stats=stats, e2e={})
    e2e, layer = metrics_for(spec, name)
    for m in e2e:
        ctx.e2e[m["name"]] = reader(m["name"]).read(ctx)
    chosen = layer if trace else e2e
    values = {}
    for m in chosen:
        v = ctx.e2e[m["name"]] if not trace else reader(m["name"]).read(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": int(offered),
        "failed": int(np.unique(dropped).shape[0]),
        "metrics": values,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(chips), "visible": len(devs),
                   "memory_peak_bytes": peak_bytes},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s()
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_by_span(10)}
    result["check"] = {k: {"value": readings[k], "limit": lim}
                       for k, lim in check_lib.LIMITS.items()}
    return {"result": result, "readings": readings,
            "control": control_readings, "compiles": compiles,
            "check_s": check_s, "trace_s": trace_s, "gc_pauses": gc_pauses,
            "e2e": ctx.e2e,
            "window": (t0, t1),
            "setup_parts": setup_parts,
            "backlog": backlog, "chips": [d.id for d in chips], "aligned": (
                None if reduced is None
                else {d.name: d.aligned for d in reduced.devices})}
