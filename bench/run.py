"""On-chip benchmark of the ask–tell service: one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (`bench/configs/<config>.json`) and its
traffic (`bench/traffic/<traffic>.json`) are found by name through
`BENCHMARK.json`.  The run builds the served path (`StudyGateway` ->
`StudyPool` -> `StudyEngine.advance` -> kernels) from the configuration,
tells the starting histories, warms up every program the traffic uses,
then measures `--seconds` of closed-loop traffic.  With `--trace 1` it
profiles the last seconds of the window and reports the per-layer metrics
instead of the end-to-end ones.  Afterwards it compares what was served
with the plain reference (`bench/check.py`).

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, and with `--trace 1`
`breakdown`, then `checks`).  Without a TPU, or outside a checkout that
holds `src/repro`, it exits non-zero and prints no result.

JAX's persistent compilation cache is kept in `.jax_cache/` at the root of
the checkout, whatever the environment says.

Options for measuring the benchmark itself, which the cells never pass:
`--set traffic.workers=256` overrides a traffic or configuration value;
`--control high|default` puts the precision control (the reference at
that lower precision) in the served state's place for `mean_err` and
`var_err`, so that `correct` reads whether the control fails them, and
logs the served state's own numbers beside it; `--fault
answer_altered|absorb_dropped` breaks the timed path on purpose; and
`--keep-trace FILE` keeps the traced run's xplane file.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


class Refused(Exception):
    """The run cannot measure here: no result is printed."""


def _load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def _override(cfg: dict, traffic: dict, items) -> None:
    for item in items or ():
        key, _, val = item.partition("=")
        root, *path = key.split(".")
        node = {"traffic": traffic, "config": cfg}[root]
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = json.loads(val)


def load_cell(name: str, overrides=None):
    spec = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = _load_json(ROOT / conf["file"])
    traffic = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    _override(cfg, traffic, overrides)
    from generator import check_params
    try:
        check_params(traffic, int(cfg["scheduler"]["n_max"]))
    except ValueError as e:
        raise SystemExit(f"traffic {cell['traffic']!r}: {e}") from None
    return spec, cell, cfg, traffic


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def quantile(values, q: float) -> float:
    """The q-quantile by the nearest-rank rule (failed asks are +inf)."""
    import math
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(0, min(len(v) - 1, math.ceil(q * len(v)) - 1))]


def _metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, f))
            except OSError:
                pass
    return total


def _log(msg: str) -> None:
    print(msg, flush=True)


def _require_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} TPU chips, JAX found "
                      f"{len(devs)}")
    return devs


def run(args, require_chip: bool = True) -> dict:
    spec, cell, cfg, traffic_p = load_cell(args.workload, args.set)
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no src/repro beside {BENCH}: not a checkout")
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    for k, v in cfg.get("env", {}).items():
        os.environ[k] = str(v)
    flags = " ".join(cfg.get("libtpu_flags", []))
    if flags:
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "") + " " + flags).strip()
    import jax
    import numpy as np
    devs = _require_chip(int(cell["chips"])) if require_chip else \
        jax.devices()
    jax.config.update("jax_compilation_cache_dir", cache)
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness
    import check
    from generator import Traffic
    clock = harness.CompileClock()
    d0 = devs[0]
    _log(f"device: {d0.platform} {d0.device_kind}, {len(devs)} visible")
    _log(f"compile cache: {cache_dir}")
    seed = int(args.seed)
    store = tempfile.mkdtemp(prefix="bench_evict_")
    try:
        return _run_cell(args, spec, cell, cfg, traffic_p, seed, store,
                         clock, devs, jax, np, harness, check, Traffic)
    finally:
        shutil.rmtree(store, ignore_errors=True)


def _run_cell(args, spec, cell, cfg, traffic_p, seed, store, clock, devs,
              jax, np, harness, check, Traffic) -> dict:
    gw = harness.build_gateway(cfg, seed & 0x7FFFFFFF, store)
    if args.fault:
        harness.plant_fault(gw, args.fault, seed)
    capture = (lambda g, sid: check.read_state(g, sid)) \
        if traffic_p.get("capture_closed") else None
    traffic = Traffic(gw, traffic_p, cfg["objective"], seed, capture=capture)
    t = time.perf_counter()
    told = traffic.fill()
    jax.block_until_ready(gw.pool.engine.state)
    _log(f"setup: fill told {told} observations in "
         f"{time.perf_counter() - t:.3f} s")
    traffic.warm_store()
    if capture is not None:
        check.read_slot(gw, 0)    # compile the read-back before the window

    # The collector's full passes inside the window are timed and printed.
    import gc
    pauses: list[float] = []
    started = [0.0]

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                started[0] = time.perf_counter()
            else:
                pauses.append(time.perf_counter() - started[0])
    gc.callbacks.append(on_gc)

    tracing = bool(args.trace)
    work: dict = {}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if tracing else None
    ann = {"window": None}
    if tracing:
        harness.instrument(gw, jax.profiler.TraceAnnotation, work)
    marks: dict = {}

    def on_open():
        marks["gc"] = len(pauses)
        if tracing:
            lead = float(args.seconds) - min(float(args.seconds), float(
                traffic_p.get("trace_s", 4.0)))
            ann["task"] = asyncio.ensure_future(tracer(lead))
        marks["compiles"] = clock.compiles
        marks["compile_s"] = clock.seconds
        marks["tick"] = gw._tick_count
        marks["setup_s"] = time.perf_counter() - PROCESS_START

    def on_close():
        marks["gc_end"] = len(pauses)
        marks["tick_end"] = gw._tick_count
        marks["compiles_end"] = clock.compiles
        if ann["window"] is not None:
            ann["window"].__exit__(None, None, None)
            ann["window"] = None
            work["window"][0] = False

    async def tracer(seconds: float):
        await asyncio.sleep(max(0.0, seconds))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann["window"] = jax.profiler.TraceAnnotation("bench.window")
        ann["window"].__enter__()
        work["window"][0] = True

    async def serve():
        out = await traffic.serve(
            float(traffic_p["warmup_s"]), float(args.seconds),
            warmup_ticks=int(traffic_p.get("warmup_ticks", 0)),
            on_open=on_open, on_close=on_close)
        if "task" in ann:
            await ann["task"]
        await gw.aclose()
        return out

    loop = asyncio.new_event_loop()
    t_open, t_close = loop.run_until_complete(serve())
    loop.close()
    jax.block_until_ready(gw.pool.engine.state)
    if tracing:
        jax.profiler.stop_trace()
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devs[:int(cell["chips"])])

    # -- end-to-end numbers, all asks issued in the window ------------------
    win = [a for a in traffic.asks if t_open <= a.t_issue < t_close]
    lat = [(a.t_reply - a.t_issue) * 1e3 if a.ok else float("inf")
           for a in win]
    delivered = sum(1 for a in traffic.asks
                    if a.ok and t_open <= a.t_reply < t_close)
    failed = sum(1 for a in win if not a.ok)
    window_s = t_close - t_open
    e2e = {"suggestions_per_s": delivered / window_s,
           "ask_p50_ms": quantile(lat, 0.50),
           "ask_p95_ms": quantile(lat, 0.95),
           "setup_s": marks["setup_s"]}
    late = sorted(traffic.think_late) or [0.0]
    ticks = [s for s in gw.stats
             if marks["tick"] < s["tick"] <= marks["tick_end"]]
    summ = gw.summary()
    _log(f"window: perf_counter {t_open:.3f} to {t_close:.3f}")
    _log(f"window: {window_s:.3f} s, asks {len(win)} attempted, {failed} "
         f"failed, {delivered} delivered; tells {traffic.tells} attempted, "
         f"{traffic.tell_failures} failed")
    _log(f"window: mean n of the suggestions' studies "
         f"{np.mean([a.n_cond for a in win if a.ok] or [0]):.1f}; ticks "
         f"{len(ticks)}, mean width "
         f"{np.mean([s['width'] for s in ticks] or [0]):.1f}, "
         f"queued_after first/last "
         f"{ticks[0]['queued_after'] if ticks else 0}/"
         f"{ticks[-1]['queued_after'] if ticks else 0}, evictions "
         f"{sum(s['evictions'] for s in ticks)}, restores "
         f"{sum(s['restores'] for s in ticks)}")
    _log(f"think timers late: p95 {quantile(late, 0.95) * 1e3:.3f} ms, max "
         f"{late[-1] * 1e3:.3f} ms")
    gc.callbacks.remove(on_gc)
    win_gc = pauses[marks["gc"]:marks["gc_end"]]
    _log(f"full collections inside the window: {len(win_gc)}, longest "
         f"{max(win_gc or [0.0]) * 1e3:.3f} ms, total "
         f"{sum(win_gc) * 1e3:.3f} ms")
    _log(f"compiles inside the window: "
         f"{marks['compiles_end'] - marks['compiles']}; set-up compile "
         f"{marks['compile_s']:.3f} s")
    _log(f"studies closed inside the window: "
         f"{sum(1 for c in traffic.closes if t_open <= c[0] < t_close)} "
         f"({len(traffic.closes)} in the run)")
    from repro.kernels import ops
    n_pad = ops._round_up(int(cfg["scheduler"]["n_max"]))
    tile = ops.acq_tile_config(n_pad, gw.pool.engine.gp_cfg.dim,
                               int(cfg["gateway"]["slots"]), False)
    _log(f"EI tile: block_r={tile.block_r} d_pad={tile.d_pad} "
         f"measured={tile.measured} (REPRO_ACQ_AUTOTUNE="
         f"{os.environ.get('REPRO_ACQ_AUTOTUNE', 'on')})")
    _log(f"eviction store: {_dir_bytes(gw.cfg.ckpt_dir)} bytes written, "
         f"{summ['evictions']} evictions, {summ['restores']} restores")
    _log(f"memory: peak {mem} bytes")

    result_device = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs),
                     "memory_peak_bytes": mem}
    metrics: dict = {}
    breakdown = None
    if tracing:
        import xplane as trace_mod
        t = time.perf_counter()
        xp = trace_mod.find_xplane(trace_dir)
        tr = trace_mod.load(xp)
        if args.keep_trace:
            shutil.copy(xp, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = _load_json(BENCH / "peaks.json")
        if devs[0].device_kind not in peaks:
            raise RuntimeError(f"device kind {devs[0].device_kind!r} is not "
                               "in bench/peaks.json")
        ctx = types.SimpleNamespace(
            ticks=ticks, slots=int(cfg["gateway"]["slots"]), trace=tr,
            work=work, peaks=peaks[devs[0].device_kind])
        for m in spec["per_layer"]:
            if applies(m, cell["name"]):
                v = _metric_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result_device["busy_s"] = tr.busy_s()
        result_device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
        _log(f"trace: {tr.window_s:.3f} s traced, {tr.busy_s():.6f} s busy, "
             f"read in {time.perf_counter() - t:.3f} s")
    else:
        for m in spec["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # -- correctness: read back, free the device state, then the reference -
    t = time.perf_counter()
    chk = traffic_p["check"]
    samples = check.pick_studies(gw, traffic, int(chk["studies"]), seed)
    numbers = check.accounting(gw, traffic, samples, cfg)
    numbers["asks_unanswered"] += sum(1 for a in win if a.ok and
                                      a.t_reply < a.t_issue)
    dim = gw.pool.engine.gp_cfg.dim
    gw = traffic.gw = None
    gc.collect()
    suggestions = check.pick_suggestions(traffic, (t_open, t_close),
                                         int(chk["suggestions"]), seed)
    served = check.posterior_numbers(samples, cfg, seed, dim,
                                     int(chk["queries"]))
    if args.control:
        for k, v in served.items():
            _log(f"served state: {k} {v!r}")
        served = check.posterior_numbers(samples, cfg, seed, dim,
                                         int(chk["queries"]),
                                         control=args.control)
        for k, v in served.items():
            _log(f"control {args.control}: {k} {v!r}")
    numbers.update(served)
    numbers["ei_rank"] = check.ei_rank(
        suggestions, traffic, cfg, seed, dim, int(chk["candidates"]))
    _log(f"check: {len(samples)} studies ("
         f"{sum(s.restored for s in samples)} restored, longest "
         f"{max((len(s.hist) for s in samples), default=0)}), "
         f"{len(suggestions)} suggestions, in "
         f"{time.perf_counter() - t:.3f} s")
    checks = {k: [numbers[k], lim]
              for k, lim in check.limits(traffic_p).items()}
    correct = all(v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": len(win), "failed": failed,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append")
    ap.add_argument("--control", choices=("high", "default"))
    ap.add_argument("--fault", choices=("answer_altered", "absorb_dropped"))
    ap.add_argument("--keep-trace", metavar="FILE")
    args = ap.parse_args(argv)
    try:
        result = run(args, require_chip=require_chip)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, (v, lim) in result["checks"].items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
