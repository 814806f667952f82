"""One run of one cell: build the job, warm it up, measure a window,
check what the timed path produced against the plain reference, and
return the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name `BENCHMARK.json` gives it:
`configs/<config>.json` with the plain reference it names
(`configs/<reference>.py`), `drivers/<driver>.py` (the program's own
loop, per driver), `traffic/<traffic>.json`, `metrics/<metric>.py`,
`work/<work>.py`. Adding a cell, a configuration or a metric is adding
files and manifest entries; nothing here is edited.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import time
from typing import Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# rounds driven before the window: the first CHECK_ROUNDS are the ones
# the reference follows; the rest make sure every program, transfer
# shape and lagged read of the steady loop has run once
CHECK_ROUNDS = 3
WARM_ROUNDS = 6
# a traced run traces this much of its window (traffic may override)
TRACE_SECONDS = 4.0


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(bench_dirs, *parts) -> str:
    for d in bench_dirs:
        path = os.path.join(d, *parts)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"{os.path.join(*parts)} under none of {list(bench_dirs)}")


class Cell:
    """A manifest entry with everything it names resolved to files."""

    def __init__(self, manifest_path: str, workload: str):
        with open(manifest_path) as f:
            self.manifest = json.load(f)
        base = os.path.dirname(os.path.abspath(manifest_path))
        self.base = base
        self.bench_dirs = [os.path.join(base, p)
                           for p in self.manifest["paths"]]
        if HERE not in self.bench_dirs:
            self.bench_dirs.append(HERE)
        try:
            self.workload = next(w for w in self.manifest["workloads"]
                                 if w["name"] == workload)
        except StopIteration:
            raise SystemExit(f"fedbench: no workload {workload!r} in "
                             f"{manifest_path}")
        entry = next(c for c in self.manifest["configs"]
                     if c["name"] == self.workload["config"])
        with open(os.path.join(base, entry["file"])) as f:
            self.config = json.load(f)
        from fedbench import traffic as traffic_mod
        self.traffic = traffic_mod.load_traffic(
            self.bench_dirs, self.workload["traffic"])
        self.chips = int(self.workload["chips"])
        self.driver = load_module(
            find(self.bench_dirs, "drivers", self.config["driver"] + ".py"),
            "fedbench_driver_" + self.config["driver"])
        self.ref_module = load_module(
            find(self.bench_dirs, "configs",
                 self.config["reference"] + ".py"),
            "fedbench_ref_" + self.config["reference"])
        self.work = load_module(
            find(self.bench_dirs, "work", self.config["work"] + ".py"),
            "fedbench_work_" + self.config["work"])

    def metrics(self, kind: str) -> list:
        """The cell's metric entries of `kind` ('end_to_end' |
        'per_layer'): those without a `workloads` key, and those whose
        key lists this cell."""
        name = self.workload["name"]
        return [m for m in self.manifest[kind]
                if "workloads" not in m or name in m["workloads"]]


def device_record(chips: int, expect_platform: Optional[str]) -> dict:
    """The device as JAX reports it; exits non-zero where it is not
    what the cell asks for (no accelerator, too few chips, a chip the
    table of peaks does not know)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if expect_platform is not None:
        if dev.platform != expect_platform:
            raise SystemExit(
                f"fedbench: JAX found platform {dev.platform!r}, this "
                f"benchmark runs on {expect_platform!r} only")
        if len(devices) < chips:
            raise SystemExit(
                f"fedbench: the cell asks for {chips} chips, JAX found "
                f"{len(devices)}")
        if peaks_for(dev.device_kind) is None:
            raise SystemExit(
                f"fedbench: device kind {dev.device_kind!r} is not in "
                f"fedbench/peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def peaks_for(kind: str) -> Optional[dict]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)["devices"].get(kind)


def memory_peak_bytes() -> int:
    """The peak on the fullest device, from `device.memory_stats()`:
    `peak_bytes_in_use` plus `peak_bytes_reserved`. On the TPU the
    loaded programs' temporary space is reserved apart from the
    buffers 'in use' (a round program with 6.4 GB of activations
    reads 0.57 GB in use and 6.38 GB reserved; PERF.md, section 2),
    so either alone leaves out part of what the chip holds."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(f"[fedbench] device {d.id} memory_stats " + json.dumps(
            {k: int(v) for k, v in sorted(stats.items())}))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


class CompileCounter:
    """Backend compiles, counted by the benchmark's own listener."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += float(duration)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all `values`."""
    s = sorted(values)
    if not s:
        return float("nan")
    rank = max(int(np.ceil(q / 100.0 * len(s))), 1)
    return float(s[rank - 1])


def run(workload: str, seed: int, seconds: float, trace: bool,
        manifest_path: Optional[str] = None,
        expect_platform: Optional[str] = "tpu",
        t_start: Optional[float] = None,
        fault: Optional[str] = None, bf16: bool = False) -> dict:
    """One run; returns the result object (`correct`, `attempted`,
    `failed`, `metrics`, `device`, `breakdown`, `checks`).

    `fault` and `bf16` are for the control and the fault tests only
    (`control.py`, `tests/`): 'half_batch' feeds the program a batch
    with the second half of the cohort masked out while the reference
    sees all of it; 'frozen' makes the round program hand back the
    server state it was given; `bf16` switches the program's own
    lower precision on. `run.py` passes none of them."""
    t_start = time.perf_counter() if t_start is None else t_start
    manifest_path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
    cell = Cell(manifest_path, workload)

    # the program and its cache directory; in a directory that holds
    # only the benchmark this import is what fails
    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    import jax
    enable_persistent_compilation_cache()
    device = device_record(cell.chips, expect_platform)
    compiles = CompileCounter()

    def lap(what: str) -> None:
        say(f"[fedbench] set-up +{time.perf_counter() - t_start:.2f} s "
            f"{what}")

    lap("imports and device")

    cache_dir = os.path.join(cell.bench_dirs[0], ".cache")
    from fedbench import reference as ref
    from fedbench import traffic as traffic_mod
    data_dir = traffic_mod.ensure_corpus(cell.traffic, cache_dir)
    run_dir = os.path.join(cache_dir, "runs", workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir, exist_ok=True)
    journal = os.path.join(run_dir, "journal.jsonl")
    lap("corpus")

    drv = cell.driver
    job = drv.build(cell.config, cell.traffic, cell.ref_module, seed,
                    data_dir, journal, bf16=bf16, trace=trace)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), job.params)
    slices = ref.leaf_slices(job.params)
    weights0 = drv.weights(job)
    lap("job built (loaders, weights, FedModel)")
    ok = False
    try:
        # ---- set-up: the first rounds, through the window's own call
        if fault == "frozen":
            _freeze(job.model)
        gen = drv.rounds(job, tamper=(_mask_second_half
                                      if fault == "half_batch" else None))
        feeds, lrs, prog_steps = [], [], []
        first_state = weights_first = None
        for i in range(max(WARM_ROUNDS, CHECK_ROUNDS)):
            out = next(gen)
            if i < CHECK_ROUNDS:
                batch = out.batch
                feeds.append(_copy_batch(batch))
                lrs.append(out.lr)
                prog_steps.append((out.outputs[0], out.upload_bytes))
                if i == 0:
                    first_state = drv.state_after_first(job, batch)
                    # for `support_overlap`: how far the two sides
                    # selected the same coordinates. An uncompressed
                    # round selects none, so it would say nothing
                    # there for one more D-vector held through set-up
                    if cell.traffic["mode"] != "uncompressed":
                        weights_first = drv.weights(job)
                    lap("first round (programs traced, compiled or "
                        "loaded)")
                if i == CHECK_ROUNDS - 1:
                    weights_checked = drv.weights(job)
        drv.sync(job)
        compiles_before = compiles.n
        setup_s = time.perf_counter() - t_start
        say(f"[fedbench] set-up {setup_s:.2f} s, {compiles.n} backend "
            f"compiles ({compiles.seconds:.1f} s)")

        # ---- the window
        window = _measure(cell, job, gen, seconds, trace, run_dir)
        window["compiles_in_window"] = compiles.n - compiles_before
        peak = memory_peak_bytes()
        ok = True
    finally:
        drv.close(job, ok)

    # ---- the comparison, once the window has closed and the
    # program's state is freed. `program` is the only name left for
    # its vectors, so that `take_sums` can let each go once read.
    program = ref.Readings(
        [ref.StepReadings(float(np.mean(np.asarray(l))), float(u),
                          first_state if i == 0 else {})
         for i, (l, u) in enumerate(prog_steps)], weights_checked,
        weights_first)
    del job, gen, out, first_state, weights_checked, weights_first
    gc.collect()
    t_ref = time.perf_counter()
    fns = ref.make_model_fns(cell.ref_module, cell.config, template)
    sums = ref.take_sums(program, ref.reference_rounds(
        ref.job_from(cell.config, cell.traffic), fns, weights0,
        feeds, lrs), weights0, slices)
    del program, weights0
    checks = ref.compare(sums, cell.traffic["limits"])
    checks["compiles_in_window"] = {
        "value": float(window["compiles_in_window"]), "limit": 0.0}
    correct = ref.is_correct(checks)
    for name, value in ref.diagnostics(sums).items():
        say(f"[fedbench] diagnostic {name}={value:.6g}")
    say(f"[fedbench] reference {time.perf_counter() - t_ref:.2f} s, "
        f"host peak {host_peak_gib():.2f} GiB resident")

    values = dict(window["metrics"])
    values["setup_s"] = setup_s
    wanted = cell.metrics("per_layer" if trace else "end_to_end")
    metrics = {}
    for m in wanted:
        v = values.get(m["name"])
        if v is None or not np.isfinite(v):
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    result = {"correct": bool(correct),
              "attempted": int(window["rounds"]),
              "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = window["busy_s"]
        device["window_s"] = window["window_s"]
        result["breakdown"] = window["breakdown"]
    result["checks"] = checks
    return result


def host_peak_gib() -> float:
    """The process's peak resident size so far (`ru_maxrss`, which
    Linux counts in KiB): what the next cell's comparison is sized
    from."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20


def _mask_second_half(batch):
    """The fault 'half of the batch left out, the mean taken over the
    rest': the second half of the cohort's rows are masked out of
    what the program is fed."""
    ids, data, mask = batch
    mask = np.array(mask)
    mask[mask.shape[0] // 2:] = 0.0
    return ids, data, mask


def _freeze(model) -> None:
    """The fault 'a step that returns its state unchanged': the round
    program runs, and the server state it was given comes back."""
    real = model._train_round

    def frozen(server, clients, batch, lr, key):
        _, clients, metrics = real(server, clients, batch, lr, key)
        return server, clients, metrics

    model._train_round = frozen


def _copy_batch(batch):
    ids, data, mask = batch
    return (np.array(ids), tuple(np.array(x) for x in data),
            np.array(mask))


def _measure(cell: Cell, job, gen, seconds: float, trace: bool,
             run_dir: str) -> dict:
    """The measured window: rounds until `seconds` have passed, ended
    by `block_until_ready` on the last round's server state. With
    `trace`, the profiler runs over the first TRACE_SECONDS of it and
    the per-layer readers get that part."""
    import jax

    drv = cell.driver
    trace_s = float(cell.traffic.get("trace_seconds", TRACE_SECONDS))
    trace_dir = os.path.join(run_dir, "trace")
    returns, stage, api, valid = [], [], [], []
    clock = time.perf_counter
    tracing = False
    traced = None
    if trace:
        # the Python tracer and the runtime's per-task host events
        # slow the host loop they are meant to watch and make the
        # trace a hundred megabytes; TraceAnnotations (level 1) stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tracing = True
    t0 = clock()
    m0 = time.monotonic()      # the program's TRACE spans' clock
    while True:
        if tracing:
            with jax.profiler.TraceAnnotation("fedbench:round"):
                out = next(gen)
        else:
            out = next(gen)
        now = clock()
        returns.append(now)
        stage.append(out.stage_s)
        api.append(out.api_s)
        valid.append(out.valid_examples)
        if tracing and now - t0 >= min(trace_s, seconds):
            drv.sync(job)
            t_traced = clock()
            jax.profiler.stop_trace()
            tracing = False
            traced = {"rounds": len(returns), "seconds": t_traced - t0,
                      "mono": (m0, time.monotonic())}
        if now - t0 >= seconds:
            break
    drv.sync(job)
    t1 = clock()
    n = len(returns)
    gaps = np.diff(np.array([t0] + returns))
    window_s = t1 - t0
    metrics = {
        "round_ms": window_s / n * 1e3,
        "round_p95_ms": percentile(gaps * 1e3, 95.0),
    }
    say(f"[fedbench] window {window_s:.3f} s, {n} rounds; host per "
        f"round: loader {np.mean(stage) * 1e3:.2f} ms, "
        f"FedModel.__call__ {np.mean(api) * 1e3:.2f} ms; "
        f"{np.mean(valid):.0f} valid examples a round")
    out = {"rounds": n, "metrics": metrics, "window_s": window_s,
           "busy_s": None, "breakdown": None}
    if trace:
        from fedbench import reduce as reducer
        m = traced["rounds"]
        ctx = {
            "cell": cell.workload["name"],
            "config": cell.config, "traffic": cell.traffic,
            "work": cell.work, "chips": cell.chips,
            "rounds": m, "window_s": traced["seconds"],
            "stage_s": stage[:m], "api_s": api[:m],
            "valid_examples": valid[:m],
            "peaks": peaks_for(jax.devices()[0].device_kind),
            "program_spans": [
                s for s in reducer.journal_spans(
                    os.path.join(run_dir, "journal.jsonl"))
                if traced["mono"][0] <= s.get("t0", -1.0)
                <= traced["mono"][1]],
            "trace": reducer.reduce_trace(reducer.find_xplane(trace_dir)),
        }
        for m_entry in cell.metrics("per_layer"):
            reader = load_module(
                find(cell.bench_dirs, "metrics", m_entry["name"] + ".py"),
                "fedbench_metric_" + m_entry["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m_entry["name"]] = value
        by_span = {}
        for span in ctx["program_spans"]:
            by_span[span["name"]] = (by_span.get(span["name"], 0.0)
                                     + float(span.get("dur", 0.0)))
        say(f"[fedbench] traced {m} rounds in {traced['seconds']:.3f} s;"
            " program spans, ms a round: " + json.dumps(
                {k: round(v / m * 1e3, 3)
                 for k, v in sorted(by_span.items())}))
        out["busy_s"] = ctx["trace"]["busy_s"]
        out["window_s"] = ctx["trace"]["window_s"]
        out["breakdown"] = reducer.breakdown(ctx["trace"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def print_result(result: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines
    of standard error; the result as the last line of standard
    output, with the same numbers under its last key."""
    checks = result.pop("checks")
    result["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        say(f"[fedbench] check {name} value={c['value']:.6g} "
            f"limit={c['limit']:.6g} {verdict}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
