"""One run of one cell: set-up, warm-up, the measured window, the checks,
the trace and its reduction, and the line the driver reads.

The window is the benchmark's own loop around the step the program's
entry points build (PERF.md §3 says what that leaves out): pick the next
resident batch, call the step, ``block_until_ready`` the whole state,
fetch the per-rank loss, read the clock — what ``Trainer._train_epoch``
does each iteration (train/loop.py:1086-1118).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import time
import typing as tp

import numpy as np

from benchmark import required_ops, spec, trace_reduce

WARMUP_STEPS = 3          # steps 1-3 of the trajectory, before the window
LOSS_SPAN = 10            # loss_at_n averages steps n-9..n
TRACE_SKIP = 5            # window steps before the traced stretch
TRACE_STEPS = 20          # length of the traced stretch
# a random model's loss is ln(classes) plus half the variance of its
# logits: near 0 for ResNet-50 (zero-initialised last BatchNorm scales),
# near half a nat for the LM (PR 21 read 10.88 against ln 32768 = 10.40)
INITIAL_LOSS_TOLERANCE = 0.10
PS_WEIGHT_TOLERANCE = 1e-5
OUT_DIR = ".bench_out"    # inside the checkout, git-ignored

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts the programs JAX builds (compiled or loaded from the cache:
    either means a new shape reached the device path)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == _COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


@dataclasses.dataclass
class Reading:
    """What a per-layer reader may look at."""

    cell: spec.Cell
    job: tp.Any                       # benchmark.job.Job, final state in it
    params: dict                      # the metric file's own "params"
    step_ms: float                    # this run's window
    host_ms: dict[str, float]         # mean per step: pick, dispatch, fence, fetch
    peak: dict                        # benchmark/peaks.json row of the chip
    values: dict[str, float]          # metrics already read in this run
    # from the trace; None where the run's window ended before the stretch
    trace: trace_reduce.Trace | None = None
    window: tuple[float, float] | None = None   # the traced stretch
    busy_s: float | None = None       # device busy in it, mean over chips

    @property
    def traced_steps(self) -> int:
        return len(self.trace.steps) if self.trace is not None else 0


def _fetch_loss(metrics) -> np.ndarray:
    return np.asarray(metrics["loss"], np.float64).reshape(-1)


def run_window(job, seconds: float, trace_dir: str | None,
               min_steps: int = 1):
    """The measured loop: steps until ``seconds`` have passed and, where a
    test asks for it, at least ``min_steps`` are done (``run.py`` never
    does: a run's window is its seconds).  Returns per-step host clocks
    ``[steps, 5]`` (start, picked, dispatched, fenced, fetched), per-step
    per-rank losses and whether a trace was taken."""
    import jax
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    state, step, batches = job.state, job.step, job.batches
    clocks, losses = [], []
    traced = False
    n = 0
    t0 = time.perf_counter()
    t_start = t0
    while True:
        if trace_dir is not None:
            if n == TRACE_SKIP:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                traced = True
                t_start = time.perf_counter()
            elif n == TRACE_SKIP + TRACE_STEPS:
                jax.profiler.stop_trace()
                t_start = time.perf_counter()
        with StepTraceAnnotation(trace_reduce.STEP_NAME, step_num=n):
            with TraceAnnotation("bench:pick"):
                x, y = batches[n % len(batches)]
            t_pick = time.perf_counter()
            with TraceAnnotation("bench:dispatch"):
                state, metrics = step(state, x, y)
            t_dispatch = time.perf_counter()
            with TraceAnnotation("bench:fence"):
                jax.block_until_ready(state)
            t_fence = time.perf_counter()
            with TraceAnnotation("bench:loss_fetch"):
                losses.append(_fetch_loss(metrics))
            t_fetch = time.perf_counter()
        clocks.append((t_start, t_pick, t_dispatch, t_fence, t_fetch))
        n += 1
        t_start = t_fetch
        if t_fetch - t0 >= seconds and n >= min_steps:
            break
    if traced and n <= TRACE_SKIP + TRACE_STEPS:
        jax.profiler.stop_trace()
    job.state = state
    return np.asarray(clocks), np.asarray(losses), traced, t_fetch - t0


def _ps_weight_error(job) -> float | None:
    ps = getattr(job.state.gossip, "ps_weight", None)
    if ps is None:
        return None
    total = float(np.sum(np.asarray(ps, np.float64)))
    return abs(total - job.world) / job.world


def _placement(job, compiled) -> dict:
    """Where the state lies, and whether the compiled step moves it
    between chips."""
    import jax

    def devices_of(tree):
        return sorted({d.id for leaf in jax.tree.leaves(tree)
                       for d in leaf.sharding.device_set})

    return {"param_devices": devices_of(job.state.params),
            "ps_weight_devices": devices_of(
                getattr(job.state.gossip, "ps_weight", ())),
            "collective_permutes":
                compiled.as_text().count("collective-permute")}


def _program_memory(compiled) -> dict:
    """The compiler's own count for the step, bytes on one chip."""
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k + "_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp")}


def device_info(job) -> tuple[dict, list]:
    """The line's ``device`` and every chip's allocator statistics, read
    right after the window.  On this runtime ``peak_bytes_in_use`` counts
    live arrays only; what the loaded step reserves for its temporaries is
    ``bytes_reserved``, a part of the memory of its own (PR 24's trace:
    in use + reserved + available = the limit; reserved = the compiler's
    temp count).  State and batches were in use all through the window
    beside that reservation, so the peak is the larger of the arrays'
    own high-water mark and what is in use now plus what is reserved."""
    devices = list(job.mesh.devices.flat)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(max(int(s.get("peak_bytes_in_use", 0)),
                   int(s.get("bytes_in_use", 0))
                   + int(s.get("bytes_reserved", 0))) for s in stats)
    return ({"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices),
             "memory_peak_bytes": peak}, stats)


def drop_step(job) -> None:
    """Unload the step: the memory its program reserves for temporaries
    goes back to the allocator.  The window is over; nothing calls it
    again."""
    import gc

    import jax

    job.step = None
    jax.clear_caches()
    gc.collect()


def _set_up(root, cell, seed, process_start, compiles, checks):
    """Everything before the first timed step; returns the job, the
    warm-up steps' losses and the seconds each phase took."""
    import jax

    phases = {"before_build": time.time() - process_start}
    clock = time.time()

    def phase(name):
        nonlocal clock
        phases[name] = time.time() - clock
        clock = time.time()

    job = spec.load_plugin(root, "builders", cell.builder).build(cell, seed)
    jax.block_until_ready((job.state, job.batches))
    phase("build_state_and_batches")
    if job.world != cell.chips:
        raise ValueError(f"{cell.name}: traffic has {job.world} ranks, "
                         f"the cell asks for {cell.chips} chips")
    # compiled ahead of its first call: the call below finds this very
    # executable, and its text and byte counts cost nothing more
    compiled = job.step.lower(job.state, *job.batches[0]).compile()
    checks["program_bytes"] = _program_memory(compiled)
    ahead = compiles.count
    phase("step_compiled_or_loaded")
    warm_up = []
    for i in range(WARMUP_STEPS):
        x, y = job.batches[i % len(job.batches)]
        job.state, metrics = job.step(job.state, x, y)
        jax.block_until_ready(job.state)
        warm_up.append(_fetch_loss(metrics))
    checks["step_built_twice"] = compiles.count - ahead
    phase("warm_up_steps")
    if cell.chips > 1:
        checks["placement"] = _placement(job, compiled)
    checks["ps_weight_error_before"] = _ps_weight_error(job)
    phase("checks_before_window")
    checks["setup_phases_s"] = phases
    return job, warm_up


def _verdicts(cell, job, checks, values, trajectory) -> dict:
    """The checks that decide ``correct``, by name."""
    first = checks["loss_first"]
    verdicts = {
        "losses_finite": bool(np.all(np.isfinite(trajectory))),
        "first_loss_is_a_random_model's":
            abs(first - job.initial_loss)
            <= INITIAL_LOSS_TOLERANCE * job.initial_loss,
        "loss_fell": "loss_at_n" in values and values["loss_at_n"] < first,
        "push_sum_mass_kept": all(
            e is None or e <= PS_WEIGHT_TOLERANCE
            for e in (checks["ps_weight_error_before"],
                      checks["ps_weight_error_after"])),
        "nothing_compiled_in_window":
            checks["compilations_in_window"] == 0,
    }
    if "reference" in checks:
        verdicts["agrees_with_plain_reference"] = checks["reference"]["ok"]
    if cell.chips > 1:
        where = checks["placement"]
        verdicts["state_on_every_chip"] = (
            len(where["param_devices"]) == cell.chips
            and len(where["ps_weight_devices"]) == cell.chips)
        verdicts["step_moves_state_between_chips"] = \
            where["collective_permutes"] > 0
    return verdicts


def _compared(job, checks, values) -> dict:
    """Each number ``_verdicts`` holds against a limit, beside that limit:
    what the driver's record keeps of a run that is not correct."""
    first = checks["loss_first"]
    out = {
        "first_loss_off_random": [abs(first - job.initial_loss)
                                  / job.initial_loss,
                                  INITIAL_LOSS_TOLERANCE],
        "loss_at_n_under_first": [values.get("loss_at_n"), first],
        "ps_weight_error": [max((e for e in (
            checks["ps_weight_error_before"],
            checks["ps_weight_error_after"]) if e is not None),
            default=None), PS_WEIGHT_TOLERANCE],
        "compiled_in_window": [checks["compilations_in_window"], 0],
    }
    if "reference" in checks:
        ref = checks["reference"]
        out["logit_error"] = [ref["logit_error"], ref["logit_tolerance"]]
        out["loss_error"] = [ref["loss_error"], ref["loss_tolerance"]]
    return {name: {"value": v, "limit": limit}
            for name, (v, limit) in out.items()}


def _per_layer(root, cell, reading: Reading, trace_dir, device, log) -> dict:
    """Read the trace where one was taken, then ask every per-layer
    metric's reader; returns what joins the result line."""
    out = {}
    if trace_dir is not None:
        reading.trace = trace_reduce.read_trace(
            trace_reduce.find_xplane(trace_dir))
        reading.window = trace_reduce.window_of(reading.trace)
        reading.busy_s = trace_reduce.mean_over_devices(
            reading.trace, lambda ev, _: trace_reduce.busy_seconds(
                ev, reading.window))
        device.update(busy_s=reading.busy_s or 0.0,
                      window_s=reading.window[1] - reading.window[0])
        parts = _breakdown(reading)
        for part, rows in parts.items():
            for name, seconds in rows:
                log(f"{cell.name}: {part}: "
                    f"{seconds * 1e3 / reading.traced_steps:9.3f} ms/step"
                    f"  {name}")
        # the contract's two lists of ten; the third is for the reader
        out["device_ops_by_kind"] = parts.pop("device_ops_by_kind")
        out["breakdown"] = parts
    metrics = {}
    for m in cell.per_layer:
        reading.params = m.get("params", {})
        value = spec.load_reader(root, m)(reading)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            reading.values[m["name"]] = value
            log(f"{cell.name}: {m['name']} = {value:.6g} {m['unit']}")
    out["metrics"] = metrics
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, process_start: float, log=print,
             min_steps: int = 1) -> dict:
    """Run one cell on whatever backend JAX has (``run.py`` refuses
    anything but the cell's TPU chips before it gets here) and return the
    result line as a dict.  ``min_steps`` is for the tests, whose machine's
    speed must not decide how far a toy window gets."""
    cell = spec.load_cell(root, workload)
    checks: dict[str, tp.Any] = {}
    with CompileCounter() as compiles:
        job, warm_up = _set_up(root, cell, seed, process_start, compiles,
                               checks)
        built = compiles.count
        setup_s = time.time() - process_start

        trace_dir = None
        if trace:
            trace_dir = os.path.join(root, OUT_DIR, "trace", workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
        clocks, losses, traced, window_s = run_window(
            job, seconds, trace_dir, min_steps)
        checks["compilations_in_window"] = compiles.count - built
    log(f"{workload}: {built} programs built in set-up, "
        f"{checks['compilations_in_window']} in the window; set-up "
        f"phases (s) {checks['setup_phases_s']}")

    # -- end to end ----------------------------------------------------
    steps = len(clocks)
    walls_ms = (clocks[:, 4] - clocks[:, 0]) * 1e3
    step_ms = window_s * 1e3 / steps
    trajectory = np.concatenate([np.asarray(warm_up), losses])
    n = cell.loss_n
    values = {"step_ms": step_ms,
              "step_ms_p90": float(np.percentile(walls_ms, 90)),
              "setup_s": setup_s}
    if LOSS_SPAN <= n <= len(trajectory):
        values["loss_at_n"] = float(np.mean(trajectory[n - LOSS_SPAN:n]))
    quantiles = dict(zip(("min", "p50", "p90", "p99", "max"), np.percentile(
        walls_ms, (0, 50, 90, 99, 100)).tolist()))
    rate = job.items_per_rank_step / step_ms * 1e3
    log(f"{workload}: {steps} steps in {window_s:.3f} s, step "
        f"{step_ms:.3f} ms (p90 {values['step_ms_p90']:.3f}), "
        f"{rate:.1f} {job.item}/s/chip, set-up {setup_s:.1f} s; step "
        f"walls {quantiles}")

    # -- correctness ---------------------------------------------------
    checks.update(
        loss_first=float(np.mean(trajectory[0])),
        loss_last=float(np.mean(trajectory[-1])),
        loss_every_10_steps=[float(np.mean(trajectory[i:i + LOSS_SPAN]))
                             for i in range(0, len(trajectory) - LOSS_SPAN + 1,
                                            LOSS_SPAN)],
        loss_expected_first=job.initial_loss,
        ps_weight_error_after=_ps_weight_error(job),
        steps_in_trajectory=len(trajectory), resolved=job.resolved)
    device, checks["memory_stats"] = device_info(job)
    log(f"{workload}: memory peak {device['memory_peak_bytes'] / 1e9:.3f} "
        f"GB; allocator {checks['memory_stats'][0]}; program "
        f"{checks['program_bytes']}")
    if job.reference_check is not None:
        # the plain reference, once the window has closed and the memory
        # peak has been read, on the state the window left, the step
        # unloaded: outside set-up, and with room for its own buffers
        # (beside the t8192 step's temporaries 1.6 GB of logits have none)
        clock = time.time()
        drop_step(job)
        checks["reference"] = job.reference_check(job.state)
        checks["reference"]["seconds"] = time.time() - clock
    verdicts = checks["verdicts"] = _verdicts(cell, job, checks, values,
                                              trajectory)
    for name, ok in verdicts.items():
        if not ok:
            log(f"{workload}: CHECK FAILED: {name}")
    result = {"correct": all(verdicts.values()), "attempted": steps,
              "failed": int(np.sum(~np.all(np.isfinite(losses), axis=1)))}

    # -- per layer -----------------------------------------------------
    if trace:
        # the readers' step time leaves the traced stretch out: starting,
        # feeding and stopping the profiler is not the program's time
        at = np.arange(steps)
        untraced = (at < TRACE_SKIP) | (at >= TRACE_SKIP + TRACE_STEPS)
        reading = Reading(
            cell=cell, job=job, params={},
            step_ms=float(walls_ms[untraced].mean()),
            host_ms=dict(zip(
                ("pick", "dispatch", "fence", "loss_fetch"),
                (np.diff(clocks, axis=1).mean(axis=0) * 1e3).tolist())),
            peak=required_ops.peaks(device["kind"], os.path.join(
                root, spec.DATA_DIR, "peaks.json")),
            values=dict(values))
        result.update(_per_layer(root, cell, reading,
                                 trace_dir if traced else None, device, log))
    else:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}
    result.update(device=device, checks=checks, workload=workload, seed=seed,
                  end_to_end_of_this_run={**values,
                                          "step_walls_ms": quantiles},
                  compared=_compared(job, checks, values))
    return result


def _breakdown(reading: Reading) -> dict:
    """The device operations that took most of the traced stretch and its
    longest idle stretches by what the host was doing, both in seconds
    over the stretch, mean over the chips."""
    trace, window = reading.trace, reading.window
    chips = max(len(trace.devices), 1)
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for events in trace.devices.values():
        inside = [e for e in events
                  if e.end > window[0] and e.start < window[1]]
        for name, s in trace_reduce.self_seconds(inside).items():
            ops[name] = ops.get(name, 0.0) + s / chips
        attributed = trace_reduce.attribute_gaps(
            trace_reduce.idle_gaps(inside, window), trace.host)
        for name, s in attributed.items():
            gaps[name] = gaps.get(name, 0.0) + s / chips
    # the same seconds by kind of operation ("fusion.13 fusion:Output
    # bf16[...]" -> "fusion fusion:Output"), for the reader of the log
    kinds: dict[str, float] = {}
    for name, s in ops.items():
        parts = name.split(" ")
        kind = " ".join([parts[0].rsplit(".", 1)[0]] + parts[1:2])
        kinds[kind] = kinds.get(kind, 0.0) + s
    return {"device_ops": trace_reduce.top(ops),
            "idle_gaps": trace_reduce.top(gaps),
            "device_ops_by_kind": trace_reduce.top(kinds, 16)}


def print_result(result: dict) -> None:
    """The line the driver reads, last on standard output, its ``compared``
    key last in it; and the same numbers, last on standard error."""
    import sys

    print(json.dumps(result, default=str), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
