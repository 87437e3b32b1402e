"""One run of one cell: set-up, the measured window, and the run record
that `check.py` judges and the metric readers read.

A window is whole passes over the traffic mix's panel of traces, job
after job, until `--seconds` have gone by; the pass in flight is finished,
so every run does the same work: the same traces, in the order its seed
draws. A traced run, whose first job runs under the profiler and whose
numbers are per-layer ones, ends with the job in flight instead: the
profiler's own start and stop are most of what it would otherwise add to
a long pass. The first job of the window keeps its cycles and counters,
and `check.py` holds them to the reference on that same trace.

The program is driven as `primetpu run` drives it (`cli cmd_run`): a
warm-up chunk through `run_loop` compiles or loads the program, then each
job is a fresh `Engine` on the compiled program, its uploads synced
before the clock starts and its counts on the host before it stops.
"""

from __future__ import annotations

import gc
import hashlib
import tempfile
import time

import numpy as np

import trafficgen

_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class CompileCounter:
    """Programs built or loaded from the persistent cache in this process,
    counted from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event in _COMPILE_EVENTS:
            self.n += 1


_counter: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """One listener per process: JAX keeps every one it is given."""
    global _counter
    if _counter is None:
        _counter = CompileCounter()
    return _counter


def _digest(cycles: np.ndarray, counters: dict) -> str:
    h = hashlib.sha256(np.ascontiguousarray(cycles, np.int64).tobytes())
    for k in sorted(counters):
        h.update(k.encode())
        h.update(np.ascontiguousarray(counters[k], np.int64).tobytes())
    return h.hexdigest()


def _lengths(events: np.ndarray) -> np.ndarray:
    return (events[:, :, 0] != trafficgen.EV_END).sum(axis=1).astype(np.int32) + 1


def _at_end(events: np.ndarray, ptr: np.ndarray) -> int:
    """Cores whose trace pointer does not stand on END."""
    p = np.minimum(ptr, events.shape[1] - 1)
    return int((events[np.arange(events.shape[0]), p, 0] != trafficgen.EV_END).sum())


def tile_mesh_for(cfg, devices: int):
    """The mesh of a configuration's `run.devices`, as `cli cmd_run
    --devices` builds it: none on one device."""
    if devices == 1:
        return None
    from primesim_tpu.parallel.sharding import tile_mesh, validate_devices

    validate_devices(cfg, devices)
    return tile_mesh(devices)


def run_job(cfg, trace, events: np.ndarray, chunk_steps: int, mesh=None,
            profile_dir: str | None = None) -> dict:
    """One whole simulation on the compiled program, timed as `cmd_run`
    times it; with `profile_dir` the timed part runs under the profiler."""
    import jax

    from primesim_tpu.sim.engine import Engine

    eng = Engine(cfg, trace, chunk_steps=chunk_steps, mesh=mesh)
    eng.block_until_ready()
    gc.collect()  # the collector's pauses belong to no job's seconds
    prof = None
    if profile_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # TraceMe spans name the host's work; frames only cost
        prof = jax.profiler.trace(profile_dir, profiler_options=opts)
        prof.__enter__()
    gc.disable()
    try:
        with jax.profiler.TraceAnnotation("benchmark_job"):
            t0 = time.perf_counter()
            eng.run()
            cycles = np.asarray(eng.cycles)
            counters = {k: np.asarray(v) for k, v in eng.counters.items()}
            seconds = time.perf_counter() - t0
    finally:
        gc.enable()
        if prof is not None:
            prof.__exit__(None, None, None)
    devs = sorted(eng.state.cycles.devices(), key=lambda d: d.id)
    return {
        "seconds": seconds,
        "steps": int(eng.steps_run),
        "instructions": int(counters["instructions"].sum()),
        "not_at_end": _at_end(events, np.asarray(eng.state.ptr)),
        "digest": _digest(cycles, counters),
        "cycles": cycles,
        "counters": counters,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
        "state_shapes": {
            k: [list(v.shape), int(v.dtype.itemsize)]
            for k, v in eng.state._asdict().items() if hasattr(v, "shape")
        },
        "events_shape": [list(eng.events.shape), int(eng.events.dtype.itemsize)],
    }


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, t_start: float,
             program_machine_patch: dict | None = None, in_window=None) -> dict:
    """Set-up, window and the record of one run. `program_machine_patch`
    and `in_window` exist for `selfcheck.py` and the tests, which break a
    run on purpose: the first changes the machine the program simulates
    (never the reference's), the second is called once inside the window."""
    config, traffic = spec["config"], spec["traffic"]
    machine = config["machine"]
    chunk_steps = int(config["run"]["chunk_steps"])
    n_cores = machine["n_cores"]

    t0 = time.perf_counter()
    panel = trafficgen.make_panel(traffic, n_cores, seed, root=spec["root"])
    length = panel[0][1].shape[1]
    parity_events = trafficgen.pad_to(
        trafficgen.make_trace(traffic, n_cores, seed, parity=True, root=spec["root"]), length)
    tracegen_s = time.perf_counter() - t0

    import jax
    import jax.numpy as jnp

    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.sim.engine import Engine, run_loop
    from primesim_tpu.trace.format import Trace

    phases = {"start_imports_device": t0 - t_start, "tracegen": tracegen_s,
              "program_imports": time.perf_counter() - t0 - tracegen_s}
    counter = compile_counter()
    cfg = MachineConfig.from_dict(
        {**machine, **(program_machine_patch or {}),
         "step_impl": config["run"]["step_impl"]})
    mesh = tile_mesh_for(cfg, int(config["run"]["devices"]))
    traces = [(i, Trace(ev, _lengths(ev)), ev, trafficgen.total_instructions(ev))
              for i, ev in panel]
    parity_trace = Trace(parity_events, _lengths(parity_events))

    # warm-up chunk: compiles, or loads from the persistent cache, the one
    # program every job of this run uses (all traces have one shape)
    compiles_before = counter.n
    t0 = time.perf_counter()
    warm = Engine(cfg, parity_trace, chunk_steps=chunk_steps, mesh=mesh)
    out = run_loop(cfg, chunk_steps, warm.events, warm.state,
                   jnp.asarray(1, jnp.int32), has_sync=warm.has_sync)
    np.asarray(out[0].cycles)
    compile_s = time.perf_counter() - t0
    # a traced run reads each op's origin from the compiled program's text
    hlo_text = run_loop.lower(
        cfg, chunk_steps, warm.events, warm.state, jnp.asarray(1, jnp.int32),
        has_sync=warm.has_sync).compile().as_text() if trace else None
    del warm, out  # two more copies of the machine in HBM otherwise

    # the parity job: the short trace through the same Engine calls and the
    # same compiled program as the timed jobs; the reference runs on it
    # once the window has closed
    t0 = time.perf_counter()
    parity = run_job(cfg, parity_trace, parity_events, chunk_steps, mesh)
    phases.update(warm_up=compile_s, parity_job=time.perf_counter() - t0)
    setup_compiles = counter.n - compiles_before
    setup_s = time.perf_counter() - t_start

    jobs: list[dict] = []
    raised: list[str] = []
    checked = None  # the first job of the window, held to the reference
    profile_dir = tempfile.mkdtemp(prefix="benchmark-xplane-") if trace else None
    compiles_before = counter.n
    t_win = time.perf_counter()
    passes = 0

    def enough() -> bool:
        return time.perf_counter() - t_win >= seconds

    while True:
        for i, tr, ev, expect in traces:
            traced = trace and not jobs
            try:
                job = run_job(cfg, tr, ev, chunk_steps, mesh, profile_dir if traced else None)
            except Exception as e:  # a job that raises is a failed job, not a lost run
                raised.append(f"pass {passes} trace {i}: {type(e).__name__}: {e}")
                continue
            job.update(trace=i, traced=traced, expect_instructions=expect)
            cycles, counters = job.pop("cycles"), job.pop("counters")
            if not jobs:
                checked = {"trace": i, "events": ev, "cycles": cycles, "counters": counters,
                           "steps": job["steps"]}
            jobs.append(job)  # the digest stands for the counts of the others
            if in_window is not None and len(jobs) == 1:
                in_window()
            if trace and enough():
                break  # a traced run ends with the job in flight, not the pass
        else:
            passes += 1
            if not enough():
                continue
        break
    window_s = time.perf_counter() - t_win
    window_compiles = counter.n - compiles_before

    # the peak on the fullest chip
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return {
        "n_cores": n_cores,
        "chips": spec["cell"]["chips"],
        "machine": machine,
        "chunk_steps": chunk_steps,
        "tracegen_s": tracegen_s,
        "compile_s": compile_s,
        "setup_s": setup_s,
        "setup_compiles": setup_compiles,
        "window_s": window_s,
        "window_compiles": window_compiles,
        "passes": passes,
        "traced": trace,
        "jobs": jobs,
        "raised": raised,
        "parity": parity,
        "parity_events": parity_events,
        "checked": checked,
        "memory_peak_bytes": max(peaks) if peaks else None,
        "profile_dir": profile_dir,
        "hlo_text": hlo_text,
        "phases_s": phases,
        "peaks": spec["peaks"],
    }
