"""Run one cell of ``BENCHMARK.json`` on the chip this process holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process, one cell:

1. refuses any device that is not a TPU listed in ``peaks.json``, or
   fewer chips than the cell asks for;
2. keeps JAX's compilation cache at ``benchmark/.jax_cache`` in the
   checkout;
3. makes the data on the device from ``--seed`` (:mod:`benchmark.data`);
4. builds the index and warms only the cell's own shapes;
5. runs the window: ``--seconds`` of the cell's traffic, with no
   compilation and no fallback off the kernels inside it;
6. compares every answer with the plain reference
   (:mod:`benchmark.compare`) and prints one JSON line: the end-to-end
   metrics with ``--trace 0``, the per-layer metrics (read from a
   profiler trace of the window and the library's counters) with
   ``--trace 1``.

Lines before the last one (on stderr) record the kernels the timed path
reached, the generator's lateness and, last, each compared number beside
its limit.  A run that cannot vouch for its numbers exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __package__ in (None, ""):
    # run as a script: import this checkout's packages, not benchmark/'s
    # siblings by bare name
    sys.path[0] = str(ROOT)

import jax  # noqa: E402

from benchmark import compare, data, loops, reference  # noqa: E402
from benchmark import systems, trace_reduce  # noqa: E402

HERE = Path(__file__).resolve().parent
CACHE_DIR = HERE / ".jax_cache"
TRACE_DIR = HERE / ".traces"
COMPILE_PREFIX = "/jax/core/compile/"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot vouch for its numbers: no result is printed."""


def log(what: str, **kv) -> None:
    print(f"{what}: {json.dumps(kv, default=float)}", file=sys.stderr,
          flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, bench_path: Path = ROOT / "BENCHMARK.json"
              ) -> dict:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"name": workload, "chips": int(w["chips"]),
            "config": load_json(ROOT / conf["file"]),
            "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def check_device(chips: int) -> dict:
    """The peaks of the chip JAX found; refuses anything else."""
    devs = jax.devices()
    peaks = load_json(HERE / "peaks.json")
    kind = devs[0].device_kind
    if devs[0].platform != "tpu" or kind not in peaks:
        raise Refused(f"needs a TPU listed in peaks.json; JAX found "
                      f"{devs[0].platform} device {kind!r}")
    if len(devs) < chips:
        raise Refused(f"needs {chips} chips; JAX found {len(devs)}")
    return peaks[kind]


def setup_compile_cache() -> None:
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileWatch:
    """The benchmark's own ``jax.monitoring`` listener for the
    ``/jax/core/compile/*`` events: counts backend compiles and measures
    the time compilation took in an interval."""

    def __init__(self) -> None:
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_kw) -> None:
        if name.startswith(COMPILE_PREFIX):
            self.events.append((name, time.perf_counter() - secs,
                                time.perf_counter()))

    def count(self, t0, t1, name=BACKEND_COMPILE) -> int:
        return sum(1 for n, _, e in self.events if n == name and t0 <= e <= t1)

    def seconds(self, t0, t1) -> float:
        """The union of compile-event intervals inside ``[t0, t1]``."""
        iv = [(max(s, t0), min(e, t1)) for _, s, e in self.events
              if e > t0 and s < t1]
        return sum(e - s for s, e in trace_reduce.union(iv))


class StageWatch:
    """Records the interval of each of the library's stage timers
    (``raft_tpu.observability.stage``) that closes while it watches, as
    ``(name, start, end)`` on ``time.perf_counter``'s clock."""

    def __init__(self) -> None:
        from raft_tpu.observability import trace
        self.module, self.stages = trace, []
        self._orig = orig = trace.stage_hook

        def hook(name, seconds):
            end = time.perf_counter()
            self.stages.append((name, end - seconds, end))
            orig(name, seconds)
        trace.stage_hook = hook

    def restore(self) -> None:
        self.module.stage_hook = self._orig


class KernelSpy:
    """Counts the traces of a Pallas entry point: a traced call is a
    kernel inside a compiled program."""

    def __init__(self, module, name: str) -> None:
        self.module, self.name, self.calls = module, name, 0
        orig = getattr(module, name)

        def wrapped(*a, **kw):
            self.calls += 1
            return orig(*a, **kw)
        setattr(module, name, wrapped)
        self._orig = orig

    def restore(self) -> None:
        setattr(self.module, self.name, self._orig)


class Trace:
    """The profiler trace of a ``--trace 1`` run.  The loop starts it
    where the window's traced span begins; it ends with the window."""

    def __init__(self, directory: Path) -> None:
        self.dir, self._window = directory, None

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        # host spans are the benchmark's own annotations; tracing every
        # Python call or the runtime's own host events would slow the
        # host path it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._window.__enter__()

    def stop(self) -> None:
        if self._window is None:
            raise Refused("the window never started its trace")
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()


def check_kernels(spies, n_shapes: int) -> dict:
    """Every warmed shape must have traced one of the cell's kernels."""
    seen = {f"{s.module.__name__}.{s.name}": s.calls for s in spies}
    if sum(seen.values()) < n_shapes:
        raise Refused(f"the timed path did not reach its kernels for each "
                      f"of {n_shapes} shapes: {seen}")
    return seen


def fallback_events(system) -> int:
    if system.FALLBACK_EVENT is None:
        return 0
    from raft_tpu.observability import flight
    return len(flight.events(system.FALLBACK_EVENT))


def memory_peak() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def obs_state():
    from raft_tpu import observability as obs
    return obs.snapshot()


def _delta(after: dict, before: dict) -> dict:
    out = {}
    for name, v in after.items():
        if isinstance(v, dict):
            b = before.get(name, {})
            out[name] = {k: x - b.get(k, 0) for k, x in v.items()
                         if isinstance(x, (int, float)) and k in ("sum",
                                                                  "count")}
        else:
            out[name] = v - before.get(name, 0)
    return out


def read_metric(name: str, ctx: dict):
    """The per-layer reader ``metrics/<name>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@dataclasses.dataclass
class Prepared:
    """A cell after set-up: data, built index and warmed loop."""
    cfg: dict
    system: object
    db: jax.Array
    pool: jax.Array
    index: object
    loop: object
    build_s: float
    stages: list
    watch: CompileWatch
    kernels: dict
    phases: dict


def prepare(cell: dict, seed: int) -> Prepared:
    """Data from the seed, the index build (timed, less compilation) and
    the loop warmed on every shape its window uses."""
    from raft_tpu import DeviceResources

    cfg, mix = cell["config"], cell["traffic"]
    system = systems.load(cfg["index"]["kind"])
    watch = CompileWatch()
    stages = StageWatch()
    spies = [KernelSpy(m, n) for m, n in system.KERNELS]
    try:
        t_d0 = time.perf_counter()
        res = DeviceResources(seed=seed % (1 << 31))
        db, pool = data.make(seed, cfg["dataset"])
        jax.block_until_ready((db, pool))
        t_b0 = time.perf_counter()
        index = system.build(res, cfg, db)
        jax.block_until_ready(index)
        t_b1 = time.perf_counter()
        loop = loops.LOOPS[mix["loop"]](res, system, cfg, mix, index, db,
                                        pool, seed)
        try:
            loop.warm()
            kernels = check_kernels(spies, loop.n_shapes)
        except BaseException:
            loop.close()
            raise
        # set-up's objects go to the collector's permanent generation, so
        # that no collection walks them again (see run_cell)
        gc.collect()
        gc.freeze()
        t_w = time.perf_counter()
    finally:
        stages.restore()
        for sp in spies:
            sp.restore()
    phases = {"data_s": t_b0 - t_d0, "build_wall_s": t_b1 - t_b0,
              "build_compile_s": watch.seconds(t_b0, t_b1),
              "warm_s": t_w - t_b1, "warm_compile_s": watch.seconds(t_b1, t_w),
              "compiles": watch.count(t_d0, t_w)}
    return Prepared(cfg=cfg, system=system, db=db, pool=pool, index=index,
                    loop=loop,
                    build_s=phases["build_wall_s"] - phases["build_compile_s"],
                    stages=stages.stages, watch=watch, kernels=kernels,
                    phases=phases)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             peak: dict, t_start: float) -> dict:
    """Set up, warm, measure, compare; returns the result line (a dict)."""
    from raft_tpu import observability as obs

    mix = cell["traffic"]
    if trace:
        obs.enable()
    t_prepare = time.perf_counter()
    try:
        p = prepare(cell, seed)
        cfg, system, loop, watch = p.cfg, p.system, p.loop, p.watch
        db, pool, index, stages = p.db, p.pool, p.index, p.stages
        build_s = p.build_s
        k = int(cfg["index"]["k"])
        log("kernels traced in warm-up", **p.kernels)
        log("set-up", init_s=t_prepare - t_start, **p.phases)
        try:
            if trace and mix["loop"] == "closed":
                # the search paths fence at their stage timers while
                # collection is on; the batch window runs as it does
                # untraced
                obs.disable()
            before = obs_state() if trace else {}
            fb0 = fallback_events(system)
            tracer = Trace(TRACE_DIR / cell["name"]) if trace else None
            setup_s = time.perf_counter() - t_start
            # no collection runs in the window: a full one over the
            # process's objects would pause every thread, and the open
            # loop's latencies would read it as the system's
            gc.disable()
            t_w0 = time.perf_counter()
            try:
                win = loop.window(
                    seconds, start_trace=tracer.start if trace else None)
            finally:
                gc.enable()
            t_w1 = time.perf_counter()
            if trace:
                tracer.stop()
            t_stop = time.perf_counter()
            compiles = watch.count(t_w0, t_w1)
            fallbacks = fallback_events(system) - fb0
            log("window", compiles=compiles, fallbacks=fallbacks,
                seconds=win.seconds, **win.notes)
            if compiles or fallbacks:
                raise Refused(f"the window compiled {compiles} programs "
                              f"and fell back off the kernels {fallbacks} "
                              f"times")
            after = obs_state() if trace else {}
            layout = system.layout(index, cfg) if trace else None
            mem = memory_peak()
        finally:
            loop.close()
        del p, loop, index
        gc.unfreeze()
        gc.collect()
    finally:
        if trace:
            obs.disable()
    # the reference runs once the program's state is freed
    _, ref_ids = reference.knn(pool, db, k)
    values = compare.numbers(pool, db, win.rows, win.ids, win.dists, ref_ids)
    values["lost"] = win.lost
    correct, checks = compare.judge(values, cfg["limits"])
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": win.attempted,
           "failed": win.failed}
    if not trace:
        e2e = dict(win.metrics, recall_at_10=values["recall"],
                   build_s=build_s, setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    else:
        t_r0 = time.perf_counter()
        summary = trace_reduce.reduce(trace_reduce.find_xplane(
            str(tracer.dir)))
        shutil.rmtree(tracer.dir, ignore_errors=True)
        log("trace", stop_s=t_stop - t_w1, reduce_s=time.perf_counter() - t_r0,
            traced_s=summary.window_s)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        ctx = {"trace": summary if summary.n_devices else None,
               "counters": _delta(after.get("counters", {}),
                                  before.get("counters", {})),
               "histograms": _delta(after.get("histograms", {}),
                                    before.get("histograms", {})),
               "stages": stages, "compile_s": watch.seconds,
               "layout": layout, "window": win,
               "pool": pool, "peak": peak, "notes": {}}
        metrics = {}
        for m in cell["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log("per-layer notes", **ctx["notes"])
        out["breakdown"] = {"device_ops": summary.top_ops(),
                            "idle_gaps": summary.top_gaps()}
    out.update(metrics=metrics, device=device, checks=checks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import raft_tpu
        if Path(raft_tpu.__file__).resolve().parents[1] != ROOT:
            raise Refused(f"raft_tpu imported from {raft_tpu.__file__}, "
                          f"not from this checkout {ROOT}")
        cell = cell_spec(args.workload)
        peak = check_device(cell["chips"])
        setup_compile_cache()
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          peak, T_START)
    except (Refused, ImportError) as e:
        print(f"benchmark: refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
