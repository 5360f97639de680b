"""Runs one cell of BENCHMARK.json: set-up, warm-up, the measured window,
the check against the plain reference, and the result line.

Everything particular to a configuration, a graph family, a traffic mix, a
program or a per-layer metric is read from its own file, found by name:

* `BENCHMARK.json`'s `configs[].file`: graph family and size, r, the
  topology, the encode route, the value type (`configs/<config>.json`);
* `graphs/<family>.py`: `generate(config, seed)` -> the graph as CSR;
* `traffic/<mix>.json`: the program and its parameters, the warm-up, and
  the limits of the correctness check;
* `programs/<program>.py`: `build(params)` (the program's vertex program),
  `reference`, `control` and `compare`;
* `metrics/<metric>.py`: `read(record)` -> one number, or None where the run
  has nothing to read.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"            # scratch inside the checkout (ignored)
CACHE = ROOT / ".jax_cache"          # fixed: the path is part of JAX's key


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---- finding things by name ------------------------------------------------

def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load_module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(spec: dict, workload: str, root: pathlib.Path = ROOT) -> dict:
    """A workload's entry with its configuration, graph family, traffic and
    program resolved from their files."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    return {"workload": w, "config": config, "traffic": traffic,
            "graph": _load_module(root / "bench" / "graphs"
                                  / f"{config['graph']}.py"),
            "program": _load_module(root / "bench" / "programs"
                                    / f"{traffic['program']}.py")}


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """`read(record)` of the per-layer metric `name`."""
    return _load_module(root / "bench" / "metrics" / f"{name}.py").read


def cell_metrics(spec: dict, workload: str, kind: str) -> list[dict]:
    """The cell's `end_to_end` or `per_layer` metrics: those that list it,
    or list no cells at all."""
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


# ---- the device --------------------------------------------------------------

def devices(chips: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX's devices are {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless JAX_COMPILATION_CACHE_DIR already names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts jaxpr traces and backend compiles while `on` (the window),
    and the persistent cache's hits and misses over the whole run."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax.monitoring

        self.on, self.count = False, 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self._event)

    def __call__(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.count += 1

    def _event(self, event, **kw):
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1


# ---- host spans on the profiler's clock ---------------------------------------

def profiled_tracer():
    """An obs tracer whose spans also open a `jax.profiler.TraceAnnotation`,
    so the program's host spans land in the device trace; `.names` holds
    every span name opened."""
    import jax
    from repro.obs import Tracer

    class _Both:
        def __init__(self, sp, name):
            self.sp, self.ann = sp, jax.profiler.TraceAnnotation(name)

        def __enter__(self):
            self.ann.__enter__()
            return self.sp.__enter__()

        def __exit__(self, *exc):
            self.sp.__exit__(*exc)
            self.ann.__exit__(*exc)
            return False

    class ProfiledTracer(Tracer):
        def __init__(self):
            super().__init__(enabled=True)
            self.names = set()

        def span(self, name, **attrs):
            self.names.add(name)
            return _Both(super().span(name, **attrs), name)

    return ProfiledTracer()


# ---- one cell ------------------------------------------------------------------

def topology(cfg: dict):
    from repro.launch.mesh import Topology

    return Topology(**cfg["topology"])


def divisible_n(n: int, K: int, r: int) -> int:
    """Smallest n' >= n divisible by K and by C(K, r): the paper's
    Remark 1, which its allocation needs."""
    step = math.lcm(K, math.comb(K, r))
    return -(-n // step) * step


def make_graph(c: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The cell's graph as CSR: the configuration's one draw of its graph
    family (`graph_seed`), isolated vertices appended up to the
    allocation's divisibility (fewer than lcm(K, C(K, r))), and its
    vertices relabelled at random from `seed` among those that the
    allocation Maps on the same servers and Reduces on the same server.

    So every seed gives the same sizes and padded shapes in another order:
    the same work, and one compile of the exchange for every seed.
    """
    from repro.core.allocation import er_allocation

    cfg = c["config"]
    K, r = topology(cfg).K, cfg["r"]
    indptr, indices = c["graph"].generate(cfg, cfg["graph_seed"])
    n = divisible_n(indptr.size - 1, K, r)
    indptr = np.concatenate(
        [indptr, np.full(n + 1 - indptr.size, indptr[-1], indptr.dtype)])
    alloc = er_allocation(n, K, r)
    alike = alloc.batch_of * K + alloc.reduce_owner
    rng = np.random.default_rng(seed)
    label = np.empty(n, np.int64)
    label[np.argsort(alike, kind="stable")] = np.lexsort((rng.random(n),
                                                          alike))
    rows = label[np.repeat(np.arange(n), np.diff(indptr))]
    keys = np.sort(rows * n + label[indices])
    relabelled = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(keys // n, minlength=n), out=relabelled[1:])
    return relabelled, (keys % n).astype(np.int32)


def build_session(c: dict, seed: int, stages: dict):
    """Graph from the seed, then the program's session, each stage timed by
    the host clock into `stages`."""
    from repro.core import engine
    from repro.core.allocation import er_allocation
    from repro.core.graph_models import Graph

    cfg, tr = c["config"], c["traffic"]
    topo = topology(cfg)

    def stage(name):
        return _Stage(stages, name)

    with stage("graph"):
        indptr, indices = make_graph(c, seed)
        n = indptr.size - 1
        g = Graph.from_csr(indptr, indices, cfg["graph"],
                           {"n": n, "seed": seed})
        alloc = er_allocation(n, topo.K, cfg["r"])
    log(f"graph: {cfg['graph']}, n={n}, {indptr[-1]} CSR entries, "
        f"max degree {np.diff(indptr).max()}, {topo}, r={cfg['r']}, "
        f"seed {seed}")
    program = c["program"].build(tr["params"])
    with stage("engine.compile"):       # plan + edge tables
        sess = engine.compile(program, g, alloc, "coded", path="sparse",
                              backend="fused", encode=cfg["encode"],
                              topology=topo)
    with stage("partition"):            # partition_plan + jit build
        sess.fused
    log(f"plan: {sess.plan.col_sender.size} coded columns, "
        f"{sess.plan.all_k.size} deliveries")
    return sess, program, g, (indptr, indices)


def control_readings(c: dict, seed: int, iters: int) -> dict:
    """The control's numbers beside the cell's limits, for one seed, at the
    cell's own size: the plain reference computed one precision below the
    configuration's (`programs/<program>.py`'s `control`), put in the
    program's place and judged by the cell's own comparison. It has to
    come out as not correct. The benchmark's own runs do not call this."""
    indptr, indices = make_graph(c, seed)
    prog, params = c["program"], c["traffic"]["params"]
    ref = prog.reference(indptr, indices, iters, params)
    got = prog.control(indptr, indices, iters, params)
    return {"seed": seed, "iters": iters, "n": indptr.size - 1,
            "control": prog.compare(got, ref),
            "limits": c["traffic"]["limits"]}


class _Stage:
    def __init__(self, stages: dict, name: str):
        self.stages, self.name = stages, name

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.stages[self.name] = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"set-up {self.name}: {self.stages[self.name]} s")


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, root: pathlib.Path = ROOT,
             require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object (not yet printed)."""
    c = cell(spec, workload, root)
    w, tr = c["workload"], c["traffic"]
    devs = devices(w["chips"], require_tpu)
    log(f"device: {devs[0].device_kind} x{len(devs)} "
        f"(platform {devs[0].platform})")
    log(f"compile cache: {use_compile_cache()}")
    from repro import obs

    tracer = profiled_tracer() if trace else None
    prev_tracer = obs.set_tracer(tracer) if trace else None
    counter = _CompileCounter()
    stages: dict[str, float] = {}
    try:
        sess, program, g, (indptr, indices) = build_session(c, seed, stages)
        state = program.init(g)
        if state.dtype != np.dtype(c["config"]["value_dtype"]):
            raise ValueError(f"the session computes in {state.dtype}, the "
                             f"configuration states "
                             f"{c['config']['value_dtype']}")
        with _Stage(stages, "warm-up"):
            for _ in range(tr["warmup_iters"]):
                state = sess.run(1, state=state).state
        done = tr["warmup_iters"]
        if trace:                           # set-up spans, then the window's
            stages.update({f"span:{sp.name}": sp.duration_s
                           for sp in tracer.spans() if not sp.instant})
            tracer.reset()
        window = _window(sess, state, seconds, trace, tracer, counter)
        done += window["iters"]
        log(f"window: {window['iters']} iterations in {window['seconds']} s, "
            f"{window['failed']} failed, {counter.count} compiles inside")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        record = None
        if trace:
            record = _record(window, tracer, stages, devs[0].platform)
        del sess, state, g                  # free the program's device state
        gc.collect()
        final = window.pop("state")
    finally:
        if trace:
            obs.set_tracer(prev_tracer)
    # The check: the state the timed session produced, against the plain
    # reference run for as many iterations from the same start.
    t_ref = time.perf_counter()
    ref = c["program"].reference(indptr, indices, done, tr["params"])
    checks = c["program"].compare(final, ref) if not window["failed"] else {}
    log(f"reference: {done} iterations in {time.perf_counter() - t_ref} s")
    limits = tr["limits"]
    correct = (not window["failed"] and set(checks) == set(limits)
               and all(checks[k] <= limits[k] for k in limits))

    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        values = {}
        for m in cell_metrics(spec, workload, "per_layer"):
            v = metric_reader(m["name"], root)(record)
            if v is not None:
                values[m["name"]] = v
        if record["trace"] is not None:
            chips = record["trace"]["chips"]
            device["busy_s"] = sum(ch["busy_s"] for ch in chips) / len(chips)
            device["window_s"] = record["trace"]["window_s"]
            from bench import tracereduce
            breakdown = {
                "device_ops": tracereduce.top([ch["ops"] for ch in chips]),
                "idle_gaps": tracereduce.top([ch["idle"] for ch in chips])}
    else:
        values = {"iter_s": window["seconds"] / max(window["iters"], 1),
                  "setup_s": window["t0"] - t_start}
        values = {k: values[k] for k in
                  (m["name"] for m in cell_metrics(spec, workload, "end_to_end"))}
    for k, v in stages.items():
        log(f"set-up split {k}: {v} s")
    log(f"compiles inside the window: {counter.count}; persistent cache "
        f"{counter.cache['hits']} hits, {counter.cache['misses']} misses")
    result = {"correct": bool(correct), "attempted": window["iters"],
              "failed": window["failed"],
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in values.items()},
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": checks.get(k), "limit": lim}
                        for k, lim in limits.items()}
    for k, lim in limits.items():
        log(f"check {k}: {checks.get(k)} (limit {lim})")
    return result


def _window(sess, state, seconds: float, trace: bool, tracer, counter) -> dict:
    """Iterations back to back, carrying the state, until one ends past
    `seconds`. Traced runs profile exactly this window."""
    import jax

    log_dir = OUT / "trace"
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans come from obs
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    iters = failed = 0
    counter.on = True
    ann = (jax.profiler.TraceAnnotation("bench.window") if trace
           else contextlib.nullcontext())
    t0 = time.perf_counter()
    with ann:
        while True:
            iters += 1
            try:
                state = sess.run(1, state=state).state
            except Exception as e:          # counted, and the run is not correct
                failed += 1
                log(f"iteration {iters} raised {type(e).__name__}: {e}")
                break
            if time.perf_counter() - t0 >= seconds:
                break
    t1 = time.perf_counter()
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    return {"iters": iters, "failed": failed, "t0": t0, "seconds": t1 - t0,
            "state": state, "log_dir": log_dir if trace else None}


def _record(window, tracer, stages, platform: str) -> dict:
    """What the per-layer metric readers read."""
    from bench import tracereduce

    spans: dict[str, float] = {}
    setup: dict[str, float] = dict(stages)
    for sp in tracer.spans():
        if sp.instant:
            continue
        spans[sp.name] = spans.get(sp.name, 0.0) + sp.duration_s
    reduced = None
    if platform == "tpu":
        names = tracer.names | {"bench.window"}
        events = tracereduce.extract(
            tracereduce.find_xplane(window["log_dir"]), names)
        win = [e for e in events["host"] if e[0] == "bench.window"]
        if win:
            reduced = tracereduce.reduce(events, win[0][1], win[0][2])
    return {"iters": window["iters"], "spans": spans, "setup": setup,
            "trace": reduced}

