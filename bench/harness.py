"""One run of one cell: set-up, the measured window, the correctness check
and the metrics, as ``run.py`` prints them.

Set-up makes the cell's graph from the seed with the configuration's
generator, hands it to the program (``COOGraph.from_edges``,
``PPRService.register_graph``, on a mesh of the cell's chips where it asks
for more than one: ``cell_mesh``), answers the warm-up and prefill queries that
compile the cell's wave shapes (and fill the result cache where the traffic
says so), resets the service's telemetry and opens the server and the
keep-alive client connections.  ``setup_s`` ends there.  The window then
sends the traffic over real sockets to ``PPRHTTPServer`` in this process;
with ``trace`` the profiler records it.  After the window the server is
stopped, the program's state freed, and the answers compared with the plain
reference at the precision the cell states (``check.py``).

``rehearse`` runs the same pieces on the CPU at a size the caller picks,
reporting only counts and the correctness check: no timing, rate or device
number comes out of it.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import shutil
import statistics
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

from bench import check, load, trace as tracing
from bench.manifest import Manifest
from bench.reference import FixedReference, Reference
from bench.traffic import make_schedule

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
GIVE_UP_S = 60.0        # an open-loop answer later than this past the last send never came


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


@dataclasses.dataclass
class Context:
    """What the metric readers (``metrics/<name>.py``) read."""
    requests: List[load.Request]
    in_window: List[load.Request]    # the answers the rate counts
    latencies_s: List[float]         # open loop: due -> read, every request
    window_s: float
    setup_s: float
    num_vertices: int
    num_edges: int
    edge_shards: int                 # devices the edge stream is split over
    kappa: int
    iterations: int
    registry: object                 # the service's telemetry registry
    summary: Optional[tracing.Summary]
    peaks: Optional[dict]

    def family(self, name: str):
        """The instruments of one telemetry family, [] when absent."""
        for fam, _kind, _help, series in self.registry.collect():
            if fam == name:
                return [inst for _labels, inst in series]
        return []

    def sample_median_ms(self, name: str) -> Optional[float]:
        """The median of a percentile-sample family's values, in ms; None
        when it holds none."""
        import numpy as np

        values = [v for inst in self.family(name) for v in inst.values()]
        return float(np.percentile(values, 50)) * 1e3 if values else None


class _CompileCounter:
    """Counts lowerings and backend compilations while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _duration: float, **_kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


class _LoopWatch:
    """Watches the window's event loop: the longest it was held past a
    wake-up, and the longest garbage collection.  It starts no thread (a
    ``faulthandler`` watchdog re-armed on the loop would join its old thread
    and start a new one four times a second, holding the interpreter
    lock)."""

    PERIOD_S = 0.25

    def __init__(self):
        self.max_lag_s = 0.0
        self.max_gc_s = 0.0
        self._gc_t0 = 0.0
        self._task = None

    def _on_gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.max_gc_s = max(self.max_gc_s,
                                time.perf_counter() - self._gc_t0)

    async def _run(self) -> None:
        while True:
            t = time.perf_counter()
            await asyncio.sleep(self.PERIOD_S)
            self.max_lag_s = max(self.max_lag_s,
                                 time.perf_counter() - t - self.PERIOD_S)

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is None:
            return
        self._task.cancel()
        await asyncio.gather(self._task, return_exceptions=True)
        self._task = None
        gc.callbacks.remove(self._on_gc)


def device_info(chips: int) -> Dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_memory(n: int) -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Setup:
    """A cell's graph, registered on a fresh service, and what the run
    needs to know of it."""
    man: Manifest
    workload: str
    cell: dict
    cfg: dict
    params: dict
    limits: dict
    device: dict
    peaks: Optional[dict]
    num_vertices: int
    edge_shards: int
    src: object
    dst: object
    linked: object                 # ids of the vertices with an edge
    precision: object              # what the window asks for
    precision_key: str             # ... as the service names it
    stated_precision: str          # what the cell states ("Q1.25", "f32")
    svc: object

    @property
    def graph(self) -> str:
        return self.cell["config"]

    @property
    def kappa(self) -> int:
        return int(self.cfg["service"]["kappa"])

    @property
    def cache_capacity(self) -> int:
        return int(self.cfg["service"]["cache_capacity"])


def cell_mesh(svc_cfg: dict, chips: int, devices):
    """The mesh a cell on ``chips`` devices registers its graph on, the axis
    its edges are split over and that axis's size: ``(None, None, 1)`` on
    one chip.  The configuration's ``service.mesh`` (``shape``, ``axes``,
    optional ``shard_axis``) states it; by default one ``"shard"`` axis over
    every chip, as the program's launchers build it.  Without
    ``shard_axis`` the program splits over the first axis."""
    if chips == 1:
        return None, None, 1
    from repro.launch.mesh import make_mesh

    spec = svc_cfg.get("mesh", {"shape": [chips], "axes": ["shard"]})
    mesh = make_mesh(spec["shape"], spec["axes"], devices=devices[:chips])
    axis = spec.get("shard_axis")
    return mesh, axis, int(mesh.shape[axis or mesh.axis_names[0]])


def set_up(root: Path, workload: str, seed: int, *, rehearsal: bool = False,
           graph: Optional[Dict] = None, service: Optional[Dict] = None,
           precision=None, log=print) -> Setup:
    """Make the cell's graph from the seed and register it on a service
    built from the configuration.  ``graph`` overrides generator parameters,
    ``service`` service settings and ``precision`` the traffic's
    (rehearsals and control readings)."""
    man = Manifest(root)
    cell = man.workload(workload)
    cfg = man.config(cell["config"])
    cfg = {**cfg, "service": {**cfg["service"], **(service or {})}}
    params = man.traffic(cell["traffic"])

    import jax

    from repro.core.coo import COOGraph
    from repro.ppr_serving import PPRService, precision_key

    if rehearsal:
        if len(jax.devices()) < int(cell["chips"]):
            raise NoChip(f"the cell asks for {cell['chips']} devices, JAX "
                         f"found {len(jax.devices())}")
        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind,
                  "count": len(jax.devices())}
        peaks = None
    else:
        device = device_info(int(cell["chips"]))
        from bench.peaks import peaks as peak_table
        from repro.launch.compile_cache import use_compile_cache

        peaks = peak_table(device["kind"])
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    gparams = {**cfg["graph"], **(graph or {})}
    t = time.perf_counter()
    n, src, dst = man.generator(gparams["generator"]).generate(gparams, seed)
    log(f"set-up: graph {gparams['generator']} V={n} E={len(src)} made in "
        f"{time.perf_counter() - t:.3f} s")
    svc_cfg = cfg["service"]
    prec = params["precision"] if precision is None else precision
    pkey = precision_key(prec)
    mesh, axis, shards = cell_mesh(svc_cfg, int(cell["chips"]),
                                   jax.devices())
    svc = PPRService(kappa=int(svc_cfg["kappa"]),
                     iterations=int(svc_cfg["iterations"]),
                     alpha=float(svc_cfg["alpha"]),
                     max_wait=float(svc_cfg["max_wait"]),
                     cache_capacity=int(svc_cfg["cache_capacity"]))
    t = time.perf_counter()
    svc.register_graph(cell["config"], COOGraph.from_edges(src, dst, n),
                       formats=[] if pkey == "f32" else [prec],
                       mesh=mesh, mesh_axis=axis, engine=svc_cfg["engine"])
    log(f"set-up: COOGraph built and registered in "
        f"{time.perf_counter() - t:.3f} s")
    return Setup(man=man, workload=workload, cell=cell, cfg=cfg,
                 params={**params, "precision": prec},
                 limits=man.limits(workload), device=device,
                 peaks=peaks, num_vertices=n, edge_shards=shards,
                 src=src, dst=dst,
                 linked=linked_vertices(n, src, dst), precision=prec, precision_key=pkey,
                 stated_precision=precision_key(params["precision"]),
                 svc=svc)


def linked_vertices(n: int, src, dst):
    """Ids of the vertices with at least one edge, ascending."""
    import numpy as np

    return np.flatnonzero(np.bincount(src, minlength=n)
                          + np.bincount(dst, minlength=n))


def answer_in_setup(st: Setup, vertices, k: int, what: str, log=print) -> None:
    """Answer ``vertices`` through the service in kappa-waves (warm-up
    compiles the wave shapes; prefill fills the result cache)."""
    from repro.ppr_serving import PPRQuery

    t = time.perf_counter()
    for i in range(0, len(vertices), st.kappa):
        st.svc.run_batch([PPRQuery(st.graph, int(v), k=k,
                                   precision=st.precision)
                          for v in vertices[i:i + st.kappa]])
    if len(vertices):
        log(f"set-up: {what} of {len(vertices)} queries in "
            f"{time.perf_counter() - t:.3f} s")


def serve(st: Setup, sched, seconds: float, trace: bool,
          tdir: Optional[Path], t_start: float) -> Dict:
    """One window of ``sched`` against the server (``_window``), with the
    compilations inside it counted under ``"compiles"``."""
    counter = _CompileCounter()
    try:
        out = asyncio.run(_window(st.svc, st.cfg, sched, st.graph, seconds,
                                  trace, tdir, counter, t_start,
                                  int(st.cell["chips"])))
    finally:
        counter.close()
    out["compiles"] = counter.count
    return out


def reference_for(st: Setup):
    """The plain reference at the precision the cell states (not the one a
    control run serves): exact fixed point for ``Qm.f``, float64 for f32."""
    svc_cfg = st.cfg["service"]
    args = (st.num_vertices, st.src, st.dst, float(svc_cfg["alpha"]),
            int(svc_cfg["iterations"]))
    if st.stated_precision == "f32":
        return Reference(*args)
    int_bits, frac_bits = st.stated_precision[1:].split(".")
    return FixedReference(*args, frac_bits=int(frac_bits),
                          int_bits=int(int_bits))


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, rehearsal: bool = False,
             graph: Optional[Dict] = None, service: Optional[Dict] = None,
             precision=None,
             trace_dir: Optional[Path] = None,
             t_start: Optional[float] = None, log=print) -> Dict:
    """The result dict ``run.py`` prints; ``log`` takes the lines printed
    before it.  ``graph``, ``service`` and ``precision`` as ``set_up``
    takes them."""
    t_start = time.perf_counter() if t_start is None else t_start
    st = set_up(root, workload, seed, rehearsal=rehearsal, graph=graph,
                service=service, precision=precision, log=log)
    sched = make_schedule(st.params, seed, st.linked, st.kappa, seconds,
                          st.cache_capacity)
    answer_in_setup(st, sched.warm, sched.k, "warm-up", log)
    answer_in_setup(st, sched.prefill, sched.k, "prefill", log)
    st.svc.telemetry.reset()

    own_dir = trace_dir is None and trace
    tdir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if own_dir \
        else trace_dir
    out = serve(st, sched, seconds, trace, tdir, t_start)
    registry = st.svc.telemetry.registry
    st.svc = None                   # free the program's state before the check
    gc.collect()
    summary = None
    if trace:
        summary = tracing.summarize(tracing.load(tdir, out["trace_window"]))
        if own_dir:
            shutil.rmtree(tdir, ignore_errors=True)

    reqs = out["requests"]
    late = [r.sent - r.due for r in reqs if r.sent]
    log(f"window: {len(reqs)} requests, {len(out['in_window'])} counted, "
        f"window {out['window_s']:.6f} s; compilations inside the window: "
        f"{out['compiles']}; generator lateness max "
        f"{max(late, default=0.0) * 1e3:.3f} ms, median "
        f"{(statistics.median(late) if late else 0.0) * 1e3:.3f} ms; "
        f"event loop held at most {out['loop_lag_s'] * 1e3:.3f} ms, "
        f"longest garbage collection {out['gc_s'] * 1e3:.3f} ms")

    svc_cfg = st.cfg["service"]
    ref = reference_for(st)
    t_ref = time.perf_counter()
    numbers = check.compare(reqs, ref, graph=st.graph,
                            precision_key=st.precision_key, k=sched.k,
                            seed=seed,
                            max_answers=int(st.limits["max_answers"]))
    correct, shown = check.verdict(numbers, st.limits)
    log(f"check: {numbers['compared']} answers against the "
        f"{st.stated_precision} reference in "
        f"{time.perf_counter() - t_ref:.1f} s")

    ctx = Context(requests=reqs, in_window=out["in_window"],
                  latencies_s=out["latencies_s"], window_s=out["window_s"],
                  setup_s=out["setup_s"], num_vertices=st.num_vertices,
                  num_edges=len(st.src), edge_shards=st.edge_shards,
                  kappa=st.kappa,
                  iterations=int(svc_cfg["iterations"]), registry=registry,
                  summary=summary, peaks=st.peaks)
    man = st.man
    if rehearsal:    # counts only: every metric no clock or trace gives
        entries = [m for m in man.end_to_end(workload)
                   + man.per_layer(workload)
                   if m["source"] not in ("host_clock", "device_trace")]
    else:
        entries = man.per_layer(workload) if trace else \
            man.end_to_end(workload)
    metrics = {}
    for m in entries:
        value = man.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(st.device)
    if not rehearsal:
        device["memory_peak_bytes"] = out["memory_peak_bytes"]
        if trace and summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    result = {"correct": correct, "attempted": len(reqs),
              "failed": sum(1 for r in reqs if not r.ok),
              "metrics": metrics, "device": device}
    if trace and summary is not None and not rehearsal:
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["checks"] = shown
    return result


async def _window(svc, cfg, sched, gname: str, seconds: float, trace: bool,
                  tdir: Optional[Path], counter: _CompileCounter,
                  t_start: float, chips: int) -> Dict:
    import jax

    from repro.ppr_serving import AdmissionConfig, PPRHTTPServer

    server = PPRHTTPServer(svc, admission=AdmissionConfig(**cfg["admission"]))
    await server.start()
    clients: List = []
    out: Dict = {}
    tw: List[int] = []
    watch = _LoopWatch()

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        tw.append(time.perf_counter_ns())
        jax.profiler.start_trace(str(tdir), profiler_options=opts)

    def stop_trace(_req=None):
        if trace and len(tw) == 1:
            tw.append(time.perf_counter_ns())
            jax.profiler.stop_trace()

    try:
        clients = await load.connect(server.host, server.port, sched.clients)
        out["setup_s"] = time.perf_counter() - t_start
        if trace:
            start_trace()
        counter.armed = True
        watch.start()
        t0 = time.perf_counter()
        if sched.loop == "closed":
            reqs = await load.closed_loop(clients, gname, sched, t0 + seconds,
                                          on_late=stop_trace)
            out.update(_closed_window(reqs, t0, t0 + seconds))
        else:
            reqs = await load.open_loop(clients, gname, sched, t0, GIVE_UP_S,
                                        server.host, server.port)
            out.update(_open_window(reqs, t0))
        counter.armed = False
        stop_trace()
        await watch.stop()
        out["loop_lag_s"], out["gc_s"] = watch.max_lag_s, watch.max_gc_s
        out["requests"] = reqs
        out["memory_peak_bytes"] = _peak_memory(chips)
        # the trace's clock starts at zero when the profiler starts
        out["trace_window"] = (0, tw[1] - tw[0]) if trace else None
    finally:
        counter.armed = False
        stop_trace()
        await watch.stop()
        for c in clients:
            await c.close()
        await server.stop()
    return out


def _closed_window(reqs: List[load.Request], t0: float, deadline: float
                   ) -> Dict:
    """The window ends when the wave running at the deadline completes: it
    counts the answers of every wave up to the one that held the first
    answer read at or after the deadline."""
    ok = [r for r in reqs if r.ok]
    late = [r for r in ok if r.recv >= deadline]
    if late:
        last_wave = min(late, key=lambda r: r.recv).payload["wave_id"]
        counted = [r for r in ok if 0 <= r.payload["wave_id"] <= last_wave]
    else:
        counted = [r for r in ok if r.recv < deadline]
    end = max((r.recv for r in counted), default=deadline)
    return {"in_window": counted, "window_s": end - t0, "latencies_s": []}


def _open_window(reqs: List[load.Request], t0: float) -> Dict:
    """Every request of the schedule, timed from when it was due; one with
    no 200 answer (none, an error, or shed by admission) counts as answered
    at the give-up time, so a refusal never reads as a fast answer."""
    last_sent = max((r.sent for r in reqs), default=t0)
    lat = [(r.recv if r.ok else last_sent + GIVE_UP_S) - r.due for r in reqs]
    end = max((r.recv for r in reqs if r.recv is not None), default=t0)
    return {"in_window": [r for r in reqs if r.ok], "window_s": end - t0,
            "latencies_s": lat}


def rehearse(root: Path, workload: str, seed: int, seconds: float,
             graph: Optional[Dict] = None, precision=None,
             log=lambda _m: None) -> Dict:
    """``run_cell`` on whatever backend JAX has, at the graph size ``graph``
    sets (by default the cell's generator's ``REHEARSAL``), with no look for
    a chip and no timing metric.  The result cache keeps the share of the
    vertices it holds in the deployment, so a tiny graph's does not hold
    them all.  A cell on more than one chip needs as many devices
    (``repro.launch.mesh.cpu_host_devices`` on the CPU)."""
    man = Manifest(root)
    cfg = man.config(man.workload(workload)["config"])
    gen = man.generator(cfg["graph"]["generator"])
    graph = gen.REHEARSAL if graph is None else graph
    share = gen.sizes({**cfg["graph"], **graph})[0] / gen.sizes(cfg["graph"])[0]
    capacity = int(cfg["service"]["cache_capacity"])
    return run_cell(root, workload, seed, seconds, trace=False,
                    rehearsal=True, graph=graph,
                    service={"cache_capacity": max(1, round(capacity * share))},
                    precision=precision, log=log)
