"""Stage tracing: the ``ppr.*`` spans land in a ``jax.profiler`` trace as
host events nested per thread; one interval feeds the stage histogram, the
wave trace and the stage sink; ``iterate`` ends on a device sync before the
top-K dispatch; the wave-gap and HTTP self-time counters count only what
their definitions say."""
import asyncio
import json
from pathlib import Path

import jax
import pytest

from repro.graphs import holme_kim_powerlaw
from repro.obs import stage
from repro.ppr_serving import PPRHTTPServer, PPRQuery, PPRService
from repro.ppr_serving import service as service_module
from repro.ppr_serving.engine import base as engine_base
from repro.ppr_serving.http import HTTPRequest, ServingApp, http_request
from repro.ppr_serving.telemetry import WAVE_STAGES, ServiceTelemetry

ITERATIONS = 3
WAVE_SPANS = ["ppr.wave." + s for s in WAVE_STAGES] + ["ppr.wave.deliver"]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TickClock:
    """Every read moves time on by one second: each interval is distinct."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def graph():
    return holme_kim_powerlaw(200, m=3, seed=5)


def _sample_values(telemetry, family):
    for name, _kind, _help, series in telemetry.registry.collect():
        if name == family:
            return [v for _labels, inst in series for v in inst.values()]
    raise KeyError(family)


# ---------------------------------------------------------------------------
# the profiler sees the program's spans
# ---------------------------------------------------------------------------
def _host_lines(log_dir: Path):
    """{(plane, line index): [(name, start_ns, end_ns)]} of the ppr.* host
    events of the one profile written under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    pd = ProfileData.from_file(str(files[-1]))
    lines = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events if e.name.startswith("ppr.")]
            if events:
                lines[(plane.name, i)] = events
    return lines


def test_profiler_trace_holds_the_wave_and_http_spans(graph, tmp_path):
    svc = PPRService(kappa=2, iterations=ITERATIONS)
    svc.register_graph("g", graph, formats=[16])
    svc.run_batch([PPRQuery("g", 1, k=3, precision=16)])   # compile first
    server = PPRHTTPServer(svc, pump_interval_s=0.005)

    async def one_request():
        await server.start()
        try:
            status, _, rec = await http_request(
                server.host, server.port, "POST", "/v1/ppr",
                {"graph": "g", "vertex": 5, "k": 3, "precision": 16})
            assert status == 200 and len(rec["recommendations"]) == 3
        finally:
            await server.stop()

    svc.telemetry.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        asyncio.run(one_request())
    finally:
        jax.profiler.stop_trace()

    lines = _host_lines(tmp_path)
    names = [n for events in lines.values() for n, _, _ in events]
    for name in ["ppr.wave", *WAVE_SPANS, "ppr.http.parse_submit",
                 "ppr.http.respond", "ppr.pump.tick", "ppr.pump.poll"]:
        assert name in names, name
    assert names.count("ppr.wave") == 1
    assert names.count("ppr.wave.step") == ITERATIONS
    # every stage of the wave nests inside ppr.wave, on the wave's thread
    (wave_line, (wave,)), = [(key, [e for e in ev if e[0] == "ppr.wave"])
                             for key, ev in lines.items()
                             if any(e[0] == "ppr.wave" for e in ev)]
    inner = [e for e in lines[wave_line] if e[0].startswith("ppr.wave.")]
    assert sorted(n for n, _, _ in inner) == sorted(
        WAVE_SPANS + ["ppr.wave.step"] * ITERATIONS)
    for _name, start, end in inner:
        assert wave[1] <= start <= end <= wave[2]
    # and the answered request counted its HTTP self-time once
    assert len(_sample_values(svc.telemetry,
                              "ppr_http_self_seconds_quantiles")) == 1


# ---------------------------------------------------------------------------
# one interval per stage, three readers
# ---------------------------------------------------------------------------
def test_stage_sink_histogram_and_wave_trace_share_intervals(graph,
                                                             monkeypatch):
    sunk = []

    def spying_stage(name, clock=None, sink=None, **attrs):
        if sink is None:
            return stage(name, clock, **attrs)

        def both(sp):
            sunk.append((sp.name.rpartition(".")[2], sp.start_s, sp.end_s))
            sink(sp)
        return stage(name, clock, both, **attrs)

    monkeypatch.setattr(service_module, "stage", spying_stage)
    svc = PPRService(kappa=2, iterations=ITERATIONS, tracing=True,
                     time_fn=TickClock())
    svc.register_graph("g", graph, formats=[16])
    svc.run_batch([PPRQuery("g", v, k=3, precision=16) for v in (1, 2)])

    assert [s for s, _, _ in sunk] == list(WAVE_STAGES)
    wave, = [t for t in svc.recorder.snapshot()["traces"]
             if t["kind"] == "wave"]
    spans = [(c["name"], c["start_s"], c["end_s"])
             for c in wave["root"]["children"]]
    assert spans == sunk
    stats = svc.telemetry.stage_stats()
    for name, start, end in sunk:
        assert end > start
        assert stats[name]["count"] == 1
        assert stats[name]["total_s"] == end - start
    attrs = {c["name"]: c.get("attrs", {}) for c in wave["root"]["children"]}
    assert attrs["warm_start"] == {"warm_cols": 0, "iterations_saved": 0}
    assert attrs["iterate"]["iterations_run"] == ITERATIONS
    assert attrs["topk"] == {"k_max": 3}
    assert "engine" in attrs["plan"]


# ---------------------------------------------------------------------------
# iterate ends on the device
# ---------------------------------------------------------------------------
def test_iterate_ends_on_a_device_sync_before_topk(graph, monkeypatch):
    svc = PPRService(kappa=2, iterations=ITERATIONS)
    svc.register_graph("g", graph, formats=[16])
    events = []
    sync, topk = jax.block_until_ready, engine_base.topk_dense
    record_stage = svc.telemetry.record_stage

    def logged_sync(x):
        events.append("sync")
        return sync(x)

    def logged_topk(*args, **kwargs):
        events.append("topk")
        return topk(*args, **kwargs)

    def logged_stage(name, seconds):
        events.append("stage:" + name)
        record_stage(name, seconds)

    monkeypatch.setattr(jax, "block_until_ready", logged_sync)
    monkeypatch.setattr(engine_base, "topk_dense", logged_topk)
    monkeypatch.setattr(svc.telemetry, "record_stage", logged_stage)
    svc.run_batch([PPRQuery("g", v, k=3, precision=16) for v in (1, 2)])
    assert events.index("sync") < events.index("stage:iterate") \
        < events.index("topk") < events.index("stage:topk")


# ---------------------------------------------------------------------------
# the two new counters
# ---------------------------------------------------------------------------
def test_wave_gap_counts_only_waves_that_waited_across_the_last(graph):
    clk = TickClock()
    svc = PPRService(kappa=2, iterations=ITERATIONS, max_wait=1e9,
                     tracing=True, time_fn=clk)
    svc.register_graph("g", graph, formats=[16])

    def gaps():
        return _sample_values(svc.telemetry, "ppr_wave_gap_seconds_quantiles")

    def wave_spans():
        return [{c["name"]: c for c in t["root"]["children"]}
                for t in svc.recorder.snapshot()["traces"]
                if t["kind"] == "wave"]

    # two waves queued together: the second waited across the first
    svc.run_batch([PPRQuery("g", v, k=3, precision=16) for v in range(4)])
    first, second = wave_spans()
    assert gaps() == [second["iterate"]["start_s"] - first["topk"]["end_s"]]
    # a wave submitted after the last one finished did not wait across it
    svc.run_batch([PPRQuery("g", v, k=3, precision=16) for v in (4, 5)])
    assert len(gaps()) == 1


def test_wave_gap_starts_afresh_after_a_reset():
    t = ServiceTelemetry()
    t.record_wave_gap(oldest_enqueued_s=0.0, iterate_start_s=5.0)
    t.record_topk_end(10.0)
    t.record_wave_gap(oldest_enqueued_s=9.0, iterate_start_s=12.5)
    t.record_wave_gap(oldest_enqueued_s=11.0, iterate_start_s=13.0)
    assert _sample_values(t, "ppr_wave_gap_seconds_quantiles") == [2.5]
    t.reset()       # the benchmark's window starts here
    t.record_wave_gap(oldest_enqueued_s=0.0, iterate_start_s=20.0)
    assert _sample_values(t, "ppr_wave_gap_seconds_quantiles") == []


def test_http_self_time_excludes_the_wait_on_the_future(graph):
    clk = FakeClock()
    svc = PPRService(kappa=2, iterations=ITERATIONS, max_wait=1e9,
                     time_fn=clk)
    svc.register_graph("g", graph, formats=[16])
    app = ServingApp(svc)
    body = json.dumps({"graph": "g", "vertex": 3, "k": 3,
                       "precision": 16}).encode()

    async def scenario():
        handler = asyncio.ensure_future(app.handle(
            HTTPRequest("POST", "/v1/ppr", {}, body)))
        await asyncio.sleep(0)          # parsed and submitted; now waiting
        assert svc.queue_depth() == 1
        clk.t += 100.0                  # queue wait and wave: not HTTP time
        svc.flush()
        resp = await handler
        assert resp.status == 200
        clk.t += 0.25                   # writing the answer: HTTP time
        resp.on_sent()

    asyncio.run(scenario())
    assert _sample_values(svc.telemetry,
                          "ppr_http_self_seconds_quantiles") == [0.25]
