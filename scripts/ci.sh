#!/usr/bin/env bash
# CI entry point: tier-1 suite + the full dry-run benchmark sweep.
#   scripts/ci.sh
#
# The benchmark sweep writes BENCH_<section>.json baselines into the repo
# root (committed), so every PR leaves a machine-readable point on the perf
# trajectory — including the sharded-serving section.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CI runs on the CPU: Pallas kernels interpreted, host devices for meshes
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== tier-1: pytest =="
python -m pytest -x -q

echo "== snapshot manifests (API surface + metric names) =="
# both also ride the pytest run above; re-run standalone so a drifted
# manifest fails loudly here with the regen command in the diff output
python -m pytest -q tests/test_api_surface.py tests/test_metric_names.py

echo "== static analysis: repro.analysis --check (findings report committed) =="
# the analyzer gates on any unbaselined finding OR stale baseline entry; the
# JSON report is a committed artifact so every PR carries its findings ledger
python -m repro.analysis --check --json ANALYSIS_findings.json

echo "== static analysis: negative self-test (one injected violation per pack) =="
# the gate is only trustworthy if it demonstrably FAILS on bad code: inject
# one violation per rule pack into a scratch tree and require nonzero exit
selftest="$(mktemp -d)"
trap 'rm -rf "$selftest"' EXIT
cat > "$selftest/fxp_bad.py" <<'EOF'
def combine(a_raw, b_raw):
    return a_raw * b_raw
EOF
cat > "$selftest/jax_bad.py" <<'EOF'
@jax.jit
def step(x):
    return float(x)
EOF
cat > "$selftest/asy_bad.py" <<'EOF'
async def run(self):
    self.service.poll()
EOF
for bad in fxp_bad.py jax_bad.py asy_bad.py; do
    if python -m repro.analysis "$selftest/$bad" --root "$selftest" \
            > /dev/null 2>&1; then
        echo "FATAL: analyzer passed injected violation $bad" >&2
        exit 1
    fi
done
echo "analyzer correctly rejected all 3 injected violations"

echo "== OTLP loopback smoke (stub collector, nonzero exit on drops) =="
# the exporter's default urllib transport against a real (loopback) HTTP
# sink: every queued span must arrive, the delta metrics push must land,
# and nothing may drop or fail — the wire path the unit tests inject around
python - <<'EOF'
import json
import sys
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

from repro.obs import MetricsRegistry, OTLPExporter, Tracer

hits = {"spans": 0, "metric_pushes": 0}


class Sink(BaseHTTPRequestHandler):
    def do_POST(self):
        payload = json.loads(
            self.rfile.read(int(self.headers.get("Content-Length", 0))))
        if self.path == "/v1/traces":
            hits["spans"] += sum(
                len(ss["spans"]) for rs in payload["resourceSpans"]
                for ss in rs["scopeSpans"])
        elif self.path == "/v1/metrics":
            hits["metric_pushes"] += 1
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"{}")

    def log_message(self, *args):
        pass


collector = HTTPServer(("127.0.0.1", 0), Sink)
threading.Thread(target=collector.serve_forever, daemon=True).start()

reg = MetricsRegistry()
exp = OTLPExporter(f"http://127.0.0.1:{collector.server_port}",
                   registry=reg, max_batch=8)
tracer = Tracer(sink=exp.record_trace)
for i in range(32):
    tr = tracer.start("query", "query", vertex=i)
    tr.span("wave", 0.0).end(0.001)
    tracer.finish(tr)
reg.counter("smoke_total", "Loopback smoke traffic.").get().inc(3)
exp.flush(reg)
collector.shutdown()

s = exp.stats()
print(f"otlp smoke: {s['spans_exported']} spans / "
      f"{s['span_batches_sent']} batches delivered, "
      f"{s['metric_pushes']} metric pushes, "
      f"{s['spans_dropped']} dropped, {s['send_failures']} send failures")
ok = (s["spans_exported"] == 64 and hits["spans"] == 64
      and s["metric_pushes"] >= 1 and hits["metric_pushes"] >= 1
      and s["spans_dropped"] == 0 and s["send_failures"] == 0
      and s["queue_depth"] == 0)
sys.exit(0 if ok else 1)
EOF

echo "== examples smoke (ported to the futures API, deprecation-clean) =="
# the ported examples must not touch the deprecated serve()/pump()/drain()
# wrappers — the warning is attributed to the calling frame (stacklevel), so
# scoping the filter to __main__ catches exactly the example's own usage
# without tripping on unrelated import-time warnings from jax/numpy
python -W error::DeprecationWarning:__main__ examples/quickstart.py
python -W error::DeprecationWarning:__main__ examples/http_serving.py

echo "== pallas engine family (interpret mode; skipped if pallas unavailable) =="
# the fused-kernel suite runs under interpret=True so it is meaningful on
# CPU-only CI hosts; a host whose jax build lacks pallas skips cleanly
# (probe exit 3 = ImportError), anything else fails the gate
pallas_rc=0
python - <<'EOF' || pallas_rc=$?
import sys
try:
    import jax.experimental.pallas  # noqa: F401
except ImportError:
    sys.exit(3)
EOF
if [ "$pallas_rc" -eq 0 ]; then
    python -m pytest -q tests/test_pallas_engine.py
elif [ "$pallas_rc" -eq 3 ]; then
    echo "skip: jax.experimental.pallas not importable on this host"
else
    echo "FATAL: pallas probe failed with unexpected status $pallas_rc" >&2
    exit 1
fi

echo "== smoke + baselines: benchmark sweep (dry run, JSON into repo root) =="
# --check gates the sweep: every ran section must leave a fresh parseable
# non-empty BENCH_<section>.json, and a skipped section must not leave a
# stale baseline behind
python -m benchmarks.run --dry-run --json . --check
