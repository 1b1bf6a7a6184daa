"""Serving-path telemetry (ISSUE 2): /metrics + /healthz endpoints,
request trace linking, and AOT-compile observability.

Tier-1 smoke (the CI satellite): boot the HTTP front end, scrape
/metrics and /healthz, check the Prometheus exposition parses — one
line per sample, `# TYPE` headers present — and that the request
latency histogram buckets and compile counters are in it.  Plus: one
served request yields ONE trace id linking admission → queue-wait →
batch-assembly → execute → respond spans, with flow arrows that
resolve.
"""
import importlib.util
import json
import os
import re
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler, serving, telemetry
from mxnet_tpu.contrib import deploy
from mxnet_tpu.gluon import nn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report_under_test",
        os.path.join(_REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = tmp_path_factory.mktemp("serve_tel")
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.initializer.Xavier(rnd_type="gaussian"),
                   ctx=mx.cpu())
    x = nd.array(np.random.RandomState(0).rand(8, 8).astype("float32"))
    deploy.export_model(net, str(d), [x], dynamic_batch=True)
    return str(d)


@pytest.fixture(autouse=True)
def _clean(tmp_path):
    telemetry.disable()
    profiler.stop()
    profiler.dump(finished=True, filename=str(tmp_path / "_flush.json"))
    yield
    telemetry.disable()
    profiler.stop()
    profiler.dump(finished=True, filename=str(tmp_path / "_flush2.json"))


def _get(url, timeout=30):
    r = urllib.request.urlopen(url, timeout=timeout)
    return r.status, r.read().decode()


def test_http_metrics_and_healthz_smoke(artifact):
    """The tier-1 scrape smoke: /healthz drain-aware, /metrics valid
    Prometheus text with latency buckets + compile counters."""
    repo = serving.ModelRepository()
    repo.add("mlp", artifact)
    srv = serving.InferenceServer(
        repo, serving.ServingConfig(max_batch_size=8,
                                    batch_timeout_ms=2.0))
    httpd = serve = None
    try:
        httpd = serving.serve_http(srv, port=0)
        port = httpd.server_address[1]
        base = f"http://127.0.0.1:{port}"

        status, body = _get(f"{base}/healthz")
        assert status == 200 and json.loads(body)["status"] == "serving"

        # traffic so the latency histogram + compile counters move
        body_req = json.dumps(
            {"inputs": [np.zeros((1, 8), "float32").tolist()]}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"{base}/v1/models/mlp:predict", data=body_req,
            headers={"Content-Type": "application/json"}), timeout=120)
        assert r.status == 200

        status, text = _get(f"{base}/metrics")
        assert status == 200
        sample_re = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? '
            r'([0-9eE\.\+\-]+|NaN|[+-]Inf)$')    # a gauge another test of
        # this process left non-finite (mx_grad_norm) is still well-formed
        families = set()
        n_samples = 0
        for ln in text.strip().split("\n"):
            if ln.startswith("# TYPE"):
                families.add(ln.split()[2])
                continue
            if ln.startswith("#"):
                continue
            assert sample_re.match(ln), f"bad exposition line {ln!r}"
            n_samples += 1
        assert n_samples > 0
        # every sample's family has a # TYPE header
        for ln in text.strip().split("\n"):
            if ln.startswith("#") or not ln:
                continue
            name = re.split(r"[{ ]", ln, 1)[0]
            base_name = re.sub(r"_(bucket|sum|count)$", "", name)
            assert name in families or base_name in families, \
                f"sample {name!r} lacks a # TYPE header"
        # acceptance: request latency histogram buckets + AOT compile
        # counters are scrapeable
        assert re.search(
            r'mx_serving_request_latency_seconds_bucket\{.*model="mlp"'
            r'.*le=', text)
        m = re.search(
            r'mx_serving_compile_total\{model="mlp",version="1"\} '
            r'(\d+)', text)
        assert m and int(m.group(1)) >= 1
        assert re.search(r'mx_serving_requests_total\{model="mlp",'
                         r'version="1"\} 1', text)

        # drain-aware healthz: 503 once shutdown begins
        srv.shutdown(drain=True)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=30)
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["status"] == \
            "draining"
    finally:
        if httpd is not None:
            httpd.shutdown()
        srv.shutdown(drain=False)


REQUEST_PHASES = ("admission", "queue-wait", "batch-assembly",
                  "execute", "respond")


def test_served_request_has_one_trace_linking_all_phases(
        artifact, tmp_path):
    repo = serving.ModelRepository()
    repo.add("mlp", artifact)
    srv = serving.InferenceServer(
        repo, serving.ServingConfig(max_batch_size=8,
                                    batch_timeout_ms=2.0))
    try:
        # warm the compile OUTSIDE the capture so the trace is lean
        srv.infer("mlp", [nd.array(np.zeros((1, 8), "float32"))],
                  timeout_ms=120000)
        telemetry.enable()
        profiler.start()
        fut = srv.submit("mlp",
                         [nd.array(np.ones((1, 8), "float32"))])
        fut.result(timeout=120)
        profiler.stop()
        telemetry.disable()
    finally:
        srv.shutdown(drain=True)
    assert fut.trace_id is not None
    fn = str(tmp_path / "req.json")
    profiler.dump(finished=True, filename=fn)
    evs = json.load(open(fn))["traceEvents"]
    mine = [e for e in evs if e.get("ph") == "X"
            and isinstance(e.get("args"), dict)
            and e["args"].get("trace_id") == fut.trace_id]
    names = {e["name"] for e in mine}
    assert set(REQUEST_PHASES) <= names, \
        f"trace {fut.trace_id} spans {sorted(names)}"
    # one trace id covers the whole request path
    adm = next(e for e in mine if e["name"] == "admission")
    qw = next(e for e in mine if e["name"] == "queue-wait")
    assert qw["args"]["parent_id"] == adm["args"]["span_id"]
    # flow arrows: an "s" where the request was enqueued, an "f" at
    # the batch, both carrying the trace id
    flows = {e["ph"] for e in evs if e.get("ph") in ("s", "f")
             and e.get("id") == fut.trace_id}
    assert flows == {"s", "f"}
    # and the whole dump passes the integrity gate
    tr = _load_trace_report()
    assert tr.check_events(evs) == []


def test_model_metrics_reset_on_new_entry(artifact):
    """A fresh _ModelEntry for the same (model, version) restarts its
    counters (lifecycle restart semantics) — per-test counts stay
    hermetic even though the registry is process-global."""
    repo1 = serving.ModelRepository()
    repo1.add("mlp", artifact)
    srv1 = serving.InferenceServer(repo1)
    srv1.infer("mlp", [nd.array(np.zeros((1, 8), "float32"))],
               timeout_ms=120000)
    assert repo1.get("mlp").metrics.snapshot()["requests"] == 1
    srv1.shutdown(drain=True)
    repo2 = serving.ModelRepository()
    repo2.add("mlp", artifact)
    assert repo2.get("mlp").metrics.snapshot()["requests"] == 0


def test_compile_seconds_histogram_records(artifact):
    reg = telemetry.get_registry()
    repo = serving.ModelRepository()
    repo.add("mlp", artifact)
    entry = repo.get("mlp")
    before = reg.get("mx_serving_compile_total") \
        .labels("mlp", "1").value
    entry.warmup([2])
    fam = reg.get("mx_serving_compile_total")
    assert fam.labels("mlp", "1").value == before + 1
    h = reg.get("mx_serving_compile_seconds").labels("mlp", "1")
    assert h.count >= 1 and h.sum > 0
