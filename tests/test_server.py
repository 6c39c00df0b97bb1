"""OpenAI-compatible endpoint over RealEngine, incl. failover under live
HTTP traffic."""
import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving.engine import EngineConfig
from repro.serving.server import serve


@pytest.fixture(scope="module")
def server():
    cfg = get_config("llama3-8b").reduced()
    svc, httpd = serve(cfg, EngineConfig(max_slots=8, max_seq=96),
                       n_instances=2, port=8931)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield svc, cfg
    httpd.shutdown()
    svc.shutdown()


def _post_full(path, payload):
    """POST returning (body, response headers) — the Deprecation-header
    tests read the headers."""
    req = urllib.request.Request(
        f"http://127.0.0.1:8931{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read()), dict(r.headers)


def _post(path, payload):
    return _post_full(path, payload)[0]


def _health():
    with urllib.request.urlopen("http://127.0.0.1:8931/health",
                                timeout=10) as r:
        return json.loads(r.read())


def test_completion_roundtrip(server):
    svc, cfg = server
    rng = np.random.default_rng(0)
    toks = rng.integers(1, cfg.vocab_size, 8).tolist()
    out = _post("/v1/completions", {"prompt_tokens": toks, "max_tokens": 6})
    assert out["object"] == "text_completion"
    assert len(out["choices"][0]["token_ids"]) == 6
    assert out["usage"]["prompt_tokens"] == 8
    # determinism (greedy): same prompt -> same completion
    out2 = _post("/v1/completions", {"prompt_tokens": toks, "max_tokens": 6})
    assert out2["choices"][0]["token_ids"] == out["choices"][0]["token_ids"]


def test_completion_reports_wall_clock_timing(server):
    """The HTTP layer reports per-request timing on the ONE wall-clock
    timebase the engine runs on: TTFT > 0, latency >= TTFT, and the
    absolute stamps are ordered arrival <= first-token <= finish."""
    svc, cfg = server
    rng = np.random.default_rng(7)
    toks = rng.integers(1, cfg.vocab_size, 12).tolist()
    out = _post("/v1/completions", {"prompt_tokens": toks, "max_tokens": 8})
    t = out["timing"]
    assert 0 < t["ttft"] <= t["latency"]
    assert t["arrival_time"] <= t["admit_time"] <= t["first_token_time"]
    assert t["first_token_time"] <= t["finish_time"]
    assert t["ttft"] == pytest.approx(
        t["first_token_time"] - t["arrival_time"])
    assert t["latency"] == pytest.approx(
        t["finish_time"] - t["arrival_time"])
    assert t["latency"] < 120.0           # sane wall seconds, not ticks


def test_health(server):
    with urllib.request.urlopen("http://127.0.0.1:8931/health", timeout=10) as r:
        h = json.loads(r.read())
    assert h["status"] == "ok"
    assert len(h["instances"]) == 2
    assert h["recovery_mode"] == "kevlarflow"
    assert h["failure_events"] == []      # nothing injected yet
    assert all("queued" in i for i in h["instances"])
    dev = jax.devices()[0]
    assert h["device"] == {"platform": dev.platform, "kind": dev.device_kind,
                           "count": len(jax.devices()),
                           "interpret": dev.platform != "tpu"}


def test_failover_under_live_traffic(server):
    """Fire concurrent requests, kill an instance mid-flight via the admin
    endpoint, and verify every request still completes."""
    svc, cfg = server
    rng = np.random.default_rng(1)
    results, errs = [], []

    def one(i):
        try:
            toks = rng.integers(1, cfg.vocab_size, 8).tolist()
            results.append(_post("/v1/completions",
                                 {"prompt_tokens": toks, "max_tokens": 12}))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    import time
    time.sleep(0.5)                      # let some requests enter decode
    _post("/admin/fail_instance", {"instance": 0})
    for t in threads:
        t.join(timeout=180)
    assert not errs, errs
    assert len(results) == 6
    assert all(len(r["choices"][0]["token_ids"]) == 12 for r in results)
    # every response carries timing even across the failure; requests that
    # migrated (or restarted) still report a positive TTFT
    for r in results:
        assert 0 < r["timing"]["ttft"] <= r["timing"]["latency"]
    health = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:8931/health", timeout=10).read())
    assert len(health["failure_events"]) == 1
    assert health["failure_events"][0]["mode"] == "kevlarflow"


def test_rejoin_endpoint_brings_spare_back(server):
    """/admin/rejoin_instance re-enters a killed instance into the LB
    group; new traffic reaches it and double-rejoin is a 409 conflict."""
    svc, cfg = server
    health = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:8931/health", timeout=10).read())
    if health["instances"][0]["alive"]:              # order-independent
        _post("/admin/fail_instance", {"instance": 0})
    out = _post("/admin/rejoin_instance", {"instance": 0})
    assert out["rejoined_instance"] == 0
    health = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:8931/health", timeout=10).read())
    assert health["instances"][0]["alive"]
    assert health["failure_events"][0]["mttr"] > 0   # failure->rejoin cycle
    rng = np.random.default_rng(3)
    toks = rng.integers(1, cfg.vocab_size, 8).tolist()
    out = _post("/v1/completions", {"prompt_tokens": toks, "max_tokens": 5})
    assert len(out["choices"][0]["token_ids"]) == 5
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post("/admin/rejoin_instance", {"instance": 0})
    assert ei.value.code == 409


# -- versioned fault/admin API (ISSUE 10) -----------------------------------


def test_health_roundtrips_typed_schema(server):
    """/health is exactly the documented HealthResponse wire shape."""
    from repro.serving.api_types import HealthResponse
    h = _health()
    assert HealthResponse.from_json(h).to_json() == h
    for inst in h["instances"]:
        d = inst["degradation"]
        assert d["state"] in ("HEALTHY", "DEGRADED", "DEAD")
        assert d["n_shards"] == 4
    assert set(h["topology"]["states"]) == {"0", "1"}


def test_v1_fault_shard_granularity_degrades_and_recovers(server):
    """POST /v1/admin/fault at shard granularity degrades the instance
    (it keeps serving at reduced capacity); /v1/admin/recover restores
    HEALTHY at full capacity."""
    svc, cfg = server
    out, headers = _post_full(
        "/v1/admin/fault",
        {"granularity": "shard", "instance_id": 1, "shard_idx": 0})
    assert out["applied"] is True
    assert out["fault"]["granularity"] == "shard"
    assert "Deprecation" not in headers        # v1 is the supported path
    h = _health()
    d = h["instances"][1]["degradation"]
    assert d["state"] == "DEGRADED" and d["lost_shards"] == [0]
    assert d["slot_cap"] < h["instances"][0]["degradation"]["slot_cap"]
    assert 0 < d["capacity_frac"] < 1.0
    assert d["layout"]["surviving"] == 3
    assert h["topology"]["degraded"] == {"1": [0]}
    assert h["instances"][1]["alive"]          # degraded, NOT dead
    # a degraded instance still serves traffic
    rng = np.random.default_rng(5)
    toks = rng.integers(1, cfg.vocab_size, 8).tolist()
    out = _post("/v1/completions", {"prompt_tokens": toks, "max_tokens": 4})
    assert len(out["choices"][0]["token_ids"]) == 4
    # recover restores all lost shards (no shard_idx needed)
    _post("/v1/admin/recover", {"granularity": "shard", "instance_id": 1})
    d = _health()["instances"][1]["degradation"]
    assert d["state"] == "HEALTHY" and d["lost_shards"] == []
    assert d["capacity_frac"] == 1.0


def test_v1_fault_validation_and_conflicts(server):
    """Malformed specs are 400 (shape), impossible transitions 409
    (state)."""
    for bad in (
            {"granularity": "node", "instance_id": 0},
            {"granularity": "shard", "instance_id": 0},       # no shard_idx
            {"granularity": "shard", "instance_id": 0, "shard_idx": 9},
            {"instance_id": 99},
            {"instance_id": 0, "unexpected": 1},
    ):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post("/v1/admin/fault", bad)
        assert ei.value.code == 400, bad
    # recovering a healthy, non-degraded instance is a conflict
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post("/v1/admin/recover",
              {"granularity": "instance", "instance_id": 1})
    assert ei.value.code == 409
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post("/v1/admin/recover",
              {"granularity": "shard", "instance_id": 1})
    assert ei.value.code == 409


def test_v1_fault_if_busy_noops_on_idle_instance(server):
    out = _post("/v1/admin/fault",
                {"granularity": "instance", "instance_id": 1,
                 "if_busy": True})
    assert out["applied"] is False             # idle: fault not applied
    assert _health()["instances"][1]["alive"]


def test_deprecated_aliases_match_v1_transitions(server):
    """The legacy /admin/* endpoints drive the same engine transitions as
    /v1/admin/* at instance granularity — legacy response bodies, plus a
    Deprecation header."""
    def states():
        h = _health()
        return h["topology"]["states"], [i["alive"] for i in h["instances"]]

    # kill via alias, recover via v1
    out, headers = _post_full("/admin/fail_instance", {"instance": 0})
    assert headers.get("Deprecation") == "true"
    assert out["failed_instance"] == 0         # legacy body unchanged
    alias_killed = states()
    _post("/v1/admin/recover", {"granularity": "instance", "instance_id": 0})
    # kill via v1, recover via alias: identical state both ways
    _post("/v1/admin/fault", {"granularity": "instance", "instance_id": 0})
    assert states() == alias_killed
    out, headers = _post_full("/admin/rejoin_instance", {"instance": 0})
    assert headers.get("Deprecation") == "true"
    assert out["rejoined_instance"] == 0
    assert states()[1] == [True, True]
    # alias double-rejoin conflicts exactly like the v1 endpoint
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post("/admin/rejoin_instance", {"instance": 0})
    assert ei.value.code == 409
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post("/v1/admin/recover",
              {"granularity": "instance", "instance_id": 0})
    assert ei.value.code == 409
