"""Typed serving-API schemas (ISSUE 10 satellite): FaultSpec validation
rules and JSON round-trips for every /health dataclass — the response
shape is a documented contract, so a field rename must break a test here,
not an operator's dashboard."""
import json

import pytest

from repro.serving.api_types import (DegradationState, DeviceInfo,
                                     FaultSpec, HealthResponse,
                                     InstanceStatus, TopologyBlock)

# -- FaultSpec --------------------------------------------------------------


def test_fault_spec_instance_roundtrip():
    spec = FaultSpec(granularity="instance", instance_id=3)
    spec.validate(n_instances=8, n_shards=4)
    again = FaultSpec.from_json(json.loads(json.dumps(spec.to_json())))
    assert again == spec


def test_fault_spec_shard_roundtrip():
    spec = FaultSpec(granularity="shard", instance_id=1, shard_idx=2,
                     if_busy=True)
    spec.validate(n_instances=8, n_shards=4)
    assert FaultSpec.from_json(spec.to_json()) == spec


def test_fault_spec_defaults_to_instance_granularity():
    spec = FaultSpec.from_json({"instance_id": 0})
    assert spec.granularity == "instance"
    assert spec.shard_idx is None
    assert spec.if_busy is False


@pytest.mark.parametrize("obj", [
    "not a dict",
    {},                                        # no instance_id
    {"instance_id": "zero"},                   # non-int id
    {"instance_id": 0, "shard_idx": "one"},    # non-int shard
    {"instance_id": 0, "bogus": 1},            # unknown field
])
def test_fault_spec_from_json_rejects_malformed(obj):
    with pytest.raises(ValueError):
        FaultSpec.from_json(obj)


@pytest.mark.parametrize("spec", [
    FaultSpec(granularity="node", instance_id=0),          # bad granularity
    FaultSpec(granularity="instance", instance_id=8),      # out of range
    FaultSpec(granularity="instance", instance_id=-1),
    FaultSpec(granularity="instance", instance_id=0, shard_idx=1),
    FaultSpec(granularity="shard", instance_id=0),          # needs shard_idx
    FaultSpec(granularity="shard", instance_id=0, shard_idx=4),
    FaultSpec(granularity="shard", instance_id=0, shard_idx=-1),
])
def test_fault_spec_validate_rejects(spec):
    with pytest.raises(ValueError):
        spec.validate(n_instances=8, n_shards=4)


def test_fault_spec_recover_may_omit_shard_idx():
    """Recovery restores ALL lost shards, so a shard-granularity recover
    needs no shard_idx — but a fault still does."""
    spec = FaultSpec(granularity="shard", instance_id=0)
    spec.validate(n_instances=8, n_shards=4, for_recover=True)
    with pytest.raises(ValueError):
        spec.validate(n_instances=8, n_shards=4)


# -- /health schema ---------------------------------------------------------


def _degradation(state="HEALTHY", lost=()):
    return DegradationState(state=state, n_shards=4,
                            lost_shards=list(lost),
                            slot_cap=4 if not lost else 3,
                            capacity_frac=1.0 if not lost else 0.75,
                            layout=None if not lost
                            else {"surviving": 4 - len(lost)})


def _instance(iid, alive=True, lost=()):
    return InstanceStatus(
        id=iid, alive=alive, role="both", active=2, queued=1, prefilling=0,
        handoffs_ready=0, pool_used_blocks=5, pool_replica_blocks=3,
        degradation=_degradation(
            state="DEAD" if not alive else ("DEGRADED" if lost
                                            else "HEALTHY"),
            lost=lost))


def _topology():
    return TopologyBlock(
        epoch=3, n_instances=2, alive=[0, 1],
        roles={"0": "both", "1": "both"},
        degraded={"1": [0]}, states={"0": "HEALTHY", "1": "DEGRADED"},
        placement="successor", routing="least_loaded", ring={"0": 1, "1": 0},
        planner={"pending": 1, "rejoins_planned": 1, "rejoins_completed": 0,
                 "plan": [{"instance": 1, "order": 0, "ready_at": 6.0,
                           "fail_time": 2.0, "granularity": "shard",
                           "ring_target_on_rejoin": 0}]})


def test_degradation_state_roundtrip():
    d = _degradation(state="DEGRADED", lost=[0, 2])
    assert DegradationState.from_json(json.loads(json.dumps(d.to_json()))) \
        == d


def test_instance_status_roundtrip():
    s = _instance(1, lost=[0])
    assert InstanceStatus.from_json(json.loads(json.dumps(s.to_json()))) == s


def test_topology_block_roundtrip():
    t = _topology()
    assert TopologyBlock.from_json(json.loads(json.dumps(t.to_json()))) == t


def test_health_response_roundtrip():
    h = HealthResponse(
        status="ok",
        device=DeviceInfo(platform="tpu", kind="TPU v5 lite", count=1,
                          interpret=False),
        instances=[_instance(0), _instance(1, lost=[0])],
        queued=3, completed=17, recovery_mode="kevlarflow",
        failure_events=[{"instance": 1, "granularity": "shard",
                         "shard_idx": 0, "mttr": -1.0}],
        replication={"mode": "delta", "bytes_total": 4096},
        prefix={"enabled": False}, disagg={"enabled": False},
        topology=_topology())
    wire = json.loads(json.dumps(h.to_json()))
    assert HealthResponse.from_json(wire) == h
    # the wire shape is plain JSON: dicts/lists/scalars all the way down
    assert wire["instances"][1]["degradation"]["state"] == "DEGRADED"
    assert wire["topology"]["states"]["1"] == "DEGRADED"
    assert wire["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "interpret": False}
