"""OpenAI-compatible HTTP front-end (paper Sec 3.3: "providing an OpenAI-
compatible server endpoint"). Minimal but real: a threaded stdlib HTTP
server over RealEngine with a background engine loop, POST /v1/completions,
GET /health, and the versioned fault-injection admin API
(``POST /v1/admin/fault`` / ``POST /v1/admin/recover`` — docs/api.md; the
legacy ``/admin/fail_instance`` / ``/admin/rejoin_instance`` paths remain
as deprecated aliases).

  PYTHONPATH=src python -m repro.serving.server --port 8080          # on a TPU
  PYTHONPATH=src python -m repro.serving.server --reduced --port 8080  # CPU toy
  curl -d '{"prompt_tokens": [1,2,3], "max_tokens": 8}' localhost:8080/v1/completions

By default it serves Yi-9B at its published widths with depth cut to 24 of
48 layers (``build_parser``/``build_configs``, which chip_smoke.py shares).
"""
from __future__ import annotations

import argparse
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax

from repro.configs import get_config
from repro.runtime import enable_compile_cache
from repro.serving.api_types import (DegradationState, DeviceInfo, FaultSpec,
                                     HealthResponse, InstanceStatus,
                                     TopologyBlock)
from repro.serving.engine import EngineConfig, RealEngine
from repro.serving.request import Request


class EngineService:
    """Background continuous-batching loop around RealEngine.

    The engine runs on the WALL clock (``clock=time.time``), so request
    timestamps — arrival, admit, first token, completion — live on one
    timebase and the HTTP layer (and the latency bench) can report real
    TTFT/latency seconds."""

    def __init__(self, cfg, ecfg: EngineConfig, n_instances: int = 2):
        self.engine = RealEngine(cfg, ecfg, n_instances=n_instances,
                                 clock=time.time)
        self.cfg = cfg
        devs = jax.devices()
        self.device = DeviceInfo(platform=devs[0].platform,
                                 kind=devs[0].device_kind, count=len(devs),
                                 interpret=self.engine.interpret)
        self._lock = threading.Lock()
        self._next_rid = 0
        self._events: dict[int, threading.Event] = {}
        self._n_signaled = 0            # engine.done prefix already signaled
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop:
            progressed = 0
            with self._lock:
                if self.engine.has_pending() or \
                        self.engine.recovery_pending():
                    progressed = self.engine.step()
                # signal only completions NEW since the last pass — the old
                # loop re-scanned (and re-set events for) the entire done
                # list on every idle iteration
                new_done = self.engine.done[self._n_signaled:]
                self._n_signaled = len(self.engine.done)
            for req in new_done:
                ev = self._events.get(req.rid)
                if ev:
                    ev.set()
            if progressed:
                # hand the GIL over between steps: the lock is not fair,
                # and without a yield this thread re-takes it before a
                # waiting request, /health or admin call ever can
                time.sleep(0)
            else:
                # idle, or stalled on a standard-mode weight reload: back
                # off instead of spinning with the lock held. A slot mid-
                # chunked-prefill IS pending work (its next chunk runs on
                # the next step), so it keeps the loop on the fast cadence
                busy = self.engine.has_pending() or any(
                    i.prefill_depth() for i in self.engine.instances)
                time.sleep(0.002 if busy else 0.01)

    def submit(self, prompt_tokens, max_tokens: int) -> Request:
        # stamped before the lock, which the loop holds a whole engine
        # step: arrival - submit is the front end's wait, admit - arrival
        # the engine's admission queue
        submitted = time.time()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid=rid, prompt_len=len(prompt_tokens),
                          max_new_tokens=max_tokens, arrival_time=time.time(),
                          submit_time=submitted,
                          prompt_tokens=list(prompt_tokens))
            self._events[rid] = threading.Event()
            self.engine.submit(req)
        return req

    def wait(self, req: Request, timeout: float = 120.0) -> bool:
        return self._events[req.rid].wait(timeout)

    def drain(self, timeout: float = 300.0) -> bool:
        """Block until every submitted request has completed — used by the
        server's clean shutdown and by the latency bench to close a run."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._lock:
                if not self.engine.has_pending():
                    return True
            time.sleep(0.005)
        return False

    # -- fault/admin entry points (versioned API's service layer) -------------
    def apply_fault(self, spec: FaultSpec):
        """One lock-held engine call per fault — instance kills and shard
        losses both. ``spec.if_busy`` is atomic with the fault itself:
        the busy check and the kill happen under the same lock, so a
        drill's fault is guaranteed to land on a serving instance."""
        with self._lock:
            return self.engine.apply_fault(spec)

    def recover(self, spec: FaultSpec):
        with self._lock:
            return self.engine.recover(spec)

    def validate_spec(self, spec: FaultSpec, for_recover: bool = False):
        """Shape-check a spec without applying it — the HTTP layer runs
        this first so malformed specs 400 while state conflicts 409."""
        spec.validate(len(self.engine.instances), self.engine.ecfg.n_shards,
                      for_recover=for_recover)

    def fail_instance(self, instance_id: int):
        return self.apply_fault(
            FaultSpec(granularity="instance", instance_id=instance_id))

    def fail_instance_if_busy(self, instance_id: int):
        """Kill the instance IFF it has in-flight requests. Returns the
        resumed rids, or None if it was idle."""
        return self.apply_fault(
            FaultSpec(granularity="instance", instance_id=instance_id,
                      if_busy=True))

    def rejoin_instance(self, instance_id: int):
        self.recover(
            FaultSpec(granularity="instance", instance_id=instance_id))

    def health(self) -> HealthResponse:
        """The /health payload as its typed schema (api_types) — built
        under the engine lock so every block is one consistent snapshot."""
        with self._lock:
            eng = self.engine
            instances = [
                InstanceStatus(
                    id=i.instance_id, alive=i.alive, role=i.role,
                    active=len(i.requests),
                    queued=len(eng.queues[i.instance_id]),
                    prefilling=i.prefill_depth(),
                    handoffs_ready=len(i.ready_handoffs),
                    pool_used_blocks=i.pool.n_used,
                    pool_replica_blocks=i.pool.replica_blocks_used(),
                    degradation=DegradationState(
                        state=eng.control.view.state_of(i.instance_id),
                        n_shards=i.n_shards,
                        lost_shards=sorted(i.lost_shards),
                        slot_cap=i.slot_cap if i.alive else 0,
                        capacity_frac=i.capacity_frac(),
                        layout=i.degraded_layout))
                for i in eng.instances]
            topo = eng.control.describe()
            return HealthResponse(
                status="ok", device=self.device, instances=instances,
                queued=eng.queue_depth(), completed=len(eng.done),
                recovery_mode=eng.ecfg.recovery,
                failure_events=[dict(e) for e in eng.failure_events],
                replication=eng.replication_stats(),
                prefix=eng.prefix_stats(),
                disagg=eng.disagg_stats(),
                # the control plane's view of the fleet: membership epoch,
                # degradation states, placement ring, and the recovery
                # plan — what an operator polls during a failure storm
                topology=TopologyBlock(**topo))

    def stats(self):
        """Legacy dict view of /health (kept for callers predating the
        typed schema)."""
        return self.health().to_json()

    def shutdown(self, drain_timeout: float = 0.0):
        """Stop the engine loop; with ``drain_timeout`` > 0, let in-flight
        generations finish first — and on timeout, say what was abandoned
        instead of exiting silently."""
        if drain_timeout > 0 and not self.drain(timeout=drain_timeout):
            with self._lock:
                eng = self.engine
                unfinished = eng.queue_depth() + \
                    sum(len(i.requests) for i in eng.instances)
                parked = len(eng._handoffs)
            print(f"shutdown: drain timed out after {drain_timeout:.0f}s — "
                  f"{unfinished} request(s) unfinished, "
                  f"{parked} handoff(s) parked")
        self._stop = True
        self._thread.join(timeout=2)


def make_handler(svc: EngineService):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet
            pass

        def _json(self, code: int, obj, headers=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for name, value in (headers or {}).items():
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/health":
                self._json(200, svc.health().to_json())
            else:
                self._json(404, {"error": "not found"})

        def _fault(self, payload, deprecated: bool = False):
            """POST /v1/admin/fault. Shape errors (bad JSON shape, spec
            out of range) are 400; state conflicts (shard fault on a dead
            instance) are 409."""
            try:
                spec = FaultSpec.from_json(payload)
                svc.validate_spec(spec)
            except ValueError as e:
                self._json(400, {"error": str(e)})
                return
            try:
                resumed = svc.apply_fault(spec)
            except ValueError as e:
                self._json(409, {"error": str(e)})
                return
            self._json(200, {
                "applied": resumed is not None,
                "fault": spec.to_json(),
                "seamlessly_resumed": resumed if resumed is not None else [],
            }, headers={"Deprecation": "true"} if deprecated else None)

        def _recover(self, payload, deprecated: bool = False):
            """POST /v1/admin/recover. Shape errors are 400; state
            conflicts (rejoining an alive instance, restoring a
            non-degraded one) are 409."""
            try:
                spec = FaultSpec.from_json(payload)
                svc.validate_spec(spec, for_recover=True)
            except ValueError as e:
                self._json(400, {"error": str(e)})
                return
            try:
                svc.recover(spec)
            except ValueError as e:
                self._json(409, {"error": str(e)})
                return
            self._json(200, {"recovered": spec.to_json()},
                       headers={"Deprecation": "true"} if deprecated
                       else None)

        def do_POST(self):
            length = int(self.headers.get("Content-Length", 0))
            try:
                payload = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError:
                self._json(400, {"error": "bad json"})
                return
            if self.path == "/v1/completions":
                toks = payload.get("prompt_tokens")
                if not toks:
                    self._json(400, {"error": "prompt_tokens required"})
                    return
                max_tokens = int(payload.get("max_tokens", 16))
                req = svc.submit(toks, max_tokens)
                if not svc.wait(req):
                    self._json(504, {"error": "timeout"})
                    return
                self._json(200, {
                    "id": f"cmpl-{req.rid}",
                    "object": "text_completion",
                    "model": svc.cfg.name,
                    "choices": [{
                        "index": 0,
                        "token_ids": req.output_tokens,
                        "finish_reason": "length",
                    }],
                    "usage": {
                        "prompt_tokens": req.prompt_len,
                        "completion_tokens": len(req.output_tokens or []),
                    },
                    "timing": req.timing(),
                    "kevlarflow": {"migrations": req.n_migrations,
                                   "retries": req.n_retries},
                })
            elif self.path == "/v1/admin/fault":
                self._fault(payload)
            elif self.path == "/v1/admin/recover":
                self._recover(payload)
            # deprecated aliases: same engine transition as the v1 pair
            # (instance granularity), legacy response bodies, plus a
            # Deprecation header — docs/api.md has the migration table
            elif self.path == "/admin/fail_instance":
                iid = int(payload.get("instance", 0))
                resumed = svc.fail_instance(iid)
                self._json(200, {"failed_instance": iid,
                                 "seamlessly_resumed": resumed},
                           headers={"Deprecation": "true"})
            elif self.path == "/admin/rejoin_instance":
                iid = int(payload.get("instance", 0))
                try:
                    svc.rejoin_instance(iid)
                except ValueError as e:
                    self._json(409, {"error": str(e)},
                               headers={"Deprecation": "true"})
                    return
                self._json(200, {"rejoined_instance": iid},
                           headers={"Deprecation": "true"})
            else:
                self._json(404, {"error": "not found"})

    return Handler


def serve(cfg, ecfg=None, n_instances=2, port=8080):
    svc = EngineService(cfg, ecfg or EngineConfig(), n_instances)
    httpd = ThreadingHTTPServer(("127.0.0.1", port), make_handler(svc))
    return svc, httpd


# The deployment served by default: Yi-9B (arXiv:2403.04652) at its
# published widths, depth cut to 24 of 48 layers, so that its bf16 weights
# (~9.35 GB) and two instances' KV pools (~0.8 GB each at 8 slots x 1024
# positions) fit one 16 GB TPU v5e.
DEFAULT_ARCH = "yi-9b"
DEFAULT_N_LAYERS = 24
MAX_SLOTS = 8
MAX_SEQ = 1024
# --reduced serves on the CPU, where the Pallas interpreter's decode step
# grows faster than linearly with the block-table width (7.8 s per engine
# step at 1024 positions against 2.4 s at 512, reduced Yi-9B, 2 instances)
REDUCED_MAX_SEQ = 256


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=DEFAULT_ARCH)
    ap.add_argument("--n-layers", type=int, default=DEFAULT_N_LAYERS,
                    help="depth cut at published widths; 0 = the published "
                         "depth")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the toy variant (ModelConfig.reduced: 2 "
                         "layers, d_model <= 256) — for the CPU")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV pool: quantized pages + scales, int8 "
                         "decode kernel, ~2x smaller replication messages")
    ap.add_argument("--recovery", default="kevlarflow",
                    choices=["kevlarflow", "standard"],
                    help="fail_instance policy: promote replicas + reroute "
                         "+ warm-spare rejoin, or restart + group-wide "
                         "weight-reload stall")
    ap.add_argument("--auto-rejoin", action="store_true",
                    help="bring a failed instance back automatically (warm "
                         "spare after --rejoin-delay s; standard mode after "
                         "--reload-penalty s)")
    ap.add_argument("--rejoin-delay", type=float, default=1.0)
    ap.add_argument("--reload-penalty", type=float, default=20.0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: run prompts through the pool in "
                         "chunks of this many tokens, interleaved with "
                         "decode steps (0 = monolithic prefill)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="prefill/decode disaggregation: the first half of "
                         "the instances run chunked prefill only and stream "
                         "finished KV pages to decode-role peers (implies "
                         "--prefill-chunk; defaults it to 8 if unset)")
    ap.add_argument("--placement", default="successor",
                    choices=["successor", "rendezvous"],
                    help="replication placement policy: next-alive ring "
                         "successor (classic), or rendezvous hashing "
                         "(minimal re-host churn on membership changes — "
                         "preferred at 8+ instances)")
    ap.add_argument("--n-shards", type=int, default=4,
                    help="tensor-parallel shards per instance — the unit "
                         "of shard-granularity faults (/v1/admin/fault "
                         "with granularity=shard degrades the instance to "
                         "its surviving slice instead of killing it)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="intern fully-covered prompt pages in a refcounted "
                         "prefix index; shared prefixes attach by reference "
                         "(copy-on-write) and skip prefill compute")
    return ap


def build_configs(args):
    """(ModelConfig, EngineConfig) from ``build_parser`` arguments. The model
    size is exactly what the arguments say: never picked from a parameter
    count or from the backend."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    elif args.n_layers:
        cfg = cfg.with_layers(args.n_layers)
    # sliding-window archs serve any max_seq (block recycling keeps only
    # the attention window resident) — no capping needed
    if args.disaggregate and args.prefill_chunk <= 0:
        args.prefill_chunk = 8      # streaming needs chunked prefill
    ecfg = EngineConfig(max_slots=MAX_SLOTS,
                        max_seq=REDUCED_MAX_SEQ if args.reduced else MAX_SEQ,
                        kv_quant=args.kv_quant, recovery=args.recovery,
                        auto_rejoin=args.auto_rejoin,
                        rejoin_delay=args.rejoin_delay,
                        reload_penalty=args.reload_penalty,
                        prefill_chunk=args.prefill_chunk,
                        prefix_cache=args.prefix_cache,
                        disaggregate=args.disaggregate,
                        placement=args.placement,
                        n_shards=args.n_shards,
                        replicate=(args.recovery == "kevlarflow"))
    return cfg, ecfg


def main():
    enable_compile_cache()
    args = build_parser().parse_args()
    cfg, ecfg = build_configs(args)
    svc, httpd = serve(cfg, ecfg, n_instances=args.instances, port=args.port)
    print(f"KevlarFlow serving {cfg.name} on :{args.port} "
          f"({args.instances} instances, {args.recovery} recovery) on "
          f"{svc.device.count} x {svc.device.kind} "
          f"({svc.device.platform}, Pallas "
          f"{'interpret' if svc.device.interpret else 'Mosaic'}). "
          f"POST /v1/completions")
    try:
        httpd.serve_forever()
    finally:
        # let in-flight generations finish; shutdown() logs what was
        # abandoned if the drain times out
        svc.shutdown(drain_timeout=30.0)


if __name__ == "__main__":
    main()
