"""Smoke run of the serving path on one TPU.

Serves Yi-9B at its published widths (depth cut to 24 of 48 layers, random
bf16 weights from seed 0) through ``repro.serving.server.serve`` over HTTP
on 127.0.0.1, with two instances, block replication and kevlarflow
recovery, in this one process. It checks, in order:

  0. the paged-attention kernel, as the engine runs it, against the plain
     oracle (kernels/ref.py) on a small pool at the served head shape;
  1. determinism: each greedy /v1/completions, sent twice, returns the
     same token ids, and every token of the longest stream is a top choice
     of the model's plain forward pass (no pages, no kernel);
  2. failover: the same prompts again, and mid-decode an instance kill
     (POST /v1/admin/fault, if_busy) on a busy instance; every request
     completes, at least one migrates, and every stream equals its
     failure-free twin token for token;
  3. recovery: POST /v1/admin/recover, then the prompts once more on the
     healed fleet, again identical;
  4. the device: JAX's platform is "tpu", the engine runs its Pallas
     kernels compiled (not interpreted), and the compiled decode step holds
     a Mosaic kernel (tpu_custom_call).

  python chip_smoke.py                               # on one TPU
  JAX_PLATFORMS=cpu python chip_smoke.py --reduced   # CPU rehearsal

The last line of a passing run is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
Any failed check ends the run with a non-zero exit and no such line; so
does a run off the TPU (without --reduced it stops before building the
model), and a --reduced run, which is a rehearsal and not a result. Every
server flag (``repro.serving.server.build_parser``) is accepted.
"""
from __future__ import annotations

import json
import statistics
import sys
import threading
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# prompt lengths as fractions of max_seq: two prefill buckets (512 and 256
# at the chip's 1024 positions), prompts longer than answers
PROMPT_FRACS = (0.29, 0.2, 0.26, 0.18)
MAX_TOKENS = 64
KILL_AFTER_TOKENS = 16               # decode progress before the kill
PROMPT_SEED = 0
HTTP_TIMEOUT = 600.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The kernel reads bf16 pages and accumulates in f32; the TPU's default
# matmul precision rounds the softmax weights to bf16 (relative error
# ~4e-3), while a wrong page or mask moves outputs by O(1).
KERNEL_TOL = 2e-2
# A served token is right when the plain forward pass ranks it within this
# many logits of its own top choice. Random-weight logits have unit spread,
# and the top two of 64000 lie about 0.2 apart. Both paths hold the same
# bf16 weights and KV and differ by rounding: a few hundredths of a logit,
# plus at most 1/32 from the reference's bf16 logits near the top. An
# attention that skips the newest token already leaves gaps near 0.19 at
# toy size, and a random token sits about four logits down.
REF_LOGIT_TOL = 0.1


def fail(msg: str):
    sys.exit(f"chip_smoke: FAIL: {msg}")


def check(cond: bool, msg: str):
    if not cond:
        fail(msg)


class Client:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(self.base + path,
                                    timeout=HTTP_TIMEOUT) as r:
            return json.loads(r.read())

    def post(self, path: str, payload: dict) -> dict:
        req = urllib.request.Request(
            self.base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT) as r:
            return json.loads(r.read())

    def complete_all(self, prompts, on_sent=None) -> list:
        """POST every prompt at once (one thread each) and return the
        responses in prompt order; raises if any request failed."""
        out = [None] * len(prompts)
        errors = []

        def one(i):
            try:
                out[i] = self.post("/v1/completions", {
                    "prompt_tokens": prompts[i], "max_tokens": MAX_TOKENS})
            except Exception as e:   # re-raised below, on the main thread
                errors.append(f"prompt {i}: {e!r}")

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        if on_sent is not None:
            on_sent()
        for t in threads:
            t.join(HTTP_TIMEOUT)
        check(not errors, f"requests failed: {errors}")
        check(all(o is not None for o in out), "a request did not return")
        return out


def tokens(responses) -> list:
    return [r["choices"][0]["token_ids"] for r in responses]


def check_kernel(cfg, interpret: bool) -> float:
    """Max |kernel - oracle| over a random 64-page pool at the served
    (heads, kv heads, head_dim, page); ragged lengths, shuffled tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.ref import paged_attention_ref

    B, pps, P = 8, 8, 64
    rng = np.random.default_rng(PROMPT_SEED)
    bf = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    q = bf(B, cfg.n_heads, cfg.head_dim)
    k = bf(cfg.n_kv_heads, P, cfg.page_size, cfg.head_dim)
    v = bf(cfg.n_kv_heads, P, cfg.page_size, cfg.head_dim)
    tables = jnp.asarray(rng.permutation(P)[:B * pps].reshape(B, pps),
                         jnp.int32)
    lengths = jnp.asarray(rng.integers(1, pps * cfg.page_size + 1, B),
                          jnp.int32)
    out = ops.paged_attention(q, k, v, tables, lengths, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                  v.astype(jnp.float32), tables, lengths)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    check(bool(jnp.isfinite(out).all()), "kernel output is not finite")
    check(err <= KERNEL_TOL, f"kernel differs from the oracle by {err}")
    return err


def check_reference(eng, prompt, served) -> tuple:
    """Teacher-force ``prompt + served`` through the family's plain
    forward pass; every served token must be within REF_LOGIT_TOL of the
    top logit at its position. Returns (share that is the exact top,
    largest gap)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api

    cfg = eng.cfg
    fwd = jax.jit(lambda p, t: api.family(cfg).forward(cfg, p, t))
    seq = jnp.asarray([prompt + served[:-1]], jnp.int32)
    logits = np.asarray(fwd(eng.params, seq)[0, len(prompt) - 1:],
                        np.float32)
    check(logits.shape == (len(served), cfg.vocab_size),
          f"reference logits have shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), "reference logits not finite")
    gap = logits.max(axis=1) - logits[np.arange(len(served)), served]
    check(float(gap.max()) <= REF_LOGIT_TOL,
          f"served token {int(gap.argmax())} is {gap.max():.3f} logits "
          "below the reference's top choice")
    return float((gap == 0).mean()), float(gap.max())


def decode_progress(svc, n: int) -> int:
    """Fewest tokens generated by the ``n`` in-flight requests, or -1 until
    all ``n`` are decoding (read under the service's lock)."""
    with svc._lock:
        eng = svc.engine
        reqs = [r for i in eng.instances for r in i.requests.values()]
        if len(reqs) != n or any(i.prefill_depth() for i in eng.instances):
            return -1
        return min(r.generated for r in reqs)


def main(argv=None) -> None:
    from repro.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    import numpy as np

    from repro.serving import server as S

    args = S.build_parser().parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.reduced:
        fail(f"JAX's default device is {dev.platform!r} ({dev.device_kind}), "
             "not a TPU; this check runs only on the chip (--reduced "
             "rehearses the flow at toy size)")

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == COMPILE_EVENT else None)

    cfg, ecfg = S.build_configs(args)
    rng = np.random.default_rng(PROMPT_SEED)
    prompts = [rng.integers(1, cfg.vocab_size, int(f * ecfg.max_seq)).tolist()
               for f in PROMPT_FRACS]
    print(f"config: {cfg.name} ({cfg.arch_type}) d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} layers={cfg.n_layers} "
          f"dtype={cfg.dtype} page={cfg.page_size}; random weights, seed 0")
    print(f"engine: {args.instances} instances, max_slots={ecfg.max_slots} "
          f"max_seq={ecfg.max_seq} recovery={ecfg.recovery} "
          f"replicate={ecfg.replicate}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    svc, httpd = S.serve(cfg, ecfg, n_instances=args.instances, port=0)
    jax.block_until_ready(svc.engine.params)
    init_s = time.perf_counter() - t0
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        run(svc, Client(httpd.server_address[1]), prompts, init_s,
            compile_s)
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.shutdown()
        server.join(5)


def run(svc, client: Client, prompts, init_s, compile_s) -> None:
    import jax
    import numpy as np

    from repro.models.paged_decode import next_bucket

    eng = svc.engine
    health = client.get("/health")
    dev = health["device"]
    print(f"device: {dev['count']} x {dev['kind']} ({dev['platform']}), "
          f"Pallas {'interpret' if dev['interpret'] else 'Mosaic'}; "
          f"pool {eng.instances[0].pool.n_blocks} blocks/instance; "
          f"init {init_s:.1f} s")

    err = check_kernel(eng.cfg, eng.interpret)
    print(f"kernel: max |paged_attention - oracle| = {err:.2e} "
          f"(tolerance {KERNEL_TOL})")

    # warm-up: compile one prefill per bucket and the decode step. Sent
    # straight to the service: its wait has no HTTP handler deadline, and
    # a cold compile at full width can outlast one
    t0 = time.perf_counter()
    n_compiles = len(compile_s)
    page = eng.cfg.page_size
    for p in {next_bucket(len(p), lo=page): p for p in prompts}.values():
        req = svc.submit(p, 2)
        check(svc.wait(req, timeout=HTTP_TIMEOUT), "warm-up timed out")
    warm_s = time.perf_counter() - t0
    print(f"warm-up: {warm_s:.1f} s wall, "
          f"{sum(compile_s[n_compiles:]):.1f} s in "
          f"{len(compile_s) - n_compiles} backend compiles")

    # 1. determinism: the same greedy prompt twice -> the same tokens
    first = client.complete_all(prompts)
    twin = tokens(first)
    check(all(len(t) == MAX_TOKENS for t in twin),
          f"expected {MAX_TOKENS} tokens each, got {[len(t) for t in twin]}")
    check(tokens(client.complete_all(prompts)) == twin,
          "greedy replay returned different tokens")
    ttft = [r["timing"]["ttft"] for r in first]
    print(f"determinism: {len(prompts)} prompts x {MAX_TOKENS} tokens, "
          "identical on replay")
    longest = max(range(len(prompts)), key=lambda i: len(prompts[i]))
    top, gap = check_reference(eng, prompts[longest], twin[longest])
    print(f"reference: {top:.0%} of {MAX_TOKENS} served tokens are the "
          f"plain forward's top choice; largest gap {gap:.3f} logits "
          f"(tolerance {REF_LOGIT_TOL})")

    # 2. failover drill: kill a busy instance mid-decode
    fault = {}

    def kill_mid_decode():
        deadline = time.time() + HTTP_TIMEOUT
        while decode_progress(svc, len(prompts)) < KILL_AFTER_TOKENS:
            check(time.time() < deadline, "drill never reached decode")
            time.sleep(0.002)
        rows = client.get("/health")["instances"]
        victim = max(rows, key=lambda r: r["active"])["id"]
        fault.update(victim=victim, reply=client.post("/v1/admin/fault", {
            "granularity": "instance", "instance_id": victim,
            "if_busy": True}))

    drill = client.complete_all(prompts, on_sent=kill_mid_decode)
    reply = fault["reply"]
    check(reply["applied"], f"fault did not land on a busy instance: {reply}")
    migrations = sum(r["kevlarflow"]["migrations"] for r in drill)
    retries = sum(r["kevlarflow"]["retries"] for r in drill)
    check(migrations >= 1, f"no request migrated: {reply}")
    check(tokens(drill) == twin,
          "failover streams differ from their failure-free twins")
    print(f"failover: killed instance {fault['victim']} after "
          f">= {KILL_AFTER_TOKENS} tokens; {migrations} migrated, "
          f"{retries} retried; all {len(drill)} streams identical")

    # 3. recovery: rejoin the instance, then serve the prompts again
    client.post("/v1/admin/recover", {"granularity": "instance",
                                      "instance_id": fault["victim"]})
    health = client.get("/health")
    check(all(r["alive"] for r in health["instances"]),
          "an instance is still down after recover")
    check(tokens(client.complete_all(prompts)) == twin,
          "streams after recovery differ")
    print(f"recover: instance {fault['victim']} rejoined; replay identical")

    # 4. the device: a TPU, Mosaic kernels, a Mosaic call in the step
    inst = next(i for i in eng.instances if i.alive)
    with svc._lock:
        hlo = inst._decode.lower(*inst.decode_args(
            np.zeros(eng.ecfg.max_slots, np.int32),
            jax.random.PRNGKey(0))).compile().as_text()
    stats = jax.devices()[0].memory_stats() or {}
    served = sum(len(r.output_tokens or []) for r in eng.done)
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"compile: {sum(compile_s):.1f} s in {len(compile_s)} backend "
          "compiles over the run")
    print(f"served: {len(eng.done)} requests, {served} tokens")
    print(f"ttft of the first phase (one smoke run, not a benchmark): "
          f"median {statistics.median(ttft):.3f} s, max {max(ttft):.3f} s")
    check(dev["platform"] == "tpu",
          f"served on platform {dev['platform']!r}, not a TPU")
    check(not dev["interpret"], "Pallas kernels ran in interpret mode")
    check("tpu_custom_call" in hlo,
          "the compiled decode step holds no Mosaic kernel")
    check(not eng.cfg.name.endswith("-reduced"),
          "a --reduced run is a rehearsal, not a result")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))


if __name__ == "__main__":
    main()
