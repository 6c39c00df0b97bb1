"""The harness end to end at toy size on the CPU: a throwaway
configuration, mix and metric added as new files are found by name; a run
is correct; a run with its timed path broken underneath is not."""
import math

import numpy as np
import pytest

import tinybench
from bench import harness as H
from bench import spec as S


def _bench(tmp_path, **kw):
    root = tinybench.make(tmp_path, **kw)
    H.use_compile_cache(root)
    return S.Bench(root, root / "bench")


def _run(bench, seed=2**33 + 5, trace=False, fault=None):
    return H.run_cell(bench, "tiny_cell", seed, 2.0, trace,
                      require_tpu=False, fault=fault)


def test_new_files_are_found_by_name_and_the_run_is_correct(tmp_path):
    bench = _bench(tmp_path, extra_metric="n_measured")
    res = _run(bench)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert res["attempted"] >= 20 and res["failed"] == 0
    assert res["checks"]["programs_built_in_window"]["value"] == 0
    assert res["checks"]["replica_blocks_compared"]["value"] >= 1
    assert list(res)[-1] == "checks"
    traced = _run(bench, seed=11, trace=True)
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["n_measured"]["value"] == traced["attempted"]
    assert "step_ms" in traced["metrics"]


def test_a_token_altered_where_it_is_produced_fails(tmp_path):
    def alter_tokens(svc):
        for inst in svc.engine.instances:
            decode = inst._decode

            def bad(*a, _decode=decode, _v=svc.cfg.vocab_size):
                nxt, *rest = _decode(*a)
                return ((nxt + 1) % _v, *rest)
            inst._decode = bad
    res = _run(_bench(tmp_path), fault=alter_tokens)
    assert not res["correct"]
    gap = res["checks"]["mean_gap_logits"]
    assert gap["value"] > gap["limit"]


def test_replicas_left_stale_fail(tmp_path):
    def drop_copies(svc):
        for inst in svc.engine.instances:
            inst.pool.copy_blocks_to = lambda *a, **kw: None
    res = _run(_bench(tmp_path), fault=drop_copies)
    assert not res["correct"]
    assert res["checks"]["replica_blocks_differing"]["value"] > 0


def test_no_accelerator_no_result():
    with pytest.raises(SystemExit, match="no accelerator"):
        H.device_info(True, 1)


def test_tails_count_every_request():
    base = {"prompt": [1] * 8, "admit": 0.5, "now": 60.0}
    measured = [dict(base, due=0.0, first=0.1 * (i + 1), finish=1.0 + i,
                     out=[7] * 11) for i in range(9)]
    # one request never got its first token: it counts as the drain's end
    measured.append(dict(base, due=0.0, first=-1.0, finish=-1.0, out=[]))
    bench = S.Bench()
    run = H.Run(conf={}, peaks={}, window=(0.0, 10.0), measured=measured,
                steps=1, step_walls=[0.1], repl_bytes=0, tokens=500)
    out = H._end_to_end(bench, "yi9b_code", run, setup_s=30.0)
    ttft = [0.1 * (i + 1) for i in range(9)] + [60.0]
    assert out["ttft_p90_s"]["value"] == pytest.approx(
        float(np.percentile(ttft, 90)))
    assert set(out) == {"ttft_p90_s", "setup_s"}
    chat = H._end_to_end(bench, "yi9b_chat", run, setup_s=30.0)
    tpot = [(1.0 + i - 0.1 * (i + 1)) / 10 * 1e3 for i in range(9)]
    assert chat["tpot_p90_ms"]["value"] == pytest.approx(
        float(np.percentile(tpot, 90)))
    batch = H._end_to_end(bench, "yi9b_batch", run, setup_s=30.0)
    assert batch["out_tok_s"]["value"] == 50.0


def test_sample_holds_the_longest_and_enough_tokens():
    done = [{"out": [1] * n, "prompt": [2] * 4} for n in (5, 30, 7, 9, 12)]
    a = H.choose_sample(done, 99, 40)
    assert a[0]["out"] == [1] * 30
    assert sum(len(r["out"]) for r in a) >= 40
    assert a == H.choose_sample(done, 99, 40)
    assert H.choose_sample([], 1, 10) == []
    assert math.isclose(H.percentile([1, 2, 3, 4], 50), 2.5)
