"""The control of ``correct``: the reference computed in int8 in the
program's place must read above the limit that the program's own runs stay
under (toy size, CPU; the chip readings are in PERF.md)."""
import numpy as np
import pytest

import tinybench
from bench import harness as H
from bench import spec as S

LIMIT = tinybench.TINY_CONFIG["check"]["mean_gap_limit"]


@pytest.fixture(scope="module")
def benches(tmp_path_factory):
    out = {}
    for config in (tinybench.TINY_CONFIG, tinybench.TINY_MOE):
        tmp = tmp_path_factory.mktemp(config["name"])
        out[config["name"]] = S.Bench(tinybench.make(tmp, config=config),
                                      tmp / "bench")
    return out


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_the_limit(benches, name, seed):
    from bench import weights
    bench = benches[name]
    conf = bench.config(name)
    params = weights.make_params(S.model_config(conf), seed)
    rng = np.random.default_rng(seed)
    sample = [{"prompt": rng.integers(1, 256, 16).tolist(),
               "out": rng.integers(1, 256, 47).tolist()} for _ in range(8)]
    gaps = H.token_gaps(bench, conf, params, sample, control=True)
    assert gaps.size == 8 * 47
    assert gaps.mean() > LIMIT
    # the float32 reference against itself: every token its first choice
    ref = H.token_gaps(bench, conf, params, [
        dict(r, out=_greedy(bench, conf, params, r)) for r in sample[:1]])
    assert ref.max() == 0.0


def _greedy(bench, conf, params, row, n=6):
    """``n`` tokens decoded greedily by the reference after the prompt."""
    import jax.numpy as jnp
    ref = bench.reference(conf)
    seq = np.zeros((1, 64), np.int32)
    p = len(row["prompt"])
    seq[0, :p] = row["prompt"]
    for i in range(n):
        x = ref.hidden(conf, params, jnp.asarray(seq))
        _, _, first = ref.logit_stats(conf, params, x, jnp.asarray(seq))
        seq[0, p + i] = int(first[0, p + i - 1])
    return seq[0, p:p + n].tolist()
