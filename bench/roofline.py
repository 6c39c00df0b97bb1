"""Operations and bytes the algorithm needs, computed from a configuration
file's sizes; the chip's peaks come from bench/peaks.json.

These are the benchmark's own: a program change that moves work around
cannot change how that work is counted.
"""
from __future__ import annotations


def matmul_params_per_token(conf: dict) -> int:
    """Weights one token's forward pass multiplies: attention projections,
    the MLP (for a mixture, the router and the ``num_experts_per_tok``
    experts a token is routed to) and the unembedding. The embedding
    lookup is a gather, not a matmul."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hd = conf["head_dim"]
    nh, nk = conf["num_attention_heads"], conf["num_key_value_heads"]
    attn = d * nh * hd + 2 * d * nk * hd + nh * hd * d
    experts = conf.get("num_local_experts", 0)
    if experts:
        mlp = conf["num_experts_per_tok"] * 3 * d * f + d * experts
    else:
        mlp = 3 * d * f
    return conf["num_hidden_layers"] * (attn + mlp) + d * conf["vocab_size"]


def model_flops(conf: dict, tokens: float) -> float:
    """2 operations (a multiply and an add) per weight per token."""
    return 2.0 * matmul_params_per_token(conf) * tokens


def paged_attention_cost(conf: dict, context_lens, kv_bytes: int = 2):
    """(operations, bytes) of one layer's decode attention over the live
    slots, each attending its whole context once.

    Operations: q.k and p.v, 2 each per head per context position per
    head dim. Bytes: every context position's K and V rows of every KV
    head, read once, plus q in and the output out. Counted from the
    context lengths alone, never from the padded block table, so any
    kernel that does this work is held to the same least time."""
    nh, nk, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    ctx = sum(int(n) for n in context_lens)
    flops = 4.0 * nh * hd * ctx
    nbytes = 2.0 * nk * hd * kv_bytes * ctx \
        + 2.0 * len(context_lens) * nh * hd * kv_bytes
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
