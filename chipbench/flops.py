"""Required operations and bytes, from shapes alone.

Required means what the algorithm needs once: matmul parameters only (an
embedding lookup is no matmul), causal attention counted as the half square
it is, nothing recomputed. ``m`` is a model configuration's dict with the
published key names (``hidden_size`` ...).
"""
from __future__ import annotations


def head_dim(m) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def kv_dim(m) -> int:
    return m["num_key_value_heads"] * head_dim(m)


def layer_matmul_params(m) -> int:
    h, i = m["hidden_size"], m["intermediate_size"]
    qo = 2 * h * m["num_attention_heads"] * head_dim(m)
    kv = 2 * h * kv_dim(m)
    return qo + kv + 3 * h * i


def head_params(m) -> int:
    return m["hidden_size"] * m["vocab_size"]


def matmul_params(m) -> int:
    """Parameters that take part in a matmul for every token."""
    return m["num_hidden_layers"] * layer_matmul_params(m) + head_params(m)


def train_flops_per_token(m, seq: int) -> float:
    """Forward and backward of one token in a sequence of ``seq``: 6 a
    matmul parameter, and causal attention, whose forward is QK^T and PV
    over on average seq/2 keys: 2 matmuls x 2 x (seq/2) x q width = 2 seq
    q_width a layer, three times that with the backward."""
    q_width = m["num_attention_heads"] * head_dim(m)
    attn = 6.0 * m["num_hidden_layers"] * seq * q_width
    return 6.0 * matmul_params(m) + attn


def forward_flops(m, new_tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """Serving: ``new_tokens`` tokens go through the layers, attending to
    ``context_sum`` keys in all (the sum over those tokens of the keys each
    one sees, itself included); ``logit_rows`` of them go through the head."""
    q_width = m["num_attention_heads"] * head_dim(m)
    layers = m["num_hidden_layers"] * (
        2.0 * layer_matmul_params(m) * new_tokens + 4.0 * q_width * context_sum)
    return layers + 2.0 * head_params(m) * logit_rows


# -- kernels: one call ---------------------------------------------------------

def flash_fwd_call(m, batch: int, seq: int, itemsize: int = 2):
    """(flops, bytes) of one causal flash forward over [batch, seq]."""
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = head_dim(m)
    flops = 2.0 * batch * nh * seq * seq * d        # 2 matmuls, half square
    q_o = 2 * batch * seq * nh * d * itemsize
    k_v = 2 * batch * seq * nkv * d * itemsize
    lse = batch * nh * seq * 4
    return flops, float(q_o + k_v + lse)


def flash_bwd_call(m, batch: int, seq: int, itemsize: int = 2):
    """(flops, bytes) of one fused causal flash backward: five matmuls over
    the half square (QK^T again, dP, dV, dQ, dK); reads q k v o do lse,
    writes dq dk dv."""
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = head_dim(m)
    flops = 5.0 * batch * nh * seq * seq * d
    q_like = 4 * batch * seq * nh * d * itemsize      # q o do dq
    kv_like = 4 * batch * seq * nkv * d * itemsize    # k v dk dv
    lse = 2 * batch * nh * seq * 4                    # lse and delta
    return flops, float(q_like + kv_like + lse)


def paged_decode_call(m, context_lens, block_size: int, itemsize: int = 2):
    """(flops, bytes) of the paged decode attention of ONE layer for a batch
    whose sequences hold ``context_lens`` cached tokens: each reads its
    blocks of K and V once and writes one new column."""
    nh, d = m["num_attention_heads"], head_dim(m)
    kvd = kv_dim(m)
    keys = float(sum(context_lens))
    blocks = float(sum(-(-c // block_size) for c in context_lens))
    flops = 4.0 * nh * d * keys
    kv_bytes = 2 * blocks * block_size * kvd * itemsize
    q_o = 2 * len(context_lens) * nh * d * itemsize
    return flops, kv_bytes + q_o


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
