"""Published peaks by ``device_kind``, and the operations and bytes an
algorithm needs, computed from shapes. A device that is not in the table
is an error, not a default. ``arch`` below is the ``transformer_config``
group of a configuration's file (plain data, not a program object) and
``block`` the module its ``block`` names (``blocks/<block>.py``): how many
weights a token meets is the block's to say, not this file's."""

from __future__ import annotations

#: one chip's published peaks. Source: Google Cloud documentation,
#: "TPU v5e" system architecture page (197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interconnect per chip).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]     # the same chip's other name


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmark/peaks.py with its source")
    return PEAKS[device_kind]


def _heads(arch):
    nh = arch["num_heads"]
    kvh = arch.get("num_kv_heads") or nh
    return nh, kvh, arch["hidden_size"] // nh


def forward_flops(block, arch: dict, new_tokens: int,
                  context_tokens: int) -> float:
    """FLOPs of a forward over ``new_tokens`` query positions that attend
    to ``context_tokens`` key positions in total (the sum, over the query
    positions, of the keys each may see): 2 per weight the block says a
    token is multiplied with (``block.matmul_params``) per token, plus
    QKᵀ and PV (2·2·head_dim per head per query-key pair)."""
    nh, _, hd = _heads(arch)
    return (2.0 * block.matmul_params(arch) * new_tokens
            + 4.0 * arch["num_layers"] * nh * hd * context_tokens)


def train_flops_per_token(block, arch: dict, seq: int) -> float:
    """Model FLOPs a training step needs per token at sequence length
    ``seq``: forward + backward = 3 × forward, causal attention sees
    seq/2 keys on average. Recomputation (remat) is NOT counted."""
    return 3.0 * forward_flops(block, arch, 1, seq / 2.0)


def paged_attention_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                         qk_pairs: int, kv_bytes: int = 2,
                         q_bytes: int = 2) -> dict:
    """One layer's paged-attention call. FLOPs: QKᵀ and PV over the
    query-key pairs. Bytes: the K and V of every position a sequence's
    queries may see, read once per sequence, plus q in and o out."""
    nh, kvh, hd = _heads(arch)
    return {"flops": 4.0 * nh * hd * qk_pairs,
            "bytes": 2.0 * kvh * hd * kv_bytes * kv_read_tokens
            + 2.0 * nh * hd * q_bytes * query_tokens}


def flash_attention_cost(arch: dict, batch: int, seq: int,
                         backward: bool = True, el_bytes: int = 2) -> dict:
    """One layer's causal flash attention over [batch, seq]: forward is
    QKᵀ and PV over seq²/2 pairs; backward needs twice that (dQ, dK, dV,
    dP — the kernel's recomputation of P is not counted). Bytes: q, k, v,
    o once each way."""
    nh, kvh, hd = _heads(arch)
    pairs = batch * seq * seq / 2.0
    fwd = 4.0 * nh * hd * pairs
    io = batch * seq * hd * el_bytes * (2 * nh + 2 * kvh)
    return {"flops": fwd * (3.0 if backward else 1.0),
            "bytes": io * (3.0 if backward else 1.0)}


def roofline_seconds(cost: dict, device_kind: str) -> float:
    """The least time the chip could take for ``cost``."""
    p = peaks(device_kind)
    return max(cost["flops"] / p["flops_bf16"],
               cost["bytes"] / p["hbm_bytes_per_s"])
