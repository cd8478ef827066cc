"""The chip's peaks, and the work each measured operation needs.

Counts come from the algorithm and the shapes, never from the compiled
program, so a later implementation that does more or less work than it
needs shows in its roofline share instead of moving the yardstick.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by `jax.Device.device_kind`. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
PEAKS = {
    "TPU v5 lite": {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None


def llama_prefill_flop_per_record(m: dict, seq_len: int) -> float:
    """Useful FLOPs to score one record of `seq_len` tokens.

    `m` holds the model's published sizes (Hugging Face `config.json`
    keys). Counted: every matmul of the body at every position
    (2 FLOPs per weight per token), causal attention (QK^T and PV over
    the lower triangle, seq_len^2 / 2 pairs), and the tied head at the
    last position only, since a score reads one position. The embedding
    lookup is a gather and counts nothing; norms and softmax are
    negligible next to the matmuls and are left out.
    """
    d = m["hidden_size"]
    layers = m["num_hidden_layers"]
    heads = m["num_attention_heads"]
    kv_heads = m["num_key_value_heads"]
    head_dim = m.get("head_dim", d // heads)
    d_ff = m["intermediate_size"]
    vocab = m["vocab_size"]
    attn_w = d * head_dim * (heads + 2 * kv_heads) + heads * head_dim * d
    mlp_w = 3 * d * d_ff
    body = 2.0 * layers * (attn_w + mlp_w) * seq_len
    attn = 2 * 2.0 * (seq_len * seq_len / 2.0) * heads * head_dim * layers
    head = 2.0 * d * vocab
    return body + attn + head


def threshold_select_bytes(records_scanned: int, records_selected: int
                           ) -> float:
    """HBM bytes an emission pass needs: read every float32 score once,
    write one int32 index per selected record."""
    return 4.0 * records_scanned + 4.0 * records_selected


def score_hist_bytes(records: int) -> float:
    """HBM bytes a sketch pass needs: read every float32 score once (the
    histogram itself is a few KiB and stays on chip)."""
    return 4.0 * records
