"""Operations and bytes the algorithms need, from shapes alone, and the one
table of peaks.  Never what an implementation happens to move: each
sequence's keys and values and each weight are read once per call, and the
FLOPs are those of the real rows.
"""

# One chip.  Source: Google Cloud documentation, "TPU v5e" system
# architecture: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.  Keyed by the exact
# ``device_kind`` JAX reports; a device not in the table is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; add it to "
            f"benchmarks/lib/opcount.py PEAKS with its source "
            f"(known: {sorted(PEAKS)})") from None


def roofline_s(flops, nbytes, device_kind):
    """The least time the chip could take, and which side bounds it."""
    p = peaks(device_kind)
    t_f, t_b = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def transformer_train_flops(B, L, n_layers, H, I, V):
    """Model FLOPs of one training step, forward + backward = 3 x forward,
    the Megatron/PaLM convention (copy of bench.py
    ``_transformer_train_flops``).  Per token per layer, multiply and add
    as 2: QKVO projections 8H^2, attention scores and context 4LH (the
    full square, as that convention counts it), MLP 4HI; head 2HV per
    token."""
    per_layer = 8 * H * H + 4 * L * H + 4 * H * I
    per_token = n_layers * per_layer + 2 * H * V
    return 3.0 * B * L * per_token


def flash_attention(B, L, heads, head_dim, itemsize=2, causal=True):
    """(FLOPs, bytes) of one layer's attention, forward and backward
    together: QK^T and PV forward (2 products), and backward the
    recomputed scores, dP, dV, dQ, dK (5 products), each 2*L*L*D per head
    and halved under the causal mask.  Bytes: forward reads Q, K, V and
    writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    product = 2.0 * B * heads * L * L * head_dim * (0.5 if causal else 1.0)
    tensor = B * L * heads * head_dim * itemsize
    return 7 * product, 12.0 * tensor


def ragged_paged_attention(rows_by_seq, heads, head_dim, itemsize=2):
    """(FLOPs, bytes) of one layer's ragged paged attention over one
    packed tick.  ``rows_by_seq``: for each sequence in the tick,
    (number of query rows, keys the last of them attends).  Row j of n
    attends ``kv - (n - 1 - j)`` keys; FLOPs are QK^T and PV over those
    (4 * keys * D per head).  Bytes: the sequence's K and V up to ``kv``
    once, and each row's q read and output written."""
    flops = nbytes = 0.0
    for n, kv in rows_by_seq:
        keys = n * kv - n * (n - 1) / 2.0
        flops += 4.0 * keys * heads * head_dim
        nbytes += (2.0 * kv + 2.0 * n) * heads * head_dim * itemsize
    return flops, nbytes
