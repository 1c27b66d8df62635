"""Operations and bytes of ragged EVA attention, from shapes alone
(``opcount.py``'s rule: what the algorithm needs, never what an
implementation happens to move)."""


def keys_of(pos, window=2048, chunk=16):
    """(exact keys, summaries) a row at position ``pos`` attends: its own
    window's positions up to its own, and one summary per chunk of every
    earlier window."""
    return pos % window + 1, pos // window * (window // chunk)


def ragged_eva_attention(rows_by_seq, heads=32, head_dim=128, window=2048,
                         chunk=16, itemsize=2):
    """(FLOPs, bytes) of one layer's EVA attention over one packed tick.
    ``rows_by_seq``: for each sequence in the tick, (number of query rows,
    positions up to and with the last of them): row j of n sits at
    position ``kv - n + j``.  Per row and key — exact or summary alike —
    and head, the score and the output are a product over ``head_dim``
    columns each, 2 FLOPs a multiply-add: ``4 * heads * head_dim *
    keys``.  Bytes: the K and V rows of every window position and of
    every summary that some row of the run attends, once (they are
    shared by the run's rows), and each row's query read and output
    written (``heads * head_dim`` each).  A run that crosses a window is
    counted window by window, as it is packed."""
    per_key = heads * head_dim
    flops = nbytes = 0.0
    for n, kv in rows_by_seq:
        first = kv - n
        while first < kv:
            last = min(kv, (first // window + 1) * window) - 1
            m = last - first + 1
            a, summaries = keys_of(first, window, chunk)
            exact = m * a + m * (m - 1) / 2.0   # a, a + 1, ... a + m - 1
            flops += 4.0 * per_key * (exact + m * summaries)
            nbytes += (2 * (last % window + 1 + summaries) * per_key
                       + 2 * m * per_key) * itemsize
            first = last + 1
    return flops, nbytes
