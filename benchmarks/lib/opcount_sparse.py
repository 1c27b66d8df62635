"""Operations and bytes of DeepSeek sparse attention's two kernels, from
shapes alone (``opcount.py``'s rule: what the algorithm needs, never what
an implementation happens to move — a walk that reads every key and masks
is measured against the work of the selected keys, and reads low)."""


def ragged_index_scores(rows_by_seq, heads=64, dim=128, itemsize=2):
    """(FLOPs, bytes) of one layer's index scores over one packed tick.
    ``rows_by_seq``: for each sequence in the tick, (number of query rows,
    keys the last of them scores).  Row j of n scores ``kv - (n - 1 - j)``
    keys, each a product over ``dim`` columns for every indexer head, 2
    FLOPs a multiply-add: ``2 * keys * heads * dim`` (the ReLU, the head
    weight and the sum over heads, 3 operations a head and key on the
    vector unit, are not counted).  Bytes: the sequence's indexer keys up
    to ``kv`` once (shared by every head and every row of the run), and
    each row's queries (``heads * dim``) read; the scores are the
    selection's input and stay on the chip in a fused form."""
    flops = nbytes = 0.0
    for n, kv in rows_by_seq:
        keys = n * kv - n * (n - 1) / 2.0
        flops += 2.0 * keys * heads * dim
        nbytes += (kv * dim + n * heads * dim) * itemsize
    return flops, nbytes


def ragged_sparse_latent_attention(rows_by_seq, topk=2048, heads=128,
                                   latent=512, rope=64, itemsize=2):
    """(FLOPs, bytes) of one layer's absorbed latent attention over the
    SELECTED positions of one packed tick.  Row j of n has ``kv - (n - 1 -
    j)`` positions to choose from and attends ``min(topk, that)``.  Per
    selected key and head: the score over ``latent + rope`` columns and
    the output over ``latent``, 2 FLOPs each.  Bytes: each row's selected
    latent rows (``latent + rope`` columns: rows share no key by right,
    their selections differ), and each row's query (``heads * (latent +
    rope)``) read and output (``heads * latent``) written."""
    flops = nbytes = 0.0
    for n, kv in rows_by_seq:
        first = kv - (n - 1)                    # the first row's context
        full = max(0, min(n, topk - first + 1)) if first <= topk else 0
        # rows whose context is at most topk attend all of it
        sel = full * first + full * (full - 1) / 2.0 + (n - full) * topk
        flops += 2.0 * sel * heads * (2 * latent + rope)
        nbytes += (sel * (latent + rope)
                   + n * heads * (2 * latent + rope)) * itemsize
    return flops, nbytes
