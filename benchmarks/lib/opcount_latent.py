"""Operations and bytes of absorbed latent attention, from shapes alone
(``opcount.py``'s rule: what the algorithm needs, never what an
implementation happens to move)."""


def ragged_latent_attention(rows_by_seq, heads=128, latent=512, rope=64,
                            itemsize=2):
    """(FLOPs, bytes) of one layer's absorbed latent attention over one
    packed tick.  ``rows_by_seq``: for each sequence in the tick, (number
    of query rows, keys the last of them attends).  Row j of n attends
    ``kv - (n - 1 - j)`` keys.  Per key and head: the score is a product
    over ``latent + rope`` columns and the output one over ``latent``,
    2 FLOPs each: ``2 * keys * heads * ((latent + rope) + latent)``.
    Bytes: the sequence's latent rows up to ``kv`` once (shared by every
    head and every row of the sequence), and each row's query
    (``heads * (latent + rope)``) read and output (``heads * latent``)
    written."""
    flops = nbytes = 0.0
    for n, kv in rows_by_seq:
        keys = n * kv - n * (n - 1) / 2.0
        flops += 2.0 * keys * heads * (2 * latent + rope)
        nbytes += (kv * (latent + rope)
                   + n * heads * (2 * latent + rope)) * itemsize
    return flops, nbytes
