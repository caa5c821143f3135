"""Attention: how much of the bucket's square an admission's attention
scores. Over the window, the program's count of query-key pairs its
admissions scored (``attn.prefill_positions_scored``, at the admission
itself: per layer the rows x keys of the blocks it was read in, each
block against the keys up to its own end or inside a window layer's
band; S x T where a layer was read whole) over the pairs of the whole
squares (``attn.prefill_positions_square``: layers x S x T). 100 % is
every pair of every bucket scored and then masked. A program without
the counters (the parent of the PR that brought them) reads nothing."""

SCORED = "attn.prefill_positions_scored"
SQUARE = "attn.prefill_positions_square"


def read(ctx):
    square = ctx["counters"].get(SQUARE, 0)
    if SCORED not in ctx["counters"] or square <= 0:
        return None
    return 100.0 * ctx["counters"][SCORED] / square
