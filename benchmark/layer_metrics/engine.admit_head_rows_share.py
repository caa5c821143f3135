"""Model step: how much of the admitted buckets goes through the output
head. Over the window, the logit rows the admission programs computed
(``engine.admit_head_rows``, at the admission itself: 1 for each program
that was told the one row it reads, its S for a program that was not)
over the padded tokens those programs ran
(``engine.admit_bucket_tokens``). 100 % is every position of every
bucket multiplied by the whole vocabulary for one row to be read; one
row per admission reads 1 over the mean bucket. A program without the
counter (the parent of the PR that brought it) reads nothing."""

HEAD_ROWS = "engine.admit_head_rows"
BUCKET_TOKENS = "engine.admit_bucket_tokens"


def read(ctx):
    ran = ctx["counters"].get(BUCKET_TOKENS, 0)
    if HEAD_ROWS not in ctx["counters"] or ran <= 0:
        return None
    return 100.0 * ctx["counters"][HEAD_ROWS] / ran
