"""KDA: how much of the bucket an admission's sequential scan runs.
Over the window, the program's count of scan chunks its admissions ran
(``kda.scan_chunks_run``, at each admission: per KDA layer the chunks
that hold a prompt position) over the chunks of their whole buckets
(``kda.scan_chunks_bucket``). 100 % is the scan over every chunk of
every bucket, pad included. A program without the counters (the parent
of the PR that brought them) reads nothing."""

RUN = "kda.scan_chunks_run"
BUCKET = "kda.scan_chunks_bucket"


def read(ctx):
    bucket = ctx["counters"].get(BUCKET, 0)
    if RUN not in ctx["counters"] or bucket <= 0:
        return None
    return 100.0 * ctx["counters"][RUN] / bucket
