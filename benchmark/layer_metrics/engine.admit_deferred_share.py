"""Engine: how often a turn reads an admission's first token only after
it has launched the shared step behind it. Over the window, the
admissions whose first token was read behind a step
(``engine.admit_deferred``, counted at that read) over all admissions
(``engine.stream_admissions``). 100 % is the chip going from every
admission straight to the step that follows it; an admission whose
token the host needs at once (paged, chunked, adopted, a drafter's, a
request's only token) counts against it. The two counters move a few
milliseconds apart (an admission is counted at its launch, its read
behind the step), so an edge of the window can cut between them: the
share is exact to one turn's admissions in the window's. A program
without the counter (the parent of the PR that brought it) reads
nothing."""

DEFERRED = "engine.admit_deferred"
ADMISSIONS = "engine.stream_admissions"


def read(ctx):
    admitted = ctx["counters"].get(ADMISSIONS, 0)
    if DEFERRED not in ctx["counters"] or admitted <= 0:
        return None
    return 100.0 * ctx["counters"][DEFERRED] / admitted
