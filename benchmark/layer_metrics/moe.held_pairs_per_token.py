"""Expert layer: (token, expert) pairs this chip's experts took per token
and sparse layer over the window: delta of ``moe.held_pairs`` over delta
of ``moe.routed_tokens`` (both made inside the admission and step
programs, prompt and generated tokens alike, a bucket's pad and frozen
rows left out). A balanced router gives ``num_experts_per_tok x held /
routed`` (1.0 for 8 x 16 / 128): more is work the other chips' experts
would have had. A program without the counters reads nothing."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe.routed_tokens"):
        return None
    return c.get("moe.held_pairs", 0) / c["moe.routed_tokens"]
