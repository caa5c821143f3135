"""Model step of the Ling hybrid share: the operations the share needs
for every reply received inside the window (``benchmark/kernels/
ling_step.py``: the prompt's prefill, its KDA scan in chunks, and each
generated token at its true context, the held experts at the balanced
router's expectation) over what the chip could do in the window
(seconds x chips x peak bf16 FLOP/s). The same requests, window and host
clock as ``tokens_per_s``; nothing from the trace. A program without
the KDA step's counter (``kda.rows_stepped``: the parent of the PR that
brought the model) reads nothing."""


def read(ctx):
    if ctx["peaks"] is None or "kda.rows_stepped" not in ctx["counters"]:
        return None
    ks = ctx["load_kernel"]("ling_step")
    model = ctx["model"]
    t_open, t_close = ctx["window"]
    flops = 0.0
    for r in ctx["all_records"]:
        if "tokens" not in r or not t_open <= r["recv"] < t_close:
            continue
        flops += ks.prefill_flops(model, r["prompt_len"])
        flops += sum(ks.decode_token_flops(model, r["prompt_len"] + j)
                     for j in range(1, len(r["tokens"])))
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"] * (t_close - t_open)
    return 100.0 * flops / peak
