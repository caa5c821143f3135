"""Model step of the K-EXAONE share: the operations the share needs for
every reply received inside the window (``benchmark/kernels/
exaone_step.py``: the prompt's prefill and each generated token at its
true context, a sliding layer's context cut at its window, the held
experts at the balanced router's expectation) over what the chip could do
in the window (seconds x chips x peak bf16 FLOP/s). The same requests,
window and host clock as ``tokens_per_s``; nothing from the trace."""


def read(ctx):
    if ctx["peaks"] is None or "expert_parallel" not in ctx["model"]:
        return None
    ks = ctx["load_kernel"]("exaone_step")
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
