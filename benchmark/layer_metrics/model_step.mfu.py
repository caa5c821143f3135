"""Model step: the operations the model needs for every reply received
inside the window (``benchmark/kernels/model_step.py``: the prompt's
prefill and each generated token at its true context) over what the
chips could do in the window (seconds x chips x peak bf16 FLOP/s). The
same requests, the same window and the same host clock as
``tokens_per_s``; nothing is read from the device trace."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    ms = ctx["load_kernel"]("model_step")
    model = ctx["model"]
    t_open, t_close = ctx["window"]
    flops = 0.0
    for r in ctx["all_records"]:
        if "tokens" not in r or not t_open <= r["recv"] < t_close:
            continue
        flops += ms.prefill_flops(model, r["prompt_len"])
        flops += sum(ms.decode_token_flops(model, r["prompt_len"] + j)
                     for j in range(1, len(r["tokens"])))
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"] * (t_close - t_open)
    return 100.0 * flops / peak
