"""Expert layer: the share of the rows its expert matmuls ran that held
no pair: 1 - delta of ``moe.held_pairs`` over delta of
``moe.pair_rows_computed`` (the static row count of the grouped matmul
that ran, ``ops/group_gemm.held_expert_ffn``: twice the balanced
expectation, or every pair when a call holds more). What the static
shapes cost the admissions' expert matmuls."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe.pair_rows_computed"):
        return None
    return 100.0 * (1.0 - c.get("moe.held_pairs", 0)
                    / c["moe.pair_rows_computed"])
