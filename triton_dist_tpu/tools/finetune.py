"""Finetune CLI: HF checkpoint → sharded training loop → orbax save.

End-to-end glue for the training stack (beyond-reference — the
reference is inference-only): ``AutoLLM.from_pretrained`` loads and
TP-shards the safetensors weights, ``models.train.make_train_step``
runs the loss/grad/optax step in any differentiable mode (including
the fused ``ag_rs`` path), and ``models.checkpoint`` saves a resumable
{params, opt_state} orbax checkpoint.

    tdt-finetune --model ./Qwen3-0.6B --data corpus.txt --steps 100 \
        --mode ag_rs --out ./ckpt

Tokenization uses the checkpoint's HF tokenizer when present, else
falls back to UTF-8 bytes (mod vocab) so weight-only dirs still work.
"""

from __future__ import annotations

import argparse
import os
import time


def _tokenize(model_dir: str, text: str, vocab_size: int):
    """HF tokenizer if the dir ships one, else UTF-8 bytes mod vocab."""
    import numpy as np
    try:
        from transformers import AutoTokenizer
        tok = AutoTokenizer.from_pretrained(model_dir)
        ids = tok(text, return_tensors="np")["input_ids"][0]
        source = "hf"
    except Exception:  # noqa: BLE001 — weight-only dir / no tokenizer
        ids = np.frombuffer(text.encode("utf-8"), np.uint8)
        source = "bytes"
    return np.asarray(ids, np.int32) % vocab_size, source


def _batches(ids, batch: int, seq: int, start: int = 0):
    """Cycle (B, S) next-token batches over the token stream;
    ``start`` fast-forwards the cycle for deterministic resume."""
    import numpy as np
    n = batch * seq
    if len(ids) < n:
        reps = -(-n // max(len(ids), 1))
        ids = np.tile(ids, reps)
    usable = len(ids) - len(ids) % n
    chunks = ids[:usable].reshape(-1, batch, seq)
    i = start
    while True:
        yield chunks[i % len(chunks)]
        i += 1


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="tdt-finetune",
        description="finetune an HF checkpoint with the fused TP stack")
    ap.add_argument("--model", required=True, help="HF checkpoint dir")
    ap.add_argument("--data", required=True,
                    help="UTF-8 text file, or a pre-packed int32 token "
                         "shard (*.bin — memory-mapped, native shuffled "
                         "epochs; see tools.data.pack_tokens)")
    ap.add_argument("--out", required=True, help="orbax checkpoint dir")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=2e-5)
    ap.add_argument("--mode", default="ag_rs",
                    help="xla | xla_ar | ag_rs | gemm_ar")
    ap.add_argument("--impl", default="pallas")
    ap.add_argument("--remat", action="store_true",
                    help="per-layer activation checkpointing")
    ap.add_argument("--resume", default=None,
                    help="orbax dir to resume params+opt_state from")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import optax

    from triton_dist_tpu.models import AutoLLM, make_train_step
    from triton_dist_tpu.models.checkpoint import load_params, save_params
    from triton_dist_tpu.runtime.compile_cache import (
        configure_compile_cache)
    from triton_dist_tpu.runtime.dist import initialize_distributed

    import numpy as np

    configure_compile_cache()
    initialize_distributed({"tp": len(jax.devices())})
    model, params = AutoLLM.from_pretrained(args.model, fwd_mode=args.mode,
                                            impl=args.impl)
    vocab = model.config.vocab_size

    step, init_opt = make_train_step(
        model, optax.adamw(args.lr, mu_dtype=jax.numpy.float32),
        mode=args.mode, remat=args.remat)
    opt_state = init_opt(params)
    step0 = 0
    if args.resume:
        like = {"params": params, "opt_state": opt_state,
                "step": np.zeros((), np.int32)}  # 0-d array: orbax
        # rejects bare numpy scalars
        restored = load_params(args.resume, like=like)
        params, opt_state = restored["params"], restored["opt_state"]
        step0 = int(restored["step"])
        print(f"[finetune] resumed from {args.resume} at step {step0}")

    if args.data.endswith(".bin"):
        # Pre-packed int32 token shard: memory-mapped, batched by the
        # native loader (tools/data.py; seeded shuffled epochs). The
        # resumed step count fast-forwards the deterministic stream so
        # the run continues with batches the saved run never saw.
        from triton_dist_tpu.tools.data import TokenDataset
        ds = TokenDataset(args.data, args.batch, args.seq)
        batch_iter = ds.batches(seed=0, start_batch=step0)
        n_tokens, source = len(ds.data), "bin"
    else:
        with open(args.data, encoding="utf-8") as f:
            text = f.read()
        ids, source = _tokenize(args.model, text, vocab)
        if len(ids) == 0:
            raise SystemExit(f"--data {args.data} produced no tokens")
        batch_iter = _batches(ids, args.batch, args.seq, start=step0)
        n_tokens = len(ids)
    print(f"[finetune] {n_tokens} tokens ({source}), "
          f"{args.batch}x{args.seq} batches, mode={args.mode}")

    t0 = time.perf_counter()
    last = None
    for i, chunk in zip(range(args.steps), batch_iter):
        chunk = np.asarray(chunk)
        if chunk.min() < 0 or chunk.max() >= vocab:
            # XLA clamps out-of-range gather ids silently — training on
            # a mis-packed shard must fail loudly instead.
            raise SystemExit(
                f"--data token ids outside [0, {vocab}) at step {i} "
                f"(min {chunk.min()}, max {chunk.max()}): shard packed "
                "with an incompatible tokenizer?")
        params, opt_state, m = step(params, opt_state,
                                    {"input_ids": jax.numpy.asarray(chunk)})
        last = float(m["loss"])
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.perf_counter() - t0
            tps = (i + 1) * args.batch * args.seq / max(dt, 1e-9)
            print(f"[finetune] step {step0 + i:>5} loss {last:.4f} "
                  f"grad_norm {float(m['grad_norm']):.3f} "
                  f"({tps:,.0f} tok/s)", flush=True)

    save_params(os.path.abspath(args.out),
                {"params": params, "opt_state": opt_state,
                 "step": np.asarray(step0 + args.steps, np.int32)})
    print(f"[finetune] saved {args.out} (final loss {last:.4f})")
    return last


if __name__ == "__main__":
    main()
