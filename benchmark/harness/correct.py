"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed: a sample,
drawn from the seed, of the requests the window finished (the longest
among them), each as the server answered it over the socket. The plain
reference runs ONCE over prompt + served tokens of each. Per served
token, the gap is how far that token's reference logit lies below the
reference's best at its position, in units of the position's logit
spread. Two numbers are compared, each with its limit from the
configuration file (``check.limits``): ``gap_max``, the widest gap, and
``gap_mean``, the mean over all served tokens. Greedy decoding: a sound
server serves the reference's best token except where two logits are
closer than its rounding, so both stay at the size of bfloat16's noise;
one wrong token reads far above ``gap_max``, a lower precision in every
layer (whose widest gap swings from seed to seed) reads far above
``gap_mean``. ``bad_answers`` counts sampled replies of the wrong length
or with an id outside the vocabulary (limit 0, exact).

``check(..., control=<precision>)`` is the control of that comparison:
the reference computed in the precision below the configuration's and
put in the program's place, judged by the same numbers and limits. The
benchmark's own runs never pass it.
"""

from __future__ import annotations

import importlib

import numpy as np


def sample_requests(records: list[dict], seed: int, min_tokens: int,
                    max_requests: int) -> list[dict]:
    """Finished requests, the longest first, then drawn from the seed
    until ``min_tokens`` served tokens or ``max_requests`` are reached."""
    done = [r for r in records if r.get("tokens")]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                       -r["index"]))
    rest = sorted((r for r in done if r is not longest),
                  key=lambda r: r["index"])
    order = np.random.default_rng([int(seed), 77]).permutation(len(rest))
    out, n = [longest], len(longest["tokens"])
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i]["tokens"])
    return out


def pack(samples: list[tuple[list[int], list[int]]], seq: int, gen: int):
    """Right-padded ids (R, seq), read positions (R, gen), served tokens
    (R, gen) and their mask. Position ``L - 1 + j`` of prompt + served
    predicts served token ``j``."""
    r = len(samples)
    ids = np.zeros((r, seq), np.int32)
    pos = np.zeros((r, gen), np.int32)
    tok = np.zeros((r, gen), np.int32)
    mask = np.zeros((r, gen), bool)
    for i, (prompt, served) in enumerate(samples):
        full = list(prompt) + list(served[:-1])
        assert len(full) <= seq and len(served) <= gen, \
            (len(full), seq, len(served), gen)
        ids[i, :len(full)] = full
        pos[i, :len(served)] = len(prompt) - 1 + np.arange(len(served))
        tok[i, :len(served)] = served
        mask[i, :len(served)] = True
    return ids, pos, tok, mask


def block_rows(heads: int, seq: int, budget: float = 1.5e9) -> int:
    """Rows of one reference call: its float32 attention scores
    (heads x seq x seq per row) stay under ``budget`` bytes."""
    return max(min(int(budget // (4.0 * heads * seq * seq)), 8), 1)


def gaps(reference, model: dict, seed: int, ids, pos, tok, mask,
         precision: str = "f32", control: str | None = None) -> dict:
    """Per served token: the reference's best logit minus its logit of
    the served token, over the position's logit spread. With ``control``
    the token judged is the one the reference puts first when computed
    in that precision (the control of the comparison)."""
    import jax.numpy as jnp
    out_gap, out_match = [], []
    # Whole blocks of one fixed shape (the last one padded with empty rows):
    # one compiled reference program per cell, whatever the sample's size.
    step = block_rows(model["num_attention_heads"], ids.shape[1])
    pad = -len(ids) % step
    if pad:
        ids, pos, tok = (np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                                     x.dtype)])
                         for x in (ids, pos, tok))
        mask = np.concatenate([mask, np.zeros((pad,) + mask.shape[1:], bool)])
    for a in range(0, len(ids), step):
        b = a + step
        logits = reference.read_logits(model, seed, ids[a:b], pos[a:b],
                                       precision)
        judged = jnp.asarray(tok[a:b])
        if control is not None:
            low = reference.read_logits(model, seed, ids[a:b], pos[a:b],
                                        control)
            judged = jnp.argmax(low, axis=-1)
            del low
        best = jnp.max(logits, axis=-1)
        at = jnp.take_along_axis(logits, judged[..., None], axis=-1)[..., 0]
        std = jnp.std(logits, axis=-1)
        out_gap.append(np.asarray((best - at) / std))
        out_match.append(np.asarray(jnp.argmax(logits, axis=-1) == judged))
        del logits
    gap = np.concatenate(out_gap)[mask]
    match = np.concatenate(out_match)[mask]
    return {"gap_max": float(gap.max()), "gap_mean": float(gap.mean()),
            "match_share": float(match.mean()), "tokens": int(mask.sum())}


def shapes(traffic: dict, bounds: tuple[int, int]) -> tuple[int, int]:
    """One padded shape per cell: the longest prompt the traffic can send
    plus its longest answer, to a multiple of 128."""
    gen = int(traffic["output_len"]["max"])
    seq = -(-(bounds[1] + gen) // 128) * 128
    return seq, gen


def _reference(cfg: dict):
    return importlib.import_module(
        f"benchmark.reference.{cfg.get('reference', 'dense_decoder')}")


def check(cfg: dict, model: dict, traffic: dict, bounds, seed: int,
          sampled: list[tuple[dict, list[int]]],
          control: str | None = None) -> dict:
    """``sampled``: (record, prompt tokens) pairs. Returns the numbers
    compared, each with its limit from ``cfg["check"]["limits"]``, and
    ``ok``. With ``control`` the tokens judged are the ones the reference
    puts first in that precision, on the same prompts and contexts."""
    reference = _reference(cfg)
    limits = cfg["check"]["limits"]
    vocab = model["vocab_size"]
    bad = sum(1 for rec, _ in sampled
              if len(rec["tokens"]) != rec["gen_len"]
              or not all(0 <= t < vocab for t in rec["tokens"]))
    numbers = {"bad_answers": {"value": bad, "limit": 0}}
    seq, gen = shapes(traffic, bounds)
    usable = [(p, rec["tokens"]) for rec, p in sampled
              if 0 < len(rec["tokens"]) <= gen
              and len(p) + len(rec["tokens"]) <= seq
              and all(0 <= t < vocab for t in rec["tokens"])]
    info = {"requests": len(sampled)}
    if usable:
        g = gaps(reference, model, seed, *pack(usable, seq, gen),
                 control=control)
        info.update(tokens=g["tokens"], match_share=g["match_share"])
    else:
        # Nothing to compare is a failed comparison (a finite stand-in:
        # the result line is JSON).
        g = {name: 1e30 for name in limits}
    for name, limit in limits.items():
        numbers[name] = {"value": g[name], "limit": float(limit)}
    ok = all(v["value"] <= v["limit"] for v in numbers.values())
    return {"ok": ok, "numbers": numbers, "info": info}
