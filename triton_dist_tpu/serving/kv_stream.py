"""Content-addressed KV-block streaming (ISSUE 18).

The transfer substrate of disaggregated prefill/decode
(serving/disagg.py, docs/serving.md "Disaggregated prefill/decode"):
a prefill replica ships the finished KV blocks of one admission to a
decode replica, keyed by the prefix cache's sha1 block-hash chain
(models/prefix_cache.py) so the negotiation is content-addressed —

    kv_offer(hash chain)  →  need_from = chain_prefix_match(hashes)
    kv_ship(block j, seq s, payload)   for each needed block, in order
    kv_commit(prompt, first token)     once every signal has landed

Only blocks the decode side's prefix cache does NOT already hold are
shipped (a warm replica receives a near-zero-byte handoff); every
shipped block carries a SEQUENCE NUMBER, and the receiver refuses to
admit until the sequence is contiguous and the recomputed hash chain
matches the offer — the "no signal before its block" discipline of the
one-sided protocols, carried at the wire layer.

Two transport tiers:

- **in-process / same-host** — blocks move through the one-sided
  symm-mem path: :func:`symm_ship` pushes a staged block buffer one
  hop along a mesh axis with the same remote-DMA protocol as
  ``ops.p2p.pp_shift`` (per-block completion = the DMA recv semaphore;
  world 1, the in-process case, is the identity hop and the payload is
  handed over by reference). The schedule the kernel follows is
  :func:`ship_schedule` — the SAME helper the ``kvstream-protocol``
  model checker executes symbolically (analysis/kvstream_model.py), so
  kernel and verifier cannot drift.
- **cross-process** — a length-prefixed wire verb on the existing
  JSON-lines protocol: the ``kv_ship`` line carries ``nbytes`` and the
  raw block payload follows the newline (:class:`KVStreamSender`, with
  the server side's framing in serving/server.py).

Payloads are packed per-block, all layers, as float32 bytes
(:func:`pack_block` / :func:`unpack_block`) — lossless for the fp32
and bf16 pool dtypes — so a block's bytes are a pure function of its
content and the hash chain really is an address.

Knobs (docs/observability.md "Knobs"): ``TDT_KVSTREAM_TIMEOUT_S``
bounds each wire round trip; ``TDT_KVSTREAM_STALE_S`` bounds how long
a half-received handoff may sit in the receiver's staging table before
it is purged (the severed-stream path — testing/chaos.py
``sever_stream``).
"""

from __future__ import annotations

import functools
import json
import socket
import threading
import time

from triton_dist_tpu import obs

__all__ = ["DEFAULT_STALE_S", "DEFAULT_TIMEOUT_S", "HandoffStaging",
           "KVStreamSender", "block_span", "needed_blocks",
           "pack_block", "ship_schedule", "symm_ship", "unpack_block"]

#: Wire round-trip budget per offer/ship/commit exchange, seconds.
DEFAULT_TIMEOUT_S = 30
#: A half-received handoff older than this is purged from the
#: receiver's staging table (the severed-stream cleanup), seconds.
DEFAULT_STALE_S = 30


def timeout_s() -> int:
    return obs.env_int("TDT_KVSTREAM_TIMEOUT_S", DEFAULT_TIMEOUT_S,
                       minimum=1)


def stale_s() -> int:
    return obs.env_int("TDT_KVSTREAM_STALE_S", DEFAULT_STALE_S,
                       minimum=1)


# -- schedule helpers (executed by the kvstream-protocol model) ------------
def needed_blocks(n_blocks: int, held_prefix: int) -> list:
    """Blocks the receiver still needs: the suffix past its
    locally-held hash-chain prefix. ``held_prefix`` is clamped into
    [0, n_blocks] — a receiver can never "hold" more than was offered,
    and dedup must never drop a block past the held prefix (the
    ``kvstream.coverage`` oracle)."""
    held = max(0, min(int(held_prefix), int(n_blocks)))
    return list(range(held, int(n_blocks)))


def ship_schedule(n_blocks: int, held_prefix: int) -> list:
    """``[(block_j, seq_s), ...]`` in ship order: the needed suffix,
    sequence-numbered from 0 with no gaps. THE one spelling of the
    ship order — the sender's loop, the receiver's contiguity check,
    and the model checker (analysis/kvstream_model.py) all execute
    this same function, so the protocol and its verifier cannot
    drift."""
    return [(j, s) for s, j in enumerate(needed_blocks(n_blocks,
                                                       held_prefix))]


def block_span(prompt_len: int, page_size: int) -> int:
    """Blocks covering one prompt's written positions [0, L):
    ``ceil(L / page)`` — the handoff's unit count."""
    return -(-int(prompt_len) // int(page_size))


# -- payload packing -------------------------------------------------------
def pack_block(layers) -> bytes:
    """Pack one block's per-layer (k, v) pages into wire bytes:
    float32, layer-major, k before v. float32 is lossless for the
    fp32 and bf16 pool dtypes, so the bytes are a pure function of
    the block's content (content-addressing holds end to end)."""
    import numpy as np
    parts = []
    for k, v in layers:
        parts.append(np.ascontiguousarray(
            np.asarray(k), dtype=np.float32).tobytes())
        parts.append(np.ascontiguousarray(
            np.asarray(v), dtype=np.float32).tobytes())
    return b"".join(parts)


def unpack_block(data: bytes, num_layers: int, shape) -> list:
    """Inverse of :func:`pack_block`: ``[(k, v), ...]`` float32 numpy
    arrays of ``shape`` (page, Hkv, D) per layer. Raises ``ValueError``
    on a size mismatch (a torn or mis-framed payload must fail the
    handoff, never admit garbage K/V)."""
    import numpy as np
    n = 1
    for d in shape:
        n *= int(d)
    per = n * 4
    if len(data) != num_layers * 2 * per:
        raise ValueError(
            f"kv block payload is {len(data)} bytes, expected "
            f"{num_layers * 2 * per} ({num_layers} layers x 2 x "
            f"{tuple(shape)} float32)")
    out, off = [], 0
    for _ in range(num_layers):
        k = np.frombuffer(data, np.float32, count=n,
                          offset=off).reshape(shape)
        off += per
        v = np.frombuffer(data, np.float32, count=n,
                          offset=off).reshape(shape)
        off += per
        out.append((k, v))
    return out


# -- in-process / same-host tier (one-sided symm-mem path) -----------------
def _ship_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str,
                 world: int, delta: int):
    """Push the staged block buffer one hop along ``axis`` — the PP
    shift-hop protocol (ops/p2p.py ``_shift_kernel``) applied to a KV
    staging buffer: barrier, start the outgoing DMA, wait the incoming
    DMA's recv semaphore (the per-block completion SIGNAL — a block is
    only ever consumed after this wait), drain the send semaphore."""
    from jax import lax
    import triton_dist_tpu.language as dl
    from triton_dist_tpu.ops.p2p import shift_partners
    me = lax.axis_index(axis)
    dst, _src = shift_partners(me, delta, world)
    dl.barrier_all(axis)
    dl.remote_copy(x_ref.at[:], o_ref.at[:], dst, send_sem, recv_sem,
                   axis=axis).start()
    dl.remote_copy(x_ref.at[:], o_ref.at[:], me, send_sem, recv_sem,
                   axis=axis).wait_recv()
    dl.remote_copy(x_ref.at[:], o_ref.at[:], dst, send_sem, recv_sem,
                   axis=axis).wait_send()


def symm_ship(x, mesh=None, axis: str = "tp", delta: int = 1,
              interpret=None):
    """One-sided push of a staged block buffer one hop along ``axis``.

    ``world == 1`` — the in-process same-host tier every CPU test and
    single-host deployment runs — is the identity hop: the "transfer"
    is the handover of the staging buffer itself, and the per-block
    sequence number (:func:`ship_schedule`) is the completion signal.
    With a real multi-device axis the staged buffer moves through the
    remote-DMA shift protocol above (collective_id 9 — ops/p2p.py owns
    8)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P
    from triton_dist_tpu.ops.common import (
        comm_params, nestable_shard_map, resolve_interpret,
        sync_interpret)
    if mesh is None:
        from triton_dist_tpu.runtime.dist import get_mesh
        mesh = get_mesh()
    world = mesh.shape[axis]
    if world == 1:
        return x
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(_ship_kernel, axis=axis, world=world,
                               delta=delta)

    def body(xs):
        return pl.pallas_call(
            kernel,
            name="kv_ship",
            out_shape=jax.ShapeDtypeStruct(xs.shape, xs.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            compiler_params=comm_params(collective_id=9, world=world),
            interpret=interpret,
        )(xs)

    out = nestable_shard_map(body, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis), check_vma=False)(x)
    return sync_interpret(out, interpret)


# -- wire tier (length-prefixed verbs on the JSON-lines protocol) ----------
class KVStreamSender:
    """One handoff's connection to the decode replica.

    Speaks the three stream verbs over a single persistent connection
    (a handoff is a conversation, not N independent round trips):
    :meth:`offer` → the receiver's ``need_from``; :meth:`ship` → one
    sequence-numbered block with its raw payload framed after the JSON
    line (``nbytes``); :meth:`commit` → the receiver verifies the
    chain, admits decode-only, and replies with the generated tokens.
    Any transport or protocol failure raises — the caller's fallback
    contract (serve locally) handles it."""

    def __init__(self, host: str, port: int,
                 timeout: float | None = None):
        self._timeout = timeout if timeout is not None else timeout_s()
        self._sock = socket.create_connection((host, port),
                                              timeout=self._timeout)
        self._rfile = self._sock.makefile("rb")

    def _round_trip(self, obj: dict, payload: bytes | None = None) -> dict:
        wire = (json.dumps(obj) + "\n").encode()
        if payload is not None:
            wire += payload
        self._sock.sendall(wire)
        line = self._rfile.readline()
        if not line:
            raise ConnectionError("kv stream peer closed mid-handoff")
        resp = json.loads(line)
        if isinstance(resp, dict) and resp.get("error"):
            raise RuntimeError(
                f"kv stream peer error: {resp.get('type')}: "
                f"{resp['error']}")
        return resp

    def offer(self, handoff_id: str, hashes_hex: list,
              n_blocks: int, meta: dict,
              trace_id: str | None = None) -> int:
        """``kv_offer``: the dedup-eligible hash chain + handoff
        geometry. Returns the receiver's ``need_from`` — the longest
        chain prefix its prefix cache already holds."""
        resp = self._round_trip({
            "cmd": "kv_offer", "handoff_id": handoff_id,
            "hashes": list(hashes_hex), "n_blocks": int(n_blocks),
            "meta": meta, "trace_id": trace_id})
        return int(resp["need_from"])

    def ship(self, handoff_id: str, block: int, seq: int,
             payload: bytes) -> None:
        """``kv_ship``: one block, sequence-numbered; the receiver's
        ack is the completion signal."""
        resp = self._round_trip(
            {"cmd": "kv_ship", "handoff_id": handoff_id,
             "block": int(block), "seq": int(seq),
             "nbytes": len(payload)}, payload)
        if int(resp.get("seq", -1)) != int(seq):
            raise RuntimeError(
                f"kv stream signal mismatch: shipped seq {seq}, "
                f"peer acked {resp.get('seq')}")

    def commit(self, handoff_id: str, prompt_ids: list, first: int,
               gen_len: int, stop_tokens=None,
               trace_id: str | None = None,
               timeout: float | None = None) -> dict:
        """``kv_commit``: the receiver verifies the chain against the
        prompt, admits the row decode-only, runs the generation, and
        replies ``{"tokens": [...]}``. The commit round trip waits on
        the whole decode, so it takes its own (longer) timeout."""
        self._sock.settimeout(timeout if timeout is not None
                              else max(self._timeout, 120.0))
        return self._round_trip({
            "cmd": "kv_commit", "handoff_id": handoff_id,
            "prompt_ids": [int(t) for t in prompt_ids],
            "first": int(first), "gen_len": int(gen_len),
            "stop_tokens": (None if stop_tokens is None
                            else [int(t) for t in stop_tokens]),
            "trace_id": trace_id})

    def close(self) -> None:
        try:
            self._rfile.close()
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class HandoffStaging:
    """Receiver-side staging table: handoff_id → the blocks received
    so far. Entries live here between ``kv_offer`` and ``kv_commit``;
    a sender that dies mid-stream (the ``sever_stream`` chaos
    scenario) simply stops shipping, so :meth:`purge_stale` drops
    half-received entries older than ``TDT_KVSTREAM_STALE_S`` and
    counts them into ``disagg.streams_severed`` — the decode replica's
    pool never leaks for a prefill replica's death."""

    def __init__(self, stale_after_s: float | None = None):
        self._lock = threading.Lock()
        self._entries: dict = {}
        self.stale_after_s = (stale_after_s if stale_after_s is not None
                              else stale_s())

    def open(self, handoff_id: str, hashes_hex: list, n_blocks: int,
             need_from: int, meta: dict) -> None:
        with self._lock:
            self._entries[handoff_id] = {
                "hashes": list(hashes_hex), "n_blocks": int(n_blocks),
                "need_from": int(need_from), "meta": dict(meta),
                "blocks": {}, "seqs": [], "t0": time.monotonic()}

    def put(self, handoff_id: str, block: int, seq: int,
            payload: bytes) -> None:
        with self._lock:
            e = self._entries.get(handoff_id)
            if e is None:
                raise KeyError(
                    f"unknown or expired handoff {handoff_id!r} "
                    f"(offer first, or the entry went stale)")
            e["blocks"][int(block)] = payload
            e["seqs"].append(int(seq))

    def take(self, handoff_id: str) -> dict:
        """Claim a completed entry for admission (removes it)."""
        with self._lock:
            e = self._entries.pop(handoff_id, None)
        if e is None:
            raise KeyError(
                f"unknown or expired handoff {handoff_id!r}")
        return e

    def drop(self, handoff_id: str) -> None:
        with self._lock:
            self._entries.pop(handoff_id, None)

    def purge_stale(self, now: float | None = None) -> int:
        """Drop entries older than the staleness budget; returns how
        many were severed (counted by the caller into
        ``disagg.streams_severed``)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            dead = [h for h, e in self._entries.items()
                    if now - e["t0"] > self.stale_after_s]
            for h in dead:
                del self._entries[h]
        return len(dead)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def verify(self, entry: dict, prompt_ids, page_size: int,
               hash_chain) -> None:
        """The admission gate: the decode row may be admitted
        decode-only ONLY when (1) the recomputed hash chain of the
        prompt matches the offered chain, (2) every shipped block's
        sequence is contiguous from 0 (no signal before its block, no
        double-ship), and (3) blocks ``need_from .. n_blocks-1`` are
        all present. Raises ``ValueError`` otherwise — the caller
        falls back to a local re-prefill, never a wrong decode."""
        offered = entry["hashes"]
        local = [h.hex() for h in hash_chain]
        if local[:len(offered)] != list(offered):
            raise ValueError(
                "kv handoff chain mismatch: offered hash chain does "
                "not match the committed prompt's recomputed chain")
        n_blocks = entry["n_blocks"]
        if n_blocks != block_span(len(prompt_ids), page_size):
            raise ValueError(
                f"kv handoff geometry mismatch: offered {n_blocks} "
                f"blocks, prompt spans "
                f"{block_span(len(prompt_ids), page_size)}")
        sched = ship_schedule(n_blocks, entry["need_from"])
        want_seqs = [s for _, s in sched]
        if sorted(entry["seqs"]) != want_seqs:
            raise ValueError(
                f"kv handoff signal sequence broken: got "
                f"{sorted(entry['seqs'])}, expected {want_seqs} "
                f"(severed stream, double-ship, or dropped signal)")
        missing = [j for j, _ in sched if j not in entry["blocks"]]
        if missing:
            raise ValueError(
                f"kv handoff incomplete: needed blocks {missing} "
                f"never arrived")
