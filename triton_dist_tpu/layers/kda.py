"""Kimi Delta Attention (KDA): a linear-attention layer whose memory is
one (d x d) matrix per head, rewritten by a gated delta rule.

    q, k, v = SiLU(conv([W_q x, W_k x, W_v x]))    causal depthwise conv
                                                   of width ``conv``
    q, k    L2-normalised per head; q scaled by d^-1/2
    g_t     = lower * sigmoid(exp(A_log) * (W_f x_t + b_f))   per-channel
              log-decay in (lower, 0); alpha_t = exp(g_t)
    beta_t  = sigmoid(W_beta x_t)                            per head
    S_t     = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t     = S_t^T q_t
    out     = W_o (sigmoid(W_gate x) * RMS_head(o))         one gate per head

What a row keeps between calls (``models/kv_cache.StateLanes``): ``S``
(heads, d, d) in float32 and the conv's last ``conv - 1`` inputs
(``conv - 1``, 3 x heads x d), neither growing with the context.

A position that is nobody's (a bucket's pad, a frozen row of the decode
step: ``live`` False) runs with ``beta = 0`` and ``g = 0``, which leaves
``S`` as it was, and the conv tail is taken at each row's last live
position: a right-padded admission hands back the state of its prompt's
true length, and a frozen row's state stays put.

Two ways through the recurrence, one result:

* one position (the decode step): the update above, in place;
* a prompt (an admission): the chunked WY form. Inside a chunk of
  ``C`` positions the updates are unrolled, with ``Gamma_t`` the
  product of the decays since the chunk began (``G = cumsum(g)``):
  ``delta = U - W S0`` where ``(I + A) [W | U] = beta [Gamma k | v]``,
  ``A_ts = beta_t sum_i k_ti k_si exp(G_ti - G_si)`` below the diagonal
  (one triangular solve per chunk and head), the outputs
  ``O = (Gamma q) S0 + P delta`` with ``P_ts = sum_i q_ti k_si
  exp(G_ti - G_si)`` on and below it, and the state the chunk hands on
  ``S0 <- Diag(Gamma_C) S0 + (k exp(G_C - G))^T delta``. Only that last
  line is sequential: a loop of small matmuls over the chunks that hold
  a live position (:func:`scan_chunks`), the rest runs over all chunks
  at once. The pairwise decays are split at the first position of each
  16-position sub-chunk of the row (``exp(G_t - G_r) exp(G_r - G_s)``):
  with the log-decay bounded below (``lower``), no factor passes
  ``exp(-15 lower)``, so float32 holds every one of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers.common import rms_norm, shard_param

HI = lax.Precision.HIGHEST

# Positions per chunk of the admission's scan, and per sub-chunk of the
# pairwise decays (see the module docstring). Module constants, no knob.
CHUNK = 64
SUB = 16


def chunk_len(s: int) -> int:
    """The chunk an ``s``-position call is scanned in: ``CHUNK``, or the
    call's own length rounded up to whole sub-chunks where shorter."""
    return min(CHUNK, -(-s // SUB) * SUB)


def scan_chunks(n, s: int):
    """Chunks of the sequential scan an ``s``-position call runs when
    its rows' live prefixes reach ``n`` positions (host ints or a traced
    int): those that hold a live position."""
    c = chunk_len(s)
    return (n + c - 1) // c


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_step(q, k, v, g, beta, state):
    """One position per row. q, k, v, g: (B, H, d); beta (B, H); state
    (B, H, d, d) float32. Returns (o (B, H, d), new state)."""
    decayed = jnp.exp(g)[..., None] * state
    kv = jnp.einsum("bhi,bhij->bhj", k, decayed, precision=HI)
    qv = jnp.einsum("bhi,bhij->bhj", q, decayed, precision=HI)
    delta = beta[..., None] * (v - kv)
    new = decayed + k[..., :, None] * delta[..., None, :]
    return qv + delta * jnp.sum(q * k, axis=-1, keepdims=True), new


def chunked_delta_rule(q, k, v, g, beta, state, n_live):
    """The recurrence over S positions at once (the module docstring's
    chunked form). q, k, v, g: (B, S, H, d) float32; beta (B, S, H);
    state (B, H, d, d) float32; ``n_live`` (traced int): the longest
    live prefix among the rows. Returns (o (B, S, H, d), new state)."""
    b, s, h, d = q.shape
    c = chunk_len(s)
    sp = -(-s // c) * c
    if sp != s:
        pad = [(0, 0), (0, sp - s), (0, 0), (0, 0)]
        q, k, v, g = (jnp.pad(x, pad) for x in (q, k, v, g))
        beta = jnp.pad(beta, pad[:3])
    n, nsub = sp // c, c // SUB

    def chunks(x):                       # (B, S, H, e) -> (B, H, N, C, e)
        return x.reshape(b, n, c, h, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g = (chunks(x) for x in (q, k, v, g))
    beta = chunks(beta[..., None])[..., 0]                # (B, H, N, C)
    big_g = jnp.cumsum(g, axis=3)
    ref = big_g[:, :, :, ::SUB]                           # (B,H,N,nsub,d)

    def local(x):        # x_t exp(G_t - G_r), r the row's sub-chunk start
        sub = x.reshape(b, h, n, nsub, SUB, d)
        return sub * jnp.exp(big_g.reshape(sub.shape) - ref[:, :, :, :, None])

    last = (jnp.arange(nsub) + 1) * SUB - 1
    seen = jnp.arange(c)[None, :] <= last[:, None]        # (nsub, C)
    expo = jnp.where(seen[:, :, None], ref[:, :, :, :, None]
                     - big_g[:, :, :, None], -jnp.inf)    # (B,H,N,nsub,C,d)
    cols = k[:, :, :, None] * jnp.exp(expo)
    a = jnp.einsum("bhnisd,bhnicd->bhnisc", local(k), cols,
                   precision=HI).reshape(b, h, n, c, c)
    p = jnp.einsum("bhnisd,bhnicd->bhnisc", local(q), cols,
                   precision=HI).reshape(b, h, n, c, c)
    t = jnp.arange(c)
    a = jnp.where(t[:, None] > t[None, :], a * beta[..., None], 0.0)
    p = jnp.where(t[:, None] >= t[None, :], p, 0.0)
    gamma = jnp.exp(big_g)
    rhs = beta[..., None] * jnp.concatenate([k * gamma, v], axis=-1)
    wu = lax.linalg.triangular_solve(
        jnp.eye(c, dtype=a.dtype) + a, rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u = wu[..., :d], wu[..., d:]
    tail = big_g[:, :, :, -1:]
    k_out = k * jnp.exp(tail - big_g)                     # <= 1
    decay = jnp.exp(tail[:, :, :, 0])                     # (B, H, N, d)

    def body(i, carry):
        st, starts = carry
        starts = lax.dynamic_update_index_in_dim(starts, st, i, 2)
        pick = lambda x: lax.dynamic_index_in_dim(x, i, 2, keepdims=False)
        delta = pick(u) - jnp.einsum("bhck,bhkv->bhcv", pick(w), st,
                                     precision=HI)
        st = pick(decay)[..., None] * st + jnp.einsum(
            "bhck,bhcv->bhkv", pick(k_out), delta, precision=HI)
        return st, starts

    starts = jnp.zeros((b, h, n, d, d), jnp.float32)
    state, starts = lax.fori_loop(0, scan_chunks(n_live, s), body,
                                  (state, starts))
    delta = u - jnp.einsum("bhnck,bhnkv->bhncv", w, starts, precision=HI)
    o = jnp.einsum("bhnck,bhnkv->bhncv", q * gamma, starts, precision=HI) \
        + jnp.einsum("bhnts,bhnsv->bhntv", p, delta, precision=HI)
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, sp, h, d)
    return o[:, :s], state


class KDAttn:
    """One KDA layer; weights and state replicated over ``axis`` (the
    replicated-activation modes)."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 conv_size: int, lower_bound: float,
                 mesh: Mesh | None = None, axis: str = "tp",
                 dtype=jnp.bfloat16, rms_eps: float = 1e-6):
        if mesh is None:
            from triton_dist_tpu.runtime.dist import get_mesh
            mesh = get_mesh()
        self.mesh, self.axis = mesh, axis
        self.hidden_size = hidden_size
        self.num_heads, self.head_dim = num_heads, head_dim
        self.conv_size, self.lower_bound = conv_size, lower_bound
        self.dtype, self.rms_eps = dtype, rms_eps

    def state_shapes(self) -> tuple:
        """What a row keeps, as ``models/kv_cache.StateLanes`` takes it."""
        h, d = self.num_heads, self.head_dim
        return (((h, d, d), jnp.float32),
                ((self.conv_size - 1, 3 * h * d), self.dtype))

    def init(self, key: jax.Array) -> dict:
        h, hd = self.hidden_size, self.num_heads * self.head_dim
        ks = jax.random.split(key, 8)
        normal = lambda k, shape, fan: (
            jax.random.normal(k, shape, jnp.float32) * fan ** -0.5
            ).astype(self.dtype)
        return self.shard_params({
            "w_q": normal(ks[0], (h, hd), h), "w_k": normal(ks[1], (h, hd), h),
            "w_v": normal(ks[2], (h, hd), h), "w_f": normal(ks[3], (h, hd), h),
            "w_beta": normal(ks[4], (h, self.num_heads), h),
            "w_gate": normal(ks[5], (h, self.num_heads), h),
            "w_o": normal(ks[6], (hd, h), hd),
            "w_conv": normal(ks[7], (self.conv_size, 3 * hd), self.conv_size),
            "a_log": jnp.zeros((self.num_heads,), jnp.float32),
            "b_f": jnp.zeros((hd,), jnp.float32),
            "o_norm": jnp.ones((self.head_dim,), self.dtype)})

    def shard_params(self, params: dict) -> dict:
        return {k: shard_param(a, self.mesh, P()) for k, a in params.items()}

    def __call__(self, params: dict, x: jax.Array, cache: tuple,
                 live: jax.Array):
        """x: (B, S, hidden) replicated; cache (state, conv tail);
        ``live`` (B, S) bool, each row's live positions a prefix.
        Returns (out (B*S, hidden), new cache)."""
        b, s, _ = x.shape
        h, d, w = self.num_heads, self.head_dim, self.conv_size
        state, conv_tail = cache
        mm = lambda name: jnp.dot(x, params[name],
                                  preferred_element_type=jnp.float32)
        qkv = jnp.concatenate([mm("w_q"), mm("w_k"), mm("w_v")], axis=-1)
        seq = jnp.concatenate([conv_tail.astype(jnp.float32), qkv], axis=1)
        wc = params["w_conv"].astype(jnp.float32)
        conv = sum(wc[j] * seq[:, j:j + s] for j in range(w))
        n_live = jnp.sum(live.astype(jnp.int32), axis=1)          # (B,)
        at = n_live[:, None] + jnp.arange(w - 1)[None]
        new_tail = jnp.take_along_axis(seq, at[..., None], axis=1)
        q, k, v = jnp.split(jax.nn.silu(conv).reshape(b, s, 3, h, d), 3,
                            axis=2)
        q = _l2norm(q[:, :, 0]) * d ** -0.5
        k = _l2norm(k[:, :, 0])
        v = v[:, :, 0]
        rate = jnp.exp(params["a_log"])[:, None]
        f = (mm("w_f") + params["b_f"]).reshape(b, s, h, d)
        g = self.lower_bound * jax.nn.sigmoid(rate * f)
        beta = jax.nn.sigmoid(mm("w_beta"))
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
        if s == 1:
            o, state = delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = chunked_delta_rule(q, k, v, g, beta, state,
                                          jnp.max(n_live))
        o = rms_norm(o, params["o_norm"], self.rms_eps)
        o = o * jax.nn.sigmoid(mm("w_gate"))[..., None]
        out = jnp.dot(o.reshape(b * s, h * d).astype(x.dtype), params["w_o"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
        return out, (state, new_tail.astype(conv_tail.dtype))
