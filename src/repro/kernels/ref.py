"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contracts: kernel tests sweep shapes/dtypes and
assert_allclose against these functions.  They are also the fallback path on
backends where the kernels are not worth launching (tiny shapes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ----------------------------------------------------------- fleet_route ---

def _as_anc(x: jnp.ndarray) -> jnp.ndarray:
    """Normalize a legacy (M,)/(B,) rack map to a (depth, ...) table."""
    a = jnp.asarray(x)
    return a[None] if a.ndim == 1 else a


def fleet_route(q: jnp.ndarray, serving: jnp.ndarray, est_rates: jnp.ndarray,
                server_anc: jnp.ndarray, task_locals: jnp.ndarray):
    """Fused fleet slot-step private routing (workload + masked argmin).

    q:           (M,K)  f32/i32 waiting tasks per (server, tier)
    serving:     (M,)   i32     class in service (0 idle, 1..K)
    est_rates:   (M,K)  f32     per-server estimated tier rates
    server_anc:  (D,M)  i32     ancestor table (legacy (M,) rack map ok)
    task_locals: (B,3)  i32     local servers per task

    Workload is computed from (q, serving) exactly as
    `core.balanced_pandas.workload` (left-associative tier sum plus the
    in-service residual), then each task argmins W_m / rate - rate * 1e-6
    over its *private* servers only — those at a tier strictly better
    than remote (tier < K-1).  Remote-tier servers are masked out; the
    fleet backend fills the remote pool by water-filling instead of
    per-task argmin.  Returns (server (B,) i32, tier (B,) i32, score
    (B,) f32 with +LARGE for tasks whose best option is remote).  Ties
    break to the lowest server index.
    """
    anc = _as_anc(server_anc)
    d, m = anc.shape
    est = jnp.asarray(est_rates, jnp.float32)
    qf = jnp.asarray(q, jnp.float32)
    k = qf.shape[1]
    w = qf[:, 0] / est[:, 0]
    for t in range(1, k):
        w = w + qf[:, t] / est[:, t]
    resid_idx = jnp.clip(serving - 1, 0, k - 1)
    resid = jnp.take_along_axis(est, resid_idx[:, None], axis=1)[:, 0]
    w = w + jnp.where(serving > 0, 1.0 / resid, 0.0)

    sid = jnp.arange(m, dtype=task_locals.dtype)
    local = jnp.any(sid[None, :, None] == task_locals[:, None, :], axis=-1)
    tier = jnp.full(local.shape, d + 1, jnp.int32)
    rate = jnp.broadcast_to(est[None, :, d + 1], local.shape)
    for lvl in range(d - 1, -1, -1):
        row = anc[lvl]
        task_groups = row[task_locals]  # (B, 3)
        share = jnp.any(row[None, :, None] == task_groups[:, None, :],
                        axis=-1)
        tier = jnp.where(share, lvl + 1, tier)
        rate = jnp.where(share, est[None, :, lvl + 1], rate)
    tier = jnp.where(local, 0, tier)
    rate = jnp.where(local, est[None, :, 0], rate)
    score = w[None, :] / rate - rate * 1e-6
    score = jnp.where(tier <= d, score, 3.0e38)
    server = jnp.argmin(score, axis=1).astype(jnp.int32)
    b = jnp.arange(task_locals.shape[0])
    return server, tier[b, server], score[b, server]


# ------------------------------------------------------------- maxweight ---

def maxweight_claim(queues: jnp.ndarray, queue_anc: jnp.ndarray,
                    idle_servers: jnp.ndarray, idle_anc: jnp.ndarray,
                    est_rates: jnp.ndarray):
    """Batched JSQ-MaxWeight claim scoring against a queue snapshot.

    queues:       (N,)   f32/i32 queue lengths
    queue_anc:    (D,N)  i32     ancestor table of each queue's owner
    idle_servers: (B,)   i32     ids of idle servers
    idle_anc:     (D,B)  i32     ancestor table of each idle server
    est_rates:    (B,K)  f32     estimated tier rates per idle server

    Legacy (N,)/(B,) rack maps are accepted (D = 1, K = 3).  Returns
    (queue (B,) i32, score (B,) f32): argmax_n w(m,n) * Q_n with empty
    queues masked to -inf.  Lowest-index tie-break.
    """
    q_anc, i_anc = _as_anc(queue_anc), _as_anc(idle_anc)
    d, n = q_anc.shape
    qid = jnp.arange(n, dtype=idle_servers.dtype)
    is_self = idle_servers[:, None] == qid[None, :]
    w = jnp.broadcast_to(est_rates[:, d + 1:d + 2], is_self.shape)
    for lvl in range(d - 1, -1, -1):
        share = i_anc[lvl][:, None] == q_anc[lvl][None, :]
        w = jnp.where(share, est_rates[:, lvl + 1:lvl + 2], w)
    w = jnp.where(is_self, est_rates[:, 0:1], w)
    score = jnp.where(queues[None, :] > 0, w * queues[None, :], -jnp.inf)
    queue = jnp.argmax(score, axis=1).astype(jnp.int32)
    b = jnp.arange(idle_servers.shape[0])
    return queue, score[b, queue]


# ------------------------------------------------------- flash attention ---

def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
        causal: bool = True, window: int = 0, softcap: float = 0.0,
        scale: float | None = None) -> jnp.ndarray:
    """Reference multi-head attention with GQA, sliding window and softcap.

    q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D) with Hq % Hkv == 0.
    window > 0 -> sliding-window causal attention of that width.
    softcap > 0 -> logits = softcap * tanh(logits / softcap) (Gemma-2).
    Decode is Tq == 1 against a Tk-long cache (pass causal=False and mask via
    kv_len semantics upstream).
    """
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qr = q.reshape(b, hkv, group, tq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qr.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    tk = k.shape[2]
    qpos = jnp.arange(tq)[:, None] + (tk - tq)  # align cache offsets
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, tq, d).astype(q.dtype)


# --------------------------------------------------------------- ssd scan ---

def ssd(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
        init_state: jnp.ndarray | None = None):
    """Reference Mamba-2 SSD (state-space dual) recurrence, sequential form.

    x: (B, T, H, P)   inputs per head (P = head dim)
    a: (B, T, H)      per-step log-decay (a_t = exp(log_a) in (0,1])
    b: (B, T, N)      input projection onto state (N = state dim)
    c: (B, T, N)      output projection
    init_state: (B, H, P, N) or None.

    h_t = a_t * h_{t-1} + x_t (outer) b_t ;  y_t = h_t @ c_t
    Returns (y (B,T,H,P), final_state (B,H,P,N)).
    """
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(state, inp):
        xt, at, bt, ct = inp
        state = state * at[:, :, None, None] + jnp.einsum(
            "bhp,bn->bhpn", xt.astype(jnp.float32), bt.astype(jnp.float32))
        yt = jnp.einsum("bhpn,bn->bhp", state, ct.astype(jnp.float32))
        return state, yt

    xs = (x.swapaxes(0, 1), jnp.exp(a).swapaxes(0, 1).astype(jnp.float32),
          b.swapaxes(0, 1), c.swapaxes(0, 1))
    final, ys = jax.lax.scan(step, init_state, xs)
    return ys.swapaxes(0, 1).astype(x.dtype), final
