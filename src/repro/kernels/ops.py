"""Public jit'd wrappers for the Pallas kernels: padding to block multiples,
CPU interpret-mode fallback, and shape plumbing.

On TPU the kernels run compiled; everywhere else (this CPU container, unit
tests) they run with ``interpret=True`` which executes the kernel body in
Python/XLA-CPU with identical semantics — that is how correctness is
validated against ref.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import maxweight as _mw
from repro.kernels import slot_step as _slot
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, mult: int, axis: int, value):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _dilate_depth0(est, ids):
    """Depth-0 (K=2) fleets reach the kernels as a synthetic depth-1 table
    whose groups are the server ids themselves (share <=> local, which the
    local override supersedes) with the remote rate duplicated into the
    unused middle column; callers remap nonzero tiers back to 1."""
    anc = jnp.asarray(ids, jnp.int32)[None, :]
    est = jnp.concatenate([est[:, :1], est[:, 1:2], est[:, 1:2]], axis=1)
    return anc, est


def fleet_route(q, serving, est_rates, server_anc, task_locals, *,
                block_tasks: int = 128, block_servers: int = 512,
                interpret: bool | None = None):
    """Fused fleet slot-step private routing.  See ref.fleet_route.

    `server_anc` is the (depth, M) `Topology.ancestors` table (a legacy
    (M,) rack map is accepted).  Accepts arbitrary B, M; pads internally
    (padding servers carry q=0/serving=0/rate=1 and pad ancestor ids that
    collide only with each other, so they sit on the masked remote tier
    and never win the argmin).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, m = task_locals.shape[0], q.shape[0]
    anc = jnp.asarray(server_anc, jnp.int32)
    anc = anc[None, :] if anc.ndim == 1 else anc
    er = jnp.asarray(est_rates, jnp.float32)
    qf = jnp.asarray(q, jnp.float32)
    k2 = anc.shape[0] == 0
    if k2:
        anc, er = _dilate_depth0(er, jnp.arange(m))
        qf = jnp.concatenate([qf[:, :1], jnp.zeros_like(qf[:, :1]),
                              qf[:, 1:2]], axis=1)
    bs = min(block_servers, _round_up(m, 128))
    bt = min(block_tasks, _round_up(b, 8))
    qf = _pad_to(qf, bs, 0, 0.0)
    sv = _pad_to(jnp.asarray(serving, jnp.int32), bs, 0, 0)
    er = _pad_to(er, bs, 0, 1.0)
    sa = _pad_to(anc, bs, 1, np.int32(2**30))
    tl = _pad_to(jnp.asarray(task_locals, jnp.int32), bt, 0, 0)
    server, tier, score = _slot.fleet_route_pallas(
        qf, sv, er, sa, tl, block_tasks=bt, block_servers=bs,
        interpret=interpret)
    server, tier, score = server[:b], tier[:b], score[:b]
    if k2:
        tier = jnp.minimum(tier, 1)  # collapse the synthetic level
    return server, tier, score


def maxweight_claim(queues, queue_anc, idle_servers, idle_anc, est_rates, *,
                    block_idle: int = 128, block_queues: int = 512,
                    interpret: bool | None = None):
    """Batched JSQ-MaxWeight claims. See ref.maxweight_claim.  Ancestor
    tables are (depth, N)/(depth, B) (legacy rack maps accepted).  Padding
    queues carry Q=0 (masked out); padding idle rows sliced off."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, n = idle_servers.shape[0], queues.shape[0]
    qa = jnp.asarray(queue_anc, jnp.int32)
    qa = qa[None, :] if qa.ndim == 1 else qa
    ia = jnp.asarray(idle_anc, jnp.int32)
    ia = ia[None, :] if ia.ndim == 1 else ia
    ids = jnp.asarray(idle_servers, jnp.int32)
    er = jnp.asarray(est_rates, jnp.float32)
    if qa.shape[0] == 0:  # depth-0 (K=2) fleet
        qa = jnp.arange(n, dtype=jnp.int32)[None, :]
        ia, er = _dilate_depth0(er, ids)
    bq = min(block_queues, _round_up(n, 128))
    bi = min(block_idle, _round_up(b, 8))
    q = _pad_to(jnp.asarray(queues, jnp.float32), bq, 0, 0.0)
    qa = _pad_to(qa, bq, 1, np.int32(2**30))
    ids = _pad_to(ids, bi, 0, 0)
    ia = _pad_to(ia, bi, 1, np.int32(2**30 - 1))
    er = _pad_to(er, bi, 0, 1.0)
    queue, score = _mw.maxweight_claim_pallas(
        q, qa, ids, ia, er, block_idle=bi, block_queues=bq,
        interpret=interpret)
    return queue[:b], score[:b]


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """Block-wise online-softmax attention (GQA/SWA/softcap).

    q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D).  See ref.mha for semantics.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def ssd(x, a, b, c, init_state=None, *, block_t: int = 128,
        interpret: bool | None = None):
    """Mamba-2 SSD chunked scan.  See ref.ssd for semantics."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _ssd.ssd_chunked(x, a, b, c, init_state=init_state,
                            block_t=block_t, interpret=interpret)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
