"""Parameter-synchronization schedules for d-Xenos (paper §5, Fig. 11).

Two explicit schedules built from ``lax.ppermute`` so the collective pattern
is ours, not XLA's:

  * ``ring_allreduce`` — the bandwidth-optimal ring [Patarasuk & Yuan]:
    (p-1) reduce-scatter steps + (p-1) all-gather steps, 2(p-1)/p · bytes
    per link;
  * ``ps_sync`` — parameter-server emulation: every worker ships its full
    tensor toward rank 0 hop-by-hop around the ring (root link serializes,
    (p-1) · bytes through the last hop), root reduces, then the result is
    broadcast back hop-by-hop.  This is the schedule Fig. 11 shows losing
    to — and sometimes losing to single-device inference.

Both are numerically equal to ``lax.psum`` (property-tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Chunked ring all-reduce along ``axis_name`` (call inside shard_map)."""
    p = lax.axis_size(axis_name)
    if p == 1:
        return x
    rank = lax.axis_index(axis_name)
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % p
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(p, -1)
    fwd = [(i, (i + 1) % p) for i in range(p)]

    # reduce-scatter: after p-1 steps, rank r owns the full sum of chunk (r+1)%p
    def rs_step(i, chunks):
        send_idx = (rank - i) % p
        piece = jnp.take(chunks, send_idx, axis=0)
        recv = lax.ppermute(piece, axis_name, fwd)
        recv_idx = (rank - i - 1) % p
        return chunks.at[recv_idx].add(recv)

    chunks = lax.fori_loop(0, p - 1, rs_step, chunks)
    # all-gather: circulate the reduced chunks
    def ag_step(i, chunks):
        send_idx = (rank + 1 - i) % p
        piece = jnp.take(chunks, send_idx, axis=0)
        recv = lax.ppermute(piece, axis_name, fwd)
        recv_idx = (rank - i) % p
        return chunks.at[recv_idx].set(recv)

    chunks = lax.fori_loop(0, p - 1, ag_step, chunks)
    out = chunks.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape)


def ps_sync(x: jax.Array, axis_name: str) -> jax.Array:
    """Parameter-server emulation: reduce-to-root + broadcast via ring hops."""
    p = lax.axis_size(axis_name)
    if p == 1:
        return x
    rank = lax.axis_index(axis_name)
    back = [(i, (i - 1) % p) for i in range(p)]
    fwd = [(i, (i + 1) % p) for i in range(p)]

    # accumulate toward rank 0: each step, every rank forwards its running
    # sum one hop down; rank 0 accumulates everything after p-1 steps.
    def acc_step(i, carry):
        acc, inflight = carry
        recv = lax.ppermute(inflight, axis_name, back)
        acc = jnp.where(rank == 0, acc + recv, acc)
        # non-root ranks keep forwarding what they received
        inflight = jnp.where(rank == 0, jnp.zeros_like(recv), recv)
        return acc, inflight

    acc, _ = lax.fori_loop(0, p - 1, acc_step, (x, x))

    # broadcast from root: p-1 hops forward
    def bc_step(i, val):
        recv = lax.ppermute(val, axis_name, fwd)
        return jnp.where(rank == i + 1, recv, val)

    return lax.fori_loop(0, p - 1, bc_step, acc)
